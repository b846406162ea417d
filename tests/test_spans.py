"""The fabric program's own spans and compile counters
(``repro.obs.spans``), and the stage scopes of its warp scan."""
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.obs import spans
from repro.sim import fabric
from repro.sim.faults import FaultSpec
from repro.sim.topology import full_bisection
from repro.sim.workloads import Message, RunConfig, permutation_scenario, run

pytestmark = pytest.mark.tier1

ROOT = Path(__file__).resolve().parents[1]
CHILDREN = ("fabric.inputs", "fabric.program", "fabric.dispatch",
            "fabric.device", "fabric.fetch", "fabric.summary")
STAGES = {"fabric.gate", "fabric.pfc", "fabric.faults", "fabric.transport",
          "fabric.route", "fabric.queues", "fabric.queues.rank",
          "fabric.receive", "fabric.complete", "fabric.warp", "fabric.cond"}


def _scenario(msg_bytes=65536):
    return permutation_scenario(full_bisection(2, 8), msg_bytes, seed=3)


def test_nothing_is_recorded_with_recording_off():
    with spans.recording() as rec:
        pass
    run(_scenario(), RunConfig(backend="fabric"))
    assert rec.spans == []
    assert set(rec.compile_s.values()) == {0.0}


def test_run_records_its_steps_in_order():
    with spans.recording() as rec:
        out = run(_scenario(), RunConfig(backend="fabric"))
    assert len(rec.spans) == 1 + len(CHILDREN)
    *kids, parent = rec.spans            # a span is kept when it ends
    assert parent.name == "fabric.run" and parent.parent is None
    assert tuple(s.name for s in kids) == CHILDREN
    assert all(s.parent == "fabric.run" for s in kids)
    assert all(s.ids == {"answer": out["answer"]} for s in rec.spans)
    assert parent.start <= kids[0].start
    assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
    assert kids[-1].end <= parent.end
    assert sum(s.end - s.start for s in kids) <= parent.end - parent.start
    # the process keeps them too, for readers that come after the run
    assert rec.spans == spans.recent()[-len(rec.spans):]


def test_each_run_gets_its_own_answer_id():
    a = run(_scenario(), RunConfig(backend="fabric"))["answer"]
    b = run(_scenario(), RunConfig(backend="fabric"))["answer"]
    assert a != b


def test_compile_counters_count_a_fresh_program_once():
    fabric.clear_program_cache()
    sc, cfg = _scenario(msg_bytes=12288), RunConfig(backend="fabric")
    before = spans.compiled()
    builds = fabric.program_builds
    with spans.recording() as first:
        run(sc, cfg)
    assert fabric.program_builds == builds + 1
    assert all(v > 0 for v in first.compile_s.values()), first.compile_s
    after = spans.compiled()
    assert after == pytest.approx({k: before[k] + first.compile_s[k]
                                   for k in before})
    with spans.recording() as second:
        run(sc, cfg)
    assert set(second.compile_s.values()) == {0.0}
    assert fabric.program_builds == builds + 1
    assert spans.compiled() == after


def lowered_scopes(shard: int = 0) -> set:
    """The ``fabric.*`` scopes in the lowered text of a RoCEv2 + PFC
    program with link and host flaps (every stage has ops there)."""
    topo = full_bisection(2, 4)
    msgs = [Message(mid=i, src=i, dst=(i + 3) % 8, size=65536.0, deps=(),
                    group=0) for i in range(8)]
    faults = FaultSpec(link_flaps=((0, 1, 10, 50),),
                       host_flaps=((1, 5, 40),))
    cfg = fabric.FabricConfig(protocol="rocev2", pfc=True, faults=faults,
                              time_warp=True, trace_every=0, shard=shard)
    flows, dep = fabric.expand_messages(msgs, 1)
    fd = fabric.build_fault_data(faults, topo.n_tor, topo.n_spine,
                                 topo.hosts_per_tor)
    prog = fabric._get_program(topo, len(flows), 2000, cfg, dep)
    text = prog.jit_single.lower(
        *fabric._flow_arrays(flows, cfg), jnp.int32(0),
        fabric._arrival_array(msgs), fd).as_text(debug_info=True)
    assert "jit_fabric_program" in text
    return set(re.findall(r"\bfabric\.[a-z][a-z.]*[a-z]\b", text)) - {
        "fabric.py"}


def test_every_stage_is_scoped_in_the_lowered_program():
    assert lowered_scopes() == STAGES


def test_every_stage_is_scoped_in_the_sharded_program():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(ROOT / "src"),
                                          str(ROOT / "tests")]),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = ("import test_spans; "
            "print(sorted(test_spans.lowered_scopes(shard=4)))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(sorted(STAGES))


def test_the_jitted_entry_is_named():
    prog = fabric._get_program(full_bisection(2, 4), 8, 100,
                               fabric.FabricConfig(time_warp=True,
                                                   trace_every=0))
    assert prog.program.__name__ == spans.PROGRAM == "fabric_program"
    assert jax.jit(prog.program).__name__ == spans.PROGRAM
