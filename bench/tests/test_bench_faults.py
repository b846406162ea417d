"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole run of ``bench/run.py`` on a small twin of a
cell (``benchtwin``), with the harness's look for a chip skipped, and
plants one fault in the program: the scan returns its state unchanged,
half of the messages are left out and the summary taken over the rest,
one message's completion time or the drop count is altered where it is
produced, or (on four forced CPU devices) the exchange between chips is
left out.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src"), str(ROOT)]

import benchtwin  # noqa: E402
from bench import run as harness  # noqa: E402
from repro.sim import fabric, workloads  # noqa: E402

CELL = "strack8k.perm64k"
SEED = 2 ** 31 + 17


def _run(root, capsys) -> dict:
    fabric.clear_program_cache()
    rc = harness.main(["--workload", CELL, "--seed", str(SEED),
                       "--seconds", "0.5"], root=root, require_tpu=False)
    fabric.clear_program_cache()
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def twin(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: "")
    return benchtwin.make(tmp_path, [CELL])


def test_sound_run_is_correct(twin, capsys):
    out = _run(twin, capsys)
    assert out["correct"] and out["failed"] == 0, out
    assert list(out)[-1] == "checks"


def test_state_left_unchanged(twin, capsys, monkeypatch):
    import jax
    monkeypatch.setattr(jax.lax, "while_loop",
                        lambda cond, body, init: init)
    out = _run(twin, capsys)
    assert not out["correct"] and out["failed"] == out["attempted"]
    assert out["checks"]["unfinished"]["value"] > 0


def test_half_the_messages_left_out(twin, capsys, monkeypatch):
    real = workloads.run_fabric_trace

    def half(topo, messages, n_ticks, cfg):
        return real(topo, messages[:len(messages) // 2], n_ticks, cfg)

    monkeypatch.setattr(workloads, "run_fabric_trace", half)
    out = _run(twin, capsys)
    assert not out["correct"]
    assert out["checks"]["missing"]["value"] > 0


@pytest.mark.parametrize("key", ["fct_us", "drops"])
def test_answer_altered_where_produced(twin, capsys, monkeypatch, key):
    real = fabric._finish_metrics

    def altered(metrics, *a, **kw):
        metrics = real(metrics, *a, **kw)
        if key == "fct_us":
            fct = list(metrics["fct_us"])
            fct[-1] = 2 * fct[-1]
            metrics["fct_us"] = fct
        else:
            metrics["drops"] += 1
        return metrics

    monkeypatch.setattr(fabric, "_finish_metrics", altered)
    out = _run(twin, capsys)
    number = {"fct_us": "fct_gap", "drops": "drops"}[key]
    assert not out["correct"]
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]


SHARDED = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path[:0] = [{here!r}, {src!r}, {root!r}]
    import benchtwin
    from bench import run as harness
    harness.use_compile_cache = lambda root: ""
    if {fault!r}:
        import jax, jax.numpy as jnp
        def local_only(x, axis_name, axis=0, tiled=False):
            n = jax.lax.axis_size(axis_name)
            return (jnp.concatenate([x] * n, axis) if tiled
                    else jnp.stack([x] * n, axis))
        jax.lax.all_gather = local_only
    root = benchtwin.make(Path({tmp!r}), [{cell!r}])
    sys.exit(harness.main(["--workload", {cell!r}, "--seed", "5",
                           "--seconds", "0.5"], root=root,
                          require_tpu=False))
""")


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "no_exchange"])
def test_exchange_between_chips_left_out(tmp_path, fault):
    cell = "strack8k-x4.perm64k"
    code = SHARDED.format(here=str(HERE), src=str(ROOT / "src"),
                          root=str(ROOT), tmp=str(tmp_path), cell=cell,
                          fault=fault)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is (not fault), result
