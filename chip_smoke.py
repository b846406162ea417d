#!/usr/bin/env python3
"""Chip smoke test: the fabric simulator's main path on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # perm8k sharded over four chips

Everything runs in this one process, through the experiment API a user
calls (``run(scenario, RunConfig(backend="fabric"))``), time-warped with
the inline jnp stages.  The default run has two phases:

  1. anchors — the golden cases of ``tests/test_golden.py`` checked
     against ``tests/golden/*.json`` by that file's rule (exact ints, 1e-6
     relative on floats), and one 16-host permutation ticked densely and
     time-warped, which must agree bit-exactly;
  2. scale — ``perm8k`` (STrack, 8192 hosts) with the events-oracle spot
     check of its 16-host twin inside ``SPOT_BAND``, and ``incast256``
     (RoCEv2 + PFC), each drained (``unfinished == 0``), incast lossless.

``--four-chips`` runs only ``perm8k`` with ``shard=4`` against the same
scenario unsharded on one of those chips (bit-exact on the parity keys),
then traces a few warp trips of the same sharded program and reduces the
trace with the benchmark's reduction (``bench/scopes.py``): collective
and device time per trip, time per ``tick()`` stage, and idle time by
host span, averaged over the chips.

Each run prints one line: scenario, hosts, messages, flows, ticks, warp
trips, cold and warm seconds (both end in a host fetch of the results)
and its key results.  Any failed check raises, so the exit code is
non-zero; on success the last line is one JSON object naming the device.
The script refuses to run anywhere but a TPU.  JAX's persistent
compilation cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``.jax_cache/`` at the repo root, so a second run skips most compiling.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from bench import scopes, tracing  # noqa: E402
from benchmarks import perf  # noqa: E402
from benchmarks.common import use_compile_cache  # noqa: E402
from repro.obs import spans  # noqa: E402
from repro.sim import fabric  # noqa: E402
from repro.sim.workloads import (RunConfig, _scenario_ticks,  # noqa: E402
                                 permutation_scenario, run)
from tests.test_golden import CASES, _snapshot, golden_mismatches  # noqa: E402

TRACE_DIR = ROOT / "traces" / "chip_smoke_four_chips"


def check(ok: bool, what) -> None:
    """Fail the run (also under ``python -O``, which strips asserts)."""
    if not ok:
        raise RuntimeError(what)


def timed_run(sc, cfg: RunConfig) -> tuple[dict, dict]:
    """Run ``sc`` twice (cold, then warm) and check the two agree."""
    b0 = fabric.program_builds
    t0 = time.perf_counter()
    res = run(sc, cfg)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = run(sc, cfg)
    warm = time.perf_counter() - t0
    check(perf._parity(res, again), ("warm rerun diverged", res, again))
    return res, {"cold_s": cold, "warm_s": warm,
                 "builds": fabric.program_builds - b0}


def report(phase: str, name: str, sc, cfg: RunConfig, res: dict, t: dict,
           **extra) -> None:
    msgs = len(sc.messages)
    keys = {k: res[k] for k in ("max_fct", "avg_fct", "unfinished", "drops",
                                "pauses", "max_collective_time")
            if k in res}
    print(f"[{phase}] {name}: hosts={sc.topo.n_hosts} msgs={msgs} "
          f"flows={msgs * cfg.subflows} ticks={_scenario_ticks(sc, cfg)} "
          f"warp={cfg.time_warp} trips={res.get('warp_trips')} "
          f"cold_s={t['cold_s']} warm_s={t['warm_s']} "
          f"compile_s={t['cold_s'] - t['warm_s']} builds={t['builds']} "
          f"{json.dumps({**keys, **extra}, sort_keys=True)}", flush=True)


def phase_anchors() -> None:
    """Golden snapshots on the chip, then dense vs warp bit-exactness."""
    diverged = {}
    for case in sorted(CASES):
        sc, cfg = CASES[case]()
        res, t = timed_run(sc, cfg)
        want = json.loads((ROOT / "tests" / "golden" / f"{case}.json")
                          .read_text())
        snap = _snapshot(res)
        bad = (golden_mismatches(snap, want) if set(snap) == set(want)
               else [("keys", sorted(snap), sorted(want))])
        if bad:
            diverged[case] = bad
        report("anchors", case, sc, cfg, res, t, golden_ok=not bad)
    check(not diverged, ("goldens diverged on the chip", diverged))
    sc = perf.scale_scenarios()["perm8k"][2]
    dense_cfg = RunConfig(backend="fabric", time_warp=False)
    warp_cfg = RunConfig(backend="fabric")
    dense, td = timed_run(sc, dense_cfg)
    warp, tw = timed_run(sc, warp_cfg)
    exact = perf._parity(dense, warp)
    report("anchors", "perm16_dense", sc, dense_cfg, dense, td)
    report("anchors", "perm16_warp", sc, warp_cfg, warp, tw,
           dense_warp_exact=exact)
    check(exact, ("dense and warp differ", dense, warp))


def phase_scale() -> None:
    """The paper's 8192-host permutation and the RoCEv2 256-to-1 incast."""
    sc, kw, spot_sc, spot_kw = perf.scale_scenarios()["perm8k"]
    spot = perf._oracle_spotcheck(spot_sc, spot_kw)
    cfg = RunConfig(backend="fabric", **kw)
    res, t = timed_run(sc, cfg)
    report("scale", "perm8k", sc, cfg, res, t, spot_ratio=spot["ratio"],
           spot_ok=spot["ok"])
    check(res["unfinished"] == 0, res)
    check(spot["ok"], ("perm8k spot check outside SPOT_BAND", spot))
    sc, kw = perf.canonical_scenarios()["incast256"]
    cfg = RunConfig(backend="fabric", **kw)
    res, t = timed_run(sc, cfg)
    report("scale", "incast256", sc, cfg, res, t)
    check(res["unfinished"] == 0 and res["drops"] == 0, res)


def traced_run(sc, cfg: RunConfig) -> tuple[dict, dict]:
    """``(summary, trace numbers)`` of one run captured by the profiler
    inside a ``bench.slice`` span, reduced by ``bench/scopes.py`` with the
    program's own spans."""
    with spans.recording() as rec:
        jax.profiler.start_trace(str(TRACE_DIR))
        try:
            with TraceAnnotation(tracing.SLICE):
                t0 = time.perf_counter()
                res = run(sc, cfg)
                t1 = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
    path = max(TRACE_DIR.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    host = [(s.start, s.end, s.name) for s in rec.spans]
    return res, scopes.reduce(path, host + [(t0, t1, tracing.SLICE)])


def phase_four_chips() -> None:
    """perm8k sharded over four chips against the same run on one chip."""
    n_dev = len(jax.devices())
    check(n_dev >= 4, f"--four-chips needs 4 devices, found {n_dev}")
    sc, kw, _, _ = perf.scale_scenarios()["perm8k"]
    one_cfg = RunConfig(backend="fabric", **kw)
    four_cfg = RunConfig(backend="fabric", shard=4, **kw)
    one, t1 = timed_run(sc, one_cfg)
    report("four_chips", "perm8k_shard0", sc, one_cfg, one, t1)
    four, t4 = timed_run(sc, four_cfg)
    exact = perf._parity(one, four)
    report("four_chips", "perm8k_shard4", sc, four_cfg, four, t4,
           shard_exact=exact)
    check(exact, ("shard=4 differs from shard=0", one, four))
    # The traced run feeds the same compiled shard=4 program one-packet
    # messages: identical shapes, so identical exchanges per trip, in a
    # few trips.  A trace of all of perm8k's trips overflows the
    # profiler's device buffer and takes minutes to write.
    short = permutation_scenario(sc.topo, sc.net.mtu_bytes, net=sc.net,
                                 seed=0)
    short_cfg = RunConfig(backend="fabric", shard=4,
                          n_ticks=_scenario_ticks(sc, four_cfg), **kw)
    b0 = fabric.program_builds
    res, r = traced_run(short, short_cfg)
    check(fabric.program_builds == b0, "the traced run rebuilt the program")
    trips = res["warp_trips"]
    print(f"[four_chips] trace: chips={r['devices']} trips={trips} "
          f"counted_trips={r['trips']} busy_s={r['busy_s']} "
          f"collective_s={r['collective_s']} "
          f"collective_ms_per_trip={1e3 * r['collective_s'] / trips} "
          f"device_ms_per_trip={r['device_ms_per_trip']} "
          f"stages_ms_per_trip={json.dumps(r['stages'])} "
          f"idle_s_by_span={json.dumps(r['idle_by_span'])}", flush=True)
    check(r["devices"] == 4 and r["trips"] == trips,
          ("expected every trip on 4 chips", r["devices"], r["trips"],
           trips))


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only perm8k with shard=4 against shard=0")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    print(f"chip_smoke: {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {use_compile_cache()}",
          flush=True)
    phases = ([phase_four_chips] if args.four_chips
              else [phase_anchors, phase_scale])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        print(f"{phase.__name__}: ok in {time.perf_counter() - t0} s",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
