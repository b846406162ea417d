"""Fabric perf harness — the trajectory toward the paper's 8K hosts.

Times the jitted fabric on the canonical scenarios, dense ticking vs the
event-horizon (time-warp) scan, separating compile from run wall-clock,
and writes the machine-readable ``BENCH_fabric.json``:

  * ``perm1024``    — 1024-host permutation (scale: per-tick cost at 32x32)
  * ``ring8``       — 8-rank chunked ring allreduce (dependency-chained
                      trace: SACK-pipe round trips + dep stalls dominate)
  * ``incast256``   — 256-to-1 incast (drop/RTO recovery gaps + long
                      post-completion tail)
  * ``perm8k``      — the paper's cluster scale: 8192-host permutation,
                      warp-only (dense ticking at 8K is not a useful
                      number), parity from a small-scale oracle spot-check
  * ``allreduce8k`` — 8192 ranks of halving-doubling allreduce as 64
                      concurrent 128-rank jobs on one shared 8K fabric
                      (multi-tenant contention included), run under the
                      active-set formulation

plus a **scale axis** (``n_hosts`` vs warp ticks/sec, compile seconds and
``program_builds``) over 64 / 256 / 1024 / 8192-host permutations, so the
XLA compile-time ceiling is tracked across PRs instead of rediscovered,
and a **kernel-backend axis**: every scenario's warp run is repeated per
``FabricConfig.kernel_backend`` (``jnp`` inline stages vs the Pallas
hot-path kernels; ``pallas_interpret`` on CPU hosts — compiled
``pallas`` does not lower for the TPU yet and is swept only when named)
under a bit-exact parity gate, and the scale axis carries a
``kernel_backend`` tag per point — so BENCH_fabric.json tracks the
kernel trajectory across PRs.  Select backends explicitly with
``--kernel-backends jnp,pallas_interpret``.

Dense+warp scenarios assert dense/warp parity (identical FCTs, drops,
pauses) before reporting; warp-only scenarios run the same workload
generator at small scale against the events oracle and gate on the fuzz
parity band.  Either way a speedup number can never come from a
semantics drift.

    PYTHONPATH=src python -m benchmarks.perf [--out BENCH_fabric.json]
    PYTHONPATH=src python -m benchmarks.perf --smoke   # CI floor check
    PYTHONPATH=src python -m benchmarks.perf --scale   # 512-host floor
    PYTHONPATH=src python -m benchmarks.perf --check BENCH_fabric.json
    PYTHONPATH=src python -m benchmarks.perf --profile traces/fabric

``make bench`` fails loudly (non-zero exit) when any scenario's
``parity_ok`` is false, when the written JSON does not match the schema
(``validate_report``), or when any scenario's warp ticks/sec regressed
more than ``REGRESSION_TOL`` against the previously committed
BENCH_fabric.json; ``--check`` re-validates an existing report.

``--smoke`` runs only the 2k-tick 16-host canary and fails if the warm
time-warped fabric drops below a ticks/sec floor; ``--scale`` is the
larger 512-host warp smoke point ``make bench`` chains.  Schema and
scaling notes: docs/performance.md.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time

import jax

from benchmarks.common import use_compile_cache
from repro.core.params import NetworkSpec
from repro.sim import fabric
from repro.sim.topology import full_bisection
from repro.sim.workloads import (RunConfig, Scenario, collective_scenario,
                                 incast_scenario, permutation_scenario, run)

#: Conservative CI floor for the warm time-warped 2k-tick canary.  The
#: reference container does ~50k warp ticks/s on this shape; flag only
#: order-of-magnitude regressions, not machine noise.
SMOKE_FLOOR_TICKS_PER_S = 5_000.0

#: Floor for the 512-host ``--scale`` smoke point (warm warp run).  A
#: single-core container does a few thousand ticks/s here; like the 2k
#: canary this flags order-of-magnitude breakage only.
SCALE_FLOOR_TICKS_PER_S = 500.0

#: ``make bench`` regression gate: fail when any scenario's warm warp
#: ticks/sec drops more than this fraction below the committed report.
REGRESSION_TOL = 0.20

#: Fabric-vs-oracle band for the warp-only scenarios' small-scale parity
#: spot-check — the differential-fuzz band (tests/test_fuzz_parity.py).
SPOT_BAND = (0.7, 1.4)

#: Lane cap for the 8K-rank allreduce: halving-doubling releases ~1-2
#: messages per rank at a time (8192 ranks), so 32k lanes is ~2x headroom
#: over the peak live-flow count while cutting per-tick transport work
#: ~3.5x vs the 114,688-flow dense formulation.  The program raises if
#: the cap is ever exceeded, so a too-small cap fails loudly mid-bench.
ALLREDUCE8K_ACTIVE_CAP = 32_768

#: Summary keys the kernel-backend parity gate compares BIT-exactly (the
#: Pallas kernels run the same stage cores as the jnp path, so any
#: difference at all is a bug, not noise).
_KERNEL_PARITY_KEYS = ("max_fct", "avg_fct", "drops", "pauses",
                       "unfinished", "max_collective_time",
                       "finished_groups")


def default_kernel_backends() -> list:
    """Kernel backends the bench sweeps by default: the inline jnp path,
    plus interpret-mode Pallas on CPU hosts (same XLA ops underneath, so
    it is cheap and bit-exact-checkable there).  Compiled ``"pallas"`` is
    not offered: the TPU lowering refuses its kernels (the ranker's
    dynamic slices, the fused cores' scatters — see
    kernels/fabric_kernels.py), so it runs only when asked for by name,
    and then fails loudly."""
    if jax.default_backend() == "cpu":
        return ["jnp", "pallas_interpret"]
    return ["jnp"]


def canonical_scenarios() -> dict:
    """name -> (Scenario, RunConfig overrides dict).  Kept in one place so
    docs, bench and tests agree on what the canaries are."""
    return {
        "perm1024": (
            permutation_scenario(full_bisection(32, 32), 64 * 2 ** 10,
                                 net=NetworkSpec(link_gbps=400.0), seed=0),
            {}),
        "ring8": (
            collective_scenario(full_bisection(2, 4), "ring", 1, 8,
                                512 * 2 ** 10,
                                net=NetworkSpec(link_gbps=100.0), seed=0,
                                chunk=32 * 2 ** 10),
            {}),
        # RoCEv2 (lossless, DCQCN): the motivation's incast case — rate
        # recovery backoff and pause phases leave long pacing gaps the
        # event-horizon scan collapses.  (An STrack incast is the warp
        # worst case instead: Algo 3/4 *targets* a standing queue, so the
        # fabric is busy wall-to-wall until completion.)
        "incast256": (
            incast_scenario(full_bisection(16, 17), 256, 64 * 2 ** 10,
                            net=NetworkSpec(link_gbps=100.0), seed=0),
            {"protocol": "rocev2"}),
    }


def scale_scenarios() -> dict:
    """The paper's 8K-host scenarios: warp-only (spec below) with a
    small-scale oracle spot-check standing in for the dense-parity gate.
    name -> (Scenario, cfg overrides, spot Scenario, spot cfg overrides).
    """
    net400 = NetworkSpec(link_gbps=400.0)
    net100 = NetworkSpec(link_gbps=100.0)
    return {
        "perm8k": (
            permutation_scenario(full_bisection(128, 64), 64 * 2 ** 10,
                                 net=net400, seed=0),
            {},
            permutation_scenario(full_bisection(4, 4), 64 * 2 ** 10,
                                 net=net400, seed=0),
            {}),
        "allreduce8k": (
            collective_scenario(full_bisection(128, 64), "hd", 64, 128,
                                128 * 2 ** 10, net=net100, seed=0),
            {"active_cap": ALLREDUCE8K_ACTIVE_CAP},
            collective_scenario(full_bisection(4, 4), "hd", 2, 8,
                                128 * 2 ** 10, net=net100, seed=0),
            {"active_cap": 48}),
    }


#: n_hosts -> full_bisection dims for the compile/throughput scale axis.
#: The 8192 point reuses the perm8k scenario run (same generator/params).
SCALE_AXIS_DIMS = {64: (8, 8), 256: (16, 16), 1024: (32, 32)}


def _time_mode(sc: Scenario, n_ticks: int, warp: bool, repeats: int,
               **cfg_kw) -> tuple[dict, dict]:
    cfg = RunConfig(backend="fabric", time_warp=warp, trace_every=0,
                    n_ticks=n_ticks, **cfg_kw)
    b0 = fabric.program_builds
    t0 = time.perf_counter()
    res = run(sc, cfg)
    cold_s = time.perf_counter() - t0
    run_s = cold_s
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = run(sc, cfg)
        run_s = min(run_s, time.perf_counter() - t0)
    row = {
        "cold_s": round(cold_s, 4),
        "run_s": round(run_s, 4),
        "compile_s": round(max(0.0, cold_s - run_s), 4),
        "ticks_per_s": round(n_ticks / run_s, 1),
        "program_builds": fabric.program_builds - b0,
    }
    if warp:
        row["warp_trips"] = res.get("warp_trips")
    return row, res


def _parity(dense: dict, warp: dict) -> bool:
    keys = ["max_fct", "avg_fct", "unfinished", "drops", "pauses"]
    keys += [k for k in ("max_collective_time", "finished_groups")
             if k in dense]
    return all(dense[k] == warp[k] or
               (dense[k] != dense[k] and warp[k] != warp[k])  # both NaN
               for k in keys)


def _kernel_parity_exact(a: dict, b: dict) -> bool:
    return all(a.get(k) == b.get(k) or
               (a.get(k) != a.get(k) and b.get(k) != b.get(k))  # both NaN
               for k in _KERNEL_PARITY_KEYS)


def _bench_kernel_rows(name: str, sc: Scenario, n_ticks: int,
                       repeats: int, cfg_kw: dict, base_res: dict,
                       kernel_backends: list) -> dict:
    """Warp re-runs of one scenario per non-jnp kernel backend, each
    gated BIT-exact against the jnp warp summary (same stage cores, so
    exactness — not a band — is the contract)."""
    rows = {}
    for kb in kernel_backends:
        if kb == "jnp":
            continue
        krow, kres = _time_mode(sc, n_ticks, True, repeats,
                                kernel_backend=kb, **cfg_kw)
        krow["parity_exact"] = _kernel_parity_exact(base_res, kres)
        rows[kb] = krow
        print(f"bench[{name}] kernels[{kb}]: warp {krow['run_s']:.3f}s "
              f"({krow['ticks_per_s']:,.0f} t/s), parity="
              f"{'exact' if krow['parity_exact'] else 'FAIL'}")
    return rows


def bench_scenario(name: str, sc: Scenario, cfg_kw: dict,
                   repeats: int = 2, kernel_backends: list = ()) -> dict:
    n_ticks = sc.default_ticks()
    b0 = fabric.program_builds
    dense_row, dense_res = _time_mode(sc, n_ticks, False, repeats, **cfg_kw)
    warp_row, warp_res = _time_mode(sc, n_ticks, True, repeats, **cfg_kw)
    row = {
        "n_ticks": n_ticks,
        "n_hosts": sc.topo.n_hosts,
        "n_msgs": len(sc.messages),
        "dense": dense_row,
        "warp": warp_row,
        "speedup": round(dense_row["run_s"] / warp_row["run_s"], 2),
        "parity_ok": _parity(dense_res, warp_res),
        "unfinished": dense_res["unfinished"],
        "max_fct_us": dense_res["max_fct"],
        "program_builds_total": fabric.program_builds - b0,
    }
    print(f"bench[{name}]: {n_ticks} ticks x {row['n_msgs']} msgs on "
          f"{row['n_hosts']} hosts | dense {dense_row['run_s']:.3f}s "
          f"({dense_row['ticks_per_s']:,.0f} t/s) | warp "
          f"{warp_row['run_s']:.3f}s ({warp_row['warp_trips']} trips) | "
          f"{row['speedup']}x, parity={'ok' if row['parity_ok'] else 'FAIL'}")
    kernels = _bench_kernel_rows(name, sc, n_ticks, repeats, cfg_kw,
                                 warp_res, kernel_backends)
    if kernels:
        row["kernels"] = kernels
    return row


def _oracle_spotcheck(sc: Scenario, cfg_kw: dict) -> dict:
    """Small-scale fabric-vs-events run of a warp-only scenario's
    generator; ok iff the completion-time ratio sits in the fuzz band."""
    fb = run(sc, RunConfig(backend="fabric", time_warp=True,
                           trace_every=0, **cfg_kw))
    ev_kw = {k: v for k, v in cfg_kw.items()
             if k not in ("active_cap", "shard")}
    ev = run(sc, RunConfig(backend="events", until=2e7, **ev_kw))
    if "max_collective_time" in fb:
        a, b = fb["max_collective_time"], ev["max_collective_time"]
    else:
        a, b = fb["max_fct"], ev["max_fct"]
    ratio = a / b
    ok = (SPOT_BAND[0] < ratio < SPOT_BAND[1]
          and fb["unfinished"] == 0 and ev["unfinished"] == 0)
    return {"n_hosts": sc.topo.n_hosts, "n_msgs": len(sc.messages),
            "fabric_us": round(a, 3), "events_us": round(b, 3),
            "ratio": round(ratio, 4), "ok": ok}


def bench_scenario_warp_only(name: str, sc: Scenario, cfg_kw: dict,
                             spot_sc: Scenario, spot_kw: dict,
                             repeats: int = 1,
                             kernel_backends: list = ()) -> dict:
    """8K-scale scenario: warp scan only (a dense 8K run is pure heat),
    with the oracle spot-check providing the parity gate."""
    spot = _oracle_spotcheck(spot_sc, spot_kw)
    n_ticks = sc.default_ticks()
    b0 = fabric.program_builds
    warp_row, warp_res = _time_mode(sc, n_ticks, True, repeats, **cfg_kw)
    row = {
        "n_ticks": n_ticks,
        "n_hosts": sc.topo.n_hosts,
        "n_msgs": len(sc.messages),
        "warp": warp_row,
        "warp_only": True,
        "parity_ok": bool(spot["ok"] and warp_res["unfinished"] == 0),
        "parity_spotcheck": spot,
        "unfinished": warp_res["unfinished"],
        "max_fct_us": warp_res["max_fct"],
        "program_builds_total": fabric.program_builds - b0,
    }
    if "active_cap" in cfg_kw:
        row["active_cap"] = cfg_kw["active_cap"]
    print(f"bench[{name}]: {n_ticks} ticks x {row['n_msgs']} msgs on "
          f"{row['n_hosts']} hosts | warp {warp_row['run_s']:.3f}s "
          f"({warp_row['ticks_per_s']:,.0f} t/s, {warp_row['warp_trips']} "
          f"trips, compile {warp_row['compile_s']:.1f}s) | spot-check "
          f"ratio {spot['ratio']} on {spot['n_hosts']} hosts, "
          f"parity={'ok' if row['parity_ok'] else 'FAIL'}")
    kernels = _bench_kernel_rows(name, sc, n_ticks, repeats, cfg_kw,
                                 warp_res, kernel_backends)
    if kernels:
        row["kernels"] = kernels
    return row


def bench_scale_axis(repeats: int = 1, kernel_backends: list = ()) -> list:
    """Warp permutation runs across host counts x kernel backends with a
    cleared program cache per point, so ``compile_s`` and
    ``program_builds`` measure the real per-scale build cost (the
    compile-time ceiling ROADMAP names) per execution substrate."""
    axis = []
    backends = list(kernel_backends) or ["jnp"]
    for n_hosts, (t, h) in sorted(SCALE_AXIS_DIMS.items()):
        sc = permutation_scenario(full_bisection(t, h), 64 * 2 ** 10,
                                  net=NetworkSpec(link_gbps=400.0), seed=0)
        n_ticks = sc.default_ticks()
        for kb in backends:
            fabric.clear_program_cache()
            row, _ = _time_mode(sc, n_ticks, True, repeats,
                                kernel_backend=kb)
            axis.append({"n_hosts": n_hosts, "n_ticks": n_ticks,
                         "kernel_backend": kb,
                         "ticks_per_s": row["ticks_per_s"],
                         "compile_s": row["compile_s"],
                         "program_builds": row["program_builds"],
                         "warp_trips": row["warp_trips"]})
            print(f"scale[{n_hosts:>5} hosts, {kb}]: "
                  f"{row['ticks_per_s']:>9,.1f} t/s warm, compile "
                  f"{row['compile_s']:.2f}s, {row['program_builds']} builds")
    return axis


#: BENCH_fabric.json schema: required keys and their types, per level.
#: ``validate_report`` walks this so a malformed report (hand-edited,
#: truncated write, schema drift) fails the gate as loudly as a parity
#: failure does.
_SCHEMA_META = {"utc": str, "jax": str, "backend": str, "platform": str,
                "device_platform": str, "device_kind": str,
                "device_count": int}
#: ``program_builds_total`` (scenario level) is the whole-scenario build
#: count across all modes — a diagnostic.  The retrace-regression hook
#: reads the per-mode ``program_builds`` inside ``warp``/``dense``
#: (``_SCHEMA_MODE``); the throughput regression gate reads
#: ``warp.ticks_per_s``.  Earlier reports spelled the scenario-level
#: field ``program_builds`` too, shadowing the per-mode one — the rename
#: keeps the two hooks unambiguous.
_SCHEMA_SCENARIO = {"n_ticks": int, "n_hosts": int, "n_msgs": int,
                    "warp": dict, "parity_ok": bool, "unfinished": int,
                    "max_fct_us": (int, float), "program_builds_total": int}
#: dense+speedup are required unless the row is flagged ``warp_only``.
_SCHEMA_SCENARIO_DENSE = {"dense": dict, "speedup": (int, float)}
_SCHEMA_MODE = {"cold_s": (int, float), "run_s": (int, float),
                "compile_s": (int, float), "ticks_per_s": (int, float),
                "program_builds": int}
#: per-backend warp re-run under ``scenarios.<name>.kernels.<backend>``;
#: ``parity_exact`` is the bit-exactness gate vs the jnp warp summary.
_SCHEMA_KERNEL_ROW = dict(_SCHEMA_MODE, parity_exact=bool)
_SCHEMA_SCALE_POINT = {"n_hosts": int, "n_ticks": int,
                       "kernel_backend": str,
                       "ticks_per_s": (int, float),
                       "compile_s": (int, float), "program_builds": int}


def validate_report(report: dict) -> list:
    """Schema-check one BENCH_fabric.json report dict.

    Returns a list of human-readable problems (empty = valid): missing or
    mis-typed keys at the meta / scenario / mode / kernels / scale-axis
    levels, any scenario whose ``parity_ok`` gate is false, and any
    kernel-backend row whose ``parity_exact`` gate is false — the caller
    turns a non-empty list into a non-zero exit.

    Which field feeds which gate (the point of the
    ``program_builds_total`` rename):

      * the **throughput regression gate** (``regression_problems``)
        reads ``scenarios.<name>.warp.ticks_per_s`` — nothing else;
      * the **retrace-regression hook** reads the per-mode
        ``program_builds`` inside ``warp`` / ``dense`` /
        ``kernels.<backend>`` rows (a warm re-run that rebuilds its
        program is a cache bug);
      * scenario-level ``program_builds_total`` is the whole-scenario
        build count across every mode — a diagnostic, read by no gate.
    """
    problems = []

    def chk(d, schema, where):
        if not isinstance(d, dict):
            problems.append(f"{where}: expected an object, got "
                            f"{type(d).__name__}")
            return False
        for k, t in schema.items():
            if k not in d:
                problems.append(f"{where}: missing key {k!r}")
            elif not isinstance(d[k], t):
                problems.append(f"{where}.{k}: expected "
                                f"{getattr(t, '__name__', t)}, got "
                                f"{type(d[k]).__name__}")
        return True

    if not isinstance(report, dict):
        return [f"report: expected an object, got {type(report).__name__}"]
    chk(report.get("meta"), _SCHEMA_META, "meta")
    scenarios = report.get("scenarios")
    if not isinstance(scenarios, dict) or not scenarios:
        problems.append("scenarios: missing or empty")
        return problems
    for name, row in scenarios.items():
        if not chk(row, _SCHEMA_SCENARIO, f"scenarios.{name}"):
            continue
        modes = ["warp"]
        if not row.get("warp_only"):
            chk(row, _SCHEMA_SCENARIO_DENSE, f"scenarios.{name}")
            modes.append("dense")
        for mode in modes:
            if isinstance(row.get(mode), dict):
                chk(row[mode], _SCHEMA_MODE, f"scenarios.{name}.{mode}")
        # kernels axis is optional (jnp-only sweeps), but when present
        # every backend row must be well-formed and bit-exact
        if "kernels" in row:
            if not isinstance(row["kernels"], dict) or not row["kernels"]:
                problems.append(f"scenarios.{name}.kernels: expected a "
                                f"non-empty object")
            else:
                for kb, krow in row["kernels"].items():
                    where = f"scenarios.{name}.kernels.{kb}"
                    if not chk(krow, _SCHEMA_KERNEL_ROW, where):
                        continue
                    if krow.get("parity_exact") is False:
                        problems.append(
                            f"{where}: parity_exact is FALSE — the "
                            f"{kb} kernel backend diverged from the "
                            f"inline jnp stages; the kernels must be "
                            f"bit-exact, so this is a kernel bug, not "
                            f"noise")
        if row.get("parity_ok") is False:
            problems.append(
                f"scenarios.{name}: parity_ok is FALSE — the fabric "
                f"diverged from its reference (dense ticking or the "
                f"events-oracle spot-check); a speedup number from this "
                f"report cannot be trusted")
    # scale axis is optional for backward compatibility with pre-scale
    # reports, but when present every point must be well-formed
    if "scale_axis" in report:
        axis = report["scale_axis"]
        if not isinstance(axis, list) or not axis:
            problems.append("scale_axis: expected a non-empty list")
        else:
            for i, pt in enumerate(axis):
                chk(pt, _SCHEMA_SCALE_POINT, f"scale_axis[{i}]")
    return problems


def regression_problems(new: dict, baseline: dict,
                        tol: float = REGRESSION_TOL) -> list:
    """Compare warm warp ticks/sec per scenario against the committed
    report; >tol fractional drops are gate failures.  The gate reads
    exactly ``scenarios.<name>.warp.ticks_per_s`` on both sides — never
    the kernels sub-rows, the dense row, or any ``program_builds*``
    field.  Scenarios missing on either side are skipped (new scenarios
    land without a baseline)."""
    problems = []
    old_sc = (baseline or {}).get("scenarios") or {}
    new_sc = (new or {}).get("scenarios") or {}
    for name in sorted(set(old_sc) & set(new_sc)):
        try:
            old_tps = float(old_sc[name]["warp"]["ticks_per_s"])
            new_tps = float(new_sc[name]["warp"]["ticks_per_s"])
        except (KeyError, TypeError, ValueError):
            continue
        if old_tps > 0 and new_tps < (1.0 - tol) * old_tps:
            problems.append(
                f"scenarios.{name}: warp ticks/sec regressed "
                f"{(1 - new_tps / old_tps) * 100:.1f}% "
                f"({old_tps:,.1f} -> {new_tps:,.1f}; gate is {tol:.0%})")
    return problems


def check_report_file(path: str) -> int:
    """Validate an existing BENCH_fabric.json; returns a process exit
    code (0 ok, 1 schema/parity problems, 2 unreadable)."""
    try:
        with open(path) as f:
            report = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench gate: cannot read {path}: {e}", file=sys.stderr)
        return 2
    problems = validate_report(report)
    for p in problems:
        print(f"bench gate: {path}: {p}", file=sys.stderr)
    if problems:
        return 1
    print(f"bench gate ok: {path} "
          f"({len(report['scenarios'])} scenarios, parity ok)")
    return 0


def _load_baseline(path: str):
    """Read the previously committed report for the regression gate.

    A missing, unreadable, corrupt, or non-object baseline means "no
    baseline" — logged loudly, never a traceback: a fresh clone or a
    mangled committed report must not block regenerating the report."""
    try:
        with open(path) as f:
            baseline = json.load(f)
    except FileNotFoundError:
        print(f"bench gate: no baseline at {path} — regression gate "
              f"skipped for this run", file=sys.stderr)
        return None
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench gate: baseline {path} unreadable ({e}) — treating "
              f"as no baseline; regression gate skipped", file=sys.stderr)
        return None
    if not isinstance(baseline, dict):
        print(f"bench gate: baseline {path} is not a JSON object "
              f"({type(baseline).__name__}) — treating as no baseline",
              file=sys.stderr)
        return None
    return baseline


def bench_all(out_path: str = "BENCH_fabric.json",
              repeats: int = 2, kernel_backends: list = None,
              history_path: str = "BENCH_history.jsonl") -> dict:
    if kernel_backends is None:
        kernel_backends = default_kernel_backends()
    # the committed report (if any) is the regression baseline — read it
    # BEFORE overwriting
    baseline = _load_baseline(out_path)
    report = {
        "meta": {
            "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "device_platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
        },
        "scenarios": {},
    }
    # scale axis first: each point measures a cold build (cache cleared
    # per host-count x backend point)
    report["scale_axis"] = bench_scale_axis(repeats=max(1, repeats - 1),
                                            kernel_backends=kernel_backends)
    for name, (sc, cfg_kw) in canonical_scenarios().items():
        report["scenarios"][name] = bench_scenario(
            name, sc, cfg_kw, repeats=repeats,
            kernel_backends=kernel_backends)
    for name, (sc, cfg_kw, spot_sc, spot_kw) in scale_scenarios().items():
        row = bench_scenario_warp_only(name, sc, cfg_kw, spot_sc, spot_kw,
                                       repeats=1,
                                       kernel_backends=kernel_backends)
        report["scenarios"][name] = row
        if name == "perm8k":
            # the 8192-host scale points reuse the perm8k runs (jnp warp
            # row + the per-backend kernels rows) instead of re-timing
            kern = row.get("kernels", {})
            for kb, w in [("jnp", row["warp"])] + sorted(kern.items()):
                if kb != "jnp" and kb not in kernel_backends:
                    continue
                report["scale_axis"].append({
                    "n_hosts": row["n_hosts"], "n_ticks": row["n_ticks"],
                    "kernel_backend": kb,
                    "ticks_per_s": w["ticks_per_s"],
                    "compile_s": w["compile_s"],
                    "program_builds": w["program_builds"],
                    "warp_trips": w["warp_trips"]})
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out_path}")
    # Loud gates: (1) schema + parity on the report we just wrote,
    # (2) warp throughput vs the previously committed report, and (3) the
    # cross-PR trend gate over BENCH_history.jsonl — fail the process on
    # any of them, never bury a regression in a report nobody reads.
    problems = validate_report(report)
    problems += regression_problems(report, baseline)
    from repro.obs import trend
    problems += trend.gate_and_append(history_path, report,
                                      tol=REGRESSION_TOL)
    if problems:
        for p in problems:
            print(f"bench gate: {p}", file=sys.stderr)
        sys.exit(1)
    return report


def smoke(n_ticks: int = 2000,
          floor: float = SMOKE_FLOOR_TICKS_PER_S) -> None:
    """2k-tick perf canary: the warm time-warped fabric must beat
    ``floor`` ticks/sec and agree exactly with dense ticking."""
    sc = permutation_scenario(full_bisection(4, 4), 64 * 2 ** 10,
                              net=NetworkSpec(), seed=0)
    dense_row, dense_res = _time_mode(sc, n_ticks, False, repeats=1)
    warp_row, warp_res = _time_mode(sc, n_ticks, True, repeats=1)
    tps = warp_row["ticks_per_s"]
    assert _parity(dense_res, warp_res), (dense_res, warp_res)
    assert tps >= floor, (
        f"perf-smoke FAILED: warm time-warp fabric ran {tps:,.0f} ticks/s "
        f"< floor {floor:,.0f} on the {n_ticks}-tick canary")
    print(f"perf-smoke ok: warp {tps:,.0f} ticks/s (floor {floor:,.0f}), "
          f"dense {dense_row['ticks_per_s']:,.0f} t/s, "
          f"{warp_row['warp_trips']} trips, parity exact")


def scale_smoke(floor: float = SCALE_FLOOR_TICKS_PER_S) -> None:
    """512-host warp smoke point (``make bench`` chains this): a midsize
    permutation must beat a conservative warm ticks/sec floor, catching
    at-scale scan regressions the 16-host canary can't see."""
    sc = permutation_scenario(full_bisection(16, 32), 64 * 2 ** 10,
                              net=NetworkSpec(link_gbps=400.0), seed=0)
    n_ticks = sc.default_ticks()
    warp_row, warp_res = _time_mode(sc, n_ticks, True, repeats=1)
    tps = warp_row["ticks_per_s"]
    assert warp_res["unfinished"] == 0, warp_res
    assert tps >= floor, (
        f"scale-smoke FAILED: warm time-warp fabric ran {tps:,.0f} ticks/s "
        f"< floor {floor:,.0f} on the 512-host permutation")
    print(f"scale-smoke ok: 512 hosts, warp {tps:,.0f} ticks/s "
          f"(floor {floor:,.0f}), compile {warp_row['compile_s']:.2f}s, "
          f"{warp_row['warp_trips']} trips")


def profile_scenario(trace_dir: str, name: str = "perm1024",
                     kernel_backend: str = "jnp") -> None:
    """One warp scenario under ``jax.profiler.trace`` (``make profile``).

    Compiles OUTSIDE the trace (a cold run first), then traces warm
    warp run(s), so the trace shows the scan body — the thing the Pallas
    kernels target — not XLA compilation.  View with
    ``tensorboard --logdir <trace_dir>`` (or ``xprof``)."""
    sc, cfg_kw = canonical_scenarios()[name]
    n_ticks = sc.default_ticks()
    cfg = RunConfig(backend="fabric", time_warp=True, trace_every=0,
                    n_ticks=n_ticks, kernel_backend=kernel_backend,
                    **cfg_kw)
    run(sc, cfg)                           # compile outside the trace
    with jax.profiler.trace(trace_dir):
        t0 = time.perf_counter()
        res = run(sc, cfg)
        run_s = time.perf_counter() - t0
    print(f"profile[{name}, {kernel_backend}]: {n_ticks} ticks in "
          f"{run_s:.3f}s warm ({n_ticks / run_s:,.0f} t/s, "
          f"{res.get('warp_trips')} trips) -> {trace_dir}")
    print(f"view with: tensorboard --logdir {trace_dir}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_fabric.json")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="2k-tick ticks/sec floor canary (CI)")
    ap.add_argument("--scale", action="store_true",
                    help="512-host warp ticks/sec floor point (CI)")
    ap.add_argument("--floor", type=float, default=None)
    ap.add_argument("--check", metavar="PATH",
                    help="validate an existing BENCH_fabric.json (schema "
                         "+ parity gate) without running anything")
    ap.add_argument("--kernel-backends", metavar="LIST", default=None,
                    help="comma list of kernel backends to sweep "
                         "(default: jnp + pallas_interpret on CPU, "
                         "jnp alone elsewhere); 'jnp' alone skips "
                         "the kernels axis")
    ap.add_argument("--profile", metavar="DIR",
                    help="trace one warm warp scenario under "
                         "jax.profiler.trace into DIR and exit")
    ap.add_argument("--profile-scenario", default="perm1024",
                    choices=sorted(canonical_scenarios()),
                    help="which canonical scenario --profile runs")
    args = ap.parse_args()
    use_compile_cache()
    backends = (None if args.kernel_backends is None
                else [b for b in args.kernel_backends.split(",") if b])
    if args.check:
        sys.exit(check_report_file(args.check))
    if args.profile:
        kb = next((b for b in (backends or []) if b != "jnp"), None)
        profile_scenario(args.profile, name=args.profile_scenario,
                         kernel_backend=kb or "jnp")
        return
    if args.smoke:
        smoke(floor=args.floor if args.floor is not None
              else SMOKE_FLOOR_TICKS_PER_S)
        return
    if args.scale:
        scale_smoke(floor=args.floor if args.floor is not None
                    else SCALE_FLOOR_TICKS_PER_S)
        return
    bench_all(args.out, repeats=args.repeats, kernel_backends=backends)


if __name__ == "__main__":
    main()
