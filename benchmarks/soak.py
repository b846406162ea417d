"""`make soak`: sustained multi-tenant operation of the warp fabric.

Runs the observatory's mixed workload — ≥2 overlapping training jobs
(dependency-chained collectives on fixed placements, staggered starts)
plus an open-loop inference/incast burst tenant — on a 64-host fabric
for ≥10 warp epochs, carrying drop/pause/ECN/retransmit counters across
epochs, and writes the Prometheus text exposition (``BENCH_soak.prom``)
that ``make serve-metrics`` serves.

Gates (non-zero exit on any failure):

  * every epoch drains (``unfinished == 0``) and the whole soak reuses
    ONE compiled fabric program (epoch traces are structure-identical);
  * the written ``.prom`` file round-trips through
    ``repro.obs.metrics.parse_prometheus``;
  * per-tenant FCT percentiles (p50, p99) from the fabric's
    ``tenant_fct`` attribution sit within the fuzz parity band
    (``SPOT_BAND``) of an events-oracle run of the same small-config
    mix.

    PYTHONPATH=src python -m benchmarks.soak [--out BENCH_soak.prom]
    PYTHONPATH=src python -m benchmarks.soak --smoke   # CI: 3 epochs
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from benchmarks.common import use_compile_cache
from repro.core.params import NetworkSpec
from repro.obs.metrics import MetricsRegistry, parse_prometheus, \
    render_prometheus
from repro.sim.topology import full_bisection
from repro.sim.traffic import InferenceTenant, TrainingJob, mixed_scenario, \
    soak
from repro.sim.workloads import RunConfig, run

#: Fabric-vs-oracle band for the per-tenant FCT spot check — the
#: differential-fuzz band (benchmarks/perf.py SPOT_BAND).
SPOT_BAND = (0.7, 1.4)


def default_fleet():
    """The ≥64-host production mix: two training jobs + a burst tenant."""
    topo = full_bisection(8, 8)          # 64 hosts, 8 ToRs, 8 spines
    net = NetworkSpec(link_gbps=400.0)
    jobs = [
        TrainingJob("train_ring", algo="ring", ranks=16,
                    collective_bytes=256 * 2 ** 10, steps=2,
                    algo_kw=(("chunk", 64 * 2 ** 10),)),
        TrainingJob("train_hd", algo="hd", ranks=16,
                    collective_bytes=256 * 2 ** 10, steps=2,
                    start_tick=64),
    ]
    tenants = [
        InferenceTenant("inference", n_flows=128,
                        mean_interarrival_ticks=4.0,
                        size_bytes=16 * 2 ** 10, size_jitter=0.5,
                        n_targets=4),
    ]
    return topo, net, jobs, tenants


def spot_fleet():
    """Small config for the events-oracle spot check (oracle wall-clock
    scales with packet count, so this stays 16 hosts / tens of flows)."""
    topo = full_bisection(4, 4)
    net = NetworkSpec(link_gbps=400.0)
    jobs = [
        TrainingJob("train_ring", algo="ring", ranks=4,
                    collective_bytes=128 * 2 ** 10),
        TrainingJob("train_hd", algo="hd", ranks=4,
                    collective_bytes=128 * 2 ** 10, start_tick=32),
    ]
    tenants = [
        InferenceTenant("inference", n_flows=24,
                        mean_interarrival_ticks=6.0,
                        size_bytes=16 * 2 ** 10, n_targets=2),
    ]
    return topo, net, jobs, tenants


def _events_tenant_fct(sc) -> dict:
    """Per-group FCT percentiles from the events oracle's msg_fct map."""
    res = run(sc, RunConfig(backend="events", until=2e7))
    msg_fct = res["msg_fct"]
    by_g: dict = {}
    for m in sc.messages:
        by_g.setdefault(m.group, []).append(msg_fct.get(m.mid))
    rows = {}
    for g, fs in by_g.items():
        done = [f for f in fs if f is not None]
        rows[g] = {
            "count": len(fs), "unfinished": len(fs) - len(done),
            "p50": float(np.percentile(done, 50)) if done else float("nan"),
            "p99": float(np.percentile(done, 99)) if done else float("nan"),
        }
    return rows


def tenant_spot_check(seed: int = 0, band=SPOT_BAND) -> list:
    """Fabric-vs-oracle per-tenant FCT parity on the small mix.

    Returns a list of human-readable problems (empty = within band)."""
    topo, net, jobs, tenants = spot_fleet()
    sc, tenant_of_group = mixed_scenario(topo, jobs, tenants, net=net,
                                         seed=seed, epoch=0)
    fb = run(sc, RunConfig())
    ev = _events_tenant_fct(sc)
    problems = []
    if fb["unfinished"]:
        problems.append(f"spot: fabric left {fb['unfinished']} messages "
                        f"unfinished")
    for g, name in sorted(tenant_of_group.items()):
        frow, erow = fb["tenant_fct"][g], ev[g]
        if erow["unfinished"]:
            problems.append(f"spot[{name}]: oracle left "
                            f"{erow['unfinished']} messages unfinished")
            continue
        for q in ("p50", "p99"):
            ratio = frow[q] / erow[q]
            ok = band[0] < ratio < band[1]
            print(f"spot[{name}] {q}: fabric {frow[q]:.2f}us vs oracle "
                  f"{erow[q]:.2f}us (ratio {ratio:.3f}, "
                  f"{'ok' if ok else 'OUT OF BAND'})")
            if not ok:
                problems.append(
                    f"spot[{name}]: {q} ratio {ratio:.3f} outside "
                    f"{band} (fabric {frow[q]:.2f}us, oracle "
                    f"{erow[q]:.2f}us)")
    return problems


def run_soak(out_path: str, epochs: int, seed: int = 0,
             n_ticks=None, smoke: bool = False) -> int:
    """Drive the soak + gates; returns a process exit code."""
    if smoke:
        topo, net, jobs, tenants = spot_fleet()
    else:
        topo, net, jobs, tenants = default_fleet()
    reg = MetricsRegistry()
    t0 = time.perf_counter()
    res = soak(topo, jobs, tenants, epochs=epochs, net=net, seed=seed,
               n_ticks=n_ticks, registry=reg, out_path=out_path,
               verbose=True)
    wall = time.perf_counter() - t0
    with open(out_path, "w") as f:
        f.write(render_prometheus(reg))
    print(f"soak: {epochs} epochs x {res['n_ticks']} ticks on "
          f"{topo.n_hosts} hosts in {wall:.1f}s "
          f"({res['totals']['messages']} messages, "
          f"{res['program_builds']} program build(s)) -> {out_path}")
    problems = []
    if res["totals"]["unfinished"]:
        problems.append(f"soak: {res['totals']['unfinished']} messages "
                        f"never finished")
    if res["program_builds"] > 1:
        problems.append(
            f"soak: {res['program_builds']} program builds across "
            f"{epochs} structure-identical epochs — the epoch traces "
            f"stopped hitting the program cache")
    # the .prom file must be real Prometheus text format
    try:
        parsed = parse_prometheus(open(out_path).read())
        assert parsed[("strack_epochs_total", ())] == float(epochs)
        print(f"soak: {out_path} round-trips the exposition parser "
              f"({len(parsed)} samples)")
    except (OSError, ValueError, KeyError, AssertionError) as e:
        problems.append(f"soak: {out_path} failed the exposition "
                        f"round-trip: {e!r}")
    problems += tenant_spot_check(seed=seed)
    for p in problems:
        print(f"soak gate: {p}", file=sys.stderr)
    return 1 if problems else 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_soak.prom")
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: small fleet, 3 epochs of 2000 ticks")
    args = ap.parse_args()
    use_compile_cache()
    if args.smoke:
        epochs = args.epochs or 3
        sys.exit(run_soak(args.out, epochs, seed=args.seed,
                          n_ticks=2000, smoke=True))
    epochs = args.epochs or 10
    sys.exit(run_soak(args.out, epochs, seed=args.seed))


if __name__ == "__main__":
    main()
