"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. ``us_per_call`` is the
simulated metric (max FCT / collective time in us); ``derived`` carries the
paper-claim validation (speedups, parity ratios, queue stability).

Every figure is driven through the ONE experiment API
(``repro.sim.workloads.run(scenario, RunConfig(...))``) — one command
reproduces the whole evaluation matrix on the jitted fabric, collectives
and 4-QP striped RoCEv2 included.

Full-scale variants of each figure are available via the per-module mains
(e.g. ``python -m benchmarks.permutation --full``).
"""
from __future__ import annotations

import sys

MIGRATION_TABLE = """\
old entry point (REMOVED in PR 8)              -> unified API call
----------------------------------------------------------------------------
run_on_fabric(sc, protocol=, lb_mode=, ...)    -> run(sc, RunConfig(backend="fabric", protocol=, lb_mode=, ...))
run_seed_sweep_on_fabric(scs, ...)             -> sweep(scs, RunConfig(...))
run_on_events(sc, transport="roce", ...)       -> run(sc, RunConfig(backend="events", protocol="rocev2", ...))
TraceRunner(sim, msgs, placement).run()        -> run(collective_scenario(...), RunConfig(...))
run_permutation(sim, msg)                      -> run(permutation_scenario(topo, msg), RunConfig(backend="events"))
run_incast(sim, fan_in, msg)                   -> run(incast_scenario(topo, fan_in, msg), RunConfig(backend="events"))
NetSim(..., roce_params=make_roce_params(net,
       qps_per_conn=4)) [4-QP striping]        -> run(sc, RunConfig(protocol="rocev2", subflows=4))

Prebuilt-sim runs (custom oracle wiring such as queue logs or link
failures) use run_scenario_on_sim(sim, scenario, until=...).
See docs/experiments.md for the full guide."""


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description=__doc__,
        epilog="Migration from the legacy entry points:\n\n"
               + MIGRATION_TABLE,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.parse_args()
    from . import permutation, oversub_linkdown, incast, collectives
    rows = []
    print("name,us_per_call,derived")

    def emit(name, us, derived):
        print(f"{name},{us if us is not None else float('nan'):.1f},{derived}")
        sys.stdout.flush()

    # Figs 9-11: permutation across link speeds
    for gbps in (200.0, 400.0, 800.0):
        rs = permutation.run(link_gbps=gbps)
        for r in rs:
            if r["transport"] == "strack" or "speedup_vs_roce" not in r:
                continue
            emit(f"fig9_perm_{int(gbps)}G_msg{r['msg']//1024}K_{r['transport']}",
                 r["max_fct_us"],
                 f"strack_speedup={r['speedup_vs_roce']:.2f}x;"
                 f"adaptive_vs_obl={r.get('adaptive_vs_oblivious', 1):.2f}x")

    # Fig 8: queue settling, from the fabric's per-tick queue-depth traces
    # (both protocols on the fast path; settle = last time any queue's
    # depth-derived delay exceeded the base-RTT-scale threshold)
    rs = permutation.run(msg_sizes=[2 * 2 ** 20], trace_queues=True,
                         backend="fabric")
    for r in rs:
        if r["backend"] != "fabric":
            continue  # roce4 (oracle) logs a different settle metric
        emit(f"fig8_settle_{r['transport']}", r["max_fct_us"],
             f"last_qdelay_over_baseRTT_at_us={r['queue_settle_us']}")

    # Figs 12-15: oversubscription + link failures
    for r in oversub_linkdown.run_oversub(4) + oversub_linkdown.run_oversub(8):
        emit(f"fig12_{r['workload']}_{r['transport']}", r["max_fct_us"],
             f"speedup={r.get('speedup_vs_roce', '')}")
    for r in (oversub_linkdown.run_linkdown(0.0625)
              + oversub_linkdown.run_linkdown(0.25)):
        emit(f"fig14_{r['workload']}_{r['transport']}", r["max_fct_us"],
             f"speedup={r.get('speedup_vs_roce', '')};"
             f"adaptive_vs_obl={r.get('adaptive_vs_oblivious', '')}")

    # Fig 4: signal timing
    for r in incast.run_signals():
        emit("fig4_signals", r["first_ecn_us"],
             f"first_rtt_rise_us={r['first_rtt_rise_us']};"
             f"ecn_leads={r['ecn_leads']}")

    # Figs 16-20: incast
    for r in incast.run_fct(8, msg=2 * 2 ** 20) + incast.run_fct(
            32, msg=2 * 2 ** 20, topo_kw=dict(n_tor=8, hosts_per_tor=8)):
        emit(f"fig19_{r['workload']}_{r['transport']}", r["max_fct_us"],
             f"drops={r['drops']};pauses={r['pauses']};"
             f"parity={r.get('strack_over_roce', '')}")
    for r in incast.run_dynamics(16):
        emit(f"fig16_dyn_{r['transport']}", r["converge_us"],
             f"jain={r['jain_fairness']:.3f};drops={r['drops']};"
             f"pauses={r['pauses']}")
    for r in incast.run_queue_stability():
        emit(f"fig20_{r['workload']}", r["median_steady_qdelay_us"],
             f"target_us={r['target_us']};p95={r['p95_steady_qdelay_us']:.1f}")

    # Figs 21-28: collectives
    for algo in ("ring", "dbt", "hd", "a2a"):
        for ov in (1, 4):
            for r in collectives.run_collectives(algo, oversub=ov):
                emit(f"fig21_{r['workload']}_{r['transport']}",
                     r["max_collective_us"],
                     f"speedup={r.get('speedup_vs_roce', '')};"
                     f"vs_4qp={r.get('speedup_vs_roce4', '')};"
                     f"cdf_spread={r['cdf_spread']:.3f};"
                     f"done={r['finished']}/{r['total']}")


if __name__ == "__main__":
    main()
