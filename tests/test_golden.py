"""Golden FCT regression gate: per-figure fabric summary snapshots.

Each case pins the headline numbers (max/avg FCT, drops, pauses, and the
collective completion time for grouped traces) of one figure-class
scenario — permutation / incast / ring allreduce / windowed all-to-all,
under STrack, RoCEv2 and the 4-QP striped RoCEv2 — against a checked-in
JSON snapshot in ``tests/golden/``.  Fidelity refactors that shift a
headline number fail HERE even when they stay inside the oracle-parity
bands, so intentional model changes must regenerate the snapshots:

    PYTHONPATH=src python -m pytest tests/test_golden.py --update-golden

and the diff reviewed like any other code change.  The fabric is
deterministic (deterministic ECN dither, hash/seeded entropy), so the
comparison is tight: exact ints, 1e-6 relative on floats.
"""
import json

import pytest

from repro.core.params import NetworkSpec
from repro.sim.faults import link_flap
from repro.sim.topology import full_bisection
from repro.sim.workloads import (RunConfig, collective_scenario,
                                 incast_scenario, permutation_scenario, run)

pytestmark = pytest.mark.tier1

NET400 = NetworkSpec(link_gbps=400.0)
NET100 = NetworkSpec(link_gbps=100.0)
TOPO44 = full_bisection(4, 4)
TOPO24 = full_bisection(2, 4)

#: Summary keys pinned by the snapshots (whichever the run reports).
GOLDEN_KEYS = ("max_fct", "avg_fct", "unfinished", "drops", "pauses",
               "max_collective_time", "finished_groups", "total_groups")


def _perm(**kw):
    return (permutation_scenario(TOPO44, 256 * 2 ** 10, net=NET400, seed=0),
            RunConfig(backend="fabric", **kw))


def _perm_flap(**kw):
    # canonical chaos case: one ToR-0 uplink flaps mid-run ([50, 400)
    # ticks) while the permutation is in flight, then recovers — pins the
    # blackhole + loss-recovery path (docs/robustness.md)
    return (permutation_scenario(TOPO44, 256 * 2 ** 10, net=NET400, seed=0),
            RunConfig(backend="fabric", faults=link_flap(0, 0, 50, 400),
                      **kw))


def _incast(**kw):
    return (incast_scenario(TOPO44, 8, 512 * 2 ** 10, net=NET400),
            RunConfig(backend="fabric", **kw))


def _ring(**kw):
    return (collective_scenario(TOPO24, "ring", 1, 8, 512 * 2 ** 10,
                                net=NET100, seed=0, chunk=32 * 2 ** 10),
            RunConfig(backend="fabric", **kw))


def _a2a(**kw):
    return (collective_scenario(TOPO24, "a2a", 2, 4, 256 * 2 ** 10,
                                net=NET100, seed=0, chunk=128 * 2 ** 10,
                                window=2),
            RunConfig(backend="fabric", **kw))


CASES = {
    "perm16_strack": lambda: _perm(),
    "perm16_roce": lambda: _perm(protocol="rocev2"),
    "perm16_flap_strack": lambda: _perm_flap(),
    "perm16_flap_roce": lambda: _perm_flap(protocol="rocev2"),
    "incast8_strack": lambda: _incast(),
    "incast8_roce": lambda: _incast(protocol="rocev2"),
    "ring8_strack": lambda: _ring(),
    "ring8_roce4": lambda: _ring(protocol="rocev2", subflows=4),
    "a2a_strack": lambda: _a2a(),
}


def _snapshot(res: dict) -> dict:
    return {k: res[k] for k in GOLDEN_KEYS if k in res}


def golden_mismatches(snap: dict, want: dict) -> list:
    """``(key, got, want)`` for every pinned key that breaks the snapshot
    rule: exact ints, 1e-6 relative on floats (chip_smoke.py applies the
    same rule to the goldens run on the chip)."""
    bad = []
    for k, v in sorted(want.items()):
        got = snap[k]
        ok = (got == pytest.approx(v, rel=1e-6) if isinstance(v, float)
              else got == v)
        if not ok:
            bad.append((k, got, v))
    return bad


@pytest.mark.parametrize("case", sorted(CASES))
def test_golden_fct(case, update_golden, golden_dir):
    sc, cfg = CASES[case]()
    snap = _snapshot(run(sc, cfg))
    path = golden_dir / f"{case}.json"
    if update_golden:
        golden_dir.mkdir(exist_ok=True)
        path.write_text(json.dumps(snap, indent=2, sort_keys=True) + "\n")
        pytest.skip(f"updated {path.name}")
    assert path.exists(), (
        f"missing golden fixture {path}; generate with "
        f"`pytest tests/test_golden.py --update-golden` and review the "
        f"numbers before checking them in")
    want = json.loads(path.read_text())
    assert set(snap) == set(want), (
        f"{case}: summary keys changed {sorted(want)} -> {sorted(snap)}; "
        f"regenerate the goldens if intentional")
    assert not golden_mismatches(snap, want), (
        case, golden_mismatches(snap, want))
