"""The trace reduction: busy union, self time, labelled idle gaps and
collective time, on hand-made events and on a recorded chip trace."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import tracing  # noqa: E402

SAMPLE = Path(__file__).resolve().parent / "data"


def test_union_and_gaps():
    cover = tracing.union([(3, 4), (0, 1), (0.5, 2), (4, 5)])
    assert cover == [(0, 2), (3, 5)]
    assert tracing.gaps(cover, -1, 6) == [(-1, 0), (2, 3), (5, 6)]


def test_self_time_subtracts_nested_ops():
    ops = [(0, 10, "while.1"), (1, 3, "fusion.2"), (4, 5, "fusion.3"),
           (4.2, 4.4, "copy.4"), (12, 13, "fusion.2")]
    got = tracing.self_times(ops)
    assert got == pytest.approx({"while.1": 7, "fusion.2": 3,
                                 "fusion.3": 0.8, "copy.4": 0.2})
    assert sum(got.values()) == pytest.approx(11)


def test_reduce_events_on_two_chips():
    host = [(0.0, 10.0, "bench.slice"), (1.0, 4.0, "bench.run"),
            (4.5, 5.5, "bench.scenario"), (6.0, 12.0, "bench.run")]
    dev = {
        "/device:TPU:0": {
            "XLA Ops": [(-1.0, 2.0, "%fusion.1 = f32[8] fusion(...)"),
                        (2.5, 4.0, "%all-gather.3 = s32[8] all-gather(...)"),
                        (7.0, 9.0, "%fusion.1 = f32[8] fusion(...)")],
            "Async XLA Ops": [(5.0, 6.0, "%all-reduce-start.2 = ...")]},
        "/device:TPU:1": {
            "XLA Ops": [(0.0, 10.0, "%fusion.1 = f32[8] fusion(...)")]},
    }
    r = tracing.reduce_events(host, dev)
    assert r["window_s"] == 10.0 and r["devices"] == 2
    assert r["busy_s"] == pytest.approx((2 + 1.5 + 2 + 10) / 2)
    assert r["collective_s"] == pytest.approx((1.5 + 1.0) / 2)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(14 / 2)]
    gaps = {tuple(g) for g in r["idle_gaps"]}
    assert gaps == {("bench.run", 0.5), ("bench.scenario", 3.0),
                    ("bench.run", 1.0)}


@pytest.mark.parametrize("path", sorted(SAMPLE.glob("*.xplane.pb")),
                         ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    trace_slice, devices = tracing.read_events(path)
    assert devices and trace_slice[1] > trace_slice[0]
    # host spans on another clock, tied to the trace by the slice span
    t0 = 1000.0
    span = trace_slice[1] - trace_slice[0]
    spans = [(t0 - 1.0, t0 + span / 2, "bench.run"),
             (t0, t0 + span, tracing.SLICE)]
    r = tracing.reduce(path, spans)
    assert r["devices"] == len(devices)
    assert r["window_s"] == pytest.approx(span)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"]
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] * 1.0001
    labels = {g[0] for g in r["idle_gaps"]}
    assert labels <= {"bench.run", tracing.BETWEEN}
