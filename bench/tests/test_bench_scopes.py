"""The stage reduction (``bench/scopes.py``) on hand-made events and on a
recorded chip trace, and the readers of the program's spans and compile
counters."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import scopes, spec, tracing  # noqa: E402
from repro.obs import spans  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
TWIN = DATA / "strack8k-x4-twin.perm64k.xplane.pb"
BODY = "jit(fabric_program)/while/body/"


@pytest.mark.parametrize("tf_op, want", [
    (BODY + "fabric.queues/fabric.queues.rank/jit(cumsum)/add:",
     "fabric.queues.rank"),
    (BODY + "fabric.transport/vmap(jit(_roll_dynamic))/select_n:",
     "fabric.transport"),
    ("jit(fabric_program)/while:", scopes.UNSCOPED),
    (None, scopes.UNSCOPED),
])
def test_innermost_scope_of_a_path(tf_op, want):
    assert scopes.scope(tf_op) == want


def test_ops_without_a_path_take_the_scope_in_force():
    ops = [(0, 1, "a", BODY + "fabric.receive/x:"),
           (1, 4, "while.3", None),          # an inner loop XLA made
           (1, 2, "b", BODY + "fabric.receive/y:"),
           (4, 5, "copy.1", "jit(fabric_program)/while:"),
           (5, 6, "copy.2", None)]
    assert scopes.scopes_in_force(ops) == [
        "fabric.receive", "fabric.receive", "fabric.receive",
        scopes.UNSCOPED, scopes.UNSCOPED]


def _trip(t0, warp_op="%fusion.9 = ..."):
    """One 10-unit trip of hand-made ops starting at ``t0``."""
    return [(t0, t0 + 4, "%fusion.1 = ...", BODY + "fabric.transport/a:"),
            (t0 + 4, t0 + 7, "%while.2 = ...", None),
            (t0 + 4.5, t0 + 5.5, "%fusion.3 = ...",
             BODY + "fabric.queues/fabric.queues.rank/b:"),
            (t0 + 7, t0 + 8, "%copy.4 = ...", "jit(fabric_program)/while:"),
            (t0 + 9, t0 + 10, warp_op, BODY + "fabric.warp/c:")]


def test_stage_time_over_whole_trips():
    # three trips in one module execution, the slice cuts the first
    ops = _trip(0) + _trip(10) + _trip(20)
    host = [(3.0, 40.0, tracing.SLICE)]
    dev = {"/device:TPU:0": {
        tracing.OPS: ops,
        scopes.MODULES: [(0, 30, "jit_fabric_program(1)", None),
                         (31, 32, "jit_add(2)", None)]}}
    r = scopes.reduce_events(host, dev)
    # marks at 9, 19, 29: three trips run in the slice, two whole ones
    assert r["trips"] == 3 and r["whole_trips"] == 2
    # a whole trip (mark to mark) holds 9 busy units of 10
    assert r["device_ms_per_trip"] == pytest.approx(1e3 * 9)
    stages = dict(r["stages"])
    assert stages == pytest.approx({
        "fabric.transport": 4e3,
        "fabric.queues.rank": 1e3 + 2e3,    # while.2's self time too
        scopes.UNSCOPED: 1e3, "fabric.warp": 1e3})
    assert sum(stages.values()) == pytest.approx(r["device_ms_per_trip"])
    assert scopes.group_ms(r["stages"], "queues") == pytest.approx(3e3)
    assert scopes.group_ms(r["stages"], "receive") == 0.0
    assert scopes.scoped_share(r["stages"]) == pytest.approx(8 / 9)


def test_trips_never_span_two_module_executions():
    ops = _trip(0) + _trip(10) + _trip(100) + _trip(110)
    dev = {"/device:TPU:0": {tracing.OPS: ops, scopes.MODULES: [
        (0, 20, "jit_fabric_program(1)", None),
        (100, 120, "jit_fabric_program(1)", None)]}}
    r = scopes.reduce_events([(0.0, 200.0, tracing.SLICE)], dev)
    assert r["trips"] == 4 and r["whole_trips"] == 2
    assert r["device_ms_per_trip"] == pytest.approx(1e3 * 9)


def test_marks_are_the_warp_ops_run_once_per_trip():
    # a hoisted warp op runs once per answer, two others every trip
    ops = _trip(0) + _trip(10) + _trip(20)
    ops += [(t + 9.5, t + 10, "%fusion.8 = ...", BODY + "fabric.warp/d:")
            for t in (0, 10, 20)]
    ops.append((-5, -4, "%hoisted.7 = ...", BODY + "fabric.warp/e:"))
    marks = scopes.trip_marks(ops, [])
    assert marks == [[9, 19, 29]]


def test_no_warp_scope_counts_no_trip():
    ops = [(s, e, n, BODY + "x:") for s, e, n, _ in _trip(0)]
    r = scopes.reduce_events([(0.0, 20.0, tracing.SLICE)],
                             {"/device:TPU:0": {tracing.OPS: ops}})
    assert r["trips"] == 0 and r["device_ms_per_trip"] is None
    assert r["stages"] == []


def test_gaps_go_to_the_innermost_span():
    host = [(0.0, 100.0, tracing.SLICE),
            (10.0, 60.0, "bench.run"), (11.0, 59.0, "fabric.run"),
            (12.0, 20.0, "fabric.inputs"), (25.0, 50.0, "fabric.device"),
            (50.0, 58.0, "fabric.fetch"), (60.0, 70.0, "bench.scenario")]
    ops = [(0.0, 5.0, "%f.1 = ...", None), (30.0, 48.0, "%f.2 = ...", None),
           (90.0, 95.0, "%f.3 = ...", None)]
    r = scopes.reduce_events(host, {"/device:TPU:0": {tracing.OPS: ops}})
    idle = dict(r["idle_by_span"])
    assert idle == pytest.approx({
        tracing.BETWEEN: 5 + 20 + 5,       # 5-10, 70-90, 95-100
        "bench.run": 1 + 1, "fabric.run": 1 + 5 + 1,
        "fabric.inputs": 8, "fabric.device": 5 + 2,
        "fabric.fetch": 8, "bench.scenario": 10})
    assert sum(idle.values()) == pytest.approx(100 - 5 - 18 - 5)


def test_recorded_twin_answer():
    """One whole answer of a bench twin on the chip: every trip counted,
    every op of the loop body under a stage scope."""
    assert TWIN.stat().st_size <= 2 * 2 ** 20
    side = json.loads(TWIN.with_suffix("").with_suffix(".json").read_text())
    host_spans = [tuple(s) for s in side["spans"]]
    r = scopes.reduce(TWIN, host_spans)
    assert r["trips"] == side["warp_trips"]
    assert r["whole_trips"] == side["warp_trips"] - 1
    total = sum(v for _, v in r["stages"])
    assert total == pytest.approx(r["device_ms_per_trip"], rel=0.01)
    assert r["device_ms_per_trip"] * r["whole_trips"] <= 1e3 * r["busy_s"]
    # the rest is the loop's own: its carry copies and its op between
    # body ops (8 hosts do little work per trip)
    assert scopes.scoped_share(r["stages"]) >= 0.9
    _, _, devices = scopes.read(TWIN)
    (lines,) = devices.values()
    body = [t for _, _, _, t in lines[tracing.OPS]
            if t and "/while/body/" in t]
    assert body and all(scopes.scope(t) != scopes.UNSCOPED for t in body)
    # the program's own spans reached the trace and the reduction
    names = {n for _, _, n in scopes.read(TWIN)[1]}
    assert {"fabric.run", "fabric.device", tracing.SLICE} <= names
    idle = dict(r["idle_by_span"])
    assert idle.get(tracing.BETWEEN, 0.0) <= 0.1 * sum(idle.values())
    # the harness's own numbers come out as tracing.reduce gives them
    base = tracing.reduce(TWIN, host_spans)
    for key in ("window_s", "busy_s", "collective_s", "device_ops",
                "idle_gaps"):
        assert r[key] == base[key]


def _run_with(answers):
    return {"answers": [{"summary": s} for s in answers]}


def test_answer_host_ms_reads_the_answers_spans():
    read = spec.reader("answer_host_ms")
    ids = [spans.next_answer() for _ in range(2)]
    # spans of known lengths, as run() leaves them
    for a, (run_s, device_s) in zip(ids, [(0.5, 0.4), (0.3, 0.1)]):
        spans._recent.append(spans.Span(0.0, run_s, "fabric.run", None,
                                        {"answer": a}))
        spans._recent.append(spans.Span(0.1, 0.1 + device_s,
                                        "fabric.device", "fabric.run",
                                        {"answer": a}))
    assert read(_run_with([{"answer": a} for a in ids])) == \
        pytest.approx(1e3 * ((0.5 - 0.4) + (0.3 - 0.1)) / 2)
    # a program without spans: its summaries carry no answer id
    assert read(_run_with([{"max_fct": 1.0}])) is None
    # an answer whose spans are gone reads nothing
    assert read(_run_with([{"answer": spans.next_answer()}])) is None


def test_compile_readers_read_the_counters(monkeypatch):
    monkeypatch.setattr(spans, "_compiled",
                        {"trace_s": 1.5, "lower_s": 0.25, "compile_s": 3.0})
    assert spec.reader("program_trace_s")({}) == pytest.approx(1.75)
    assert spec.reader("executable_load_s")({}) == pytest.approx(3.0)
