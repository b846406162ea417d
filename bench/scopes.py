"""Reduces a profiler capture of the fabric program to device time per
``tick()`` stage and per warp trip, and labels idle gaps by host span.

The program names each stage of its warp scan with ``jax.named_scope``
(``fabric.gate``, ``fabric.pfc``, ``fabric.faults``,
``fabric.transport``, ``fabric.route``, ``fabric.queues`` with
``fabric.queues.rank`` nested, ``fabric.receive``, ``fabric.complete``,
``fabric.warp``, and ``fabric.cond`` for the loop's condition).  A TPU
trace gives each op's scope path in the ``tf_op`` stat of its event
metadata (``jit(fabric_program)/while/body/fabric.queues/...``), which
``jax.profiler.ProfileData`` does not expose, so this module reads the
``.xplane.pb`` with the XPlane protobuf classes of the installed
``tensorflow`` package, loaded from their file without importing
``tensorflow``.

Per TPU plane, within the ``bench.slice`` host span:

* trips: the executions in the slice of one op that runs once per warp
  trip (``trip_marks``); the span from one to the next in the same
  execution of the program's module is one whole trip;
* busy time per trip: the union of the ``XLA Ops`` intervals inside the
  whole trips, over their number;
* stage time per trip: self time (``tracing.self_times``) of each op,
  put under the innermost ``fabric.*`` scope of its path
  (``scopes_in_force``), or ``unscoped``, summed over the whole trips and
  divided by their number;
* idle time by host span: each gap of the busy union, split over the
  innermost host span (``fabric.*`` or ``bench.*``) open at each instant,
  ``bench.between`` where none is.

Numbers are averaged over the chips, as ``tracing.reduce_events`` does.
"""
from __future__ import annotations

import bisect
import functools
import importlib.util
import math
import os
import re

from bench import tracing

MODULES = "XLA Modules"
PROGRAM_MODULE = "jit_fabric_program"
MARK = "fabric.warp"
UNSCOPED = "unscoped"
#: The innermost ``fabric.*`` component of a scope path.
SCOPE = re.compile(r"(?:^|[/(])(fabric\.[a-z][a-z.]*[a-z])(?=[/):]|$)")
#: Stages as the per-layer metrics group them.
GROUPS = {
    "transport": ("fabric.transport",),
    "queues": ("fabric.queues", "fabric.queues.rank"),
    "receive": ("fabric.receive",),
    "warp": ("fabric.warp", "fabric.cond"),
}


@functools.cache
def xplane_pb2():
    """The ``xplane_pb2`` module of the installed ``tensorflow`` package,
    loaded from its file: importing ``tensorflow`` itself would start its
    runtime next to JAX's."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or spec.origin is None:
        raise ImportError("reading op scopes needs the XPlane protobuf "
                          "classes of the tensorflow package")
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def scope(tf_op: str | None) -> str:
    """``.../fabric.queues/fabric.queues.rank/add:`` -> the innermost
    ``fabric.*`` scope, else ``unscoped``."""
    found = SCOPE.findall(tf_op or "")
    return found[-1] if found else UNSCOPED


def read(path) -> tuple:
    """``(slice, host events, devices)`` of one ``.xplane.pb``, in seconds.

    ``slice`` is the ``(start, end)`` of the ``bench.slice`` host event;
    ``host events`` every host annotation ``(start, end, name)``;
    ``devices`` ``{plane: {line: [(start, end, name, tf_op), ...]}}`` for
    the ``XLA Ops``, ``Async XLA Ops`` and ``XLA Modules`` lines."""
    space = xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    host, devices = [], {}
    for plane in space.planes:
        is_host = plane.name.startswith("/host:")
        if not is_host and not plane.name.startswith("/device:TPU:"):
            continue
        tf_op_id = {k for k, m in plane.stat_metadata.items()
                    if m.name == "tf_op"}
        meta = {}
        for k, m in plane.event_metadata.items():
            tf_op = next((st.str_value for st in m.stats
                          if st.metadata_id in tf_op_id), None)
            meta[k] = (m.name, tf_op)
        lines = {}
        for line in plane.lines:
            if not is_host and line.name not in (tracing.OPS,
                                                 tracing.ASYNC_OPS, MODULES):
                continue
            # whole nanoseconds, as jax.profiler.ProfileData gives them
            t0 = line.timestamp_ns
            lines[line.name] = [
                ((t0 + ev.offset_ps // 1000) * 1e-9,
                 (t0 + ev.offset_ps // 1000 + ev.duration_ps // 1000) * 1e-9,
                 *meta[ev.metadata_id]) for ev in line.events]
        if is_host:
            host += [(s, e, n) for evs in lines.values()
                     for s, e, n, _ in evs]
        else:
            devices[plane.name] = lines
    (trace_slice,) = [(s, e) for s, e, n in host if n == tracing.SLICE]
    return trace_slice, host, devices


def trip_marks(ops: list, modules: list) -> list:
    """Start times of one op that runs once per warp trip, one list per
    execution of the program's module.

    The loop's condition has no op of its own in a TPU trace (XLA folds
    it into the body: the ``fabric.cond`` scope appears on no event), so
    the mark is an op under ``fabric.warp``, the idle test and warp
    target that close every trip: of those, the ones run the most common
    number of times, and of them the first to run."""
    runs: dict = {}
    for s, _, name, tf_op in ops:
        if scope(tf_op) == MARK:
            runs.setdefault(name, []).append(s)
    if not runs:
        return []
    counts = [len(v) for v in runs.values()]
    common = max(set(counts), key=counts.count)
    marks = min((v for v in runs.values() if len(v) == common),
                key=min)
    spans = [(s, e) for s, e, name, _ in modules
             if name.startswith(PROGRAM_MODULE)] or [(-math.inf, math.inf)]
    return [[m for m in sorted(marks) if lo <= m <= hi] for lo, hi in spans]


def stage_times(ops: list, trips: list) -> tuple:
    """``(busy seconds, {scope: self seconds})`` over the ``trips``: each
    op is cut to each trip it overlaps (the loop's own op spans them
    all), so the self times add up to the busy time."""
    if not trips:
        return 0.0, {}
    names = scopes_in_force(ops)
    shortest = min(hi - lo for lo, hi in trips)
    longs = [i for i, (s, e, _, _) in enumerate(ops) if e - s >= shortest]
    short = sorted((s, i) for i, (s, e, _, _) in enumerate(ops)
                   if e - s < shortest)
    starts = [s for s, _ in short]
    pieces, busy = [], 0.0
    for k, (lo, hi) in enumerate(trips):
        near = [i for _, i in short[bisect.bisect_left(starts, lo - shortest):
                                    bisect.bisect_left(starts, hi)]]
        cut = [(max(ops[i][0], lo), min(ops[i][1], hi), (k, i))
               for i in near + longs if ops[i][1] > lo and ops[i][0] < hi]
        busy += sum(e - s for s, e in tracing.union([c[:2] for c in cut]))
        pieces += cut
    selfs: dict = {}
    for (_, i), t in tracing.self_times(pieces).items():
        selfs[names[i]] = selfs.get(names[i], 0.0) + t
    return busy, selfs


def scopes_in_force(ops: list) -> list:
    """The scope of each op of one line.  An op XLA made up itself has
    no scope path: an inner loop of a stage takes the scope of the first
    op it encloses that has one, any other (a copy it inserted) the scope
    of the last op before it that has one.  An op whose path names no
    ``fabric.*`` scope, such as the loop's own carry copies
    (``jit(fabric_program)/while:``), is ``unscoped``."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    out, last = [UNSCOPED] * len(ops), UNSCOPED
    for j, i in enumerate(order):
        if ops[i][3]:
            last = out[i] = scope(ops[i][3])
            continue
        out[i] = last
        for q in range(j + 1, len(order)):
            k = order[q]
            if ops[k][0] >= ops[i][1]:
                break
            if ops[k][3]:
                out[i] = scope(ops[k][3])
                break
    return out


def depths(spans: list) -> list:
    """Nesting depth of each ``(start, end, name)`` span: how many of the
    others contain it."""
    return [sum(1 for j, (s2, e2, _) in enumerate(spans)
                if j != i and s2 <= s and e <= e2
                and (s2, e2) != (s, e))
            for i, (s, e, _) in enumerate(spans)]


def innermost(spans: list) -> list:
    """``[(start, end, name), ...]``: the time the ``(start, end, name)``
    host spans cover, cut where the innermost open span changes (the
    deepest; of two as deep, the later)."""
    depth = depths(spans)
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(d, s, n) for (s, e, n), d in zip(spans, depth)
                 if s <= a and b <= e]
        if open_:
            out.append((a, b, max(open_)[2]))
    return out


def label_gaps(gaps: list, segments: list) -> dict:
    """``{span name: seconds}`` of the ``gaps`` under the innermost host
    span open at each instant (``segments`` from ``innermost``), and
    ``bench.between`` where none is."""
    starts = [s for s, _, _ in segments]
    out: dict = {}
    for lo, hi in gaps:
        covered = 0.0
        k = max(0, bisect.bisect_right(starts, lo) - 1)
        while k < len(segments) and segments[k][0] < hi:
            s, e, name = segments[k]
            t = min(e, hi) - max(s, lo)
            if t > 0:
                out[name] = out.get(name, 0.0) + t
                covered += t
            k += 1
        if hi - lo > covered:
            out[tracing.BETWEEN] = (out.get(tracing.BETWEEN, 0.0)
                                    + (hi - lo - covered))
    return out


def reduce_events(host: list, devices: dict) -> dict:
    """The per-stage numbers of one slice from plain events.

    ``host``: ``[(start, end, name), ...]`` host spans on the trace's
    clock, ``bench.slice`` among them; ``devices``: ``{plane: {line:
    [(start, end, name, tf_op), ...]}}``, ``XLA Ops`` and ``XLA Modules``
    lines of each TPU plane.  Per-trip numbers are in milliseconds."""
    (lo, hi), = [(s, e) for s, e, n in host if n == tracing.SLICE]
    segments = innermost([h for h in host if h[2] != tracing.SLICE
                          and h[1] > lo and h[0] < hi])
    trips, whole, busy, stages, idle = [], [], [], {}, {}
    for lines in devices.values():
        ops = [(s, e, tracing.instruction(n), t)
               for s, e, n, t in tracing.clip(lines.get(tracing.OPS, []),
                                              lo, hi)]
        marks = [[m for m in run if lo <= m <= hi]
                 for run in trip_marks(ops, lines.get(MODULES, []))]
        trips.append(sum(len(run) for run in marks))
        bounds = [pair for run in marks for pair in zip(run, run[1:])]
        b, selfs = stage_times(ops, bounds)
        if bounds:
            whole.append(len(bounds))
            busy.append(1e3 * b / len(bounds))
            for k, v in selfs.items():
                stages.setdefault(k, []).append(1e3 * v / len(bounds))
        cover = tracing.union([(s, e) for s, e, *_ in ops])
        for k, v in label_gaps(tracing.gaps(cover, lo, hi),
                               segments).items():
            idle[k] = idle.get(k, 0.0) + v / len(devices)
    n = max(1, len(busy))
    return {
        "trips": min(trips, default=0), "whole_trips": min(whole, default=0),
        "device_ms_per_trip": sum(busy) / n if busy else None,
        "stages": sorted([[k, sum(v) / n] for k, v in stages.items()],
                         key=lambda kv: -kv[1]),
        "idle_by_span": sorted([[k, v] for k, v in idle.items()],
                               key=lambda kv: -kv[1])}


def scoped_share(stages: list) -> float | None:
    """Share of the stage time under some ``fabric.*`` scope."""
    total = sum(v for _, v in stages)
    return 1.0 - dict(stages).get(UNSCOPED, 0.0) / total if total else None


def reduce(path, spans: list) -> dict:
    """Reduce the trace at ``path``: ``tracing.reduce_events``'s numbers
    (busy, collectives, top ops, gaps by ``bench.*`` span) and this
    module's; ``spans`` are host spans ``[(start, end, name), ...]`` on
    the host's clock, ``bench.slice`` among them, as ``tracing.reduce``
    takes them."""
    trace_slice, _, devices = read(path)
    if not devices:
        raise ValueError(f"{path}: no TPU plane in the trace")
    (host_slice,) = [(s, e) for s, e, n in spans if n == tracing.SLICE]
    shift = trace_slice[0] - host_slice[0]
    host = [(s + shift, e + shift, n) for s, e, n in spans
            if n != tracing.SLICE] + [(*trace_slice, tracing.SLICE)]
    plain = {p: {k: [(s, e, n) for s, e, n, _ in evs]
                 for k, evs in lines.items() if k != MODULES}
             for p, lines in devices.items()}
    return {**tracing.reduce_events(host, plain),
            **reduce_events(host, devices)}


def group_ms(stages: list, group: str) -> float | None:
    """Milliseconds per trip of one ``GROUPS`` entry, from ``stages``."""
    got = dict(stages)
    if not got:
        return None
    return sum(got.get(k, 0.0) for k in GROUPS[group])
