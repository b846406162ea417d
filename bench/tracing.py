"""Reduces a profiler capture of a slice of answers to per-layer numbers.

Reads the ``.xplane.pb`` the JAX profiler writes (``jax.profiler.
ProfileData``; nothing else of JAX).  The slice is the host span
``bench.slice`` that ``run.py`` records around the capture.  Within it,
per TPU plane:

* busy time: the union of the intervals of the ops on the ``XLA Ops``
  line (``Async XLA Ops``, the copies that overlap them, do not count);
* self time per HLO instruction (time not covered by an op nested in
  it), summed over chips, for the top device ops;
* idle gaps: the complement of the busy union, each labelled by the
  benchmark's host span it overlaps most (``bench.scenario``,
  ``bench.run``, or ``bench.between`` where no span covers it);
* collective time: ops of both lines whose instruction is a cross-chip
  collective, as ``chip_smoke.py`` counts them.
"""
from __future__ import annotations

SLICE = "bench.slice"
#: Host spans that label idle gaps, innermost first.
HOST_SPANS = ("bench.scenario", "bench.run")
BETWEEN = "bench.between"
OPS, ASYNC_OPS = "XLA Ops", "Async XLA Ops"
#: HLO instruction-name prefixes of the cross-chip exchanges.
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")
TOP = 10


def instruction(event_name: str) -> str:
    """``%all-gather.3 = ...`` -> ``all-gather.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: list) -> list:
    """Disjoint, sorted cover of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def self_times(ops: list) -> dict:
    """Seconds per instruction not covered by an op nested inside it.

    ``ops`` is ``[(start, end, name), ...]`` on one line."""
    out: dict = {}
    stack: list = []  # [end, name]
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out[name] = out.get(name, 0.0) + (e - s)
        if stack:
            parent = stack[-1][1]
            out[parent] -= min(e, stack[-1][0]) - s
        stack.append([e, name])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: tuple, spans: list) -> str:
    """The host span overlapping ``gap`` most (innermost on ties)."""
    best, best_len = BETWEEN, 0.0
    for s, e, name in spans:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best_len or (overlap == best_len > 0
                                  and HOST_SPANS.index(name)
                                  < HOST_SPANS.index(best)):
            best, best_len = name, overlap
    return best


def reduce_events(host: list, devices: dict) -> dict:
    """The slice's numbers from plain events.

    ``host``: ``[(start_s, end_s, name), ...]`` of the benchmark's host
    spans, ``bench.slice`` among them; ``devices``: ``{plane: {line:
    [(start_s, end_s, event name), ...]}}`` of each TPU plane.
    """
    (lo, hi), = [(s, e) for s, e, n in host if n == SLICE]
    spans = [h for h in clip(host, lo, hi) if h[2] in HOST_SPANS]
    busy_s, coll_s, selfs, idle = [], [], {}, []
    for lines in devices.values():
        ops = [(s, e, instruction(n)) for s, e, n in
               clip(lines.get(OPS, []), lo, hi)]
        cover = union([(s, e) for s, e, _ in ops])
        busy_s.append(sum(e - s for s, e in cover))
        for name, t in self_times(ops).items():
            selfs[name] = selfs.get(name, 0.0) + t
        idle += [(label(g, spans), g[1] - g[0]) for g in gaps(cover, lo, hi)]
        asyn = [(s, e, instruction(n)) for s, e, n in
                clip(lines.get(ASYNC_OPS, []), lo, hi)]
        coll_s.append(sum(e - s for s, e, n in ops + asyn
                          if n.startswith(COLLECTIVES)))
    n = max(1, len(devices))
    top = sorted(selfs.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": hi - lo, "busy_s": sum(busy_s) / n,
            "collective_s": sum(coll_s) / n, "devices": len(devices),
            "device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [list(g) for g in
                          sorted(idle, key=lambda g: -g[1])[:TOP]]}


def read_events(path) -> tuple:
    """``(slice, device lines)`` of one ``.xplane.pb``, in seconds:
    ``slice`` is the ``(start, end)`` of the ``bench.slice`` span."""
    from jax.profiler import ProfileData
    slices, devices = [], {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            slices += [(ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9)
                       for line in plane.lines for ev in line.events
                       if ev.name == SLICE]
        elif plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {
                line.name: [(ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                            for ev in line.events]
                for line in plane.lines if line.name in (OPS, ASYNC_OPS)}
    (trace_slice,) = slices
    return trace_slice, devices


def reduce(path, spans: list) -> dict:
    """Reduce the trace at ``path``; ``spans`` are the benchmark's host
    spans ``[(start, end, name), ...]`` on the host's own clock, with the
    ``bench.slice`` span that ties that clock to the trace's."""
    trace_slice, devices = read_events(path)
    if not devices:
        raise ValueError(f"{path}: no TPU plane in the trace")
    (host_slice,) = [(s, e) for s, e, n in spans if n == SLICE]
    shift = trace_slice[0] - host_slice[0]
    host = [(s + shift, e + shift, n) for s, e, n in spans if n != SLICE]
    return reduce_events(host + [(*trace_slice, SLICE)], devices)
