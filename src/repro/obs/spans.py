"""Host spans and compile counters of the fabric program's own time.

``span(name, **ids)`` marks one step of the host path of a run.  It
opens a ``jax.profiler.TraceAnnotation``, so a profiler capture shows the
step on the device trace's own clock, and when it ends it records
``Span(start, end, name, parent, ids)`` on ``time.perf_counter``: in a
bounded buffer of the latest spans (``recent()``), and in every
``recording()`` open at the time.  A span nested in another inherits its
ids, so the steps of one ``run()`` share its ``answer`` id.  Nothing is
written out.

The spans of one fabric answer (``workloads.run`` and
``fabric.run_fabric_trace``):

* ``fabric.run``: the whole call, with the ``answer`` id the summary
  returns; its children, in order:
* ``fabric.inputs``: messages to flows, checks, fault data, flow and
  arrival arrays, shard padding;
* ``fabric.program``: ``fabric._get_program``, the program cache;
* ``fabric.dispatch``: the call of the jitted program (a first call
  traces, lowers and compiles or loads it);
* ``fabric.device``: waiting for the device to finish the scan;
* ``fabric.fetch``: the final state's ``device_get`` and the eager
  retransmit and recovery counts;
* ``fabric.summary``: the host-side metrics of the final state.

The compile counters add up the seconds JAX reports through
``jax.monitoring`` for tracing (``trace_s``), lowering to MLIR
(``lower_s``) and backend compiling or loading from the persistent cache
(``compile_s``) the jitted ``fabric_program``, and nothing else.  They
are kept for the whole process (``compiled()``) and per recording.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

#: Name of the jitted fabric program; its compiles are the ones counted.
PROGRAM = "fabric_program"
#: How the compile events name it: traced function, then module.
_PROGRAM_NAMES = (PROGRAM, f"jit({PROGRAM})")
#: ``jax.monitoring`` duration events -> counter.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
#: Spans kept by ``recent()``: a few hundred answers' worth.
KEEP = 4096


class Span(NamedTuple):
    start: float
    end: float
    name: str
    parent: Optional[str]
    ids: dict


class Recording:
    """What ends while a ``recording()`` is open: spans in the order they
    end, and compile seconds of ``fabric_program``."""

    def __init__(self):
        self.spans: list = []
        self.compile_s = dict.fromkeys(COMPILE_EVENTS.values(), 0.0)


_lock = threading.Lock()
_open = threading.local()          # .stack: [(name, ids)] of this thread
_recent: collections.deque = collections.deque(maxlen=KEEP)
_recordings: list = []
_compiled = dict.fromkeys(COMPILE_EVENTS.values(), 0.0)
_answers = itertools.count()
_listening = False


def next_answer() -> int:
    """A fresh ``answer`` id for one ``fabric.run`` span."""
    return next(_answers)


def _stack() -> list:
    if not hasattr(_open, "stack"):
        _open.stack = []
    return _open.stack


@contextlib.contextmanager
def span(name: str, **ids):
    """Mark the enclosed host work as the span ``name``."""
    stack = _stack()
    parent = stack[-1] if stack else None
    ids = {**(parent[1] if parent else {}), **ids}
    stack.append((name, ids))
    try:
        with jax.profiler.TraceAnnotation(name, **ids):
            start = time.perf_counter()
            yield
            end = time.perf_counter()
        rec = Span(start, end, name, parent[0] if parent else None, ids)
        _recent.append(rec)
        for r in tuple(_recordings):
            r.spans.append(rec)
    finally:
        stack.pop()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    key = COMPILE_EVENTS.get(event)
    if key is None or kwargs.get("fun_name") not in _PROGRAM_NAMES:
        return
    with _lock:
        _compiled[key] += duration
        for r in _recordings:
            r.compile_s[key] += duration


def listen() -> None:
    """Start the compile counters (once per process; idempotent)."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


@contextlib.contextmanager
def recording():
    """Collect every span that ends, and every compile of the fabric
    program, while the block runs; yields the ``Recording``."""
    listen()
    rec = Recording()
    with _lock:
        _recordings.append(rec)
    try:
        yield rec
    finally:
        with _lock:
            _recordings.remove(rec)


def recent() -> list:
    """The latest ``KEEP`` spans of the process, in the order they
    ended."""
    return list(_recent)


def compiled() -> dict:
    """Compile seconds of ``fabric_program`` in this process so far."""
    with _lock:
        return dict(_compiled)
