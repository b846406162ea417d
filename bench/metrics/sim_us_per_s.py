"""Simulated microseconds per wall second: the simulated span of every
answer in the window (scenario start to its last completion, as the
plain reference completes it) over the window's host-clock seconds.
The span is the reference's, so an answer the program finishes later
counts no more work.  None where the reference finished no message of
an answer (the run is then not correct)."""
import math


def read(run):
    spans = [a["ref_span_us"] for a in run["answers"]]
    if not all(math.isfinite(s) for s in spans):
        return None
    return sum(spans) / run["window_s"]
