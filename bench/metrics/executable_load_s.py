"""Seconds of the fabric program's backend compile, or of its load from
JAX's persistent compilation cache, as the program's compile counters
report them (``repro.obs.spans.compiled``): all in set-up, since the
window compiles nothing.  None where the program keeps no such
counters."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    return spans.compiled()["compile_s"]
