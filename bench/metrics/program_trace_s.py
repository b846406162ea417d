"""Seconds JAX spent tracing the fabric program to a jaxpr and lowering
it to an MLIR module, as the program's compile counters report them
(``repro.obs.spans.compiled``): all in set-up, since the window compiles
nothing (the run checks ``fabric.program_builds``).  None where the
program keeps no such counters."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    c = spans.compiled()
    return c["trace_s"] + c["lower_s"]
