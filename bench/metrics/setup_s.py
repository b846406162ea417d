"""Seconds from process start to the first timed call: JAX start-up, the
program's compile or cache load, and one warm-up answer."""


def read(run):
    return run["setup_s"]
