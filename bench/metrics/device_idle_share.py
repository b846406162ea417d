"""Percent of the traced slice in which no operation ran on the device,
averaged over the chips used: 100 * (1 - busy / slice)."""


def read(run):
    trace = run["trace"]
    if trace is None or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
