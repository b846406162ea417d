"""Milliseconds of cross-chip collectives (all-gather, all-reduce, ...)
per warp trip: their share of the traced slice, averaged over the chips,
times the window's milliseconds per trip."""


def read(run):
    trace = run["trace"]
    if trace is None or not trace["collective_s"]:
        return None
    answers = run["answers"]
    ms_per_trip = (1e3 * sum(a["wall_s"] for a in answers)
                   / sum(a["trips"] for a in answers))
    return trace["collective_s"] / trace["window_s"] * ms_per_trip
