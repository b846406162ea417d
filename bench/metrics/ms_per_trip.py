"""Host-clock milliseconds per warp trip: the window's answers' wall over
their trips (taken before any profiler starts)."""


def read(run):
    answers = run["answers"]
    return (1e3 * sum(a["wall_s"] for a in answers)
            / sum(a["trips"] for a in answers))
