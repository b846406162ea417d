"""Seconds the first ``run()`` spends getting its program (compile, or
load from the persistent cache): the warm-up answer's wall minus what
its warp trips take at the window's seconds per trip."""


def read(run):
    answers = run["answers"]
    s_per_trip = (sum(a["wall_s"] for a in answers)
                  / sum(a["trips"] for a in answers))
    return run["cold_s"] - run["warm_trips"] * s_per_trip
