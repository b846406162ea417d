"""Warp trips (iterations of the time-warped ``while_loop``) per answer,
the mean over the window's answers: the program's ``warp_trips``."""


def read(run):
    answers = run["answers"]
    return sum(a["trips"] for a in answers) / len(answers)
