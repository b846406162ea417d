"""Milliseconds of host work in each answer's ``run()``: the mean over
the window's answers of the program's ``fabric.run`` span less its
``fabric.device`` span (the wait for the scan), matched to the answers
by the ``answer`` id each summary carries (``repro.obs.spans``).  None
where the program records no such spans."""
RUN, DEVICE = "fabric.run", "fabric.device"


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    ids = [a["summary"].get("answer") for a in run["answers"]]
    if None in ids:
        return None
    seconds: dict = {}
    for s in spans.recent():
        if s.name in (RUN, DEVICE) and s.ids.get("answer") in ids:
            seconds.setdefault(s.ids["answer"], {})[s.name] = s.end - s.start
    if any(len(seconds.get(i, ())) != 2 for i in ids):
        return None
    return 1e3 * sum(seconds[i][RUN] - seconds[i][DEVICE]
                     for i in ids) / len(ids)
