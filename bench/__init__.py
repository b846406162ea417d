"""Chip benchmark of the fabric simulator (``python3 bench/run.py``).

Everything a cell needs is found by name from ``BENCHMARK.json``:
``configs/<config>.json`` (the deployment), ``traffic/<mix>.json`` (the
generator's parameters), ``cells/<cell>.json`` (the correctness limits
and the control) and ``metrics/<metric>.py`` (one reader per per-layer
metric).  ``reference/`` is the plain event-driven reference that
decides ``correct``.
"""
