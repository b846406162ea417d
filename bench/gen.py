"""The benchmark's one traffic generator: a mix's parameters and a seed to
flows.

A traffic mix is a data file, ``traffic/<mix>.json``: ``pattern`` names
a module ``patterns/<pattern>.py`` and ``params`` are the keyword
arguments of its ``flows(n_hosts, seed, **params)``.  A new mix of a
known pattern is a new data file; a new pattern is a new module, found
by name.  Flows are ``(src, dst, bytes)`` tuples, all released at t=0;
every seed of one mix gives the same number of flows of the same sizes
to the same shape of fabric, so one compiled program serves them all.
"""
from __future__ import annotations

import hashlib
import importlib.util
from pathlib import Path

PATTERNS = Path(__file__).resolve().parent / "patterns"


def answer_seed(seed: int, i: int) -> int:
    """The seed of the ``i``-th answer of a run started with ``seed``
    (``i = -1`` is the warm-up): 63 bits of SHA-256 of both."""
    digest = hashlib.sha256(f"{seed}:{i}".encode()).digest()
    return int.from_bytes(digest[:8], "big") >> 1


def pattern(name: str):
    """The ``flows`` function of ``patterns/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_pattern_{name.replace('.', '_').replace('-', '_')}",
        PATTERNS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.flows


def flows(mix: dict, n_hosts: int, seed: int) -> list:
    """The flows of one answer of ``mix`` on a fabric of ``n_hosts``."""
    return pattern(mix["pattern"])(n_hosts, seed, **mix["params"])
