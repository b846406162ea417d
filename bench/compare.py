"""Decides ``correct``: each answer of the window against the plain reference.

An answer is one scenario's summary as ``run()`` returns it.  The
reference (``reference/``) runs the same flows on the same deployment in
another process, after the window has closed, and the numbers below
compare the two.  Each cell's file (``cells/<cell>.json``) names the
numbers it compares and the limit of each; the worst answer of a run
decides.
"""
from __future__ import annotations

import math
import multiprocessing
import os

import numpy as np

#: Completion-time statistics compared, each over the answer's messages.
STATS = ("mean", "p50", "p99", "max")


def fct_stats(fcts) -> dict:
    done = np.asarray([f for f in fcts if f is not None], dtype=np.float64)
    if not done.size:
        return {s: math.nan for s in STATS}
    return {"mean": float(done.mean()),
            "p50": float(np.percentile(done, 50)),
            "p99": float(np.percentile(done, 99)),
            "max": float(done.max())}


def program_reading(summary: dict) -> dict:
    """The program's answer, read from the summary ``run()`` returns."""
    tenants = summary["tenant_fct"]
    if len(tenants) != 1:
        raise ValueError(f"expected one message group, got {len(tenants)}")
    (row,) = tenants.values()
    return {"count": row["count"], "unfinished": summary["unfinished"],
            "drops": summary["drops"],
            "mean": summary["avg_fct"], "p50": row["p50"], "p99": row["p99"],
            "max": summary["max_fct"]}


def reference_reading(ref: dict) -> dict:
    fcts = ref["fct_us"]
    return {"count": len(fcts), "unfinished": sum(f is None for f in fcts),
            "drops": ref["drops"],
            **fct_stats(fcts)}


def log_gap(a: float, b: float) -> float:
    """|ln(a / b)|, infinite where either side is missing or not positive."""
    if not (a > 0 and b > 0):
        return math.inf
    return abs(math.log(a / b))


def numbers(prog: dict, ref: dict, n_msgs: int) -> dict:
    """The numbers one answer is judged by.

    ``missing``: messages the answer does not account for;
    ``unfinished``: messages it left incomplete; ``drops``: how far its
    count of dropped packets lies from the reference's; ``fct_gap``: the
    widest |ln(program / reference)| over the completion-time statistics.
    """
    return {
        "missing": abs(n_msgs - prog["count"]),
        "unfinished": prog["unfinished"],
        "drops": abs(prog["drops"] - ref["drops"]),
        "fct_gap": max(log_gap(prog[s], ref[s]) for s in STATS),
    }


def worst(per_answer: list) -> dict:
    return {k: max(n[k] for n in per_answer) for k in per_answer[0]}


def judge(readings: dict, limits: dict) -> tuple:
    """``(correct, checks)``: each limited number beside its limit."""
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in limits.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def _reference_job(config: dict, flows: list, seed: int,
                   sender: str | None) -> dict:
    from bench import controls
    from bench.reference import STrackSender, simulate
    cls = controls.SENDERS[sender] if sender else STrackSender
    return simulate(config, flows, seed, cls)


def run_references(config: dict, jobs: list, sender: str | None = None,
                   workers: int | None = None) -> list:
    """The reference's result for each ``(flows, seed)`` of ``jobs``, in
    parallel processes that import nothing of the program or of JAX."""
    n = workers or max(1, min(len(jobs), (os.cpu_count() or 2) - 1))
    ctx = multiprocessing.get_context("spawn")
    pool = ctx.Pool(n)
    try:
        return pool.starmap(_reference_job,
                            [(config, f, s, sender) for f, s in jobs])
    finally:
        pool.close()
        pool.join()
