"""Readings that set a cell's correctness limits, and its control's.

    python3 bench/calibrate.py --workload strack8k.perm64k \
        --seeds 1000-1011 --control-seeds 2000-2002

Runs the cell's program as the timed path does, one answer per seed, and
its control (``cells/<cell>.json``: the program with one of its options
switched, or the reference with an engine swapped in), and compares each
with the plain reference.  Prints one JSON line per answer, then the
largest reading of sound runs and the smallest of the control for each
number.  It needs the chip, like ``run.py``; the benchmark's own runs do
not run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seed_range(text: str) -> list:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None, root: Path = ROOT, require_tpu: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_range, required=True)
    ap.add_argument("--control-seeds", type=seed_range, default=[])
    args = ap.parse_args(argv)
    root = Path(root)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import compare, gen, spec as spec_mod
    from bench import run as harness

    spec = spec_mod.Spec(root)
    chips = int(spec.workload(args.workload)["chips"])
    harness.devices(chips, require_tpu)
    harness.use_compile_cache(root)
    cell = harness.Cell(spec, args.workload)
    control = cell.cell["control"]
    n_ticks = cell.horizon(0)
    t0 = time.perf_counter()
    sound = [harness.answer(cell, cell.run_config(n_ticks),
                            gen.answer_seed(s, 0)) for s in args.seeds]
    print(f"calibrate: {len(sound)} sound answers in "
          f"{time.perf_counter() - t0} s", file=sys.stderr, flush=True)
    if "program" in control:
        ctl_cfg = cell.run_config(n_ticks, **control["program"])
        ctl = [harness.answer(cell, ctl_cfg, gen.answer_seed(s, 0))
               for s in args.control_seeds]
    else:
        ctl = [{"seed": gen.answer_seed(s, 0),
                "flows": cell.flows(gen.answer_seed(s, 0))}
               for s in args.control_seeds]
    jobs = [(a["flows"], a["seed"]) for a in sound + ctl]
    t0 = time.perf_counter()
    refs = compare.run_references(cell.config, jobs)
    print(f"calibrate: {len(jobs)} references in "
          f"{time.perf_counter() - t0} s", file=sys.stderr, flush=True)
    if "reference" in control:
        outs = compare.run_references(cell.config, jobs[len(sound):],
                                      sender=control["reference"])
        progs = [compare.reference_reading(o) for o in outs]
    else:
        progs = [compare.program_reading(a["summary"]) for a in ctl]
    progs = [compare.program_reading(a["summary"]) for a in sound] + progs
    rows = []
    for i, (a, prog, r) in enumerate(zip(sound + ctl, progs, refs)):
        ref = compare.reference_reading(r)
        row = {"kind": "sound" if i < len(sound) else "control",
               "seed": a["seed"], "wall_s": a.get("wall_s"),
               "trips": a.get("trips"), "program": prog, "reference": ref,
               "numbers": compare.numbers(prog, ref, len(a["flows"]))}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for kind, pick in (("sound", max), ("control", min)):
        got = [r["numbers"] for r in rows if r["kind"] == kind]
        if got:
            summary[kind] = {k: pick(n[k] for n in got) for k in got[0]}
    print(json.dumps({"workload": args.workload, **summary}), flush=True)
    return summary


if __name__ == "__main__":
    main()
