"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload strack8k.perm64k --seed 7 --seconds 30 \
        --trace 0

Set-up builds the cell's deployment (``configs/``) and compiles or
loads the fabric program with one short answer of
``repro.sim.workloads.run``: the window's program (same flows, same tick
horizon) on messages of one packet.  The window then asks for answers, each on a
fresh scenario from the generator (``traffic/``) under a seed derived
from ``--seed`` and its index, until ``--seconds`` have passed; it ends
on a whole answer.  Every scenario of a cell has the same shape, so the
window compiles nothing (``fabric.program_builds`` is checked).  After
the window every answer is compared with the plain reference
(``reference/``), in parallel processes off the chip.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
reports its per-layer metrics, from the same window plus a profiler
capture of a short slice of further answers (``trace_slice_s`` of the
cell's file).  The last line of standard output is one JSON object; the
numbers compared with the reference come last there, and as the last
lines of standard error.  The run refuses a
host without a TPU, or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """The host has no TPU, or fewer chips than the cell asks for."""


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache, at a fixed path in the
    checkout, for every program however quickly it compiles."""
    import jax
    path = root / "bench" / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


def devices(chips: int, require_tpu: bool) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs


class Cell:
    """One cell: its deployment, traffic, limits and the program's config.

    The configuration file's ``topology``, ``network`` and ``run_config``
    go to ``FatTree``, ``NetworkSpec`` and ``RunConfig`` as they are."""

    def __init__(self, spec, name: str):
        from repro.core.params import NetworkSpec
        from repro.sim.topology import FatTree
        self.name = name
        self.workload = spec.workload(name)
        self.config = spec.config(self.workload["config"])
        self.mix = spec.traffic(self.workload["traffic"])
        self.cell = spec.cell(name)
        c = self.config
        if int(c["chips"]) != int(self.workload["chips"]):
            raise ValueError(f"{name}: config chips {c['chips']} != "
                             f"workload chips {self.workload['chips']}")
        self.topo = FatTree(**c["topology"])
        self.net = NetworkSpec(**c["network"])

    def run_config(self, n_ticks: int, **override):
        """The program's config for this cell, its horizon pinned to
        ``n_ticks`` (what ``run()`` would pick for the cell's scenarios)."""
        from repro.sim.workloads import RunConfig
        return RunConfig(backend="fabric", n_ticks=n_ticks,
                         **{**self.config["run_config"], **override})

    def flows(self, seed: int) -> list:
        from bench import gen
        return gen.flows(self.mix, self.topo.n_hosts, seed)

    def scenario(self, flows: list):
        from repro.sim.workloads import Scenario
        return Scenario.from_flows(self.name, self.topo, self.net, flows)

    def horizon(self, seed: int) -> int:
        """The tick horizon ``run()`` picks for the cell's scenarios (the
        same for every seed of a mix)."""
        return self.scenario(self.flows(seed)).default_ticks()

    def warm_up(self, cfg, seed: int) -> dict:
        """Set-up: one answer with the program the window runs, on the
        seed's flows cut to one packet each, so the program is compiled or
        loaded with few warp trips."""
        from repro.sim.workloads import run
        flows = [(s, d, float(self.net.mtu_bytes))
                 for s, d, _ in self.flows(seed)]
        return {"trips": int(run(self.scenario(flows), cfg)["warp_trips"])}


def answer(cell: Cell, cfg, seed: int, spans: list | None = None) -> dict:
    """One whole answer: scenario build, ``run()``, its host-clock wall.

    ``spans`` collects ``(start, end, name)`` of both steps on the host's
    ``perf_counter`` clock."""
    from repro.sim.workloads import run
    t0 = time.perf_counter()
    flows = cell.flows(seed)
    sc = cell.scenario(flows)
    t1 = time.perf_counter()
    summary = run(sc, cfg)
    t2 = time.perf_counter()
    if spans is not None:
        spans += [(t0, t1, "bench.scenario"), (t1, t2, "bench.run")]
    return {"seed": seed, "flows": flows, "summary": summary,
            "wall_s": t2 - t0, "trips": int(summary["warp_trips"]),
            "span_us": float(summary["max_fct"])}


def window(cell: Cell, cfg, seed: int, seconds: float) -> tuple:
    """Answers on fresh scenarios until ``seconds`` have passed."""
    from bench import gen
    answers, i = [], 0
    t0 = time.perf_counter()
    while True:
        answers.append(answer(cell, cfg, gen.answer_seed(seed, i)))
        i += 1
        if time.perf_counter() - t0 >= seconds:
            return answers, time.perf_counter() - t0


def traced_slice(cell: Cell, cfg, seed: int, mean_wall_s: float,
                 trace_dir: Path) -> tuple:
    """Profile ``trace_slice_s`` seconds of further answers, centred on
    the end of the first one so the slice holds an answer boundary.  A
    whole 8K-host answer overflows the profiler's device buffer, and the
    trace's size grows with the slice and the chips.

    Returns the trace file and the host spans (``bench.slice`` among
    them) on the ``perf_counter`` clock.  The spans are recorded here and
    not as profiler annotations: one that starts before the capture does
    not reach the trace."""
    import jax
    from jax.profiler import TraceAnnotation
    from bench import gen
    slice_s = float(cell.cell["trace_slice_s"])
    stop = threading.Event()
    started = threading.Event()
    spans, errors = [], []

    def answers():
        try:
            i = 0
            while not stop.is_set():
                if i == 0:
                    started.set()
                answer(cell, cfg, gen.answer_seed(seed, 10_000 + i), spans)
                i += 1
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)
            started.set()

    shutil.rmtree(trace_dir, ignore_errors=True)
    worker = threading.Thread(target=answers, name="bench-answers")
    worker.start()
    try:
        started.wait()
        time.sleep(max(0.0, mean_wall_s - slice_s / 2))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only, no Python calls
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        try:
            with TraceAnnotation("bench.slice"):
                t0 = time.perf_counter()
                time.sleep(slice_s)
                t1 = time.perf_counter()
        finally:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            print(f"bench: profiler stop took "
                  f"{time.perf_counter() - t_stop} s", file=sys.stderr)
    finally:
        stop.set()
        worker.join()
    if errors:
        raise errors[0]
    path = max(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return path, spans + [(t0, t1, "bench.slice")]


def memory_peak(devs: list) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def check_answers(cell: Cell, answers: list) -> tuple:
    """``(correct, failed, checks, notes)`` against the reference.

    Each answer also gets ``ref_span_us``, the reference's last
    completion: the simulated work the answer stands for."""
    from bench import compare
    jobs = [(a["flows"], a["seed"]) for a in answers]
    refs = compare.run_references(cell.config, jobs)
    per, failed, notes = [], 0, []
    limits = cell.cell["limits"]
    for a, r in zip(answers, refs):
        ref = compare.reference_reading(r)
        a["ref_span_us"] = ref["max"]
        if ref["unfinished"]:
            notes.append(f"reference left {ref['unfinished']} message(s) "
                         f"of seed {a['seed']} unfinished")
        nums = compare.numbers(compare.program_reading(a["summary"]), ref,
                               len(a["flows"]))
        per.append(nums)
        failed += not compare.judge(nums, limits)[0]
    correct, checks = compare.judge(compare.worst(per), limits)
    return correct and not notes, failed, checks, notes


def run_cell(args, root: Path, require_tpu: bool = True) -> tuple:
    """``(result, notes on what was not correct, answers)`` of one run."""
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import gen, spec as spec_mod
    from repro.sim import fabric

    spec = spec_mod.Spec(root)
    workload = spec.workload(args.workload)
    devs = devices(int(workload["chips"]), require_tpu)
    use_compile_cache(root)
    cell = Cell(spec, args.workload)
    cfg = cell.run_config(cell.horizon(gen.answer_seed(args.seed, -1)))

    # set-up: a short answer compiles or loads the window's program
    t0 = time.perf_counter()
    warm = cell.warm_up(cfg, gen.answer_seed(args.seed, -1))
    cold_s = time.perf_counter() - t0
    setup_s = time.perf_counter() - T0

    builds = fabric.program_builds
    answers, window_s = window(cell, cfg, args.seed, args.seconds)
    builds_in_window = fabric.program_builds - builds
    run = {"cold_s": cold_s, "warm_trips": warm["trips"],
           "window_s": window_s, "answers": answers, "trace": None,
           "setup_s": setup_s}
    if args.trace:
        from bench import tracing
        trace_dir = root / "bench" / ".traces" / args.workload
        mean_wall = window_s / len(answers)
        path, spans = traced_slice(cell, cfg, args.seed, mean_wall,
                                   trace_dir)
        t0 = time.perf_counter()
        run["trace"] = tracing.reduce(path, spans)
        print(f"bench: trace of {path.stat().st_size} bytes reduced in "
              f"{time.perf_counter() - t0} s", file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)
    peak = memory_peak(devs[:int(workload["chips"])])

    correct, failed, checks, notes = check_answers(cell, answers)
    if builds_in_window:
        notes.append(f"{builds_in_window} program build(s) in the window")
        correct = False

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in spec.metrics(kind, args.workload):
        value = spec_mod.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(answers),
           "failed": failed, "metrics": metrics, "device": device}
    if run["trace"] is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        out["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                            "idle_gaps": run["trace"]["idle_gaps"]}
    out["checks"] = checks
    return out, notes, answers


def main(argv=None, root: Path = ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out, notes, answers = run_cell(args, Path(root), require_tpu)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for a in answers:
        print(f"bench: answer seed={a['seed']} wall_s={a['wall_s']} "
              f"trips={a['trips']} span_us={a['span_us']} "
              f"ref_span_us={a.get('ref_span_us')}", file=sys.stderr)
    print(f"bench: {time.perf_counter() - T0} s since start",
          file=sys.stderr)
    for n in notes:
        print(f"bench: not correct: {n}", file=sys.stderr)
    for name, c in out["checks"].items():
        value = c["value"]
        if isinstance(value, float) and not math.isfinite(value):
            c["value"] = str(value)
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
