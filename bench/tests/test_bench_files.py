"""Every file BENCHMARK.json names loads by name, and the file keeps to
the benchmark's contract: keys, names, units, bounds and paths."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import spec as spec_mod  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_keys():
    entry_keys = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer",
                      "moves"},
    }
    for kind, keys in entry_keys.items():
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names)), kind
        for e in BENCH[kind]:
            assert set(e) - {"workloads"} == keys, e
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher"), e
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_move_a_reported_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_load_by_name(workload):
    spec = spec_mod.Spec(ROOT)
    w = spec.workload(workload)
    config = spec.config(w["config"])
    mix = spec.traffic(w["traffic"])
    cell = spec.cell(workload)
    assert int(config["chips"]) == w["chips"]
    assert int(config["run_config"]["shard"]) == (
        w["chips"] if w["chips"] > 1 else 0)
    assert (ROOT / "bench" / "patterns" / f"{mix['pattern']}.py").is_file()
    for exact in ("missing", "unfinished", "drops"):
        assert cell["limits"][exact] == 0
    assert set(cell["control"]) & {"program", "reference"}
    assert 0 < cell["trace_slice_s"] <= 1.0
    for kind in ("end_to_end", "per_layer"):
        assert spec.metrics(kind, workload)


@pytest.mark.parametrize("metric", [m["name"] for k in ("end_to_end",
                                                         "per_layer")
                                    for m in BENCH[k]])
def test_metric_reader_loads_by_name(metric):
    assert callable(spec_mod.reader(metric))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_live_under_paths(entry):
    path = ROOT / entry["file"]
    assert path.resolve().is_relative_to(ROOT / "bench")
    config = json.loads(path.read_text())
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_config_reaches_the_program_as_it_is(workload):
    """The configuration file's ``topology``, ``network`` and
    ``run_config`` become the program's objects key for key."""
    sys.path[:0] = [str(ROOT / "src")]
    from bench import run as harness
    cell = harness.Cell(spec_mod.Spec(ROOT), workload)
    cfg = cell.run_config(123)
    assert cfg.backend == "fabric" and cfg.n_ticks == 123
    for key, value in cell.config["run_config"].items():
        assert getattr(cfg, key) == value, key
    for key, value in cell.config["topology"].items():
        assert getattr(cell.topo, key) == value, key
    for key, value in cell.config["network"].items():
        assert getattr(cell.net, key) == value, key
    assert cell.run_config(1, lb_mode="fixed").lb_mode == "fixed"
