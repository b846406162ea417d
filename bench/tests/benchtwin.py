"""Small twins of the benchmark's cells, for CPU tests of the harness.

A twin keeps its cell's configuration, traffic, limits and control, on
the small fabric its configuration file names under ``twin`` (and with
the traffic parameters its mix names there); the harness reads it from a
checkout-like directory, as it reads the real cells.
"""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def make(tmp: Path, cells: list) -> Path:
    """Write twins of ``cells`` under ``tmp``; returns ``tmp``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for sub in ("configs", "traffic", "cells"):
        (tmp / "bench" / sub).mkdir(parents=True, exist_ok=True)
    workloads, configs = [], []
    for name in cells:
        w = next(x for x in bench["workloads"] if x["name"] == name)
        c = next(x for x in bench["configs"] if x["name"] == w["config"])
        config = json.loads((ROOT / c["file"]).read_text())
        mix = json.loads((ROOT / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        config["topology"].update(config["twin"]["topology"])
        mix["params"].update(mix.get("twin", {}))
        (tmp / "bench" / "configs" / f"{w['config']}.json").write_text(
            json.dumps(config))
        (tmp / "bench" / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(mix))
        shutil.copy(ROOT / "bench" / "cells" / f"{name}.json",
                    tmp / "bench" / "cells" / f"{name}.json")
        workloads.append(w)
        configs.append({**c, "file": f"bench/configs/{w['config']}.json"})
    bench.update(workloads=workloads,
                 configs=list({c["name"]: c for c in configs}.values()))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
