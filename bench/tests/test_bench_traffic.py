"""The benchmark's traffic generator: every mix names a pattern that loads
by name, one program shape for every seed of a cell's mix, and at seed 0
the same flows as the program's own generator."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import gen, spec as spec_mod  # noqa: E402
from repro.core.params import NetworkSpec  # noqa: E402
from repro.sim.topology import FatTree  # noqa: E402
from repro.sim.workloads import (Scenario, incast_scenario,  # noqa: E402
                                 permutation_pairs)

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
SEEDS = [0, 1, 2 ** 31 + 11, 2 ** 62 + 5, gen.answer_seed(7, 3)]


def _cell(name):
    spec = spec_mod.Spec(ROOT)
    w = spec.workload(name)
    return spec.config(w["config"]), spec.traffic(w["traffic"])


def _program_flows(pattern, topo, params, seed):
    """The program's own generator for each pattern the mixes use."""
    if pattern == "permutation":
        return [(s, d, float(params["msg_bytes"]))
                for s, d in permutation_pairs(topo.n_hosts, seed)]
    if pattern == "incast":
        return list(incast_scenario(topo, params["fan_in"],
                                    params["msg_bytes"], dst=params["dst"],
                                    seed=seed).flows)
    raise KeyError(pattern)


@pytest.mark.parametrize("mix", MIXES)
def test_mix_names_a_pattern_that_loads(mix):
    data = spec_mod.Spec(ROOT).traffic(mix)
    assert set(data) <= {"about", "pattern", "params", "twin"}
    assert (ROOT / "bench" / "patterns" / f"{data['pattern']}.py").is_file()
    assert callable(gen.pattern(data["pattern"]))
    assert set(data.get("twin", {})) <= set(data["params"])


@pytest.mark.parametrize("name", CELLS)
def test_one_program_shape_for_every_seed(name):
    config, mix = _cell(name)
    topo = FatTree(**config["topology"])
    net = NetworkSpec(**config["network"])
    shapes = set()
    for seed in SEEDS:
        flows = gen.flows(mix, topo.n_hosts, seed)
        assert all(s != d for s, d, _ in flows)
        sc = Scenario.from_flows(name, topo, net, flows)
        shapes.add((len(flows), tuple(sorted(b for _, _, b in flows)),
                    sc.default_ticks()))
    assert len(shapes) == 1, shapes


@pytest.mark.parametrize("mix", MIXES)
def test_seed_zero_matches_the_program_generator(mix):
    data = spec_mod.Spec(ROOT).traffic(mix)
    topo = FatTree(n_tor=128, hosts_per_tor=64, n_spine=64)
    for seed in (0, 5):
        assert gen.flows(data, topo.n_hosts, seed) == _program_flows(
            data["pattern"], topo, data["params"], seed)


def test_answer_seeds_differ_and_repeat():
    seeds = [gen.answer_seed(2 ** 33 + 1, i) for i in range(-1, 50)]
    assert len(set(seeds)) == len(seeds)
    assert seeds == [gen.answer_seed(2 ** 33 + 1, i) for i in range(-1, 50)]
    assert all(0 <= s < 2 ** 63 for s in seeds)
