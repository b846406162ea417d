"""Each cell's control comes out not correct, and its sound program
correct, against the cell's own limits, on a small twin of the cell
(``benchtwin``) on the CPU.  ``bench/calibrate.py`` makes the same
readings on the chip at the cell's own size."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(Path(__file__).resolve().parent), str(ROOT / "src"),
                str(ROOT)]

import benchtwin  # noqa: E402
from bench import calibrate, compare, spec as spec_mod  # noqa: E402

CELLS = [w["name"] for w in spec_mod.Spec(ROOT).bench["workloads"]
         if w["chips"] == 1]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes(name, tmp_path, monkeypatch):
    from bench import run as harness
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: "")
    root = benchtwin.make(tmp_path, [name])
    got = calibrate.main(["--workload", name, "--seeds", "11-13",
                          "--control-seeds", "21-23"],
                         root=root, require_tpu=False)
    limits = spec_mod.Spec(root).cell(name)["limits"]
    sound_ok, sound = compare.judge(got["sound"], limits)
    control_ok, control = compare.judge(got["control"], limits)
    assert sound_ok, sound
    assert not control_ok, control
