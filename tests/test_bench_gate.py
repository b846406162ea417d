"""Unit tests for the `make bench` parity gate: the BENCH_fabric.json
schema checker must flag parity failures, malformed reports and warp
throughput regressions with a non-zero exit, not bury them in a report
nobody reads."""
import copy
import json

import pytest

from benchmarks.perf import (check_report_file, regression_problems,
                             validate_report)

GOOD = {
    "meta": {"utc": "2026-07-31T00:00:00Z", "jax": "0.9.0",
             "backend": "tpu", "platform": "Linux",
             "device_platform": "tpu", "device_kind": "TPU v5 lite",
             "device_count": 1},
    "scenarios": {
        "perm1024": {
            "n_ticks": 9000, "n_hosts": 1024, "n_msgs": 1024,
            "dense": {"cold_s": 10.0, "run_s": 8.0, "compile_s": 2.0,
                      "ticks_per_s": 1125.0, "program_builds": 1},
            "warp": {"cold_s": 3.0, "run_s": 0.5, "compile_s": 2.5,
                     "ticks_per_s": 18000.0, "warp_trips": 1234,
                     "program_builds": 1},
            "speedup": 16.0, "parity_ok": True, "unfinished": 0,
            "max_fct_us": 700.5, "program_builds_total": 2,
            "kernels": {
                "pallas_interpret": {
                    "cold_s": 3.2, "run_s": 0.55, "compile_s": 2.65,
                    "ticks_per_s": 16363.6, "warp_trips": 1234,
                    "program_builds": 1, "parity_exact": True},
            },
        },
        "perm8k": {
            "n_ticks": 4452, "n_hosts": 8192, "n_msgs": 8192,
            "warp": {"cold_s": 27.0, "run_s": 20.0, "compile_s": 7.0,
                     "ticks_per_s": 216.0, "warp_trips": 113,
                     "program_builds": 1},
            "warp_only": True, "parity_ok": True, "unfinished": 0,
            "max_fct_us": 11.06, "program_builds_total": 1,
            "parity_spotcheck": {"n_hosts": 16, "n_msgs": 16,
                                 "fabric_us": 9.99, "events_us": 9.88,
                                 "ratio": 1.011, "ok": True},
        },
    },
    "scale_axis": [
        {"n_hosts": 64, "n_ticks": 4452, "kernel_backend": "jnp",
         "ticks_per_s": 9000.0, "compile_s": 5.0, "program_builds": 1,
         "warp_trips": 100},
        {"n_hosts": 64, "n_ticks": 4452,
         "kernel_backend": "pallas_interpret", "ticks_per_s": 8800.0,
         "compile_s": 5.1, "program_builds": 1, "warp_trips": 100},
        {"n_hosts": 8192, "n_ticks": 4452, "kernel_backend": "jnp",
         "ticks_per_s": 216.0, "compile_s": 7.0, "program_builds": 1,
         "warp_trips": 113},
    ],
}


def test_valid_report_passes():
    assert validate_report(GOOD) == []


def test_scale_axis_is_optional():
    old_style = copy.deepcopy(GOOD)
    del old_style["scale_axis"]
    assert validate_report(old_style) == []


def test_parity_failure_is_flagged():
    bad = copy.deepcopy(GOOD)
    bad["scenarios"]["perm1024"]["parity_ok"] = False
    problems = validate_report(bad)
    assert any("parity_ok is FALSE" in p for p in problems)


def test_warp_only_rows_skip_dense_requirements():
    # perm8k has no dense leg or speedup and must still validate (above),
    # but a NON-warp_only row without them must be flagged
    bad = copy.deepcopy(GOOD)
    bad["scenarios"]["perm8k"]["warp_only"] = False
    problems = validate_report(bad)
    assert any("missing key 'dense'" in p for p in problems)
    assert any("missing key 'speedup'" in p for p in problems)


def test_schema_violations_are_flagged():
    # missing scenario key
    bad = copy.deepcopy(GOOD)
    del bad["scenarios"]["perm1024"]["speedup"]
    assert any("missing key 'speedup'" in p for p in validate_report(bad))
    # missing scenario-level program_builds_total (the whole-scenario
    # build-count diagnostic)
    bad = copy.deepcopy(GOOD)
    del bad["scenarios"]["perm1024"]["program_builds_total"]
    assert any("missing key 'program_builds_total'" in p
               for p in validate_report(bad))
    # missing per-mode program_builds (what the retrace-regression hook
    # actually reads — distinct from the scenario-level total)
    bad = copy.deepcopy(GOOD)
    del bad["scenarios"]["perm1024"]["warp"]["program_builds"]
    assert any("warp: missing key 'program_builds'" in p
               for p in validate_report(bad))
    # wrong type
    bad = copy.deepcopy(GOOD)
    bad["scenarios"]["perm1024"]["n_ticks"] = "9000"
    assert any("n_ticks" in p for p in validate_report(bad))
    # every report names the device it ran on
    for k in ("device_platform", "device_kind", "device_count"):
        bad = copy.deepcopy(GOOD)
        del bad["meta"][k]
        assert any(f"meta: missing key {k!r}" in p
                   for p in validate_report(bad))
    bad = copy.deepcopy(GOOD)
    bad["meta"]["device_count"] = "1"
    assert any("meta.device_count" in p for p in validate_report(bad))
    # malformed scale-axis point
    bad = copy.deepcopy(GOOD)
    del bad["scale_axis"][0]["compile_s"]
    assert any("scale_axis[0]" in p for p in validate_report(bad))
    # scale-axis points must carry their kernel_backend tag
    bad = copy.deepcopy(GOOD)
    del bad["scale_axis"][1]["kernel_backend"]
    assert any("scale_axis[1]: missing key 'kernel_backend'" in p
               for p in validate_report(bad))
    bad = copy.deepcopy(GOOD)
    bad["scale_axis"] = []
    assert any("scale_axis" in p for p in validate_report(bad))
    # empty scenarios
    assert any("scenarios" in p
               for p in validate_report({"meta": GOOD["meta"],
                                         "scenarios": {}}))
    # not even a dict
    assert validate_report([1, 2, 3])


def test_kernel_rows_are_validated():
    """The kernels axis: optional, but present rows must be well-formed
    and bit-exact — parity_exact=False is a gate failure by itself."""
    # the fixture's kernels row validates (test_valid_report_passes), and
    # a jnp-only report without one still validates
    no_kernels = copy.deepcopy(GOOD)
    del no_kernels["scenarios"]["perm1024"]["kernels"]
    assert validate_report(no_kernels) == []
    # parity_exact=False fires the gate naming backend and scenario
    bad = copy.deepcopy(GOOD)
    bad["scenarios"]["perm1024"]["kernels"]["pallas_interpret"][
        "parity_exact"] = False
    problems = validate_report(bad)
    assert any("parity_exact is FALSE" in p
               and "perm1024.kernels.pallas_interpret" in p
               for p in problems)
    # missing timing / parity keys inside a kernel row are flagged
    bad = copy.deepcopy(GOOD)
    del bad["scenarios"]["perm1024"]["kernels"]["pallas_interpret"][
        "parity_exact"]
    assert any("kernels.pallas_interpret: missing key 'parity_exact'" in p
               for p in validate_report(bad))
    # an empty kernels object is malformed, not silently fine
    bad = copy.deepcopy(GOOD)
    bad["scenarios"]["perm1024"]["kernels"] = {}
    assert any("kernels" in p for p in validate_report(bad))


def test_regression_gate_ignores_kernel_rows():
    """The throughput gate reads scenarios.<name>.warp.ticks_per_s only;
    a kernel-backend slowdown (or a removed kernels row) never fires it."""
    new = copy.deepcopy(GOOD)
    new["scenarios"]["perm1024"]["kernels"]["pallas_interpret"][
        "ticks_per_s"] = 1.0
    assert regression_problems(new, GOOD) == []
    del new["scenarios"]["perm1024"]["kernels"]
    assert regression_problems(new, GOOD) == []


def test_regression_gate():
    new = copy.deepcopy(GOOD)
    # identical reports: no problems
    assert regression_problems(new, GOOD) == []
    # 10% drop: inside the 20% tolerance
    new["scenarios"]["perm1024"]["warp"]["ticks_per_s"] = 16200.0
    assert regression_problems(new, GOOD) == []
    # 50% drop: gate fires, message names the scenario
    new["scenarios"]["perm1024"]["warp"]["ticks_per_s"] = 9000.0
    problems = regression_problems(new, GOOD)
    assert len(problems) == 1 and "perm1024" in problems[0]
    # scenarios only on one side are skipped; absent baseline is a pass
    del new["scenarios"]["perm1024"]
    assert regression_problems(new, GOOD) == []
    assert regression_problems(GOOD, None) == []


def test_check_report_file_exit_codes(tmp_path):
    good = tmp_path / "good.json"
    good.write_text(json.dumps(GOOD))
    assert check_report_file(str(good)) == 0

    bad_dict = copy.deepcopy(GOOD)
    bad_dict["scenarios"]["perm1024"]["parity_ok"] = False
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(bad_dict))
    assert check_report_file(str(bad)) == 1

    broken = tmp_path / "broken.json"
    broken.write_text("{not json")
    assert check_report_file(str(broken)) == 2
    assert check_report_file(str(tmp_path / "absent.json")) == 2


def _patch_runners(monkeypatch, parity_ok=True):
    import benchmarks.perf as perf

    def fake_bench_scenario(name, sc, cfg_kw, repeats=2,
                            kernel_backends=()):
        row = copy.deepcopy(GOOD["scenarios"]["perm1024"])
        row["parity_ok"] = parity_ok
        return row

    monkeypatch.setattr(perf, "bench_scenario", fake_bench_scenario)
    monkeypatch.setattr(perf, "canonical_scenarios",
                        lambda: {"fake": (None, {})})
    monkeypatch.setattr(perf, "scale_scenarios", lambda: {})
    monkeypatch.setattr(perf, "bench_scale_axis",
                        lambda repeats=1, kernel_backends=():
                        copy.deepcopy(GOOD["scale_axis"]))
    return perf


def test_bench_all_exits_nonzero_on_parity_failure(monkeypatch, tmp_path):
    """bench_all must sys.exit(1) — not merely log — when a scenario's
    dense/warp parity gate fails."""
    perf = _patch_runners(monkeypatch, parity_ok=False)
    out = tmp_path / "BENCH_fabric.json"
    hist = tmp_path / "BENCH_history.jsonl"     # NOT the repo's trend file
    with pytest.raises(SystemExit) as exc:
        perf.bench_all(str(out), repeats=1, history_path=str(hist))
    assert exc.value.code == 1
    # the report is still written for post-mortem, then the gate fires
    assert json.loads(out.read_text())["scenarios"]["fake"]["parity_ok"] \
        is False


def test_bench_all_exits_nonzero_on_throughput_regression(monkeypatch,
                                                          tmp_path):
    """bench_all reads the committed report before overwriting and fails
    on a >20% warp ticks/sec drop at any shared scenario."""
    perf = _patch_runners(monkeypatch, parity_ok=True)
    out = tmp_path / "BENCH_fabric.json"
    hist = tmp_path / "BENCH_history.jsonl"     # NOT the repo's trend file
    baseline = {"scenarios": {"fake": {
        "warp": {"ticks_per_s":
                 GOOD["scenarios"]["perm1024"]["warp"]["ticks_per_s"]
                 * 10.0}}}}
    out.write_text(json.dumps(baseline))
    with pytest.raises(SystemExit) as exc:
        perf.bench_all(str(out), repeats=1, history_path=str(hist))
    assert exc.value.code == 1
    # a matching baseline passes (fresh report replaces it)
    out.write_text(json.dumps({"scenarios": {"fake": {
        "warp": {"ticks_per_s":
                 GOOD["scenarios"]["perm1024"]["warp"]["ticks_per_s"]}}}}))
    report = perf.bench_all(str(out), repeats=1, history_path=str(hist))
    assert report["scenarios"]["fake"]["parity_ok"] is True
