# Developer / CI entry points.  PYTHONPATH is prepended, not replaced.
PY      := python
PP      := PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: tier1 test test-fast fabric-smoke collective-smoke bench-smoke \
	chaos-smoke scale-smoke smoke bench benchmarks update-golden profile \
	soak soak-smoke serve-metrics

# The tier-1 gate (same command as ROADMAP.md).
tier1:
	$(PP) $(PY) -m pytest -x -q

# Full suite: everything, fuzz at its full example count (pytest.ini
# registers the tier1 / slow / fuzz markers).
test:
	$(PP) $(PY) -m pytest -q

# Smoke-speed suite: slow-marked tests excluded and the differential fuzz
# suite reduced to 3 examples (full count under `make test` / tier1).
# The second pass re-runs the shard-marked tests under a FORCED 4-device
# host platform so multi-device shard_map parity never silently skips on
# single-device CI hosts (XLA_FLAGS must be set before jax imports, so it
# needs its own interpreter).  That pass is CPU-only by construction.
test-fast:
	$(PP) REPRO_FUZZ_EXAMPLES=3 $(PY) -m pytest -q -m "not slow"
	$(PP) REPRO_FUZZ_EXAMPLES=3 JAX_PLATFORMS=cpu \
	  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	  $(PY) -m pytest -q -m shard

# Regenerate tests/golden/*.json after an INTENTIONAL fidelity change;
# review the diff like code.
update-golden:
	$(PP) $(PY) -m pytest tests/test_golden.py --update-golden -q

# 2k-tick jitted fabric runs (STrack + RoCEv2-on-fabric canary): perf and
# baseline-port regressions on the lax.scan hot path fail fast here.
fabric-smoke:
	$(PP) $(PY) -m benchmarks.fabric_smoke 2000 all

# 2k-tick dependency-scheduled collective on the fabric (ring allreduce,
# strack + rocev2 + 4-QP striped rocev2): gating/striping regressions on
# the unified run(scenario, cfg) path fail fast here.
collective-smoke:
	$(PP) $(PY) -m benchmarks.collectives --backend fabric --smoke

# 2k-tick perf canary: warm time-warped fabric must beat a ticks/sec
# floor and agree exactly with dense ticking (see docs/performance.md).
bench-smoke:
	$(PP) $(PY) -m benchmarks.perf --smoke

# Chaos-path gates (benchmarks/oversub_linkdown.py --chaos-smoke):
# the degenerate t=0 flap schedule must reproduce native dead-link
# results bit-exactly, a mid-run flap must drain with recovery-counter
# activity, and a clean+flapped chaos soak must compile ONE program.
chaos-smoke:
	$(PP) $(PY) -m benchmarks.oversub_linkdown --chaos-smoke

# What CI should run on every change.
smoke: tier1 fabric-smoke collective-smoke bench-smoke chaos-smoke

# 512-host warp smoke point: a midsize permutation must clear a warm
# ticks/sec floor, catching at-scale scan regressions the 16-host
# bench-smoke canary can't see.
scale-smoke:
	$(PP) $(PY) -m benchmarks.perf --scale

# Perf trajectory: dense vs event-horizon wall-clock + ticks/sec on the
# canonical scenarios (1024-host permutation, chunked ring, incast-256),
# the warp-only 8K scenarios (perm8k, allreduce8k) and the n_hosts scale
# axis; writes BENCH_fabric.json.  Runs the 512-host scale smoke first,
# then exits non-zero when any scenario's parity gate fails, the JSON
# violates the schema, or warp ticks/sec regressed >20% against the
# previously committed report (benchmarks/perf.py validate_report /
# regression_problems; re-check with --check).
bench: scale-smoke
	$(PP) $(PY) -m benchmarks.perf --out BENCH_fabric.json

# Trace one warm warp scenario (perm1024) under jax.profiler.trace into
# traces/fabric: compile happens outside the trace, so the profile shows
# the scan body the Pallas kernels target.  View with
# `tensorboard --logdir traces/fabric`.  Override the scenario or the
# kernel backend via benchmarks.perf --profile* / --kernel-backends.
profile:
	$(PP) $(PY) -m benchmarks.perf --profile traces/fabric

# Full paper-figure benchmark sweep (slow).
benchmarks:
	$(PP) $(PY) -m benchmarks.run

# Observatory soak: 64-host mixed workload (2 training jobs + an
# inference burst tenant) for 10 warp epochs, counters carried across
# epochs; writes BENCH_soak.prom (Prometheus text exposition) and gates
# on drain, one-program reuse, exposition round-trip and the per-tenant
# FCT spot check vs the events oracle (benchmarks/soak.py, docs/
# observatory.md).
soak:
	$(PP) $(PY) -m benchmarks.soak --out BENCH_soak.prom

# CI-sized soak: small fleet, 3 epochs of 2000 ticks, same gates.
soak-smoke:
	$(PP) $(PY) -m benchmarks.soak --smoke --out BENCH_soak.prom

# Serve the soak's metrics file on http://127.0.0.1:9109/metrics
# (re-read per scrape, so a running soak shows up live).
serve-metrics:
	$(PP) $(PY) -m repro.obs.exporter --file BENCH_soak.prom --port 9109
