"""Observatory: zero-dependency fabric telemetry.

The soak driver (``repro.sim.traffic.soak``) folds each epoch's fabric
counters — queue depth max/p99, PFC pauses, ECN marks, drops,
retransmits, per-tenant FCT percentiles — into a
:class:`~repro.obs.metrics.MetricsRegistry`, renders it in Prometheus
text exposition format, and dumps it to a ``.prom`` file that
``repro.obs.exporter`` can serve over HTTP with nothing but the stdlib.
``repro.obs.trend`` keeps the cross-PR benchmark trajectory
(``BENCH_history.jsonl``) and gates regressions against the best run in
history, not just the last one.

Everything above is pure stdlib: no prometheus_client, no jax.
``repro.obs.spans`` (imported on its own, it needs jax) holds the
program's own host spans and compile counters: the fabric program's
time, not the simulated network's.
"""
from .metrics import (MetricsRegistry, parse_prometheus,  # noqa: F401
                      render_prometheus)
from .trend import append_run, gate_and_append, load_history, \
    trend_problems  # noqa: F401
