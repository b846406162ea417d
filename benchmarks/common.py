"""Shared benchmark helpers: reduced-scale topologies + transport variants.

Every driver goes through the ONE experiment API
(``repro.sim.workloads.run``/``sweep``): a transport name from
``TRANSPORTS`` maps to a :class:`~repro.sim.workloads.RunConfig` via
``transport_config(tr, backend=...)``, so each figure is one
``run(scenario, cfg)`` call whichever backend/protocol/striping it needs.
"""
from __future__ import annotations

import os
import time
from pathlib import Path

from repro.core.params import NetworkSpec
from repro.sim.events import NetSim
from repro.sim.topology import FatTree
from repro.sim.workloads import RunConfig, run, sweep

# Reduced scale (container = 1 CPU core). Paper: 8192 hosts, <=100MB msgs.
QUICK_TOPO = dict(n_tor=4, hosts_per_tor=4)      # 16 hosts
FULL_TOPO = dict(n_tor=16, hosts_per_tor=16)     # 256 hosts
MSG_SIZES_QUICK = [4 * 2**10, 128 * 2**10, 512 * 2**10, 2 * 2**20]
MSG_SIZES_FULL = MSG_SIZES_QUICK + [8 * 2**20]

# Transport variant -> RunConfig fields.  ALL of these run on the jitted
# fabric now, including the 4-QP striped RoCEv2 ("roce4", previously the
# last event-backend benchmark leg).
TRANSPORT_CFG = {
    "strack": dict(protocol="strack", lb_mode="adaptive"),
    "strack-obl": dict(protocol="strack", lb_mode="oblivious"),
    "strack-fixed": dict(protocol="strack", lb_mode="fixed"),
    "roce": dict(protocol="rocev2"),
    "roce4": dict(protocol="rocev2", subflows=4),
}

TRANSPORTS = ["strack", "strack-obl", "roce", "roce4"]
FABRIC_TRANSPORTS = list(TRANSPORT_CFG)

#: Home of JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR
#: is unset: one fixed directory inside the checkout, so a later run of any
#: entry point finds what an earlier one compiled.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX places the cache there
    itself and nothing is overridden; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Call before the first compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir


def transport_config(transport: str, backend: str = "fabric",
                     **overrides) -> RunConfig:
    """RunConfig for one named transport variant on one backend."""
    if transport not in TRANSPORT_CFG:
        raise ValueError(f"unknown transport {transport!r}; expected one "
                         f"of {sorted(TRANSPORT_CFG)}")
    return RunConfig(backend=backend, **{**TRANSPORT_CFG[transport],
                                         **overrides})


def run_transport(transport: str, scenario, backend: str = "fabric",
                  **overrides) -> dict:
    """``run(scenario, cfg)`` for one named transport variant."""
    return run(scenario, transport_config(transport, backend, **overrides))


def sweep_transport(transport: str, scenarios, backend: str = "fabric",
                    **overrides) -> list:
    """``sweep(scenarios, cfg)`` for one named transport variant (fabric:
    one vmapped jit over the batch)."""
    return sweep(scenarios, transport_config(transport, backend,
                                             **overrides))


# Back-compat spellings (pre-RunConfig helpers).
def run_fabric_transport(transport: str, scenario, n_ticks=None,
                         trace_queues: bool = False) -> dict:
    return run_transport(transport, scenario, backend="fabric",
                         n_ticks=n_ticks, trace_queues=trace_queues)


def sweep_fabric_transport(transport: str, scenarios, n_ticks=None,
                           trace_queues: bool = False) -> list:
    return sweep_transport(transport, scenarios, backend="fabric",
                           n_ticks=n_ticks, trace_queues=trace_queues)


def run_events_transport(transport: str, scenario, until: float = 1e6,
                         seed: int = 0, log_queues: bool = False):
    """Run any TRANSPORTS variant on the event oracle; returns (result, sim)
    so callers can read queue-delay logs off the sim."""
    from repro.sim.workloads import run_scenario_on_sim
    sim = make_sim(transport, scenario.topo, scenario.net, seed=seed,
                   log_queues=log_queues)
    return run_scenario_on_sim(sim, scenario, until=until), sim


def make_sim(transport: str, topo: FatTree, net: NetworkSpec, **kw) -> NetSim:
    """Prebuilt NetSim for a named transport (queue-logging drivers)."""
    if transport == "strack":
        return NetSim(topo, net, transport="strack", **kw)
    if transport == "strack-obl":
        return NetSim(topo, net, transport="strack", oblivious_spray=True,
                      **kw)
    if transport == "roce":
        return NetSim(topo, net, transport="roce", **kw)
    if transport == "roce4":
        from repro.core.params import make_roce_params
        return NetSim(topo, net, transport="roce",
                      roce_params=make_roce_params(net, qps_per_conn=4),
                      **kw)
    raise ValueError(transport)


def timed(fn, *a, **kw):
    t0 = time.time()
    out = fn(*a, **kw)
    return out, time.time() - t0


def csv_row(name: str, us: float, derived: str) -> str:
    return f"{name},{us:.1f},{derived}"
