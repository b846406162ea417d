"""The benchmark's plain reference: a discrete-event packet simulator.

``simulate(config, flows, seed)`` runs the same flows on the same
deployment as the program under test, through an event-driven engine
copied from the program's oracle (``netsim.py`` and ``engines.py``), and
returns per-message completion times and the drop count.
It shares no code, weights or tables with the program: only the flow
list, which both take from the benchmark's generator.
"""
from __future__ import annotations

from .engines import STrackSender
from .netsim import NetSim
from .params import NetworkSpec, make_strack_params
from .topology import FatTree

#: Simulated microseconds after which the reference gives up on a message
#: (counted unfinished).  The slowest cell finishes well inside 1 ms.
UNTIL_US = 20_000.0


def build_sim(config: dict, seed: int, sender=STrackSender) -> NetSim:
    """A NetSim for one deployment (a configuration file's dict)."""
    rc = config["run_config"]
    if rc["protocol"] != "strack" or rc["lb_mode"] != "adaptive":
        raise ValueError("the reference runs STrack with adaptive spraying")
    net = NetworkSpec(**config["network"])
    sim = NetSim(FatTree(**config["topology"]), net,
                 strack_params=make_strack_params(
                     net, max_paths=int(rc["max_paths"])),
                 seed=seed)
    sim.strack_sender = sender
    return sim


def simulate(config: dict, flows, seed: int, sender=STrackSender) -> dict:
    """Run ``flows`` [(src, dst, bytes), ...], all released at t=0.

    Returns ``{"fct_us": [per-message FCT or None], "drops"}``.
    """
    sim = build_sim(config, seed, sender)
    msgs = [sim.add_flow(int(s), int(d), float(b)) for s, d, b in flows]
    sim.run(until=UNTIL_US)
    return {"fct_us": [m.fct for m in msgs], "drops": sim.total_drops}
