"""The ONE experiment API (Section 4.2/4.3): Scenario + RunConfig + run().

Every experiment in the paper's evaluation matrix is a :class:`Scenario` —
topology + network + a list of :class:`Message` records carrying optional
*dependency edges* (``mid/src/dst/size/deps/group``) — executed by a single
entry point against a :class:`RunConfig`:

    >>> res = run(scenario, RunConfig(backend="fabric", protocol="strack"))
    >>> rows = sweep(scenarios, RunConfig(protocol="rocev2", subflows=4))

``RunConfig`` names the backend ("fabric" = the jitted multi-queue
fat-tree in ``fabric.py``, ~1000x faster; "events" = the discrete-event
oracle in ``events.py``), the protocol ("strack" | "rocev2"), the STrack
load-balance mode (adaptive / oblivious / fixed spray), PFC losslessness,
message->sub-flow striping (``subflows=4`` is the paper's tuned 4-QP
RoCEv2), the event-horizon scan (``time_warp``, default on: dead tick
intervals collapse with bit-exact results), trace decimation
(``trace_every``), queue tracing and seeds.  ``sweep()`` takes one config
or a list: data axes (msg sizes, lb_mode, entropy seed) vmap through ONE
cached program; static axes (protocol, subflows, pfc) partition into one
vmapped batch per program shape (docs/performance.md).  Both backends honour dependency
scheduling — a message launches only once all its ``deps`` completed — so
the collective traces of Figs 21-28 run on the fast path too; plain flow
lists are simply the deps-free special case.

Builders cover the evaluation matrix: ``permutation_scenario`` (Figs
8-11), ``incast_scenario`` (Figs 16-20), ``oversub_scenario`` (Figs
12-13), ``linkdown_scenario`` (Figs 14-15) and ``collective_scenario``
(Figs 1-2, 21-28: ring / double-binary-tree / halving-doubling allreduce
and windowed all-to-all via ``repro.collective.algorithms``, multi-job
placement included).  Both backends return the same summary dict
(max_fct / avg_fct / unfinished / drops / pauses, plus group_fct /
max_collective_time / finished_groups / total_groups for grouped traces)
so results are directly comparable — the parity gates in
``tests/test_fabric*.py`` and ``tests/test_collective_fabric.py`` rely on
that.

:class:`TraceRunner` is the event-backend dependency scheduler (also the
parity oracle for the fabric's); ``run_scenario_on_sim`` runs a scenario
on a prebuilt NetSim when custom oracle wiring (queue logs, link
failures) is needed.  The PR 3 deprecation shims are gone — see
docs/experiments.md for the run()/sweep() migration table.
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass, replace
from typing import Optional, Sequence, Tuple

import numpy as np

from ..core.params import NetworkSpec, make_roce_params
from ..obs import spans
from .events import NetSim
from .fabric import (FabricConfig, _rto_us, run_fabric_trace,
                     run_fabric_trace_batch, summarize)
from .faults import FaultSpec
from .topology import FatTree, full_bisection, oversubscribed, \
    with_link_failures


def permutation_pairs(n_hosts: int, seed: int = 0) -> list[tuple[int, int]]:
    """Random derangement: every host sends one flow and receives one."""
    rng = random.Random(seed)
    while True:
        perm = list(range(n_hosts))
        rng.shuffle(perm)
        if all(perm[i] != i for i in range(n_hosts)):
            return [(i, perm[i]) for i in range(n_hosts)]


# --------------------------------------------------------------------------- #
# Messages + Scenario — one object, both backends
# --------------------------------------------------------------------------- #

@dataclass(frozen=True)
class Message:
    """One message of a workload trace, with dependency edges.

    ``src``/``dst`` are host ids; ``deps`` lists the ``mid``s that must
    complete before this message may launch (paper Section 4.3 trace
    semantics); ``group`` tags which collective instance the message
    belongs to.  A plain flow is a ``Message`` with no deps.

    ``arrival`` is the earliest tick the message may launch even once its
    deps are met — the open-loop knob the multi-tenant traffic generator
    (``sim/traffic.py``) uses for staggered job starts and Poisson-style
    burst arrivals.  0 (the default) preserves the closed-loop semantics.
    On the events backend it converts to microseconds via the scenario
    network's ``mtu_serialize_us`` (one fabric tick = one MTU slot).
    """

    mid: int
    src: int
    dst: int
    size: float
    deps: Tuple[int, ...] = ()
    group: int = 0
    arrival: int = 0

    def __post_init__(self):
        object.__setattr__(self, "deps", tuple(self.deps))


#: Deprecated alias — collective trace generators historically emitted
#: ``TraceMessage``; the unified API calls them :class:`Message`.
TraceMessage = Message


@dataclass(frozen=True)
class Scenario:
    """A backend-agnostic workload: who sends what, after whom, where."""

    name: str
    topo: FatTree
    net: NetworkSpec
    messages: Tuple[Message, ...]
    #: Optional chaos schedule (sim/faults.py): scheduled link/NIC flaps,
    #: degraded links and seeded corruption, honoured by BOTH backends.
    #: ``RunConfig.faults`` overrides this when set.
    faults: Optional[FaultSpec] = None

    @classmethod
    def from_flows(cls, name: str, topo: FatTree, net: NetworkSpec,
                   flows: Sequence[Tuple[int, int, float]]) -> "Scenario":
        """Wrap a plain [(src, dst, bytes), ...] list (the deps-free case)."""
        return cls(name=name, topo=topo, net=net,
                   messages=tuple(Message(mid=i, src=s, dst=d, size=float(b))
                                  for i, (s, d, b) in enumerate(flows)))

    @property
    def flows(self) -> Tuple[Tuple[int, int, float], ...]:
        """The flow-list view (message sizes, dependency edges dropped)."""
        return tuple((m.src, m.dst, m.size) for m in self.messages)

    @property
    def has_deps(self) -> bool:
        return any(m.deps for m in self.messages)

    @property
    def n_groups(self) -> int:
        return len({m.group for m in self.messages})

    @property
    def is_trace(self) -> bool:
        """True when the scenario carries trace structure (dependency
        edges or several groups) and so reports collective group metrics
        on BOTH backends (TraceRunner scheduling on events)."""
        return self.has_deps or self.n_groups > 1

    def default_ticks(self) -> int:
        """Tick budget for a fabric run: the larger of the worst
        per-destination serialisation and the dependency critical path
        (chained traces serialise whole messages end-to-end, each handoff
        costing a delivery+ack round trip), with convergence margin.

        Each handoff budgets one full base RTT plus a small per-hop
        quantization slack: the per-hop pipeline realizes the RTT in
        whole-tick serialization + propagation stages, so rounding can
        cost a couple of ticks per dependency step."""
        mtu = self.net.mtu_bytes
        rtt_ticks = self.net.base_rtt_us / self.net.mtu_serialize_us + 2
        pkts: dict[int, float] = {}
        per_dst: dict[int, float] = {}
        for m in self.messages:
            pkts[m.mid] = math.ceil(m.size / mtu)
            per_dst[m.dst] = per_dst.get(m.dst, 0.0) + pkts[m.mid]
        bottleneck = max(per_dst.values()) if per_dst else 1.0
        # critical path over the dependency DAG (iterative DFS — edges may
        # point at any mid, not just smaller ones; deps on the current DFS
        # path would be cycles and are skipped rather than looping)
        by_mid = {m.mid: m for m in self.messages}
        depth: dict[int, float] = {}
        visiting: set[int] = set()
        for root in by_mid:
            stack = [root]
            while stack:
                mid = stack[-1]
                if mid in depth:
                    stack.pop()
                    visiting.discard(mid)
                    continue
                visiting.add(mid)
                todo = [d for d in by_mid[mid].deps
                        if d in by_mid and d not in depth
                        and d not in visiting]
                if todo:
                    stack.extend(todo)
                    continue
                stack.pop()
                visiting.discard(mid)
                base = max((depth[d] for d in by_mid[mid].deps
                            if d in depth), default=0.0)
                # an arrival tick can hold a message past its deps: the
                # critical path through it starts no earlier than that
                base = max(base, float(by_mid[mid].arrival))
                depth[mid] = base + pkts[mid] + rtt_ticks
        crit = max(depth.values()) if depth else 1.0
        return int(4 * max(bottleneck, crit) + 30 * rtt_ticks + 1000)


# --------------------------------------------------------------------------- #
# Scenario builders — the paper's evaluation matrix
# --------------------------------------------------------------------------- #

def permutation_scenario(topo: FatTree, msg_bytes: float,
                         net: Optional[NetworkSpec] = None,
                         seed: int = 0) -> Scenario:
    net = net or NetworkSpec()
    pairs = permutation_pairs(topo.n_hosts, seed)
    return Scenario.from_flows(
        f"permutation_{topo.n_hosts}", topo, net,
        [(s, d, float(msg_bytes)) for s, d in pairs])


def incast_scenario(topo: FatTree, fan_in: int, msg_bytes: float,
                    dst: int = 0, net: Optional[NetworkSpec] = None,
                    seed: int = 0) -> Scenario:
    """fan_in sources -> one destination (sampled like the legacy runner)."""
    net = net or NetworkSpec()
    rng = random.Random(seed)
    candidates = [h for h in range(topo.n_hosts) if h != dst]
    srcs = rng.sample(candidates, min(fan_in, len(candidates)))
    return Scenario.from_flows(
        f"incast_{fan_in}to1", topo, net,
        [(s, dst, float(msg_bytes)) for s in srcs])


def oversub_scenario(n_tor: int, hosts_per_tor: int, ratio: int,
                     msg_bytes: float, net: Optional[NetworkSpec] = None,
                     seed: int = 0) -> Scenario:
    topo = oversubscribed(n_tor, hosts_per_tor, ratio)
    sc = permutation_scenario(topo, msg_bytes, net, seed)
    return Scenario(name=f"oversub_{ratio}:1", topo=topo, net=sc.net,
                    messages=sc.messages)


def linkdown_scenario(topo_kw: dict, frac_links_down: float,
                      msg_bytes: float, net: Optional[NetworkSpec] = None,
                      seed: int = 0) -> Scenario:
    """Permutation over an asymmetric (dead-link) full-bisection fabric."""
    base = full_bisection(**topo_kw)
    n_links = base.n_tor * base.n_spine
    n_down = max(1, int(frac_links_down * n_links))
    topo = with_link_failures(base, n_down,
                              n_tors_affected=max(1, base.n_tor // 2),
                              seed=seed)
    sc = permutation_scenario(topo, msg_bytes, net, seed)
    return Scenario(name=f"linkdown_{n_down}", topo=topo, net=sc.net,
                    messages=sc.messages)


def collective_scenario(topo: FatTree, algo: str, n_jobs: int,
                        ranks_per_job: int, collective_bytes: float,
                        net: Optional[NetworkSpec] = None, seed: int = 0,
                        **algo_kw) -> Scenario:
    """Dependency-scheduled collective trace (Figs 1-2, 21-28) as a
    Scenario: ``n_jobs`` instances of ``algo`` (ring / dbt / hd / a2a from
    ``repro.collective.algorithms``), each group randomly placed on the
    cluster; rank ids are resolved to hosts here so the trace runs
    unchanged on either backend.  ``algo_kw`` reaches the generator
    (``chunk=``, ``window=`` for a2a)."""
    from ..collective.algorithms import multi_job  # cycle: algorithms ← us
    net = net or NetworkSpec()
    msgs, placement = multi_job(algo, n_jobs, ranks_per_job, topo.n_hosts,
                                collective_bytes, seed=seed, **algo_kw)
    return Scenario(
        name=f"{algo}_x{n_jobs}r{ranks_per_job}",
        topo=topo, net=net,
        messages=tuple(Message(mid=m.mid, src=placement[m.src],
                               dst=placement[m.dst], size=m.size,
                               deps=tuple(m.deps), group=m.group,
                               arrival=m.arrival)
                       for m in msgs))


# --------------------------------------------------------------------------- #
# RunConfig + run()/sweep(): the single entry point, both backends
# --------------------------------------------------------------------------- #

BACKENDS = ("fabric", "events")
PROTOCOLS = ("strack", "rocev2")
LB_MODES = ("adaptive", "oblivious", "fixed")
ACK_PATHS = ("perhop", "folded")
KERNEL_BACKENDS = ("jnp", "pallas", "pallas_interpret")


@dataclass(frozen=True)
class RunConfig:
    """Everything about HOW a scenario runs (the scenario says WHAT)."""

    backend: str = "fabric"          # fabric (jitted) | events (oracle)
    protocol: str = "strack"         # strack | rocev2
    lb_mode: str = "adaptive"        # STrack spray: adaptive|oblivious|fixed
    pfc: Optional[bool] = None       # None -> lossless iff rocev2
    max_paths: int = 64              # STrack entropy space
    subflows: int = 1                # message striping (4 = tuned RoCEv2)
    n_ticks: Optional[int] = None    # fabric horizon (None -> default_ticks)
    switch_buffer_bytes: Optional[float] = None  # None -> backend default
    roce_entropy_seed: Optional[int] = None      # align QP entropy w/ oracle
    # --- per-hop latency model ------------------------------------------
    # "perhop" (default): packets accrue serialization + propagation at
    # every queue stage and ACKs return over their flow's reverse path, so
    # the uncongested RTT realizes net.base_rtt_us on BOTH backends (the
    # events oracle always runs this model).  "folded" restores the
    # fabric's legacy single-constant return pipe (fabric-only knob).
    ack_path: str = "perhop"
    # Per-link propagation override (us); None derives it from the
    # scenario's NetworkSpec (net.hop_prop_effective_us).  Honoured by
    # both backends.
    hop_prop_us: Optional[float] = None
    # Fabric: ticks a PFC pause/resume frame takes to reach the upstream
    # queue (None -> one hop of propagation; the oracle always delays
    # pause frames by its propagation).
    pfc_delay_ticks: Optional[int] = None
    # Event-horizon scan (fabric): skip provably-dead tick intervals in one
    # scan trip.  Bit-identical completion ticks / drops / pauses vs dense
    # ticking (tests/test_timewarp.py); set False to force dense ticking.
    time_warp: bool = True
    # Per-tick trace decimation (fabric): 0 = no trace (summaries come
    # from the exact final carry — the default, so scan-carry memory no
    # longer scales with n_ticks), k>=1 = snapshot every k ticks (forces
    # dense ticking).
    trace_every: int = 0
    trace_queues: bool = False       # fabric: per-tick queue-depth settle
    qdelay_threshold_us: float = 8.0
    # Fabric active set: lane count for the NIC/timer stage (None = every
    # flow is a lane).  Caps the per-tick cost at O(active_cap) instead of
    # O(n_flows) for traces where most flows are dep-gated or already
    # done; the program RAISES post-run if the cap was ever exceeded.
    # Requires the no-trace path (trace_every=0, trace_queues off).
    active_cap: Optional[int] = None
    # Fabric sharding: partition the program over this many devices with
    # shard_map (queues by switch block, flows by block; the inter-pod hop
    # is an explicit all_gather exchange).  0/1 = single-device.  The
    # mesh takes the first N chips of a multi-chip TPU host; on the CPU,
    # force N host devices with XLA_FLAGS=--xla_force_host_platform_
    # device_count=N.  Bit-exact vs unsharded; requires trace_every=0.
    shard: int = 0
    # Fabric kernel backend for the scan body's hot stages: "jnp"
    # (inline, XLA-fused — the default and the path that runs on the
    # TPU), "pallas" (compiled Pallas kernels, which the TPU lowering
    # refuses: it raises there) or "pallas_interpret" (Pallas interpret
    # mode, runs anywhere incl. CPU CI, bit-exact vs jnp per
    # tests/test_fabric_kernels.py + the fuzz suite's kernel leg);
    # single-device only (shard <= 1).
    kernel_backend: str = "jnp"
    # Chaos schedule (sim/faults.py): time-varying link/NIC flaps,
    # degraded links, seeded corruption.  Overrides ``Scenario.faults``
    # when set; faults are program *data* on the fabric backend (one
    # compiled program serves every schedule of the same shape).  When
    # ``n_ticks`` is None the default horizon is extended past the last
    # fault edge so recovery has room to complete.
    faults: Optional[FaultSpec] = None
    seed: int = 1234                 # events-backend rng seed
    until: float = 1e9               # events-backend horizon (us)

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"expected one of {BACKENDS}")
        if self.protocol not in PROTOCOLS:
            raise ValueError(f"unknown protocol {self.protocol!r}; "
                             f"expected one of {PROTOCOLS}")
        if self.lb_mode not in LB_MODES:
            raise ValueError(f"unknown lb_mode {self.lb_mode!r}; "
                             f"expected one of {LB_MODES}")
        if self.ack_path not in ACK_PATHS:
            raise ValueError(f"unknown ack_path {self.ack_path!r}; "
                             f"expected one of {ACK_PATHS}")
        if self.trace_every < 0:
            raise ValueError(
                f"trace_every must be >= 0, got {self.trace_every}")
        if self.active_cap is not None and self.active_cap <= 0:
            raise ValueError(
                f"active_cap must be positive, got {self.active_cap}")
        if self.shard < 0:
            raise ValueError(f"shard must be >= 0, got {self.shard}")
        if self.kernel_backend not in KERNEL_BACKENDS:
            raise ValueError(
                f"unknown kernel_backend {self.kernel_backend!r}; "
                f"expected one of {KERNEL_BACKENDS}")
        if self.kernel_backend != "jnp" and self.shard > 1:
            raise ValueError(
                f"kernel_backend={self.kernel_backend!r} requires "
                f"shard <= 1 (the sharded program keeps its inline jnp "
                f"stages)")
        if (self.active_cap or self.shard > 1) and (
                self.trace_every or self.trace_queues):
            raise ValueError(
                "active_cap/shard need the no-trace path "
                "(trace_every=0, trace_queues=False)")


def run(sc: Scenario, cfg: RunConfig = RunConfig()) -> dict:
    """Run one scenario under one config; oracle-comparable summary dict.

    Dispatches on ``cfg.backend``: the jitted fabric honours dependency
    gating and sub-flow striping inside its ``lax.scan``; the event oracle
    uses :class:`TraceRunner` (deps) or plain flow addition (no deps).
    """
    if cfg.backend == "fabric":
        return _run_fabric_backend(sc, cfg)
    return _run_events_backend(sc, cfg)


def sweep(scenarios: Sequence[Scenario],
          cfg=RunConfig()) -> list:
    """Run a batch of same-structure scenarios under one config — or under
    a matching list of configs (a multi-axis sweep).

    ``cfg`` is a single :class:`RunConfig` (applied to every scenario) or
    a sequence of them.  Lengths must match, or either side may be length
    1 and is broadcast — so ``sweep([sc], [cfg_a, cfg_b, cfg_c])`` sweeps
    config axes over one scenario and ``sweep(seeds, cfg)`` sweeps seeds
    under one config.

    On the fabric backend, everything that is *data* to the compiled
    program is vmapped through ONE jitted XLA call per program shape:
    message src/dst/sizes (e.g. msg-size or placement-seed axes),
    ``lb_mode`` (a traced scalar) and ``roce_entropy_seed``.  Axes that
    change the program itself (protocol, pfc, ``subflows``, n_ticks,
    buffer sizes, time_warp) partition the sweep into one vmapped batch
    per group — each served by the program cache, so repeated sweeps
    compile nothing.  All scenarios must share a topology, network and
    message/dependency structure (different src/dst/size patterns are
    fine: that is the point).  On the events backend it simply loops.
    Returns one summary dict per (scenario, config) pair, input order.
    """
    if not scenarios:
        raise ValueError("sweep() needs at least one scenario")
    scenarios = list(scenarios)
    cfgs = list(cfg) if isinstance(cfg, (list, tuple)) else [cfg]
    if not cfgs:
        raise ValueError("sweep() needs at least one config")
    if len(scenarios) == 1 and len(cfgs) > 1:
        scenarios = scenarios * len(cfgs)
    if len(cfgs) == 1 and len(scenarios) > 1:
        cfgs = cfgs * len(scenarios)
    if len(cfgs) != len(scenarios):
        raise ValueError(
            f"sweep() got {len(scenarios)} scenarios and {len(cfgs)} "
            f"configs; lengths must match, or either side must be 1")
    # the shared-structure requirement exists so one vmapped program can
    # serve the batch — it only binds the fabric-backend entries (the
    # events oracle simply loops and takes any mix of scenarios)
    fabric_ix = [i for i, rc in enumerate(cfgs) if rc.backend == "fabric"]
    sc0 = scenarios[fabric_ix[0]] if fabric_ix else None
    for i in fabric_ix[1:]:
        sc = scenarios[i]
        if sc.topo != sc0.topo:
            raise ValueError(
                f"sweep() scenarios must share a topology: field 'topo' of "
                f"{sc.name!r} is {sc.topo}, of {sc0.name!r} is {sc0.topo}")
        if sc.net != sc0.net:
            raise ValueError(
                f"sweep() scenarios must share a network: field 'net' of "
                f"{sc.name!r} is {sc.net}, of {sc0.name!r} is {sc0.net}")
        if len(sc.messages) != len(sc0.messages):
            raise ValueError(
                f"sweep() scenarios must share the message structure: "
                f"field 'messages' of {sc.name!r} has {len(sc.messages)} "
                f"entries, of {sc0.name!r} has {len(sc0.messages)}")
        structure = [(m.deps, m.group) for m in sc.messages]
        structure0 = [(m.deps, m.group) for m in sc0.messages]
        if structure != structure0:
            bad = next(i for i, (a, b) in
                       enumerate(zip(structure, structure0)) if a != b)
            raise ValueError(
                f"sweep() scenarios must share the dependency structure: "
                f"field 'messages[{bad}].deps/group' of {sc.name!r} is "
                f"{structure[bad]}, of {sc0.name!r} is {structure0[bad]}")
    out: list = [None] * len(cfgs)
    # group fabric pairs by everything static to the program; lb_mode and
    # entropy seed are data axes within a group
    groups: dict = {}
    for i, (sc, rc) in enumerate(zip(scenarios, cfgs)):
        if rc.backend != "fabric":
            out[i] = run(sc, rc)
            continue
        fcfg = _fabric_cfg(sc, rc)
        key = (replace(fcfg, lb_mode="adaptive", roce_entropy_seed=None),
               rc.n_ticks, rc.trace_queues)
        groups.setdefault(key, []).append(i)
    for idxs in groups.values():
        rc0 = cfgs[idxs[0]]
        fcfg0 = _fabric_cfg(scenarios[idxs[0]], rc0)
        ticks = rc0.n_ticks or max(_scenario_ticks(scenarios[i], cfgs[i])
                                   for i in idxs)
        _, per_entry = run_fabric_trace_batch(
            scenarios[idxs[0]].topo,
            [scenarios[i].messages for i in idxs], ticks, fcfg0,
            lb_modes=[cfgs[i].lb_mode for i in idxs],
            entropy_seeds=[cfgs[i].roce_entropy_seed for i in idxs])
        for i, metrics in zip(idxs, per_entry):
            out[i] = _fabric_summary(scenarios[i], cfgs[i], metrics)
    return out


# --------------------------------------------------------------------------- #
# Backend plumbing
# --------------------------------------------------------------------------- #

def _effective_faults(sc: Scenario, cfg: RunConfig) -> Optional[FaultSpec]:
    """RunConfig.faults wins over Scenario.faults (config says HOW)."""
    return cfg.faults if cfg.faults is not None else sc.faults


def _scenario_ticks(sc: Scenario, cfg: RunConfig) -> int:
    """Fabric horizon: explicit n_ticks, else default_ticks() extended by
    the fault schedule — a flap that outlives the clean-run horizon needs
    the window itself, a few RTOs of loss recovery (go-back-N may need a
    full timeout per loss burst) and the clean drain budget after the
    last edge.  Time-warp makes the generous margin nearly free: dead
    tick intervals collapse in one scan trip."""
    if cfg.n_ticks is not None:
        return cfg.n_ticks
    ticks = sc.default_ticks()
    fs = _effective_faults(sc, cfg)
    if fs is not None and fs.last_edge > 0:
        rto_ticks = math.ceil(_rto_us(_fabric_cfg(sc, cfg))
                              / sc.net.mtu_serialize_us)
        ticks = max(ticks, fs.last_edge + 4 * rto_ticks + ticks)
    return ticks


def _fabric_cfg(sc: Scenario, cfg: RunConfig) -> FabricConfig:
    time_warp, trace_every = cfg.time_warp, cfg.trace_every
    if cfg.trace_queues:
        trace_every = trace_every or 1
    if trace_every:
        # any per-tick trace (queue settle or an explicit trace_every=k)
        # needs dense ticking: a data-dependent trip count can't stack one
        time_warp = False
    kw = dict(net=sc.net, max_paths=cfg.max_paths, lb_mode=cfg.lb_mode,
              protocol=cfg.protocol, pfc=cfg.pfc, subflows=cfg.subflows,
              roce_entropy_seed=cfg.roce_entropy_seed,
              ack_path=cfg.ack_path, hop_prop_us=cfg.hop_prop_us,
              pfc_delay_ticks=cfg.pfc_delay_ticks,
              time_warp=time_warp, trace_every=trace_every,
              active_cap=cfg.active_cap, shard=cfg.shard,
              kernel_backend=cfg.kernel_backend,
              faults=_effective_faults(sc, cfg))
    if cfg.switch_buffer_bytes is not None:
        kw["switch_buffer_bytes"] = cfg.switch_buffer_bytes
    return FabricConfig(**kw)


def _queue_settle_us(metrics: dict, threshold_us: float) -> float:
    """Last simulated time any fabric queue's delay (depth x tick) exceeded
    ``threshold_us`` — the fabric analogue of the event backend's
    queue-delay logs (Fig 8 settling time).  With a decimated trace
    (``trace_every=k``) rows sample block ends, so the settle time is
    quantised to k ticks."""
    q = np.asarray(metrics["qsize"], dtype=float)      # [rows, Q]
    tick = metrics["tick_us"]                          # per-pkt delay unit
    k = max(1, metrics.get("trace_every", 1))          # row -> tick stride
    over = np.nonzero((q * tick > threshold_us).any(axis=1))[0]
    return float((over[-1] + 1) * k * tick) if len(over) else 0.0


def _fabric_summary(sc: Scenario, cfg: RunConfig, metrics: dict) -> dict:
    out = summarize(metrics)
    out["backend"] = "fabric"
    out["name"] = sc.name
    out["protocol"] = cfg.protocol
    out["lb_mode"] = cfg.lb_mode
    out["subflows"] = cfg.subflows
    if "warp_trips" in metrics:  # event-horizon diagnostics
        out["warp_trips"] = int(np.asarray(metrics["warp_trips"]))
        out["end_tick"] = int(np.asarray(metrics["end_tick"]))
    if cfg.trace_queues:
        out["queue_settle_us"] = _queue_settle_us(metrics,
                                                  cfg.qdelay_threshold_us)
    return out


def _run_fabric_backend(sc: Scenario, cfg: RunConfig) -> dict:
    answer = spans.next_answer()
    with spans.span("fabric.run", answer=answer):
        fcfg = _fabric_cfg(sc, cfg)
        _, metrics = run_fabric_trace(sc.topo, sc.messages,
                                      _scenario_ticks(sc, cfg), fcfg)
        out = _fabric_summary(sc, cfg, metrics)
    out["answer"] = answer  # the id of this call's spans (obs/spans.py)
    return out


def _events_sim(sc: Scenario, cfg: RunConfig, **netsim_kw) -> NetSim:
    if cfg.hop_prop_us is not None:
        # the oracle reads its per-link propagation from the NetworkSpec;
        # a RunConfig override rides in on a replaced spec
        sc = replace(sc, net=replace(sc.net, hop_prop_us=cfg.hop_prop_us))
    kw = dict(seed=cfg.seed)
    if cfg.switch_buffer_bytes is not None:
        kw["switch_buffer_bytes"] = cfg.switch_buffer_bytes
    fs = _effective_faults(sc, cfg)
    if fs is not None:
        kw["faults"] = fs
    kw.update(netsim_kw)
    if cfg.protocol == "strack":
        if cfg.lb_mode == "fixed":
            raise ValueError("lb_mode='fixed' (single-path pinning) only "
                             "exists on the fabric backend")
        # a caller-provided kwarg (legacy shim path) wins over lb_mode
        obl = kw.pop("oblivious_spray", cfg.lb_mode == "oblivious")
        return NetSim(sc.topo, sc.net, transport="strack",
                      oblivious_spray=obl, **kw)
    rp = kw.pop("roce_params",
                make_roce_params(sc.net, qps_per_conn=cfg.subflows))
    return NetSim(sc.topo, sc.net, transport="roce", roce_params=rp, **kw)


def _run_events_backend(sc: Scenario, cfg: RunConfig,
                        **netsim_kw) -> dict:
    sim = _events_sim(sc, cfg, **netsim_kw)
    return run_scenario_on_sim(sim, sc, until=cfg.until)


def _summarize_sim(sim: NetSim) -> dict:
    fcts = [fl.fct for fl in sim.flows.values() if fl.fct is not None]
    return {
        "max_fct": max(fcts) if fcts else float("nan"),
        "avg_fct": sum(fcts) / len(fcts) if fcts else float("nan"),
        "unfinished": sum(1 for fl in sim.flows.values() if fl.fct is None),
        "drops": sim.total_drops,
        "pauses": len(sim.pause_log),
        # uniform recovery/fault schema (same keys as fabric summarize()):
        # the oracle counts fault losses directly; per-protocol recovery
        # counters live inside the ref engines and are reported as 0 here
        "retransmits": 0,
        "rto_fires": 0,
        "sack_recoveries": 0,
        "gbn_rewinds": 0,
        "blackholed_pkts": getattr(sim, "blackholed_pkts", 0),
        "corrupt_drops": getattr(sim, "corrupt_drops", 0),
    }


# --------------------------------------------------------------------------- #
# TraceRunner: the event-backend dependency scheduler (fabric parity oracle)
# --------------------------------------------------------------------------- #

class TraceRunner:
    """Replays dependency traces on a NetSim: a message launches when all
    its dependencies have completed (paper Section 4.3 trace semantics).

    ``placement`` maps message src/dst ids to hosts (identity when the
    messages already carry host ids, as ``Scenario.messages`` do)."""

    def __init__(self, sim: NetSim, messages: list,
                 placement: dict[int, int]):
        self.sim = sim
        self.msgs = {m.mid: m for m in messages}
        self.placement = placement  # rank -> host
        self.children: dict[int, list[int]] = {m.mid: [] for m in messages}
        self.pending_deps = {m.mid: len(m.deps) for m in messages}
        for m in messages:
            for d in m.deps:
                self.children[d].append(m.mid)
        self.flow_to_msg: dict[int, int] = {}
        self.done: set[int] = set()
        self.group_done_ts: dict[int, float] = {}
        self.group_msgs: dict[int, int] = {}
        for m in messages:
            self.group_msgs[m.group] = self.group_msgs.get(m.group, 0) + 1
        sim.on_flow_done = self._on_flow_done

    def _launch(self, m: Message, now: float):
        # honour the open-loop arrival tick: one fabric tick = one MTU
        # serialisation slot, so arrival converts via mtu_serialize_us
        start = max(now, m.arrival * self.sim.net.mtu_serialize_us)
        fl = self.sim.add_flow(self.placement[m.src], self.placement[m.dst],
                               m.size, start_ts=start, meta=m.mid)
        self.flow_to_msg[fl.id] = m.mid

    def _on_flow_done(self, fl, now: float):
        mid = self.flow_to_msg.get(fl.id)
        if mid is None:
            return
        m = self.msgs[mid]
        self.done.add(mid)
        self.group_msgs[m.group] -= 1
        if self.group_msgs[m.group] == 0:
            self.group_done_ts[m.group] = now
        for c in self.children[mid]:
            self.pending_deps[c] -= 1
            if self.pending_deps[c] == 0:
                self._launch(self.msgs[c], now)

    def run(self, until: float = 1e9) -> dict:
        for m in self.msgs.values():
            if self.pending_deps[m.mid] == 0:
                self._launch(m, 0.0)
        self.sim.run(until=until)
        finished = len(self.group_done_ts)
        msg_fct = {mid: fl.fct for fl in self.sim.flows.values()
                   if (mid := self.flow_to_msg.get(fl.id)) is not None
                   and fl.fct is not None}
        return {
            "group_fct": dict(self.group_done_ts),
            "max_collective_time": (max(self.group_done_ts.values())
                                    if self.group_done_ts else float("nan")),
            "finished_groups": finished,
            "total_groups": len(self.group_msgs) if self.group_msgs else 0,
            "drops": self.sim.total_drops,
            "pauses": len(self.sim.pause_log),
            "msg_fct": msg_fct,
        }


# --------------------------------------------------------------------------- #
# Prebuilt-sim entry point (custom oracle wiring: queue logs, failures)
# --------------------------------------------------------------------------- #

def run_scenario_on_sim(sim: NetSim, sc: Scenario,
                        until: float = 1e9) -> dict:
    """Run a scenario on a prebuilt NetSim (custom params / queue logging).

    Honours dependency edges via :class:`TraceRunner`."""
    if sc.is_trace:
        placement = {h: h for m in sc.messages for h in (m.src, m.dst)}
        res = TraceRunner(sim, list(sc.messages), placement).run(until=until)
        out = {**_summarize_sim(sim), **res}
    else:
        for m in sc.messages:
            sim.add_flow(m.src, m.dst, m.size,
                         start_ts=m.arrival * sim.net.mtu_serialize_us)
        sim.run(until=until)
        out = _summarize_sim(sim)
    out["backend"] = "events"
    out["name"] = sc.name
    return out
