"""Vectorized multi-queue fat-tree fabric — one XLA program, every protocol.

The jitted counterpart of the ``events.py`` oracle: a 2-tier Clos fabric
(host NICs -> per-ToR uplink queues -> per-spine downlink queues -> per-host
downlink queues) simulated as fixed-shape ring-buffer arrays inside a single
``lax.scan``.  The fabric is *protocol-generic*: per-flow transport logic is
plugged in through a :class:`Protocol` record of init / on-data / on-ack /
on-timer / next-packet transition functions, and both of the paper's
transports run on this fast path:

  * **STrack** (``core/transport.py``): window-based CC + adaptive spray +
    selective retransmission.  Path entropy matters: every packet is
    ECMP-hashed (the jnp mirror of ``topology._mix``) onto a live uplink,
    so Algorithm 2's spray state steers real queues.
  * **RoCEv2** (``dcqcn_fab.py``): DCQCN rate-based CC + go-back-N, single
    fixed path per flow — the paper's baseline, previously event-sim-only.

The queue layer also models **PFC** (priority flow control) for lossless
mode: per-ingress byte accounting against the dynamic shared-buffer
threshold ``xoff = alpha * free / (1 + alpha)`` (mirroring
``events.Switch``), with pause/resume masks applied inside the scan —
a paused fabric queue stops serving, a paused NIC stops injecting.

The scan is **event-horizon driven** by default at the experiment API
(``FabricConfig.time_warp``): after any tick that leaves the fabric
provably idle — no queued packet, no released flow offering a packet, no
unrecorded dependency release — the loop advances ``now`` straight to the
earliest next interesting time (pending timer expiry via
``Protocol.next_event``, pacing/rate credit release, or return-pipe
arrival) in one trip, so dependency stalls, DCQCN recovery backoff and
post-completion tails cost O(1) instead of one trip per dead tick.
Completion ticks, drops and pause counts are bit-identical to dense
ticking (tests/test_timewarp.py); the per-tick metrics trace is opt-in
and decimated (``trace_every``) since a data-dependent trip count cannot
stack one.  Programs are built+jitted once per static shape through an
LRU cache (``_get_program``), with ``lb_mode`` a traced scalar so spray
modes, entropy seeds and message patterns all reuse one XLA program —
``workloads.sweep()`` vmaps those axes through it.  docs/performance.md
has the full model and the ``make bench`` numbers.

Time model (1 tick = 1 MTU serialization time at link rate), since the
per-hop latency pipeline (``ack_path="perhop"``, the default):

  * each host clocks out <=1 data packet per tick (NIC rate == link rate;
    flows sharing a NIC are arbitrated round-robin) plus rare probes,
  * every queue-ring slot carries a *departure-time lane* (``PktQ.ready``):
    a packet served or injected at tick ``t`` becomes serviceable at the
    next hop at ``t + 1 + hop_prop_ticks`` — one tick of serialization
    plus the per-link propagation delay, both accrued AT EVERY TRAVERSED
    STAGE (host->uplink->downlink->host), so RTT samples and ECN marks
    reflect real per-hop queueing + propagation instead of one folded
    constant,
  * egress ECN marking on the residual queue depth between Kmin..Kmax
    (deterministic dither; RoCEv2 mode uses the 1-BDP DCQCN threshold),
  * lossy mode tail-drops data beyond 5 BDP; lossless (PFC) mode never
    drops data — backpressure bounds the queues; PFC accounting is
    per-PACKET wire bytes (odd tails and 64B probes, not whole MTUs) and
    pause/resume frames take ``pfc_delay_ticks`` to reach the upstream
    queue (one hop of propagation, as in the oracle),
  * ACK/SACK/CNP messages return through a per-flow reverse-path pipe
    whose latency is the ACK's own store-and-forward pipeline —
    ``hops * (prop + ack serialization)`` for that flow's path (2 hops
    same-ToR, 4 cross-ToR) — so the uncongested data+ACK round trip
    realizes exactly ``net.base_rtt_us`` on fabric AND oracle,
  * variable message sizes are first-class: the final PSN of a message is
    its odd tail (``ref.pkt_size`` semantics) in the send window, DCQCN
    pacing/byte-counter, receiver byte counts and PFC accounting; a tail
    packet still costs one serialization tick (tick quantization).
  * ``ack_path="folded"`` (or a ``delay_ticks`` override, as ``jaxsim.py``
    uses) restores the legacy model: no per-hop propagation, the full
    base-RTT remainder folded into one fixed-latency return pipe.

Dependency-scheduled messages (collective traces, Figs 21-28) run inside
the same ``lax.scan``: every flow belongs to a *message*, messages carry
static dependency edges, and per-message pending-dep counters gate sending —
a message becomes sendable the tick its counter reaches zero, and its
completion decrements its children's counters.  Messages optionally fan out
into ``subflows`` striped sub-flows (the paper's 4-QP "optimized RoCEv2"),
each a single-path flow with its own entropy; the message completes when the
last sub-flow completes.  Plain flow lists are the deps-free, 1-sub-flow
special case of the same machinery.

sim/ module map
---------------
  topology.py   FatTree: Python Clos model + ECMP hash (shared ground truth)
  fabric.py     this file — the fast path for BOTH protocols; >=4-ToR
                fabrics, spray modes, dead links, oversubscription, PFC,
                dependency gating + sub-flow striping for collective traces
  dcqcn_fab.py  RoCEv2 (DCQCN + go-back-N) per-flow transitions
  jaxsim.py     the 1-queue special case of the fabric (incast Figs 16-20)
  events.py     discrete-event oracle (parity tests + TraceRunner oracle
                for the collective parity gates); ~1000x slower
  workloads.py  the one experiment API: Scenario (dependency-edged
                messages) + RunConfig + run()/sweep() over both backends
"""
from __future__ import annotations

import dataclasses
import math
import random
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core import reliability as rel
from ..kernels.fabric_kernels import (flow_transition_kernel, iota1,
                                      rank_in_queue_core,
                                      serve_enqueue_kernel)
from ..core import transport as tp
from ..core.params import (ACK_WIRE_BYTES, NetworkSpec, RoCEParams,
                           STrackParams, make_roce_params,
                           make_strack_params)
from ..core.reliability import SackMsg
from ..obs import spans
from .faults import (FaultSpec, build_fault_data, duty_open, fault_u01,
                     validate_faults)
from .dcqcn_fab import (RoceFabParams, empty_roce_msgs, init_roce_flow,
                        init_roce_rcv, make_roce_fab_params, roce_done,
                        roce_next_event, roce_next_packet, roce_on_ack,
                        roce_on_data, roce_on_timer)
from .topology import FatTree

LB_MODES = ("adaptive", "oblivious", "fixed")
PROTOCOLS = ("strack", "rocev2")
ACK_PATHS = ("perhop", "folded")
KERNEL_BACKENDS = ("jnp", "pallas", "pallas_interpret")


def ecmp_mix(a: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    """jnp mirror of ``topology._mix`` (uint32 wrap-around arithmetic)."""
    u = jnp.uint32
    h = a.astype(jnp.uint32) * u(2654435761)
    h = h ^ (b.astype(jnp.uint32) * u(2246822519))
    h = h * u(3266489917)
    h = h ^ (c.astype(jnp.uint32) * u(668265263))
    h = h * u(374761393)
    return ((h >> u(8)) ^ (h & u(0xFF))).astype(jnp.int32)


class ArrayTopo(NamedTuple):
    """Array-ized FatTree: everything the jitted fabric needs as jnp data."""

    n_tor: int
    n_spine: int
    hosts_per_tor: int
    n_hosts: int
    live_mask: jax.Array   # bool[T, S]: (tor, spine) link is up
    live_list: jax.Array   # i32[T, S]: i-th live spine of tor (padded)
    n_live: jax.Array      # i32[T]

    @classmethod
    def from_fat_tree(cls, topo: FatTree) -> "ArrayTopo":
        T, S = topo.n_tor, topo.n_spine
        mask = [[(t, s) not in topo.dead_links for s in range(S)]
                for t in range(T)]
        llist, nlive = [], []
        for t in range(T):
            ups = topo.live_up[t]
            llist.append(ups + [ups[0]] * (S - len(ups)))
            nlive.append(len(ups))
        return cls(n_tor=T, n_spine=S, hosts_per_tor=topo.hosts_per_tor,
                   n_hosts=topo.n_hosts,
                   live_mask=jnp.asarray(mask, bool),
                   live_list=jnp.asarray(llist, jnp.int32),
                   n_live=jnp.asarray(nlive, jnp.int32))

    def tor_of(self, host: jax.Array) -> jax.Array:
        return host // self.hosts_per_tor

    def ecmp_spine(self, src: jax.Array, dst: jax.Array,
                   entropy: jax.Array) -> jax.Array:
        """Vectorized ECMP onto a live uplink (bit-exact vs FatTree)."""
        tor = self.tor_of(src)
        k = ecmp_mix(src, dst, entropy) % self.n_live[tor]
        return self.live_list[tor, k]


# --------------------------------------------------------------------------- #
# Protocol dispatch: the per-flow transport plugged into the fabric
# --------------------------------------------------------------------------- #

class Protocol(NamedTuple):
    """Per-flow transport engine record (all fns are per-flow; the fabric
    vmaps them).  Message pytrees must carry a bool ``valid`` leaf named
    ``valid`` — the return pipe relies on it.

      init(total_pkts[N], tail_bytes[N], entropy0[N])
                                       -> (flow_states, rcv_states)
      empty_msgs(h, n)                 -> msg pytree, leading dims (h, n)
      on_data(rcv, psn, size, ecn, ent, ts, probe, now) -> (rcv, msg)
      on_ack(flow, msg, now)           -> flow
      on_timer(flow, now)              -> (flow, TxPacket)
      next_packet(flow, now)           -> (flow, TxPacket)
      done(flow)                       -> bool
      cong_pkts(flow)                  -> f32 window-equivalent in packets
      next_event(flow)                 -> (timer_event_us, send_event_us):
          the earliest future times at which on_timer / next_packet stop
          being no-ops for this flow (+inf if never) — the per-flow half
          of the event-horizon (time-warp) scan contract: before those
          times, an idle fabric can skip ticks without changing state.
      stat_retx(flows)                 -> i32 per-flow retransmitted-packet
          count, derived elementwise from the final flow pytree (works on
          vmapped [B, N] states too) — observability only, never read
          inside the scan.
      stat_recovery(flows)             -> dict of i32 per-flow recovery
          counters with the UNIFORM keys ``rto_fires`` /
          ``sack_recoveries`` / ``gbn_rewinds`` — zero-filled where a
          protocol has no such mechanism, so summaries and dashboards
          never KeyError across protocols.
    """

    name: str
    uses_spray: bool       # fabric lb_mode applies; else protocol's entropy
    init: Callable
    empty_msgs: Callable
    on_data: Callable
    on_ack: Callable
    on_timer: Callable
    next_packet: Callable
    done: Callable
    cong_pkts: Callable
    next_event: Callable
    stat_retx: Callable
    stat_recovery: Callable


def _empty_sack_pipe(p: STrackParams, h: int, n: int) -> SackMsg:
    z = lambda dt: jnp.zeros((h, n), dt)
    return SackMsg(valid=z(bool), epsn=z(jnp.int32), sack_base=z(jnp.int32),
                   sack_bits=jnp.zeros((h, n, p.sack_bitmap_bits), bool),
                   bytes_recvd=z(jnp.float32), ooo_cnt=z(jnp.int32),
                   ecn=z(bool), entropy=z(jnp.int32), ts=z(jnp.float32),
                   probe_reply=z(bool))


def make_strack_protocol(p: STrackParams) -> Protocol:
    """STrack: window CC (Algo 3/4) + spray (Algo 2) + SACK reliability."""

    def init(total_pkts, tail_bytes, entropy0):
        del entropy0  # spray picks paths; no per-flow pinned entropy
        fl = jax.vmap(lambda tpk, tb: tp.init_flow(p, tpk, tail_bytes=tb))(
            total_pkts, tail_bytes)
        rcv = jax.vmap(rel.init_receiver)(total_pkts)
        return fl, rcv

    def on_data(r, psn, size, ecn, ent, ts, probe, now):
        del now
        return rel.receiver_on_data(r, p, psn, size, ecn, ent, ts, probe)

    def on_timer(f, now):
        # The oracle only arms a flow's timers when the flow is added
        # (i.e. when its dependencies released it); mirror that by holding
        # probes until the flow has actually sent data.
        f2, tx = tp.flow_on_timer(f, p, now)
        started = f.rel.bytes_sent > 0
        probe = tx.valid & started
        return f2, tx._replace(valid=probe, is_probe=probe)

    def stat_retx(f):
        # STrack tracks cumulative bytes_sent (first transmissions +
        # retransmissions); the excess over the message's wire bytes,
        # rounded to MTUs, is the retransmitted-packet count.
        wire = ((f.rel.total_pkts - 1).astype(jnp.float32) * p.mtu_bytes
                + f.rel.tail_bytes)
        extra = jnp.round((f.rel.bytes_sent - wire) / p.mtu_bytes)
        return jnp.where(f.rel.total_pkts > 0,
                         jnp.maximum(extra, 0.0).astype(jnp.int32), 0)

    return Protocol(
        name="strack", uses_spray=True, init=init,
        empty_msgs=lambda h, n: _empty_sack_pipe(p, h, n),
        on_data=on_data,
        on_ack=lambda f, m, now: tp.flow_on_sack(f, p, m, now),
        on_timer=on_timer,
        next_packet=lambda f, now: tp.flow_next_packet(f, p, now),
        done=tp.flow_done,
        cong_pkts=lambda f: f.cc.cwnd,
        next_event=lambda f: tp.flow_next_event(f, p),
        stat_retx=stat_retx,
        stat_recovery=lambda f: {
            "rto_fires": f.rel.rto_fires,
            "sack_recoveries": f.rel.recoveries,
            "gbn_rewinds": jnp.zeros_like(f.rel.rto_fires)})


def make_rocev2_protocol(p: RoceFabParams) -> Protocol:
    """RoCEv2: DCQCN rate CC + go-back-N, one fixed path per flow."""

    def init(total_pkts, tail_bytes, entropy0):
        fl = jax.vmap(lambda tpk, e, tb: init_roce_flow(
            p, tpk, e, tail_bytes=tb))(total_pkts, entropy0, tail_bytes)
        rcv = jax.vmap(init_roce_rcv)(total_pkts)
        return fl, rcv

    def on_data(r, psn, size, ecn, ent, ts, probe, now):
        del ent, ts, probe  # single path; RTT is not a DCQCN signal
        return roce_on_data(r, p, psn, size, ecn, now)

    def next_packet(f, now):
        f2, (valid, psn, entropy, is_rtx) = roce_next_packet(f, p, now)
        return f2, tp.TxPacket(valid=valid, psn=psn, entropy=entropy,
                               is_rtx=is_rtx, is_probe=jnp.zeros((), bool))

    def on_timer(f, now):
        f2, probe = roce_on_timer(f, p, now)
        z = jnp.zeros((), jnp.int32)
        return f2, tp.TxPacket(valid=probe, psn=z, entropy=f.entropy,
                               is_rtx=jnp.zeros((), bool), is_probe=probe)

    # window-equivalent in packets: instantaneous rate x base-ish RTT
    rtt_us = p.window_pkts * p.mtu_bytes / p.line_rate_Bpus

    return Protocol(
        name="rocev2", uses_spray=False, init=init,
        empty_msgs=empty_roce_msgs,
        on_data=on_data,
        on_ack=lambda f, m, now: jax.tree.map(
            lambda n_, o: jnp.where(m.valid, n_, o),
            roce_on_ack(f, p, m, now), f),
        on_timer=on_timer,
        next_packet=next_packet,
        done=roce_done,
        cong_pkts=lambda f: f.rate * rtt_us / p.mtu_bytes,
        next_event=lambda f: roce_next_event(f, p),
        stat_retx=lambda f: f.retransmits,
        stat_recovery=lambda f: {
            "rto_fires": f.rto_fires,
            "sack_recoveries": jnp.zeros_like(f.rto_fires),
            "gbn_rewinds": f.gbn_rewinds})


# --------------------------------------------------------------------------- #
# PFC: dynamic-threshold pause/resume gate (shared with the unit tests)
# --------------------------------------------------------------------------- #

def pfc_gate(paused: jax.Array, ingress_bytes: jax.Array,
             xoff_bytes: jax.Array, xon_frac: float = 0.5) -> jax.Array:
    """One PFC hysteresis step, elementwise over ingress ports.

    Pause when the port's accounted bytes exceed ``xoff``; once paused, stay
    paused until they fall below ``xon_frac * xoff`` (``events.Switch``
    semantics: pause > _xoff(), resume < 0.5 * _xoff()).
    """
    pause = ingress_bytes > xoff_bytes
    resume = ingress_bytes < xon_frac * xoff_bytes
    return pause | (paused & ~resume)


# --------------------------------------------------------------------------- #
# Messages: dependency structure + sub-flow striping (static per program)
# --------------------------------------------------------------------------- #

class _FlowMsg(NamedTuple):
    """Minimal message record for the deps-free ``run_fabric`` wrapper
    (``workloads.Message`` is the duck-typed public equivalent)."""

    mid: int
    src: int
    dst: int
    size: float
    deps: tuple = ()
    group: int = 0
    arrival: int = 0


class DepSpec(NamedTuple):
    """Static message/dependency structure a fabric program closes over.

    Flows are the striped sub-flows of messages: ``msg_of_flow`` maps each
    sub-flow back to its message; ``edge_parent[e] -> edge_child[e]`` are
    the dependency edges (child waits for parent); ``init_pending`` is each
    message's dependency in-degree.  ``msg_ids`` / ``group_ids`` keep the
    caller's original identifiers for reporting.
    """

    n_msgs: int
    n_groups: int
    msg_of_flow: jax.Array   # i32[N]
    group_of_msg: jax.Array  # i32[n_msgs]
    init_pending: jax.Array  # i32[n_msgs]
    edge_parent: jax.Array   # i32[E]
    edge_child: jax.Array    # i32[E]
    msg_ids: tuple           # original mids, program order
    group_ids: tuple         # original group ids, program order


def expand_messages(messages, subflows: int = 1):
    """Fan messages out into striped sub-flows.

    Returns ``(flows, dep)`` where ``flows`` is the [(src, dst, bytes), ...]
    list of sub-flows (each message split into ``subflows`` equal stripes,
    mirroring the oracle's multi-QP striping) and ``dep`` the
    :class:`DepSpec` tying them back together.
    """
    k = max(1, int(subflows))
    messages = list(messages)
    if not messages:
        raise ValueError("expand_messages() needs at least one message")
    mid_ix = {m.mid: i for i, m in enumerate(messages)}
    if len(mid_ix) != len(messages):
        raise ValueError("duplicate message ids in trace")
    group_ids = tuple(sorted({m.group for m in messages}))
    gid_ix = {g: i for i, g in enumerate(group_ids)}
    flows, msg_of_flow = [], []
    edge_parent, edge_child, pending = [], [], []
    for i, m in enumerate(messages):
        pending.append(len(m.deps))
        for d in m.deps:
            if d not in mid_ix:
                raise ValueError(f"message {m.mid} depends on unknown "
                                 f"message {d}")
            edge_parent.append(mid_ix[d])
            edge_child.append(i)
        for _ in range(k):
            flows.append((m.src, m.dst, m.size / k))
            msg_of_flow.append(i)
    return flows, DepSpec(
        n_msgs=len(messages), n_groups=len(group_ids),
        msg_of_flow=jnp.asarray(msg_of_flow, jnp.int32),
        group_of_msg=jnp.asarray([gid_ix[m.group] for m in messages],
                                 jnp.int32),
        init_pending=jnp.asarray(pending, jnp.int32),
        edge_parent=jnp.asarray(edge_parent, jnp.int32),
        edge_child=jnp.asarray(edge_child, jnp.int32),
        msg_ids=tuple(m.mid for m in messages),
        group_ids=group_ids)


def _trivial_dep(flows) -> DepSpec:
    """Deps-free 1:1 flow<->message mapping (the plain-flow special case)."""
    n = len(flows)
    iota = jnp.arange(n, dtype=jnp.int32)
    e = jnp.zeros((0,), jnp.int32)
    return DepSpec(n_msgs=n, n_groups=1, msg_of_flow=iota,
                   group_of_msg=jnp.zeros((n,), jnp.int32),
                   init_pending=jnp.zeros((n,), jnp.int32),
                   edge_parent=e, edge_child=e,
                   msg_ids=tuple(range(n)), group_ids=(0,))


class PktQ(NamedTuple):
    """Ring-buffer packet fields, shape [n_queues + 1, cap] (last row trash)."""

    flow: jax.Array    # i32
    psn: jax.Array     # i32
    ts: jax.Array      # f32 (send timestamp, us)
    probe: jax.Array   # bool
    ecn: jax.Array     # bool (accumulated across hops)
    ent: jax.Array     # i32 (path entropy)
    ready: jax.Array   # i32 (departure-time lane: earliest service tick —
    #                    arrival at this hop after upstream serialization
    #                    plus the link's propagation delay)
    spine: jax.Array   # i32 (spine chosen at injection; 0 for same-ToR —
    #                    PFC ingress accounting reads it at the host-down
    #                    dequeue instead of re-deriving ECMP, which would
    #                    diverge once fault schedules make masks
    #                    time-varying)


class FabricState(NamedTuple):
    flows: NamedTuple        # protocol flow states, vmapped [N]
    rcv: NamedTuple          # protocol receiver states, vmapped [N]
    q: PktQ                  # [Q+1, cap]
    qhead: jax.Array         # i32[Q+1]
    qsize: jax.Array         # i32[Q+1]
    pipe: NamedTuple         # [H, N]: per-flow ACK/SACK/CNP return pipe
    obl_rr: jax.Array        # i32[N]: oblivious-spray round robin
    drops: jax.Array         # i32
    delivered: jax.Array     # f32[N]
    done_tick: jax.Array     # i32[N], -1 until message completion
    # --- PFC (all-zero and untouched when pfc is off) ---
    qbytes: jax.Array        # f32[Q+1]: per-queue wire-byte occupancy
    ing_host: jax.Array      # f32[NH]: bytes at ToR(h) from host h's NIC
    ing_sd: jax.Array        # f32[S, T]: bytes at ToR t from spine s
    ing_up: jax.Array        # f32[T, S]: bytes at spine s from ToR t
    paused_nic: jax.Array    # bool[NH]
    paused_sd: jax.Array     # bool[S, T]: spine_down[s][t] paused by ToR t
    paused_up: jax.Array     # bool[T, S]: tor_up[t][s] paused by spine s
    pfc_line: jax.Array      # bool[max(PD,1), NH+2*TS]: pause-frame delay
    #                          line (decision at tick u lands at u + PD)
    pauses: jax.Array        # i32: cumulative pause (xoff) events
    # --- dependency scheduling (trivial when the trace has no deps) ---
    pending: jax.Array           # i32[n_msgs]: unmet dependency count
    msg_done: jax.Array          # bool[n_msgs]
    msg_release_tick: jax.Array  # i32[n_msgs], -1 until sendable
    msg_done_tick: jax.Array     # i32[n_msgs], -1 until complete
    group_done_tick: jax.Array   # i32[G], -1 until all group msgs complete
    act_overflow: jax.Array      # i32: ticks the live-flow count exceeded
    #                              cfg.active_cap (always 0 when unset)
    # --- observability counters (never read back inside the scan) ---
    ecn_marks: jax.Array         # i32: ECN-marked data pkts delivered
    qdepth_hi: jax.Array         # i32[Q+1]: running per-queue depth max
    # --- chaos counters (static zeros when cfg.faults is None) ---
    blackholed: jax.Array        # i32: pkts lost to a down link
    corrupt_drops: jax.Array     # i32: pkts lost to corruption draws
    tx_rows: jax.Array           # i32[Q+1]: accepted data injections per
    #                              target row (entropy-shift observability)
    win_retx: jax.Array          # i32[FW]: retx attempts attributed to
    #                              each flap window (+2 RTO of afterglow)


@dataclasses.dataclass(frozen=True)
class FabricConfig:
    net: NetworkSpec = dataclasses.field(default_factory=NetworkSpec)
    max_paths: int = 64
    lb_mode: str = "adaptive"        # adaptive | oblivious | fixed (STrack)
    timer_every: int = 8             # ticks between timer sweeps
    delay_ticks: Optional[int] = None  # return-pipe latency override
    #                                    (implies the folded legacy model)
    protocol: str = "strack"         # strack | rocev2
    pfc: Optional[bool] = None       # None -> lossless iff rocev2
    # --- per-hop latency pipeline ---------------------------------------
    # "perhop" (default): packets accrue serialization + propagation at
    # every traversed queue stage and ACKs return through a per-flow
    # reverse-path pipe sized to that flow's hop count, so the uncongested
    # RTT realizes net.base_rtt_us exactly (the oracle's model).
    # "folded": the legacy model — no per-hop propagation, the whole
    # base-RTT remainder folded into one fixed return-pipe latency.
    ack_path: str = "perhop"
    # Per-link propagation override (us); None uses the NetworkSpec's
    # derived value (net.hop_prop_effective_us).
    hop_prop_us: Optional[float] = None
    # Ticks a PFC pause/resume frame takes to reach the upstream queue.
    # None derives one hop of propagation (0 in folded mode — the legacy
    # next-tick behavior).
    pfc_delay_ticks: Optional[int] = None
    # Message -> sub-flow striping (paper's 4-QP "optimized RoCEv2"): each
    # message is split into this many equal-size single-QP sub-flows, each
    # with its own path entropy; the message completes when the last
    # sub-flow does.
    subflows: int = 1
    # Shared-buffer bytes per switch for PFC accounting.  NB: the oracle's
    # NetSim default is 64 MB, which never pauses at reduced scale; the
    # fabric default is sized so lossless backpressure is actually exercised
    # (and ring capacity stays bounded).  Parity tests pass the same value
    # to both backends.
    switch_buffer_bytes: float = 4e6
    pfc_alpha: float = 1.0           # dynamic threshold: a * free / (1 + a)
    pfc_xon_frac: float = 0.5        # resume below this fraction of xoff
    roce: Optional[RoCEParams] = None  # rocev2 constant overrides
    # When set, per-flow QP entropy replays ``random.Random(seed)`` in flow
    # order — the exact draw sequence NetSim uses — so a seed-aligned
    # fabric-vs-oracle RoCEv2 run sees identical ECMP collisions.  Default
    # (None) uses a deterministic hash of (src, dst, flow index).
    roce_entropy_seed: Optional[int] = None
    # Event-horizon ("time-warp") scan: when the fabric is provably idle
    # (no queued packets, no sendable packet, no unrecorded dependency
    # release), advance time straight to the earliest next interesting
    # tick — timer sweep, pacing release, or return-pipe arrival — in one
    # scan trip instead of ticking densely through the dead interval.
    # Completion ticks / drops / pauses are bit-identical to dense
    # ticking (tests/test_timewarp.py); only the per-tick trace is
    # unavailable, so time_warp implies trace_every=0.
    time_warp: bool = False
    # Per-tick metrics trace decimation: snapshot the trace every k ticks
    # (1 = dense, the legacy behavior).  0 disables the trace entirely —
    # summaries then come from the final scan carry, which stays exact at
    # any decimation — and is what large-host runs want: the stacked
    # [n_ticks, Q] trace is what used to cap host count.
    trace_every: int = 1
    # Active-set formulation: when set, the per-tick transport work
    # (ACK processing, timers, next-packet, enqueue candidates) runs over
    # at most this many compacted lanes — the flows that are released
    # (deps met) and not yet done — instead of all N flows.  Bit-exact vs
    # the dense formulation as long as the live count never exceeds the
    # cap; an overflow is detected in-scan and raised after the run.
    # Requires trace_every=0 (or time_warp): the decimated trace samples
    # all-flow means that the active set deliberately skips.
    active_cap: Optional[int] = None
    # Shard the fabric over this many devices with shard_map (0/1 = off):
    # queue rings partition by switch row block, flow/receiver/return-pipe
    # state by flow block; popped heads and NIC offers cross pods through
    # explicit all_gather exchanges while all small per-queue vectors stay
    # replicated, so results are bit-exact vs the unsharded program.
    # The mesh takes the first ``shard`` visible devices: TPU chips on a
    # multi-chip host (perm8k at shard=4 on a v5e 2x2 is bit-exact vs
    # shard=0, ``chip_smoke.py --four-chips``), or the forced host devices
    # of ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on the CPU.
    shard: int = 0
    # Kernel backend for the scan body's three hot stages (fused ring
    # service+enqueue, the sort-free enqueue ranker, per-flow protocol
    # transitions — see kernels/fabric_kernels.py):
    #   "jnp"              the stage cores run inline, XLA-fused (default;
    #                      the path that runs on the TPU)
    #   "pallas"           compiled Pallas kernels; the TPU lowering
    #                      refuses them (dynamic_slice in the ranker,
    #                      scatter in the fused cores), so this raises there
    #   "pallas_interpret" Pallas interpret mode: the kernel path's call
    #                      structure + bit-exactness on any backend (CPU
    #                      CI; tests/test_fabric_kernels.py)
    # Interpret mode is bit-exact vs "jnp" (same stage cores, gated by
    # the differential-fuzz suite).  Single-device only: shard > 1
    # keeps its inline jnp stages (all_gather exchanges cannot live
    # inside a kernel body).
    kernel_backend: str = "jnp"
    # Time-varying fault schedule (sim/faults.py): scheduled link/host
    # flaps, fractional-credit degrades and seeded per-link corruption.
    # Entry COUNTS are static (program cache key); every time/probability
    # value and the PRNG seed ride in as traced data, so one compiled
    # program serves any schedule of the same shape.  None = no faults
    # (and the fault stages vanish from the program entirely).
    faults: Optional[FaultSpec] = None

    @property
    def pfc_enabled(self) -> bool:
        return self.pfc if self.pfc is not None else (
            self.protocol == "rocev2")


def _bwhere(mask, new, old):
    """tree-where with a leading mask broadcast over trailing dims."""
    return jax.tree.map(
        lambda n, o: jnp.where(
            mask.reshape(mask.shape + (1,) * (n.ndim - mask.ndim)), n, o),
        new, old)


def _scatter_rows(tree_all, tree_rows, idx, n):
    """Scatter rows into per-flow pytrees; idx == n hits a trash row."""
    def one(a, b):
        pad = jnp.zeros((1,) + a.shape[1:], a.dtype)
        return jnp.concatenate([a, pad], 0).at[idx].set(b)[:n]
    return jax.tree.map(one, tree_all, tree_rows)


def _scatter_add(vec, idx, val, n):
    pad = jnp.zeros((1,) + vec.shape[1:], vec.dtype)
    return jnp.concatenate([vec, pad], 0).at[idx].add(val)[:n]


def _gather_rows(tree, idx, n):
    """Gather rows from per-flow pytrees; idx == n reads a zero trash row
    (the dual of :func:`_scatter_rows` — active-set and shard lanes use it
    to pull compacted row subsets)."""
    def one(a):
        pad = jnp.zeros((1,) + a.shape[1:], a.dtype)
        return jnp.concatenate([a, pad], 0)[idx]
    return jax.tree.map(one, tree)


def _set_rows(vec, idx, val, n):
    """Flat-vector row set with a trash slot at idx == n."""
    pad = jnp.zeros((1,) + vec.shape[1:], vec.dtype)
    return jnp.concatenate([vec, pad], 0).at[idx].set(val)[:n]


def _scatter_pipe(pipe, rows, slot, fidx, valid, h, n):
    """Scatter per-delivery message rows into the [H, N] return pipe at
    per-flow slots (each flow's ACK rides its own reverse-path latency).
    Invalid entries hit the trash slot past the flattened pipe."""
    flat_idx = jnp.where(valid, slot * n + fidx, h * n)

    def one(a, b):
        flat = a.reshape((h * n,) + a.shape[2:])
        pad = jnp.zeros((1,) + flat.shape[1:], a.dtype)
        out = jnp.concatenate([flat, pad], 0).at[flat_idx].set(b)
        return out[:h * n].reshape(a.shape)

    return jax.tree.map(one, pipe, rows)


def _hop_delays(cfg: FabricConfig) -> dict:
    """Static per-hop delay constants the program closes over.

    Returns K (per-link propagation, whole ticks), D_same/D_cross (ACK
    return-pipe ticks for same-ToR / cross-ToR flows) and PD (PFC
    pause-frame propagation ticks).  In "perhop" mode the return delay is
    the remainder of the hop-exact round trip — float propagation and ACK
    serialization are rounded ONCE here, so the realized uncongested RTT
    stays within a tick of ``h * (mtu_ser + ack_ser + 2 * prop)``; the
    folded mode (or a ``delay_ticks`` override) reproduces the legacy
    single-constant pipe with no per-hop propagation.
    """
    net = cfg.net
    tick_us = net.mtu_serialize_us
    folded = cfg.ack_path == "folded" or cfg.delay_ticks is not None
    if folded:
        if cfg.delay_ticks is not None:
            d = int(cfg.delay_ticks)
        else:
            d = max(1, round(net.base_rtt_us / tick_us) - 3)
        K, D_same, D_cross = 0, d, d
    else:
        prop_us = (cfg.hop_prop_us if cfg.hop_prop_us is not None
                   else net.hop_prop_effective_us)
        k_f = prop_us / tick_us
        a_f = net.ack_serialize_us / tick_us
        K = int(round(k_f))

        def ret(hops):
            # hops = one-way store-and-forward stage count (NIC included);
            # the fabric's forward pass realizes (hops-1)*(1+K) ticks, the
            # pipe carries the rest of the exact round trip
            rtt_f = hops * (1.0 + a_f + 2.0 * k_f)
            return max(1, int(round(rtt_f - (hops - 1) * (1 + K))))

        D_same, D_cross = ret(2), ret(4)
    if cfg.pfc_delay_ticks is not None:
        PD = max(0, int(cfg.pfc_delay_ticks))
    else:
        PD = K
    return dict(K=K, D_same=D_same, D_cross=D_cross, PD=PD,
                H=max(D_same, D_cross) + 2)


#: Chunk width of the sort-free ranker: candidates split into blocks of
#: this size; each block is resolved with a dense lower-triangle count and
#: blocks are combined through a scatter-add table + exclusive cumsum.
#: Intra-block work is O(M * CHUNK) and the cross-block table is
#: O(M / CHUNK * n_queues) memory, so CHUNK trades flat FLOPs against
#: table footprint; 256 keeps both small from 1K through 8K hosts.
_RANK_CHUNK = 256


def _rank_in_queue_argsort(qid: jax.Array, flag: jax.Array) -> jax.Array:
    """Stable-argsort reference ranker, O(M log M) — kept as a second
    independent implementation for the property tests (the hot path uses
    the sort-free :func:`_rank_in_queue`).  Same contract: rank among
    flag-set candidates of the same queue in candidate-index order, with
    an explicit ``-1`` fill at non-flagged entries."""
    m = qid.shape[0]
    key = qid * 2 + (~flag).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    sq = qid[order]
    start = jnp.searchsorted(sq, sq, side="left").astype(jnp.int32)
    rank_sorted = jnp.arange(m, dtype=jnp.int32) - start
    ranks = jnp.zeros((m,), jnp.int32).at[order].set(rank_sorted)
    return jnp.where(flag, ranks, -1)


def _rank_in_queue(qid: jax.Array, flag: jax.Array,
                   n_queues: int) -> jax.Array:
    """Rank of each candidate among flag-set candidates of the same queue,
    in candidate-index order; non-flagged entries are ``-1`` (explicit
    masked fill — callers must not read ranks where ``flag`` is unset).

    Sort-free and fully parallel (no sequential carry): candidates split
    into ``_RANK_CHUNK``-wide blocks; a single scatter-add builds the
    [n_blocks, n_queues] table of flagged counts per (block, queue), an
    exclusive cumsum over the block axis turns it into each block's
    per-queue starting rank, and a batched dense lower-triangle count
    resolves ordering within blocks.  O(M * CHUNK) flat work — the
    "scatter-add / segmented-cumsum" replacement for the old per-tick
    stable argsort (O(M log M) with sort constants); ``n_queues`` is
    static so the table is fixed-shape.
    """
    m = qid.shape[0]
    c = _RANK_CHUNK
    qid = qid.astype(jnp.int32)
    if m == 0:
        return jnp.zeros((0,), jnp.int32)
    pad = (-m) % c
    if pad:
        qid_p = jnp.concatenate(
            [qid, jnp.full((pad,), n_queues, jnp.int32)])
        flag_p = jnp.concatenate([flag, jnp.zeros((pad,), bool)])
    else:
        qid_p, flag_p = qid, flag
    nb = qid_p.shape[0] // c
    qc = qid_p.reshape(nb, c)
    fc = flag_p.reshape(nb, c)
    # cross-block base: flagged count of each (earlier block, same queue);
    # one flat scatter-add (non-flagged entries land in the n_queues trash
    # column) then an exclusive cumsum down the block axis
    qw = n_queues + 1
    blk = jnp.repeat(jnp.arange(nb, dtype=jnp.int32), c)
    slot = blk * qw + jnp.where(flag_p, qid_p, n_queues)
    tbl = jnp.zeros((nb * qw,), jnp.int32).at[slot].add(
        flag_p.astype(jnp.int32)).reshape(nb, qw)
    start = jnp.cumsum(tbl, axis=0) - tbl
    base = start.reshape(-1)[blk * qw + qid_p]
    # intra-block: dense strictly-lower-triangle same-queue count
    tril = jnp.tril(jnp.ones((c, c), bool), k=-1)
    intra = jnp.sum((qc[:, :, None] == qc[:, None, :])
                    & fc[:, None, :] & tril[None, :, :],
                    axis=2).astype(jnp.int32)
    ranks = base + intra.reshape(-1)
    return jnp.where(flag, ranks[:m], -1)


def _make_protocol(cfg: FabricConfig):
    """Resolve cfg -> (Protocol, ecn kmin/kmax in packets)."""
    net = cfg.net
    if cfg.protocol == "strack":
        p = make_strack_params(net, max_paths=cfg.max_paths)
        proto = make_strack_protocol(p)
        kmin_p = net.ecn_kmin_bytes / net.mtu_bytes
        kmax_p = net.ecn_kmax_bytes / net.mtu_bytes
        target_qdelay_us = p.target_qdelay_us
    elif cfg.protocol == "rocev2":
        rp = cfg.roce or make_roce_params(net)
        proto = make_rocev2_protocol(make_roce_fab_params(net, rp))
        # "ECN threshold to one BDP for DCQCN" (paper Section 4.1)
        kmin_p = rp.ecn_kmin_bdp * net.bdp_pkts
        kmax_p = rp.ecn_kmax_bdp * net.bdp_pkts
        target_qdelay_us = net.base_rtt_us
    else:
        raise ValueError(f"unknown protocol {cfg.protocol!r}; "
                         f"expected one of {PROTOCOLS}")
    return proto, kmin_p, kmax_p, target_qdelay_us


def _rto_us(cfg: "FabricConfig") -> float:
    """The resolved protocol's retransmission timeout (us) — the unit the
    chaos recovery gates and per-flap-window attribution derive from."""
    if cfg.protocol == "strack":
        return make_strack_params(cfg.net, max_paths=cfg.max_paths).rto_us
    rp = cfg.roce or make_roce_params(cfg.net)
    return make_roce_fab_params(cfg.net, rp).rto_us


def _make_program(topo: FatTree, n_flows: int, n_ticks: int,
                  cfg: FabricConfig, dep: Optional[DepSpec] = None,
                  n_real: Optional[int] = None):
    """Build the pure jnp fabric program for fixed (topology, N, ticks).

    Returns ``program(src, dst, total_pkts, tail_bytes, ent0, lb_code) ->
    (final_state, tick_metrics)`` — jittable and vmappable (the sweep
    helpers vmap it over stacked flow arrays).  ``lb_code`` is the traced
    ``LB_MODES`` index, so one compiled program serves every STrack spray
    mode (and every entropy seed / message-size pattern); ``tail_bytes``
    is each flow's odd-tail wire size (data, like sizes).  ``dep`` is the
    static message/dependency structure the program closes over; ``None``
    means one deps-free message per flow.

    Programs are expensive to build and trace: go through
    :func:`_get_program`, which caches them on the static dims.  Every
    call here bumps ``program_builds`` — the regression hook the cache
    tests key on.
    """
    global program_builds
    program_builds += 1
    if cfg.lb_mode not in LB_MODES:
        raise ValueError(f"unknown lb_mode {cfg.lb_mode!r}; "
                         f"expected one of {LB_MODES}")
    if cfg.ack_path not in ACK_PATHS:
        raise ValueError(f"unknown ack_path {cfg.ack_path!r}; "
                         f"expected one of {ACK_PATHS}")
    if cfg.trace_every < 0:
        raise ValueError(f"trace_every must be >= 0, got {cfg.trace_every}")
    # the event-horizon scan cannot stack a per-tick trace (its trip count
    # is data-dependent): warp runs are events-only summaries
    trace_every = 0 if cfg.time_warp else cfg.trace_every
    DP = int(cfg.shard) if int(cfg.shard) > 1 else 1
    A = int(cfg.active_cap) if cfg.active_cap else 0
    if cfg.kernel_backend not in KERNEL_BACKENDS:
        raise ValueError(f"unknown kernel_backend {cfg.kernel_backend!r}; "
                         f"expected one of {KERNEL_BACKENDS}")
    use_kernels = cfg.kernel_backend != "jnp"
    interpret = cfg.kernel_backend == "pallas_interpret"
    if use_kernels and DP > 1:
        raise ValueError(
            f"kernel_backend={cfg.kernel_backend!r} requires shard <= 1: "
            f"the sharded program's all_gather exchanges cannot run "
            f"inside a Pallas kernel body")
    if A < 0:
        raise ValueError(f"active_cap must be positive, got {A}")
    if A and trace_every:
        raise ValueError(
            "active_cap requires trace_every=0 (or time_warp): the dense "
            "trace samples all-flow means the active set skips")
    if DP > 1:
        if A:
            raise ValueError("active_cap and shard are mutually exclusive")
        if trace_every:
            raise ValueError(
                "shard requires trace_every=0 (or time_warp): the per-tick "
                "trace is not defined on the sharded program")
        n_dev = len(jax.devices())
        if n_dev < DP:
            raise ValueError(
                f"cfg.shard={DP} needs {DP} devices but only {n_dev} are "
                f"visible: run on a host with {DP} chips, or on the CPU "
                f"export "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={DP}")
    net = cfg.net
    proto, kmin_p, kmax_p, _ = _make_protocol(cfg)
    pfc = cfg.pfc_enabled
    # Static fault-shape gates (sim/faults.py): entry COUNTS decide which
    # chaos code paths exist in the trace — when a class is absent its
    # entire path vanishes, so fault-free programs stay bit-identical to
    # the pre-chaos fabric.  The VALUES (times, probabilities, seed) ride
    # in as the traced FaultData argument.
    faults = cfg.faults if cfg.faults is not None else FaultSpec()
    F_ROW = (2 * len(faults.link_flaps) + len(faults.uplink_flaps)
             + len(faults.host_flaps))
    F_NIC = len(faults.host_flaps)
    F_UP = len(faults.link_flaps) + len(faults.uplink_flaps)
    F_DEG = 2 * len(faults.link_degrade)
    F_COR = 2 * len(faults.link_corrupt) + len(faults.host_corrupt)
    FW = faults.n_flap_windows
    HAS_FAULTS = faults.total_entries > 0
    # per-flap-window retransmit attribution covers the flap plus two
    # RTOs of recovery afterglow
    rto_ticks = int(math.ceil(_rto_us(cfg) / net.mtu_serialize_us))
    at = ArrayTopo.from_fat_tree(topo)
    T, S, NH = at.n_tor, at.n_spine, at.n_hosts
    HPT = at.hosts_per_tor
    TS = T * S
    Q = 2 * TS + NH                     # tor_up + spine_down + host_down
    N = n_flows
    if N <= 0:
        raise ValueError("fabric program needs at least one flow")
    # NR: the "real" (pre-padding) flow count.  The sharded path pads the
    # flow axis to a device multiple with inert zero-packet flows; NIC
    # round-robin arbitration keys on NR so padded and unpadded programs
    # arbitrate identically (bit-exact shard-vs-unsharded parity).
    NR = int(n_real) if n_real is not None else N
    if DP > 1 and N % DP != 0:
        raise ValueError(f"sharded flow axis must be a multiple of "
                         f"shard={DP}, got {N} (callers pad with inert "
                         f"flows via _shard_pad_inputs)")
    if A >= N:
        A = 0  # cap >= N: the dense formulation is already minimal
    NL = N // DP                     # flow lanes per pod
    QRL = -(-(Q + 1) // DP)          # ring rows per pod (global trash incl.)
    QR = QRL * DP
    if dep is None:
        dep = _trivial_dep(range(N))
    n_msgs, n_groups = dep.n_msgs, dep.n_groups
    n_edges = int(dep.edge_parent.shape[0])

    tick_us = net.mtu_serialize_us
    drop_pkts = int(net.drop_bytes // net.mtu_bytes)
    buffer_pkts = int(cfg.switch_buffer_bytes // net.mtu_bytes)
    # worst-case same-tick arrivals at one queue: every ToR host injecting
    # data+probe (tor_up / host_down) or every spine/ToR handing down a pkt
    max_extra = max(T, S + 2 * HPT)
    if pfc:
        # lossless: PFC backpressure bounds the queues; data is only shed
        # at the (never-expected) ring hard cap
        data_drop_pkts = buffer_pkts + max_extra
        hard_pkts = data_drop_pkts
    else:
        data_drop_pkts = drop_pkts
        hard_pkts = drop_pkts + max_extra  # probes squeeze past data drop
    cap = hard_pkts + max_extra + 2
    hd = _hop_delays(cfg)
    K, D_same, D_cross, PD, H = (hd["K"], hd["D_same"], hd["D_cross"],
                                 hd["PD"], hd["H"])
    n_ports = NH + 2 * TS            # PFC delay-line width (nic | sd | up)

    mtu_f = jnp.float32(net.mtu_bytes)
    ack_f = jnp.float32(ACK_WIRE_BYTES)
    buffer_b = jnp.float32(cfg.switch_buffer_bytes)
    qrows = jnp.arange(Q, dtype=jnp.int32)
    is_up_row = qrows < TS
    spine_of_row = jnp.where(is_up_row, qrows % S, (qrows - TS) // T)
    host_tor = jnp.arange(NH, dtype=jnp.int32) // HPT

    def body(src, dst, total_pkts, tail_b, ent0, lb_code, arrival, fd):
        # Bump the retrace counter at TRACE time (python side effects fire
        # once per jax trace, not per run) — the job-batching regression
        # hook: bucketed batch sizes must not retrace this body.
        global program_traces
        program_traces += 1
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        total_pkts = jnp.asarray(total_pkts, jnp.int32)
        tail_b = jnp.asarray(tail_b, jnp.float32)
        lb_code = jnp.asarray(lb_code, jnp.int32)
        # per-MESSAGE earliest-launch tick (open-loop arrivals); plain
        # traced data, so one compiled program serves every arrival
        # pattern — all-zero degenerates to the closed-loop semantics
        arrival = jnp.asarray(arrival, jnp.int32)
        src_tor = src // HPT
        dst_tor = dst // HPT
        same_tor = src_tor == dst_tor
        iota_n = jnp.arange(N, dtype=jnp.int32)
        fixed_ent = ecmp_mix(src, dst, iota_n) % cfg.max_paths
        # per-flow ACK return latency: the reverse path's store-and-forward
        # pipeline (2 hops same-ToR, 4 cross-ToR; one constant in folded
        # mode where D_same == D_cross)
        dflow = jnp.where(same_tor, jnp.int32(D_same), jnp.int32(D_cross))

        if DP > 1:
            # pod-local offsets: flow lanes [foff, foff+NL), ring rows
            # [qoff, qoff+QRL) live on this pod; everything else replicated
            pod = jax.lax.axis_index("pod")
            foff = pod * NL
            qoff = pod * QRL

            def fslice(x):
                """This pod's [NL] slice of a replicated [N] flow vector."""
                return jax.lax.dynamic_slice_in_dim(x, foff, NL)

            def gath(tree):
                """Concatenate pod-local leading axes back to global."""
                return jax.tree.map(
                    lambda a: jax.lax.all_gather(a, "pod", tiled=True),
                    tree)

        def wire_bytes(flow, psn, probe):
            """Per-packet wire size: probes are ACK-sized, the final PSN
            of a message is its odd tail, everything else a full MTU."""
            f = jnp.clip(flow, 0, N - 1)
            tail = psn >= total_pkts[f] - 1
            return jnp.where(probe, ack_f,
                             jnp.where(tail, tail_b[f], mtu_f))

        if DP > 1:
            fl0, rcv0 = proto.init(fslice(total_pkts), fslice(tail_b),
                                   fslice(ent0))
            q_rows = QRL
        else:
            fl0, rcv0 = proto.init(total_pkts, tail_b, ent0)
            q_rows = Q + 1
        q0 = PktQ(flow=jnp.full((q_rows, cap), -1, jnp.int32),
                  psn=jnp.zeros((q_rows, cap), jnp.int32),
                  ts=jnp.zeros((q_rows, cap), jnp.float32),
                  probe=jnp.zeros((q_rows, cap), bool),
                  ecn=jnp.zeros((q_rows, cap), bool),
                  ent=jnp.zeros((q_rows, cap), jnp.int32),
                  ready=jnp.zeros((q_rows, cap), jnp.int32),
                  spine=jnp.zeros((q_rows, cap), jnp.int32))
        st0 = FabricState(
            flows=fl0, rcv=rcv0, q=q0,
            qhead=jnp.zeros((Q + 1,), jnp.int32),
            qsize=jnp.zeros((Q + 1,), jnp.int32),
            pipe=proto.empty_msgs(H, NL if DP > 1 else N),
            obl_rr=iota_n % cfg.max_paths,  # stagger oblivious spray starts
            drops=jnp.zeros((), jnp.int32),
            delivered=jnp.zeros((N,), jnp.float32),
            done_tick=jnp.full((N,), -1, jnp.int32),
            qbytes=jnp.zeros((Q + 1,), jnp.float32),
            ing_host=jnp.zeros((NH,), jnp.float32),
            ing_sd=jnp.zeros((S, T), jnp.float32),
            ing_up=jnp.zeros((T, S), jnp.float32),
            paused_nic=jnp.zeros((NH,), bool),
            paused_sd=jnp.zeros((S, T), bool),
            paused_up=jnp.zeros((T, S), bool),
            pfc_line=jnp.zeros((max(PD, 1), n_ports), bool),
            pauses=jnp.zeros((), jnp.int32),
            pending=dep.init_pending,
            msg_done=jnp.zeros((n_msgs,), bool),
            msg_release_tick=jnp.full((n_msgs,), -1, jnp.int32),
            msg_done_tick=jnp.full((n_msgs,), -1, jnp.int32),
            group_done_tick=jnp.full((n_groups,), -1, jnp.int32),
            act_overflow=jnp.zeros((), jnp.int32),
            ecn_marks=jnp.zeros((), jnp.int32),
            qdepth_hi=jnp.zeros((Q + 1,), jnp.int32),
            blackholed=jnp.zeros((), jnp.int32),
            corrupt_drops=jnp.zeros((), jnp.int32),
            tx_rows=jnp.zeros((Q + 1,), jnp.int32),
            win_retx=jnp.zeros((FW,), jnp.int32))

        # ---- kernel-backend dispatch ---------------------------------
        # The hot stages below are *core* functions over explicit
        # operands, called either inline (kernel_backend="jnp" — XLA
        # fuses them exactly as before) or through the fused-stage
        # Pallas kernels, which run the SAME core inside one
        # pallas_call: one implementation, two execution substrates,
        # bit-exact by construction (tests/test_fabric_kernels.py + the
        # fuzz suite's kernel leg).  The sharded program (DP > 1) keeps
        # its inline jnp stages: its all_gather exchanges cannot live in
        # a kernel body.
        if use_kernels:
            def _trans(core, args):
                return flow_transition_kernel(core, args,
                                              interpret=interpret)

            def _serve(core, args):
                return serve_enqueue_kernel(core, args,
                                            interpret=interpret)
        else:
            def _trans(core, args):
                return core(*args)
            _serve = _trans

        def timers_of(fl, now):
            return jax.vmap(lambda f: proto.on_timer(f, now))(fl)

        def empty_tx(n):
            return tp.TxPacket(
                valid=jnp.zeros((n,), bool),
                psn=jnp.zeros((n,), jnp.int32),
                entropy=jnp.zeros((n,), jnp.int32),
                is_rtx=jnp.zeros((n,), bool),
                is_probe=jnp.zeros((n,), bool))

        def dense_trans_core(flows0, due, sendable, eff_nic, src_, t):
            """Kernel-3 core, dense variant: due-ACK apply, timer sweep,
            next-packet offers and NIC round-robin arbitration over all
            N flow lanes (see flow_transition_kernel)."""
            now = t.astype(jnp.float32) * tick_us
            lanes = iota1(N)
            fl = jax.vmap(lambda f, m: proto.on_ack(f, m, now))(
                flows0, due)
            # Gated (dependency-pending) flows keep their init-time
            # timer state — their deadlines effectively start counting
            # at release, as in the oracle where timers are armed at
            # add_flow time.
            fl_t, probe_tx = jax.lax.cond(
                (t % cfg.timer_every) == 0,
                lambda f: timers_of(f, now),
                lambda f: (f, empty_tx(N)), fl)
            probe_valid = probe_tx.valid & sendable
            if pfc:
                # A paused NIC emits nothing.  Withhold the timer-state
                # commit for flows whose probe was blocked (their probe
                # deadline and spray state stay put), so the probe is
                # *delayed* until resume — as in the oracle, where it
                # waits in the paused NIC queue — not silently lost.
                blocked = probe_tx.valid & eff_nic[src_]
                fl = _bwhere(sendable & (~blocked), fl_t, fl)
                probe_valid = probe_valid & (~blocked)
            else:
                fl = _bwhere(sendable, fl_t, fl)
            fl_sent, tx = jax.vmap(
                lambda f: proto.next_packet(f, now))(fl)
            can_tx = tx.valid & sendable
            score = jnp.where(can_tx, (lanes - t) % NR, NR)
            best = jax.ops.segment_min(score, src_, num_segments=NH)
            sel = can_tx & (score == best[src_])
            if pfc:
                # a paused NIC injects nothing (state update withheld
                # too, so the flow re-offers the same packet next tick)
                sel = sel & (~eff_nic[src_])
            fl = _bwhere(sel, fl_sent, fl)
            return fl, tx, probe_tx, probe_valid, sel, can_tx

        def active_trans_core(flows0, pipe_cur, act_idx, eff_nic, src_,
                              t):
            """Kernel-3 core, active-set variant: the <= A released
            not-done lanes are gathered from the [N] flow state, stepped
            and scattered back inside the core, so the [A]-shaped flow
            pytrees never materialize outside the kernel call."""
            now = t.astype(jnp.float32) * tick_us
            lane_ok = act_idx < N
            act_clip = jnp.minimum(act_idx, N - 1)
            lane_src = src_[act_clip]
            due = _gather_rows(pipe_cur, act_idx, N)
            rows = _gather_rows(flows0, act_idx, N)
            rows = jax.vmap(lambda f, m: proto.on_ack(f, m, now))(
                rows, due)
            rows_t, probe_tx = jax.lax.cond(
                (t % cfg.timer_every) == 0,
                lambda f: timers_of(f, now),
                lambda f: (f, empty_tx(A)), rows)
            probe_valid = probe_tx.valid & lane_ok
            if pfc:
                blocked = probe_tx.valid & eff_nic[lane_src]
                rows = _bwhere(lane_ok & (~blocked), rows_t, rows)
                probe_valid = probe_valid & (~blocked)
            else:
                rows = _bwhere(lane_ok, rows_t, rows)
            rows_sent, tx = jax.vmap(
                lambda f: proto.next_packet(f, now))(rows)
            can_tx = tx.valid & lane_ok
            score = jnp.where(can_tx, (act_idx - t) % NR, NR)
            best = jax.ops.segment_min(score, lane_src,
                                       num_segments=NH)
            sel = can_tx & (score == best[lane_src])
            if pfc:
                sel = sel & (~eff_nic[lane_src])
            rows = _bwhere(sel, rows_sent, rows)
            fl = _scatter_rows(flows0, rows,
                               jnp.where(lane_ok, act_idx, N), N)
            # non-lane flows cannot change done-ness (only ACK
            # processing completes a flow, and every released not-done
            # flow is a lane), so per-lane done bits suffice for the
            # completion step
            done_lane = jax.vmap(proto.done)(rows)
            return (fl, tx, probe_tx, probe_valid, sel, can_tx,
                    done_lane)

        def serve_enqueue_core(qtree, qhead0, qsize0, paused_row, dst_,
                               dst_tor_, total_pkts_, tail_b_,
                               lane_flow, tx_psn, probe_psn, ent_d,
                               ent_p, inj_sp, inj_spp, sel, probe_valid,
                               inj_q, inj_qp, row_down, row_duty,
                               row_cor_p, fseed, t):
            """Kernel-1 core: fused queue-ring service + two-pass
            enqueue.  Serve: every unpaused queue pops its head packet
            once the head's departure-time lane says it has arrived
            (upstream serialization + link propagation accrued), with
            occupancy-fraction ECN marking.  Enqueue: fabric advances +
            NIC data/probe injections rank among same-queue candidates
            (all-pairs mask when small, the sort-free chunked ranker —
            kernel 2 — at scale), drop on occupancy and scatter into the
            flat rings with next-hop departure times (see
            serve_enqueue_kernel)."""
            now = t.astype(jnp.float32) * tick_us
            qrows_ = iota1(Q)
            is_up = qrows_ < TS
            spine_row = jnp.where(is_up, qrows_ % S, (qrows_ - TS) // T)

            def wire(flow, psn, probe):
                """Per-packet wire size: probes are ACK-sized, the final
                PSN of a message is its odd tail, else a full MTU."""
                f = jnp.clip(flow, 0, N - 1)
                tail = psn >= total_pkts_[f] - 1
                return jnp.where(probe, jnp.float32(ACK_WIRE_BYTES),
                                 jnp.where(tail, tail_b_[f],
                                           jnp.float32(net.mtu_bytes)))

            # serve: pop ready heads, ECN-mark on occupancy fraction
            qs = qsize0[:Q]
            if pfc:
                has = (qs > 0) & (~paused_row)
            else:
                has = qs > 0
            hidx = qhead0[:Q] % cap
            pop = PktQ(*[f[qrows_, hidx] for f in qtree])
            has = has & (pop.ready <= t)
            if row_duty is not None:
                # degraded rows serve only on duty-cycle-open ticks
                has = has & row_duty
            residual = jnp.maximum(qs - 1, 0).astype(jnp.float32)
            frac = jnp.clip((residual - kmin_p)
                            / jnp.maximum(kmax_p - kmin_p, 1e-9),
                            0.0, 1.0)
            dither = jnp.abs(jnp.sin(
                t.astype(jnp.float32) * 12.9898
                + qrows_.astype(jnp.float32) * 78.233))
            mark = has & (~pop.probe) & (frac > dither * 0.999)
            ecn_out = pop.ecn | mark
            served = has.astype(jnp.int32)
            qhead1 = qhead0.at[:Q].add(served)
            qsize1 = qsize0.at[:Q].add(-served)

            # chaos: a down link still serves (its buffer drains) but
            # everything it pops is blackholed; corruption drops data
            # packets on a counter-keyed u01 draw.  Both remove the
            # packet from the advance/delivery candidate set; PFC
            # dequeue accounting keeps the original ``has`` (the packet
            # really left the buffer).
            surv = has
            bh_add = jnp.zeros((), jnp.int32)
            cor_add = jnp.zeros((), jnp.int32)
            if row_down is not None:
                bh_add = jnp.sum(has & row_down).astype(jnp.int32)
                surv = surv & (~row_down)
            if row_cor_p is not None:
                u = fault_u01(fseed, qrows_, t, pop.psn)
                corrupt = surv & (~pop.probe) & (u < row_cor_p)
                cor_add = jnp.sum(corrupt).astype(jnp.int32)
                surv = surv & (~corrupt)

            fclip = jnp.clip(pop.flow, 0, N - 1)
            pop_bytes = wire(pop.flow, pop.psn, pop.probe)
            # fabric advance targets (tor_up -> spine_down -> host_down)
            adv_tgt = jnp.where(
                is_up, TS + spine_row * T + dst_tor_[fclip],
                2 * TS + dst_[fclip])[:2 * TS]
            adv_valid = surv[:2 * TS]

            # enqueue: fabric advances + data + probes
            L_ = lane_flow.shape[0]
            cand_qid = jnp.concatenate([adv_tgt, inj_q, inj_qp])
            cand_valid = jnp.concatenate([adv_valid, sel, probe_valid])
            now_l = jnp.full((L_,), now, jnp.float32)
            zb, ob = jnp.zeros((L_,), bool), jnp.ones((L_,), bool)
            # every enqueue (fabric advance or NIC injection) arrives at
            # the next stage after 1 tick of serialization + K ticks of
            # link propagation — the per-hop departure-time lane
            cand = PktQ(
                flow=jnp.concatenate(
                    [pop.flow[:2 * TS], lane_flow, lane_flow]),
                psn=jnp.concatenate(
                    [pop.psn[:2 * TS], tx_psn, probe_psn]),
                ts=jnp.concatenate([pop.ts[:2 * TS], now_l, now_l]),
                probe=jnp.concatenate([pop.probe[:2 * TS], zb, ob]),
                ecn=jnp.concatenate([ecn_out[:2 * TS], zb, zb]),
                ent=jnp.concatenate([pop.ent[:2 * TS], ent_d, ent_p]),
                ready=jnp.full((2 * TS + 2 * L_,), 0, jnp.int32)
                + t + 1 + K,
                spine=jnp.concatenate(
                    [pop.spine[:2 * TS], inj_sp, inj_spp]))
            # per-candidate wire bytes (PFC accounting is per-packet)
            cand_bytes = jnp.concatenate([
                pop_bytes[:2 * TS],
                wire(lane_flow, tx_psn, zb),
                wire(lane_flow, probe_psn, ob)])
            # Two-pass enqueue. Pass 1: drop decision from the occupancy
            # bound qsize + rank-among-valid (over-counts same-tick
            # earlier drops by design — the queue is at threshold then
            # anyway).  Pass 2: ring positions from rank-among-ACCEPTED,
            # so accepted packets pack the ring contiguously and a drop
            # never leaves a stale gap slot.  Small candidate counts use
            # the all-pairs mask (cheaper than the sweep); at scale the
            # sort-free chunked scatter-add ranker runs in O(M * CHUNK)
            # flat work.
            M = 2 * TS + 2 * L_
            if M <= 256:
                tril = (jax.lax.broadcasted_iota(jnp.int32, (M, M), 1)
                        < jax.lax.broadcasted_iota(jnp.int32, (M, M),
                                                   0))
                same_q = cand_qid[:, None] == cand_qid[None, :]

                def rank_among(flag):
                    return jnp.sum(same_q & flag[None, :] & tril,
                                   axis=1).astype(jnp.int32)
            elif use_kernels:
                def rank_among(flag):
                    return rank_in_queue_core(cand_qid, flag, Q)
            else:
                def rank_among(flag):
                    return _rank_in_queue(cand_qid, flag, Q)
            with jax.named_scope("fabric.queues.rank"):
                rank_v = rank_among(cand_valid)
            occ = qsize1[cand_qid] + rank_v
            dropped = cand_valid & (
                ((~cand.probe) & (occ >= data_drop_pkts))
                | (occ >= hard_pkts))
            accept = cand_valid & (~dropped)
            with jax.named_scope("fabric.queues.rank"):
                rank_a = rank_among(accept)
            pos = (qhead1[cand_qid] + qsize1[cand_qid] + rank_a) % cap
            flat_idx = jnp.where(accept, cand_qid * cap + pos, Q * cap)
            q1 = PktQ(*[f.reshape(-1).at[flat_idx].set(v)
                        .reshape(Q + 1, cap)
                        for f, v in zip(qtree, cand)])
            added = jax.ops.segment_sum(
                accept.astype(jnp.int32),
                jnp.where(accept, cand_qid, Q), num_segments=Q + 1)
            qsize2 = (qsize1 + added).at[Q].set(0)
            qhead2 = qhead1.at[Q].set(0)
            drops_add = jnp.sum(dropped).astype(jnp.int32)
            return (q1, qhead2, qsize2, pop, has, surv, ecn_out,
                    pop_bytes, cand_qid, cand_bytes, accept, drops_add,
                    bh_add, cor_add)

        def tick(st: FabricState, t):
            """One dense tick at tick-index ``t`` -> (new_state, can_any).

            ``can_any`` is whether any released flow offered a data packet
            this tick — the send half of the idleness test the time-warp
            scan uses (timer/pacing/pipe wakeups are handled by
            ``warp_target``).

            Stage order (reordered from the historical serve-first
            layout so each hot stage is one kernel call; equivalent
            because the transport lanes never read this tick's pops, and
            the return-pipe slot the receivers write, (t + D[flow]) % H
            with 1 <= D[flow] <= H - 2, is always distinct from the slot
            t % H the transport stage reads and clears): dependency
            gate; PFC effective-pause masks; per-flow transport lanes
            (kernel 3); spray/entropy + ECMP injection targets; fused
            ring service + enqueue (kernel 1, ranking via kernel 2);
            deliveries -> receivers + return-pipe writes; PFC
            accounting; completion.
            """
            now = t.astype(jnp.float32) * tick_us

            with jax.named_scope("fabric.gate"):
                # ---- 0. dependency gate: a message is sendable the tick its
                # pending-dep counter reaches zero AND its arrival tick has
                # come (deps-free, arrival-0 traces: always) ------------------
                sendable_msg = (st.pending <= 0) & (arrival <= t)
                sendable = sendable_msg[dep.msg_of_flow]
                msg_release_tick = jnp.where(
                    sendable_msg & (st.msg_release_tick < 0),
                    t.astype(jnp.int32), st.msg_release_tick)

            with jax.named_scope("fabric.pfc"):
                # ---- 0b. PFC effective-pause masks: the decision from PD
                # ticks ago (pause frames propagate one hop upstream), read
                # by both the NIC gate (transport) and the serve step -------
                if pfc:
                    if PD > 0:
                        eff = st.pfc_line[t % PD]
                        eff_nic = eff[:NH]
                        eff_sd = eff[NH:NH + TS].reshape(S, T)
                        eff_up = eff[NH + TS:].reshape(T, S)
                    else:
                        eff_nic, eff_sd, eff_up = (st.paused_nic,
                                                   st.paused_sd,
                                                   st.paused_up)
                    paused_row = jnp.concatenate(
                        [eff_up.reshape(-1), eff_sd.reshape(-1),
                         jnp.zeros((NH,), bool)])
                else:
                    # None leaves vanish under pytree flattening, so the
                    # kernel wrappers pass these through untouched
                    eff_nic = paused_row = None

            with jax.named_scope("fabric.faults"):
                # ---- 0c. chaos masks: per-tick link state from the traced
                # fault schedule (sim/faults.py).  Entry counts are static,
                # so every branch below vanishes from fault-free programs;
                # inactive windows (t outside [t0, t1)) scatter into the
                # trash row, so inert entries are exact no-ops.
                ti = t.astype(jnp.int32)
                if F_ROW > 0:
                    f_act = (fd.flap_row_t0 <= ti) & (ti < fd.flap_row_t1)
                    row_down = jnp.zeros((Q + 1,), bool).at[
                        jnp.where(f_act, fd.flap_row, Q)].set(True)[:Q]
                else:
                    row_down = None
                if F_NIC > 0:
                    n_act = (fd.flap_nic_t0 <= ti) & (ti < fd.flap_nic_t1)
                    nic_down = jnp.zeros((NH + 1,), bool).at[
                        jnp.where(n_act, fd.flap_nic, NH)].set(True)[:NH]
                else:
                    nic_down = None
                if F_DEG > 0:
                    d_act = (fd.deg_t0 <= ti) & (ti < fd.deg_t1)
                    d_closed = d_act & (~duty_open(ti, fd.deg_num))
                    row_duty = jnp.ones((Q + 1,), bool).at[
                        jnp.where(d_closed, fd.deg_row, Q)].set(False)[:Q]
                else:
                    row_duty = None
                if F_COR > 0:
                    c_act = (fd.cor_t0 <= ti) & (ti < fd.cor_t1)
                    row_cor_p = jnp.zeros((Q + 1,), jnp.float32).at[
                        jnp.where(c_act, fd.cor_row, Q)].max(fd.cor_p)[:Q]
                    fseed = fd.seed
                else:
                    row_cor_p = None
                    fseed = None
                if F_UP > 0:
                    # flapped uplinks leave the ECMP candidate set for the
                    # flap window.  Live spines in ascending order via a
                    # stable argsort on the down-mask — exactly the static
                    # live_list construction, so with no flap active this is
                    # bit-identical to at.ecmp_spine.
                    u_act = (fd.flap_up_t0 <= ti) & (ti < fd.flap_up_t1)
                    up_down = jnp.zeros((TS + 1,), bool).at[
                        jnp.where(u_act, fd.flap_up, TS)].set(
                        True)[:TS].reshape(T, S)
                    live_now = at.live_mask & (~up_down)
                    n_live_now = jnp.maximum(
                        jnp.sum(live_now, axis=1).astype(jnp.int32), 1)
                    live_order = jnp.argsort(~live_now, axis=1,
                                             stable=True).astype(jnp.int32)

                    def pick_spine(s_, d_, e_):
                        tor_ = s_ // HPT
                        k_ = ecmp_mix(s_, d_, e_) % n_live_now[tor_]
                        return live_order[tor_, k_]
                else:
                    pick_spine = at.ecmp_spine

            with jax.named_scope("fabric.transport"):
                # ---- 1. transport lanes: due ACKs, timers, sends (kernel 3)
                # Three equivalent lane formulations of the same per-flow
                # steps (all bit-exact in observables — the fuzz suite pins
                # them against each other):
                #   * dense (default): lanes are all N flows,
                #   * active-set: lanes are the <= A flows that are released
                #     and not done, compacted with a fill-value nonzero (the
                #     ascending index order preserves candidate order, hence
                #     ranks, drops and ring layout),
                #   * sharded: this pod's NL flow lanes; NIC offers cross pods
                #     through an all_gather so arbitration stays global.
                # The transport stage reads + clears return-pipe slot t % H
                # BEFORE the receivers (stage 3 below) write slot
                # (t + D[flow]) % H — always a different slot, so this is
                # order-independent.
                cur = t % H
                overflow = jnp.zeros((), jnp.int32)
                if DP > 1:
                    due = jax.tree.map(lambda a: a[cur], st.pipe)
                    flows_l = jax.vmap(lambda f, m: proto.on_ack(f, m, now))(
                        st.flows, due)
                    pipe = st.pipe._replace(valid=st.pipe.valid.at[cur].set(
                        jnp.zeros((NL,), bool)))
                    flows_t_l, probe_tx_l = jax.lax.cond(
                        (t % cfg.timer_every) == 0,
                        lambda f: timers_of(f, now),
                        lambda f: (f, empty_tx(NL)), flows_l)
                    probe_tx = gath(probe_tx_l)
                    probe_valid = probe_tx.valid & sendable
                    if pfc:
                        blocked = probe_tx.valid & eff_nic[src]
                        flows_l = _bwhere(fslice(sendable & (~blocked)),
                                          flows_t_l, flows_l)
                        probe_valid = probe_valid & (~blocked)
                    else:
                        flows_l = _bwhere(fslice(sendable), flows_t_l,
                                          flows_l)
                    flows_sent_l, tx_l = jax.vmap(
                        lambda f: proto.next_packet(f, now))(flows_l)
                    tx = gath(tx_l)
                    can_tx = tx.valid & sendable
                    score = jnp.where(can_tx, (iota_n - t) % NR, NR)
                    best = jax.ops.segment_min(score, src, num_segments=NH)
                    sel = can_tx & (score == best[src])
                    if pfc:
                        sel = sel & (~eff_nic[src])
                    flows = _bwhere(fslice(sel), flows_sent_l, flows_l)
                    lane_flow, lane_src, lane_dst = iota_n, src, dst
                    lane_same, lane_stor = same_tor, src_tor
                    lane_fix, lane_rr = fixed_ent, st.obl_rr
                    lane_idx, L = iota_n, N
                elif A:
                    # active set: released, not-yet-done flows (ascending
                    # flow index; fill lanes read/write the trash row).  The
                    # compaction + overflow check stay outside the core
                    # (nonzero's static-size fill semantics); the gathered
                    # transitions run inside it.
                    done_prev = jax.vmap(proto.done)(st.flows)
                    act_mask = sendable & (~done_prev)
                    act_idx = jnp.nonzero(
                        act_mask, size=A, fill_value=N)[0].astype(jnp.int32)
                    lane_ok = act_idx < N
                    act_clip = jnp.minimum(act_idx, N - 1)
                    overflow = (jnp.sum(act_mask) > A).astype(jnp.int32)
                    pipe_cur = jax.tree.map(lambda a: a[cur], st.pipe)
                    (flows, tx, probe_tx, probe_valid, sel, can_tx,
                     done_lane) = _trans(
                        active_trans_core,
                        (st.flows, pipe_cur, act_idx, eff_nic, src, t))
                    pipe = st.pipe._replace(valid=st.pipe.valid.at[cur].set(
                        jnp.zeros((N,), bool)))
                    lane_flow, lane_src = act_clip, src[act_clip]
                    lane_dst = dst[act_clip]
                    lane_same, lane_stor = (same_tor[act_clip],
                                            src_tor[act_clip])
                    lane_fix, lane_rr = (fixed_ent[act_clip],
                                         st.obl_rr[act_clip])
                    lane_idx, L = act_idx, A
                else:
                    due = jax.tree.map(lambda a: a[cur], st.pipe)
                    flows, tx, probe_tx, probe_valid, sel, can_tx = _trans(
                        dense_trans_core,
                        (st.flows, due, sendable, eff_nic, src, t))
                    pipe = st.pipe._replace(valid=st.pipe.valid.at[cur].set(
                        jnp.zeros((N,), bool)))
                    lane_flow, lane_src, lane_dst = iota_n, src, dst
                    lane_same, lane_stor = same_tor, src_tor
                    lane_fix, lane_rr = fixed_ent, st.obl_rr
                    lane_idx, L = iota_n, N

            with jax.named_scope("fabric.route"):
                if not proto.uses_spray:
                    ent = tx.entropy
                    ent_probe = probe_tx.entropy
                    obl_rr = st.obl_rr
                else:
                    # lb_mode is a traced scalar (LB_MODES index) so sweeps can
                    # vmap spray modes through ONE compiled program; the
                    # selects below are index arithmetic, not extra queue work.
                    is_obl = lb_code == 1
                    is_fix = lb_code == 2
                    ent_obl = (lane_rr + 1) % cfg.max_paths
                    ent = jnp.where(is_obl, ent_obl,
                                    jnp.where(is_fix, lane_fix, tx.entropy))
                    ent_probe = jnp.where(
                        is_obl, ent_obl,
                        jnp.where(is_fix, lane_fix, probe_tx.entropy))
                    if A:
                        obl_rr = _set_rows(
                            st.obl_rr, jnp.where(is_obl & sel, lane_idx, N),
                            ent_obl, N)
                    else:
                        obl_rr = jnp.where(is_obl & sel, ent_obl, st.obl_rr)

                spine = pick_spine(lane_src, lane_dst, ent)
                inj_q = jnp.where(lane_same, 2 * TS + lane_dst,
                                  lane_stor * S + spine)
                spine_p = pick_spine(lane_src, lane_dst, ent_probe)
                inj_qp = jnp.where(lane_same, 2 * TS + lane_dst,
                                   lane_stor * S + spine_p)

            with jax.named_scope("fabric.faults"):
                # retransmit attempts COMMITTED this tick (before any NIC
                # blackhole: the attempt happened even into a dead cable) —
                # attributed to active flap windows below
                if FW > 0:
                    rtx_n = jnp.sum(sel & tx.is_rtx).astype(jnp.int32)
                bh_nic = jnp.zeros((), jnp.int32)
                if nic_down is not None:
                    # host->ToR uplink down: the flow commits its send state
                    # (the NIC transmitted into a dead cable) but the packet
                    # never becomes an enqueue candidate — the sender learns
                    # via silence, then RTO / SACK / go-back-N
                    ln_down = nic_down[lane_src]
                    bh_nic = (jnp.sum(sel & ln_down)
                              + jnp.sum(probe_valid & ln_down)
                              ).astype(jnp.int32)
                    sel = sel & (~ln_down)
                    probe_valid = probe_valid & (~ln_down)

            with jax.named_scope("fabric.queues"):
                # ---- 2. fused ring service + enqueue (kernels 1 + 2) -------
                if DP > 1:
                    # Inline jnp: the inter-pod hop — each pod pops its own
                    # ring rows' heads and the [~Q x 7 scalar] head fields
                    # cross pods in one all_gather; on enqueue each pod
                    # writes only the ring rows it owns (the accept /
                    # position math is replicated, so every pod agrees).
                    qs = st.qsize[:Q]
                    if pfc:
                        has = (qs > 0) & (~paused_row)
                    else:
                        has = qs > 0
                    qhead_pad = jnp.pad(st.qhead, (0, QR - (Q + 1)))
                    hidx_l = jax.lax.dynamic_slice_in_dim(
                        qhead_pad, qoff, QRL) % cap
                    pop_l = PktQ(*[f[jnp.arange(QRL), hidx_l]
                                   for f in st.q])
                    pop = PktQ(*[a[:Q] for a in gath(pop_l)])
                    has = has & (pop.ready <= t)
                    if row_duty is not None:
                        has = has & row_duty
                    residual = jnp.maximum(qs - 1, 0).astype(jnp.float32)
                    frac = jnp.clip((residual - kmin_p)
                                    / jnp.maximum(kmax_p - kmin_p, 1e-9),
                                    0.0, 1.0)
                    dither = jnp.abs(jnp.sin(
                        t.astype(jnp.float32) * 12.9898
                        + qrows.astype(jnp.float32) * 78.233))
                    mark = has & (~pop.probe) & (frac > dither * 0.999)
                    ecn_out = pop.ecn | mark
                    served = has.astype(jnp.int32)
                    qhead = st.qhead.at[:Q].add(served)
                    qsize = st.qsize.at[:Q].add(-served)
                    # chaos blackhole/corruption — replicated math, identical
                    # on every pod (see serve_enqueue_core for semantics)
                    surv = has
                    bh_add = jnp.zeros((), jnp.int32)
                    cor_add = jnp.zeros((), jnp.int32)
                    if row_down is not None:
                        bh_add = jnp.sum(has & row_down).astype(jnp.int32)
                        surv = surv & (~row_down)
                    if row_cor_p is not None:
                        u = fault_u01(fseed, qrows, ti, pop.psn)
                        corrupt = surv & (~pop.probe) & (u < row_cor_p)
                        cor_add = jnp.sum(corrupt).astype(jnp.int32)
                        surv = surv & (~corrupt)
                    fclip = jnp.clip(pop.flow, 0, N - 1)
                    pop_bytes = wire_bytes(pop.flow, pop.psn, pop.probe)
                    adv_tgt = jnp.where(
                        is_up_row, TS + spine_of_row * T + dst_tor[fclip],
                        2 * TS + dst[fclip])[:2 * TS]
                    adv_valid = surv[:2 * TS]
                    cand_qid = jnp.concatenate([adv_tgt, inj_q, inj_qp])
                    cand_valid = jnp.concatenate(
                        [adv_valid, sel, probe_valid])
                    now_l = jnp.full((L,), now, jnp.float32)
                    zb, ob = jnp.zeros((L,), bool), jnp.ones((L,), bool)
                    cand = PktQ(
                        flow=jnp.concatenate(
                            [pop.flow[:2 * TS], lane_flow, lane_flow]),
                        psn=jnp.concatenate(
                            [pop.psn[:2 * TS], tx.psn, probe_tx.psn]),
                        ts=jnp.concatenate(
                            [pop.ts[:2 * TS], now_l, now_l]),
                        probe=jnp.concatenate(
                            [pop.probe[:2 * TS], zb, ob]),
                        ecn=jnp.concatenate([ecn_out[:2 * TS], zb, zb]),
                        ent=jnp.concatenate(
                            [pop.ent[:2 * TS], ent, ent_probe]),
                        ready=jnp.full((2 * TS + 2 * L,), 0, jnp.int32)
                        + t + 1 + K,
                        spine=jnp.concatenate(
                            [pop.spine[:2 * TS], spine, spine_p]))
                    cand_bytes = jnp.concatenate([
                        pop_bytes[:2 * TS],
                        wire_bytes(lane_flow, tx.psn, zb),
                        wire_bytes(lane_flow, probe_tx.psn, ob)])
                    M = 2 * TS + 2 * L
                    if M <= 256:
                        tril = jnp.tril(jnp.ones((M, M), bool), k=-1)
                        same_q = cand_qid[:, None] == cand_qid[None, :]

                        def rank_among(flag):
                            return jnp.sum(same_q & flag[None, :] & tril,
                                           axis=1).astype(jnp.int32)
                    else:
                        def rank_among(flag):
                            return _rank_in_queue(cand_qid, flag, Q)
                    with jax.named_scope("fabric.queues.rank"):
                        rank_v = rank_among(cand_valid)
                    occ = qsize[cand_qid] + rank_v
                    dropped = cand_valid & (
                        ((~cand.probe) & (occ >= data_drop_pkts))
                        | (occ >= hard_pkts))
                    accept = cand_valid & (~dropped)
                    with jax.named_scope("fabric.queues.rank"):
                        rank_a = rank_among(accept)
                    pos = (qhead[cand_qid] + qsize[cand_qid] + rank_a) % cap
                    ownq = accept & (cand_qid >= qoff) \
                        & (cand_qid < qoff + QRL)
                    flat_idx = jnp.where(
                        ownq, (cand_qid - qoff) * cap + pos, QRL * cap)

                    def _wrow(f, v):
                        flat = f.reshape(-1)
                        pad1 = jnp.zeros((1,), f.dtype)
                        out = jnp.concatenate([flat, pad1], 0).at[flat_idx]
                        return out.set(v)[:QRL * cap].reshape(QRL, cap)

                    q = PktQ(*[_wrow(f, v) for f, v in zip(st.q, cand)])
                    added = jax.ops.segment_sum(
                        accept.astype(jnp.int32),
                        jnp.where(accept, cand_qid, Q), num_segments=Q + 1)
                    qsize = (qsize + added).at[Q].set(0)
                    qhead = qhead.at[Q].set(0)
                    drops = st.drops + jnp.sum(dropped).astype(jnp.int32)
                else:
                    (q, qhead, qsize, pop, has, surv, ecn_out, pop_bytes,
                     cand_qid, cand_bytes, accept, drops_add, bh_add,
                     cor_add) = _serve(
                        serve_enqueue_core,
                        (st.q, st.qhead, st.qsize, paused_row, dst,
                         dst_tor, total_pkts, tail_b, lane_flow, tx.psn,
                         probe_tx.psn, ent, ent_probe, spine, spine_p, sel,
                         probe_valid, inj_q, inj_qp, row_down, row_duty,
                         row_cor_p, fseed, t))
                    fclip = jnp.clip(pop.flow, 0, N - 1)
                    drops = st.drops + drops_add

            with jax.named_scope("fabric.receive"):
                # ---- 3. deliveries -> per-flow receivers (one host = one q)
                # (surv, not has: blackholed/corrupted packets left their
                # buffer but never arrive)
                del_has = surv[2 * TS:]
                del_flow = fclip[2 * TS:]
                slot_del = (t + dflow[del_flow]) % H
                if DP > 1:
                    # receiver + return-pipe state live on the flow-owner
                    # pod: every pod walks the global delivery rows but
                    # gathers / commits only the flows it owns (trash row
                    # otherwise)
                    own = del_has & (del_flow >= foff) \
                        & (del_flow < foff + NL)
                    lrow = jnp.where(own, del_flow - foff, NL)
                    rrows = _gather_rows(st.rcv, lrow, NL)
                    commit, fidx, n_lanes = own, lrow, NL
                else:
                    rrows = jax.tree.map(lambda a: a[del_flow], st.rcv)
                    commit, fidx, n_lanes = del_has, del_flow, N
                rnew, sack = jax.vmap(
                    lambda r, psn, sz, ecn, ent_, ts, pb: proto.on_data(
                        r, psn, sz, ecn, ent_, ts, pb, now))(
                    rrows, pop.psn[2 * TS:], pop_bytes[2 * TS:],
                    ecn_out[2 * TS:], pop.ent[2 * TS:],
                    pop.ts[2 * TS:], pop.probe[2 * TS:])
                rnew = _bwhere(commit, rnew, rrows)
                rcv = _scatter_rows(st.rcv, rnew,
                                    jnp.where(commit, fidx, n_lanes),
                                    n_lanes)
                delivered = _scatter_add(
                    st.delivered,
                    jnp.where(del_has & (~pop.probe[2 * TS:]), del_flow, N),
                    pop_bytes[2 * TS:], N)
                # ECN observability: marked data packets counted at host
                # delivery (outside the kernel cores, so identical across
                # every lane formulation and kernel backend; warp-safe —
                # skipped ticks deliver nothing)
                ecn_add = jnp.sum(del_has & ecn_out[2 * TS:]
                                  & (~pop.probe[2 * TS:])).astype(jnp.int32)

                # write emitted messages into the return pipe at slot
                # t + D[flow]: each flow's ACK rides its own reverse path
                # (never the slot the transport stage cleared this tick:
                # 1 <= D[flow] <= H - 2)
                sack_valid = sack.valid & commit
                pipe = _scatter_pipe(pipe, sack._replace(valid=sack_valid),
                                     slot_del, fidx, sack_valid, H, n_lanes)

            with jax.named_scope("fabric.pfc"):
                # ---- 6b. PFC: per-ingress accounting + pause/resume masks
                # Ingress attribution is derivable per packet: a packet's
                # port at any switch follows from (flow src/dst, queue row,
                # entropy), so the counters are maintained incrementally
                # without storing a port field in the ring.  Accounting is
                # per-packet WIRE bytes: odd tail packets and 64B probes
                # count their real size, not a whole MTU (``events.Switch``
                # semantics).
                if pfc:
                    # dequeues leaving a switch buffer
                    f_up, f_sd, f_hd = (fclip[:TS], fclip[TS:2 * TS],
                                        fclip[2 * TS:])
                    ing_host = _scatter_add(
                        st.ing_host, jnp.where(has[:TS], src[f_up], NH),
                        -pop_bytes[:TS], NH)
                    sd_i = jnp.arange(TS, dtype=jnp.int32)
                    sd_s = sd_i // T   # spine of spine_down row TS + s*T + t
                    up_flat = st.ing_up.reshape(-1)
                    up_flat = _scatter_add(
                        up_flat,
                        jnp.where(has[TS:2 * TS],
                                  src_tor[f_sd] * S + sd_s, TS),
                        -pop_bytes[TS:2 * TS], TS)
                    # the spine that handed the packet down is the ring's
                    # injection-time spine lane — re-deriving it from ECMP
                    # would diverge once fault schedules make the candidate
                    # masks time-varying
                    pkt_spine = pop.spine[2 * TS:]
                    hd_same = same_tor[f_hd]
                    served_hd = has[2 * TS:]
                    ing_host = _scatter_add(
                        ing_host,
                        jnp.where(served_hd & hd_same, src[f_hd], NH),
                        -pop_bytes[2 * TS:], NH)
                    sd_flat = st.ing_sd.reshape(-1)
                    sd_flat = _scatter_add(
                        sd_flat,
                        jnp.where(served_hd & (~hd_same),
                                  pkt_spine * T + host_tor, TS),
                        -pop_bytes[2 * TS:], TS)
                    # enqueues entering a switch buffer
                    # t*S+s of the source row
                    up_i = jnp.arange(TS, dtype=jnp.int32)
                    up_flat = _scatter_add(
                        up_flat, jnp.where(accept[:TS], up_i, TS),
                        cand_bytes[:TS], TS)
                    sd_flat = _scatter_add(
                        sd_flat, jnp.where(accept[TS:2 * TS], sd_i, TS),
                        cand_bytes[TS:2 * TS], TS)
                    acc_data = accept[2 * TS:2 * TS + L]
                    acc_probe = accept[2 * TS + L:]
                    ing_host = _scatter_add(
                        ing_host, jnp.where(acc_data, lane_src, NH),
                        cand_bytes[2 * TS:2 * TS + L], NH)
                    ing_host = _scatter_add(
                        ing_host, jnp.where(acc_probe, lane_src, NH),
                        cand_bytes[2 * TS + L:], NH)
                    ing_sd = sd_flat.reshape(S, T)
                    ing_up = up_flat.reshape(T, S)

                    # byte-accurate shared-buffer occupancy (served bytes out,
                    # accepted bytes in) for the dynamic threshold
                    qbytes = st.qbytes.at[:Q].add(
                        -jnp.where(has, pop_bytes, 0.0))
                    add_b = jax.ops.segment_sum(
                        jnp.where(accept, cand_bytes, 0.0),
                        jnp.where(accept, cand_qid, Q), num_segments=Q + 1)
                    qbytes = (qbytes + add_b).at[Q].set(0.0)
                    qsz_b = qbytes[:Q]
                    tor_occ = (qsz_b[:TS].reshape(T, S).sum(1)
                               + qsz_b[2 * TS:].reshape(T, HPT).sum(1))
                    spine_occ = qsz_b[TS:2 * TS].reshape(S, T).sum(1)
                    a = cfg.pfc_alpha
                    xoff_tor = a * jnp.maximum(buffer_b - tor_occ, 0.0) \
                        / (1 + a)
                    xoff_spine = a * jnp.maximum(buffer_b - spine_occ, 0.0) \
                        / (1 + a)

                    # the gate chains on the switch's DECISION state; the
                    # effective (upstream) state lags it by the pause-frame
                    # propagation delay via the pfc_line ring
                    paused_nic = pfc_gate(st.paused_nic, ing_host,
                                          xoff_tor[host_tor], cfg.pfc_xon_frac)
                    paused_sd = pfc_gate(st.paused_sd, ing_sd,
                                         xoff_tor[None, :], cfg.pfc_xon_frac)
                    paused_up = pfc_gate(st.paused_up, ing_up,
                                         xoff_spine[None, :], cfg.pfc_xon_frac)
                    pauses = st.pauses + (
                        jnp.sum(paused_nic & ~st.paused_nic)
                        + jnp.sum(paused_sd & ~st.paused_sd)
                        + jnp.sum(paused_up & ~st.paused_up)).astype(jnp.int32)
                    if PD > 0:
                        dec = jnp.concatenate(
                            [paused_nic, paused_sd.reshape(-1),
                             paused_up.reshape(-1)])
                        pfc_line = st.pfc_line.at[t % PD].set(dec)
                    else:
                        pfc_line = st.pfc_line
                else:
                    qbytes = st.qbytes
                    ing_host, ing_sd, ing_up = (st.ing_host, st.ing_sd,
                                                st.ing_up)
                    paused_nic, paused_sd, paused_up = (
                        st.paused_nic, st.paused_sd, st.paused_up)
                    pfc_line = st.pfc_line
                    pauses = st.pauses

            with jax.named_scope("fabric.complete"):
                # ---- 7. completion + metrics --------------------------------
                if DP > 1:
                    done = jax.lax.all_gather(
                        jax.vmap(proto.done)(flows), "pod", tiled=True)
                elif A:
                    # done lanes update in place from the core's per-lane
                    # done bits (see active_trans_core)
                    done = _set_rows(
                        done_prev, jnp.where(lane_ok, act_idx, N),
                        done_lane, N)
                else:
                    done = jax.vmap(proto.done)(flows)
                done_tick = jnp.where(done & (st.done_tick < 0),
                                      t.astype(jnp.int32), st.done_tick)

                # message completion: all sub-flows done; newly-completed
                # messages decrement their children's pending-dep counters
                # (the children become sendable NEXT tick, step 0 above)
                undone = jax.ops.segment_sum((~done).astype(jnp.int32),
                                             dep.msg_of_flow,
                                             num_segments=n_msgs)
                msg_done = undone == 0
                newly = msg_done & (~st.msg_done)
                if n_edges > 0:
                    dec = jax.ops.segment_sum(
                        newly[dep.edge_parent].astype(jnp.int32),
                        dep.edge_child, num_segments=n_msgs)
                    pending = st.pending - dec
                else:
                    pending = st.pending
                msg_done_tick = jnp.where(newly, t.astype(jnp.int32),
                                          st.msg_done_tick)
                g_undone = jax.ops.segment_sum((~msg_done).astype(jnp.int32),
                                               dep.group_of_msg,
                                               num_segments=n_groups)
                group_done_tick = jnp.where(
                    (g_undone == 0) & (st.group_done_tick < 0),
                    t.astype(jnp.int32), st.group_done_tick)

                # chaos observability: accepted data injections per target
                # row (the entropy-shift gates read this) + per-flap-window
                # retransmit attribution.  Both are exact on warp runs:
                # skipped ticks inject nothing.
                acc_data_l = accept[2 * TS:2 * TS + L]
                tx_rows = st.tx_rows.at[
                    jnp.where(acc_data_l, inj_q, Q)].add(1)
                if FW > 0:
                    in_win = (fd.win_t0 <= ti) \
                        & (ti < fd.win_t1 + 2 * rto_ticks)
                    win_retx = st.win_retx + jnp.where(in_win, rtx_n, 0)
                else:
                    win_retx = st.win_retx

                new_st = FabricState(
                    flows=flows, rcv=rcv, q=q, qhead=qhead, qsize=qsize,
                    pipe=pipe, obl_rr=obl_rr, drops=drops, delivered=delivered,
                    done_tick=done_tick, qbytes=qbytes, ing_host=ing_host,
                    ing_sd=ing_sd, ing_up=ing_up, paused_nic=paused_nic,
                    paused_sd=paused_sd, paused_up=paused_up,
                    pfc_line=pfc_line, pauses=pauses,
                    pending=pending, msg_done=msg_done,
                    msg_release_tick=msg_release_tick,
                    msg_done_tick=msg_done_tick,
                    group_done_tick=group_done_tick,
                    act_overflow=st.act_overflow + overflow,
                    ecn_marks=st.ecn_marks + ecn_add,
                    # post-enqueue depth max; identity on warp-skipped ticks
                    qdepth_hi=jnp.maximum(st.qdepth_hi, qsize),
                    blackholed=st.blackholed + bh_add + bh_nic,
                    corrupt_drops=st.corrupt_drops + cor_add,
                    tx_rows=tx_rows, win_retx=win_retx)
            with jax.named_scope("fabric.warp"):
                can_any = jnp.any(can_tx)
            return new_st, can_any

        def snapshot(st: FabricState) -> dict:
            """Per-tick trace row, derived purely from state (so dense and
            decimated traces sample the identical quantities)."""
            done = jax.vmap(proto.done)(st.flows)
            return {
                "qsize": st.qsize[:Q],
                "drops_trace": st.drops,
                "done": jnp.sum(done).astype(jnp.int32),
                "cwnd_mean": jnp.mean(jax.vmap(proto.cong_pkts)(st.flows)),
                "delivered": st.delivered,
                "pauses_trace": st.pauses,
                "paused_ports": (jnp.sum(st.paused_nic)
                                 + jnp.sum(st.paused_sd)
                                 + jnp.sum(st.paused_up)).astype(jnp.int32),
            }

        def warp_target(st: FabricState, t):
            """Earliest tick > t that could be non-identity given an idle
            fabric: the soonest of (a) the first timer sweep at which some
            released flow's deadline has expired, (b) the first pacing
            release at which a window-open flow may send, (c) the next
            return-pipe slot holding an undelivered ACK/SACK/CNP, (d) the
            earliest departure-time-lane arrival of an in-flight packet
            (the per-hop pipeline's occupancy).  All are conservative
            lower bounds (floor rounding): an executed tick that turns out
            to be identity simply re-skips, so parity is exact and
            progress is >= 1 tick per trip.
            """
            if DP > 1:
                timer_ev, send_ev = gath(
                    jax.vmap(proto.next_event)(st.flows))
            else:
                timer_ev, send_ev = jax.vmap(proto.next_event)(st.flows)
            sendable = ((st.pending <= 0) & (arrival <= t))[dep.msg_of_flow]
            inf = jnp.float32(jnp.inf)
            timer_ev = jnp.where(sendable, timer_ev, inf)
            send_ev = jnp.where(sendable, send_ev, inf)

            def ev_tick(ev, half_early):
                e = jnp.min(ev)
                ratio = e / jnp.float32(tick_us) - half_early
                tk = jnp.where(
                    jnp.isfinite(e),
                    jnp.floor(jnp.minimum(
                        ratio, jnp.float32(n_ticks))).astype(jnp.int32),
                    jnp.int32(n_ticks))
                return jnp.maximum(t + 1, tk)

            every = cfg.timer_every
            t_timer = ev_tick(timer_ev, 0.0)
            t_timer = ((t_timer + every - 1) // every) * every
            # pacing tolerance mirrors next_packet: now + tick/2 >= ts
            t_send = ev_tick(send_ev, 0.5)
            slots = jnp.arange(H, dtype=jnp.int32)
            due = t + 1 + (slots - t - 1) % H
            if DP > 1:
                pipe_any = jnp.any(jax.lax.all_gather(
                    jnp.any(st.pipe.valid, axis=1), "pod"), axis=0)
            else:
                pipe_any = jnp.any(st.pipe.valid, axis=1)
            t_pipe = jnp.min(jnp.where(pipe_any, due, jnp.int32(n_ticks)))
            # in-flight pipeline occupancy: the earliest ready tick of any
            # nonempty unpaused queue's head (paused queues cannot change
            # state while the fabric is otherwise idle — the gate is a
            # fixed point absent serves/enqueues, and idle requires the
            # pause-frame delay line settled)
            if DP > 1:
                qhead_pad = jnp.pad(st.qhead, (0, QR - (Q + 1)))
                hidx_l = jax.lax.dynamic_slice_in_dim(
                    qhead_pad, qoff, QRL) % cap
                rdy = jax.lax.all_gather(
                    st.q.ready[jnp.arange(QRL), hidx_l], "pod",
                    tiled=True)[:Q]
            else:
                hidx = st.qhead[:Q] % cap
                rdy = st.q.ready[qrows, hidx]
            pending_q = st.qsize[:Q] > 0
            if pfc:
                dec_row = jnp.concatenate(
                    [st.paused_up.reshape(-1), st.paused_sd.reshape(-1),
                     jnp.zeros((NH,), bool)])
                pending_q = pending_q & (~dec_row)
            t_queue = jnp.maximum(t + 1, jnp.min(jnp.where(
                pending_q, rdy, jnp.int32(n_ticks))))
            # (e) the earliest future open-loop arrival of a dep-met
            # message (its release tick records at exactly that tick);
            # empty mask (all-arrival-0 traces) -> n_ticks, a no-op
            t_arr = jnp.maximum(t + 1, jnp.min(jnp.where(
                (st.pending <= 0) & (st.msg_release_tick < 0),
                arrival, jnp.int32(n_ticks))))
            tgt = jnp.minimum(jnp.minimum(t_timer, t_send),
                              jnp.minimum(t_pipe, t_queue))
            tgt = jnp.minimum(tgt, t_arr)
            if HAS_FAULTS:
                # (f) fault-schedule transitions are first-class wake
                # sources: a warp trip can never jump over a flap /
                # degrade / corruption boundary, so link state is
                # re-evaluated at every edge (docs/robustness.md)
                t_fault = jnp.maximum(t + 1, jnp.min(jnp.where(
                    fd.edges > t, fd.edges, jnp.int32(n_ticks))))
                tgt = jnp.minimum(tgt, t_fault)
            return jnp.minimum(tgt, jnp.int32(n_ticks))

        if cfg.time_warp:
            def trip(carry):
                t, st, trips = carry
                st, can_any = tick(st, t)
                # Idle <=> every future tick up to the warp target is a
                # provable no-op: no released flow offered a packet this
                # tick (send eligibility is time-independent between
                # timer/pacing/ack events), any queued packet is still in
                # flight on its link (warp_target wakes at the earliest
                # departure-lane arrival), no freshly-released message
                # still needs its release tick recorded, and the PFC
                # pause-frame delay line holds no in-flight transition.
                with jax.named_scope("fabric.warp"):
                    idle = ((~can_any)
                            & ~jnp.any((st.pending <= 0) & (arrival <= t)
                                       & (st.msg_release_tick < 0)))
                    if pfc and PD > 0:
                        dec = jnp.concatenate(
                            [st.paused_nic, st.paused_sd.reshape(-1),
                             st.paused_up.reshape(-1)])
                        idle = idle & jnp.all(st.pfc_line == dec[None, :])
                    t_next = jnp.where(idle, warp_target(st, t), t + 1)
                return t_next, st, trips + jnp.int32(1)

            def cond(carry):
                with jax.named_scope("fabric.cond"):
                    return carry[0] < n_ticks

            end_t, final, trips = jax.lax.while_loop(
                cond, trip, (jnp.int32(0), st0, jnp.int32(0)))
            return final, {"warp_trips": trips, "end_tick": end_t}

        if trace_every == 0:
            final = jax.lax.fori_loop(
                0, n_ticks, lambda t, st: tick(st, t)[0], st0)
            return final, {}

        k = trace_every
        n_blocks, rem = divmod(n_ticks, k)

        def block(st, b):
            st = jax.lax.fori_loop(
                0, k, lambda i, s: tick(s, b * k + i)[0], st)
            return st, snapshot(st)

        final, ys = jax.lax.scan(block, st0,
                                 jnp.arange(n_blocks, dtype=jnp.int32))
        if rem:  # the trace samples block ends; the summary carry is exact
            final = jax.lax.fori_loop(n_blocks * k, n_ticks,
                                      lambda t, s: tick(s, t)[0], final)
        return final, ys

    if DP > 1:
        # One shard_map around the whole program: the heavy state (queue
        # rings by switch-row block; flow/receiver/return-pipe by flow
        # block) lives partitioned for the entire scan, the small
        # per-queue/per-message vectors are computed replicated (identical
        # op order on every pod — bit-exact vs the unsharded program), and
        # the two explicit all_gather exchanges above are the only
        # cross-pod traffic.
        Pspec = jax.sharding.PartitionSpec
        mesh = jax.make_mesh((DP,), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        fl_s, rcv_s = jax.eval_shape(
            proto.init,
            jax.ShapeDtypeStruct((NL,), jnp.int32),
            jax.ShapeDtypeStruct((NL,), jnp.float32),
            jax.ShapeDtypeStruct((NL,), jnp.int32))
        pipe_s = jax.eval_shape(lambda: proto.empty_msgs(H, NL))
        rep = Pspec()
        st_spec = FabricState(
            flows=jax.tree.map(lambda _: Pspec("pod"), fl_s),
            rcv=jax.tree.map(lambda _: Pspec("pod"), rcv_s),
            q=PktQ(*([Pspec("pod")] * len(PktQ._fields))),
            qhead=rep, qsize=rep,
            pipe=jax.tree.map(lambda _: Pspec(None, "pod"), pipe_s),
            obl_rr=rep, drops=rep, delivered=rep, done_tick=rep,
            qbytes=rep, ing_host=rep, ing_sd=rep, ing_up=rep,
            paused_nic=rep, paused_sd=rep, paused_up=rep, pfc_line=rep,
            pauses=rep, pending=rep, msg_done=rep, msg_release_tick=rep,
            msg_done_tick=rep, group_done_tick=rep, act_overflow=rep,
            ecn_marks=rep, qdepth_hi=rep, blackholed=rep,
            corrupt_drops=rep, tx_rows=rep, win_retx=rep)
        m_spec = ({"warp_trips": rep, "end_tick": rep}
                  if cfg.time_warp else {})
        sharded = jax.shard_map(
            body, mesh=mesh, in_specs=(rep,) * 8,
            out_specs=(st_spec, m_spec), check_vma=False)

        def program(src, dst, total_pkts, tail_b, ent0, lb_code, arrival,
                    fd):
            return sharded(src, dst, total_pkts, tail_b, ent0, lb_code,
                           arrival, fd)
    else:
        program = body
    # the jitted entry's name: the XLA module is jit_fabric_program, every
    # op's scope path in a device trace starts with it, and the compile
    # counters of obs/spans.py count its compiles only
    program.__name__ = program.__qualname__ = spans.PROGRAM
    program.dims = dict(T=T, S=S, NH=NH, TS=TS, Q=Q, cap=cap, H=H,
                        K=K, D_same=D_same, D_cross=D_cross, PD=PD,
                        shard=DP, active_cap=A)
    return program


# --------------------------------------------------------------------------- #
# Program cache: build + jit once per static shape, reuse across run()/sweep()
# --------------------------------------------------------------------------- #

#: Cumulative count of fresh program builds (cache misses).  The regression
#: tests assert this does not grow when a same-shape scenario re-runs.
program_builds = 0

#: Cumulative count of jax TRACES of fabric program bodies (bumped by a
#: python side effect inside the body, which only runs while tracing).  A
#: cached program can still retrace when called with a new input shape —
#: e.g. a new batch size — so this is the regression hook for the
#: job-axis bucketing: bucketed job counts must reuse one trace.
program_traces = 0

_PROGRAM_CACHE: "OrderedDict[tuple, _Program]" = OrderedDict()
_PROGRAM_CACHE_MAX = 32  # LRU bound: compiled executables are not free


class _Program(NamedTuple):
    """One cached fabric program: the raw builder output plus its jitted
    single-run and vmapped-batch entry points (kept as stable callables so
    jax's own jit cache is hit instead of re-tracing every call)."""

    program: Callable
    jit_single: Callable
    jit_batch: Callable
    dims: dict


def _program_key(topo: FatTree, n_flows: int, n_ticks: int,
                 cfg: FabricConfig, dep: DepSpec) -> tuple:
    """Hashable fingerprint of everything `_make_program` closes over.

    ``lb_mode`` and ``roce_entropy_seed`` are *data* to the program (traced
    lb_code argument / host-computed ent0 array) and ``subflows`` is fully
    captured by the flow count + DepSpec, so all three are normalized out —
    sweeping them reuses one compiled program.
    """
    # The fault schedule is program DATA except for its entry counts:
    # shape_key is the static part (and an empty spec is the same program
    # as no spec at all), so same-shape chaos schedules share one compile.
    fkey = (cfg.faults.shape_key if cfg.faults is not None
            else (0, 0, 0, 0, 0, 0))
    norm = dataclasses.replace(
        cfg, lb_mode="adaptive", roce_entropy_seed=None, subflows=1,
        trace_every=0 if cfg.time_warp else cfg.trace_every,
        faults=None)
    dep_key = (dep.n_msgs, dep.n_groups,
               np.asarray(dep.msg_of_flow).tobytes(),
               np.asarray(dep.group_of_msg).tobytes(),
               np.asarray(dep.init_pending).tobytes(),
               np.asarray(dep.edge_parent).tobytes(),
               np.asarray(dep.edge_child).tobytes())
    return ((topo.n_tor, topo.hosts_per_tor, topo.n_spine, topo.dead_links),
            n_flows, n_ticks, norm, dep_key, fkey)


def _get_program(topo: FatTree, n_flows: int, n_ticks: int,
                 cfg: FabricConfig, dep: Optional[DepSpec] = None,
                 n_real: Optional[int] = None) -> _Program:
    """Cached (program, jitted entry points) for the given static dims."""
    if dep is None:
        dep = _trivial_dep(range(n_flows))
    key = _program_key(topo, n_flows, n_ticks, cfg, dep) + (n_real,)
    prog = _PROGRAM_CACHE.get(key)
    if prog is None:
        spans.listen()  # count this program's first trace and compile
        program = _make_program(topo, n_flows, n_ticks, cfg, dep,
                                n_real=n_real)
        # the batch axis vmaps the flow-array inputs; the fault schedule
        # is shared across the whole batch (in_axes=None broadcasts it)
        prog = _Program(program=program, jit_single=jax.jit(program),
                        jit_batch=jax.jit(jax.vmap(
                            program,
                            in_axes=(0, 0, 0, 0, 0, 0, 0, None))),
                        dims=program.dims)
        _PROGRAM_CACHE[key] = prog
        while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
            _PROGRAM_CACHE.popitem(last=False)
    else:
        _PROGRAM_CACHE.move_to_end(key)
    return prog


def clear_program_cache() -> None:
    """Drop all cached fabric programs (frees their jit caches too)."""
    _PROGRAM_CACHE.clear()


def _check_flows(flows, n_hosts: int) -> None:
    for s_, d_, _ in flows:
        if not (0 <= s_ < n_hosts and 0 <= d_ < n_hosts and s_ != d_):
            raise ValueError(f"bad flow endpoint (src={s_}, dst={d_}) for "
                             f"{n_hosts} hosts")


_UNSET = object()


def _flow_arrays(flows, cfg: FabricConfig, entropy_seed=_UNSET):
    """Host-side program inputs for one flow list.  ``entropy_seed``
    overrides ``cfg.roce_entropy_seed`` (sweeps vmap the seed axis, so the
    batch helper passes a per-entry seed against one shared cfg).

    Returns ``(src, dst, total_pkts, tail_bytes, ent0)`` — ``tail_bytes``
    is the wire size of each flow's final PSN (``ref.pkt_size`` odd-tail
    semantics: sub-MTU and non-MTU-multiple messages are first-class)."""
    if entropy_seed is _UNSET:
        entropy_seed = cfg.roce_entropy_seed
    mtu = cfg.net.mtu_bytes
    src = jnp.asarray([f[0] for f in flows], jnp.int32)
    dst = jnp.asarray([f[1] for f in flows], jnp.int32)
    npkts = [max(1, int(math.ceil(f[2] / mtu))) for f in flows]
    total_pkts = jnp.asarray(npkts, jnp.int32)
    tail_bytes = jnp.asarray(
        [max(1.0, float(f[2]) - (n - 1) * mtu)
         for f, n in zip(flows, npkts)], jnp.float32)
    if entropy_seed is not None:
        rng = random.Random(entropy_seed)
        ent0 = jnp.asarray([rng.randrange(1 << 16) for _ in flows],
                           jnp.int32)
    else:
        # per-flow pinned entropy for non-spray protocols (one QP each, the
        # analogue of the oracle's rng.randrange(1 << 16)); striped
        # sub-flows of one message get distinct draws via the flow index
        iota_n = jnp.arange(len(flows), dtype=jnp.int32)
        ent0 = ecmp_mix(src, dst, iota_n + jnp.int32(40503)) % (1 << 16)
    return src, dst, total_pkts, tail_bytes, ent0


def _arrival_array(messages) -> jax.Array:
    """Per-message earliest-launch ticks (i32[n_msgs], input order).

    ``arrival`` is optional on the message records (``_FlowMsg`` and
    ``workloads.Message`` both default it to 0), so legacy traces keep
    the closed-loop all-zero array."""
    return jnp.asarray([max(0, int(getattr(m, "arrival", 0)))
                        for m in messages], jnp.int32)


def _pad_flow_arrays(arrs, npad: int, n_hosts: int):
    """Pad program input arrays with ``npad`` inert flows.

    Pad flows have ``total_pkts == 0`` — both protocols initialise them
    done-at-t0 and they never produce a candidate packet — so the padded
    program is observable-identical to the unpadded one (the NIC
    arbitration modulus uses ``n_real``, not the padded count)."""
    src, dst, total_pkts, tail_bytes, ent0 = arrs
    z = jnp.zeros((npad,), jnp.int32)
    return (jnp.concatenate([src, z]),
            jnp.concatenate([dst, jnp.full((npad,), n_hosts - 1,
                                           jnp.int32)]),
            jnp.concatenate([total_pkts, z]),
            jnp.concatenate([tail_bytes, jnp.ones((npad,), jnp.float32)]),
            jnp.concatenate([ent0, z]))


def _pad_dep(dep: DepSpec, npad: int) -> DepSpec:
    """Extend a DepSpec with ``npad`` pad flows, each its own dep-free
    message in its own extra group (so no real message or group waits on,
    or is counted with, a pad)."""
    ar = np.arange(npad, dtype=np.int32)
    cat = lambda a, b: jnp.asarray(
        np.concatenate([np.asarray(a, np.int32), b.astype(np.int32)]))
    pad_ids = tuple(f"__shard_pad{i}" for i in range(npad))
    return DepSpec(
        n_msgs=dep.n_msgs + npad, n_groups=dep.n_groups + npad,
        msg_of_flow=cat(dep.msg_of_flow, dep.n_msgs + ar),
        group_of_msg=cat(dep.group_of_msg, dep.n_groups + ar),
        init_pending=cat(dep.init_pending, np.zeros(npad)),
        edge_parent=dep.edge_parent, edge_child=dep.edge_child,
        msg_ids=dep.msg_ids + pad_ids, group_ids=dep.group_ids + pad_ids)


def _shard_pad_inputs(flows, dep: DepSpec, arrs, cfg: FabricConfig,
                      n_hosts: int):
    """Pad the flow axis to a multiple of ``cfg.shard`` so the per-pod
    lane count is uniform.  Returns ``(arrs, dep_run, n_real)`` where
    ``n_real`` is None when no padding was needed."""
    d = int(cfg.shard)
    npad = (-len(flows)) % d
    if npad == 0:
        return arrs, dep, None
    return (_pad_flow_arrays(arrs, npad, n_hosts), _pad_dep(dep, npad),
            len(flows))


def _slice_fin(fin: dict, n: int, n_msgs: int, n_groups: int) -> dict:
    """Strip shard-pad entries from a :func:`_final_host` dict so the
    metrics layer only ever sees the caller's real flows/messages/groups."""
    out = dict(fin)
    for k, m in (("done_tick", n), ("delivered", n), ("retx", n),
                 ("rto_fires", n), ("sack_recoveries", n),
                 ("gbn_rewinds", n),
                 ("msg_done_tick", n_msgs), ("msg_release_tick", n_msgs),
                 ("group_done_tick", n_groups)):
        if k in fin:
            out[k] = fin[k][..., :m]
    return out


#: Final-state arrays the host-side metrics derive from — fetched in ONE
#: ``jax.device_get`` (the old per-scalar pulls were a device-sync storm
#: that dominated wall-clock at collective flow counts).
_FINAL_KEYS = ("done_tick", "msg_done_tick", "msg_release_tick",
               "group_done_tick", "drops", "pauses", "delivered",
               "act_overflow", "ecn_marks", "qdepth_hi", "blackholed",
               "corrupt_drops", "tx_rows", "win_retx")


def _final_host(finals) -> dict:
    """One host round-trip for every final-state array the metrics need
    (works on a vmapped batch state too: values keep their leading batch
    dim; slice per entry on the host)."""
    vals = jax.device_get(tuple(getattr(finals, k) for k in _FINAL_KEYS))
    return dict(zip(_FINAL_KEYS, vals))


def _us_or_none(ticks, ok, tick_us: float) -> list:
    """[tick * tick_us or None] rows from host arrays (vectorized; no
    per-element device access)."""
    us = np.asarray(ticks, dtype=np.float64) * tick_us
    return [float(v) if o else None
            for v, o in zip(us, np.asarray(ok, dtype=bool))]


def _finish_metrics(metrics: dict, fin: dict, cfg: FabricConfig,
                    dims: dict, dep: DepSpec) -> dict:
    """Attach host-side derived metrics for one run.

    ``fin`` is the :func:`_final_host` dict (one batch entry) of the final
    state.  ``fct_us`` is MESSAGE-level: release (deps met) to
    last-sub-flow completion — identical to the old per-flow FCT for
    deps-free single-sub-flow traces.  ``drops``/``pauses`` are the exact
    final-carry counters, independent of any (decimated or disabled)
    per-tick trace.
    """
    T, S, TS = dims["T"], dims["S"], dims["TS"]
    tick_us = cfg.net.mtu_serialize_us
    _, _, _, target_qdelay_us = _make_protocol(cfg)
    metrics["tick_us"] = tick_us
    metrics["trace_every"] = 0 if cfg.time_warp else cfg.trace_every
    metrics["target_qdelay_pkts"] = target_qdelay_us / tick_us
    dt = np.asarray(fin["done_tick"])
    metrics["done_tick"] = dt
    # +1: a message is complete when its last ACK lands, i.e. at tick end
    metrics["subflow_fct_us"] = _us_or_none(dt + 1, dt >= 0, tick_us)
    mdt = np.asarray(fin["msg_done_tick"])
    mrt = np.asarray(fin["msg_release_tick"])
    metrics["fct_us"] = _us_or_none(mdt + 1 - np.maximum(mrt, 0),
                                    mdt >= 0, tick_us)
    metrics["msg_release_us"] = _us_or_none(mrt, mrt >= 0, tick_us)
    metrics["msg_ids"] = dep.msg_ids
    # original group id per message (tenant attribution in summarize)
    gof = np.asarray(dep.group_of_msg)
    metrics["msg_group_ids"] = tuple(dep.group_ids[g] for g in gof)
    # exact summary counters from the final scan carry (satellite of the
    # event-horizon change: summaries stay exact when the trace is
    # decimated or off entirely)
    metrics["drops"] = int(fin["drops"])
    metrics["pauses"] = int(fin["pauses"])
    ov = int(np.asarray(fin["act_overflow"]).reshape(-1)[-1])
    if ov:
        raise RuntimeError(
            f"active_cap={dims.get('active_cap')} exceeded on {ov} tick(s) "
            f"— sendable flows beyond the cap would silently stall; raise "
            f"FabricConfig.active_cap (or set it to None)")
    metrics["delivered_final"] = np.asarray(fin["delivered"])
    # observability counters: exact final-carry scalars/vectors, available
    # at any trace decimation (incl. off) and under the warp scan
    metrics["ecn_marks"] = int(np.asarray(fin["ecn_marks"]).reshape(-1)[-1])
    metrics["qdepth_hi_pkts"] = np.asarray(fin["qdepth_hi"])[:dims["Q"]]
    # recovery + chaos counters: UNIFORM keys, zero-filled where a
    # protocol or backend lacks the underlying counter, so dashboards and
    # the bench schema never KeyError (docs/robustness.md)
    metrics["retransmits"] = (int(np.sum(np.asarray(fin["retx"])))
                              if "retx" in fin else 0)
    for k in ("rto_fires", "sack_recoveries", "gbn_rewinds"):
        metrics[k] = int(np.sum(np.asarray(fin[k]))) if k in fin else 0
    for k_out, k_in in (("blackholed_pkts", "blackholed"),
                        ("corrupt_drops", "corrupt_drops")):
        metrics[k_out] = (int(np.asarray(fin[k_in]).reshape(-1)[-1])
                          if k_in in fin else 0)
    if "tx_rows" in fin:
        # accepted data injections per queue row (entropy-shift gates)
        metrics["tx_rows_pkts"] = np.asarray(fin["tx_rows"])[:dims["Q"]]
    if "win_retx" in fin:
        # retransmit attempts attributed to each flap window (+2 RTO)
        metrics["win_retx"] = np.asarray(fin["win_retx"])
    # Collective (group) metrics only for traces that actually carry
    # trace structure (dependency edges or several groups) — the events
    # backend likewise only reports group keys for TraceRunner-scheduled
    # traces, and the summary-dict contract is that both backends return
    # the same keys per scenario.
    if int(dep.edge_parent.shape[0]) > 0 or dep.n_groups > 1:
        gdt = np.asarray(fin["group_done_tick"])
        metrics["group_ids"] = dep.group_ids
        metrics["group_done_us"] = _us_or_none(gdt + 1, gdt >= 0, tick_us)
    metrics["queue_ids"] = {
        "tor_up": lambda t_, s_: t_ * S + s_,
        "spine_down": lambda s_, t_: TS + s_ * T + t_,
        "host_down": lambda h_: 2 * TS + h_,
    }
    return metrics


def run_fabric_trace(topo: FatTree, messages, n_ticks: int,
                     cfg: FabricConfig = FabricConfig()):
    """Simulate a dependency-edged message trace on the jitted fat-tree.

    ``messages`` is a sequence of records with ``mid/src/dst/size/deps/
    group`` attributes (``workloads.Message``); ``cfg.subflows`` stripes
    each message over that many single-QP sub-flows.  Returns
    (final_state, metrics): message/group completion metrics always, the
    per-tick trace per ``cfg.trace_every`` (events-only when 0 or when
    ``cfg.time_warp`` collapses dead intervals).

    Programs are cached on the static dims — repeated same-shape calls
    (benchmark seed loops, parity pairs) trace and compile exactly once.
    """
    with spans.span("fabric.inputs"):
        flows, dep = expand_messages(messages, cfg.subflows)
        _check_flows(flows, topo.n_hosts)
        if cfg.faults is not None:
            validate_faults(cfg.faults, topo)
        fd = build_fault_data(cfg.faults, topo.n_tor, topo.n_spine,
                              topo.hosts_per_tor)
        arrs = _flow_arrays(flows, cfg)
        arrival = _arrival_array(messages)
        dep_run, n_real = dep, None
        if int(cfg.shard) > 1:
            arrs, dep_run, n_real = _shard_pad_inputs(
                flows, dep, arrs, cfg, topo.n_hosts)
            arrival = jnp.concatenate([
                arrival,
                jnp.zeros((dep_run.n_msgs - dep.n_msgs,), jnp.int32)])
        src, dst, total_pkts, tails, ent0 = arrs
    with spans.span("fabric.program"):
        prog = _get_program(topo, int(src.shape[0]), n_ticks, cfg, dep_run,
                            n_real=n_real)
    with spans.span("fabric.dispatch"):
        lb = jnp.int32(LB_MODES.index(cfg.lb_mode))
        final, metrics = prog.jit_single(src, dst, total_pkts, tails, ent0,
                                         lb, arrival, fd)
        proto, _, _, _ = _make_protocol(cfg)  # overlaps the device
    with spans.span("fabric.device"):
        jax.block_until_ready((final, metrics))
    with spans.span("fabric.fetch"):
        fin = _final_host(final)
        fin["retx"] = jax.device_get(proto.stat_retx(final.flows))
        fin.update(jax.device_get(proto.stat_recovery(final.flows)))
    with spans.span("fabric.summary"):
        if n_real is not None:
            fin = _slice_fin(fin, n_real, dep.n_msgs, dep.n_groups)
        metrics = _finish_metrics(dict(metrics), fin, cfg, prog.dims, dep)
    return final, metrics


def run_fabric(topo: FatTree,
               flows: Sequence[Tuple[int, int, float]],
               n_ticks: int,
               cfg: FabricConfig = FabricConfig()):
    """Simulate ``flows`` = [(src_host, dst_host, msg_bytes), ...] on a
    fat-tree for ``n_ticks``; returns (final_state, per-tick metrics).

    The deps-free special case of :func:`run_fabric_trace` (one message per
    flow, striped if ``cfg.subflows > 1``)."""
    msgs = [_FlowMsg(mid=i, src=s, dst=d, size=b)
            for i, (s, d, b) in enumerate(flows)]
    return run_fabric_trace(topo, msgs, n_ticks, cfg)


def _job_bucket(b: int) -> int:
    """Next power-of-two bucket for the vmapped job axis (1, 2, 4, 8...).

    Batch sizes inside one bucket present identical input shapes to the
    cached program's ``jit_batch`` entry point, so they share a single
    trace/compile."""
    return 1 << (int(b) - 1).bit_length()


def run_fabric_trace_batch(topo: FatTree, messages_batch, n_ticks: int,
                           cfg: FabricConfig = FabricConfig(),
                           lb_modes: Optional[Sequence[str]] = None,
                           entropy_seeds: Optional[Sequence] = None):
    """vmap a batch of same-structure message traces through ONE jitted
    fabric program.

    All batch entries must share the dependency structure (message count,
    deps, groups, sub-flow fan-out) and topology; everything that is mere
    *data* to the program may vary per entry: src/dst/size patterns,
    ``lb_modes`` (per-entry STrack spray mode) and ``entropy_seeds``
    (per-entry QP-entropy seed, RoCEv2) — the config axes ``sweep()``
    fans out.  Returns (stacked_final_state, [metrics_dict_per_entry]).

    The job axis is bucket-padded to the next power of two (pad entries
    replay entry 0 and are dropped from the results), so nearby job counts
    share ONE jit trace of the cached program instead of re-tracing per
    batch size — the multi-tenant compile-time lever.  The returned
    stacked final state keeps the padded leading dim."""
    if not messages_batch:
        raise ValueError("need at least one message trace")
    if int(cfg.shard) > 1:
        raise ValueError(
            "cfg.shard > 1 builds one shard_map program over the device "
            "mesh; vmapped batches are unsupported — loop "
            "run_fabric_trace instead")
    B = len(messages_batch)
    if lb_modes is None:
        lb_modes = [cfg.lb_mode] * B
    if entropy_seeds is None:
        entropy_seeds = [cfg.roce_entropy_seed] * B
    if len(lb_modes) != B or len(entropy_seeds) != B:
        raise ValueError(
            f"lb_modes/entropy_seeds must match the batch: got "
            f"{len(lb_modes)}/{len(entropy_seeds)} for {B} traces")
    for m in lb_modes:
        if m not in LB_MODES:
            raise ValueError(f"unknown lb_mode {m!r}; "
                             f"expected one of {LB_MODES}")
    expanded = [expand_messages(ms, cfg.subflows) for ms in messages_batch]
    dep = expanded[0][1]
    for i, (_, d) in enumerate(expanded[1:], start=1):
        if int(d.msg_of_flow.shape[0]) != int(dep.msg_of_flow.shape[0]):
            raise ValueError(
                f"batch entry {i} has {int(d.msg_of_flow.shape[0])} "
                f"sub-flows, entry 0 has {int(dep.msg_of_flow.shape[0])}")
        same_deps = (
            d.edge_parent.shape == dep.edge_parent.shape
            and bool(jnp.all(d.edge_parent == dep.edge_parent))
            and bool(jnp.all(d.edge_child == dep.edge_child))
            and bool(jnp.all(d.group_of_msg == dep.group_of_msg)))
        if not same_deps:
            raise ValueError(
                f"batch entry {i} has a different dependency/group "
                f"structure than entry 0 — the whole batch runs under "
                f"entry 0's static DepSpec, so structures must match")
    if cfg.faults is not None:
        validate_faults(cfg.faults, topo)
    fd = build_fault_data(cfg.faults, topo.n_tor, topo.n_spine,
                          topo.hosts_per_tor)
    arrs = []
    arrivals = []
    for (flows, _), seed, msgs in zip(expanded, entropy_seeds,
                                      messages_batch):
        _check_flows(flows, topo.n_hosts)
        arrs.append(_flow_arrays(flows, cfg, entropy_seed=seed))
        arrivals.append(_arrival_array(msgs))
    lb_codes = [LB_MODES.index(m) for m in lb_modes]
    BP = _job_bucket(B)
    if BP > B:
        arrs = arrs + [arrs[0]] * (BP - B)
        arrivals = arrivals + [arrivals[0]] * (BP - B)
        lb_codes = lb_codes + [lb_codes[0]] * (BP - B)
    srcs = jnp.stack([a[0] for a in arrs])
    dsts = jnp.stack([a[1] for a in arrs])
    pkts = jnp.stack([a[2] for a in arrs])
    tails = jnp.stack([a[3] for a in arrs])
    ents = jnp.stack([a[4] for a in arrs])
    arrv = jnp.stack(arrivals)
    lbs = jnp.asarray(lb_codes, jnp.int32)
    prog = _get_program(topo, int(srcs.shape[1]), n_ticks, cfg, dep)
    finals, stacked = prog.jit_batch(srcs, dsts, pkts, tails, ents, lbs,
                                     arrv, fd)
    # one transfer for the finals + one for any stacked trace (the old
    # per-entry gather re-pulled the full batch B times)
    proto, _, _, _ = _make_protocol(cfg)
    fin_all = _final_host(finals)
    fin_all["retx"] = jax.device_get(proto.stat_retx(finals.flows))
    fin_all.update(jax.device_get(proto.stat_recovery(finals.flows)))
    stacked = jax.device_get(dict(stacked))
    per_entry = []
    for i in range(B):
        m = {k: v[i] for k, v in stacked.items()}
        fin_i = {k: v[i] for k, v in fin_all.items()}
        per_entry.append(_finish_metrics(m, fin_i, cfg, prog.dims, dep))
    return finals, per_entry


def run_fabric_batch(topo: FatTree,
                     flows_batch: Sequence[Sequence[Tuple[int, int, float]]],
                     n_ticks: int,
                     cfg: FabricConfig = FabricConfig()):
    """vmap a batch of same-shape flow lists (e.g. seeds of one workload)
    through ONE jitted fabric program (deps-free special case)."""
    sizes = {len(fl) for fl in flows_batch}
    if len(sizes) != 1:
        raise ValueError(f"flow lists must be same-shape, got sizes {sizes}")
    msgs_batch = [[_FlowMsg(mid=i, src=s, dst=d, size=b)
                   for i, (s, d, b) in enumerate(fl)] for fl in flows_batch]
    return run_fabric_trace_batch(topo, msgs_batch, n_ticks, cfg)


def summarize(metrics: dict) -> dict:
    """Event-oracle-style summary (max/avg FCT, unfinished, drops, pauses).

    Keys match ``workloads._summarize_sim`` so fabric and oracle results are
    directly comparable; ``pauses`` counts PFC xoff events (0 when PFC is
    off or the protocol runs lossy).  When the trace carries group
    structure, the TraceRunner-style collective keys (``group_fct`` /
    ``max_collective_time`` / ``finished_groups`` / ``total_groups``) ride
    along, keyed by the caller's original group ids.
    """
    fcts = [f for f in metrics["fct_us"] if f is not None]
    # drops/pauses are exact final-carry scalars since the trace became
    # opt-in; reshape(-1)[-1] also accepts a legacy per-tick array
    out = {
        "max_fct": max(fcts) if fcts else float("nan"),
        "avg_fct": sum(fcts) / len(fcts) if fcts else float("nan"),
        "unfinished": sum(1 for f in metrics["fct_us"] if f is None),
        "drops": int(np.asarray(metrics["drops"]).reshape(-1)[-1]),
        "pauses": int(np.asarray(metrics["pauses"]).reshape(-1)[-1]),
    }
    # observatory counters (absent on legacy/partial metrics dicts)
    if "ecn_marks" in metrics:
        out["ecn_marks"] = int(metrics["ecn_marks"])
    # recovery + chaos counters: uniformly present and zero-filled across
    # both protocols and backends — never a KeyError downstream
    for k in ("retransmits", "rto_fires", "sack_recoveries",
              "gbn_rewinds", "blackholed_pkts", "corrupt_drops"):
        out[k] = int(metrics.get(k, 0))
    # chaos attribution vectors (fabric backend only): accepted data
    # injections per queue row (entropy-shift gates) and retransmit
    # attempts attributed to each flap window (+2 RTO).  Tuples, not
    # arrays: summary dicts must stay ==-comparable and JSON-friendly.
    txr = metrics.get("tx_rows_pkts")
    if txr is not None:
        out["tx_rows_pkts"] = tuple(int(v)
                                    for v in np.asarray(txr).reshape(-1))
    wr = metrics.get("win_retx")
    if wr is not None and np.asarray(wr).size:
        out["win_retx"] = tuple(int(v)
                                for v in np.asarray(wr).reshape(-1))
    qhi = metrics.get("qdepth_hi_pkts")
    if qhi is not None:
        qhi = np.asarray(qhi)
        out["qdepth_max_pkts"] = int(qhi.max()) if qhi.size else 0
        out["qdepth_p99_pkts"] = (float(np.percentile(qhi, 99))
                                  if qhi.size else 0.0)
    gd = metrics.get("group_done_us")
    if gd is not None:
        gids = metrics.get("group_ids", tuple(range(len(gd))))
        group_fct = {g: t for g, t in zip(gids, gd) if t is not None}
        out["group_fct"] = group_fct
        out["max_collective_time"] = (max(group_fct.values())
                                      if group_fct else float("nan"))
        out["finished_groups"] = len(group_fct)
        out["total_groups"] = len(gd)
    # per-tenant (per original group id) FCT attribution: percentiles over
    # the message-level FCTs of each group
    mgids = metrics.get("msg_group_ids")
    if mgids is not None:
        by_g: dict = {}
        for g, f in zip(mgids, metrics["fct_us"]):
            by_g.setdefault(g, []).append(f)
        tenant = {}
        for g, fs in by_g.items():
            done = [f for f in fs if f is not None]
            row = {"count": len(fs),
                   "unfinished": len(fs) - len(done)}
            if done:
                arr = np.asarray(done, dtype=np.float64)
                row.update(p50=float(np.percentile(arr, 50)),
                           p99=float(np.percentile(arr, 99)),
                           avg=float(arr.mean()), max=float(arr.max()))
            else:
                row.update(p50=float("nan"), p99=float("nan"),
                           avg=float("nan"), max=float("nan"))
            tenant[g] = row
        out["tenant_fct"] = tenant
    return out
