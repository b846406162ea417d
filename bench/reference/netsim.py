"""Event-driven packet-level network simulator: the benchmark's plain reference.

A copy of the program's discrete-event oracle (``repro/sim/events.py``),
STrack only: without its RoCEv2/PFC path, fault schedule and logging
hooks, which no cell uses.  Kept here so the yardstick does not move
when the program does.  htsim-style simulation of the
STrack paper's evaluation fabric:

* directional FIFO queues with serialization + propagation delay,
* egress ECN marking (mark on dequeue from the residual queue depth),
* silent tail drops at ``drop_bytes`` (lossy),
* pull-based host NICs (ACK-clocked window transports ask the flow engine
  for the next packet only when the wire is free).

Transports plug in through the engines in ``engines.py``.  Times in us,
sizes in bytes.  It imports nothing of the program.
"""
from __future__ import annotations

import heapq
import itertools
import math
from typing import Callable, Optional

from . import engines as ref
from .params import NetworkSpec, STrackParams, make_strack_params
from .topology import FatTree


class Queue:
    """Directional FIFO with serialization, ECN egress marking and drops."""

    __slots__ = ("name", "rate", "prop", "fifo", "occ", "busy",
                 "ecn_kmin", "ecn_kmax", "drop_bytes",
                 "drops", "max_occ", "sim", "drain_host")

    def __init__(self, sim, name, rate, prop, ecn_kmin=None, ecn_kmax=None,
                 drop_bytes=None, drain_host=None):
        self.sim = sim
        self.name = name
        self.rate = rate            # bytes/us
        self.prop = prop            # us
        self.fifo: list = []        # list of (pkt, next_hop, enq_ts)
        self.occ = 0.0              # bytes
        self.busy = False
        self.ecn_kmin = ecn_kmin
        self.ecn_kmax = ecn_kmax
        self.drop_bytes = drop_bytes
        self.drain_host = drain_host  # host id to re-pump when NIC drains
        self.drops = 0
        self.max_occ = 0.0

    def enqueue(self, pkt, next_hop, now):
        sim = self.sim
        if self.drop_bytes is not None and pkt.kind == ref.DATA \
                and self.occ + pkt.size > self.drop_bytes:
            self.drops += 1
            sim.total_drops += 1
            return  # silent drop
        self.fifo.append((pkt, next_hop, now))
        self.occ += pkt.size
        if self.occ > self.max_occ:
            self.max_occ = self.occ
        if not self.busy:
            self.busy = True
            sim.schedule(now + pkt.size / self.rate, "deq", self)

    def service(self, now):
        """Dequeue-completion event: head packet finished serializing."""
        pkt, next_hop, enq_ts = self.fifo.pop(0)
        self.occ -= pkt.size
        # Egress ECN: mark by the RESIDUAL queue (the queue behind this pkt).
        if self.ecn_kmin is not None and pkt.kind == ref.DATA:
            q = self.occ
            if q >= self.ecn_kmax:
                pkt.ecn = True
            elif q > self.ecn_kmin:
                frac = (q - self.ecn_kmin) / max(self.ecn_kmax - self.ecn_kmin, 1e-9)
                if self.sim.rng.random() < frac:
                    pkt.ecn = True
        self.sim.schedule(now + self.prop, "hop", (pkt, next_hop))
        if self.fifo:
            self.sim.schedule(now + self.fifo[0][0].size / self.rate,
                              "deq", self)
        else:
            self.busy = False
            if self.drain_host is not None and not self.fifo:
                # NIC wire is free again: let the host clock out more packets
                self.sim.schedule_pump(now, self.drain_host)

class Flow:
    """One message between (src, dst). Owns sender+receiver engines."""

    __slots__ = ("id", "src", "dst", "msg_bytes", "sender", "receiver",
                 "start_ts", "timer_seq", "meta")

    def __init__(self, fid, src, dst, msg_bytes, start_ts, meta=None):
        self.id = fid
        self.src = src
        self.dst = dst
        self.msg_bytes = msg_bytes
        self.start_ts = start_ts
        self.sender = None
        self.receiver = None
        self.timer_seq = 0
        self.meta = meta

    @property
    def fct(self):
        dt = self.sender.done_ts
        return dt - self.start_ts if dt is not None else None


class NetSim:
    """The discrete-event engine."""

    #: STrack sender engine; a control run swaps in a subclass.
    strack_sender = ref.STrackSender

    def __init__(self, topo: FatTree, net: NetworkSpec, *,
                 strack_params: Optional[STrackParams] = None,
                 seed: int = 1234):
        import random
        self.rng = random.Random(seed)
        self.topo = topo
        self.net = net
        self.sp = strack_params or make_strack_params(net)
        self.now = 0.0
        self.evq: list = []
        self.seq = itertools.count()
        self.flows: dict[int, Flow] = {}
        self.host_flows: dict[int, list] = {h: [] for h in range(topo.n_hosts)}
        self.host_rr: dict[int, int] = {h: 0 for h in range(topo.n_hosts)}
        self.total_drops = 0
        self.pump_pending: dict[int, float] = {}   # host -> scheduled t
        self.on_flow_done: Optional[Callable] = None
        self._fid = itertools.count()

        rate = net.rate_Bpus
        # Per-link propagation from the shared NetworkSpec delay model
        # (derived so the uncongested cross-ToR RTT == net.base_rtt_us,
        # exactly as the jitted fabric's per-hop pipeline realizes it).
        self.prop_us = net.hop_prop_effective_us
        prop = self.prop_us
        kmin = net.ecn_kmin_bytes
        kmax = net.ecn_kmax_bytes
        drop = net.drop_bytes

        # Queues
        self.nic_q = [Queue(self, f"nic{h}", rate, prop,
                            drain_host=h)
                      for h in range(topo.n_hosts)]
        self.tor_up = [[Queue(self, f"t{t}->s{s}", rate, prop,
                              kmin, kmax, drop)
                        for s in range(topo.n_spine)]
                       for t in range(topo.n_tor)]
        self.spine_down = [[Queue(self, f"s{s}->t{t}", rate, prop,
                                  kmin, kmax, drop)
                            for t in range(topo.n_tor)]
                           for s in range(topo.n_spine)]
        self.host_down = [Queue(self, f"t->h{h}", rate, prop,
                                kmin, kmax, drop)
                          for h in range(topo.n_hosts)]
    # ------------------------------------------------------------------ #
    def schedule(self, t, kind, payload):
        heapq.heappush(self.evq, (t, next(self.seq), kind, payload))

    def schedule_pump(self, t, host):
        """Deduplicated pump scheduling: at most one pending pump per host
        at or before any requested time (prevents event storms when many
        paced flows share a NIC)."""
        pending = self.pump_pending.get(host)
        if pending is not None and pending <= t + 1e-9:
            return
        self.pump_pending[host] = t
        heapq.heappush(self.evq, (t, next(self.seq), "pump", host))

    def add_flow(self, src, dst, msg_bytes, start_ts=0.0, meta=None) -> Flow:
        fid = next(self._fid)
        fl = Flow(fid, src, dst, msg_bytes, start_ts, meta)
        sp = self.sp
        fl.sender = self.strack_sender(sp, fid, msg_bytes, start_ts)
        fl.receiver = ref.STrackReceiver(sp, fl.sender.total_pkts)
        self.flows[fid] = fl
        self.host_flows[src].append(fl)
        self.schedule_pump(start_ts, src)
        self._arm_timer(fl, start_ts)
        return fl

    # ------------------------------------------------------------------ #
    def _route(self, pkt, src, dst):
        """Queues a packet takes from src's ToR to dst host."""
        topo = self.topo
        st, dt = topo.tor_of(src), topo.tor_of(dst)
        if st == dt:
            return [self.host_down[dst]]
        s = topo.ecmp_spine(src, dst, pkt.entropy)
        return [self.tor_up[st][s], self.spine_down[s][dt],
                self.host_down[dst]]

    def _launch(self, pkt, now):
        """Send pkt from its src host NIC through the fabric to pkt.dst."""
        pkt._route = self._route(pkt, pkt.src, pkt.dst)
        pkt._hop = 0
        self.nic_q[pkt.src].enqueue(pkt, ("fabric", pkt), now)

    def _pump(self, host, now):
        """Pull-based NIC: clock out packets while the wire is free."""
        nic = self.nic_q[host]
        if nic.busy:
            return
        flows = self.host_flows[host]
        n = len(flows)
        if n == 0:
            return
        start = self.host_rr[host]
        for i in range(n):
            fl = flows[(start + i) % n]
            snd = fl.sender
            if snd.done():
                continue
            if fl.start_ts > now + 1e-9:
                # future-dated flow (an open-loop arrival): a shared
                # host's pump must not clock it out early; re-arm for
                # its start time (the dedup in schedule_pump may have
                # swallowed the pump add_flow armed)
                self.schedule_pump(fl.start_ts, host)
                continue
            if not snd.can_send():
                continue
            pkt = snd.next_packet(now)
            if pkt is None:
                continue
            pkt.src, pkt.dst = fl.src, fl.dst
            self.host_rr[host] = (start + i + 1) % n
            self._launch(pkt, now)
            return

    # ------------------------------------------------------------------ #
    def _arm_timer(self, fl, now):
        dl = fl.sender.next_timer_deadline()
        if dl != math.inf:
            fl.timer_seq += 1
            self.schedule(max(dl, now + 1e-3), "timer", (fl, fl.timer_seq))

    def _on_timer(self, fl, seq, now):
        if seq != fl.timer_seq or fl.sender.done():
            return
        probe = fl.sender.on_timer(now)
        if probe is not None:
            probe.src, probe.dst = fl.src, fl.dst
            self._launch(probe, now)
        self.schedule_pump(now, fl.src)
        self._arm_timer(fl, now)

    def _deliver(self, pkt, now):
        """Packet reached an endpoint host."""
        fl = self.flows[pkt.flow]
        if pkt.kind in (ref.DATA, ref.PROBE):
            out = fl.receiver.on_data(pkt, now)
            if out is None:
                return
            out.src, out.dst = fl.dst, fl.src
            self._launch(out, now)
        else:  # SACK back at the sender
            was_done = fl.sender.done()
            fl.sender.on_sack(pkt, now)
            self._arm_timer(fl, now)
            self.schedule_pump(now, fl.src)
            if fl.sender.done() and not was_done and self.on_flow_done:
                self.on_flow_done(fl, now)

    # ------------------------------------------------------------------ #
    def run(self, until: float = math.inf, max_events: int = 200_000_000):
        evq = self.evq
        n = 0
        while evq and n < max_events:
            t, seq, kind, payload = heapq.heappop(evq)
            if t > until:
                # keep the event for a later run(until=...) call
                heapq.heappush(evq, (t, seq, kind, payload))
                self.now = until
                return
            self.now = t
            n += 1
            if kind == "deq":
                payload.service(t)
            elif kind == "hop":
                pkt, nh = payload
                if nh[0] == "fabric":
                    self._advance(pkt, t)
                else:
                    self._deliver(pkt, t)
            elif kind == "pump":
                if self.pump_pending.get(payload) is not None \
                        and self.pump_pending[payload] <= t + 1e-9:
                    self.pump_pending.pop(payload, None)
                self._pump(payload, t)
            elif kind == "timer":
                fl, seq = payload
                self._on_timer(fl, seq, t)

    def _advance(self, pkt, now):
        """Move pkt to its next fabric hop or deliver at host."""
        hops = pkt._route
        i = pkt._hop
        if i < len(hops):
            q = hops[i]
            pkt._hop = i + 1
            q.enqueue(pkt, ("fabric", pkt) if i + 1 < len(hops)
                      else ("host", pkt.dst), now)
            # after the NIC, subsequent "hop" events carry ("fabric", pkt)
        else:
            self._deliver(pkt, now)
