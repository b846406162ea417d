"""Section 3.3 — STrack reliability in fixed-shape JAX form.

A real NIC ASIC tracks reordering with *fixed-size* bitmaps; this module is
the JAX mirror of that hardware: the receiver keeps a ``W``-bit arrival
bitmap anchored at EPSN, the sender keeps ``W``-bit sacked/claimed bitmaps.
All control flow is jnp.where / fixed-length vector ops so the whole thing
vmaps across flows.

Packet sizes: every PSN is a full MTU except the message's final PSN,
whose wire size is the message's odd tail (``RelState.tail_bytes``,
mirroring ``ref.STrackSender.pkt_size``).  The sent/claimed byte ledgers
account that tail exactly, so sub-MTU messages and odd tails keep
``inflight_bytes`` consistent with the receiver's ``bytes_recvd``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .params import STrackParams

REORDER_WINDOW = 512  # W: receiver/sender reorder window, packets


class SackMsg(NamedTuple):
    """The SACK wire format of Fig. 7 (plus echoed path/ts/ecn)."""

    valid: jax.Array        # bool: was a SACK emitted
    epsn: jax.Array         # i32
    sack_base: jax.Array    # i32
    sack_bits: jax.Array    # bool[sack_bitmap_bits]
    bytes_recvd: jax.Array  # f32
    ooo_cnt: jax.Array      # i32
    ecn: jax.Array          # bool (echoed)
    entropy: jax.Array      # i32 (echoed)
    ts: jax.Array           # f32 (echoed send timestamp)
    probe_reply: jax.Array  # bool


class ReceiverState(NamedTuple):
    epsn: jax.Array             # i32
    bitmap: jax.Array           # bool[W] relative to epsn (bit 0 == epsn)
    bytes_recvd: jax.Array      # f32, deduplicated
    bytes_since_sack: jax.Array  # f32
    lpsn: jax.Array             # i32, -1 = invalid
    total_pkts: jax.Array       # i32


def init_receiver(total_pkts) -> ReceiverState:
    return ReceiverState(
        epsn=jnp.zeros((), jnp.int32),
        bitmap=jnp.zeros((REORDER_WINDOW,), bool),
        bytes_recvd=jnp.zeros((), jnp.float32),
        bytes_since_sack=jnp.zeros((), jnp.float32),
        lpsn=jnp.full((), -1, jnp.int32),
        total_pkts=jnp.asarray(total_pkts, jnp.int32),
    )


def _shift(bitmap: jax.Array, shift: jax.Array, left: bool) -> jax.Array:
    """``bitmap`` moved by a traced ``shift`` in 0..len(bitmap), zero-filled:
    towards bit 0 if ``left``, else away from it (bits pushed past the end
    are lost).

    A log-step barrel shifter of static slices.  Under vmap a slice at a
    traced offset (``jnp.roll``, ``dynamic_slice``) is a gather, and the TPU
    compiler turns that gather into a serial loop over the rows."""
    n = bitmap.shape[0]
    for k in range(n.bit_length()):
        b = 1 << k
        fill = jnp.zeros((b,), bitmap.dtype)
        moved = (jnp.concatenate([bitmap[b:], fill]) if left
                 else jnp.concatenate([fill, bitmap[:n - b]]))
        bitmap = jnp.where((shift & b) != 0, moved, bitmap)
    return bitmap


def _shift_left(bitmap: jax.Array, shift: jax.Array) -> jax.Array:
    """bitmap <<= shift, zero-filled (shift is traced, 0..len(bitmap))."""
    return _shift(bitmap, shift, left=True)


def _sack_segment(bitmap: jax.Array, off: jax.Array,
                  nbits: int) -> jax.Array:
    """The ``nbits`` bits of ``bitmap`` from ``off`` >= 0 on, zeros past
    its end (``off`` is clamped to len(bitmap), as ``dynamic_slice``
    clamps)."""
    padded = jnp.concatenate([bitmap, jnp.zeros((nbits,), bitmap.dtype)])
    return _shift_left(padded, jnp.clip(off, 0, bitmap.shape[0]))[:nbits]


def _place_segment(bits: jax.Array, off: jax.Array, width: int) -> jax.Array:
    """A ``width``-bit map holding ``bits`` from ``off`` on (bits past its
    end are lost); ``off`` is clamped to 0..width."""
    padded = jnp.concatenate([bits, jnp.zeros((width,), bits.dtype)])[:width]
    return _shift(padded, jnp.clip(off, 0, width), left=False)


def receiver_on_data(rs: ReceiverState, p: STrackParams, psn: jax.Array,
                     size: jax.Array, ecn: jax.Array, entropy: jax.Array,
                     ts: jax.Array, is_probe: jax.Array,
                     ) -> tuple[ReceiverState, SackMsg]:
    """Process one data/probe packet; maybe emit a SACK (Section 3.3.1)."""
    W = REORDER_WINDOW
    psn = jnp.asarray(psn, jnp.int32)
    rel = psn - rs.epsn
    relc = jnp.clip(rel, 0, W - 1)
    inwin = (rel >= 0) & (rel < W)
    already = jnp.where(rel < 0, True, rs.bitmap[relc] & inwin)
    new = (~already) & inwin & (~is_probe)

    bitmap = jnp.where(new, rs.bitmap.at[relc].set(True), rs.bitmap)
    got = jnp.where(new, jnp.asarray(size, jnp.float32), 0.0)
    bytes_recvd = rs.bytes_recvd + got
    bytes_since_sack = rs.bytes_since_sack + got

    # Advance EPSN past the contiguous prefix of arrivals.
    all_set = jnp.all(bitmap)
    shift = jnp.where(bitmap[0],
                      jnp.where(all_set, W, jnp.argmax(~bitmap)), 0
                      ).astype(jnp.int32)
    epsn = rs.epsn + shift
    bitmap = _shift_left(bitmap, shift)

    lpsn = jnp.where(new & ((rs.lpsn < 0) | (psn < rs.lpsn)), psn, rs.lpsn)

    trigger = (bytes_since_sack >= p.ack_coalesce_bytes) \
        | (new & (rel == 0)) | is_probe | (epsn >= rs.total_pkts)

    # SACK segment containing the lowest PSN since the last SACK.
    lpsn_eff = jnp.maximum(jnp.where(lpsn < 0, epsn, lpsn), epsn)
    seg = (lpsn_eff - epsn) // p.sack_bitmap_bits
    base = epsn + seg * p.sack_bitmap_bits
    off = base - epsn
    sack_bits = _sack_segment(bitmap, off, p.sack_bitmap_bits)

    sack = SackMsg(
        valid=trigger,
        epsn=epsn,
        sack_base=base,
        sack_bits=sack_bits,
        bytes_recvd=bytes_recvd,
        ooo_cnt=jnp.sum(bitmap).astype(jnp.int32),
        ecn=jnp.asarray(ecn, bool),
        entropy=jnp.asarray(entropy, jnp.int32),
        ts=jnp.asarray(ts, jnp.float32),
        probe_reply=jnp.asarray(is_probe, bool),
    )
    new_rs = ReceiverState(
        epsn=epsn,
        bitmap=bitmap,
        bytes_recvd=bytes_recvd,
        bytes_since_sack=jnp.where(trigger, 0.0, bytes_since_sack),
        lpsn=jnp.where(trigger, jnp.int32(-1), lpsn),
        total_pkts=rs.total_pkts,
    )
    return new_rs, sack


class RelState(NamedTuple):
    """Sender-side reliability ledger (Section 3.3.2)."""

    epsn: jax.Array          # i32: receiver's cumulative ack point
    sacked: jax.Array        # bool[W] rel. to epsn
    claimed: jax.Array       # bool[W]: declared lost, not yet re-sent
    psn_next: jax.Array      # i32
    total_pkts: jax.Array    # i32
    tail_bytes: jax.Array    # f32: wire size of the final PSN (odd tail)
    bytes_sent: jax.Array    # f32
    bytes_recvd_seen: jax.Array  # f32
    bytes_claimed: jax.Array     # f32
    in_recovery: jax.Array   # bool
    recover_high: jax.Array  # i32
    probe_deadline: jax.Array  # f32
    rto_deadline: jax.Array    # f32
    done_ts: jax.Array         # f32, -1 until done
    rto_fires: jax.Array       # i32: RTO expirations (recovery observability)
    recoveries: jax.Array      # i32: SACK-triggered recovery entries


def init_rel(p: STrackParams, total_pkts, now: float = 0.0,
             tail_bytes=None) -> RelState:
    W = REORDER_WINDOW
    if tail_bytes is None:
        tail_bytes = float(p.mtu_bytes)
    return RelState(
        epsn=jnp.zeros((), jnp.int32),
        sacked=jnp.zeros((W,), bool),
        claimed=jnp.zeros((W,), bool),
        psn_next=jnp.zeros((), jnp.int32),
        total_pkts=jnp.asarray(total_pkts, jnp.int32),
        tail_bytes=jnp.asarray(tail_bytes, jnp.float32),
        bytes_sent=jnp.zeros((), jnp.float32),
        bytes_recvd_seen=jnp.zeros((), jnp.float32),
        bytes_claimed=jnp.zeros((), jnp.float32),
        in_recovery=jnp.zeros((), bool),
        recover_high=jnp.full((), -1, jnp.int32),
        probe_deadline=jnp.full((), now + p.probe_rtts * p.base_rtt_us,
                                jnp.float32),
        rto_deadline=jnp.full((), now + p.rto_us, jnp.float32),
        done_ts=jnp.full((), -1.0, jnp.float32),
        rto_fires=jnp.zeros((), jnp.int32),
        recoveries=jnp.zeros((), jnp.int32),
    )


def inflight_bytes(rel: RelState) -> jax.Array:
    return rel.bytes_sent - rel.bytes_recvd_seen - rel.bytes_claimed


def rel_done(rel: RelState) -> jax.Array:
    return rel.epsn >= rel.total_pkts


def pkt_wire_bytes(rel: RelState, p: STrackParams,
                   psn: jax.Array) -> jax.Array:
    """Wire size of one data PSN: full MTU, except the odd tail packet."""
    return jnp.where(psn >= rel.total_pkts - 1, rel.tail_bytes,
                     jnp.float32(p.mtu_bytes))


def _mask_wire_bytes(mask: jax.Array, epsn: jax.Array, rel: RelState,
                     p: STrackParams) -> jax.Array:
    """Total wire bytes of the PSNs flagged in ``mask`` (a W-bitmap
    anchored at ``epsn``): full MTUs except the message's final PSN."""
    W = mask.shape[0]
    n = jnp.sum(mask).astype(jnp.float32)
    tail_rel = rel.total_pkts - 1 - epsn
    tail_in = (tail_rel >= 0) & (tail_rel < W)
    tail_flag = mask[jnp.clip(tail_rel, 0, W - 1)] & tail_in
    return n * p.mtu_bytes - jnp.where(
        tail_flag, p.mtu_bytes - rel.tail_bytes, 0.0)


def _enter_recovery(rel: RelState, p: STrackParams, high: jax.Array,
                    enter: jax.Array) -> RelState:
    """Declare unsacked/unclaimed packets in [epsn, high) lost."""
    W = REORDER_WINDOW
    high = jnp.maximum(rel.recover_high, high)
    span = jnp.arange(W) < jnp.clip(high - rel.epsn, 0, W)
    lost = span & (~rel.sacked) & (~rel.claimed) \
        & (jnp.arange(W) + rel.epsn < rel.psn_next)
    lost = lost & enter
    return rel._replace(
        claimed=rel.claimed | lost,
        bytes_claimed=rel.bytes_claimed + _mask_wire_bytes(lost, rel.epsn,
                                                           rel, p),
        in_recovery=rel.in_recovery | enter,
        recover_high=jnp.where(enter, high, rel.recover_high),
    )


def rel_on_sack(rel: RelState, p: STrackParams, sack: SackMsg,
                cwnd_pkts: jax.Array, achieved_bdp_pkts: jax.Array,
                qdelay: jax.Array, now: jax.Array,
                ) -> tuple[RelState, jax.Array]:
    """Apply one SACK. Returns (new_state, newly_acked_bytes)."""
    W = REORDER_WINDOW
    now = jnp.asarray(now, jnp.float32)

    # --- probe-based loss detection (Algo 1 line 13) ---
    probe_loss = sack.probe_reply & (qdelay < 2 * p.base_rtt_us) \
        & (achieved_bdp_pkts == 0.0) & (~rel_done(rel))

    # --- cumulative advance ---
    shift = jnp.clip(sack.epsn - rel.epsn, 0, W).astype(jnp.int32)
    advanced = shift > 0
    idx = jnp.arange(W)
    # claimed-but-now-acked packets shifting out: un-claim their bytes
    unclaim_out = rel.claimed & (idx < shift)
    sacked = _shift_left(rel.sacked, shift)
    claimed = _shift_left(rel.claimed, shift)
    epsn = rel.epsn + shift
    bytes_claimed = rel.bytes_claimed - _mask_wire_bytes(unclaim_out,
                                                         rel.epsn, rel, p)

    # --- selective bits ---
    off = sack.sack_base - epsn  # may be negative (stale segment)
    placed = _place_segment(sack.sack_bits, off, W)
    placed = placed & (off >= 0)  # drop stale segments entirely for safety
    newly = placed & (~sacked)
    unclaim_sel = newly & claimed
    bytes_claimed = bytes_claimed - _mask_wire_bytes(unclaim_sel, epsn,
                                                     rel, p)
    sacked = sacked | placed
    claimed = claimed & (~unclaim_sel)

    acked_bytes = jnp.maximum(0.0, sack.bytes_recvd - rel.bytes_recvd_seen)
    bytes_recvd_seen = jnp.maximum(rel.bytes_recvd_seen, sack.bytes_recvd)

    rel = rel._replace(
        epsn=epsn, sacked=sacked, claimed=claimed,
        bytes_claimed=bytes_claimed, bytes_recvd_seen=bytes_recvd_seen,
        probe_deadline=now + p.probe_rtts * p.base_rtt_us,
        rto_deadline=jnp.where(advanced, now + p.rto_us, rel.rto_deadline),
    )

    # --- OOO-based loss detection ---
    thresh = jnp.maximum(cwnd_pkts, float(p.min_ooo_threshold))
    any_sacked = jnp.any(sacked)
    high_sacked = epsn + jnp.where(
        any_sacked, W - jnp.argmax(sacked[::-1]), 0).astype(jnp.int32)
    ooo_loss = (sack.ooo_cnt.astype(jnp.float32) > thresh) & sack.valid
    enter = ooo_loss | probe_loss
    high = jnp.where(probe_loss, rel.psn_next,
                     jnp.where(any_sacked, high_sacked, epsn))
    fresh_entry = enter & (~rel.in_recovery)
    rel = _enter_recovery(rel, p, high, enter)
    rel = rel._replace(
        recoveries=rel.recoveries + fresh_entry.astype(jnp.int32))

    # --- recovery exit ---
    exit_rec = rel.in_recovery & (rel.epsn >= rel.recover_high)
    rel = rel._replace(
        in_recovery=rel.in_recovery & (~exit_rec),
        recover_high=jnp.where(exit_rec, jnp.int32(-1), rel.recover_high),
        done_ts=jnp.where(rel_done(rel) & (rel.done_ts < 0), now,
                          rel.done_ts),
    )
    return rel, acked_bytes


def rel_next_psn(rel: RelState, p: STrackParams, cwnd_pkts: jax.Array,
                 ) -> tuple[RelState, jax.Array, jax.Array, jax.Array]:
    """Pick the next PSN to transmit. Returns (state, psn, is_rtx, valid)."""
    W = REORDER_WINDOW
    has_rtx = jnp.any(rel.claimed)
    window_ok = inflight_bytes(rel) < cwnd_pkts * p.mtu_bytes
    seq_ok = rel.psn_next - rel.epsn < W  # keep ledger in-window
    has_new = (rel.psn_next < rel.total_pkts) & seq_ok
    valid = (~rel_done(rel)) & window_ok & (has_rtx | has_new)

    rtx_rel = jnp.argmax(rel.claimed).astype(jnp.int32)
    use_rtx = valid & has_rtx
    psn = jnp.where(use_rtx, rel.epsn + rtx_rel, rel.psn_next)
    claimed = jnp.where(use_rtx, rel.claimed.at[rtx_rel].set(False),
                        rel.claimed)
    psn_next = jnp.where(valid & (~has_rtx), rel.psn_next + 1, rel.psn_next)
    bytes_sent = rel.bytes_sent + jnp.where(
        valid, pkt_wire_bytes(rel, p, psn), 0.0)
    return (rel._replace(claimed=claimed, psn_next=psn_next,
                         bytes_sent=bytes_sent),
            psn, use_rtx, valid)


def rel_on_timer(rel: RelState, p: STrackParams, now: jax.Array,
                 ) -> tuple[RelState, jax.Array]:
    """RTO + probe timers. Returns (state, send_probe)."""
    now = jnp.asarray(now, jnp.float32)
    active = ~rel_done(rel)
    rto = active & (now >= rel.rto_deadline)
    rel = _enter_recovery(rel, p, rel.psn_next, rto)
    rel = rel._replace(
        rto_deadline=jnp.where(rto, now + p.rto_us, rel.rto_deadline),
        rto_fires=rel.rto_fires + rto.astype(jnp.int32))
    probe = active & (~rto) & (now >= rel.probe_deadline)
    rel = rel._replace(
        probe_deadline=jnp.where(
            probe, now + p.probe_rtts * p.base_rtt_us, rel.probe_deadline))
    return rel, probe
