"""Pallas kernels for the fabric scan body's three dominant stages.

The ``lax.scan`` body in ``sim/fabric.py`` spends its time in three
gather/scatter-heavy stages: the fused queue-ring service + enqueue step
(ring-head pop, occupancy drop/ECN decisions, two-pass rank + flat ring
scatter), the sort-free enqueue ranker, and the per-flow protocol
transitions (``on_ack`` / ``on_timer`` / ``next_packet``, optionally over
a gathered ``active_cap`` slate).  This module provides those stages as
Pallas kernels, selected by ``FabricConfig.kernel_backend``:

  * ``"jnp"`` (default) — no Pallas: the fabric calls the stage *core*
    functions inline and XLA fuses them as before.
  * ``"pallas"`` — compiled Pallas kernels.  They do not lower for the
    TPU yet (see the caveats below), so this backend fails loudly there.
  * ``"pallas_interpret"`` — Pallas interpret mode: the kernel bodies run
    as ordinary XLA ops on any backend (CPU CI), preserving the kernel
    call structure and ref semantics without a Mosaic/Triton compile.

Bit-exactness strategy
----------------------
The serve/enqueue and transition kernels are *fused-core* kernels: the
fabric builds one core function per stage (closing over its static dims
and protocol dispatch) and either calls it inline (jnp backend) or hands
it to :func:`fused_stage_kernel`, which runs the SAME core inside a
single-block ``pallas_call`` — all operands loaded from refs up front,
all results stored back at the end.  Both paths therefore execute the
same math on the same operands, so they are bit-exact by construction;
the differential-fuzz suite (``tests/test_fuzz_parity.py``) and the
per-kernel parity tests (``tests/test_fabric_kernels.py``) gate it.

The ranker is a genuinely independent second implementation — a
sequential block sweep carrying a running per-queue count table instead
of the jnp path's scatter-add table + exclusive cumsum + batched tril —
and is validated against the O(M^2) oracle and the argsort reference in
``tests/test_rank_active.py`` / ``tests/test_fabric_kernels.py``.
Integer ranks are deterministic, so algorithm independence still yields
bit-identical results.

Compiled-mode caveats (see docs/performance.md "Kernel backends"): the
Pallas TPU lowering refuses these kernels, as a compile for a TPU v5e
shows (tests/test_tpu_compile.py pins both refusals):

  * the ranker raises ``NotImplementedError: Unimplemented primitive in
    Pallas TPU lowering: dynamic_slice`` — its block sweep reads and
    writes blocks with ``dynamic_index_in_dim`` / ``dynamic_update_slice``;
  * the fused serve/enqueue and transition cores raise the same error for
    ``scatter`` — their ``.at[].set/add`` updates.

Nothing falls back: ``kernel_backend="pallas"`` raises that error, and the
benchmark no longer offers it by default.  Beyond lowering, the fused
stages are single-block (every operand must fit VMEM) and trace protocol
``lax.cond`` / segment ops inside the kernel body.  Interpret mode has
none of these limits and is the only mode that runs, on CPU CI.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Block width of the ranker kernel's sequential sweep (matches the jnp
#: ranker's ``_RANK_CHUNK``: intra-block work is a dense CHUNK x CHUNK
#: strictly-lower-triangle count).
RANK_CHUNK = 256


def iota1(n: int) -> jax.Array:
    """1-D int32 iota that is legal inside TPU Pallas kernel bodies
    (TPU requires >= 2-D iota; this broadcasts then squeezes)."""
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)[:, 0]


# --------------------------------------------------------------------------- #
# Kernel 2: the sort-free enqueue ranker
# --------------------------------------------------------------------------- #

def rank_in_queue_core(qid: jax.Array, flag: jax.Array, n_queues: int,
                       chunk: int = RANK_CHUNK) -> jax.Array:
    """Rank of each candidate among flag-set candidates of the same queue
    (candidate-index order), ``-1`` at non-flagged entries — the
    ``fabric._rank_in_queue`` contract as one kernel-safe computation.

    Single sequential sweep over ``chunk``-wide blocks carrying a running
    per-queue count table: each block reads its per-queue starting ranks
    from the table (the incremental equivalent of the jnp path's
    scatter-add table + exclusive block cumsum), resolves intra-block
    order with a dense strictly-lower-triangle same-queue count, and
    scatter-adds its own flagged counts back into the table.  Runs as-is
    inside other kernel bodies (the fused serve/enqueue kernel inlines it
    for candidate counts past the all-pairs cutoff).
    """
    m = qid.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.int32)
    c = int(chunk)
    qid = qid.astype(jnp.int32)
    pad = (-m) % c
    if pad:
        qid = jnp.concatenate(
            [qid, jnp.full((pad,), n_queues, jnp.int32)])
        flag = jnp.concatenate([flag, jnp.zeros((pad,), bool)])
    nb = (m + pad) // c
    qc = qid.reshape(nb, c)
    fc = flag.reshape(nb, c)
    tril = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
            < jax.lax.broadcasted_iota(jnp.int32, (c, c), 0))

    def block(b, carry):
        counts, out = carry
        qb = jax.lax.dynamic_index_in_dim(qc, b, 0, keepdims=False)
        fb = jax.lax.dynamic_index_in_dim(fc, b, 0, keepdims=False)
        base = counts[qb]
        intra = jnp.sum((qb[:, None] == qb[None, :])
                        & fb[None, :] & tril, axis=1).astype(jnp.int32)
        out = jax.lax.dynamic_update_slice(
            out, jnp.where(fb, base + intra, -1), (b * c,))
        counts = counts.at[jnp.where(fb, qb, n_queues)].add(
            fb.astype(jnp.int32))
        return counts, out

    _, out = jax.lax.fori_loop(
        0, nb, block, (jnp.zeros((n_queues + 1,), jnp.int32),
                       jnp.zeros((nb * c,), jnp.int32)))
    return out[:m]


def rank_in_queue_kernel(qid: jax.Array, flag: jax.Array, n_queues: int,
                         *, chunk: int = RANK_CHUNK,
                         interpret: bool = True) -> jax.Array:
    """The ranker as a standalone single ``pallas_call`` (the three XLA
    ops of the jnp path — scatter-add table, exclusive cumsum, batched
    tril resolve — collapsed into one kernel)."""
    if qid.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)

    def kernel(q_ref, f_ref, o_ref):
        o_ref[...] = rank_in_queue_core(q_ref[...], f_ref[...],
                                        n_queues, chunk)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((qid.shape[0],), jnp.int32),
        interpret=interpret)(
        jnp.asarray(qid, jnp.int32), jnp.asarray(flag, bool))


# --------------------------------------------------------------------------- #
# Kernels 1 & 3: fused-core stages (serve+enqueue, per-flow transitions)
# --------------------------------------------------------------------------- #

def fused_stage_kernel(core, args, *, interpret: bool = True):
    """Run ``core(*args)`` as one single-block ``pallas_call``.

    ``args`` is an arbitrary pytree-per-argument tuple (protocol flow
    states, queue rings, lane vectors, traced scalars); every leaf
    becomes a kernel input ref, scalars ride as shape-(1,) arrays.  The
    kernel body loads all refs, rebuilds the argument pytrees, calls the
    SAME core function the jnp backend calls inline, and stores the
    flattened result pytree into the output refs — so the Pallas and jnp
    paths are one implementation and differ only in execution substrate.
    Output shapes/dtypes come from ``jax.eval_shape`` on the core, which
    keeps this wrapper agnostic to the protocol's state pytrees.
    """
    flat, treedef = jax.tree.flatten(args)
    flat = [jnp.asarray(x) for x in flat]
    in_scalar = [x.ndim == 0 for x in flat]
    ins = [x[None] if s else x for x, s in zip(flat, in_scalar)]

    out_struct = jax.eval_shape(
        lambda *xs: core(*jax.tree.unflatten(treedef, xs)), *flat)
    out_leaves, out_tree = jax.tree.flatten(out_struct)
    out_scalar = [s.shape == () for s in out_leaves]
    out_shape = tuple(
        jax.ShapeDtypeStruct((1,) if sc else s.shape, s.dtype)
        for s, sc in zip(out_leaves, out_scalar))
    n_in = len(ins)

    def kernel(*refs):
        vals = [r[...] for r in refs[:n_in]]
        vals = [v[0] if s else v for v, s in zip(vals, in_scalar)]
        outs = core(*jax.tree.unflatten(treedef, vals))
        for ref, leaf, sc in zip(refs[n_in:], jax.tree.leaves(outs),
                                 out_scalar):
            ref[...] = leaf[None] if sc else leaf

    res = pl.pallas_call(kernel, out_shape=out_shape,
                         interpret=interpret)(*ins)
    if not isinstance(res, (tuple, list)):
        res = (res,)
    res = [r[0] if sc else r for r, sc in zip(res, out_scalar)]
    return jax.tree.unflatten(out_tree, res)


def serve_enqueue_kernel(core, args, *, interpret: bool = True):
    """Kernel 1: fused queue-ring service + two-pass enqueue (ring-head
    pop, ECN mark, occupancy drop/accept, rank + flat ring scatter,
    departure-time lane update) as one kernel call."""
    return fused_stage_kernel(core, args, interpret=interpret)


def flow_transition_kernel(core, args, *, interpret: bool = True):
    """Kernel 3: per-flow protocol transitions (``on_ack`` / ``on_timer``
    / ``next_packet`` + NIC round-robin arbitration) as one kernel call.
    The active-set variant gathers the ``active_cap`` lane slate from the
    [N] state and scatters it back INSIDE the kernel, so the
    intermediate [A]-shaped flow pytrees never materialize in HBM."""
    return fused_stage_kernel(core, args, interpret=interpret)
