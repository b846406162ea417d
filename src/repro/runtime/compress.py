"""Hierarchical int8 inter-pod gradient reduction (wire-level compression).

EXPERIMENTS.md §Perf cell 3 lesson 4: quantise-dequantise around an
all-reduce is a no-op to the fabric — XLA still moves f32.  This module
restructures the reduction itself with shard_map so the *inter-pod hop*
(the STrack-relevant DCN traffic) carries int8:

    1. intra-pod psum in f32 (ICI, cheap),
    2. per-tensor symmetric int8 quantisation,
    3. inter-pod exchange of the int8 payload (collective_permute — 4x
       fewer wire bytes, visible in the compiled HLO),
    4. local dequantise + add, with the quantisation error fed back by the
       caller (runtime/optimizer.compress_grads).

For >2 pods the exchange generalises to a ring of int8 permutes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quantize(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def hierarchical_int8_psum(x, mesh, *, pod_axis: str = "pod",
                           intra_axes=("data",)):
    """All-reduce ``x`` over (pod_axis + intra_axes) with int8 on the pod hop.

    x must be replicated over `model` (or further shard_map'ed by caller).
    Returns the full sum, same dtype as x.
    """
    n_pods = dict(zip(mesh.axis_names, mesh.devices.shape))[pod_axis]
    assert n_pods == 2, "ring generalisation for >2 pods: TODO"

    def body(xs):
        # (1) intra-pod reduction in full precision
        local = jax.lax.psum(xs, intra_axes)
        # (2) quantise the pod-local sum
        q, scale = _quantize(local.astype(jnp.float32))
        # (3) exchange int8 payload + scale with the peer pod
        other_q = jax.lax.ppermute(q, pod_axis, [(0, 1), (1, 0)])
        other_s = jax.lax.ppermute(scale, pod_axis, [(0, 1), (1, 0)])
        # (4) dequantise and combine
        total = local.astype(jnp.float32) \
            + other_q.astype(jnp.float32) * other_s
        return total.astype(xs.dtype)

    axes = (pod_axis,) + tuple(intra_axes)
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=P((*axes,)),     # all reduce axes stacked on dim 0
        out_specs=P((*axes,)),
        check_vma=False,
    )
    # x is logically replicated over the reduce axes: feed each device its
    # shard view by treating the leading dim... callers pass the already
    # device-local value; here we emulate with a psum-style contract:
    return f(x)


def two_stage_allreduce_bytes_demo(mesh, shape=(1024, 1024)):
    """Lower both a plain f32 psum and the hierarchical int8 version and
    return their per-device collective bytes (for tests/benchmarks)."""
    from ..launch.roofline import parse_collective_bytes
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    axes = tuple(a for a in mesh.axis_names if a != "model")

    def plain(v):
        def body(vs):
            return jax.lax.psum(vs, axes)
        return jax.shard_map(body, mesh=mesh, in_specs=P((*axes,)),
                             out_specs=P((*axes,)), check_vma=False)(v)

    def hier(v):
        return hierarchical_int8_psum(v, mesh, pod_axis="pod",
                                      intra_axes=tuple(
                                          a for a in axes if a != "pod"))

    out = {}
    for name, fn in (("plain_f32", plain), ("hier_int8", hier)):
        c = jax.jit(fn).lower(x).compile()
        coll = parse_collective_bytes(c.as_text(), mesh.devices.size)
        out[name] = coll
    return out
