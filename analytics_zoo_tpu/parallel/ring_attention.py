"""Ring attention: exact attention over a sequence-sharded context.

Long-context capability the reference lacks entirely (SURVEY §5 "Long-context
/ sequence parallelism: absent"). The sequence dim of Q/K/V lives sharded over
the mesh's "sequence" axis; each device computes attention of its local query
block against every key/value block, rotating K/V around the ring with
`lax.ppermute` (one neighbour hop per step, riding ICI) while accumulating an
online (flash-style) softmax — so a T-length context needs only T/n per-device
memory and never materializes the [T, T] score matrix across devices.

The algorithm is the blockwise-parallel/ring formulation (Liu et al., ring
attention; same online-softmax update as the Pallas flash kernel in
`analytics_zoo_tpu/pallas/flash_attention.py`, which handles the *within
device* blocking — the two compose: ring over devices, flash within).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.common.mesh import BATCH_AXES, DeviceMesh

NEG_INF = -1e30


def _ring_attention_shard(q, k, v, kmask, axis: str):
    """Per-shard body. q: [B, H, Tq, D] local; k/v: [B, H, Tk, D] local;
    kmask: [B, Tk] additive (0 / -inf-like) for local keys, or None."""
    axis_size = lax.psum(1, axis)
    scale = 1.0 / math.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32) * scale
    B, H, Tq, D = q.shape

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def block_update(o, l, m, k, v, kmask):
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k.astype(jnp.float32))
        if kmask is not None:
            s = s + kmask[:, None, None, :]
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = (o * alpha[..., None]
                 + jnp.einsum("bhqk,bhkd->bhqd", p,
                              v.astype(jnp.float32)))
        return o_new, l_new, m_new

    # Derive initial accumulators from qf so they carry the same
    # varying-axes type as the loop outputs (shard_map vma check).
    o0 = jnp.zeros_like(qf)
    l0 = jnp.zeros_like(qf[..., 0])
    m0 = jnp.zeros_like(qf[..., 0]) + NEG_INF
    # Local block first, then rotate-and-accumulate n-1 times — the final
    # rotation (whose result would be discarded) never happens.
    o0, l0, m0 = block_update(o0, l0, m0, k, v, kmask)

    def step(carry, _):
        o, l, m, k, v, kmask = carry
        k = lax.ppermute(k, axis, perm)
        v = lax.ppermute(v, axis, perm)
        if kmask is not None:
            kmask = lax.ppermute(kmask, axis, perm)
        o, l, m = block_update(o, l, m, k, v, kmask)
        return (o, l, m, k, v, kmask), None

    (o, l, m, _, _, _), _ = lax.scan(
        step, (o0, l0, m0, k, v, kmask), None, length=axis_size - 1)
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked query rows -> zeros
    return (o / l[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mask: Optional[jax.Array] = None, *,
                   mesh: DeviceMesh, axis: str = "sequence",
                   head_axis: Optional[str] = "tensor"):
    """Exact attention with Q/K/V sequence-sharded over `axis`.

    q, k, v: [B, H, T, D]; mask: optional additive key mask [B, T]
    (0 for keep, large-negative for drop — the BERT convention,
    `keras/transformer.py make_mask` squeezed to 2D).
    Batch shards over the data axes, heads over `head_axis`, T over `axis`.
    """
    n = mesh.size(axis)
    if n == 1 and mesh.size(head_axis or "tensor") == 1:
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention)
        m4 = None if mask is None else mask[:, None, None, :]
        return _reference_attention(q, k, v, m4)

    qkv_spec = P(BATCH_AXES, head_axis, axis, None)
    mask_spec = P(BATCH_AXES, axis)

    shard_fn = functools.partial(_ring_attention_shard, axis=axis)
    if mask is None:
        fn = jax.shard_map(
            lambda q, k, v: shard_fn(q, k, v, None),
            mesh=mesh.mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec, check_vma=False)
        return fn(q, k, v)
    fn = jax.shard_map(
        shard_fn, mesh=mesh.mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec, check_vma=False)
    return fn(q, k, v, mask)
