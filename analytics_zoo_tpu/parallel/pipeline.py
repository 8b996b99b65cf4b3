"""Pipeline parallelism: GPipe-style microbatch schedule over the mesh's
"pipeline" axis.

Capability beyond the reference (data-parallel only, SURVEY §2.5): a stack of
S identical stages (the transformer-block case) is sharded one-stage-per-
device-group along "pipeline"; microbatches stream in and activations hop
stage-to-stage with `lax.ppermute` (neighbour transfers — the pattern that
tolerates DCN between slices, which is why "pipeline" is the outermost mesh
axis, `common/mesh.py`). The whole schedule is one `lax.scan` inside
`shard_map`, so it jits to a single XLA program and is differentiable (the
ppermute transposes to the reverse permutation in backward).

Schedule: T = n_micro + S - 1 ticks (fill + drain). At tick t, stage 0 eats
microbatch t (ticks >= n_micro recompute the last microbatch; their outputs
are discarded), stage p processes what stage p-1 produced at t-1, and the last
stage's outputs from ticks S-1..T-1 are the results, broadcast with a masked
psum.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.common.mesh import BATCH_AXES, DeviceMesh


def _pipeline_shard(params, mbs, stage_fn: Callable, axis: str, n_stages: int):
    """Per-shard body. params: this stage's params (leading dim 1 stripped
    by caller's tree_map); mbs: [M, mb, ...] microbatches (replicated over
    the pipeline axis)."""
    M = mbs.shape[0]
    T = M + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    idx = lax.axis_index(axis)

    def body(act, t):
        recv = lax.ppermute(act, axis, perm)
        mb_t = lax.dynamic_index_in_dim(
            mbs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        inp = jnp.where(idx == 0, mb_t, recv)
        out = stage_fn(params, inp)
        return out, out

    # carry becomes pipeline-varying after the first ppermute; mark the
    # initial value to match (shard_map vma typing)
    act0 = lax.pcast(jnp.zeros_like(mbs[0]), axis, to="varying")
    _, ys = lax.scan(body, act0, jnp.arange(T))
    valid = ys[n_stages - 1:]                      # [M, mb, ...]
    out = jnp.where(idx == n_stages - 1, valid, jnp.zeros_like(valid))
    return lax.psum(out, axis)                     # broadcast final outputs


def pipeline_apply(stage_fn: Callable, stacked_params, microbatches,
                   mesh: DeviceMesh, axis: str = "pipeline",
                   seq_axis: str = None):
    """Run `stage_fn(params_s, x) -> y` (same x/y shape) for stages
    s = 0..S-1 as a pipeline.

    stacked_params: pytree whose leaves have leading dim S (one slice per
    stage), sharded over `axis`. microbatches: [n_micro, mb_size, ...];
    the batch dim shards over the data axes as usual. Returns
    [n_micro, mb_size, ...] outputs (identical on every pipeline rank).

    `seq_axis`: when the microbatches carry a sequence dim at position 2
    that is already sharded over a mesh axis (ring-attention output),
    name it here so the pipeline consumes it sharded instead of forcing
    an all-gather + full rematerialization between the two shard_maps
    (per-token stages never need the full sequence).
    """
    S = mesh.size(axis)
    n_stacked = {leaf.shape[0]
                 for leaf in jax.tree_util.tree_leaves(stacked_params)}
    if n_stacked != {S} and S != 1:
        raise ValueError(
            f"stacked_params leading dims {sorted(n_stacked)} must all equal "
            f"the pipeline axis size ({S})")
    if S == 1:
        def apply_all(x):
            def body(x, p):
                return stage_fn(p, x), None
            y, _ = lax.scan(body, x, stacked_params)
            return y
        return jax.vmap(apply_all)(microbatches)

    param_specs = jax.tree_util.tree_map(
        lambda p: P(axis, *([None] * (p.ndim - 1))), stacked_params)
    mb_spec = P(None, BATCH_AXES, seq_axis) if seq_axis \
        else P(None, BATCH_AXES)

    def shard(params, mbs):
        params = jax.tree_util.tree_map(
            lambda p: jnp.squeeze(p, axis=0), params)
        return _pipeline_shard(params, mbs, stage_fn, axis, S)

    fn = jax.shard_map(shard, mesh=mesh.mesh,
                       in_specs=(param_specs, mb_spec),
                       out_specs=mb_spec, check_vma=False)
    return fn(stacked_params, microbatches)


def to_microbatches(x, n_micro: int):
    """[B, ...] -> [n_micro, B/n_micro, ...] by INTERLEAVING (microbatch i
    takes rows i::n_micro). A contiguous split of a data-sharded batch
    would land the n_micro dim on the data axis (forcing a reshard every
    pipeline tick); interleaving keeps the per-microbatch batch dim
    sharded exactly like the full batch."""
    B = x.shape[0]
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible by n_micro {n_micro}")
    return jnp.swapaxes(
        x.reshape((B // n_micro, n_micro) + x.shape[1:]), 0, 1)


def from_microbatches(y):
    """Inverse of `to_microbatches` (restores original row order)."""
    n_micro, mb = y.shape[0], y.shape[1]
    return jnp.swapaxes(y, 0, 1).reshape((n_micro * mb,) + y.shape[2:])
