"""Grouped matrix products — one matmul per group of rows, Pallas TPU kernels.

An expert layer (`keras/moe.py`) sorts its token-slots by expert, so that
the rows of expert 0 come first, then those of expert 1, and so on, and
multiplies each run of rows by its own expert's matrix:

    out[start_g : start_g + size_g] = lhs[start_g : start_g + size_g] @ rhs[g]

The group sizes are data (what the router chose in this step), the buffer
is static and as long as the worst case needs, and the work follows the
sizes: the kernels visit the row tiles that hold a group's rows and no
others, through a grid whose length is itself computed from the sizes
(the layout of jax's `pallas.ops.tpu.megablox`: a list of work items
(group, row tile), handed to the index maps by scalar prefetch; a tile that
two groups share is visited once for each, and each stores its own rows).
Rows past the last group belong to nobody: in a tile that is visited they
come back zero, in tiles that are never visited they are NOT WRITTEN
(whatever the buffer held), so a caller selects (`jnp.where`, never a
multiplication) the rows it owns.

`grouped_matmul` is differentiable in `lhs` and `rhs` (custom VJP), and
both gradients are grouped too: d lhs is the same kernel against the
transposed matrices (`moe_gmm_dlhs`), d rhs one kernel that adds up
`lhs_g^T @ dout_g` over the row tiles of each group (`moe_gmm_drhs`; a
group with no rows gets zeros). The forward is `moe_gmm_fwd`: the three
names are what the compiler puts on the instructions, which the
benchmark's per-kernel metrics match.

Off the TPU (CPU tests) the same mathematics runs as `jax.lax.ragged_dot`,
or through the Pallas interpreter with `interpret=True`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# rows of a tile: a group of a few hundred rows wastes little on its two
# partial tiles, and a [256, K] x [K, N] product keeps the MXU fed
_TILE_ROWS = 256
# what one block of a weight matrix, or one f32 accumulator, may hold:
# double-buffered and with the row blocks beside it the kernels stay under
# the 16 MiB of scoped VMEM a kernel gets by default on the v5e
_BLOCK_BYTES = 3 * 2 ** 20


def _tile(dim: int, cap: int) -> int:
    """The largest divisor of `dim` that is a multiple of 128 and at most
    `cap`; `dim` itself where it is small enough or has no such divisor."""
    if dim <= cap:
        return dim
    for t in range(cap - cap % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def _work_items(group_sizes, m: int, tm: int):
    """The kernels' list of work: (offsets [G + 1], group of item w, row
    tile of item w, both [m / tm + G]) and the number of items. Group g
    owns rows offsets[g] .. offsets[g + 1]; its items are the row tiles
    those rows touch, in order, and the groups follow one another, so a
    tile shared by two groups is visited by consecutive items. Empty
    groups have no item. Entries past the count repeat the last group and
    are never read by a grid step (the grid ends at the count)."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    n_max = m // tm + G
    group_of = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                          total_repeat_length=n_max)
    before = jnp.cumsum(tiles) - tiles          # items of earlier groups
    tile_of = first[group_of] + jnp.arange(n_max, dtype=jnp.int32) \
        - before[group_of]
    return (offsets, group_of, jnp.clip(tile_of, 0, m // tm - 1)), \
        tiles.sum()


def _expected_rows(m: int) -> int:
    """Rows a cost estimate counts: the grid is data, so the scheduler is
    given a guess (three quarters of the buffer)."""
    return int(0.75 * m)


def _own_rows(offsets, g, tile, tm):
    """[tm, 1] bool: which rows of row tile `tile` belong to group `g`."""
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return jnp.logical_and(rows >= offsets[g], rows < offsets[g + 1])


def _gmm_kernel(tm, transpose_rhs, offsets, group_of, tile_of, lhs_ref,
                rhs_ref, out_ref):
    from jax.experimental import pallas as pl

    w = pl.program_id(1)
    g, tile = group_of[w], tile_of[w]
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
    acc = jax.lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                              preferred_element_type=jnp.float32)
    # the first item of a tile finds whatever the buffer held: the rows
    # that are not this group's start from zero
    first = jnp.logical_or(w == 0, tile_of[jnp.maximum(w - 1, 0)] != tile)
    held = jnp.where(first, 0.0, out_ref[...].astype(jnp.float32))
    out_ref[...] = jnp.where(_own_rows(offsets, g, tile, tm), acc,
                             held).astype(out_ref.dtype)


def _gmm(lhs, rhs, group_sizes, transpose_rhs: bool, name: str,
         interpret: bool):
    """lhs [m, k] (m a multiple of the row tile) times rhs[g] ([G, k, n],
    or [G, n, k] with `transpose_rhs`) group by group -> [m, n]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = min(_TILE_ROWS, m)
    tn = _tile(n, max(128, _BLOCK_BYTES // (k * rhs.dtype.itemsize)))
    meta, n_items = _work_items(group_sizes, m, tm)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, tn, k), lambda j, w, off, grp, til: (grp[w], j, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, k, tn), lambda j, w, off, grp, til: (grp[w], 0, j))
    rows = _expected_rows(m)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm, transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, w, off, grp, til: (til[w], 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, w, off, grp, til: (til[w], j)),
            grid=(n // tn, n_items),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(rows * (k + n) + rhs.size) * lhs.dtype.itemsize),
        interpret=interpret,
        name=name,
    )(*meta, lhs, rhs)


def _drhs_kernel(tm, offsets, group_of, tile_of, n_items, lhs_ref, dout_ref,
                 out_ref, acc_sc):
    from jax.experimental import pallas as pl

    w = pl.program_id(2)
    g, tile = group_of[w], tile_of[w]

    @pl.when(jnp.logical_or(w == 0, group_of[jnp.maximum(w - 1, 0)] != g))
    def _first_of_group():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    own = _own_rows(offsets, g, tile, tm)
    lhs = jnp.where(own, lhs_ref[...], jnp.zeros((), lhs_ref.dtype))
    dout = jnp.where(own, dout_ref[...], jnp.zeros((), dout_ref.dtype))
    acc_sc[...] += jax.lax.dot_general(
        lhs, dout, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(w == n_items[0] - 1, group_of[w + 1] != g))
    def _last_of_group():
        out_ref[...] = acc_sc[...].astype(out_ref.dtype)


def _drhs(lhs, dout, group_sizes, dtype, interpret: bool):
    """sum over the rows of each group of lhs_row^T dout_row:
    lhs [m, k], dout [m, n] -> [G, k, n]; zeros for an empty group."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = dout.shape[1]
    n_groups = group_sizes.shape[0]
    tm = min(_TILE_ROWS, m)
    tn = _tile(n, 1024)
    tk = _tile(k, max(128, _BLOCK_BYTES // (4 * tn)))
    (offsets, group_of, tile_of), n_items = _work_items(group_sizes, m, tm)
    rows = _expected_rows(m)
    out = pl.pallas_call(
        functools.partial(_drhs_kernel, tm),
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda a, b, w, off, grp, til, cnt:
                             (til[w], a)),
                pl.BlockSpec((tm, tn), lambda a, b, w, off, grp, til, cnt:
                             (til[w], b)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda a, b, w, off, grp, til, cnt:
                (grp[w], a, b)),
            grid=(k // tk, n // tn, n_items),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=rows * (k + n) * lhs.dtype.itemsize
            + n_groups * k * n * jnp.dtype(dtype).itemsize),
        interpret=interpret,
        name="moe_gmm_drhs",
    )(offsets, group_of, tile_of, n_items.reshape(1), lhs, dout)
    # a group with no rows has no item: its block was never written
    return jnp.where((group_sizes > 0)[:, None, None], out,
                     jnp.zeros((), dtype))


def _use_kernels(interpret: Optional[bool]) -> bool:
    return bool(interpret) or jax.default_backend() == "tpu"


def grouped_matmul(lhs, rhs, group_sizes, interpret: Optional[bool] = None):
    """lhs [m, k], rows sorted by group; rhs [G, k, n]; group_sizes [G]
    int32 with sum <= m. Returns [m, n] in lhs's type: row r of group g is
    `lhs[r] @ rhs[g]` (float32 accumulation). Rows past the last group are
    unspecified (module docstring): select the rows you own.
    Differentiable in `lhs` and `rhs`."""
    if not _use_kernels(interpret):
        return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))
    m = lhs.shape[0]
    pad = (-m) % min(_TILE_ROWS, -(-m // 8) * 8)
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _grouped(lhs, rhs, group_sizes.astype(jnp.int32), bool(interpret))
    return out[:m] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, False, "moe_gmm_fwd", interpret)


def _grouped_fwd(lhs, rhs, group_sizes, interpret):
    return _grouped(lhs, rhs, group_sizes, interpret), (lhs, rhs,
                                                        group_sizes)


def _grouped_bwd(interpret, res, dout):
    lhs, rhs, group_sizes = res
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm(dout, rhs, group_sizes, True, "moe_gmm_dlhs", interpret)
    drhs = _drhs(lhs, dout, group_sizes, rhs.dtype, interpret)
    return dlhs, drhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)
