"""The gated short convolution of an LFM2 conv layer
(`keras/gated_conv.py`) between its two projections — a Pallas TPU kernel
pair.

The in-projection's output [B, T, 3C] holds three parts side by side,
[B | C | x], each C channels wide:

    v_t = B_t * x_t
    c_t = sum_i taps[i] * v_{t - (K - 1) + i}     causal, a filter a channel,
                                                  zeros before token 0
    y_t = C_t * c_t                               [B, T, C]

In XLA that is three slices, a product, a pad, K shifted slices and a
second product, with [T, C] arrays between fusions. `gated_conv_fwd` does
it with a tile of tokens x 512 columns of each part in VMEM, in float32
whatever the step's type, 256 tokens of 128 channels at a time (the
`_AT_ONCE` of `pallas/short_conv.py`), reading the three parts of the one
array through three BlockSpecs on their column blocks and the 16 rows
before the tile of B and x through two more. `gated_conv_bwd` (custom VJP;
the in-projection's output and the taps are all that is kept) computes v
and c again in VMEM, walks the tiles of a sequence backwards as
`qkv_short_conv_bwd` does (the filter's gradient needs the K - 1 tokens
after a tile, carried in VMEM), and writes the gradient of all three parts
into ONE [B, T, 3C] array: a fourth grid axis walks the parts, the first
step of a tile computes all three (dB into its own block, dC and dx into
VMEM) and the next two write what is kept. The taps' gradient [K, C] is
summed in float32 in an output block that stays in VMEM across the batch
and the tiles.

The filter itself, its transpose and the taps' gradient are
`pallas/short_conv.py`'s (`_filtered`, `_filter_transposed`, `_taps_sums`),
with the taps count K static: the KDA layer's kernels trace the same code at
K = 4. The two names are what the compiler puts on the instructions and
what the benchmark's `gated_conv_time_share` matches; they match none of
`qkv_short_conv_`, `kda_` or `flash_`.

`gated_conv_fits` says which shapes the kernels take: on the TPU (or
`interpret`), parts of whole lane tiles and a sequence of whole tiles of
16 tokens or more. Everything else is the layer's XLA path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.pallas.delta_rule import _takes_kernels
from analytics_zoo_tpu.pallas.short_conv import (_HALO, _SEEN, _bytes,
                                                 _filter_transposed,
                                                 _filtered, _head_columns,
                                                 _head_taps, _heads_per_step,
                                                 _rows_at, _sub_rows,
                                                 _taps_sums, _tile)

# channels of a block the body takes at a time: one lane tile
_W = 128


def gated_conv_fits(shape, conv_size: int, interpret) -> bool:
    """Whether the kernels take an in-projection's output [B, T, 3C]."""
    _, T, C3 = shape
    return (_takes_kernels(interpret) and C3 % 3 == 0
            and (C3 // 3) % _W == 0 and _tile(T) is not None
            and 1 < conv_size <= _SEEN + 1)


def _gated_reader(b_ref, x_ref, bh_ref, xh_ref, cols):
    """`_filtered`'s readers of v = B * x, in float32."""
    f32 = jnp.float32

    def read(start, size):
        at = _rows_at(start, size)
        return b_ref[at, cols].astype(f32) * x_ref[at, cols].astype(f32)

    return read, lambda: (bh_ref[:, cols].astype(f32)
                          * xh_ref[:, cols].astype(f32))


def _fwd_kernel(chunks, b_ref, c_ref, x_ref, bh_ref, xh_ref, taps_ref,
                y_ref):
    """B, C, x [tile, chunks * 128], the 16 rows before the tile of B and
    x, taps [K, chunks * 128] float32 -> y [tile, chunks * 128]."""
    from jax.experimental import pallas as pl
    first_tile = pl.program_id(2) == 0
    tile = b_ref.shape[0]
    rows = _sub_rows(tile, _W)
    blocks = tile // rows

    def block(i, carry):
        h, r0 = i // blocks, (i % blocks) * rows
        cols = _head_columns(h, _W)
        _, c = _filtered(*_gated_reader(b_ref, x_ref, bh_ref, xh_ref, cols),
                         _head_taps(taps_ref, cols, rows), r0, rows,
                         first_tile)
        at = _rows_at(r0, rows)
        y_ref[at, cols] = (c_ref[at, cols].astype(jnp.float32)
                           * c).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks * blocks, block, None)


def _bwd_kernel(chunks, b_ref, c_ref, x_ref, bh_ref, xh_ref, taps_ref,
                dy_ref, d_ref, dtaps_ref, after_ref, kept_ref):
    """The tiles of a sequence from its last to its first, each visited
    three times (grid axis 3, the part of d written): the first visit
    computes dB into its block and dC, dx into `kept_ref` [2, tile,
    chunks * 128]; the next two write those. `after_ref` [chunks, 8, 128]
    float32: the filter output's cotangent on the first rows of the tile
    after this one (zeros after the sequence's end)."""
    from jax.experimental import pallas as pl
    f32, K = jnp.float32, taps_ref.shape[0]
    step, part = pl.program_id(2), pl.program_id(3)
    first_tile = step == pl.num_programs(2) - 1
    tile = b_ref.shape[0]
    rows = _sub_rows(tile, _W)
    blocks = tile // rows

    @pl.when(jnp.logical_and(step == 0, part == 0))
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)

    @pl.when(jnp.logical_and(jnp.logical_and(step == 0, part == 0),
                             pl.program_id(1) == 0))
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    def chunk(h, carry):
        cols = _head_columns(h, _W)
        taps = _head_taps(taps_ref, cols, rows)

        def block(j, carry):
            after, sums = carry
            r0 = (blocks - 1 - j) * rows
            at = _rows_at(r0, rows)
            copies, c = _filtered(
                *_gated_reader(b_ref, x_ref, bh_ref, xh_ref, cols), taps,
                r0, rows, first_tile)
            dy = dy_ref[at, cols].astype(f32)
            d_c = dy * c_ref[at, cols].astype(f32)
            sums = _taps_sums(sums, d_c, copies)
            d_v = _filter_transposed(taps, d_c, after)
            d_ref[at, cols] = (d_v * x_ref[at, cols].astype(f32)).astype(
                d_ref.dtype)
            kept_ref[0, at, cols] = (dy * c).astype(kept_ref.dtype)
            kept_ref[1, at, cols] = (d_v * b_ref[at, cols].astype(f32)
                                     ).astype(kept_ref.dtype)
            return d_c[:_SEEN], sums

        after, sums = jax.lax.fori_loop(
            0, blocks, block,
            (after_ref[h], (jnp.zeros((_SEEN, _W), f32),) * K))
        after_ref[h] = after
        for i, s in enumerate(sums):
            dtaps_ref[i:i + 1, cols] += jnp.sum(s, axis=0, keepdims=True)
        return carry

    @pl.when(part == 0)
    def _():
        jax.lax.fori_loop(0, chunks, chunk, None)

    @pl.when(part > 0)
    def _():
        d_ref[...] = kept_ref[part - 1]


def _plan(bcx, tile):
    """(channels of the parts, 128-channel chunks a grid step, its column
    blocks in one part, the tiles of a sequence)."""
    T, C = bcx.shape[1], bcx.shape[2] // 3
    chunks = _heads_per_step(C // _W, _W)
    return C, chunks, C // (chunks * _W), T // tile


def _in_specs(tile, chunks, groups, K, at):
    """B, C, x, the 16 rows before the tile of B and of x, taps: block
    specs over a grid whose steps `at` maps to (batch, column block of a
    part, tile)."""
    from jax.experimental import pallas as pl
    wc, halos = chunks * _W, tile // _HALO

    def part(p):
        return pl.BlockSpec((None, tile, wc), lambda *g: (
            at(*g)[0], at(*g)[2], p * groups + at(*g)[1]))

    def halo(p):
        return pl.BlockSpec((None, _HALO, wc), lambda *g: (
            at(*g)[0], jnp.maximum(at(*g)[2] * halos - 1, 0),
            p * groups + at(*g)[1]))

    return [part(0), part(1), part(2), halo(0), halo(2),
            pl.BlockSpec((K, wc), lambda *g: (0, at(*g)[1]))]


def _kernel_fwd(bcx, taps, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (B, T, _), K = bcx.shape, taps.shape[0]
    C, chunks, groups, tiles = _plan(bcx, tile)
    out = jax.ShapeDtypeStruct((B, T, C), bcx.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunks),
        out_shape=out,
        grid=(B, groups, tiles),
        in_specs=_in_specs(tile, chunks, groups, K, lambda b, g, t: (b, g, t)),
        out_specs=pl.BlockSpec((None, tile, chunks * _W),
                               lambda b, g, t: (b, t, g)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=B * T * C * (2 * K + 2), transcendentals=0,
            bytes_accessed=_bytes(bcx, taps, out)),
        interpret=interpret,
        name="gated_conv_fwd",
    )(bcx, bcx, bcx, bcx, bcx, taps)


def _kernel_bwd(bcx, taps, dy, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (B, T, _), K = bcx.shape, taps.shape[0]
    C, chunks, groups, tiles = _plan(bcx, tile)
    wc, last = chunks * _W, tiles - 1

    def at(g, b, t, p):
        return b, g, last - t

    outs = [jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
            jax.ShapeDtypeStruct(taps.shape, jnp.float32)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunks),
        out_shape=outs,
        grid=(groups, B, tiles, 3),
        in_specs=_in_specs(tile, chunks, groups, K, at) + [
            pl.BlockSpec((None, tile, wc),
                         lambda g, b, t, p: (b, last - t, g))],
        out_specs=[pl.BlockSpec((None, tile, wc),
                                lambda g, b, t, p: (b, last - t,
                                                    p * groups + g)),
                   pl.BlockSpec((K, wc), lambda g, b, t, p: (0, g))],
        scratch_shapes=[pltpu.VMEM((chunks, _SEEN, _W), jnp.float32),
                        pltpu.VMEM((2, tile, wc), bcx.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=B * T * C * (6 * K + 5), transcendentals=0,
            bytes_accessed=_bytes(bcx, taps, dy, *outs)),
        interpret=interpret,
        name="gated_conv_bwd",
    )(bcx, bcx, bcx, bcx, bcx, taps, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _kernels(bcx, taps, tile, interpret):
    return _kernel_fwd(bcx, taps.astype(jnp.float32), tile, interpret)


def _kernels_fwd(bcx, taps, tile, interpret):
    # nothing but the operands is kept: the gradient filters again
    return _kernels(bcx, taps, tile, interpret), (bcx, taps)


def _kernels_bwd(tile, interpret, res, dy):
    bcx, taps = res
    d, d_taps = _kernel_bwd(bcx, taps.astype(jnp.float32), dy, tile,
                            interpret)
    return d, d_taps.astype(taps.dtype)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def gated_conv(bcx, taps, interpret=None, tile=None):
    """bcx [B, T, 3C] (the parts [B | C | x]), taps [K, C] -> y [B, T, C]
    in bcx's type (module docstring), for a shape `gated_conv_fits` takes.
    `tile`: tokens of a tile, for tests; the largest of `short_conv`'s
    tiles that divides T unless given. Differentiable in both."""
    T = bcx.shape[1]
    tile = tile or _tile(T)
    if T % tile or tile % _sub_rows(tile, _W):
        raise ValueError(f"{T} tokens are no whole tiles of {tile}")
    return _kernels(bcx, taps, tile, bool(interpret))
