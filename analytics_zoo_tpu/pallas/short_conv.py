"""The q/k/v stage of a KDA layer between its projections and the
recurrence (`keras/linear_attention.py`) — a Pallas TPU kernel pair.

One of q, k, v as it leaves its projection, [B, T, n * w] (n heads of w
channels), becomes rows [B * n, T, w] of the recurrence:

    c_t = sum_i taps[i] * x_{t - (K - 1) + i}      causal, a filter a channel,
                                                   zeros before token 0
    a   = c * sigmoid(c)                           SiLU
    a   = a * rsqrt(sum_head(a^2) + eps) * scale   q and k only (`unit_scale`)

In XLA that is a pad, K shifted slices, a float32 reduction over a reshaped
minor dimension and a transpose, with a dozen [T, n * w] arrays between
fusions. `qkv_short_conv_fwd` does it with a tile of tokens x a few heads'
columns in VMEM, in float32 whatever the step's type, 256 tokens of one
head at a time (`_AT_ONCE`), and writes head-major rows through its output
BlockSpec: the transpose is no pass of its own. The K - 1 tokens of history
come from a second BlockSpec on the 16 rows before the tile (zeros at
token 0), so every grid step is its own. `qkv_short_conv_bwd` (custom VJP; the
projection and the taps are all that is kept) computes the forward again
in VMEM and walks the tiles of a sequence backwards: the gradient of the
filter needs the K - 1 tokens AFTER a tile, which it carries in VMEM from
the grid step before; the taps' gradient [K, n * w] is accumulated in
float32 in an output block that stays in VMEM across the batch and the
tiles. The two names are what the compiler puts on the instructions and
what the benchmark's `kda_short_conv_time_share` matches; they must not
begin `kda_`, which `kda_time_share` and `kda_roofline` read as the pass
over the chunks (`pallas/delta_rule.py`).

`short_conv_fits` says which shapes the kernels take: on the TPU (or
`interpret`), heads in whole lane tiles and a sequence in whole tiles of 16
tokens or more. Everything else is the layer's XLA path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.pallas.delta_rule import _takes_kernels

# tokens of a tile, the largest that divides T
_TILES = (512, 256, 128, 64, 32, 16)
# columns of a tile: the heads of a grid step are the most that fit them
_TILE_COLUMNS = 512
# rows of the block before a tile that bring its history: a whole tile of
# the narrowest type (bfloat16: 16 sublanes)
_HALO = 16
# of those, the rows the filter is shown: a float32 tile's 8 sublanes
_SEEN = 8
# elements of one head the kernels take at a time: 256 tokens at 128
# channels. Alone on a v5e (q of [1, 16384, 4096] bfloat16, forward /
# gradient): 16 tokens 4.55 / 5.07 ms, 32 2.35 / 2.68, 128 0.91 / 1.29,
# 256 0.67 / 1.06, 512 0.61 / 1.07 (v, no norm: 128 0.56 / 0.89, 256 0.58 /
# 0.98, 512 0.63 / 1.15): a short block waits for its own lane reductions.
# The heads and blocks are `fori_loop`s, not Python loops: unrolled, the
# kernels' jaxprs (1,500 equations the gradient's) cost the step program
# 15 s of tracing and 12 s of lowering in EVERY process, cached or not
_AT_ONCE = 32768


def _tile(T: int):
    return next((t for t in _TILES if T % t == 0), None)


def _heads_per_step(n: int, w: int) -> int:
    return next(h for h in (4, 2, 1) if n % h == 0 and h * w <= max(
        w, _TILE_COLUMNS))


def _sub_rows(tile: int, w: int) -> int:
    """Tokens of a head taken at a time: the tile, or the power of two
    (16 or more) that `_AT_ONCE` allows."""
    if tile * w <= _AT_ONCE:
        return tile
    return max(_HALO, 1 << ((_AT_ONCE // w).bit_length() - 1))


def short_conv_fits(shape, n_head: int, conv_size: int, interpret) -> bool:
    """Whether the kernels take a projection [B, T, n_head * w]."""
    _, T, C = shape
    return (_takes_kernels(interpret) and C % n_head == 0
            and (C // n_head) % 128 == 0 and _tile(T) is not None
            and 1 < conv_size <= _SEEN + 1)


def _roll_rows(a, shift: int):
    """out[r] = a[r - shift] along the rows, around the ends."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(a, shift % a.shape[0], 0)


def _rows_at(start, size):
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(start, _HALO), size)


def _head_sum(a):
    return jnp.sum(a, axis=-1, keepdims=True)


def _filtered(read, read_halo, taps, r0, rows, first_tile):
    """The filter core, shared with `pallas/gated_conv.py`: rows r0 ..
    r0 + rows of one block of columns of a tile, whose input `read(start,
    size)` gives as [size, w] and `read_halo()` as the 16 rows before the
    tile, in any type: (the K copies of the input in float32 that the taps
    multiply, the last of them the input itself; the filter's output c).
    K is `len(taps)`, static."""
    f32, K = jnp.float32, len(taps)
    # the rows before r0: the tile's own, or before its first the halo's
    own = read(jnp.maximum(r0, _HALO) - _HALO, _HALO)
    before = jnp.where(r0 == 0, read_halo(), own).astype(f32)
    before = jnp.where(jnp.logical_and(r0 == 0, first_tile), 0.0,
                       before[_HALO - _SEEN:])
    x = read(r0, rows).astype(f32)
    seen = jnp.concatenate([before, x], axis=0)
    copies = [_roll_rows(seen, K - 1 - i)[_SEEN:] for i in range(K - 1)] + [x]
    return copies, sum(t * s for t, s in zip(taps, copies))


def _taps_sums(sums, d_c, copies):
    """The taps' gradient of a block added to `sums`, one [8, w] float32
    partial sum a tap: d_c times each copy, summed down to the 8 sublanes
    of a tile (the lane reduction is left for once a head)."""
    rows, w = d_c.shape
    return tuple(s + (d_c * x).reshape(rows // _SEEN, _SEEN, w).sum(axis=0)
                 for s, x in zip(sums, copies))


def _filter_transposed(taps, d_c, after):
    """The filter's gradient to its input on a block of rows: d_x[t] = sum_i
    taps[i] * d_c[t + (K - 1) - i], with `after` [8, w] the cotangent of
    the first rows after the block (zeros after the sequence's end)."""
    K, rows = len(taps), d_c.shape[0]
    ahead = jnp.concatenate([d_c, after], axis=0)
    return taps[K - 1] * d_c + sum(
        taps[i] * _roll_rows(ahead, -(K - 1 - i))[:rows]
        for i in range(K - 1))


def _reader(x_ref, halo_ref, cols):
    """`_filtered`'s two readers over one array's columns `cols`."""
    return (lambda start, size: x_ref[_rows_at(start, size), cols],
            lambda: halo_ref[:, cols])


def _head_taps(taps_ref, cols, rows):
    return [jnp.broadcast_to(taps_ref[i:i + 1, cols], (rows, cols.size))
            for i in range(taps_ref.shape[0])]


def _head_columns(h, w):
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(h * w, 128), w)


def _fwd_kernel(heads, w, scale, eps, x_ref, halo_ref, taps_ref, out_ref):
    """x [tile, heads * w], the 16 rows before it, taps [K, heads * w]
    float32 -> rows [heads, tile, w]."""
    from jax.experimental import pallas as pl
    first_tile = pl.program_id(2) == 0
    tile = x_ref.shape[0]
    rows = _sub_rows(tile, w)
    blocks = tile // rows

    def block(i, carry):
        h, r0 = i // blocks, (i % blocks) * rows
        cols = _head_columns(h, w)
        _, c = _filtered(*_reader(x_ref, halo_ref, cols),
                         _head_taps(taps_ref, cols, rows), r0, rows,
                         first_tile)
        a = c * jax.nn.sigmoid(c)
        if scale is not None:
            a = a * (jax.lax.rsqrt(_head_sum(a * a) + eps) * scale)
        out_ref[h, _rows_at(r0, rows), :] = a.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, heads * blocks, block, None)


def _bwd_kernel(heads, w, scale, eps, x_ref, halo_ref, taps_ref, do_ref,
                dx_ref, dtaps_ref, after_ref):
    """The tiles of a sequence from its last to its first. `after_ref`
    [heads, 8, w] float32: the filter output's cotangent on the first rows
    of the tile after this one (zeros after the sequence's end)."""
    from jax.experimental import pallas as pl
    f32, K = jnp.float32, taps_ref.shape[0]
    step = pl.program_id(2)
    first_tile = step == pl.num_programs(2) - 1
    tile = x_ref.shape[0]
    rows = _sub_rows(tile, w)
    blocks = tile // rows

    @pl.when(step == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)

    @pl.when(jnp.logical_and(step == 0, pl.program_id(1) == 0))
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    def head(h, carry):
        cols = _head_columns(h, w)
        taps = _head_taps(taps_ref, cols, rows)

        def block(j, carry):
            after, sums = carry
            r0 = (blocks - 1 - j) * rows
            copies, c = _filtered(*_reader(x_ref, halo_ref, cols), taps,
                                  r0, rows, first_tile)
            gate = jax.nn.sigmoid(c)
            a = c * gate
            d_a = do_ref[h, _rows_at(r0, rows), :].astype(f32)
            if scale is not None:
                r = jax.lax.rsqrt(_head_sum(a * a) + eps)
                d_a = (d_a - a * (r * r * _head_sum(d_a * a))) * (r * scale)
            d_c = d_a * (gate + a * (1.0 - gate))
            sums = _taps_sums(sums, d_c, copies)
            d_x = _filter_transposed(taps, d_c, after)
            dx_ref[_rows_at(r0, rows), cols] = d_x.astype(dx_ref.dtype)
            return d_c[:_SEEN], sums

        after, sums = jax.lax.fori_loop(
            0, blocks, block,
            (after_ref[h], (jnp.zeros((_SEEN, w), f32),) * K))
        after_ref[h] = after
        for i, s in enumerate(sums):
            dtaps_ref[i:i + 1, cols] += jnp.sum(s, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, heads, head, None)


def _specs(T, n, w, K, tile, heads, reverse):
    """(x, halo, taps, rows) block specs over a grid whose last axis is the
    tiles of a sequence, first to last or (`reverse`) last to first; the
    axes before it (batch, group of heads) in the order `reverse` walks
    them: groups outermost, so the taps' gradient stays where it is."""
    from jax.experimental import pallas as pl
    groups, last, halos = n // heads, T // tile - 1, tile // _HALO

    def at(index):
        if reverse:
            return lambda g, b, t: index(b, g, last - t)
        return index

    return (
        pl.BlockSpec((None, tile, heads * w), at(lambda b, g, t: (b, t, g))),
        pl.BlockSpec((None, _HALO, heads * w), at(
            lambda b, g, t: (b, jnp.maximum(t * halos - 1, 0), g))),
        pl.BlockSpec((K, heads * w), at(lambda b, g, t: (0, g))),
        pl.BlockSpec((heads, tile, w), at(
            lambda b, g, t: (b * groups + g, t, 0))))


def _bytes(*arrays):
    return sum(a.size * a.dtype.itemsize for a in arrays)


def _plan(x, taps, n, tile, reverse):
    """(heads a grid step, a head's width, `_specs`) of a call."""
    T, w = x.shape[1], x.shape[2] // n
    heads = _heads_per_step(n, w)
    return heads, w, _specs(T, n, w, taps.shape[0], tile, heads, reverse)


def _kernel_fwd(x, taps, n, scale, eps, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (B, T, _), K = x.shape, taps.shape[0]
    heads, w, (x_spec, halo_spec, taps_spec, rows_spec) = _plan(
        x, taps, n, tile, reverse=False)
    out = jax.ShapeDtypeStruct((B * n, T, w), x.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads, w, scale, eps),
        out_shape=out,
        grid=(B, n // heads, T // tile),
        in_specs=[x_spec, halo_spec, taps_spec],
        out_specs=rows_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=x.size * (2 * K + 8), transcendentals=x.size,
            bytes_accessed=_bytes(x, taps, out)),
        interpret=interpret,
        name="qkv_short_conv_fwd",
    )(x, x, taps)


def _kernel_bwd(x, taps, d_rows, n, scale, eps, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (B, T, _), K = x.shape, taps.shape[0]
    heads, w, (x_spec, halo_spec, taps_spec, rows_spec) = _plan(
        x, taps, n, tile, reverse=True)
    outs = [jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(taps.shape, jnp.float32)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads, w, scale, eps),
        out_shape=outs,
        grid=(n // heads, B, T // tile),
        in_specs=[x_spec, halo_spec, taps_spec, rows_spec],
        out_specs=[x_spec, taps_spec],
        scratch_shapes=[pltpu.VMEM((heads, _SEEN, w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=x.size * (6 * K + 24), transcendentals=x.size,
            bytes_accessed=_bytes(x, taps, d_rows, *outs)),
        interpret=interpret,
        name="qkv_short_conv_bwd",
    )(x, x, taps, d_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _kernels(x, taps, n, scale, eps, tile, interpret):
    return _kernel_fwd(x, taps.astype(jnp.float32), n, scale, eps, tile,
                       interpret)


def _kernels_fwd(x, taps, n, scale, eps, tile, interpret):
    # nothing but the operands is kept: the gradient filters again
    return _kernels(x, taps, n, scale, eps, tile, interpret), (x, taps)


def _kernels_bwd(n, scale, eps, tile, interpret, res, d_rows):
    x, taps = res
    d_x, d_taps = _kernel_bwd(x, taps.astype(jnp.float32), d_rows, n, scale,
                              eps, tile, interpret)
    return d_x, d_taps.astype(taps.dtype)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def short_conv_rows(projected, taps, n_head: int, unit_scale, eps: float,
                    interpret=None, tile=None):
    """projected [B, T, n_head * w], taps [K, n_head * w] -> rows
    [B * n_head, T, w] in `projected`'s type (module docstring), for a
    shape `short_conv_fits` takes. `unit_scale` None: no norm. `tile`:
    tokens of a tile, for tests; the largest of `_TILES` that divides T
    unless given. Differentiable in `projected` and `taps`."""
    _, T, C = projected.shape
    tile = tile or _tile(T)
    if T % tile or tile % _sub_rows(tile, C // n_head):
        raise ValueError(f"{T} tokens are no whole tiles of {tile}")
    return _kernels(projected, taps, n_head,
                    None if unit_scale is None else float(unit_scale),
                    float(eps), tile, bool(interpret))
