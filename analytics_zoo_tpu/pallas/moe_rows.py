"""Row moves between token order and an expert layer's sorted buffer —
Pallas TPU kernels whose grid follows the held count.

An expert layer (`keras/moe.py`) sorts its N x k token-slots by expert into
a buffer of N x k rows. Its first `count` places hold the slots whose
expert lives here (`count` is the sum of the held experts' group sizes);
the places after them hold slots whose expert lives on another chip, and
nobody reads them. The kernels here move rows between the tokens and the
buffer and visit the first `count` places alone: `count` is data, handed
to them by scalar prefetch, and their grids and loops are computed from it,
as `grouped_matmul`'s grid is from the group sizes.

    moe_rows_gather        out[p] = x[src[p]]                      p < count
                           with `scale`: times scale[p]
                           with `dot_with`: also dot[p] = ys[p] . x[src[p]]
                           in float32
    moe_rows_combine       out[n] = sum over j of x[position[n, j]]
                           where keep[n, j] (times w[n, j] with weights):
                           float32 in the order of j, written once in x's
                           type; a token with no kept slot gets zeros and
                           costs no fetch
    moe_rows_gather_pack,  rows laid out for the fetches (below): the
    moe_rows_combine_pack  tokens a gather reads, the buffer's first
                           `count` places a combine reads

Rows travel one DMA a row, a tile's DMAs all started and then all waited.
A DMA moves whole tiles of the second-minor dimension (8 rows of an
[R, H] array: Mosaic refuses a 1-row slice), so a row of [R, H] cannot be
fetched alone; a row of an [R, 1, W] array is a tile of its own. Rows are
therefore fetched from "words" [R, 1, W]: bfloat16 rows as uint32 words,
each holding column i in its low half and column i + H / 2 in its high
half (W = H / 2, the rows' own bytes), rows of another type widened to
float32 (W = H).

The combine moves each token's kept slots to the front first (`_by_rank`):
a token's fetches are then a loop over its kept slots, and 8 tokens' r-th
slots are added only where one of them keeps r + 1 slots or more; the sum
stays in the order of j.

What a kernel does not visit it does not write: the gather's places past
`count` are unspecified (whatever the output's memory held), as are its
`dot` there. Consumers select them away (`grouped_matmul`'s kernels do, by
their own rows), never multiply them.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

# places of the buffer a gather step fetches
_TILE_ROWS = 256
# tokens a combine step writes: its k x 128 fetched rows sit in VMEM at once
_TILE_TOKENS = 128
# an int32 vector lies in HBM in tiles of 1024 entries; a block of one in
# SMEM is a whole number of them
_INDEX_BLOCK = 1024


def takes_kernels(interpret) -> bool:
    return bool(interpret) or jax.default_backend() == "tpu"


def _packed(dtype) -> bool:
    return jnp.dtype(dtype) == jnp.bfloat16


def _words(dtype, hidden: int):
    """(type, width) of a row of `hidden` values of `dtype` as words."""
    if _packed(dtype):
        return jnp.uint32, hidden // 2
    return jnp.float32, hidden


def fits(n_tokens: int, hidden: int, dtype, interpret) -> bool:
    """Whether the kernels take a layer call of `n_tokens` rows of `hidden`
    values of `dtype`: tokens in a multiple of 16, and a word row a whole
    number of 128 lanes on the chip (any even width under the
    interpreter)."""
    if not takes_kernels(interpret) or n_tokens % 16:
        return False
    if _packed(dtype) and hidden % 2:
        return False
    return bool(interpret) or _words(dtype, hidden)[1] % 128 == 0


def _tile(m: int, cap: int) -> int:
    """The largest multiple of 16 that divides `m` and is at most `cap`;
    `m` itself where `m` is smaller (`fits` holds m to multiples of 16)."""
    if m <= cap:
        return m
    return next(t for t in range(cap - cap % 16, 0, -16) if m % t == 0)


def _pack(v, packed: bool):
    """Rows [r, H] of float32 holding values of the rows' type -> words
    [r, W]."""
    if not packed:
        return v
    half = v.shape[-1] // 2
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    return (bits[..., :half] >> 16) | (bits[..., half:]
                                      & jnp.uint32(0xFFFF0000))


def _unpack(w, packed: bool):
    """Words [r, W] -> rows [r, H] float32."""
    if not packed:
        return w
    f32 = jnp.float32
    lo = jax.lax.bitcast_convert_type(w << 16, f32)
    hi = jax.lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000), f32)
    return jnp.concatenate([lo, hi], axis=-1)


def _index_blocks(idx, tile: int):
    """A flat int32 index vector padded (with -1) to whole SMEM blocks, the
    block's length and the grid steps one block serves."""
    block = math.lcm(tile, _INDEX_BLOCK)
    pad = (-idx.shape[0]) % block
    if pad:
        idx = jnp.pad(idx, (0, pad), constant_values=-1)
    return idx, block, block // tile


def _start_row(src_hbm, buf, sem, row, slot):
    """Starts the DMA of row `row` of src_hbm [R, 1, W] into buf[slot]."""
    from jax.experimental.pallas import tpu as pltpu
    pltpu.make_async_copy(src_hbm.at[row], buf.at[slot], sem).start()


def _wait_rows(src_hbm, buf, sem, n):
    """Waits for `n` row DMAs started on `sem` (a wait counts one row's
    bytes, whichever row it names)."""
    from jax.experimental.pallas import tpu as pltpu

    def wait(_, carry):
        pltpu.make_async_copy(src_hbm.at[0], buf.at[0], sem).wait()
        return carry
    jax.lax.fori_loop(0, n, wait, 0)


def _word_rows(buf, start, n: int):
    """Rows start .. start + n of a [R, 1, W] VMEM buffer as [n, W]."""
    from jax.experimental import pallas as pl
    return buf[pl.ds(start, n), 0, :]


def _column(row):
    """[1, t] -> [t, 1] (a transpose of whole 128-lane tiles)."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _row(col):
    """[t, 1] -> [1, t]."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1, :]


def _gather_kernel(tm, per_block, packed, scaled, dotted, count_ref,
                   src_ref, *refs):
    from jax.experimental import pallas as pl

    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    words_hbm = refs.pop(0)
    ys_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    buf, sem = refs
    i = pl.program_id(0)
    # the places of this tile below the count, and this tile's part of the
    # index block
    live = jnp.clip(count_ref[0] - i * tm, 0, tm)
    first = (i % per_block) * tm

    def start(r, carry):
        _start_row(words_hbm, buf, sem, src_ref[first + r], r)
        return carry
    jax.lax.fori_loop(0, live, start, 0)
    _wait_rows(words_hbm, buf, sem, live)
    rows = _unpack(_word_rows(buf, 0, tm), packed)
    if dotted:
        dot_ref[...] = _row(jnp.sum(
            ys_ref[...].astype(jnp.float32) * rows, axis=1, keepdims=True))
    if scaled:
        rows = rows * _column(scale_ref[...])
    out_ref[...] = rows.astype(out_ref.dtype)


def gather(x, src, count, scale=None, dot_with=None,
           interpret: Optional[bool] = None):
    """Rows of x [N, H] at the buffer's places: out [M, H] in x's type with
    out[p] = x[src[p]] for p < count[0] (src [M] int32, every entry a row of
    x; places past the count unspecified). `scale` [M] float32 multiplies
    place p's row (after the product in float32, one rounding); `dot_with`
    [M, H] also returns dot [M] float32 = sum over columns of
    dot_with[p] * x[src[p]] (unspecified past the count)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, (N, H) = src.shape[0], x.shape
    packed = _packed(x.dtype)
    wtype, width = _words(x.dtype, H)
    tm = _tile(M, _TILE_ROWS)
    n_tiles = M // tm
    idx, block, per_block = _index_blocks(src.astype(jnp.int32), tm)
    steps = (count.reshape(1)[0] + tm - 1) // tm
    row_spec = pl.BlockSpec((None, 1, tm), lambda i, c: (i, 0, 0))
    in_specs = [pl.BlockSpec((block,), lambda i, c: (i // per_block,),
                             memory_space=pltpu.SMEM)]
    operands = [idx]
    if scale is not None:
        in_specs.append(row_spec)
        operands.append(scale.astype(jnp.float32).reshape(n_tiles, 1, tm))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(pack(x, N, "moe_rows_gather_pack", interpret))
    out_shape = [jax.ShapeDtypeStruct((M, H), x.dtype)]
    out_specs = [pl.BlockSpec((tm, H), lambda i, c: (i, 0))]
    if dot_with is not None:
        in_specs.append(pl.BlockSpec((tm, H), lambda i, c: (i, 0)))
        operands.append(dot_with)
        out_shape.append(jax.ShapeDtypeStruct((n_tiles, 1, tm), jnp.float32))
        out_specs.append(row_spec)
    rows = M // 4                          # a guess: the grid is data
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tm, per_block, packed,
                          scale is not None, dot_with is not None),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=in_specs,
            out_specs=out_specs,
            grid=(steps,),
            scratch_shapes=[pltpu.VMEM((tm, 1, width), wtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=rows * (width * 4 + H * x.dtype.itemsize
                                   * (2 if dot_with is not None else 1))),
        interpret=interpret,
        name="moe_rows_gather",
    )(count.reshape(1).astype(jnp.int32), *operands)
    if dot_with is None:
        return out[0]
    return out[0], out[1].reshape(M)


def _pack_kernel(packed, count_ref, rows_ref, words_ref):
    words_ref[:, 0, :] = _pack(rows_ref[...].astype(jnp.float32), packed)


def pack(rows, count, name: str, interpret: Optional[bool] = None):
    """rows [M, H]' first `count` rows (an int, or [1] int32 data) as words
    [M, 1, W]; rows past the count unwritten. `name` names the kernel for
    the trace: `moe_rows_gather_pack` for the token side of a gather,
    `moe_rows_combine_pack` for the buffer a combine reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, H = rows.shape
    wtype, width = _words(rows.dtype, H)
    tm = _tile(M, _TILE_ROWS)
    count = jnp.asarray(count, jnp.int32).reshape(1)
    steps = (count[0] + tm - 1) // tm
    return pl.pallas_call(
        functools.partial(_pack_kernel, _packed(rows.dtype)),
        out_shape=jax.ShapeDtypeStruct((M, 1, width), wtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, H), lambda i, c: (i, 0))],
            out_specs=pl.BlockSpec((tm, 1, width), lambda i, c: (i, 0, 0)),
            grid=(steps,),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=(M // 4) * (H * rows.dtype.itemsize + width * 4)),
        interpret=interpret,
        name=name,
    )(count, rows)


def _combine_kernel(tn, k, tok_per_block, slot_per_block, packed, weighted,
                    tile_held_ref, kept_ref, pos_ref, keep_ref, *refs):
    from jax.experimental import pallas as pl

    if weighted:
        w_ref, words_hbm, out_ref, buf, acc, groups, sem = refs
    else:
        w_ref = None
        words_hbm, out_ref, buf, acc, groups, sem = refs
    i = pl.program_id(0)
    tok0 = (i % tok_per_block) * tn
    slot0 = (i % slot_per_block) * tn * k
    for g in range(tn // 8):
        groups[g] = 0

    def start(n, carry):
        # the token's kept slots are its first `kept` (`_by_rank`); slot
        # (n, r) lands in buf[r * tn + n]
        kept = kept_ref[tok0 + n]

        def one(r, c):
            _start_row(words_hbm, buf, sem, pos_ref[slot0 + n * k + r],
                       r * tn + n)
            return c
        jax.lax.fori_loop(0, kept, one, 0)
        # the most slots one of 8 tokens keeps
        groups[n // 8] = jnp.maximum(groups[n // 8], kept)
        return carry
    jax.lax.fori_loop(0, tn, start, 0)
    acc[...] = jnp.zeros(acc.shape, jnp.float32)
    _wait_rows(words_hbm, buf, sem, tile_held_ref[i])

    def add(g, carry):
        # _gather_sum's sum, 8 tokens at a time, in the order of j; a slot
        # none of the 8 keeps would add zeros and is passed over
        most = groups[g]
        rows8 = pl.ds(pl.multiple_of(g * 8, 8), 8)
        for j in range(k):
            @pl.when(j < most)
            def _():
                rows = _unpack(_word_rows(buf, j * tn + g * 8, 8), packed)
                # a slot not kept was not fetched: selected away
                piece = jnp.where(keep_ref[rows8, j:j + 1] > 0, rows, 0.0)
                if weighted:
                    piece = piece * w_ref[rows8, j:j + 1]
                acc[rows8, :] = acc[rows8, :] + piece
        return carry
    jax.lax.fori_loop(0, tn // 8, add, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _by_rank(position, keep, weights):
    """A token's kept slots moved to the front, in the order of j: column r
    of the results is the token's r-th kept slot (its position, a kept
    flag, its weight). The kernel adds a token's columns in order, so the
    sum is still in the order of j, and 8 tokens' column r is visited
    only where one of them keeps r + 1 slots or more."""
    k = keep.shape[1]
    rank = jnp.cumsum(keep, axis=1, dtype=jnp.int32) - 1
    # [N, j, r]: slot j is the token's r-th kept one
    at = jnp.logical_and(keep[:, :, None],
                         rank[:, :, None] == jnp.arange(k)[None, None, :])
    position = jnp.sum(jnp.where(at, position[:, :, None], 0), axis=1)
    if weights is not None:
        weights = jnp.sum(jnp.where(at, weights[:, :, None], 0.0), axis=1)
    held = keep.sum(axis=1, keepdims=True, dtype=jnp.int32)
    return position, jnp.arange(k)[None, :] < held, weights


def combine(rows, count, position, keep, weights=None,
            interpret: Optional[bool] = None):
    """out [N, H] in the type of the buffer rows [M, H] (its first
    count[0] places read, no other): token n's kept slots' rows, at
    position [N, k], added up in float32 in the order of j (each times
    weights [N, k] where given)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, k = position.shape
    H, dtype, packed = rows.shape[1], rows.dtype, _packed(rows.dtype)
    words = pack(rows, count, "moe_rows_combine_pack", interpret)
    tn = _tile(N, _TILE_TOKENS)
    position, keep, weights = _by_rank(position, keep, weights)
    kept, tok_block, tok_per_block = _index_blocks(
        keep.sum(axis=1, dtype=jnp.int32), tn)
    pos, slot_block, slot_per_block = _index_blocks(
        position.astype(jnp.int32).reshape(-1), tn * k)
    tile_held = keep.reshape(N // tn, tn * k).sum(axis=1, dtype=jnp.int32)
    slot_spec = pl.BlockSpec((tn, k), lambda i, t: (i, 0))
    operands = [kept, pos, keep.astype(jnp.float32)]
    in_specs = [
        pl.BlockSpec((tok_block,), lambda i, t: (i // tok_per_block,),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((slot_block,), lambda i, t: (i // slot_per_block,),
                     memory_space=pltpu.SMEM),
        slot_spec]
    if weights is not None:
        operands.append(weights.astype(jnp.float32))
        in_specs.append(slot_spec)
    operands.append(words)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    return pl.pallas_call(
        functools.partial(_combine_kernel, tn, k, tok_per_block,
                          slot_per_block, packed, weights is not None),
        out_shape=jax.ShapeDtypeStruct((N, H), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tn, H), lambda i, t: (i, 0)),
            grid=(N // tn,),
            scratch_shapes=[
                pltpu.VMEM((k * tn, 1, words.shape[2]), words.dtype),
                pltpu.VMEM((tn, H), jnp.float32),
                pltpu.SMEM((tn // 8,), jnp.int32),
                pltpu.SemaphoreType.DMA(())],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * k * H // 4, transcendentals=0,
            bytes_accessed=(N * k // 4) * words.shape[2] * 4
            + N * H * jnp.dtype(dtype).itemsize),
        interpret=interpret,
        name="moe_rows_combine",
    )(tile_held, *operands)
