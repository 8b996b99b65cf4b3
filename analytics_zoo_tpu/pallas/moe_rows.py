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

Rows travel one DMA a row, and the fetches run as a pipeline over the
grid: step s starts tile s's row DMAs into one of two buffers, then waits
for tile s - 1's (started by step s - 1 into the other) and computes with
them, so the DMA engine moves one tile while the vector unit works on the
one before and the output block of the one before that is written back.
The grid has one step more than there are tiles: the first step only
starts, the last only waits and computes. A step waits for its rows 8 at
a time (one wait a descriptor of 8 rows' bytes), its list of rows padded
to a whole number of 8 by rows that are fetched and never used: a gather
fetches the next places' token rows, a combine the buffer's place 0 (held
wherever a tile holds a row) into 8 spare slots.

A DMA moves whole tiles of the second-minor dimension (8 rows of an
[R, H] array: Mosaic refuses a 1-row slice), so a row of [R, H] cannot be
fetched alone; a row of an [R, 1, W] array is a tile of its own. Rows are
therefore fetched from "words" [R, 1, W]: bfloat16 rows as uint32 words,
each holding column i in its low half and column i + H / 2 in its high
half (W = H / 2, the rows' own bytes), rows of another type widened to
float32 (W = H).

The combine's rows come from a list that XLA makes ahead (`plan`, once a
layer call, for the combine forward and the dispatch's backward alike),
by one sort of the N x k slots: a token tile's held rows, rank-major (a
token's kept slots moved to its front, `_by_rank`; then every token's
first kept slot, every token's second, ...), each with its place in the
buffer and its VMEM slot (token n's r-th kept slot lands in r * tn + n).
The kernel's issue loop runs over the tile's held rows alone. 8 tokens'
sum is carried in registers over their ranks, up to the most slots one of
them keeps (that most, too, made ahead), in the order of j, and written
once. The token tile is 128, halved while two buffers of k x tn rows and
the blocks would not fit the VMEM a kernel may use.

What a kernel does not visit it does not write: the gather's places past
`count` are unspecified (whatever the output's memory held), as are its
`dot` there. Consumers select them away (`grouped_matmul`'s kernels do, by
their own rows), never multiply them.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# places of the buffer a gather step fetches
_TILE_ROWS = 256
# tokens a combine step writes, at most (`_combine_tile`)
_TILE_TOKENS = 128
# VMEM a row kernel's two buffers and its blocks may take, of the 16 MiB a
# kernel may use unless told otherwise (`_gather_tile`, `_combine_tile`)
_VMEM_BUDGET = 14 * 2 ** 20
# rows a wait counts, and the multiple a step's list of rows is padded to
_CHUNK = 8
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


def _wait_chunks(src_hbm, buf, sem, n):
    """Waits for `n` x 8 row DMAs started on `sem`: one wait a descriptor
    of 8 rows (a wait counts its descriptor's bytes, whichever rows it
    names)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def wait(_, carry):
        pltpu.make_async_copy(src_hbm.at[pl.ds(0, _CHUNK)],
                              buf.at[pl.ds(0, _CHUNK)], sem).wait()
        return carry
    jax.lax.fori_loop(0, n, wait, 0)


def _start_chunk(c, one, unroll: bool):
    """one(r) for the rows r of chunk c: unrolled on the chip, where the
    scheduler overlaps the 8 DMAs' scalar work; a loop in the interpreter
    (the same DMAs in a fraction of the trace)."""
    def body(u, carry):
        one(c * _CHUNK + u)
        return carry
    jax.lax.fori_loop(0, _CHUNK, body, 0, unroll=unroll)


def _chunks(rows):
    """The waits of `rows` fetched rows, the list padded to 8s."""
    return (rows + _CHUNK - 1) // _CHUNK


def _column(row):
    """[1, t] -> [t, 1] (a transpose of whole 128-lane tiles)."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _row(col):
    """[t, 1] -> [1, t]."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1, :]


def _gather_kernel(tm, n_tiles, per_block, packed, scaled, dotted, unroll,
                   count_ref, src_ref, *refs):
    from jax.experimental import pallas as pl

    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    words_hbm = refs.pop(0)
    ys_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    buf, sem = refs
    s = pl.program_id(0)
    count = count_ref[0]

    def live(t):
        """The places of tile t below the count."""
        return jnp.clip(count - t * tm, 0, tm)

    # tile s's rows into buffer s % 2; its part of the index block
    first = (s % per_block) * tm
    to, on = buf.at[s % 2], sem.at[s % 2]

    def start(c, carry):
        def one(r):
            _start_row(words_hbm, to, on, src_ref[first + r], r)
        _start_chunk(c, one, unroll)
        return carry
    jax.lax.fori_loop(0, jnp.where(s < n_tiles, _chunks(live(s)), 0), start,
                      0)

    @pl.when(s > 0)
    def _():
        t = s - 1
        _wait_chunks(words_hbm, buf.at[t % 2], sem.at[t % 2],
                     _chunks(live(t)))
        rows = _unpack(buf[t % 2, :, 0, :], packed)
        if dotted:
            dot_ref[...] = _row(jnp.sum(
                ys_ref[...].astype(jnp.float32) * rows, axis=1,
                keepdims=True))
        if scaled:
            rows = rows * _column(scale_ref[...])
        out_ref[...] = rows.astype(out_ref.dtype)


def _gather_tile(M: int, hidden: int, dtype, dotted: bool) -> int:
    """The gather's buffer tile: at most 256 places, halved while two
    buffers of word rows, the output's (and with `dotted` the second
    operand's) two blocks and the rows in float32 would take more than
    `_VMEM_BUDGET`."""
    width, size = _words(dtype, hidden)[1], jnp.dtype(dtype).itemsize
    per_row = (2 * width * 4
               + (2 * hidden * size + hidden * 4) * (2 if dotted else 1))
    cap = _TILE_ROWS
    while True:
        tm = _tile(M, cap)
        if tm * per_row <= _VMEM_BUDGET or tm <= 16:
            return tm
        cap = tm // 2


def _lagged(i):
    """The tile a step computes with: the one before the step's own."""
    return jnp.maximum(i - 1, 0)


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
    tm = _gather_tile(M, H, x.dtype, dot_with is not None)
    n_tiles = M // tm
    idx, block, per_block = _index_blocks(src.astype(jnp.int32), tm)
    steps = (count.reshape(1)[0] + tm - 1) // tm
    row_spec = pl.BlockSpec((None, 1, tm), lambda i, c: (_lagged(i), 0, 0))
    in_specs = [pl.BlockSpec(
        (block,), lambda i, c: (jnp.minimum(i, n_tiles - 1) // per_block,),
        memory_space=pltpu.SMEM)]
    operands = [idx]
    if scale is not None:
        in_specs.append(row_spec)
        operands.append(scale.astype(jnp.float32).reshape(n_tiles, 1, tm))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(pack(x, N, "moe_rows_gather_pack", interpret))
    tile_spec = pl.BlockSpec((tm, H), lambda i, c: (_lagged(i), 0))
    out_shape = [jax.ShapeDtypeStruct((M, H), x.dtype)]
    out_specs = [tile_spec]
    if dot_with is not None:
        in_specs.append(tile_spec)
        operands.append(dot_with)
        out_shape.append(jax.ShapeDtypeStruct((n_tiles, 1, tm), jnp.float32))
        out_specs.append(row_spec)
    rows = M // 4                          # a guess: the grid is data
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tm, n_tiles, per_block, packed,
                          scale is not None, dot_with is not None,
                          not interpret),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=in_specs,
            out_specs=out_specs,
            grid=(steps + 1,),
            scratch_shapes=[pltpu.VMEM((2, tm, 1, width), wtype),
                            pltpu.SemaphoreType.DMA((2,))],
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


def _combine_kernel(tn, k, n_tiles, per_block, most_per_block, packed,
                    weighted, unroll, held_ref, place_ref, slot_ref,
                    most_ref, kept_ref, *refs):
    from jax.experimental import pallas as pl

    if weighted:
        w_ref, words_hbm, out_ref, buf, sem = refs
    else:
        w_ref = None
        words_hbm, out_ref, buf, sem = refs
    s = pl.program_id(0)
    # tile s's held rows into buffer s % 2, 8 at a time: its list's entries
    # (place in the buffer, VMEM slot) in its index block
    first = (s % per_block) * (k * tn)
    to, on = buf.at[s % 2], sem.at[s % 2]

    def start(c, carry):
        def one(r):
            _start_row(words_hbm, to, on, place_ref[first + r],
                       slot_ref[first + r])
        _start_chunk(c, one, unroll)
        return carry
    jax.lax.fori_loop(0, jnp.where(
        s < n_tiles, _chunks(held_ref[jnp.minimum(s, n_tiles - 1)]), 0),
        start, 0)

    @pl.when(s > 0)
    def _():
        t = s - 1
        fetched = buf.at[t % 2]
        _wait_chunks(words_hbm, fetched, sem.at[t % 2],
                     _chunks(held_ref[t]))
        group0 = (t % most_per_block) * (tn // 8)

        def group(g, carry):
            # _gather_sum's sum of 8 tokens, in the order of j, carried in
            # registers over the ranks one of them keeps
            rows8 = pl.ds(pl.multiple_of(g * 8, 8), 8)
            kept = kept_ref[rows8, :]

            def rank(j, acc):
                rows = _unpack(fetched[pl.ds(pl.multiple_of(
                    j * tn + g * 8, 8), 8), 0, :], packed)
                # a slot not kept was not fetched: selected away
                piece = jnp.where(kept > j, rows, 0.0)
                if weighted:
                    piece = piece * w_ref[j, rows8, :]
                return acc + piece
            out_ref[rows8, :] = jax.lax.fori_loop(
                0, most_ref[group0 + g], rank,
                jnp.zeros((8, out_ref.shape[1]), jnp.float32)
            ).astype(out_ref.dtype)
            return carry
        jax.lax.fori_loop(0, tn // 8, group, 0)


def _by_rank(keep, *values):
    """A token's kept slots moved to the front, in the order of j: the mask
    of the kept-first columns and each of `values` [N, k] moved alike
    (column r: the token's r-th kept slot's). The kernel adds a token's
    columns in order, so the sum is still in the order of j."""
    k = keep.shape[1]
    rank = jnp.cumsum(keep, axis=1, dtype=jnp.int32) - 1
    # [N, j, r]: slot j is the token's r-th kept one
    at = jnp.logical_and(keep[:, :, None],
                         rank[:, :, None] == jnp.arange(k)[None, None, :])
    held = keep.sum(axis=1, keepdims=True, dtype=jnp.int32)
    return (jnp.arange(k)[None, :] < held, *(
        jnp.sum(jnp.where(at, v[:, :, None], jnp.zeros((), v.dtype)), axis=1)
        for v in values))


def _combine_tile(n_tokens: int, k: int, hidden: int, dtype) -> int:
    """The combine's token tile: at most 128, halved while two buffers of
    k x tn word rows (and the 8 spare ones), the output's two blocks and
    the weights' two (a rank's column lane-padded) would take more than
    `_VMEM_BUDGET`."""
    width = _words(dtype, hidden)[1]
    cap = _TILE_TOKENS
    while True:
        tn = _tile(n_tokens, cap)
        need = 2 * ((k * tn + _CHUNK) * width * 4
                    + tn * hidden * jnp.dtype(dtype).itemsize
                    + k * tn * 128 * 4)
        if need <= _VMEM_BUDGET or tn <= 16:
            return tn
        cap = tn // 2


class Plan(NamedTuple):
    """A combine's rows, listed ahead (`plan`): the slots held here,
    [N, k]; each token tile's held rows, [tiles]; per tile, k x tn entries
    of the place in the buffer and the VMEM slot of its held rows,
    rank-major, then padding; and the most slots one of 8 tokens keeps,
    [N / 8]."""
    held: jax.Array
    tile_held: jax.Array
    place: jax.Array
    slot: jax.Array
    most: jax.Array


def plan(position, held, hidden: int, dtype) -> Plan:
    """The list of the held rows that `combine` fetches for the slots at
    `position` [N, k] where `held` [N, k], rows of `hidden` values of
    `dtype`: one sort of the N x k slots, tile by tile, that moves the held
    ones to their tile's front in rank-major order. A tile's entries past
    its held rows name place 0 and the spare slots k x tn + (m mod 8)."""
    N, k = held.shape
    tn = _combine_tile(N, k, hidden, dtype)
    n_tiles = N // tn
    keep, position = _by_rank(held, position.astype(jnp.int32))

    def by_tile(a):
        """[N, k] -> [tiles, k x tn]: tile, rank, token."""
        return a.reshape(n_tiles, tn, k).transpose(0, 2, 1).reshape(
            n_tiles, k * tn)
    live, entry = by_tile(keep), jnp.arange(k * tn, dtype=jnp.int32)
    key, place = jax.lax.sort(
        (jnp.where(live, entry, k * tn + entry),
         jnp.where(live, by_tile(position), 0)), dimension=1, num_keys=1)
    slot = jnp.where(key < k * tn, key, k * tn + entry % _CHUNK)
    kept = keep.sum(axis=1, dtype=jnp.int32)
    return Plan(held, kept.reshape(n_tiles, tn).sum(axis=1, dtype=jnp.int32),
                place.reshape(-1), slot.reshape(-1),
                kept.reshape(N // 8, 8).max(axis=1))


def combine(rows, count, route: Plan, weights=None,
            interpret: Optional[bool] = None):
    """out [N, H] in the type of the buffer rows [M, H] (its first
    count[0] places read, no other): token n's kept slots' rows, at the
    places `route` (`plan`) lists, added up in float32 in the order of j
    (each times weights [N, k] where given)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, k = route.held.shape
    H, dtype, packed = rows.shape[1], rows.dtype, _packed(rows.dtype)
    words = pack(rows, count, "moe_rows_combine_pack", interpret)
    n_tiles = route.tile_held.shape[0]
    tn = N // n_tiles
    place, block, per_block = _index_blocks(route.place, k * tn)
    slot = _index_blocks(route.slot, k * tn)[0]
    most, most_block, most_per_block = _index_blocks(route.most, tn // 8)
    list_spec = pl.BlockSpec(
        (block,), lambda i, t: (jnp.minimum(i, n_tiles - 1) // per_block,),
        memory_space=pltpu.SMEM)
    operands = [place, slot, most,
                route.held.sum(axis=1, keepdims=True, dtype=jnp.int32)]
    in_specs = [
        list_spec, list_spec,
        pl.BlockSpec((most_block,),
                     lambda i, t: (_lagged(i) // most_per_block,),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((tn, 1), lambda i, t: (_lagged(i), 0))]
    if weights is not None:
        # rank r's weights [k, N, 1]: a rank's 8 tokens are a column
        _, weights = _by_rank(route.held, weights.astype(jnp.float32))
        operands.append(weights.T[:, :, None])
        in_specs.append(pl.BlockSpec((k, tn, 1),
                                     lambda i, t: (0, _lagged(i), 0)))
    operands.append(words)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    return pl.pallas_call(
        functools.partial(_combine_kernel, tn, k, n_tiles, per_block,
                          most_per_block, packed, weights is not None,
                          not interpret),
        out_shape=jax.ShapeDtypeStruct((N, H), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tn, H), lambda i, t: (_lagged(i), 0)),
            grid=(n_tiles + 1,),
            scratch_shapes=[
                pltpu.VMEM((2, k * tn + _CHUNK, 1, words.shape[2]),
                           words.dtype),
                pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * k * H // 4, transcendentals=0,
            bytes_accessed=(N * k // 4) * words.shape[2] * 4
            + N * H * jnp.dtype(dtype).itemsize),
        interpret=interpret,
        name="moe_rows_combine",
    )(route.tile_held, *operands)
