"""Decode attention — single-token queries against the pooled KV cache.

The generative decode step (serving/decode.py) asks one question per
leased slot: "given this slot's ONE new query vector, attend over the
first `lengths[s]` cached positions of that slot's KV rows". Unlike
flash attention (O(T²) work per call) decode attention is memory-bound:
the arithmetic is two [1,D]×[D,L] products per head, but every byte of
the live KV prefix streams from HBM each step. The kernel therefore
reads the KV pool IN PLACE — `pallas_call` takes the full
`[slots, H, max_kv_len, D]` pool buffers and the grid only visits the
first `kv_bucket // block_k` key blocks, so no slice copy of the pool
is ever materialized and the bytes actually moved scale with the
serving bucket, not the pool capacity.

Grid: (slots, heads, k-blocks) with the k axis innermost and
"arbitrary", online-softmax state (acc, m, l) in VMEM scratch across k
steps — the same canonical shape as `flash_attention`, degenerated to a
1-row query block. Positions at or past `lengths[s]` are masked with a
large negative additive constant (not -inf: a fully-masked first block
would turn the running max into -inf and poison the rescale with
inf-inf). `lengths` must be >= 1 per slot — the engine guarantees it
(prefill writes at least one position before any step; dead slots are
passed length 1 and their output rows are discarded host-side).

Off-TPU the exact jnp gather path (`_reference_decode_attention`) runs
instead — same math, no tiling — decided statically from the backend
like `flash_attention._flash_supported`.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


def _attend_window(q, k, v, lengths, kv_bucket):
    """The shared exact-attention core: q [S, H, D] against a
    MATERIALIZED window k/v [S, H, kv_bucket, D], masked past
    `lengths`. Both the contiguous and the paged reference paths call
    this with identical shapes, so a paged window gathered from blocks
    produces bitwise-identical outputs to the contiguous slice it
    mirrors — the property the paged-parity tests pin."""
    D = q.shape[-1]
    scores = jnp.einsum("shd,shld->shl", q, k) / math.sqrt(D)
    scores = scores.astype(jnp.float32)
    pos = jnp.arange(kv_bucket, dtype=jnp.int32)
    mask = pos[None, None, :] < lengths[:, None, None]
    scores = jnp.where(mask, scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("shl,shld->shd", weights, v)


def _reference_decode_attention(q, k_pool, v_pool, lengths, kv_bucket):
    """Exact decode attention over the first `kv_bucket` pool positions.
    q: [S, H, D]; k_pool/v_pool: [S, H, L, D]; lengths: int32 [S]."""
    k = jax.lax.slice_in_dim(k_pool, 0, kv_bucket, axis=2)
    v = jax.lax.slice_in_dim(v_pool, 0, kv_bucket, axis=2)
    return _attend_window(q, k, v, lengths, kv_bucket)


def gather_kv_window(pool, tables, kv_bucket: int):
    """Materialize the logical [S, H, kv_bucket, D] window of a BLOCK
    pool [num_blocks, H, block_len, D] through per-sequence block
    tables [S, >= kv_bucket // block_len]. Pure gather — the values are
    exactly the bytes the blocks hold, in logical position order."""
    num_blocks, H, block_len, D = pool.shape
    n_kb = kv_bucket // block_len
    tb = tables[:, :n_kb]                       # [S, n_kb]
    g = pool[tb]                                # [S, n_kb, H, bl, D]
    g = jnp.moveaxis(g, 2, 1)                   # [S, H, n_kb, bl, D]
    return g.reshape(g.shape[0], H, kv_bucket, D)


def _reference_paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                      kv_bucket):
    """Exact paged decode attention: gather the block window, then the
    SAME math as the contiguous reference."""
    k = gather_kv_window(k_pool, tables, kv_bucket)
    v = gather_kv_window(v_pool, tables, kv_bucket)
    return _attend_window(q, k, v, lengths, kv_bucket)


def _decode_supported() -> bool:
    """Static backend gate (no exception-driven fallback): the Mosaic
    kernel runs on TPU; CPU tests take the exact reference path."""
    return jax.default_backend() == "tpu"


def _decode_cost(q, kv_bucket, n_heads, itemsize):
    """Analytic roofline model (check_pallas_cost lint: HLO cost
    analysis sees ~0 inside a Mosaic call). Decode is MEMORY-bound:
    bytes are dominated by streaming the live K and V prefixes —
    2 · S·H·kv_bucket·D — while flops are just the two bucket×D
    products per (slot, head); the roofline accountant must see that
    ratio or it would misread decode steps as idle compute."""
    from jax.experimental import pallas as pl

    S, H, D = q.shape[0], n_heads, q.shape[-1]
    kv_bytes = 2 * S * H * kv_bucket * D * itemsize
    qo_bytes = 2 * S * H * D * itemsize + 4 * S
    return pl.CostEstimate(
        flops=4 * S * H * kv_bucket * D,            # QKᵀ + PV
        bytes_accessed=kv_bytes + qo_bytes,
        transcendentals=S * H * kv_bucket)


def _row_spec(D, index_map):
    """One slot's one head's [1, D] query / output row, carried as a
    `[S, H, 1, D]` array: Mosaic wants a block's last two dims to be
    multiples of (8, 128) or the array's whole extent, and a (1, D) block
    of `[S, H, D]` has 1 of H rows in its second-to-last dim."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((1, 1, 1, D), index_map)


def _decode_kernel(scale, n_kb, q_ref, k_ref, v_ref, len_ref, o_ref,
                   acc_sc, m_sc, l_sc):
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    ki = pl.program_id(2)
    block_k = k_ref.shape[2]

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, -1e30)
        l_sc[...] = jnp.zeros_like(l_sc)

    qb = q_ref[0, 0]                                       # [1, D]
    kb = k_ref[0, 0]                                       # [bk, D]
    vb = v_ref[0, 0]
    scores = jnp.dot(qb, kb.T,
                     preferred_element_type=jnp.float32) * scale  # [1, bk]
    pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    scores = jnp.where(pos < len_ref[s, 0], scores, -1e30)
    m_prev, l_prev = m_sc[...], l_sc[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    acc_sc[...] = acc_sc[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), vb, preferred_element_type=jnp.float32)
    m_sc[...] = m_new
    l_sc[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)

    @pl.when(ki == n_kb - 1)
    def _flush():
        o_ref[0, 0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


def decode_attention(q, k_pool, v_pool, lengths, kv_bucket: int,
                     block_k: int = 128,
                     interpret: Optional[bool] = None):
    """One decode step of attention for every slot.

    q: [S, H, D] — the current token's query per slot.
    k_pool/v_pool: [S, H, L, D] — the FULL KV pool; only positions
    [0, kv_bucket) are read (kv_bucket is the static serving bucket,
    `<= L`, chosen per step by the DecodeScheduler).
    lengths: int32 [S] — live KV length per slot, all >= 1; positions
    >= lengths[s] are masked. Returns [S, H, D].
    """
    S, H, D = q.shape
    L = k_pool.shape[2]
    if not 1 <= kv_bucket <= L:
        raise ValueError(f"kv_bucket {kv_bucket} outside [1, {L}]")
    lengths = lengths.astype(jnp.int32)
    if not (_decode_supported() or interpret):
        return _reference_decode_attention(q, k_pool, v_pool, lengths,
                                           kv_bucket)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_k = min(block_k, kv_bucket)
    if kv_bucket % block_k:
        # bucket ladders are powers of two >= 1; the kernel does not pad
        # the pool, and does not quietly hand a TPU step to the reference
        raise ValueError(
            f"kv_bucket {kv_bucket} is not a multiple of block_k "
            f"{block_k}")
    n_kb = kv_bucket // block_k
    scale = 1.0 / math.sqrt(D)
    item = jnp.dtype(q.dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale, n_kb),
        grid=(S, H, n_kb),
        in_specs=[
            _row_spec(D, lambda s, h, j: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda s, h, j: (s, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda s, h, j: (s, h, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=_row_spec(D, lambda s, h, j: (s, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, H, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=_decode_cost(q, kv_bucket, H, item),
        interpret=bool(interpret) if interpret is not None else False,
    )(q[:, :, None, :], k_pool, v_pool, lengths.reshape(S, 1))
    return out[:, :, 0, :]


# ---------------------------------------------------------------------------
# paged variant (ISSUE 19): block-table indirection into a block pool
# ---------------------------------------------------------------------------
def _paged_cost(q, kv_bucket, n_heads, block_len, itemsize):
    """Same memory-bound roofline as `_decode_cost` plus the table
    stream: the kernel still moves 2 · S·H·kv_bucket·D KV bytes per
    step — block indirection changes WHICH bytes, not how many — and
    reads S · kv_bucket/block_len int32 table entries from SMEM."""
    from jax.experimental import pallas as pl

    S, H, D = q.shape[0], n_heads, q.shape[-1]
    kv_bytes = 2 * S * H * kv_bucket * D * itemsize
    qo_bytes = 2 * S * H * D * itemsize + 4 * S
    table_bytes = 4 * S * (kv_bucket // block_len)
    return pl.CostEstimate(
        flops=4 * S * H * kv_bucket * D,            # QKᵀ + PV
        bytes_accessed=kv_bytes + qo_bytes + table_bytes,
        transcendentals=S * H * kv_bucket)


def _paged_kernel(scale, n_kb, block_len, tbl_ref, q_ref, k_ref, v_ref,
                  len_ref, o_ref, acc_sc, m_sc, l_sc):
    """Identical online-softmax walk to `_decode_kernel`; the ONLY
    difference is upstream — the BlockSpec index map routed k/v block
    `j` through the prefetched table, so `k_ref`/`v_ref` here hold the
    slot's j-th LOGICAL block wherever it physically lives. Masking is
    by logical position, exactly as before."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, -1e30)
        l_sc[...] = jnp.zeros_like(l_sc)

    qb = q_ref[0, 0]                                       # [1, D]
    kb = k_ref[0, 0]                                       # [bl, D]
    vb = v_ref[0, 0]
    scores = jnp.dot(qb, kb.T,
                     preferred_element_type=jnp.float32) * scale  # [1, bl]
    pos = ki * block_len + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_len), 1)
    scores = jnp.where(pos < len_ref[s, 0], scores, -1e30)
    m_prev, l_prev = m_sc[...], l_sc[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    acc_sc[...] = acc_sc[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), vb, preferred_element_type=jnp.float32)
    m_sc[...] = m_new
    l_sc[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)

    @pl.when(ki == n_kb - 1)
    def _flush():
        o_ref[0, 0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                           kv_bucket: int,
                           interpret: Optional[bool] = None):
    """One decode step of attention for every slot, KV read through
    per-sequence block tables.

    q: [S, H, D] — the current token's query per slot.
    k_pool/v_pool: [num_blocks, H, block_len, D] — the FULL block
    pool; slot ``s``'s logical positions ``[j*block_len, (j+1)*
    block_len)`` live in physical block ``tables[s, j]``.
    tables: int32 [S, T] with ``T >= kv_bucket // block_len``; only the
    first ``kv_bucket // block_len`` entries are read (entries past a
    slot's live length may point anywhere valid — the scratch block by
    convention — because masking is by `lengths`).
    lengths: int32 [S] — live KV length per slot, all >= 1.
    Returns [S, H, D].

    The grid is (slots, heads, k-blocks) exactly like the contiguous
    kernel; the table rides in as a scalar-prefetch operand
    (`PrefetchScalarGridSpec`) so the k/v BlockSpec index maps can
    dereference it — the indirection costs an SMEM read per grid step,
    not a gather copy of the pool.
    """
    S, H, D = q.shape
    num_blocks, _, block_len, _ = k_pool.shape
    if kv_bucket < 1 or kv_bucket % block_len:
        raise ValueError(
            f"kv_bucket {kv_bucket} must be a positive multiple of "
            f"block_len {block_len}")
    n_kb = kv_bucket // block_len
    if tables.shape[-1] < n_kb:
        raise ValueError(
            f"block table has {tables.shape[-1]} entries, kv_bucket "
            f"{kv_bucket} needs {n_kb}")
    lengths = lengths.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    if not (_decode_supported() or interpret):
        return _reference_paged_decode_attention(
            q, k_pool, v_pool, tables, lengths, kv_bucket)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = 1.0 / math.sqrt(D)
    item = jnp.dtype(q.dtype).itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,            # tables[:, :n_kb]
        grid=(S, H, n_kb),
        in_specs=[
            _row_spec(D, lambda s, h, j, tbl: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, block_len, D),
                         lambda s, h, j, tbl: (tbl[s, j], h, 0, 0)),
            pl.BlockSpec((1, 1, block_len, D),
                         lambda s, h, j, tbl: (tbl[s, j], h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=_row_spec(D, lambda s, h, j, tbl: (s, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale, n_kb, block_len),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=_paged_cost(q, kv_bucket, H, block_len, item),
        interpret=bool(interpret) if interpret is not None else False,
    )(tables[:, :n_kb], q[:, :, None, :], k_pool, v_pool,
      lengths.reshape(S, 1))
    return out[:, :, 0, :]
