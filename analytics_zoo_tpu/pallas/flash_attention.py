"""Flash attention — Pallas TPU kernels with reference fallback.

The reference has no fused attention at all (its longest-sequence support is
full O(L²) attention on one device, survey §5 long-context note); this module
is part of the beyond-reference long-context capability.

Kernel structure (the canonical TPU flash shape, pallas_guide.md): the grid
is (batch·heads, q-blocks, k-blocks) with the k axis innermost and marked
"arbitrary", so Pallas pipelines K/V block DMAs while online-softmax state
(acc, m, l) lives in VMEM scratch across k steps — VMEM stays O(block²)
at any sequence length. Matmuls run in the input dtype (bf16 on the MXU)
with f32 accumulation; softmax statistics stay f32. The backward pass is a
custom VJP with two more kernels (dQ over q-blocks, dK/dV over k-blocks)
recomputing weights from the saved logsumexp instead of materializing [T,T]
— so training (BERT, ring attention shards) runs flash end-to-end.

Attention dropout runs INSIDE the kernels: `pltpu.prng_seed(seed, tile)`
reseeds per (batch·head, q-block, k-block) tile, so the backward kernels
regenerate bit-identical masks without storing them. The softmax
denominator uses undropped weights (dropout applies to the normalized
weights — `drop(p)/l == drop(p/l)`), matching the semantics of dropping
softmax output.

`flash_attention` falls back to a jnp implementation when Pallas is
unavailable for the current backend (e.g. CPU tests) — same math, no
tiling; dropout there uses jax.random (different bits, same distribution).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.pallas.dropout import _byte_threshold


def _reference_attention(q, k, v, mask=None, dropout_rate: float = 0.0,
                         dropout_key=None):
    """Exact O(L²) attention — the shared non-flash numerics (also what
    `keras.transformer.dot_product_attention` delegates to)."""
    depth = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(depth)
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = scores + mask
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and dropout_key is not None:
        keep = 1.0 - dropout_rate
        m = jax.random.bernoulli(dropout_key, keep, weights.shape)
        weights = jnp.where(m, weights / keep, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def _flash_supported(mask) -> bool:
    """The Pallas kernel runs on TPU and supports padding masks
    ([B,1,1,T]); full [B,1,T,T] masks or other backends use the exact
    reference path (decided statically — no exception-driven fallback)."""
    if jax.default_backend() != "tpu":
        return False
    if mask is not None and mask.ndim == 4 and mask.shape[2] != 1:
        return False
    return True


def _auto_block(T: int) -> int:
    """Largest multiple of 128 that divides T, capped at 1024 — big tiles
    amortize DMA/softmax-state overhead (see the v5e table in
    docs/ROOFLINE.md) without padding sequence lengths like 1152 that a
    1024 block would round up to 2048 (~3× wasted attention work).
    Lengths with no 128-multiple divisor fall back to 128 + the pad
    path."""
    for b in (1024, 512, 256, 128):
        if T % b == 0:
            return b
    return 128


def flash_attention(q, k, v, mask: Optional[jax.Array] = None,
                    dropout_rate: float = 0.0,
                    dropout_seed: Optional[jax.Array] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    bwd_block_q: Optional[int] = None,
                    bwd_block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q,k,v: [B, H, T, Dh]. mask: additive [B,1,1,T] (padding) or
    [B,1,T,T] (full; reference path only). `dropout_rate` > 0 needs
    `dropout_seed` (scalar int32). Differentiable (custom VJP); the mask
    receives a zero cotangent (padding masks are data, not parameters).
    Returns [B, H, T, Dh].

    Block sizes default to the largest 128-multiple divisor of T up to
    1024: per-tile work must amortize the DMA + softmax-state overhead —
    measured on v5e at T=2048, 1024×1024 blocks run the fwd+bwd 4.4×
    faster than 128×128 and beat the XLA reference attention (~12 vs
    ~19 ms fwd). VMEM stays O(block_q·block_k) f32 (~4 MB at 1024²) plus
    the K/V double buffers."""
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("flash_attention: dropout_rate > 0 needs a "
                         "dropout_seed (deterministic in-kernel masks)")
    use_dropout = dropout_rate > 0.0
    if mask is not None and mask.ndim == 4 and mask.shape[2] != 1:
        # full [B,1,T,T] masks always take the exact reference path — the
        # kernels assume a broadcastable padding mask
        key = jax.random.PRNGKey(dropout_seed) if use_dropout else None
        return _reference_attention(q, k, v, mask,
                                    dropout_rate if use_dropout else 0.0,
                                    key)
    if not (_flash_supported(mask) or interpret):
        key = None
        if use_dropout:
            key = jax.random.PRNGKey(jnp.asarray(dropout_seed, jnp.int32)
                                     if not hasattr(dropout_seed, "dtype")
                                     else dropout_seed)
        return _reference_attention(q, k, v, mask,
                                    dropout_rate if use_dropout else 0.0,
                                    key)
    B, H, T, D = q.shape
    if block_q is None:
        block_q = _auto_block(T)
    if block_k is None:
        block_k = _auto_block(T)
    # Backward kernels hold more VMEM live per tile (pnorm, dw, plus the
    # dq/dk/dv accumulators) than the forward, so their sweet spot can be
    # smaller; default to the forward blocks.
    env_bwd = os.environ.get("ZOO_FLASH_BWD_BLOCK")
    if env_bwd and bwd_block_q is None and bwd_block_k is None:
        # tuning HINT, not a contract: applied only where it is legal for
        # THIS call — a process can hold models with several seq lengths
        try:
            env_val = int(env_bwd)
        except ValueError:
            raise ValueError(f"ZOO_FLASH_BWD_BLOCK={env_bwd!r}: not an int")
        applicable = (env_val > 0 and env_val % 128 == 0
                      and T % env_val == 0
                      # dropout masks regenerate per (qi, ki) tile — the
                      # backward must match the forward tiling exactly
                      and (not use_dropout
                           or (env_val == block_q and env_val == block_k)))
        if applicable:
            bwd_block_q = bwd_block_k = env_val
    if bwd_block_q is None:
        bwd_block_q = block_q
    if bwd_block_k is None:
        bwd_block_k = block_k
    if use_dropout and (bwd_block_q != block_q or bwd_block_k != block_k):
        # explicit caller-passed mismatch is a programming error
        raise ValueError("flash_attention: in-kernel dropout requires "
                         "bwd blocks == fwd blocks (mask regeneration is "
                         "tile-indexed)")
    if mask is None:
        mask = jnp.zeros((B, 1, 1, T), jnp.float32)
    block = math.lcm(block_q, block_k, bwd_block_q, bwd_block_k)
    if T % block:
        pad = (-T) % block
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        maskp = jnp.pad(mask, ((0, 0), (0, 0), (0, 0), (0, pad)),
                        constant_values=-1e9)
        out = flash_attention(qp, kp, vp, maskp, dropout_rate, dropout_seed,
                              block_q, block_k, bwd_block_q, bwd_block_k,
                              interpret)
        return out[:, :, :T]
    seed = jnp.asarray(dropout_seed if use_dropout else 0,
                       jnp.int32).reshape(1, 1)
    rate = float(dropout_rate) if use_dropout else 0.0
    return _flash(q, k, v, mask, seed, rate, block_q, block_k,
                  bwd_block_q, bwd_block_k,
                  bool(interpret) if interpret is not None else False)


# ---------------------------------------------------------------------------
# custom-VJP core (assumes T % lcm(block_q, block_k) == 0, mask [B,1,1,T])
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, mask, seed, rate, block_q, block_k, bwd_block_q,
           bwd_block_k, interpret):
    out, _ = _flash_fwd(q, k, v, mask, seed, rate, block_q, block_k,
                        interpret)
    return out


def _keep_scale(s_ref, rate, n_qb, n_kb, qi, ki, shape):
    """Deterministic per-tile dropout scale: 1/keep where kept, 0 where
    dropped. Identical bits in forward and both backward kernels (the tile
    index folds (bh, qi, ki); prng_seed on this mosaic takes 2 scalars).

    The PRNG is the expensive part (~20 cycles/word on v5e — measured
    45 ms/step across the three kernels at seq 2048 when drawing one
    uint32 per element), so draw one word per FOUR elements and use each
    byte as an independent keep-draw: keep iff byte < t, t =
    round(keep*256), scaled by the exact keep probability t/256 (unbiased;
    rate quantized to 1/256 like `pallas/dropout._u8_dropout`). Which
    byte lands on which column is an arbitrary fixed bijection — the mask
    stays iid Bernoulli and regenerates bit-identically in the backward
    kernels."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh = pl.program_id(0)
    tile = (bh * n_qb + qi) * n_kb + ki
    pltpu.prng_seed(s_ref[0, 0], tile)
    words = pltpu.prng_random_bits((shape[0], shape[1] // 4))
    words = words.astype(jnp.uint32)
    t = _byte_threshold(rate)
    bytes_ = jnp.concatenate(
        [(words >> (8 * j)) & jnp.uint32(0xFF) for j in range(4)], axis=1)
    return jnp.where(bytes_ < jnp.uint32(t), 256.0 / t, 0.0)


def _fwd_kernel(rate, scale, n_qb, n_kb, q_ref, k_ref, v_ref, m_ref, s_ref,
                o_ref, lse_ref, acc_sc, m_sc, l_sc):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)

    qb = q_ref[0]                                          # [bq, D]
    kb = k_ref[0]
    vb = v_ref[0]
    mb = m_ref[0]                                          # [1, bk]
    scores = jnp.dot(qb, kb.T,
                     preferred_element_type=jnp.float32) * scale + mb
    m_prev, l_prev = m_sc[...], l_sc[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    if rate > 0.0:
        p_drop = p * _keep_scale(s_ref, rate, n_qb, n_kb, qi, ki,
                                 (block_q, block_k))
    else:
        p_drop = p
    acc_sc[...] = acc_sc[...] * alpha + jnp.dot(
        p_drop.astype(v_ref.dtype), vb, preferred_element_type=jnp.float32)
    m_sc[...] = m_new
    l_sc[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)

    @pl.when(ki == n_kb - 1)
    def _flush():
        o_ref[0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)
        lse_ref[0] = m_sc[...] + jnp.log(l_sc[...])        # [bq, 1]


def _attn_cost(n_matmuls, q, extra_f32_out_elems=0):
    """Analytic roofline model for one attention kernel over [B,H,T,D]
    (check_pallas_cost lint: HLO cost analysis sees ~0 inside a Mosaic
    call). `n_matmuls` counts the T×T×D matmul-shaped products the
    kernel runs per head (2 flops each); bytes are the O(T·D) streams —
    q/k/v-sized reads and writes — NOT the O(T²) scores, which is the
    IO-aware point of flash attention; exp() is one per score."""
    from jax.experimental import pallas as pl

    B, H, T, D = q.shape
    bh = B * H
    item = jnp.dtype(q.dtype).itemsize
    streams = 4 + n_matmuls  # rough: q,k,v(+dout...) in, grads/out out
    return pl.CostEstimate(
        flops=2 * n_matmuls * bh * T * T * D,
        bytes_accessed=bh * T * D * item * streams + extra_f32_out_elems * 4,
        transcendentals=bh * T * T)


def _flash_fwd(q, k, v, mask, seed, rate, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    scale = 1.0 / math.sqrt(D)
    n_qb, n_kb = T // block_q, T // block_k
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    mf = jnp.repeat(mask[:, 0, :, :], H, axis=0)           # [B*H, 1, T]

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, rate, scale, n_qb, n_kb),
        grid=(B * H, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=_attn_cost(2, q,                    # QKᵀ + PV
                                 extra_f32_out_elems=B * H * T),
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf, mf, seed)
    out = out.reshape(B, H, T, D)
    return out, (q, k, v, mask, seed, out, lse)


def _dq_kernel(rate, scale, n_qb, n_kb, q_ref, k_ref, v_ref, m_ref, s_ref,
               do_ref, lse_ref, delta_ref, dq_ref, dq_sc):
    """Standalone dq (accumulate over ki in scratch): the fallback when
    n_kb is large enough that the fused kernel's per-ki dq partials
    (n_kb × T × D f32 in HBM) would cost real memory — see _flash_bwd."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    qb = q_ref[0]
    kb = k_ref[0]
    vb = v_ref[0]
    mb = m_ref[0]
    dob = do_ref[0]
    lse = lse_ref[0]                                       # [bq, 1]
    delta = delta_ref[0]                                   # [bq, 1]
    pnorm = jnp.exp(jnp.dot(qb, kb.T,
                            preferred_element_type=jnp.float32)
                    * scale + mb - lse)                    # softmax weights
    dw = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
    if rate > 0.0:
        dw = dw * _keep_scale(s_ref, rate, n_qb, n_kb, qi, ki,
                              (block_q, block_k))
    ds = pnorm * (dw - delta)                              # [bq, bk]
    dq_sc[...] += jnp.dot(ds.astype(k_ref.dtype), kb,
                          preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _flush():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(rate, scale, n_qb, n_kb, q_ref, k_ref, v_ref, m_ref, s_ref,
                do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_sc, dv_sc):
    """dk/dv-only companion of _dq_kernel for the large-n_kb fallback."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    qb = q_ref[0]
    kb = k_ref[0]
    vb = v_ref[0]
    mb = m_ref[0]                                          # [1, bk]
    dob = do_ref[0]
    lse = lse_ref[0]                                       # [bq, 1]
    delta = delta_ref[0]
    pnorm = jnp.exp(jnp.dot(qb, kb.T,
                            preferred_element_type=jnp.float32)
                    * scale + mb - lse)                    # [bq, bk]
    dw = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
    if rate > 0.0:
        keep_scale = _keep_scale(s_ref, rate, n_qb, n_kb, qi, ki,
                                 (block_q, block_k))
        dw = dw * keep_scale
        dv_p = pnorm * keep_scale
    else:
        dv_p = pnorm
    ds = pnorm * (dw - delta)
    dk_sc[...] += jnp.dot(ds.T.astype(q_ref.dtype), qb,
                          preferred_element_type=jnp.float32)
    dv_sc[...] += jnp.dot(dv_p.T.astype(do_ref.dtype), dob,
                          preferred_element_type=jnp.float32)

    @pl.when(qi == n_qb - 1)
    def _flush():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(rate, scale, n_qb, n_kb, q_ref, k_ref, v_ref, m_ref,
                      s_ref, do_ref, lse_ref, delta_ref, dqp_ref, dk_ref,
                      dv_ref, dk_sc, dv_sc):
    """ONE backward kernel (round-5 fusion): the previous dq/dkv pair each
    recomputed `pnorm` and `dw` — 7 matmuls per tile where 5 suffice (and
    two dropout-mask regenerations where one does). dk/dv accumulate over
    qi exactly as before; dq has the transposed accumulation order, so
    each grid step writes its PARTIAL contribution ds·K to its own
    [ki]-indexed output block (no revisited-output accumulation) and the
    caller reduces the n_kb partials — at 1024-blocks that is a 2-term
    sum, trivially XLA-fused against the matmul that consumes dq."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    qb = q_ref[0]
    kb = k_ref[0]
    vb = v_ref[0]
    mb = m_ref[0]                                          # [1, bk]
    dob = do_ref[0]
    lse = lse_ref[0]                                       # [bq, 1]
    delta = delta_ref[0]
    pnorm = jnp.exp(jnp.dot(qb, kb.T,
                            preferred_element_type=jnp.float32)
                    * scale + mb - lse)                    # [bq, bk]
    dw = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
    if rate > 0.0:
        keep_scale = _keep_scale(s_ref, rate, n_qb, n_kb, qi, ki,
                                 (block_q, block_k))
        dw = dw * keep_scale
        dv_p = pnorm * keep_scale
    else:
        dv_p = pnorm
    ds = pnorm * (dw - delta)
    dqp_ref[0, 0] = jnp.dot(ds.astype(k_ref.dtype), kb,
                            preferred_element_type=jnp.float32)
    dk_sc[...] += jnp.dot(ds.T.astype(q_ref.dtype), qb,
                          preferred_element_type=jnp.float32)
    dv_sc[...] += jnp.dot(dv_p.T.astype(do_ref.dtype), dob,
                          preferred_element_type=jnp.float32)

    @pl.when(qi == n_qb - 1)
    def _flush():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd(rate, _fwd_block_q, _fwd_block_k, block_q, block_k, interpret,
               res, dout):
    # _fwd_block_* are unused: mask regeneration derives its tile indices
    # from the bwd blocks, which flash_attention() forces equal to the fwd
    # blocks whenever dropout is active.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, mask, seed, out, lse = res
    B, H, T, D = q.shape
    scale = 1.0 / math.sqrt(D)
    n_qb, n_kb = T // block_q, T // block_k
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    dof = dout.reshape(B * H, T, D)
    mf = jnp.repeat(mask[:, 0, :, :], H, axis=0)
    # delta[i] = rowsum(dO * O) — the softmax-jacobian diagonal term
    delta = jnp.sum(dof.astype(jnp.float32)
                    * out.reshape(B * H, T, D).astype(jnp.float32),
                    axis=-1, keepdims=True)                # [BH, T, 1]

    # Fused single-kernel backward when (a) the dq-partials buffer is
    # cheap (n_kb × T × D f32 per head-batch; ≤4 partials ≈ ≤2 dq-sized
    # f32 buffers) and (b) the tile fits scoped VMEM — the fused kernel
    # holds pnorm/dw/ds (+ the dropout mask) live together, ~19.7 MB of
    # f32 tiles at 1024². Round-5 measured the alternative of raising
    # `vmem_limit_bytes` to 48 MB so 1024² compiles: 12.2 ms bwd vs the
    # two-kernel pair's 9.6 ms at the same tiling (B=16,H=12,T=2048,
    # D=64, all three grads consumed) — that much live VMEM destroys
    # Mosaic's DMA/compute overlap, so the fused form only pays at
    # tiles ≤512k where it measured ~9.0 ms (1024×512). Otherwise fall
    # back to the two-kernel form — its dq accumulates in VMEM scratch
    # with O(T·D) HBM, paying the duplicated pnorm/dw matmuls instead.
    if n_kb <= 4 and block_q * block_k <= 512 * 1024:
        dqp, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, rate, scale, n_qb, n_kb),
            grid=(B * H, n_kb, n_qb),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b, 0, j)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, j, i: (b, j, i, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * H, n_kb, T, D), jnp.float32),
                jax.ShapeDtypeStruct((B * H, T, D), k.dtype),
                jax.ShapeDtypeStruct((B * H, T, D), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            # scores, dv, dw, dq-partial, dk matmuls; the dqp partials
            # buffer is an extra n_kb×T×D f32 write stream
            cost_estimate=_attn_cost(5, q,
                                     extra_f32_out_elems=B * H * n_kb
                                     * T * D),
            interpret=interpret,
            name="flash_bwd_fused",
        )(qf, kf, vf, mf, seed, dof, lse, delta)
        # the transposed-order accumulation, done where it is cheap: n_kb
        # partials summed by XLA (f32), then scaled — bytes ≈ one
        # dq-sized read per partial, noise next to the matmuls it
        # replaced
        dq = (dqp.sum(axis=1) * scale).astype(q.dtype)
    else:
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, rate, scale, n_qb, n_kb),
            grid=(B * H, n_qb, n_kb),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, D),
                                   lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            cost_estimate=_attn_cost(3, q),   # scores, dw/ds, dq
            interpret=interpret,
            name="flash_dq",
        )(qf, kf, vf, mf, seed, dof, lse, delta)
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, rate, scale, n_qb, n_kb),
            grid=(B * H, n_kb, n_qb),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b, 0, j)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * H, T, D), k.dtype),
                jax.ShapeDtypeStruct((B * H, T, D), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            cost_estimate=_attn_cost(4, q),   # scores, dv, ds, dk
            interpret=interpret,
            name="flash_dkv",
        )(qf, kf, vf, mf, seed, dof, lse, delta)

    shape = (B, H, T, D)
    # padding masks are data, not parameters — zero cotangent
    return (dq.reshape(shape), dk.reshape(shape), dv.reshape(shape),
            jnp.zeros_like(mask), jnp.zeros_like(seed))


def _flash_fwd_rule(q, k, v, mask, seed, rate, block_q, block_k,
                    bwd_block_q, bwd_block_k, interpret):
    return _flash_fwd(q, k, v, mask, seed, rate, block_q, block_k,
                      interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd)
