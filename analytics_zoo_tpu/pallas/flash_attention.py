"""Flash attention — Pallas TPU kernels with reference fallback.

The reference has no fused attention at all (its longest-sequence support is
full O(L²) attention on one device, survey §5 long-context note); this module
is part of the beyond-reference long-context capability.

Kernel structure (the canonical TPU flash shape, pallas_guide.md): the grid
is (batch·heads, q-blocks, k-blocks) with the k axis innermost and marked
"arbitrary", so Pallas pipelines K/V block DMAs while online-softmax state
(acc, m, l) lives in VMEM scratch across k steps — VMEM stays O(block²)
at any sequence length. Matmuls run in the input dtype (bf16 on the MXU)
with f32 accumulation; softmax statistics stay f32.

The forward's [block_q, block_k] block is its DMA tile, not its unit of
work: the body walks a 1024-column tile in two chunks of 512 columns
(`_fwd_chunk`; narrower tiles whole), one online-softmax update a chunk, so
that a chunk's two products and its softmax have nothing to wait for but
the [block_q, 128] statistics of the chunk before and the compiler can run
the MXU under the vector work. What is done once an element is kept to
what has to be: 1/sqrt(D) multiplies the q block where it is a power of
two (D = 16, 64, 256: exact in any float type) and the scores otherwise;
the row sums stay per-lane partial sums until the flush; dropout selects
(`_keep_of`, a boolean) and its gain 1/keep meets the accumulator at the
flush; and in a tile the causal diagonal crosses, the second chunk runs on
the rows that can see it.

The backward pass is a custom VJP that recomputes the weights from the saved
output and logsumexp instead of materializing [T,T], at the forward's tiling,
in ONE kernel (`flash_bwd_fused`): each (q-block, k-block) tile's weights,
dropout mask and dW are computed once and feed all three gradients — five
T×T×D products. The kernel walks the DMA tile in column chunks (a quarter of
`block_k`, `_bwd_chunk`), so its live f32 intermediates are a quarter of the
tile; dK/dV accumulate in scratch over the q-blocks of one k-block, dQ in a
[T, D] scratch that stays on the chip for a whole head-batch. That resident
dQ grows with T: up to the 15 MiB that fit the scoped VMEM a Mosaic kernel
gets by default the kernel asks for nothing, over it it asks the compiler
for what it has reckoned (`vmem_limit_bytes`, `_bwd_fused_vmem_limit`), up
to half of the TensorCore's VMEM (64 MiB on the v5e: bfloat16 at 1024 tiles
to T = 53,248 at one width of 128 or under, to T = 25,600 at keys 192 /
values 128). Longer sequences, and tiles that pass the default at one
tile of T already (float32 at 1024 tiles, heads of 256, tiles over 1024
columns or with no 128-aligned quarter to walk), run the same mathematics
as two kernels
(`flash_dq`, `flash_dkv`) that recompute the weights in each — seven
products — with O(block) VMEM at any T.

The residuals. The forward kernel leaves two arrays for the backward: its
output `[B, H, T, D]` and the rows' log-sum-exp, float32 `[B*H, 1, T]` with
T on the lanes (blocks `(1, 1, block_q)`, the layout of the additive mask
rows). The kernels compute with a row statistic as a `[block_q, 128]` array
equal along its lanes (`_lanes`): the forward, which keeps its running
max that way (and its running sum as per-lane partial sums, reduced once a
q-block), transposes the sum of the two into a row at the flush, and every
backward kernel transposes the row block back once a tile, outside its
chunk loop (`_stat_of`). A `[.., T, 1]` array is never
kept: HBM lays a last dimension of 1 out 128 lanes wide, 67 MB a call at
32 x 4096 rows for 0.5 MB of values, written by the forward, read twice
by the backward and, saved across a checkpoint, 2 GB of a 32-application
step. `_flash_fwd` names
both residuals (`jax.ad_checkpoint.checkpoint_name`: `FLASH_OUT_NAME`,
`FLASH_LSE_NAME`) and `save_flash_residuals` is the `jax.checkpoint` policy
that keeps exactly them: a checkpointed block with that policy computes
everything around the kernel again in its backward pass and the kernel's
forward not at all, with the gradients it had, bit for bit. Outside such a
checkpoint a name is the identity and lowers to nothing.

Attention dropout runs INSIDE the kernels: `pltpu.prng_seed(seed, tile)`
reseeds per (batch·head, q-block, k-block) tile, so the backward kernels
regenerate bit-identical masks without storing them. The softmax
denominator uses undropped weights (dropout applies to the normalized
weights — `drop(p)/l == drop(p/l)`), matching the semantics of dropping
softmax output.

A causal mask is a FLAG of the kernels (`causal=True`, static), not an
operand: tiles wholly above the diagonal are skipped in the grid's body
(`pl.when`; their K/V or Q blocks are not fetched either — the index maps
repeat the last needed block), tiles the diagonal crosses are masked from two
iotas, tiles below it run the unmasked body. The causal kernels carry a
`_causal` suffix on their names and count the causal half in their cost
estimates. A full `[B,1,T,T]` mask operand still takes the reference path.

Two head widths. Queries and keys may be wider than values (`q, k:
[B, H, T, Dk]`, `v: [B, H, T, Dv]`; latent attention's keys carry 64 rotary
columns beside their 128, `keras/latent_attention.py`): every kernel takes
its widths from its blocks, the scores are scaled by 1/sqrt(Dk), the output
and dV are Dv wide, dQ and dK are Dk wide. Such kernels carry a `_mla`
suffix after `_causal` (`_kernel_name`), so a trace tells them from the
one-width kernels; at Dk == Dv nothing differs from the one-width form,
instruction for instruction (`tests/test_pallas_tpu_lowering.py` pins the
modules). A width that is no multiple of 128 lanes (192) is laid out padded
to the next one in VMEM, which `_bwd_fused_vmem_need` counts: at 192 / 128
the one-kernel backward's resident dQ and its q, k and dk blocks are 256
lanes wide, so it fits the default scoped VMEM at one 1024 tile (T = 1024)
and asks for more from T = 2048 (30 MiB at the expert model's T = 8192).

Grouped-query heads. K and V may have fewer heads than q (`q: [B, H, T,
Dk]`, `k: [B, H / group, T, Dk]`; LFM2's attention layers: 32 query heads
on 8 K/V heads, `keras/grouped_attention.py`): query head h reads K/V head
h // group. The kernels are the same; only the K/V BlockSpecs index
`b // group` over the flattened heads, so K and V are never repeated to H
heads in HBM. The backward kernels write dK and dV a QUERY head, in
float32, and `_flash_bwd` sums a group's in one reduction: the one-kernel
backward keeps a query head's dQ resident in VMEM, which a group's four
would pass at the lengths served. Such kernels carry a `_gqa` suffix last
(`_kernel_name`); at group 1 every kernel and its index maps are what they
were, instruction for instruction.

`flash_attention` falls back to a jnp implementation when Pallas is
unavailable for the current backend (e.g. CPU tests) — same math, no
tiling; dropout there uses jax.random (different bits, same distribution).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.observability.registry import get_registry
from analytics_zoo_tpu.pallas.dropout import _byte_threshold

# The forward kernel's two residuals by name, and the `jax.checkpoint` policy
# that keeps exactly them (module docstring, "The residuals").
FLASH_OUT_NAME = "attention_kernel_out"
FLASH_LSE_NAME = "attention_kernel_lse"
save_flash_residuals = jax.checkpoint_policies.save_only_these_names(
    FLASH_OUT_NAME, FLASH_LSE_NAME)


def _reference_attention(q, k, v, mask=None, dropout_rate: float = 0.0,
                         dropout_key=None, causal: bool = False):
    """Exact O(L²) attention — the shared non-flash numerics (also what
    `keras.transformer.dot_product_attention` delegates to). `causal`
    adds a materialised lower-triangular [T, T] mask. K and V may have
    fewer heads than q, a whole fraction of them (grouped-query heads:
    query head h reads K/V head h // group): they are read grouped, never
    repeated."""
    B, H, T, depth = q.shape
    group = H // k.shape[1]
    if group > 1:
        mask = None if mask is None else mask[:, :, None]
        q = q.reshape(B, H // group, group, T, depth)
        k, v = k[:, :, None], v[:, :, None]
    scores = jnp.einsum("...qd,...kd->...qk", q, k) / math.sqrt(depth)
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = scores + mask
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores,
                           _MASKED)
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and dropout_key is not None:
        keep = 1.0 - dropout_rate
        m = jax.random.bernoulli(dropout_key, keep, weights.shape)
        weights = jnp.where(m, weights / keep, 0.0)
    out = jnp.einsum("...qk,...kd->...qd", weights, v)
    return out.reshape(B, H, T, v.shape[-1]) if group > 1 else out


# what a masked score reads: finite, so a row's running maximum never
# meets inf - inf, and far enough down that exp() of it is exactly 0
_MASKED = -1e30


def _flash_supported(mask) -> bool:
    """The Pallas kernel runs on TPU and supports padding masks
    ([B,1,1,T]) and, as the static flag `causal`, the causal mask; a full
    [B,1,T,T] mask operand or another backend uses the exact reference
    path (decided statically — no exception-driven fallback)."""
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
                    interpret: Optional[bool] = None,
                    causal: bool = False):
    """q: [B, H, T, Dk]; k: [B, Hkv, T, Dk]; v: [B, Hkv, T, Dv] (Dv may
    differ from Dk: the scores are scaled by 1/sqrt(Dk); Hkv may be a whole
    fraction of H, grouped-query heads, module docstring). mask: additive
    [B,1,1,T] (padding) or [B,1,T,T] (full; reference path only). `causal` (static) masks every
    key after the query's own position, inside the kernels. `dropout_rate`
    > 0 needs `dropout_seed` (scalar int32). Differentiable (custom VJP);
    the mask receives a zero cotangent (padding masks are data, not
    parameters). Returns [B, H, T, Dv].

    Block sizes default to the largest 128-multiple divisor of T up to
    1024: per-tile work must amortize the DMA + softmax-state overhead —
    measured on v5e at T=2048, 1024×1024 blocks run the fwd+bwd 4.4×
    faster than 128×128 and beat the XLA reference attention (~12 vs
    ~19 ms fwd). The block is the DMA tile; the kernels compute inside it
    in column chunks (`_fwd_chunk`, `_bwd_chunk`), so VMEM holds
    O(block_q·chunk) f32 (~2 MB at 1024 x 512) plus the K/V double
    buffers."""
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
                                    key, causal)
    if not (_flash_supported(mask) or interpret):
        key = None
        if use_dropout:
            key = jax.random.PRNGKey(jnp.asarray(dropout_seed, jnp.int32)
                                     if not hasattr(dropout_seed, "dtype")
                                     else dropout_seed)
        return _reference_attention(q, k, v, mask,
                                    dropout_rate if use_dropout else 0.0,
                                    key, causal)
    B, H, T, _ = q.shape
    if H % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"flash_attention: {k.shape[1]} K/V heads do not "
                         f"divide {H} query heads into groups")
    if block_q is None:
        block_q = _auto_block(T)
    if block_k is None:
        block_k = _auto_block(T)
    if mask is None:
        mask = jnp.zeros((B, 1, 1, T), jnp.float32)
    block = math.lcm(block_q, block_k)
    if T % block:
        pad = (-T) % block
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        maskp = jnp.pad(mask, ((0, 0), (0, 0), (0, 0), (0, pad)),
                        constant_values=-1e9)
        out = flash_attention(qp, kp, vp, maskp, dropout_rate, dropout_seed,
                              block_q, block_k, interpret, causal)
        return out[:, :, :T]
    seed = jnp.asarray(dropout_seed if use_dropout else 0,
                       jnp.int32).reshape(1, 1)
    rate = float(dropout_rate) if use_dropout else 0.0
    two, gqa = _two_widths(q, v), _group(q, k) > 1
    get_registry().gauge(
        "flash_forward_chunk_columns", "columns of its DMA tile the flash "
        "forward computes at a time (the tile's width where it is not "
        "chunked), at the blocks flash_attention last picked").set(
            _fwd_chunk(block_k),
            kernel=_kernel_name("flash_fwd", causal, two, gqa))
    limit = _bwd_fused_vmem_limit(block_q, block_k, T, q.shape[-1],
                                  q.dtype.itemsize, v.shape[-1], gqa)
    asked = get_registry().gauge(
        "flash_backward_vmem_limit_bytes", "scoped VMEM the flash backward "
        "asks the compiler for (0: the compiler's default holds it), by the "
        "backward kernel that runs at the blocks flash_attention last "
        "picked")
    for name in (("flash_bwd_fused",) if limit is not None
                 else ("flash_dq", "flash_dkv")):
        asked.set(limit or 0, kernel=_kernel_name(name, causal, two, gqa))
    return _flash(q, k, v, mask, seed, rate, block_q, block_k,
                  bool(interpret) if interpret is not None else False,
                  bool(causal))


# ---------------------------------------------------------------------------
# custom-VJP core (assumes T % lcm(block_q, block_k) == 0, mask [B,1,1,T])
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, mask, seed, rate, block_q, block_k, interpret, causal):
    out, _ = _flash_fwd(q, k, v, mask, seed, rate, block_q, block_k,
                        interpret, causal)
    return out


# -- the causal mask, tile by tile --------------------------------------------
# A [block_q, block_k] tile (qi, ki) holds query rows qi·block_q.. and key
# columns ki·block_k..; key c is seen by query r iff c <= r.
def _tile_needed(qi, ki, block_q, block_k):
    """Not wholly above the diagonal: its first column is seen by its
    last row."""
    return ki * block_k <= qi * block_q + (block_q - 1)


def _tile_unmasked(qi, ki, block_q, block_k):
    """Wholly on or below the diagonal: its last column is seen by its
    first row."""
    return ki * block_k + (block_k - 1) <= qi * block_q


def _last_k_block(qi, block_q, block_k):
    """The last k-block a q-block needs (`_tile_needed`)."""
    return (qi * block_q + (block_q - 1)) // block_k


def _first_q_block(ki, block_q, block_k):
    """The first q-block that needs a k-block (`_tile_needed`)."""
    return (ki * block_k) // block_q


def _causal_scores(scores, row0, col0):
    """`scores` of query rows row0.. and key columns col0.. with every key
    after the query's own position set to `_MASKED`. Column 0 of the first
    k-block is seen by every row, so a row's running maximum is finite
    before it meets a fully masked stretch."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    return jnp.where(cols <= rows, scores, _MASKED)


def _two_widths(q, v) -> bool:
    """Keys wider than values (module docstring, "Two head widths")."""
    return q.shape[-1] != v.shape[-1]


def _group(q, k) -> int:
    """Query heads a K/V head serves (module docstring, "Grouped-query
    heads")."""
    return q.shape[-3] // k.shape[-3]


def _kernel_name(name, causal, two_widths=False, gqa=False):
    """The name the compiler puts on the kernel's instruction, which the
    benchmark's per-kernel metrics match (docs/ProgrammingGuide/
    observability.md): `_causal` for the causal form, then `_mla` where
    the keys are wider than the values, then `_gqa` where a K/V head
    serves several query heads."""
    return name + ("_causal" if causal else "") \
        + ("_mla" if two_widths else "") + ("_gqa" if gqa else "")


def _on_causal_tiles(causal, qi, ki, block_q, block_k, tile):
    """Run `tile(masked)` for grid step (qi, ki): always and unmasked
    without `causal`; else not at all above the diagonal, masked where the
    diagonal crosses the tile, unmasked below it."""
    from jax.experimental import pallas as pl

    if not causal:
        tile(False)
        return
    unmasked = _tile_unmasked(qi, ki, block_q, block_k)
    pl.when(unmasked)(lambda: tile(False))
    pl.when(jnp.logical_and(_tile_needed(qi, ki, block_q, block_k),
                            jnp.logical_not(unmasked)))(lambda: tile(True))


def _tile_words(s_ref, n_qb, n_kb, qi, ki, shape):
    """The dropout draw of one (bh, qi, ki) tile: a [shape[0], shape[1]/4]
    array of uint32 words, one byte an element. The tile index folds
    (bh, qi, ki); prng_seed on this mosaic takes 2 scalars."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh = pl.program_id(0)
    tile = (bh * n_qb + qi) * n_kb + ki
    pltpu.prng_seed(s_ref[0, 0], tile)
    words = pltpu.prng_random_bits((shape[0], shape[1] // 4))
    return words.astype(jnp.uint32)


def _keep_of(words, rate, lo, hi):
    """Dropout mask of the tile's columns [lo, hi), boolean: kept or
    dropped. Byte j of a word is the draw of column j·(block_k/4) + (the
    word's own column), so byte plane j IS the mask of the j-th quarter of
    the tile and a chunk of columns needs no more than a shift of the
    words it covers."""
    plane = words.shape[1]
    t = _byte_threshold(rate)
    bytes_ = []
    for j in range(4):
        a = max(lo, j * plane) - j * plane
        b = min(hi, (j + 1) * plane) - j * plane
        if a < b:
            bytes_.append((words[:, a:b] >> (8 * j)) & jnp.uint32(0xFF))
    # side by side as bytes, then ONE compare: Mosaic does not lay boolean
    # pieces narrower than a lane tile side by side
    bytes_ = bytes_[0] if len(bytes_) == 1 else jnp.concatenate(bytes_,
                                                                axis=1)
    return bytes_ < jnp.uint32(t)


def _keep_gain(rate):
    """What a kept weight is multiplied by: the exact 1 / (t/256) of the
    byte rule (unbiased; the rate is quantized to 1/256 like
    `pallas/dropout._u8_dropout`)."""
    return 256.0 / _byte_threshold(rate)


def _keep_scale(s_ref, rate, n_qb, n_kb, qi, ki, shape):
    """Deterministic per-tile dropout scale, 1/keep where kept and 0 where
    dropped, for the two-kernel backward. Identical bits in the forward
    and the backward kernels.

    The PRNG is the expensive part (~20 cycles/word on v5e when drawing
    one uint32 per element), so draw one word per FOUR elements and use
    each byte as an independent keep-draw: keep iff byte < t, t =
    round(keep*256). Which byte lands on which column is an arbitrary
    fixed bijection — the mask stays iid Bernoulli and regenerates
    bit-identically in the backward kernels."""
    keep = _keep_of(_tile_words(s_ref, n_qb, n_kb, qi, ki, shape), rate,
                    0, shape[1])
    return jnp.where(keep, _keep_gain(rate), 0.0)


# -- a row statistic in the kernels --------------------------------------------
# One value a query row (a running max or sum, the log-sum-exp) lives in the
# kernels as a [block_q, 128] array that is EQUAL ALONG ITS LANES, the layout
# jax's own TPU flash kernel keeps its statistics in: meeting a [block_q, w]
# tile it is sliced or laid side by side (`_lanes`), which moves no data,
# where a [block_q, 1] column is broadcast along the lanes by an operation a
# vreg every time it is used (the backward used its column once a chunk, four
# times a tile: 7% of the kernel at T = 2048, PERF.md, PR 27). HBM holds the
# log-sum-exp as a [1, block_q] row (T on the lanes); one aligned
# [block_q, 128] <-> [128, block_q] transpose turns one into the other.
_LANES = 128


def _lanes(stat, width):
    """A [n, 128] statistic, equal along its lanes, as [n, width]."""
    if width <= _LANES:
        return stat[:, :width]
    if width % _LANES:
        return jnp.broadcast_to(stat[:, :1], (stat.shape[0], width))
    return jnp.tile(stat, (1, width // _LANES))


def _stat_of(row):
    """A [1, n] row block of HBM -> the [n, 128] statistic."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _fwd_chunk(block_k: int) -> int:
    """Columns the forward computes at a time inside its [block_q,
    block_k] DMA tile: 512 where the tile holds two or more such chunks,
    else the whole tile. Its statistics are updated once a chunk and cost
    what 128 columns of scores cost, so it walks wider chunks than the
    backward (`_bwd_chunk`): on the v5e halves of a 1024 tile beat
    quarters and eighths, and a 512 tile whole beats its halves (PERF.md,
    PR 29)."""
    return 512 if block_k > 512 and block_k % 512 == 0 else block_k


def _scale_on_q(scale: float) -> bool:
    """Whether 1/sqrt(D) may multiply the [block_q, D] q block in place of
    every score: only a power of two (D = 16, 64, 256) does so without
    moving a single rounding, in bfloat16 and in float32."""
    return math.frexp(scale)[0] == 0.5


def _lane_sums(p):
    """[n, w] -> [n, 128]: the row sums of `p` as per-lane partial sums
    (column c adds into lane c mod 128), no lane reduction."""
    out = p[:, :_LANES]
    for lo in range(_LANES, p.shape[1], _LANES):
        out = out + p[:, lo:lo + _LANES]
    return out


def _fwd_kernel(rate, scale, n_qb, n_kb, causal, q_ref, k_ref, v_ref, m_ref,
                s_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc):
    """The DMA tile is [block_q, block_k]; the body walks it in column
    chunks (`_fwd_chunk`), one online-softmax update a chunk, so the
    live f32 intermediates are [block_q, chunk] and a chunk's product
    waits for the chunk before it only through the [block_q, 128]
    statistics. `m_sc` is the running max, equal along its lanes; `l_sc`
    holds the running sum as per-lane PARTIAL sums (`_lane_sums`: the
    rescale by `alpha` is the same in every lane), reduced along the
    lanes once a q-block, at the flush. Dropout zeroes the dropped
    weights a chunk; their gain 1/keep meets the [block_q, D] accumulator
    once, at the flush. In a tile the causal diagonal crosses (square
    tiles: qi == ki) the chunk at column `lo` holds nothing for the rows
    above `lo`, which would leave their statistics as they are: it is
    computed on the rows from `lo` down."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    chunk = _fwd_chunk(block_k)
    lane_sums = chunk % _LANES == 0
    on_q = _scale_on_q(scale)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)

    def tile(masked):
        qb = q_ref[0]                                      # [bq, D]
        if on_q:
            qb = qb * jnp.asarray(scale, qb.dtype)
        if rate > 0.0:
            words = _tile_words(s_ref, n_qb, n_kb, qi, ki,
                                (block_q, block_k))
        for lo in range(0, block_k, chunk):
            cols = slice(lo, lo + chunk)
            below = masked and block_q == block_k and lo > 0
            rows = slice(lo, None) if below else slice(None)
            scores = jnp.dot(qb[rows], k_ref[0, cols, :].T,
                             preferred_element_type=jnp.float32)
            if not on_q:
                scores = scores * scale
            scores = scores + m_ref[0, :, cols]            # [1, chunk]
            if masked:
                scores = _causal_scores(
                    scores, qi * block_q + (lo if below else 0),
                    ki * block_k + lo)
            m_prev = m_sc[rows, :]                         # [rows, 128]
            m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - _lanes(m_new, chunk))     # [rows, chunk]
            m_sc[rows, :] = m_new
            l_sc[rows, :] = l_sc[rows, :] * alpha + (
                _lane_sums(p) if lane_sums
                else p.sum(axis=1, keepdims=True))
            if rate > 0.0:
                p = jnp.where(_keep_of(words[rows], rate, lo, lo + chunk),
                              p, 0.0)
            acc_sc[rows, :] = acc_sc[rows, :] * _lanes(
                alpha, acc_sc.shape[1]) + jnp.dot(
                    p.astype(v_ref.dtype), v_ref[0, cols, :],
                    preferred_element_type=jnp.float32)

    _on_causal_tiles(causal, qi, ki, block_q, block_k, tile)

    @pl.when(ki == n_kb - 1)
    def _flush():
        acc, l = acc_sc[...], l_sc[...]
        if lane_sums:
            l = jnp.broadcast_to(l.sum(axis=1, keepdims=True), l.shape)
        if rate > 0.0:
            acc = acc * _keep_gain(rate)
        o_ref[0] = (acc / _lanes(l, acc.shape[1])).astype(o_ref.dtype)
        lse_ref[0] = (m_sc[...] + jnp.log(l)).T[:1]        # [1, bq]


def _attn_cost(qk_matmuls, v_matmuls, q, v, extra_f32_out_elems=0,
               causal=False, group=1):
    """Analytic roofline model for one attention kernel over q
    [..., T, Dk] and v [..., T, Dv] (check_pallas_cost lint: HLO cost
    analysis sees ~0 inside a Mosaic call). `qk_matmuls` counts the
    T×T×Dk matmul-shaped products the kernel runs per head (scores, dQ,
    dK) and `v_matmuls` the T×T×Dv ones (context, dW, dV), 2 flops each;
    bytes are the O(T·D) streams — q/k/v-sized reads and writes, half of
    them at each width — NOT the O(T²) scores, which is the IO-aware point
    of flash attention; exp() is one per score. A causal kernel is counted
    at the lower triangle: half the products and half the scores (what the
    algorithm needs; the tiles the diagonal crosses are computed whole).
    With grouped-query heads (`group` > 1) the K and V the kernel reads
    are counted once a K/V head, the other streams once a query head."""
    from jax.experimental import pallas as pl

    *lead, T, Dk = q.shape
    Dv = v.shape[-1]
    bh = math.prod(lead)
    item = jnp.dtype(q.dtype).itemsize
    # rough: q,k,v(+dout...) in, grads/out out
    streams = 4 + qk_matmuls + v_matmuls
    half = 2 if causal else 1
    streamed = bh * T * (Dk + Dv) * item * streams // 2
    if group > 1:
        streamed -= bh * T * (Dk + Dv) * item * (group - 1) // group
    return pl.CostEstimate(
        flops=2 * bh * T * T * (qk_matmuls * Dk + v_matmuls * Dv) // half,
        bytes_accessed=streamed + extra_f32_out_elems * 4,
        transcendentals=bh * T * T // half)


def _flash_fwd(q, k, v, mask, seed, rate, block_q, block_k, interpret,
               causal):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    Dv, group = v.shape[-1], _group(q, k)
    scale = 1.0 / math.sqrt(D)
    n_qb, n_kb = T // block_q, T // block_k
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H // group, T, D)
    vf = v.reshape(B * H // group, T, Dv)
    mf = jnp.repeat(mask[:, 0, :, :], H, axis=0)           # [B*H, 1, T]

    def kj(i, j):
        # a skipped step names the block it already holds: no DMA
        return jnp.minimum(j, _last_k_block(i, block_q, block_k)) \
            if causal else j

    def kv(b):
        # query head b reads K/V head b // group, never a repeated copy
        return b // group if group > 1 else b

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, rate, scale, n_qb, n_kb, causal),
        grid=(B * H, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j: (kv(b), kj(i, j), 0)),
            pl.BlockSpec((1, block_k, Dv),
                         lambda b, i, j: (kv(b), kj(i, j), 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, kj(i, j))),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=_attn_cost(1, 1, q, v,              # QKᵀ + PV
                                 extra_f32_out_elems=B * H * T,
                                 causal=causal, group=group),
        interpret=interpret,
        name=_kernel_name("flash_fwd", causal, _two_widths(q, v),
                          group > 1),
    )(qf, kf, vf, mf, seed)
    out = checkpoint_name(out.reshape(B, H, T, Dv), FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return out, (q, k, v, mask, seed, out, lse)


def _delta(do_ref, o_ref):
    """delta[i] = rowsum(dO * O), the softmax-jacobian diagonal term of a
    q-block, [bq, 1] f32: 64 multiply-adds a row beside the tile's
    thousands, so every backward kernel computes it where it needs it and
    no [T, 1] array (128 lanes wide in HBM) is written or read."""
    return jnp.sum(do_ref[0].astype(jnp.float32)
                   * o_ref[0].astype(jnp.float32), axis=1, keepdims=True)


def _dq_kernel(rate, scale, n_qb, n_kb, causal, q_ref, k_ref, v_ref, m_ref,
               s_ref, do_ref, lse_ref, o_ref, dq_ref, dq_sc):
    """Standalone dq (accumulate over ki in scratch): half of the
    two-kernel backward for shapes the fused kernel's VMEM need rules out
    — see _flash_bwd."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def tile(masked):
        qb = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        mb = m_ref[0]
        dob = do_ref[0]
        lse = _lanes(_stat_of(lse_ref[0]), block_k)        # [bq, bk]
        delta = _delta(do_ref, o_ref)                      # [bq, 1]
        scores = jnp.dot(qb, kb.T,
                         preferred_element_type=jnp.float32) * scale + mb
        if masked:
            scores = _causal_scores(scores, qi * block_q, ki * block_k)
        pnorm = jnp.exp(scores - lse)                      # softmax weights
        dw = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        if rate > 0.0:
            dw = dw * _keep_scale(s_ref, rate, n_qb, n_kb, qi, ki,
                                  (block_q, block_k))
        ds = pnorm * (dw - delta)                          # [bq, bk]
        dq_sc[...] += jnp.dot(ds.astype(k_ref.dtype), kb,
                              preferred_element_type=jnp.float32)

    _on_causal_tiles(causal, qi, ki, block_q, block_k, tile)

    @pl.when(ki == n_kb - 1)
    def _flush():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(rate, scale, n_qb, n_kb, causal, q_ref, k_ref, v_ref, m_ref,
                s_ref, do_ref, lse_ref, o_ref, dk_ref, dv_ref, dk_sc, dv_sc):
    """dk/dv-only companion of _dq_kernel."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def tile(masked):
        qb = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        mb = m_ref[0]                                      # [1, bk]
        dob = do_ref[0]
        lse = _lanes(_stat_of(lse_ref[0]), block_k)        # [bq, bk]
        delta = _delta(do_ref, o_ref)
        scores = jnp.dot(qb, kb.T,
                         preferred_element_type=jnp.float32) * scale + mb
        if masked:
            scores = _causal_scores(scores, qi * block_q, ki * block_k)
        pnorm = jnp.exp(scores - lse)                      # [bq, bk]
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

    _on_causal_tiles(causal, qi, ki, block_q, block_k, tile)

    @pl.when(qi == n_qb - 1)
    def _flush():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_chunk(block_k: int) -> int:
    """Columns the fused backward computes at a time inside its
    [block_q, block_k] DMA tile: a quarter of the tile, which is one byte
    plane of the dropout words (`_keep_of`), where that is lane-aligned;
    else the whole tile (tiles of 128 and 256 columns are small enough)."""
    quarter = block_k // 4
    return quarter if quarter % 128 == 0 else block_k


# Scoped VMEM and the one-kernel backward. A Mosaic kernel gets 16 MiB of
# scoped VMEM by default, a default of the compiler and not the chip's size
# (a v5e TensorCore has 128 MiB), and `CompilerParams(vmem_limit_bytes=...)`
# is how a kernel asks for more. Where the reckoned need
# (`_bwd_fused_vmem_need`) is within `_BWD_FUSED_VMEM_BYTES` (15 MiB: that
# default less `_VMEM_ROOM_BYTES` for what the reckoning does not count,
# the mask blocks and semaphores), the kernel asks nothing and lowers as it
# always has. Over it the kernel asks for its need, rounded up to a MiB,
# plus the same room (`_bwd_fused_vmem_limit`), as long as that is within
# the ceiling (`_bwd_fused_vmem_ceiling`). The ceiling is there for the one
# buffer that grows with T, the resident dQ: a tile that passes the default
# at the shortest sequence it serves (float32 at 1024 tiles, a tile with no
# 128-aligned quarter to walk from 768 columns up, any tile over 1024
# columns) runs as the pair at every length, as it always has, and so does
# a sequence whose dQ passes the ceiling.
_DEFAULT_SCOPED_VMEM_BYTES = 16 * 2 ** 20
_VMEM_ROOM_BYTES = 2 ** 20
_BWD_FUSED_VMEM_BYTES = _DEFAULT_SCOPED_VMEM_BYTES - _VMEM_ROOM_BYTES
# VMEM of the TensorCore the repo is built for, where no TPU is attached
# (a kernel cross-lowered or compiled ahead of time from a CPU): jax's own
# table, `jax._src.pallas.mosaic.tpu_info`, case "TPU v5 lite".
_V5E_VMEM_BYTES = 128 * 2 ** 20


def _bwd_fused_vmem_ceiling() -> int:
    """The most scoped VMEM the one-kernel backward may ask for: half of
    the TensorCore's VMEM (`pltpu.get_tpu_info()` where the default device
    is a TPU, else the v5e's), so that nothing of XLA's around the call is
    squeezed, and never under the compiler's default, which every chip
    grants."""
    from jax.experimental.pallas import tpu as pltpu

    vmem = (pltpu.get_tpu_info().vmem_capacity_bytes
            if jax.devices()[0].platform == "tpu" else _V5E_VMEM_BYTES)
    return max(vmem // 2, _DEFAULT_SCOPED_VMEM_BYTES)


def _bwd_fused_vmem_need(block_q, block_k, T, D, itemsize, Dv=None,
                         gqa=False) -> int:
    """Bytes of VMEM the one-kernel backward holds at these shapes,
    reckoned from what it holds, every [rows, D] buffer padded to 128
    lanes as Mosaic lays it out at deployment sizes (q, k, dq and dk at
    the key width `D`; v, dO, O and dv at the value width `Dv`, which is
    `D` unless given): dq for the whole
    head-batch (f32 scratch plus its double-buffered output block); the
    double-buffered q/dO/O and k/v/dk/dv blocks, the dk/dv accumulators,
    the log-sum-exp's double-buffered [1, block_q] row blocks (8 sublanes
    each) and the [block_q, 128] statistic a tile turns one into;
    the tile's dropout words and 4.5 live f32 [block_q, chunk]
    intermediates. Held against the chip's compiler over T = 512..8192,
    D = 32..256, bf16 and f32 (PERF.md, PR 25): Mosaic allocates 3.4 such
    intermediates where it pads every buffer (192 head-batches) and as
    little as half the total where it does not (4 head-batches), so the
    reckoning reads high, never low (T = 8192 at keys 192 / values 128,
    64 head-batches: 28.56 MiB reckoned, 22.5 allocated). With the
    log-sum-exp as rows Mosaic
    asks for 0.8-1.0 MiB less at 1024 tiles than with [block_q, 1] column
    blocks (PERF.md, PR 27), the reckoning for 0.44 MiB less. With
    grouped-query heads (`gqa`) the dk/dv blocks are float32."""
    lanes = -(-D // 128) * 128
    lanes_v = lanes if Dv is None else -(-Dv // 128) * 128
    chunk = _bwd_chunk(block_k)
    resident_dq = T * lanes * (4 + 2 * itemsize)
    streams = ((2 * block_q + 4 * block_k) * lanes * itemsize
               + (4 * block_q + 4 * block_k) * lanes_v * itemsize
               + block_k * (lanes + lanes_v) * 4
               + 2 * 8 * block_q * 4 + block_q * _LANES * 4)
    live = block_q * block_k + int(4.5 * block_q * chunk * 4)
    if gqa:
        streams += 2 * block_k * (lanes + lanes_v) * (4 - itemsize)
    return resident_dq + streams + live


def _bwd_fused_vmem_limit(block_q, block_k, T, D, itemsize, Dv=None,
                          gqa=False):
    """The backward's form at these shapes, as the `vmem_limit_bytes` its
    one kernel asks for: 0 where the compiler's default holds it (no limit
    is passed), the need rounded up to a MiB plus the room where it has to
    ask, None where it runs as the pair (comment above)."""
    def need(T):
        return _bwd_fused_vmem_need(block_q, block_k, T, D, itemsize, Dv,
                                    gqa)
    if need(T) <= _BWD_FUSED_VMEM_BYTES:
        return 0
    if need(math.lcm(block_q, block_k)) > _BWD_FUSED_VMEM_BYTES:
        return None             # the tile, not T: no length is helped
    limit = -(-need(T) // 2 ** 20) * 2 ** 20 + _VMEM_ROOM_BYTES
    return limit if limit <= _bwd_fused_vmem_ceiling() else None


def _bwd_fused_fits(block_q, block_k, T, D, itemsize, Dv=None,
                    gqa=False) -> bool:
    """Whether the backward runs as one kernel at these shapes."""
    return _bwd_fused_vmem_limit(block_q, block_k, T, D, itemsize,
                                 Dv, gqa) is not None


def _bwd_fused_kernel(rate, scale, n_qb, n_kb, causal, q_ref, k_ref, v_ref,
                      m_ref, s_ref, do_ref, lse_ref, o_ref, dq_ref, dk_ref,
                      dv_ref, dq_sc, dk_sc, dv_sc):
    """ONE backward kernel: the weights, dW and the dropout mask of a tile
    are computed once and feed dq, dk and dv — 5 matmuls a tile where the
    dq/dkv pair runs 7 (and one mask regeneration where it runs two).

    The DMA tile is [block_q, block_k]; the body walks it in column chunks
    (`_bwd_chunk`), so the live f32 intermediates are [block_q, chunk].
    dk/dv accumulate over qi in scratch rows of their chunk. dq has the
    transposed accumulation order: its [T, D] f32 scratch and its output
    block belong to the head-batch (block index (b, 0, 0)), so they stay
    in VMEM over both inner grid axes and go back to HBM once."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]
    chunk = _bwd_chunk(block_k)
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(ki == 0)
    def _init_dq():
        dq_sc[rows, :] = jnp.zeros((block_q, dq_sc.shape[1]), jnp.float32)

    def tile(masked):
        qb = q_ref[0]
        dob = do_ref[0]
        lse = _lanes(_stat_of(lse_ref[0]), chunk)   # [bq, chunk], once a tile
        delta = _delta(do_ref, o_ref)
        if rate > 0.0:
            words = _tile_words(s_ref, n_qb, n_kb, qi, ki,
                                (block_q, block_k))
        for lo in range(0, block_k, chunk):
            cols = slice(lo, lo + chunk)
            kc = k_ref[0, cols, :]
            vc = v_ref[0, cols, :]
            scores = jnp.dot(qb, kc.T, preferred_element_type=jnp.float32) \
                * scale + m_ref[0, :, cols]
            if masked:
                scores = _causal_scores(scores, qi * block_q,
                                        ki * block_k + lo)
            pnorm = jnp.exp(scores - lse)                  # [bq, chunk]
            dw = jnp.dot(dob, vc.T, preferred_element_type=jnp.float32)
            if rate > 0.0:
                keep_scale = jnp.where(
                    _keep_of(words, rate, lo, lo + chunk),
                    _keep_gain(rate), 0.0)
                dw = dw * keep_scale
                dv_p = pnorm * keep_scale
            else:
                dv_p = pnorm
            ds = pnorm * (dw - delta)
            dq_sc[rows, :] += jnp.dot(ds.astype(k_ref.dtype), kc,
                                      preferred_element_type=jnp.float32)
            dk_sc[cols, :] += jnp.dot(ds.T.astype(q_ref.dtype), qb,
                                      preferred_element_type=jnp.float32)
            dv_sc[cols, :] += jnp.dot(dv_p.T.astype(do_ref.dtype), dob,
                                      preferred_element_type=jnp.float32)

    _on_causal_tiles(causal, qi, ki, block_q, block_k, tile)

    @pl.when(ki == n_kb - 1)
    def _flush_dq():
        dq_ref[0, rows, :] = (dq_sc[rows, :] * scale).astype(dq_ref.dtype)

    @pl.when(qi == n_qb - 1)
    def _flush():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_in_specs(block_q, block_k, D, Dv, q_major, causal, group=1):
    """Input BlockSpecs shared by the three backward kernels (q, k, v,
    mask, seed, dO, lse, O), with the q-, the k- and the v-block spec of
    a query head, which the gradients are written by (q, k `D` wide; v,
    dO, O `Dv` wide). With grouped-query heads (`group` > 1) the k and v
    INPUT blocks are those of K/V head b // group. A
    `q_major` grid is (bh, qi, ki), the other (bh, ki, qi). With `causal`
    the inner axis's blocks stop at the diagonal: a skipped step names the
    nearest needed block, which the pipeline already holds."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(shape, of_q, at):
        axis = 1 if of_q == q_major else 2
        if not causal or axis == 1:
            return pl.BlockSpec(shape, lambda *g: at(g[0], g[axis]))
        if q_major:     # inner axis walks k-blocks: none past the diagonal
            return pl.BlockSpec(shape, lambda b, i, j: at(b, jnp.minimum(
                j, _last_k_block(i, block_q, block_k))))
        return pl.BlockSpec(shape, lambda b, j, i: at(b, jnp.maximum(
            i, _first_q_block(j, block_q, block_k))))
    q_spec = spec((1, block_q, D), True, lambda b, i: (b, i, 0))
    k_spec = spec((1, block_k, D), False, lambda b, j: (b, j, 0))
    o_spec = spec((1, block_q, Dv), True, lambda b, i: (b, i, 0))
    v_spec = spec((1, block_k, Dv), False, lambda b, j: (b, j, 0))
    m_spec = spec((1, 1, block_k), False, lambda b, j: (b, 0, j))
    lse_spec = spec((1, 1, block_q), True, lambda b, i: (b, 0, i))
    k_in, v_in = k_spec, v_spec
    if group > 1:
        k_in = spec((1, block_k, D), False, lambda b, j: (b // group, j, 0))
        v_in = spec((1, block_k, Dv), False,
                    lambda b, j: (b // group, j, 0))
    return ([q_spec, k_in, v_in, m_spec,
             pl.BlockSpec(memory_space=pltpu.SMEM), o_spec, lse_spec,
             o_spec], q_spec, k_spec, v_spec)


def _grads(qf, vf, group):
    """The backward kernels' outputs dq, dk, dv: a query head's each, in
    q's type; with grouped-query heads dk and dv in float32, for the sum
    over a group's query heads (`_flash_bwd`) to round once."""
    BH, T, D = qf.shape
    kv_type = qf.dtype if group == 1 else jnp.float32
    return (jax.ShapeDtypeStruct((BH, T, D), qf.dtype),
            jax.ShapeDtypeStruct((BH, T, D), kv_type),
            jax.ShapeDtypeStruct((BH, T, vf.shape[-1]), kv_type))


def _bwd_fused(rate, scale, block_q, block_k, interpret, causal, operands):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qf, kf, vf = operands[:3]
    BH, T, D = qf.shape
    Dv, group = vf.shape[-1], _group(qf, kf)
    n_qb, n_kb = T // block_q, T // block_k
    in_specs, _, k_spec, v_spec = _bwd_in_specs(
        block_q, block_k, D, Dv, q_major=False, causal=causal, group=group)
    grad, grad_k, grad_v = _grads(qf, vf, group)
    # unset (None) wherever the compiler's default holds the kernel
    limit = _bwd_fused_vmem_limit(block_q, block_k, T, D,
                                  qf.dtype.itemsize, Dv, group > 1) or None
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, rate, scale, n_qb, n_kb,
                          causal),
        grid=(BH, n_kb, n_qb),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, T, D), lambda b, j, i: (b, 0, 0)),
                   k_spec, v_spec],
        out_shape=[grad, grad_k, grad_v],
        scratch_shapes=[
            pltpu.VMEM((T, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        # dq is revisited over both inner axes
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=limit),
        # scores, dq, dk at the key width; dw, dv at the value width
        cost_estimate=_attn_cost(3, 2, qf, vf, causal=causal,
                                 group=group),
        interpret=interpret,
        name=_kernel_name("flash_bwd_fused", causal, _two_widths(qf, vf),
                          group > 1),
    )(*operands)


def _bwd_pair(rate, scale, block_q, block_k, interpret, causal, operands):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qf, kf, vf = operands[:3]
    BH, T, D = qf.shape
    Dv, group = vf.shape[-1], _group(qf, kf)
    two, gqa = _two_widths(qf, vf), group > 1
    n_qb, n_kb = T // block_q, T // block_k
    grad, grad_k, grad_v = _grads(qf, vf, group)
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    in_specs, q_spec, _, _ = _bwd_in_specs(block_q, block_k, D, Dv,
                                           q_major=True, causal=causal,
                                           group=group)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, rate, scale, n_qb, n_kb, causal),
        grid=(BH, n_qb, n_kb),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=grad,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=semantics,
        # scores, dq at the key width; dw at the value width
        cost_estimate=_attn_cost(2, 1, qf, vf, causal=causal,
                                 group=group),
        interpret=interpret,
        name=_kernel_name("flash_dq", causal, two, gqa),
    )(*operands)
    in_specs, _, k_spec, v_spec = _bwd_in_specs(
        block_q, block_k, D, Dv, q_major=False, causal=causal, group=group)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, rate, scale, n_qb, n_kb, causal),
        grid=(BH, n_kb, n_qb),
        in_specs=in_specs,
        out_specs=[k_spec, v_spec],
        out_shape=[grad_k, grad_v],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        compiler_params=semantics,
        # scores, dk at the key width; dw, dv at the value width
        cost_estimate=_attn_cost(2, 2, qf, vf, causal=causal,
                                 group=group),
        interpret=interpret,
        name=_kernel_name("flash_dkv", causal, two, gqa),
    )(*operands)
    return dq, dk, dv


def _flash_bwd(rate, block_q, block_k, interpret, causal, res, dout):
    q, k, v, mask, seed, out, lse = res
    B, H, T, D = q.shape
    Dv, group = v.shape[-1], _group(q, k)
    scale = 1.0 / math.sqrt(D)
    qf, kf, vf, dof, of = (x.reshape(-1, T, x.shape[-1])
                           for x in (q, k, v, dout, out))
    mf = jnp.repeat(mask[:, 0, :, :], H, axis=0)
    # One algorithm, two forms, chosen from the shapes alone: the fused
    # kernel wherever its VMEM need fits the chip (`_bwd_fused_vmem_limit`),
    # else the pair that pays the duplicated pnorm/dw matmuls with O(block)
    # VMEM at any T. Both run at the forward's tiling — the dropout mask is
    # keyed by tile.
    fused = _bwd_fused_fits(block_q, block_k, T, D, q.dtype.itemsize, Dv,
                            group > 1)
    dq, dk, dv = (_bwd_fused if fused else _bwd_pair)(
        rate, scale, block_q, block_k, interpret, causal,
        (qf, kf, vf, mf, seed, dof, lse, of))
    if group > 1:
        # a K/V head's gradient: its group's query heads' summed, in one
        # reduction (the kernels keep a query head's dq resident, so a
        # group's four would not fit VMEM at the lengths served)
        dk, dv = (g.reshape(B, H // group, group, T, g.shape[-1]).sum(
            axis=2).astype(k.dtype) for g in (dk, dv))
    # padding masks are data, not parameters — zero cotangent
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            jnp.zeros_like(mask), jnp.zeros_like(seed))


_flash.defvjp(_flash_fwd, _flash_bwd)
