"""Dropout tuned for TPU: uint8 random bytes by default, with a Pallas
in-kernel-RNG alternative and a jax.random fallback.

Motivation (docs/ROOFLINE.md): XLA's `RngBitGenerator` is not fusible —
every `jax.random.bernoulli` materializes a full uint32 bit tensor to HBM
(4 bytes per masked element, written by the RNG op and read back by the
select). Profiled on v5e (BERT-base, batch 256, seq 128, dropout on all
sites): 16.4 ms/step of rng-bit-generator time plus ~15 ms/step of u32
copies/slices — the whole measured dropout tax.

Three implementations, selected by `ZOO_DROPOUT_IMPL` (a named
implementation runs or raises; it is never swapped for another):

- `u8` (default on TPU) — draw ONE random byte per element
  (`jax.random.bits(..., uint8)`) and keep iff byte < t where
  t = round(keep*256). Scaling uses the exact keep probability t/256, so
  the estimator stays unbiased; the rate is quantized to 1/256 (0.1 →
  0.1016). Bits traffic drops 4x and the compare+select still fuses into
  the surrounding XLA chain. Measured: dropout-on step time equals
  dropout-off within noise (interleaved min-of-5: 191.4 vs 190.1 ms vs
  225.9 ms for u32 bernoulli).
- `pallas` — bits generated INSIDE a Pallas kernel (`pltpu.prng_seed` +
  `prng_random_bits`) per tile; the custom VJP reseeds the identical
  per-tile PRNG in the backward pass (no residual stored; same
  deterministic keep-rule as the in-kernel flash-attention dropout).
  Zero RNG HBM traffic, but the kernel boundary breaks XLA fusions —
  profiled NET SLOWER than u8 in BERT context (+10.3 ms/step kernels,
  +5.7 ms/step lost fusion vs −16.4 rng). Kept for composition in
  hand-written kernels and as the regeneration pattern's reference.
- `u32` — plain `jax.random.bernoulli` (default off-TPU; exact rate).

The reference has per-layer JVM dropout (`keras/layers/Dropout.scala`);
choosing the mask representation for HBM-bandwidth and XLA-fusion
behavior is the TPU-native redesign of that layer's hot path.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp


def _dropout_threshold(rate: float) -> int:
    """keep iff bits >= threshold (uint32 compare) — the keep-rule of the
    full-width Pallas kernel below (`impl=pallas`)."""
    return min(int(rate * 2 ** 32), 2 ** 32 - 1)


def _byte_threshold(rate: float) -> int:
    """keep iff byte < t — the shared uint8 keep-rule: t = round(keep*256),
    clamped to [1, 255]. Scale by the EXACT keep probability t/256 for an
    unbiased estimator (the rate is quantized to 1/256). Used by
    `_u8_dropout` here and `flash_attention._keep_scale` (imported) so the
    byte rule never diverges between the two modules."""
    return max(1, min(255, int(round((1.0 - rate) * 256))))


def _plain_dropout(rng, rate: float, x):
    """jax.random fallback — inverted dropout, same semantics."""
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, jnp.shape(x))
    return jnp.where(mask, x / keep, 0.0)


def _u8_dropout(rng, rate: float, x):
    """Inverted dropout from uint8 random bytes: keep iff byte < t where
    t = round(keep*256), scaled by the EXACT keep probability t/256 (so
    the estimator stays unbiased; the rate is quantized to 1/256 — 0.1
    becomes 0.1016). Bernoulli via uint32 bits materializes 4 bytes of
    RNG output per element to HBM (XLA cannot fuse RngBitGenerator into
    consumers); bytes cut that traffic 4x and the compare+select still
    fuses into the surrounding chain."""
    t = _byte_threshold(rate)
    bits = jax.random.bits(rng, jnp.shape(x), jnp.uint8)
    keep_eff = t / 256.0
    return jnp.where(bits < t, x / jnp.asarray(keep_eff, x.dtype),
                     jnp.zeros((), x.dtype))


def _tile_rows(m: int, c: int) -> int:
    """Largest divisor of m (power-of-two preferred) keeping a tile at or
    under ~256K elements — block + bits + out in VMEM stay ~3 MB f32."""
    cap = max(1, (256 * 1024) // c)
    best = 1
    for bm in (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2):
        if bm <= cap and m % bm == 0:
            return bm
    for bm in range(min(cap, m), 0, -1):
        if m % bm == 0:
            best = bm
            break
    return best


def _kernel(rate, x_ref, s_ref, o_ref):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    pltpu.prng_seed(s_ref[0, 0], i)
    bits = pltpu.prng_random_bits(x_ref.shape)
    keep = bits.astype(jnp.uint32) >= jnp.uint32(_dropout_threshold(rate))
    xb = x_ref[...]
    scale = jnp.asarray(1.0 / (1.0 - rate), xb.dtype)
    o_ref[...] = jnp.where(keep, xb * scale, 0).astype(o_ref.dtype)


def _apply(x2d, seed, rate, interpret):
    """Run the kernel over a [M, C] view (C a multiple of 128)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, C = x2d.shape
    bm = _tile_rows(M, C)
    item = jnp.dtype(x2d.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_kernel, rate),
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, C), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), x2d.dtype),
        # analytic roofline model (check_pallas_cost lint): one read +
        # one write of x, ~3 elementwise ops (threshold/scale/select) —
        # the PRNG bits never touch HBM
        cost_estimate=pl.CostEstimate(flops=3 * M * C,
                                      bytes_accessed=2 * M * C * item,
                                      transcendentals=0),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2d, seed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused(x2d, seed, rate, interpret):
    return _apply(x2d, seed, rate, interpret)


def _fused_fwd(x2d, seed, rate, interpret):
    # no residual tensors: the backward regenerates the mask from the seed
    return _apply(x2d, seed, rate, interpret), seed


def _fused_bwd(rate, interpret, seed, dout):
    # d/dx [mask*scale*x] = mask*scale — the same kernel applied to dout
    return _apply(dout, seed, rate, interpret), jnp.zeros_like(seed)


_fused.defvjp(_fused_fwd, _fused_bwd)


def _view_2d(x):
    """Reshape-only [M, C] view with C a lane-aligned multiple of 128, or
    None when no such view exists without padding."""
    n = math.prod(x.shape)
    if x.ndim >= 2 and x.shape[-1] % 128 == 0:
        return (n // x.shape[-1], x.shape[-1])
    if n % 128 == 0:
        for c in (1024, 512, 256, 128):
            if n % c == 0:
                return (n // c, c)
    return None


def fused_dropout(x, rate: float, *, rng=None,
                  seed: Optional[jax.Array] = None):
    """Inverted dropout over `x` at `rate`. Pass a PRNG key via `rng` (a
    scalar int32 seed is derived) or a scalar int32 `seed` directly.
    Differentiable. rate >= 1 zeroes the tensor (the bernoulli keep=0
    degenerate case, matching `keras/layers/Dropout.scala` semantics)."""
    if rate <= 0.0:
        return x
    if rate >= 1.0:
        return jnp.zeros_like(x)
    if rng is None and seed is None:
        raise ValueError("fused_dropout needs `rng` or `seed`")
    impl = os.environ.get("ZOO_DROPOUT_IMPL")
    if impl is None:
        impl = "u8" if jax.default_backend() == "tpu" else "u32"
    if impl not in ("u8", "u32", "pallas"):
        raise ValueError(f"ZOO_DROPOUT_IMPL={impl!r} (want u8|u32|pallas)")
    if rng is None:
        rng = jax.random.PRNGKey(jnp.asarray(seed, jnp.int32))
    if impl == "u32":
        return _plain_dropout(rng, rate, x)
    if impl == "u8":
        return _u8_dropout(rng, rate, x)
    # pallas was asked for by name: it runs the kernel or it fails —
    # quietly drawing u8 bytes instead would time the wrong program
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "ZOO_DROPOUT_IMPL=pallas needs a TPU backend, found "
            f"{jax.default_backend()!r}")
    shape2d = _view_2d(x)
    if shape2d is None:
        raise ValueError(
            f"ZOO_DROPOUT_IMPL=pallas: shape {tuple(x.shape)} has no "
            "reshape-only [M, C] view with C a multiple of 128 lanes")
    if seed is None:
        seed = jax.random.randint(rng, (), 0, 2 ** 31 - 1, jnp.int32)
    seed = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    out = _fused(x.reshape(shape2d), seed, float(rate), False)
    return out.reshape(x.shape)
