"""Fused Adam — the optimizer sweep as ONE blocked Pallas pass (ISSUE 9).

BENCH r05 pinned NCF at 33% of its achievable memory bound and the
roofline per-op breakdown (docs/ROOFLINE.md) blamed the dense-Adam
sweep: optax builds the update as a chain of materialized trees (new
mu, new nu, the updates tree, then `apply_updates`), and XLA's fusion
does not collapse the chain back to the information-theoretic floor —
the sweep reads/writes the parameter set 10-12× per step where 7
element-passes suffice (read g; read+write p, m, v). Structural
repacking (flat/stacked buffers) could not fix this because the extra
passes are *between* ops, not between tensors. This module goes below
XLA: one kernel reads a (grad, m, v, param) tile from HBM, applies the
whole Adam update in VMEM, and writes (m, v, param) back — 7 passes
total, in-place via `input_output_aliases`, the FlashAttention
IO-aware-kernel argument applied to the optimizer.

Numerics: bias correction is folded into two scalars computed OUTSIDE
the kernel (`a = lr·√c2/c1`, `b = eps·√c2` with `c_i = 1 - βᵢᵗ`), so
the in-kernel math is `p ← p − a·m̂/(√v̂ + b) − lr·wd·p` with
`m̂, v̂` the *uncorrected* new moments — algebraically identical to
`optax.adam`/`adamw` (decoupled weight decay), moments always f32,
params f32 or bf16 (cast at the write). Schedules stay host-side: the
caller passes the resolved per-step `lr`.

Every `pallas_call` carries an analytic `cost_estimate` (XLA's HLO
cost analysis cannot see inside a custom call), so the roofline layer
(`observability/roofline.py`) keeps counting the fused step's true HBM
bytes — `update_cost()` is that model, exported for tests and benches.

`interpret=None` auto-selects interpreter mode off-TPU so tier-1
exercises the exact kernel code path on the CPU; on a TPU backend it
never resolves to the interpreter, and a kernel Mosaic refuses raises
out of the fit's first step — nothing probes ahead and nothing swaps
plain optax in for a fused step that was asked for.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Per-operand VMEM budget for a block: 7 live buffers (4 in + 3 out)
# double-buffered must fit under Mosaic's 16 MB scoped-VMEM limit;
# 512 KB/block → ≤ 7 MB resident, big enough to amortize DMA issue
# overhead.
_BLOCK_BYTES = 512 * 1024


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """Off-TPU backends run the kernel through the Pallas interpreter —
    same code path, same block walk — so CPU tests test the kernel."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _block_rows(rows: int, cols: int) -> int:
    """Largest multiple-of-8 row count whose f32 block stays under the
    VMEM budget (min 8 — smaller blocks pad to the (8, 128) f32 tile
    anyway). The budget counts the block as VMEM holds it, lanes padded
    to a multiple of 128: at the unpadded width a 64-wide NCF table got
    2048-row blocks, 16.43 MB of scoped VMEM, and Mosaic refused the
    step (RESOURCE_EXHAUSTED, limit 16 MB; PR 21 chip run)."""
    lanes = -(-max(cols, 1) // 128) * 128
    bm = max(8, _BLOCK_BYTES // (4 * lanes))
    bm -= bm % 8
    return min(max(bm, 8), max(rows, 1))


def _fold_scalars(count, lr, b1: float, b2: float, eps: float,
                  weight_decay: float):
    """(a, b, lr·wd) f32 vector: the whole bias-correction folded into
    scalars so the kernel is pure elementwise math. `count` is the NEW
    step number t (post-increment), `lr` may be traced (schedules)."""
    t = jnp.asarray(count, jnp.float32)
    c1 = 1.0 - jnp.asarray(b1, jnp.float32) ** t
    c2 = 1.0 - jnp.asarray(b2, jnp.float32) ** t
    sq2 = jnp.sqrt(c2)
    lr = jnp.asarray(lr, jnp.float32)
    return jnp.stack([lr * sq2 / c1, eps * sq2, lr * weight_decay])


def _adam_math(p, m, v, g, a, b, lrwd, b1: float, b2: float):
    """The shared update — used verbatim by the kernel body, the scalar
    (ndim-0) jnp path, and the segment kernel, so every path is the
    same math by construction."""
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * (g * g)
    p_new = p - a * m_new / (jnp.sqrt(v_new) + b) - lrwd * p
    return p_new, m_new, v_new


def _fused_kernel(b1, b2, s_ref, p_ref, m_ref, v_ref, g_ref,
                  p_out, m_out, v_out):
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    p_new, m_new, v_new = _adam_math(p, m_ref[...], v_ref[...], g,
                                     s_ref[0], s_ref[1], s_ref[2], b1, b2)
    p_out[...] = p_new.astype(p_out.dtype)
    m_out[...] = m_new
    v_out[...] = v_new


def leaf_cost(shape, dtype) -> Tuple[int, int]:
    """(flops, HBM bytes) of one fused update of one leaf: read g +
    read/write each of p (param dtype), m, v (f32) — the 7-pass floor
    the kernel achieves. ~12 elementwise flops + one sqrt per element."""
    import numpy as np
    n = int(np.prod(shape)) if shape else 1
    pbytes = jnp.dtype(dtype).itemsize
    return 12 * n, n * (4 + 2 * pbytes + 4 * 4)


def update_cost(params) -> Tuple[int, int]:
    """Analytic (flops, bytes) of one fused sweep over a whole tree —
    the roofline model benches and tests compare gauges against."""
    flops = bytes_ = 0
    for leaf in jax.tree_util.tree_leaves(params):
        f, b = leaf_cost(jnp.shape(leaf), leaf.dtype)
        flops += f
        bytes_ += b
    return flops, bytes_


def _leaf_update(p, m, v, g, scal, b1: float, b2: float, interpret: bool):
    """One leaf through the kernel: viewed as (rows, last-dim), blocked
    over rows. Leading-dim collapse keeps the minor dim — a free
    relayout on TPU — unlike a flat 1-D repacking of the leaves."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if p.ndim == 0:
        # scalars are un-tileable; same math, jnp (bias scales etc.)
        g32 = g.astype(jnp.float32)
        p_new, m_new, v_new = _adam_math(p.astype(jnp.float32), m, v, g32,
                                         scal[0], scal[1], scal[2], b1, b2)
        return p_new.astype(p.dtype), m_new, v_new

    shape = p.shape
    cols = shape[-1]
    rows = p.size // cols
    p2, m2, v2, g2 = (x.reshape(rows, cols) for x in (p, m, v, g))
    bm = _block_rows(rows, cols)
    flops, bytes_ = leaf_cost(shape, p.dtype)

    def bs():
        return pl.BlockSpec((bm, cols), lambda i: (i, 0))

    p_new, m_new, v_new = pl.pallas_call(
        functools.partial(_fused_kernel, b1, b2),
        grid=(pl.cdiv(rows, bm),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  bs(), bs(), bs(), bs()],
        out_specs=[bs(), bs(), bs()],
        out_shape=[jax.ShapeDtypeStruct((rows, cols), p.dtype),
                   jax.ShapeDtypeStruct((rows, cols), jnp.float32),
                   jax.ShapeDtypeStruct((rows, cols), jnp.float32)],
        # in-place: the params/moments buffers ARE the outputs — the
        # donation contract of the trainer step stays buffer reuse
        input_output_aliases={1: 0, 2: 1, 3: 2},
        cost_estimate=pl.CostEstimate(flops=flops, bytes_accessed=bytes_,
                                      transcendentals=p.size),
        interpret=interpret,
    )(scal, p2, m2, v2, g2)
    return (p_new.reshape(shape), m_new.reshape(shape),
            v_new.reshape(shape))


def fused_adam_step(params, mu, nu, grads, count, *, lr,
                    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                    weight_decay: float = 0.0,
                    interpret: Optional[bool] = None):
    """One fused Adam step over a pytree: returns (params, mu, nu) with
    every leaf updated by one kernel pass. `count` is the new step
    number (1 on the first call); `lr` may be a traced scalar."""
    interpret = _resolve_interpret(interpret)
    scal = _fold_scalars(count, lr, b1, b2, eps, weight_decay)
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_m = treedef.flatten_up_to(mu)
    flat_v = treedef.flatten_up_to(nu)
    flat_g = treedef.flatten_up_to(grads)
    out = [_leaf_update(p, m, v, g, scal, b1, b2, interpret)
           for p, m, v, g in zip(flat_p, flat_m, flat_v, flat_g)]
    return tuple(jax.tree_util.tree_unflatten(treedef, [o[i] for o in out])
                 for i in range(3))
