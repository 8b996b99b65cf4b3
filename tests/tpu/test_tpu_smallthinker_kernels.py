"""On-chip specs of the sliding-window flash kernels SmallThinker brought,
at the cell's shape: 28 query heads on 4 K/V heads of 128, 16,384 tokens,
a window of 4096, bfloat16. Forward and the three gradients against a
float32 reference under the band mask (computed a K/V head and 2048
queries at a time, so that it fits), and their time against the causal
grouped-query kernels on the same operands: the band holds 43.75% of the
causal triangle's pairs, and a kernel that skips the tiles left of it
takes about that share of the causal kernels' time."""

import time

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.tpu

B, H, KV, T, D, W = 1, 28, 4, 16384, 128, 4096
_QUERIES = 2048


def _operands():
    ks = jax.random.split(jax.random.PRNGKey(38), 4)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, KV, T, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, KV, T, D), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, H, T, D), jnp.bfloat16)
    return q, k, v, do


def _banded_reference(q, k, v):
    """float32 attention over i - W < j <= i, a K/V head's group of query
    heads and 2048 queries at a time, each block a checkpoint."""
    group = H // KV
    cols = jnp.arange(T)

    def one_group(args):
        qg, kh, vh = args                       # [group, T, D], [T, D] x 2

        @jax.checkpoint
        def some_queries(block):
            first, qb = block                   # [group, Tq, D]
            s = jnp.einsum("gqd,kd->gqk", qb, kh) / jnp.sqrt(float(D))
            rows = first + jnp.arange(_QUERIES)
            seen = (cols[None, :] <= rows[:, None]) \
                & (cols[None, :] > rows[:, None] - W)
            s = jnp.where(seen, s, -jnp.inf)
            return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(s, axis=-1), vh)

        blocks = qg.reshape(group, T // _QUERIES, _QUERIES, D) \
            .transpose(1, 0, 2, 3)
        out = jax.lax.map(some_queries,
                          (jnp.arange(0, T, _QUERIES), blocks))
        return out.transpose(1, 0, 2, 3).reshape(group, T, D)

    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(one_group, (
            q[0].astype(f32).reshape(KV, group, T, D), k[0].astype(f32),
            v[0].astype(f32)))
    return out.reshape(B, H, T, D)


def _sides(fn):
    def run(q, k, v, do):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(do.astype(out.dtype)))
    return jax.jit(run)


def _rel(a, b):
    f32 = jnp.float32
    return float(jnp.linalg.norm(a.astype(f32) - b.astype(f32))
                 / jnp.linalg.norm(b.astype(f32)))


def test_window_kernels_match_the_float32_band():
    from analytics_zoo_tpu.pallas.flash_attention import flash_attention
    q, k, v, do = _operands()
    got = _sides(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=W))(q, k, v, do)
    want = _sides(_banded_reference)(q, k, v, do)
    rel = [_rel(a, b) for a, b in zip(got, want)]
    print(f"window_flash_onchip rel_err out,dq,dk,dv={rel}")
    assert got[2].shape == k.shape and got[3].dtype == jnp.bfloat16
    # bfloat16 operands and outputs, float32 statistics: the causal gqa
    # kernels' bound
    assert max(rel) < 2e-2


def _seconds(fn, args, reps=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def test_window_kernels_skip_the_tiles_left_of_the_band():
    """Forward + backward of the window kernels against the causal `_gqa`
    kernels on the same operands: at most 55% of their time (the band is
    43.75% of the triangle's pairs; masked tiles would cost the whole
    triangle)."""
    from analytics_zoo_tpu.pallas.flash_attention import flash_attention
    args = _operands()
    window = _seconds(_sides(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=W)), args)
    causal = _seconds(_sides(lambda q, k, v: flash_attention(
        q, k, v, causal=True)), args)
    fwd_w = _seconds(jax.jit(lambda q, k, v, do: flash_attention(
        q, k, v, causal=True, window=W)), args)
    fwd_c = _seconds(jax.jit(lambda q, k, v, do: flash_attention(
        q, k, v, causal=True)), args)
    print(f"window_vs_causal_onchip fwd_bwd_ms window={window * 1e3:.3f} "
          f"causal={causal * 1e3:.3f} ratio={window / causal:.4f} "
          f"fwd_ms window={fwd_w * 1e3:.3f} causal={fwd_c * 1e3:.3f} "
          f"ratio={fwd_w / fwd_c:.4f}")
    assert window <= 0.55 * causal
