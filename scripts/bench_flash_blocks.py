"""A/B flash-attention fwd+bwd at a given tile shape on the real chip; the
backward runs at the forward's tiling, as one kernel where that fits VMEM
(scripts/bench_flash_decomp.py times both forms).

    python scripts/bench_flash_blocks.py <block_q> <block_k> [rate]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

if jax.default_backend() == "tpu":
    jax.config.update("jax_default_prng_impl", "rbg")

from analytics_zoo_tpu.pallas.flash_attention import flash_attention


def main():
    bq, bk = int(sys.argv[1]), int(sys.argv[2])
    rate = float(sys.argv[3]) if len(sys.argv) > 3 else 0.1
    B, H, T, D = 16, 12, 2048, 64
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, H, T, D), jnp.bfloat16)
    k = jnp.asarray(rs.randn(B, H, T, D), jnp.bfloat16)
    v = jnp.asarray(rs.randn(B, H, T, D), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, dropout_rate=rate,
                            dropout_seed=7, block_q=bq, block_k=bk)
        return jnp.sum(o.astype(jnp.float32))

    iters = 10

    def step(i, carry):
        acc, = carry
        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(
            q + (acc * 1e-20).astype(q.dtype), k, v)
        # consume ALL grads: with gq alone, XLA dead-code-eliminates the
        # separate dk/dv pallas_call where the backward is the two-kernel
        # form, and it times only its dq half (docs/ROOFLINE.md round 5)
        return (acc + jnp.sum(gq.astype(jnp.float32))
                + jnp.sum(gk.astype(jnp.float32))
                + jnp.sum(gv.astype(jnp.float32)),)

    run = jax.jit(
        lambda: jax.lax.fori_loop(0, iters, step, (jnp.float32(0),))[0])
    float(run())
    best = float("inf")
    for _ in range(4):
        t0 = time.perf_counter()
        float(run())
        best = min(best, time.perf_counter() - t0)
    print(f"RESULT blocks {bq}x{bk} rate {rate}: "
          f"{best / iters * 1e3:.2f} ms per fwd+bwd")


if __name__ == "__main__":
    main()
