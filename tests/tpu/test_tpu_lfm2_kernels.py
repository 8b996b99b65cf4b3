"""On-chip specs of the two kernel families LFM2 brought: the gated filter
pair `gated_conv_fwd` / `gated_conv_bwd` at the cell's shape, and the
grouped-query causal flash kernels at 32 query heads on 8 K/V heads,
each compiled by the chip's compiler and held to XLA on the same
values."""

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.tpu


def _rel(a, b):
    f32 = jnp.float32
    return float(jnp.linalg.norm(a.astype(f32) - b.astype(f32))
                 / jnp.linalg.norm(b.astype(f32)))


class TestGatedConvOnChip:
    """[1, 16384, 6144] (the in-projection's [B | C | x]) -> [1, 16384,
    2048], output and both gradients against `gated_filter` in float32."""

    def _sides(self, dtype, T=16384, C=2048):
        from analytics_zoo_tpu.keras.gated_conv import gated_filter
        from analytics_zoo_tpu.pallas import gated_conv as gc
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        bcx = jax.random.normal(ks[0], (1, T, 3 * C)).astype(dtype)
        taps = jax.random.uniform(ks[1], (3, C), minval=-0.57, maxval=0.57)
        dy = jax.random.normal(ks[2], (1, T, C)).astype(dtype)
        assert gc.gated_conv_fits(bcx.shape, 3, None)

        def both(fn):
            def run(a, t, d):
                y, vjp = jax.vjp(fn, a, t)
                return (y, *vjp(d))
            return jax.jit(run)
        f32 = jnp.float32
        got = both(gc.gated_conv)(bcx, taps, dy)
        want = both(gated_filter)(bcx.astype(f32), taps, dy.astype(f32))
        rel = [_rel(a, b) for a, b in zip(got, want)]
        print(f"gated_conv_onchip {jnp.dtype(dtype).name} "
              f"rel_err y,d_bcx,d_taps={rel}")
        return rel

    def test_seq16384_bfloat16_output_and_both_gradients(self):
        y, d_bcx, d_taps = self._sides(jnp.bfloat16)
        # one rounding of a result to bfloat16: 2^-9 an element
        assert y < 2.0 ** -8 and d_bcx < 2.0 ** -8
        assert d_taps < 1e-3                  # summed in float32

    def test_float32_as_the_forward_check_runs_it(self):
        assert max(self._sides(jnp.float32, T=4096)) < 1e-5


class TestGroupedQueryFlashOnChip:
    """32 query heads on 8 K/V heads of 64 at 4096 tokens, bfloat16: the
    `_gqa` kernels against the exact path with K/V repeated."""

    def test_forward_and_grads_match_the_repeated_reference(self):
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention, flash_attention)
        B, H, kv, T, D = 1, 32, 8, 4096, 64
        ks = jax.random.split(jax.random.PRNGKey(8), 4)
        q = jax.random.normal(ks[0], (B, H, T, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, kv, T, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, kv, T, D), jnp.bfloat16)
        do = jax.random.normal(ks[3], (B, H, T, D), jnp.bfloat16)

        def sides(fn):
            def run(q, k, v, do):
                out, vjp = jax.vjp(fn, q, k, v)
                return (out, *vjp(do.astype(out.dtype)))
            return jax.jit(run)(q, k, v, do)
        got = sides(lambda q, k, v: flash_attention(q, k, v, causal=True))
        f32 = jnp.float32
        want = sides(lambda q, k, v: _reference_attention(
            q.astype(f32), jnp.repeat(k, H // kv, 1).astype(f32),
            jnp.repeat(v, H // kv, 1).astype(f32), causal=True))
        rel = [_rel(a, b) for a, b in zip(got, want)]
        print(f"gqa_flash_onchip rel_err out,dq,dk,dv={rel}")
        assert got[2].shape == k.shape and got[3].dtype == jnp.bfloat16
        assert max(rel) < 2e-2

    def test_compiled_step_names_the_gqa_kernels(self):
        from analytics_zoo_tpu.pallas.flash_attention import flash_attention
        q = jnp.zeros((1, 32, 2048, 64), jnp.bfloat16)
        kv = jnp.zeros((1, 8, 2048, 64), jnp.bfloat16)

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile().as_text()
        assert "flash_fwd_causal_gqa" in text
        assert "flash_bwd_fused_causal_gqa" in text
