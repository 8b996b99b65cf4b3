"""On-chip specs of the expert layer's row kernels (`pallas/moe_rows.py`):
`moe_rows_gather`, `moe_rows_combine` and their two packing kernels at the
four expert cells' widths, compiled by the chip's compiler and held to
XLA's gathers (`keras/moe.py`) on the same values, with the buffer's places
past the held count poisoned by NaN: nothing there may be read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _routing(N, k, n_held, n_routed, seed, lean=1.0):
    """A drop-free dispatch of N tokens choosing k of n_routed experts
    each, the first n_held held: (order, position, held, count). `lean` > 1
    draws the held experts' scores that many times larger, so more slots
    are held than n_held / n_routed."""
    rng = np.random.default_rng(seed)
    scores = rng.random((N, n_routed), np.float32)
    scores[:, :n_held] *= lean
    experts = np.argsort(-scores, axis=1)[:, :k]
    held = jnp.asarray(experts < n_held)
    key = jnp.where(held, jnp.asarray(experts, jnp.int32), n_held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    position = jnp.argsort(order).astype(jnp.int32).reshape(N, k)
    return order, position, held, held.sum(dtype=jnp.int32).reshape(1)


class TestRowKernelsOnChip:
    """(N, k, H, held of routed) of kimi-linear-48b-a3b, kanana-2-30b-a3b,
    lfm2-8b-a1b (at a quarter held, and at half, where its routers drift)
    and smallthinker-21b-a3b: the dispatch and combine forward bit for bit,
    the combine's backward (the weighted rows, the weights' gradient) to
    float32 rounding, all finite with NaN past the count."""

    @pytest.mark.parametrize("N,k,H,n_held,n_routed,lean", [
        (16384, 8, 2304, 8, 256, 1.0),
        (16384, 6, 2048, 16, 128, 1.0),
        (16384, 4, 2048, 8, 32, 1.0),
        (16384, 4, 2048, 8, 32, 1.2),
        (16384, 6, 2560, 16, 64, 1.0),
    ])
    def test_kernels_match_xla_and_read_no_place_past_the_count(
            self, N, k, H, n_held, n_routed, lean):
        from analytics_zoo_tpu.keras.moe import _gather_sum
        from analytics_zoo_tpu.pallas import moe_rows
        assert moe_rows.fits(N, H, jnp.bfloat16, None)
        order, position, held, count = _routing(N, k, n_held, n_routed,
                                                N + k, lean)
        c = int(count[0])
        assert 0 < c < N * k
        if lean > 1:
            assert c > N * k * 0.45  # about half held
        ks = jax.random.split(jax.random.PRNGKey(k), 4)
        bf, f32 = jnp.bfloat16, jnp.float32
        x = jax.random.normal(ks[0], (N, H)).astype(bf)
        g = jax.random.normal(ks[1], (N, H)).astype(bf)
        w = jax.random.uniform(ks[2], (N, k), minval=0.05, maxval=1.0)
        dead = (jnp.arange(N * k) >= c)[:, None]
        ys = jnp.where(dead, jnp.nan, jax.random.normal(ks[3], (N * k, H))
                       ).astype(bf)
        src = order // k

        @jax.jit
        def kernels(x, g, ys, w):
            xs = moe_rows.gather(x, src, count)
            route = moe_rows.plan(position, held, H, bf)
            out = moe_rows.combine(ys, count, route, w)
            dx = moe_rows.combine(ys, count, route)
            w_sorted = w.reshape(-1)[order].astype(bf).astype(f32)
            d_ys, along = moe_rows.gather(g, src, count, scale=w_sorted,
                                          dot_with=ys)
            d_w = jnp.where(held, along[position], 0.0)
            return xs, out, dx, d_ys, d_w

        @jax.jit
        def xla(x, g, ys, w):
            rows = g[src]
            w_sorted = w.reshape(-1)[order].astype(bf)
            along = jnp.sum(ys.astype(f32) * rows.astype(f32), axis=1)
            return (x[src], _gather_sum(ys, position, held, w).astype(bf),
                    _gather_sum(ys, position, held).astype(bf),
                    rows * w_sorted[:, None],
                    jnp.where(held, along[position], 0.0))

        got = kernels(x, g, ys, w)
        want = xla(x, g, ys, w)
        for a in (got[1], got[2], got[4]):
            assert bool(jnp.isfinite(a.astype(f32)).all())
        assert bool(jnp.isfinite(got[3][:c].astype(f32)).all())
        assert bool((got[0][:c] == want[0][:c]).all())
        assert bool((got[3][:c] == want[3][:c]).all())
        for a, b in ((got[1], want[1]), (got[2], want[2])):
            diff = jnp.abs(a.astype(f32) - b.astype(f32))
            # one float32 sum in the same order, rounded once: at most a
            # last bit of bfloat16 apart
            assert float(jnp.max(diff - 2 ** -7 * jnp.abs(b.astype(f32)))
                         ) <= 0.0
        np.testing.assert_allclose(got[4], want[4], rtol=1e-5, atol=1e-4)
