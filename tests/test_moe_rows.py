"""The expert layer's row kernels (`pallas/moe_rows.py`, through the Pallas
interpreter) against XLA's gathers (`keras/moe.py`, the path off the TPU),
in every use: the dispatch forward and its gradient, the combine forward
and its gradients to the rows and the weights, and the layer built on
them. The buffer's places past the held count are poisoned with NaN: the
kernels never read them. The combine's list of held rows (`moe_rows.plan`)
is made once and carried from the dispatch forward into its backward, as
the layer carries it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.moe import (MoEFeedForward, _from_sorted,
                                         _to_sorted)
from analytics_zoo_tpu.pallas import moe_rows

N, K, H, HELD, ROUTED = 144, 4, 64, 4, 16


def _routing(held_per_token):
    """A dispatch whose token n holds held_per_token[n] of its K slots
    (distinct experts of the first HELD of ROUTED): order, position, held,
    count, the layer's `_dispatch` over it."""
    rng = np.random.default_rng(7)
    experts = np.zeros((len(held_per_token), K), np.int32)
    for n, h in enumerate(held_per_token):
        experts[n, :h] = rng.permutation(HELD)[:h]
        experts[n, h:] = HELD + rng.permutation(ROUTED - HELD)[:K - h]
        experts[n] = rng.permutation(experts[n])
    layer = MoEFeedForward(H, 8, ROUTED, K, experts_held=(0, HELD))
    order, position, sizes, held = layer._dispatch(jnp.asarray(experts))
    return order, position, held, sizes.sum(dtype=jnp.int32).reshape(1)


def _held_per_token(share):
    rng = np.random.default_rng(3)
    if share == "none":
        return [0] * N
    if share == "all":
        return [K] * N
    if share == "few":          # 18 of 576 slots, about 3%
        return [1 if n % 8 == 3 else 0 for n in range(N)]
    if share == "tiles":
        # the combine's three tiles of 48 tokens: every count of held slots
        # (K among them), then a tile that holds none, then a fuller one
        return ([n % (K + 1) for n in range(48)] + [0] * 48
                + list(rng.integers(1, K + 1, 48)))
    # about half, with tokens of 0, 1 and K held slots
    return [0, 1, K] + list(rng.integers(0, K + 1, N - 3))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("share", ["none", "few", "half", "all", "tiles"])
def test_the_row_kernels_are_xlas_gathers_at_the_held_places(share, dtype):
    order, position, held, count = _routing(_held_per_token(share))
    c = int(count[0])
    assert c == int(held.sum())
    # the combine's tiles are 48 tokens, 3 of them (a grid of 4 steps: the
    # two buffers turn over, the last step only adds); the gather's are 192
    # places, 1 ("few"), 2 ("half", "tiles") or 3 ("all") of them
    assert moe_rows.plan(position, held, H, dtype).tile_held.shape == (3,)
    if share in ("few", "half", "tiles"):
        # a count that ends inside a gather tile and inside a wait's 8 rows
        assert c % 192 and c % 8
    if share == "tiles":
        assert int(moe_rows.plan(position, held, H, dtype).tile_held[1]) == 0
    rng = np.random.default_rng(11)
    f32 = jnp.float32
    dead = (jnp.arange(N * K) >= c)[:, None]

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), f32).astype(dtype)

    x, ys = draw(N, H), jnp.where(dead, jnp.nan, draw(N * K, H))
    g_xs = jnp.where(dead, jnp.nan, draw(N * K, H))
    g_out = draw(N, H)
    w = jnp.asarray(rng.uniform(0.05, 1.0, (N, K)), f32)

    def sides(kernels):
        route = None if kernels is None else moe_rows.plan(
            position, held, H, dtype)
        xs, to_vjp = jax.vjp(lambda a: _to_sorted(
            a, order, position, held, count, route, kernels), x)
        out, from_vjp = jax.vjp(lambda r, v: _from_sorted(
            r, v, order, position, held, count, route, kernels), ys, w)
        return (xs, to_vjp(g_xs)[0], out.astype(dtype),
                *from_vjp(g_out.astype(out.dtype)))

    got, want = sides(True), sides(None)
    xs, dx, out, d_ys, d_w = got
    for a in (dx, out, d_w):
        assert bool(jnp.isfinite(a.astype(f32)).all())
    assert out.dtype == dtype and dx.dtype == dtype
    # the dispatch forward and the combine's weighted rows: the same
    # values moved, bit for bit
    np.testing.assert_array_equal(xs[:c], want[0][:c])
    np.testing.assert_array_equal(d_ys[:c], want[3][:c])
    if dtype == jnp.bfloat16:
        # the same float32 sums in the same order, rounded once
        np.testing.assert_array_equal(out, want[2])
        np.testing.assert_array_equal(dx, want[1])
    else:
        np.testing.assert_allclose(out, want[2], rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(dx, want[1], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(d_w, want[4], rtol=1e-5, atol=1e-5)
    if share == "none":
        assert not np.asarray(out.astype(f32)).any()


def test_the_plan_lists_each_tiles_held_rows_rank_major():
    """`moe_rows.plan` of the three-tile routing: tile t's first
    tile_held[t] entries are its held slots' places, every token's first
    kept slot, then every token's second, ..., each bound for VMEM slot
    r x 48 + n; the rest of the tile's K x 48 entries fetch place 0 into
    the 8 spare slots; and the most slots one of 8 tokens keeps."""
    _, position, held, _ = _routing(_held_per_token("tiles"))
    route = moe_rows.plan(position, held, H, jnp.bfloat16)
    tn, pos, hd = 48, np.asarray(position), np.asarray(held)
    place = np.asarray(route.place).reshape(3, K * tn)
    slot = np.asarray(route.slot).reshape(3, K * tn)
    kept = hd.sum(axis=1)
    for t in range(3):
        want = [(pos[n][hd[n]][r], r * tn + n - t * tn) for r in range(K)
                for n in range(t * tn, (t + 1) * tn) if r < kept[n]]
        m = len(want)
        assert int(route.tile_held[t]) == m
        assert list(zip(place[t, :m].tolist(), slot[t, :m].tolist())) == [
            (int(p), s) for p, s in want]
        assert not place[t, m:].any()
        np.testing.assert_array_equal(
            slot[t, m:], K * tn + np.arange(m, K * tn) % 8)
    np.testing.assert_array_equal(route.most,
                                  kept.reshape(-1, 8).max(axis=1))


def test_the_kernels_tile_the_cells_and_refuse_what_they_cannot():
    assert moe_rows.fits(16384, 2304, jnp.bfloat16, True)
    assert moe_rows.fits(16384, 2048, jnp.float32, True)
    assert not moe_rows.fits(16384, 2048, jnp.bfloat16, None)  # off the TPU
    assert not moe_rows.fits(16392 + 1, 2048, jnp.bfloat16, True)
    for M in (16384 * 8, 16384 * 6, 16384 * 4):
        assert moe_rows._tile(M, moe_rows._TILE_ROWS) == 256
    assert moe_rows._tile(16384, moe_rows._TILE_TOKENS) == 128
    # the pipelined kernels' tiles at the cells' widths, halved where two
    # steps' rows would not fit beside the rest in VMEM
    for k, width in ((6, 2560), (4, 2048), (6, 2048), (8, 2304)):
        assert moe_rows._combine_tile(16384, k, width, jnp.bfloat16) == 128
        assert moe_rows._gather_tile(16384 * k, width, jnp.bfloat16,
                                     True) == 256
    assert moe_rows._combine_tile(16384, 6, 2560, jnp.float32) == 64
    assert moe_rows._gather_tile(16384 * 6, 2560, jnp.float32, True) == 128
    assert moe_rows._gather_tile(16384 * 6, 2560, jnp.float32, False) == 256
    # the words hold the rows' own bytes, the rows past the count unwritten
    x = jnp.asarray(np.random.default_rng(0).normal(size=(48, 256)),
                    jnp.bfloat16)
    words = moe_rows.pack(x, jnp.asarray([40], jnp.int32), "moe_rows_pack",
                          interpret=True)
    assert words.shape == (48, 1, 128) and words.dtype == jnp.uint32
    np.testing.assert_array_equal(
        moe_rows._unpack(words[:40, 0], True).astype(jnp.bfloat16), x[:40])


def _layer(held, **kw):
    return MoEFeedForward(64, 32, 16, 3, experts_held=held, shared_width=64,
                          routed_scaling_factor=2.448, **kw)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_layer_with_the_row_kernels_is_the_layer_without(dtype):
    """`tests/test_moe_decoder.py`'s sizes, experts 4-8 of 16 held: outputs
    and every parameter's gradient the same with the kernels (interpreted,
    grouped products too) as with XLA's gathers and the ragged product."""
    whole = _layer((0, 16)).build(jax.random.PRNGKey(0))
    params = dict(whole, experts={k: v[4:8]
                                  for k, v in whole["experts"].items()})
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    u = jnp.asarray(np.random.default_rng(3).normal(size=(2, 32, 64)), dtype)
    cot = jnp.asarray(np.random.default_rng(4).normal(size=(2, 32, 64)),
                      jnp.float32)
    plain, kernels = _layer((4, 8)), _layer((4, 8), interpret=True)
    assert kernels.row_kernels and not plain.row_kernels
    assert not _layer((0, 16), interpret=True).row_kernels

    def loss(layer):
        return jax.value_and_grad(lambda p, a: jnp.sum(
            layer.call(p, a).astype(jnp.float32) * cot), argnums=(0, 1))(
                params, u)

    (v_got, g_got), (v_want, g_want) = loss(kernels), loss(plain)
    tol = 0 if dtype == jnp.bfloat16 else 2e-5
    assert abs(float(v_got) - float(v_want)) <= tol * abs(float(v_want))
    flat_got = jax.tree_util.tree_leaves_with_path(g_got)
    flat_want = jax.tree_util.tree_leaves(g_want)
    assert len(flat_got) == len(flat_want) == 9
    for (path, a), b in zip(flat_got, flat_want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * (np.abs(b).max() + 1e-30), \
            jax.tree_util.keystr(path)
