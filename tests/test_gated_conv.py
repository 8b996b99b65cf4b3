"""LFM2's gated short-convolution mixer: the kernel pair
`gated_conv_fwd` / `gated_conv_bwd` (`pallas/gated_conv.py`, in interpret
mode) against XLA's `gated_filter` and `jax.vjp` of it, the layer against
its equations written out, and both kernel pairs that share the filter
core cross-lowered for the TPU under their own names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.gated_conv import GatedShortConv, gated_filter
from analytics_zoo_tpu.pallas import gated_conv as gc


def _operands(B=2, T=64, C=256, K=3, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcx = jax.random.normal(ks[0], (B, T, 3 * C)).astype(dtype)
    taps = jax.random.uniform(ks[1], (K, C), minval=-0.6, maxval=0.6)
    dy = jax.random.normal(ks[2], (B, T, C)).astype(dtype)
    return bcx, taps, dy


def _both(fn, bcx, taps, dy):
    y, vjp = jax.vjp(fn, bcx, taps)
    return (y, *vjp(dy))


@pytest.mark.parametrize("tile", [16, 32, 64])
def test_kernels_match_xla_and_its_vjp_across_tiles(tile):
    """Tiles of 16 and 32 tokens put the two rows before a tile in the
    halo block; a tile of 64 has one tile a sequence: the history is the
    zeros before token 0. Two sequences: the batch never leaks."""
    bcx, taps, dy = _operands()
    got = _both(lambda a, t: gc.gated_conv(a, t, interpret=True, tile=tile),
                bcx, taps, dy)
    want = _both(gated_filter, bcx, taps, dy)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-5)


def test_token_zero_and_the_gates_as_the_equations_write_them():
    bcx, taps, _ = _operands(B=1, T=32, C=128)
    y = np.asarray(gc.gated_conv(bcx, taps, interpret=True, tile=16))
    b, c, x = (np.asarray(p) for p in np.split(np.asarray(bcx), 3, axis=-1))
    w = np.asarray(taps)
    v = b * x
    # token 0 sees itself alone (the last tap), token 1 itself and token 0
    np.testing.assert_allclose(y[0, 0], c[0, 0] * w[2] * v[0, 0], rtol=1e-5)
    np.testing.assert_allclose(
        y[0, 1], c[0, 1] * (w[2] * v[0, 1] + w[1] * v[0, 0]), rtol=1e-5)
    # token 17, the second row of the second tile: its history crosses
    np.testing.assert_allclose(
        y[0, 17], c[0, 17] * (w[0] * v[0, 15] + w[1] * v[0, 16]
                              + w[2] * v[0, 17]), rtol=1e-5, atol=1e-6)


def test_bfloat16_operands_filter_in_float32():
    bcx, taps, dy = _operands(dtype=jnp.bfloat16, seed=3)
    got = _both(lambda a, t: gc.gated_conv(a, t, interpret=True), bcx, taps,
                dy)
    want = _both(gated_filter, bcx.astype(jnp.float32), taps,
                 dy.astype(jnp.float32))
    assert got[0].dtype == got[1].dtype == jnp.bfloat16
    assert got[2].dtype == jnp.float32
    for a, b in zip(got, want):
        rel = float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                    / jnp.linalg.norm(b))
        # one rounding of a result to bfloat16; the taps summed in float32
        assert rel < 2.0 ** -8


def test_fits_follows_the_shape():
    assert gc.gated_conv_fits((1, 16384, 6144), 3, True)
    assert not gc.gated_conv_fits((1, 16384, 6144), 3, None)     # the CPU
    assert not gc.gated_conv_fits((1, 16384, 3 * 100), 3, True)
    assert not gc.gated_conv_fits((1, 40, 6144), 3, True)
    assert not gc.gated_conv_fits((1, 64, 6144), 10, True)


def test_layer_is_its_equations_on_both_paths():
    """In-projection split B, C, x in that order, the filter over B * x,
    C times it, the out-projection; the kernels (interpret) and XLA give
    the same output and gradients."""
    H, T = 128, 32
    layer = GatedShortConv(H, 3, init="normal", name="conv_eq")
    params = layer.build(jax.random.PRNGKey(1))
    assert params["in_kernel"].shape == (H, 3 * H)
    assert params["conv"].shape == (3, H)
    assert float(jnp.abs(params["conv"]).max()) <= 3 ** -0.5
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T, H))
    bcx = np.asarray(x @ params["in_kernel"])
    b, c, xx = bcx[..., :H], bcx[..., H:2 * H], bcx[..., 2 * H:]
    v = np.pad(b * xx, ((0, 0), (2, 0), (0, 0)))
    w = np.asarray(params["conv"])
    y = c * (w[0] * v[:, :T] + w[1] * v[:, 1:T + 1] + w[2] * v[:, 2:])
    want = y @ np.asarray(params["out_kernel"])
    np.testing.assert_allclose(np.asarray(layer.call(params, x)), want,
                               rtol=2e-4, atol=2e-5)
    kernels = GatedShortConv(H, 3, init="normal", interpret=True,
                             name="conv_eq_k")

    def loss(layer, p):
        return jnp.sum(jnp.sin(layer.call(p, x)))
    g_xla = jax.grad(lambda p: loss(layer, p))(params)
    g_k = jax.grad(lambda p: loss(kernels, p))(params)
    for name in params:
        np.testing.assert_allclose(np.asarray(g_k[name]),
                                   np.asarray(g_xla[name]), rtol=1e-4,
                                   atol=1e-5)


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_both_kernel_pairs_lower_for_the_tpu_under_their_names(tpu_backend,
                                                               dtype):
    """The LFM2 cell's shape, [1, 16384, 6144], lowers as gated_conv_fwd
    and gated_conv_bwd; the KDA layer's kernels, which share the filter
    core, lower as qkv_short_conv_* still."""
    from analytics_zoo_tpu.pallas import short_conv as sc
    sds = jax.ShapeDtypeStruct
    bcx, taps = sds((1, 16384, 6144), dtype), sds((3, 2048), jnp.float32)

    def lowered(fn, *args):
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    text = lowered(lambda a, t: jax.vjp(gc.gated_conv, a, t)[1](
        gc.gated_conv(a, t)), bcx, taps)
    assert text.count("gated_conv_fwd") >= 1
    assert "gated_conv_bwd" in text
    assert "qkv_short_conv" not in text and "kda_" not in text
    x, t4 = sds((1, 1024, 512), dtype), sds((4, 512), jnp.float32)
    text = lowered(lambda a, t: jax.grad(lambda a, t: sc.short_conv_rows(
        a, t, 4, 1.0, 1e-6).astype(jnp.float32).sum(), (0, 1))(a, t), x, t4)
    assert "qkv_short_conv_bwd" in text and "gated_conv" not in text


@pytest.mark.parametrize("scale,digests", [
    (None, ["e11594a594e0690e", "a34cd334f7035122"]),
    (128 ** -0.5, ["86b6bf53573a07c1", "7b1abb14446a9657"])])
def test_the_kda_kernels_are_pr_35s_instruction_for_instruction(
        tpu_backend, scale, digests):
    """The filter core moved into functions that `gated_conv_*` shares,
    with the taps count K static: at the KDA cell's shape (one of q, k, v
    [1, 16384, 4096], 32 heads of 128, K = 4) `qkv_short_conv_bwd` and
    `qkv_short_conv_fwd` lower to the modules of PR 35's tree (commit
    f6fe9f3; made by `_mosaic_digests` on it)."""
    from test_pallas_tpu_lowering import _mosaic_digests

    from analytics_zoo_tpu.pallas import short_conv as sc
    x = jax.ShapeDtypeStruct((1, 16384, 4096), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((4, 4096), jnp.float32)

    def rows(a, t):
        return sc.short_conv_rows(a, t, 32, scale, 1e-6)
    grad = jax.grad(lambda a, t: rows(a, t).astype(jnp.float32).sum(),
                    (0, 1))
    assert _mosaic_digests(grad, x, taps) + _mosaic_digests(rows, x, taps) \
        == digests
