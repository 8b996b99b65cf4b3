"""Sliding windows: the causal flash kernels with `window` (interpret mode)
against `_reference_attention` under the band mask, forward and both
backward forms, with equal and grouped heads; the band's step and pair
counts; the names, operands and cost of the TPU lowering at the
SmallThinker cell's shapes; and the layers around the window: the
grouped-query attention's options, the softmax router and the router that
reads the block's input, before attention."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.pallas import flash_attention as fa
from analytics_zoo_tpu.pallas.flash_attention import (_reference_attention,
                                                      flash_attention)


def _qkv(H, group, T, D, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, H, T, D)) * 0.5
    k = jax.random.normal(ks[1], (1, H // group, T, D)) * 0.5
    v = jax.random.normal(ks[2], (1, H // group, T, D))
    do = jax.random.normal(ks[3], (1, H, T, D))
    return q, k, v, do


@functools.partial(jax.jit, static_argnums=0)
def _sides(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out, *vjp(do))


@pytest.mark.parametrize("H,group,T,window,fused", [
    # equal heads, T no multiple of the window, a window under one tile;
    # the dq / dkv pair
    (2, 1, 256, 96, False),
    # seven query heads on one K/V head, T padded to the tiles, a window
    # of no whole tiles; the one-kernel backward
    (7, 7, 320, 200, True),
])
def test_window_kernels_match_the_band_masked_reference(monkeypatch, H,
                                                        group, T, window,
                                                        fused):
    if not fused:
        monkeypatch.setattr(fa, "_bwd_fused_fits", lambda *a, **k: False)
    q, k, v, do = _qkv(H, group, T, 32, seed=T + window)
    got = _sides(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=True, block_q=128,
        block_k=128), q, k, v, do)
    want = _sides(lambda q, k, v: _reference_attention(
        q, k, v, causal=True, window=window), q, k, v, do)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-4)
    # the band is narrower than the causal triangle: it is what moved them
    causal = _reference_attention(q, k, v, causal=True)
    assert float(jnp.abs(causal - want[0]).max()) > 1e-2


def test_a_window_as_long_as_the_sequence_is_causal():
    q, k, v, do = _qkv(1, 1, 256, 32, seed=3)

    def run(**kw):
        return _sides(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True, block_q=128, block_k=128,
            **kw), q, k, v, do)
    for a, b in zip(run(window=256), run()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    band = _reference_attention(q, k, v, causal=True, window=1000)
    np.testing.assert_array_equal(
        np.asarray(band), np.asarray(_reference_attention(q, k, v,
                                                          causal=True)))


def test_a_window_needs_the_causal_flag():
    q, k, v, _ = _qkv(2, 1, 128, 16, seed=0)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=64, interpret=True)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0, interpret=True)


def test_the_band_walks_its_tiles_alone():
    """At 16,384 tokens, a window of 4096 and 1024 tiles a q-block needs 5
    k-blocks of 16 and a k-block 5 q-blocks; the band holds 43.75% of the
    causal triangle's pairs."""
    assert fa._band_steps(4096, 1024, 1024, 16, 16, True) == 5
    assert fa._band_steps(4096, 1024, 1024, 16, 16, False) == 5
    assert fa._band_steps(None, 1024, 1024, 16, 16, True) == 16
    # the window's edge inside a tile: one more block
    assert fa._band_steps(100, 128, 128, 4, 4, True) == 2
    assert fa._band_steps(1000, 128, 128, 4, 4, False) == 4
    pairs = fa._band_pairs(16384, 4096)
    assert pairs == 4096 * 4097 // 2 + 12288 * 4096 == 58_722_304
    assert 0.4374 < pairs / (16384 * 16385 / 2) < 0.4376
    assert fa._band_pairs(100, 4096) == 100 * 101 // 2
    assert fa._kernel_name("flash_fwd", True, False, True, 4096) \
        == "flash_fwd_causal_gqa_window"


@pytest.mark.parametrize("dtype,backward", [
    (jnp.bfloat16, ["flash_bwd_fused_causal_gqa_window"]),
    # float32 at 1024 tiles passes the default scoped VMEM at one tile:
    # the dq / dkv pair
    (jnp.float32, ["flash_dq_causal_gqa_window",
                   "flash_dkv_causal_gqa_window"])])
def test_cell_shapes_lower_to_window_kernels_reading_kv_grouped(
        monkeypatch, dtype, backward):
    """28 query heads on 4 K/V heads of 128 at 16,384 tokens, window 4096:
    the kernels carry `_window` last, read K and V as [4, T, 128] and
    count the band's pairs."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = jax.ShapeDtypeStruct
    q = sds((1, 28, 16384, 128), dtype)
    kv = sds((1, 4, 16384, 128), dtype)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=4096).astype(
            jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)).as_text()
    names = re.findall(r"(flash_[a-z_]+_window)", text)
    assert sorted(set(names)) == sorted(["flash_fwd_causal_gqa_window"]
                                        + backward)
    calls = re.findall(r"tpu_custom_call.*?: \((tensor<[^)]*)\)", text)
    assert len(calls) == 1 + len(backward)
    for operands in calls:
        types = re.findall(r"tensor<([0-9x]+)x(bf16|f32|i32)>", operands)
        assert types[1][0] == types[2][0] == "4x16384x128", types
    flops = [int(f) for f in re.findall(r"flops\\22:(\d+)", text)]
    # QK^T + PV forward; scores, dQ, dK and dW, dV backward (the pair
    # computes the scores and dW in both of its kernels)
    per = 2 * 28 * 58_722_304 * 128
    assert flops == ([2 * per, 5 * per] if len(backward) == 1
                     else [2 * per, 3 * per, 4 * per])


def test_grouped_attention_options_and_their_defaults():
    """The defaults build LFM2's layer (q/k norm weights, rotary); with
    qk_norm off no norm weights exist; a NoPE layer ignores the rotary
    tables; a window layer is the band-masked attention of its
    projections."""
    from analytics_zoo_tpu.keras.grouped_attention import \
        GroupedQueryAttention
    from analytics_zoo_tpu.keras.transformer import (apply_rotary,
                                                     rotary_tables)
    H, n, kv, d, T = 64, 4, 2, 16, 48
    default = GroupedQueryAttention(H, n, kv, d, init="normal", name="g0")
    assert set(default.build(jax.random.PRNGKey(0))) == {
        "q_kernel", "k_kernel", "v_kernel", "q_norm", "k_norm",
        "out_kernel"}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T, H))
    tables = rotary_tables(T, d, 1.5e6)
    nope = GroupedQueryAttention(H, n, kv, d, init="normal", qk_norm=False,
                                 rotary=False, name="g1")
    p = nope.build(jax.random.PRNGKey(0))
    assert set(p) == {"q_kernel", "k_kernel", "v_kernel", "out_kernel"}
    moved = (tables[0] * 3.0, tables[1] * 0.5)
    call = jax.jit(lambda layer, p, t: layer.call(p, [x, t]),
                   static_argnums=0)
    np.testing.assert_array_equal(np.asarray(call(nope, p, tables)),
                                  np.asarray(call(nope, p, moved)))
    window = GroupedQueryAttention(H, n, kv, d, init="normal", qk_norm=False,
                                   window=8, name="g2")

    def heads(a):
        return a.reshape(2, T, -1, d).transpose(0, 2, 1, 3)
    q, k, v = (heads(x @ p[name]) for name in ("q_kernel", "k_kernel",
                                               "v_kernel"))
    cos, sin = tables
    ctx = _reference_attention(apply_rotary(q, cos, sin),
                               apply_rotary(k, cos, sin), v, causal=True,
                               window=8)
    want = ctx.transpose(0, 2, 1, 3).reshape(2, T, -1) @ p["out_kernel"]
    np.testing.assert_allclose(np.asarray(call(window, p, tables)),
                               np.asarray(want), rtol=1e-5, atol=1e-6)


def test_the_softmax_router_is_a_plain_softmax_top_k_renormalised():
    from analytics_zoo_tpu.keras.moe import route
    u = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    kern = jax.random.normal(jax.random.PRNGKey(1), (16, 64)) * 0.3
    experts, w = route(u, kern, None, 6, 1.0, score="softmax")
    logits = np.asarray(u, np.float64) @ np.asarray(kern, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=-1)[:, :6]
    assert np.array_equal(np.sort(np.asarray(experts), -1), np.sort(top, -1))
    chosen = np.take_along_axis(p, np.asarray(experts), -1)
    np.testing.assert_allclose(np.asarray(w),
                               chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    with pytest.raises(ValueError, match="bias"):
        route(u, kern, jnp.zeros(64), 6, 1.0, score="softmax")
    with pytest.raises(ValueError, match="bias"):
        route(u, kern, None, 6, 1.0)


def test_the_router_before_attention_reads_the_blocks_input():
    """Perturbing the FFN norm's weight moves the block's output but never
    the choice, which reads RMSNorm_1(h); the experts' input is still
    RMSNorm_2(h')."""
    from analytics_zoo_tpu.keras.grouped_attention import \
        GroupedQueryAttention
    from analytics_zoo_tpu.keras.moe import MoEFeedForward
    from analytics_zoo_tpu.keras.transformer import (PreNormDecoderBlock,
                                                     rotary_tables)
    H, T = 32, 24
    moe = MoEFeedForward(H, 16, 8, 2, experts_held=(0, 4), init="normal",
                         hidden_act="relu", router_score="softmax",
                         name="pre_moe")
    attn = GroupedQueryAttention(H, 2, 1, 16, init="normal", qk_norm=False,
                                 name="pre_attn")
    block = PreNormDecoderBlock(attn, moe, route_before_attention=True,
                                name="pre_block")
    p = block.build(jax.random.PRNGKey(0), (None, T, H))
    assert set(p["ffn"]["router"]) == {"kernel"}          # no bias
    h = jax.random.normal(jax.random.PRNGKey(1), (2, T, H))
    rot = rotary_tables(T, 16, 1e4)
    seen = []
    routing = moe.routing

    def told(params, u):
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), u)
        return routing(params, u)
    moe.routing = told
    call = jax.jit(lambda p: block.call(p, [h, rot]))
    out = call(p)
    moved = call(dict(p, ffn_norm={"gamma": p["ffn_norm"]["gamma"] * 1.7}))
    jax.effects_barrier()
    assert len(seen) == 2
    np.testing.assert_array_equal(np.asarray(seen[0]), np.asarray(seen[1]))
    np.testing.assert_allclose(
        np.asarray(seen[0]), np.asarray(block.norm.call(p["attn_norm"], h)),
        rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(out - moved).max()) > 1e-4
