"""Grouped-query heads: the causal flash kernels with K/V heads a whole
fraction of the query heads (interpret mode) against
`_reference_attention` with K/V repeated, forward and both backward forms;
their names and operands in the TPU lowering at the LFM2 cell's shapes;
the attention layer with per-head q/k norms against its equations; and
`MoEDecoderLM` with conv and grouped-query mixers and a tied head. At
group 1 the kernels lower to the modules `tests/test_pallas_tpu_lowering.
py` pins, instruction for instruction."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.pallas import flash_attention as fa
from analytics_zoo_tpu.pallas.flash_attention import (_reference_attention,
                                                      flash_attention)


def _qkv(B, H, group, T, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, H, T, D)) * 0.5
    k = jax.random.normal(ks[1], (B, H // group, T, D)) * 0.5
    v = jax.random.normal(ks[2], (B, H // group, T, D))
    do = jax.random.normal(ks[3], (B, H, T, D))
    return q, k, v, do


def _repeated(q, k, v, group):
    return _reference_attention(q, jnp.repeat(k, group, 1),
                                jnp.repeat(v, group, 1), causal=True)


def _sides(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out, *vjp(do))


@pytest.mark.parametrize("group", [1, 2, 4])
def test_kernels_match_the_reference_with_kv_repeated(group):
    q, k, v, do = _qkv(2, 8, group, 256, 64, seed=group)
    got = _sides(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True, block_q=128, block_k=128),
        q, k, v, do)
    want = _sides(lambda q, k, v: _repeated(q, k, v, group), q, k, v, do)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-4)


def test_the_two_kernel_backward_sums_a_group_too():
    """float32 at 1024 tiles passes the default scoped VMEM at one tile:
    the backward runs as the dq / dkv pair."""
    T, D, group = 1024, 64, 4
    assert not fa._bwd_fused_fits(T, T, T, D, 4, D, True)
    q, k, v, do = _qkv(1, 4, group, T, D, seed=9)
    got = _sides(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True), q, k, v, do)
    want = _sides(lambda q, k, v: _repeated(q, k, v, group), q, k, v, do)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-4)


def test_the_exact_path_reads_kv_grouped_with_a_padding_mask():
    q, k, v, _ = _qkv(2, 8, 4, 64, 16, seed=5)
    mask = jnp.where(jnp.arange(64) < 50, 0.0, -1e9)[None, None, None, :]
    mask = jnp.broadcast_to(mask, (2, 1, 1, 64))
    got = _reference_attention(q, k, v, mask, causal=True)
    want = _reference_attention(q, jnp.repeat(k, 4, 1), jnp.repeat(v, 4, 1),
                                mask, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_heads_that_do_not_group_are_refused():
    q, k, v, _ = _qkv(1, 6, 1, 128, 16)
    with pytest.raises(ValueError, match="groups"):
        flash_attention(q, k[:, :4], v[:, :4], causal=True, interpret=True)


def test_the_dkv_reckoning_counts_float32_gradient_blocks():
    plain = fa._bwd_fused_vmem_need(1024, 1024, 16384, 64, 2, 64)
    gqa = fa._bwd_fused_vmem_need(1024, 1024, 16384, 64, 2, 64, True)
    assert gqa - plain == 2 * 1024 * 256 * 2
    # the LFM2 cell's one backward kernel asks for its need, under the
    # ceiling
    assert fa._bwd_fused_vmem_limit(1024, 1024, 16384, 64, 2, 64, True) \
        == 29 * 2 ** 20


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_cell_shapes_lower_with_kv_never_repeated(tpu_backend):
    """32 query heads on 8 K/V heads of 64 at 16,384 tokens: the kernels
    carry `_gqa`, read K and V as [8, T, 64] (never a 32-head copy), and
    write dK, dV a query head in float32 for one reduction."""
    sds = jax.ShapeDtypeStruct
    q = sds((1, 32, 16384, 64), jnp.bfloat16)
    kv = sds((1, 8, 16384, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)).as_text()
    names = re.findall(r'kernel_name = "([a-z_]+)"', text) or re.findall(
        r'(flash_[a-z_]+gqa)', text)
    assert {"flash_fwd_causal_gqa", "flash_bwd_fused_causal_gqa"} \
        <= set(names)
    calls = re.findall(r"tpu_custom_call.*?: \((tensor<[^)]*)\)", text)
    assert len(calls) == 2
    for operands in calls:
        types = re.findall(r"tensor<([0-9x]+)x(bf16|f32|i32)>", operands)
        assert types[1][0] == types[2][0] == "8x16384x64", types
    assert "tensor<32x16384x64xf32>" in text


def test_attention_layer_is_its_equations():
    """q, k RMS-normed per head (one weight each), rotate-half rotary over
    all d columns, query head h on K/V head h // 4, scaled by 1/sqrt(d)."""
    from analytics_zoo_tpu.keras.grouped_attention import \
        GroupedQueryAttention
    from analytics_zoo_tpu.keras.transformer import rotary_tables
    H, n, kv, d, T = 256, 8, 2, 32, 48
    layer = GroupedQueryAttention(H, n, kv, d, rms_eps=1e-5, init="normal",
                                  name="gqa_eq")
    p = layer.build(jax.random.PRNGKey(0))
    p["q_norm"]["gamma"] = 1.0 + jax.random.uniform(jax.random.PRNGKey(1),
                                                    (d,))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T, H))
    tables = rotary_tables(T, d, 1e6)
    got = np.asarray(layer.call(p, [x, tables]))

    def norm(a, w):
        return a / np.sqrt(np.mean(a ** 2, -1, keepdims=True) + 1e-5) * w

    def rope(a):
        t = np.arange(T)[:, None] * 1e6 ** (-np.arange(d // 2) * 2.0 / d)
        c, s = np.cos(t)[:, None], np.sin(t)[:, None]
        lo, hi = a[..., :d // 2], a[..., d // 2:]
        return np.concatenate([lo * c - hi * s, hi * c + lo * s], -1)
    xs = np.asarray(x, np.float64)
    q = rope(norm((xs @ np.asarray(p["q_kernel"])).reshape(2, T, n, d),
                  np.asarray(p["q_norm"]["gamma"])))
    k = rope(norm((xs @ np.asarray(p["k_kernel"])).reshape(2, T, kv, d),
                  np.asarray(p["k_norm"]["gamma"])))
    v = (xs @ np.asarray(p["v_kernel"])).reshape(2, T, kv, d)
    out = np.zeros((2, T, n, d))
    for h in range(n):
        s = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // 4]) / d ** 0.5
        s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out[:, :, h] = np.einsum("bqk,bkd->bqd", w, v[:, :, h // 4])
    want = out.reshape(2, T, n * d) @ np.asarray(p["out_kernel"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    flash = GroupedQueryAttention(H, n, kv, d, rms_eps=1e-5, init="normal",
                                  use_flash=True, name="gqa_eq_flash")
    np.testing.assert_allclose(np.asarray(flash.call(p, [x, tables])), got,
                               rtol=1e-5, atol=1e-6)


def _lfm2_like(**kw):
    from analytics_zoo_tpu.models.moe_decoder import MoEDecoderLM
    args = dict(vocab=97, hidden_size=64, n_layer=6, n_head=4,
                kv_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=16,
                v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
                n_routed_experts=8, num_experts_per_tok=2, n_dense_layer=2,
                experts_held=(0, 4), rms_eps=1e-5, rope_theta=1e6,
                mixers=["conv", "conv", "gqa", "conv", "conv", "conv"],
                conv={"conv_size": 3}, gqa={"n_kv_head": 2, "head_dim": 16},
                tie_embeddings=True, router_eps=1e-6, name="lfm2_like")
    args.update(kw)
    return MoEDecoderLM(**args)


def test_decoder_with_conv_and_grouped_query_mixers():
    from analytics_zoo_tpu.observability.registry import get_registry
    model = _lfm2_like()
    snap = get_registry().snapshot()
    gauges = {name: [s["value"] for s in snap[name]["series"]
                     if s["labels"].get("model") == model.name][0]
              for name in ("model_layers_conv", "model_layers_gqa",
                           "model_layers_full", "model_layers_linear")}
    assert gauges == {"model_layers_conv": 5, "model_layers_gqa": 1,
                      "model_layers_full": 1, "model_layers_linear": 0}
    params = jax.jit(model.build)(jax.random.PRNGKey(0))
    assert sorted(k for k in params if k.startswith("blocks")) == [
        "blocks_0_conv_dense", "blocks_2_gqa_moe", "blocks_3_conv_moe"]
    assert "lm_head_kernel" not in params          # tied to the embedding
    assert model.moe.norm_eps == 1e-6 and model.rope_dim == 16
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 97)
    logits = model.apply(params, ids)
    hidden = model.hidden(params, ids)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(hidden @ params["word_embeddings"].T),
        rtol=1e-6)
    proj = model.apply(params, ids, training=True)
    assert proj.kernel.shape == (64, 97)
    assert model.expert_choice(params, ids).shape == (4, 2, 32, 2)


def test_latent_beside_grouped_query_is_refused_and_defaults_stand():
    with pytest.raises(ValueError, match="never latent beside gqa"):
        _lfm2_like(mixers=["latent", "conv", "gqa", "conv", "conv", "conv"])
    from analytics_zoo_tpu.keras.moe import route
    u = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    kern = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    bias = jnp.zeros((6,))
    _, w = route(u, kern, bias, 2, 1.0)
    _, w20 = route(u, kern, bias, 2, 1.0, 1e-20)
    _, w6 = route(u, kern, bias, 2, 1.0, 1e-6)
    assert np.array_equal(np.asarray(w), np.asarray(w20))
    assert not np.array_equal(np.asarray(w), np.asarray(w6))
