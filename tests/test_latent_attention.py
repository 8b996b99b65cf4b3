"""Latent attention (`keras/latent_attention.py`), the flash kernels at two
head widths (`pallas/flash_attention.py`, through the Pallas interpreter),
the rotary pairing argument and the pre-norm block: against plain
attention and the plain reference's own latent attention at small sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.latent_attention import LatentSelfAttention
from analytics_zoo_tpu.keras.transformer import (CausalSelfAttention,
                                                 GatedFFN,
                                                 PreNormDecoderBlock,
                                                 TransformerDecoderBlock,
                                                 apply_rotary, gated_ffn,
                                                 rotary_tables)
from analytics_zoo_tpu.pallas import flash_attention as fa
from benchmark.reference import kanana_moe as reference

CFG = dict(num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, kv_lora_rank=32, rope_theta=1e6, rms_norm_eps=1e-6)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _qkv(T, dk, dv, seed=0, B=1, H=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (B, H, T, d), jnp.float32) * 0.4
            for k, d in zip(ks, (dk, dk, dv))]


# -- the kernels at two widths -----------------------------------------------
@pytest.mark.parametrize("T,dk,dv,causal", [
    (256, 48, 32, True),        # one tile
    (512, 192, 128, True),      # the published widths, 2 x 2 tiles of 256
    (256, 48, 32, False),
])
def test_two_width_flash_matches_plain_attention_forward_and_grads(
        T, dk, dv, causal):
    q, k, v = _qkv(T, dk, dv)
    block = 256 if T > 256 else None

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, interpret=True,
                                  block_q=block, block_k=block)

    def plain(q, k, v):
        return fa._reference_attention(q, k, v, causal=causal)

    got = flash(q, k, v)
    assert got.shape == (1, 2, T, dv)
    np.testing.assert_allclose(got, plain(q, k, v), atol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(9), got.shape)
    g_flash = jax.grad(lambda *a: jnp.sum(flash(*a) * cot),
                       argnums=(0, 1, 2))(q, k, v)
    g_plain = jax.grad(lambda *a: jnp.sum(plain(*a) * cot),
                       argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_plain):
        assert a.shape == b.shape
        assert _rel(a, b) < 1e-4


def test_two_width_pair_backward_matches_too():
    """Shapes whose one-kernel backward does not fit run the pair: forced
    here by a float32 T = 1024 at 1024 tiles (the gate says no)."""
    q, k, v = _qkv(1024, 48, 32, seed=1)
    assert not fa._bwd_fused_fits(1024, 1024, 1024, 48, 4, 32)
    cot = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 1024, 32))
    g_flash = jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, causal=True, interpret=True) * cot), argnums=(0, 1, 2))(q, k, v)
    g_plain = jax.grad(lambda *a: jnp.sum(fa._reference_attention(
        *a, causal=True) * cot), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_plain):
        assert _rel(a, b) < 1e-4


def test_the_scale_is_the_key_widths():
    q, k, v = _qkv(128, 48, 32)
    one = fa._reference_attention(q, k, v)
    w = jax.nn.softmax(jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(48.0),
                       axis=-1)
    np.testing.assert_allclose(one, jnp.einsum("bhqk,bhkd->bhqd", w, v),
                               atol=1e-5)


def test_kernel_names_tell_the_two_width_form():
    assert fa._kernel_name("flash_fwd", False) == "flash_fwd"
    assert fa._kernel_name("flash_fwd", True) == "flash_fwd_causal"
    assert fa._kernel_name("flash_dq", True, True) == "flash_dq_causal_mla"
    assert fa._kernel_name("flash_fwd", False, True) == "flash_fwd_mla"


_MIB = 2 ** 20


@pytest.mark.parametrize("T,D,Dv,limit", [
    # one width, under the 15 MiB that fit the compiler's default scoped
    # VMEM: one kernel that asks nothing, as ever (tests/tpu holds the
    # reckoning to the chip)
    (4096, 128, None, 0), (2048, 64, None, 0), (4096, 128, 128, 0),
    # over it the kernel asks for its reckoned need, rounded up, and a MiB
    (5120, 128, None, 17 * _MIB), (8192, 64, None, 20 * _MIB),
    # keys of 192 lie in 256 lanes, in dQ, the q, k and dk blocks alike:
    # one 1024 tile fits the default, two do not; 8192 is the expert fit's
    (1024, 192, 128, 0), (2048, 192, 128, 18 * _MIB),
    (8192, 192, 128, 30 * _MIB),
    # the ceiling is half of the v5e's 128 MiB: the last length under it,
    # and the first over it, which still gets the pair
    (25600, 192, 128, 64 * _MIB), (26624, 192, 128, None),
    (53248, 128, None, 64 * _MIB), (54272, 128, None, None),
])
def test_fused_backward_gate_at_two_widths(T, D, Dv, limit):
    assert fa._bwd_fused_vmem_limit(1024, 1024, T, D, 2, Dv) == limit
    assert fa._bwd_fused_fits(1024, 1024, T, D, 2, Dv) == (limit is not None)


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("D,Dv", [(64, 64), (128, 128), (192, 128),
                                  (256, 256)])
def test_the_backward_asks_for_vmem_only_over_the_default(D, Dv, itemsize):
    """Over every tile `_auto_block` picks and every length to 64k: no
    limit is asked wherever the reckoned need is within the 15 MiB that
    fit today, so those shapes lower as they always have; a limit asked
    covers the need and a MiB of room and stays within the ceiling; and a
    tile that passes the default at one tile of T gets the pair at every
    length."""
    ceiling = fa._bwd_fused_vmem_ceiling()
    assert ceiling == 64 * _MIB     # the v5e's, where no TPU is attached
    for block in (128, 256, 512, 1024):
        one_tile = fa._bwd_fused_vmem_need(block, block, block, D, itemsize,
                                           Dv)
        for T in range(block, 65536 + 1, block):
            need = fa._bwd_fused_vmem_need(block, block, T, D, itemsize, Dv)
            limit = fa._bwd_fused_vmem_limit(block, block, T, D, itemsize,
                                             Dv)
            if need <= fa._BWD_FUSED_VMEM_BYTES:
                assert limit == 0, (block, T)
            elif one_tile > fa._BWD_FUSED_VMEM_BYTES:
                assert limit is None, (block, T)
            elif limit is not None:
                assert need + _MIB <= limit <= ceiling, (block, T)
                assert limit % _MIB == 0 and limit < need + 2 * _MIB
            else:
                assert need + _MIB > ceiling - _MIB, (block, T)


def test_attn_cost_counts_each_product_at_its_width():
    q = jax.ShapeDtypeStruct((2, 4, 512, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 4, 512, 128), jnp.bfloat16)
    cost = fa._attn_cost(3, 2, q, v, causal=True)
    assert cost.flops == 2 * 8 * 512 * 512 * (3 * 192 + 2 * 128) // 2
    assert cost.transcendentals == 8 * 512 * 512 // 2
    # at one width: what the one-width count was (n products of width D)
    same = fa._attn_cost(1, 1, v, v)
    assert same.flops == 2 * 2 * 8 * 512 * 512 * 128
    assert same.bytes_accessed == 8 * 512 * 128 * 2 * 6


# -- rotary pairing -----------------------------------------------------------
def test_interleaved_rotary_is_rotate_half_of_the_deinterleaved_input():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 16, 8))
    cos, sin = rotary_tables(16, 8, 1e4)
    deinterleaved = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    np.testing.assert_allclose(apply_rotary(x, cos, sin, interleaved=True),
                               apply_rotary(deinterleaved, cos, sin),
                               atol=1e-6)
    # and it is the reference's rotation of neighbouring pairs
    np.testing.assert_allclose(apply_rotary(x, cos, sin, interleaved=True),
                               reference._rotary(x, 1e4), atol=1e-5)


def test_scores_do_not_depend_on_the_column_order_q_and_k_share():
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 16, 8))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 16, 8))
    cos, sin = rotary_tables(16, 8, 1e4)

    def scores(interleaved, q, k):
        return jnp.einsum("bhqd,bhkd->bhqk",
                          apply_rotary(q, cos, sin, interleaved),
                          apply_rotary(k, cos, sin, interleaved))
    perm = jnp.concatenate([jnp.arange(0, 8, 2), jnp.arange(1, 8, 2)])
    np.testing.assert_allclose(scores(True, q, k),
                               scores(False, q[..., perm], k[..., perm]),
                               atol=1e-5)


def test_causal_self_attention_still_pairs_by_halves():
    """`CausalSelfAttention` shares `apply_rotary` and keeps its default."""
    layer = CausalSelfAttention(32, 2)
    p = layer.build(jax.random.PRNGKey(0), (None, None, 32))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32))
    rotary = rotary_tables(8, 16, 1e4)
    out = layer.call(p, [x, rotary])
    qkv = (x @ p["qkv_kernel"]).reshape(1, 8, 3, 2, 16)
    q, k, v = [qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3)]
    want = fa._reference_attention(apply_rotary(q, *rotary),
                                   apply_rotary(k, *rotary), v, causal=True)
    want = want.transpose(0, 2, 1, 3).reshape(1, 8, 32) @ p["out_kernel"]
    np.testing.assert_allclose(out, want, atol=1e-5)


# -- the layer ----------------------------------------------------------------
def _layer(**kw):
    return LatentSelfAttention(64, 2, kv_lora_rank=32, qk_nope_head_dim=16,
                               qk_rope_head_dim=8, v_head_dim=16, **kw)


def test_latent_attention_matches_the_plain_reference():
    layer = _layer()
    p = layer.build(jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in p.items() if k != "kv_norm"} == {
        "q_kernel": (64, 48), "kv_a_kernel": (64, 40),
        "kv_b_kernel": (32, 64), "out_kernel": (32, 64)}
    p["kv_norm"]["gamma"] = jnp.linspace(0.5, 1.5, 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    rotary = rotary_tables(24, 8, 1e6)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(
            lambda p: jnp.sum(layer.call(p, [x, rotary]) ** 2))(p)
        want, g_want = jax.value_and_grad(
            lambda p: jnp.sum(reference._mla(x, p, CFG, {}, False) ** 2))(p)
    assert abs(float(got) - float(want)) < 1e-4 * float(want)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        assert _rel(a, b) < 1e-4


def test_every_fault_of_the_reference_moves_the_attention():
    layer = _layer()
    p = layer.build(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 64))
    got = layer.call(p, [x, rotary_tables(24, 8, 1e6)])
    for fault in ("rope_key_dropped", "causal_mask_dropped",
                  "kv_norm_dropped"):
        broken = reference._mla(x, p, CFG, {fault: True}, False)
        assert _rel(got, broken) > 1e-2, fault


def test_latent_attention_through_the_interpreted_kernels():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 64))
    rotary = rotary_tables(256, 8, 1e6)
    plain = _layer()
    p = plain.build(jax.random.PRNGKey(0))
    flash = _layer(use_flash=True)
    calls = []
    real = fa.flash_attention

    def interpreted(q, k, v, **kw):
        calls.append((q.shape, k.shape, v.shape))
        return real(q, k, v, interpret=True, **kw)

    import analytics_zoo_tpu.keras.latent_attention as module
    module.flash_attention = interpreted
    try:
        got = flash.call(p, [x, rotary])
    finally:
        module.flash_attention = real
    assert calls == [((1, 2, 256, 24), (1, 2, 256, 24), (1, 2, 256, 16))]
    np.testing.assert_allclose(got, plain.call(p, [x, rotary]), atol=2e-5)


# -- the blocks ---------------------------------------------------------------
def test_pre_norm_block_is_two_residual_branches():
    attn = _layer(name="b_attn")
    ffn = GatedFFN(64, 96, name="b_ffn")
    block = PreNormDecoderBlock(attn, ffn, name="b")
    p = block.build(jax.random.PRNGKey(0), (None, None, 64))
    assert set(p) == {"attn_norm", "ffn_norm", "attn", "ffn"}
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64))
    rotary = rotary_tables(16, 8, 1e6)

    def norm(x, g):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g
    a = h + attn.call(p["attn"], [norm(h, p["attn_norm"]["gamma"]), rotary])
    want = a + gated_ffn(p["ffn"], norm(a, p["ffn_norm"]["gamma"]),
                         jax.nn.silu)
    np.testing.assert_allclose(block.call(p, [h, rotary]), want, atol=1e-5)


def test_sandwich_block_kept_its_numbers_through_the_shared_ffn():
    """`TransformerDecoderBlock.ffn_branch` now calls `gated_ffn`: the same
    three products as before."""
    block = TransformerDecoderBlock(32, 2, 48, name="s")
    p = block.build(jax.random.PRNGKey(0), (None, None, 32))
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32))
    u = block.norm.call(p["ffn_in_norm"], h)
    f = (jax.nn.silu(u @ p["ffn_gate_kernel"]) * (u @ p["ffn_up_kernel"])) \
        @ p["ffn_down_kernel"]
    np.testing.assert_allclose(
        block.ffn_branch(p, h),
        h + block.norm.call(p["ffn_out_norm"], f), atol=1e-6)
