"""Flash-attention training (custom VJP) tests.

CPU suite runs the kernels in Pallas interpret mode (no-dropout paths —
interpret mode has no TPU PRNG). The dropout-in-kernel numerics are
TPU-gated: `TestOnTPU` re-runs automatically when the suite executes on a
real chip, and was validated on v5e by extracting the kernel's masks and
comparing against dense attention with identical masks (fwd) and dense
autodiff (bwd)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.pallas import flash_attention as fa
from analytics_zoo_tpu.pallas.flash_attention import (_reference_attention,
                                                      flash_attention)


def _bwd_kernels(q, block, causal=False):
    """Names of the Pallas calls in the backward of a flash call on
    q-shaped operands (traced, nothing runs)."""
    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=block, block_k=block,
                               interpret=True, causal=causal
                               ).astype(jnp.float32).sum()
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    return sorted(set(re.findall(r"name=(flash_(?!fwd)\w+)", str(jaxpr))))


def _assert_grad_parity(q, k, v, mask=None, causal=False, **blocks):
    """flash (interpreted) against reference autodiff, all three grads."""
    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask, interpret=True,
                                       causal=causal, **blocks) ** 2)

    def lr(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, mask,
                                            causal=causal) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def _qkv(B=2, H=3, T=256, D=64, seed=0):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(B, H, T, D), jnp.float32),
            jnp.asarray(rs.randn(B, H, T, D), jnp.float32),
            jnp.asarray(rs.randn(B, H, T, D), jnp.float32))


def _pad_mask(T, masked, B=1):
    """Additive padding mask [B, 1, 1, T]: the last `masked` keys out."""
    return jnp.where(jnp.arange(T)[None, None, None, :] < T - masked,
                     0.0, -1e9) * jnp.ones((B, 1, 1, T))


def _reference_lse(q, k, mask=None, causal=False):
    """logsumexp over the keys of the reference's scores, [B, H, T]."""
    T, D = q.shape[2:]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    if mask is not None:
        scores = scores + mask
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -1e30)
    return jax.nn.logsumexp(scores, axis=-1)


class TestFlashVJP:
    def test_forward_parity(self):
        q, k, v = _qkv()
        o1 = np.asarray(flash_attention(q, k, v, interpret=True))
        o2 = np.asarray(_reference_attention(q, k, v))
        np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)

    def test_forward_parity_with_padding_mask(self):
        q, k, v = _qkv()
        T = q.shape[2]
        mask = jnp.where(jnp.arange(T)[None, None, None, :] < T - 17,
                         0.0, -1e9) * jnp.ones((2, 1, 1, T))
        o1 = np.asarray(flash_attention(q, k, v, mask=mask, interpret=True))
        o2 = np.asarray(_reference_attention(q, k, v, mask))
        np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)

    def test_gradient_parity(self):
        q, k, v = _qkv()
        T = q.shape[2]
        mask = jnp.where(jnp.arange(T)[None, None, None, :] < T - 9,
                         0.0, -1e9) * jnp.ones((2, 1, 1, T))
        _assert_grad_parity(q, k, v, mask)

    def test_non_multiple_seq_len_pads(self):
        q, k, v = _qkv(T=200)
        o1 = np.asarray(flash_attention(q, k, v, interpret=True))
        o2 = np.asarray(_reference_attention(q, k, v))
        assert o1.shape == (2, 3, 200, 64)
        np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)

    def test_gradient_through_padding(self):
        q, k, v = _qkv(T=200)
        g = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, interpret=True) ** 2))(q)
        assert np.asarray(g).shape == q.shape
        assert np.isfinite(np.asarray(g)).all()

    def test_block_sizes(self):
        q, k, v = _qkv(T=512)
        o_ref = np.asarray(_reference_attention(q, k, v))
        for bq, bk in [(128, 256), (256, 128), (256, 256)]:
            o = np.asarray(flash_attention(q, k, v, block_q=bq, block_k=bk,
                                           interpret=True))
            np.testing.assert_allclose(o, o_ref, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("padded", [False, True])
    def test_gradient_parity_fused_chunks_and_blocks(self, padded):
        # T=1024 at 512 tiles: two q-blocks, two k-blocks and four
        # 128-column chunks a tile, all through the one-kernel backward
        # (dq accumulates over ki in its resident scratch, dk/dv over qi)
        q, k, v = _qkv(B=1, H=2, T=1024)
        T = q.shape[2]
        assert _bwd_kernels(q, block=512) == ["flash_bwd_fused"]
        mask = None
        if padded:
            mask = jnp.where(jnp.arange(T)[None, None, None, :] < T - 77,
                             0.0, -1e9) * jnp.ones((1, 1, 1, T))
        _assert_grad_parity(q, k, v, mask, block_q=512, block_k=512)

    def test_gradient_parity_two_kernel_fallback(self):
        # a 2048 x 2048 tile's chunks are past the fused kernel's VMEM
        # reckoning, so the backward is the two-kernel (dq + dkv) form —
        # both must match reference autodiff
        q, k, v = _qkv(B=1, H=1, T=2048, D=16)
        assert _bwd_kernels(q, block=2048) == ["flash_dkv", "flash_dq"]
        _assert_grad_parity(q, k, v, block_q=2048, block_k=2048)

    @pytest.mark.parametrize("T,block,kernels", [
        (128, 128, ["flash_bwd_fused"]),
        (768, 128, ["flash_bwd_fused"]),      # six k-blocks: dq resident
        (2048, 1024, ["flash_bwd_fused"]),    # the seq-2048 fit's shape
        (4096, 1024, ["flash_bwd_fused"]),
        # dq passes the default scoped VMEM: the kernel asks for more
        (8192, 1024, ["flash_bwd_fused"]),
        (53248, 1024, ["flash_bwd_fused"]),   # the last under the ceiling
        (54272, 1024, ["flash_dkv", "flash_dq"]),   # dq passes the ceiling
        (1536, 768, ["flash_dkv", "flash_dq"]),     # no aligned quarter
    ])
    def test_backward_form_follows_the_shapes(self, T, block, kernels):
        q = jax.ShapeDtypeStruct((2, 12, T, 64), jnp.bfloat16)
        assert _bwd_kernels(q, block=block) == kernels

    @pytest.mark.parametrize("T,D,kernels", [
        # the seq-4096 decoder fit's shape: 16 heads of 128, 1024 tiles
        (4096, 128, ["flash_bwd_fused_causal"]),
        (4096, 64, ["flash_bwd_fused_causal"]),
        (8192, 128, ["flash_bwd_fused_causal"]),
        (65536, 128, ["flash_dkv_causal", "flash_dq_causal"]),
    ])
    def test_causal_backward_form_and_names(self, T, D, kernels):
        # the causal flag changes the kernels' names, never their form
        q = jax.ShapeDtypeStruct((2, 16, T, D), jnp.bfloat16)
        assert _bwd_kernels(q, block=1024, causal=True) == kernels
        assert _bwd_kernels(q, block=1024) == [
            k.replace("_causal", "") for k in kernels]

    @pytest.mark.parametrize("D", [64, 128])
    @pytest.mark.parametrize("T,bq,bk,kernels", [
        # one-kernel backward: a diagonal of masked tiles, tiles skipped
        # above it, unmasked tiles below it, four chunks a tile
        (1024, 512, 512, ["flash_bwd_fused_causal"]),
        # oblong tiles either way: the diagonal crosses two k-blocks of a
        # q-block, or two q-blocks of a k-block
        (512, 256, 128, ["flash_bwd_fused_causal"]),
        (512, 128, 256, ["flash_bwd_fused_causal"]),
        # two-kernel backward (a 768 tile has no 128-aligned quarter)
        (1536, 768, 768, ["flash_dkv_causal", "flash_dq_causal"]),
    ])
    def test_causal_forward_and_gradient_parity(self, T, bq, bk, kernels, D):
        q, k, v = _qkv(B=1, H=2, T=T, D=D)
        if bq == bk:
            assert _bwd_kernels(q, block=bq, causal=True) == kernels
        o1 = np.asarray(flash_attention(q, k, v, block_q=bq, block_k=bk,
                                        interpret=True, causal=True))
        o2 = np.asarray(_reference_attention(q, k, v, causal=True))
        np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)
        _assert_grad_parity(q, k, v, causal=True, block_q=bq, block_k=bk)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("T,block,kernels", [
        (1024, 512, ["flash_bwd_fused"]),
        (1536, 768, ["flash_dkv", "flash_dq"]),
    ])
    def test_log_sum_exp_is_a_row_and_both_backward_forms_read_it(
            self, T, block, kernels, causal):
        # the residual the forward kernel leaves for the backward: T on the
        # lanes ([B*H, 1, T], never [.., T, 1], which HBM holds 128 lanes
        # wide), equal to logsumexp of the reference's scores
        q, k, v = _qkv(B=1, H=2, T=T)
        mask = _pad_mask(T, 77)
        _, res = fa._flash_fwd(q, k, v, mask, jnp.zeros((1, 1), jnp.int32),
                               0.0, block, block, True, causal)
        lse = res[-1]
        assert lse.shape == (2, 1, T) and lse.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(lse[:, 0]),
            np.asarray(_reference_lse(q, k, mask, causal)[0]),
            rtol=1e-5, atol=1e-5)
        # and the backward that turns it into a column again, in the
        # one-kernel and in the two-kernel form
        assert _bwd_kernels(q, block=block, causal=causal) == [
            n + "_causal" if causal else n for n in kernels]
        _assert_grad_parity(q, k, v, mask, causal=causal, block_q=block,
                            block_k=block)

    @pytest.mark.parametrize("width", [64, 128, 192, 256])
    def test_a_row_statistic_meets_a_tile_of_any_width(self, width):
        # what the kernels do with a [1, n] row block of HBM: a [n, 128]
        # statistic equal along its lanes, sliced, laid side by side or
        # (no whole number of lane tiles) broadcast to the tile's width
        row = jnp.arange(256, dtype=jnp.float32)[None, :] * 0.5
        got = fa._lanes(fa._stat_of(row), width)
        np.testing.assert_array_equal(
            np.asarray(got), np.broadcast_to(np.asarray(row).T, (256, width)))

    def test_causal_with_a_padding_mask_and_a_padded_length(self):
        # T = 200 pads to 256 with masked keys; causal on top of it
        q, k, v = _qkv(B=2, H=2, T=200)
        T = q.shape[2]
        mask = jnp.where(jnp.arange(T)[None, None, None, :] < T - 9,
                         0.0, -1e9) * jnp.ones((2, 1, 1, T))
        o1 = np.asarray(flash_attention(q, k, v, mask=mask, interpret=True,
                                        causal=True))
        o2 = np.asarray(_reference_attention(q, k, v, mask, causal=True))
        np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)

    def test_causal_off_the_tpu_is_the_reference_with_a_triangle(self):
        # decided statically: no kernel, a materialised lower triangle
        q, k, v = _qkv(T=128)
        T = 128
        tri = jnp.where(jnp.arange(T)[:, None] >= jnp.arange(T)[None, :],
                        0.0, -1e9)[None, None]
        jaxpr = str(jax.make_jaxpr(
            lambda *a: flash_attention(*a, causal=True))(q, k, v))
        assert "pallas_call" not in jaxpr
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, causal=True)),
            np.asarray(_reference_attention(q, k, v, tri)),
            rtol=1e-5, atol=1e-5)
        # later keys move nothing earlier
        v2 = v.at[:, :, 100:].add(3.0)
        np.testing.assert_array_equal(
            np.asarray(flash_attention(q, k, v, causal=True))[:, :, :100],
            np.asarray(flash_attention(q, k, v2, causal=True))[:, :, :100])

    def test_full_mask_takes_reference_path_even_interpreted(self):
        q, k, v = _qkv(T=128)
        T = 128
        causal = jnp.where(jnp.arange(T)[None, None, :, None]
                           >= jnp.arange(T)[None, None, None, :],
                           0.0, -1e9) * jnp.ones((2, 1, T, T))
        o1 = np.asarray(flash_attention(q, k, v, mask=causal,
                                        interpret=True))
        o2 = np.asarray(_reference_attention(q, k, v, causal))
        np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)

    def test_dropout_without_seed_raises(self):
        q, k, v = _qkv(T=128)
        with pytest.raises(ValueError, match="dropout_seed"):
            flash_attention(q, k, v, dropout_rate=0.1)

    def test_cpu_fallback_dropout_distribution(self):
        # non-interpret on CPU → reference fallback with jax.random bits
        q, k, v = _qkv(T=128)
        o = np.asarray(flash_attention(q, k, v, dropout_rate=0.5,
                                       dropout_seed=jnp.int32(3)))
        o0 = np.asarray(flash_attention(q, k, v))
        assert not np.allclose(o, o0)


class TestChunkedForward:
    """PR 29: the forward walks its DMA tile in column chunks
    (`_fwd_chunk`) and scales the q block where 1/sqrt(D) is a power of
    two."""

    @pytest.mark.parametrize("D", [64, 128])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("padded", [False, True])
    @pytest.mark.parametrize("block_k", [128, 256, 512, 1024])
    def test_output_and_log_sum_exp_match_the_reference(self, block_k,
                                                        padded, causal, D):
        # 1024 columns a tile are two chunks of 512; 512, 256 and 128 are
        # one chunk. Causal, the second chunk of a diagonal tile runs on
        # the lower half of the rows. D = 64 scales q (0.125 is a power
        # of two), D = 128 the scores.
        T = 1024
        assert fa._fwd_chunk(block_k) == min(block_k, 512)
        assert fa._scale_on_q(1 / np.sqrt(D)) == (D == 64)
        q, k, v = _qkv(B=1, H=1, T=T, D=D, seed=block_k + D)
        mask = _pad_mask(T, 77) if padded else None
        out, res = fa._flash_fwd(
            q, k, v, jnp.zeros((1, 1, 1, T)) if mask is None else mask,
            jnp.zeros((1, 1), jnp.int32), 0.0, block_k, block_k, True,
            causal)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(_reference_attention(q, k, v, mask, causal=causal)),
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(res[-1][:, 0]),
            np.asarray(_reference_lse(q, k, mask, causal)[0]),
            rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("t", [1, 230, 255])
    @pytest.mark.parametrize("plane,lo,hi", [
        (256, 0, 256), (256, 256, 512), (256, 512, 768),  # one plane each,
        (256, 768, 1024),                                 # as the backward
        (256, 0, 512), (256, 512, 1024),     # two, as the forward's chunks
        (256, 0, 1024), (256, 128, 384), (256, 640, 896),     # straddling
        (256, 200, 840),
        (64, 0, 256), (32, 0, 128)])    # tiles of 256 and 128 columns
    def test_keep_predicate_is_the_byte_extraction(self, plane, lo, hi, t):
        # the boolean mask is ((word >> 8j) & 0xFF) < t for the byte plane
        # j each column falls in: held on random words and on the bytes at
        # the threshold's two sides with every lower bit set
        rs = np.random.RandomState(lo + hi + t)
        words = rs.randint(0, 2 ** 32, size=(64, plane), dtype=np.uint64)
        edge = [(b << 8 * j) | ((1 << 8 * j) - 1)
                for j in range(4) for b in (max(t - 1, 0), t, 255, 0)]
        words[0, :len(edge)] = edge[:plane]
        words[1, :2] = [0, 0xFFFFFFFF]
        words = words.astype(np.uint32)
        rate = 1.0 - t / 256.0
        assert fa._byte_threshold(rate) == t
        got = np.asarray(fa._keep_of(jnp.asarray(words), rate, lo, hi))
        cols = np.arange(lo, hi)
        bytes_ = (words[:, cols % plane] >> (8 * (cols // plane))[None, :]
                  .astype(np.uint32)) & np.uint32(0xFF)
        assert got.dtype == bool and got.shape == (64, hi - lo)
        np.testing.assert_array_equal(got, bytes_ < t)
        assert fa._keep_gain(rate) == 256.0 / t

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("T,columns", [(2048, 512), (512, 512),
                                           (256, 256), (384, 128)])
    def test_gauge_names_the_chunk_of_the_blocks_last_picked(self, T,
                                                             columns,
                                                             causal):
        from analytics_zoo_tpu.observability.registry import get_registry
        x = jax.ShapeDtypeStruct((1, 1, T, 64), jnp.float32)
        jax.make_jaxpr(lambda q: flash_attention(
            q, q, q, interpret=True, causal=causal))(x)
        gauge = get_registry().get("flash_forward_chunk_columns")
        assert gauge.value(kernel="flash_fwd_causal" if causal
                           else "flash_fwd") == columns


@pytest.mark.parametrize("T,Dk,Dv,causal,asked", [
    # the compiler's default holds the one kernel: it asks nothing
    (2048, 64, 64, False, {"flash_bwd_fused": 0}),
    # over it the one kernel asks for what it reckoned (the expert fit's
    # shape: 28.56 MiB, rounded up, and a MiB)
    (8192, 192, 128, True, {"flash_bwd_fused_causal_mla": 30 * 2 ** 20}),
    (8192, 128, 128, True, {"flash_bwd_fused_causal": 20 * 2 ** 20}),
    # dq passes the ceiling: the pair, which asks nothing, by both names
    (65536, 128, 128, True, {"flash_dq_causal": 0, "flash_dkv_causal": 0}),
])
def test_gauge_names_the_vmem_the_backward_asks_for(T, Dk, Dv, causal,
                                                    asked):
    from analytics_zoo_tpu.observability.registry import get_registry
    q = jax.ShapeDtypeStruct((1, 1, T, Dk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 1, T, Dv), jnp.bfloat16)
    jax.make_jaxpr(lambda q, v: flash_attention(
        q, q, v, interpret=True, causal=causal))(q, v)
    gauge = get_registry().get("flash_backward_vmem_limit_bytes")
    for kernel, limit in asked.items():
        assert gauge.value(kernel=kernel) == limit


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="in-kernel dropout needs the TPU PRNG")
class TestOnTPU:
    def test_dropout_deterministic_and_vjp_consistent(self):
        q, k, v = _qkv(T=256, H=4)
        f = lambda *a: flash_attention(  # noqa: E731
            *a, dropout_rate=0.1, dropout_seed=jnp.int32(42))
        oA = np.asarray(f(q, k, v))
        oB = np.asarray(f(q, k, v))
        assert np.array_equal(oA, oB)
        oC = np.asarray(flash_attention(q, k, v, dropout_rate=0.1,
                                        dropout_seed=jnp.int32(7)))
        assert not np.array_equal(oA, oC)
        g = jax.grad(lambda q: jnp.sum(f(q, k, v) ** 2))(q)
        assert np.isfinite(np.asarray(g)).all()
