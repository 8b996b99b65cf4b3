"""On-chip numeric specs: the Pallas flash-attention kernels vs the exact
reference attention, the custom VJP vs dense autodiff, in-kernel dropout
bit-determinism, and one real `fit` step — the per-layer numeric-spec style
of the reference's layer specs (`zoo/src/test/.../keras/layers/`, SURVEY §4)
applied to the kernels only a real chip can run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _qkv(B=2, H=4, T=256, D=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, H, T, D)
    return [jax.random.normal(k, shape, jnp.float32) * 0.3 for k in ks]


class TestFlashForward:
    def test_matches_reference_no_mask(self):
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention, flash_attention)
        q, k, v = _qkv()
        got = np.asarray(flash_attention(q, k, v))
        ref = np.asarray(_reference_attention(q, k, v))
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)

    def test_matches_reference_padding_mask(self):
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention, flash_attention)
        q, k, v = _qkv(T=384)
        B, T = q.shape[0], q.shape[2]
        keep = np.ones((B, 1, 1, T), np.float32)
        keep[:, :, :, T // 2:] = 0.0
        mask = jnp.asarray((1.0 - keep) * -1e9)
        got = np.asarray(flash_attention(q, k, v, mask))
        ref = np.asarray(_reference_attention(q, k, v, mask))
        np.testing.assert_allclose(got[:, :, :T // 2], ref[:, :, :T // 2],
                                   rtol=2e-2, atol=2e-3)

    def test_non_multiple_seq_len_pads(self):
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention, flash_attention)
        q, k, v = _qkv(T=200)   # not a multiple of 128
        got = np.asarray(flash_attention(q, k, v))
        ref = np.asarray(_reference_attention(q, k, v))
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)


class TestFlashBackward:
    def test_vjp_matches_dense_autodiff(self):
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention, flash_attention)
        q, k, v = _qkv()

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(_reference_attention(q, k, v) ** 2)

        gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-2, atol=5e-3)


class TestInKernelDropout:
    def test_bit_determinism(self):
        from analytics_zoo_tpu.pallas.flash_attention import flash_attention
        q, k, v = _qkv()
        seed = jnp.asarray(42, jnp.int32)
        a = np.asarray(flash_attention(q, k, v, dropout_rate=0.1,
                                       dropout_seed=seed))
        b = np.asarray(flash_attention(q, k, v, dropout_rate=0.1,
                                       dropout_seed=seed))
        np.testing.assert_array_equal(a, b)

    def test_seed_changes_mask(self):
        from analytics_zoo_tpu.pallas.flash_attention import flash_attention
        q, k, v = _qkv()
        a = np.asarray(flash_attention(
            q, k, v, dropout_rate=0.1, dropout_seed=jnp.asarray(1, jnp.int32)))
        b = np.asarray(flash_attention(
            q, k, v, dropout_rate=0.1, dropout_seed=jnp.asarray(2, jnp.int32)))
        assert np.abs(a - b).max() > 0


class TestDropoutBackwardAgainstItsOwnMask:
    # 2048: 1024 tiles, the forward takes two byte planes of the words a
    # chunk and the backward one; 512, 256 and 128: one chunk a tile in
    # both, with planes of 128, 64 and 32 lanes laid side by side
    @pytest.mark.parametrize("T", [2048, 512, 256, 128])
    def test_dropout_grads_match_explicit_mask_reference(self, T):
        """The in-kernel dropout path of the backward, held to a
        reference: the forward is linear in V, so T/64 calls with V set to
        64-column slices of the identity give the kernel's dropped weight
        matrix, whose non-zeros ARE the mask; plain jnp attention with
        that mask then has the gradients the kernel must produce."""
        from analytics_zoo_tpu.pallas.dropout import _byte_threshold
        from analytics_zoo_tpu.pallas.flash_attention import flash_attention
        D, rate = 64, 0.1
        # bfloat16 as the seq-2048 fit runs it: the one-kernel backward
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in _qkv(B=1, H=1, T=T, D=D, seed=5))
        seed = jnp.asarray(11, jnp.int32)

        def flash(q, k, v):
            return flash_attention(q, k, v, dropout_rate=rate,
                                   dropout_seed=seed)
        eye = jnp.eye(T, dtype=jnp.bfloat16)
        fwd = jax.jit(flash)
        dropped = jnp.concatenate(
            [fwd(q, k, eye[None, None, :, c:c + D])[0, 0]
             for c in range(0, T, D)], axis=1)             # [T, T]
        keep = dropped > 0
        kept = float(keep.mean())
        t = _byte_threshold(rate)
        assert abs(kept - t / 256.0) < 4 * np.sqrt(0.1 * 0.9) / T, kept
        scale = jnp.where(keep, 256.0 / t, 0.0)

        def explicit(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
            w = jax.nn.softmax(s.astype(jnp.float32), axis=-1) * scale
            return jnp.einsum("bhqk,bhkd->bhqd", w, v)
        np.testing.assert_allclose(np.asarray(fwd(q, k, v)),
                                   np.asarray(explicit(q, k, v)),
                                   rtol=2e-2, atol=2e-3)
        gf = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(explicit(*a) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-2, atol=5e-3)
        # and bit for bit: dV is linear in dO, so the same slices of the
        # identity as cotangents give the BACKWARD's dropped weights
        # (transposed), whose non-zeros must be the forward's
        _, vjp = jax.vjp(flash, q, k, v)
        back = jnp.concatenate(
            [vjp(eye[None, None, :, c:c + D])[2][0, 0]
             for c in range(0, T, D)], axis=1)             # [T keys, T rows]
        np.testing.assert_array_equal(np.asarray(back.T > 0),
                                      np.asarray(keep))


class TestFusedBackwardFits:
    @pytest.mark.parametrize("T,D,Dv,dtype,mib", [
        (4096, 64, 64, jnp.bfloat16, 0), (2048, 128, 128, jnp.bfloat16, 0),
        (512, 64, 64, jnp.float32, 0), (1536, 64, 64, jnp.bfloat16, 0),
        (2048, 64, 64, jnp.bfloat16, 0),    # the seq-2048 fit's own shape
        # the largest under the ceiling at each width (PR 31): the kernel
        # asks for all 64 MiB of it (float32: the longest that still gets
        # 512 tiles, since a 1024 tile passes the default by itself)
        (53248, 64, 64, jnp.bfloat16, 64), (53248, 128, 128, jnp.bfloat16, 64),
        (25600, 192, 128, jnp.bfloat16, 64), (38400, 64, 64, jnp.float32, 63)])
    def test_shapes_the_gate_admits_compile(self, T, D, Dv, dtype, mib):
        """`_bwd_fused_vmem_need` reckons the kernel's VMEM need from the
        shapes; the chip's compiler has the last word. Shapes near the
        reckoning's limits compile (nothing runs) as ONE backward kernel:
        under the default scoped-VMEM limit, and under the ceiling with
        the limit the kernel asks for."""
        from analytics_zoo_tpu.pallas import flash_attention as fa
        block = fa._auto_block(T)
        assert fa._bwd_fused_vmem_ceiling() == 64 * 2 ** 20   # a v5e
        assert fa._bwd_fused_vmem_limit(
            block, block, T, D, jnp.dtype(dtype).itemsize,
            Dv) == mib * 2 ** 20
        # many head-batches: Mosaic pads every buffer only at such sizes
        # (fewer of the longest, whose operands would not fit the HBM)
        B = 8 if T <= 4096 else 2
        q = jax.ShapeDtypeStruct((B, 12, T, D), dtype)
        v = jax.ShapeDtypeStruct((B, 12, T, Dv), dtype)

        def loss(q, k, v):
            out = fa.flash_attention(q, k, v, dropout_rate=0.1,
                                     dropout_seed=jnp.int32(3))
            return out.astype(jnp.float32).sum()
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, v).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        assert "flash_bwd_fused" in text


class TestCausalFlashOnChip:
    """The causal form of the kernels at the seq-4096 decoder fit's shape
    (heads of 128, 1024 x 1024 tiles): numbers against plain attention
    with a triangular mask, and the edges of `_bwd_fused_fits` there."""

    def test_seq4096_d128_forward_and_grads_match_reference(self):
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention, flash_attention)
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in _qkv(B=1, H=2, T=4096, D=128, seed=5))
        got = flash_attention(q, k, v, causal=True).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            ref = _reference_attention(*f32, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-2, atol=4e-3)

        def loss(attn, q, k, v):
            return jnp.sum(attn(q, k, v, causal=True).astype(
                jnp.float32) ** 2)
        gf = jax.grad(lambda *a: loss(flash_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
        with jax.default_matmul_precision("highest"):
            gr = jax.grad(lambda *a: loss(_reference_attention, *a),
                          argnums=(0, 1, 2))(*f32)
        for a, b in zip(gf, gr):
            a, b = np.asarray(a.astype(jnp.float32)), np.asarray(b)
            assert np.linalg.norm(a - b) <= 0.02 * np.linalg.norm(b)
        # later keys and values move nothing earlier
        v2 = v.at[:, :, 3000:].add(1.0)
        np.testing.assert_array_equal(
            np.asarray(got[:, :, :3000]),
            np.asarray(flash_attention(q, k, v2, causal=True).astype(
                jnp.float32)[:, :, :3000]))

    @pytest.mark.parametrize("T,D,dtype,kernels", [
        # T = 4096 at 1024 tiles reckons 14.56 MiB of the 15 that fit the
        # default (the log-sum-exp as row blocks and one column, PR 27);
        # one more tile of T (15.56) and the one kernel asks for 17 MiB
        # (PR 31; the pair before); T = 53248 asks for the ceiling's 64 MiB
        # and one more tile of T is the pair's
        (4096, 128, jnp.bfloat16, ["flash_bwd_fused_causal",
                                   "flash_fwd_causal"]),
        (5120, 128, jnp.bfloat16, ["flash_bwd_fused_causal",
                                   "flash_fwd_causal"]),
        (54272, 128, jnp.bfloat16, ["flash_dkv_causal", "flash_dq_causal",
                                    "flash_fwd_causal"]),
    ])
    def test_edges_of_the_gate_at_heads_of_128_compile(self, T, D, dtype,
                                                       kernels):
        import re

        from analytics_zoo_tpu.pallas import flash_attention as fa
        block = fa._auto_block(T)
        assert fa._bwd_fused_fits(block, block, T, D, jnp.dtype(
            dtype).itemsize) == (kernels[0] == "flash_bwd_fused_causal")
        # the fit's 32 head-batches (2 sequences x 16 heads)
        x = jax.ShapeDtypeStruct((2, 16, T, D), dtype)

        def loss(q, k, v):
            return fa.flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile().as_text()
        assert sorted(set(re.findall(
            r"(flash_(?:fwd|bwd_fused|dq|dkv)_causal)", text))) == kernels
        assert text.count('custom_call_target="tpu_custom_call"') \
            == len(kernels)


class TestTwoWidthFlashOnChip:
    """Keys wider than values (latent attention: 192 / 128) at the expert
    model's fit shape, T = 8192: numbers against plain attention, forward
    and all three gradients, and which kernels the shapes get."""

    def _qkv(self, T, seed=7):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return [(jax.random.normal(k, (1, 2, T, d), jnp.float32)
                 * 0.3).astype(jnp.bfloat16)
                for k, d in zip(ks, (192, 192, 128))]

    def test_seq8192_k192_v128_forward_and_grads_match_reference(self):
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention, flash_attention)
        from analytics_zoo_tpu.pallas import flash_attention as fa
        q, k, v = self._qkv(8192)
        # the backward held below is the one kernel that asks for 30 MiB
        assert fa._bwd_fused_vmem_limit(1024, 1024, 8192, 192, 2,
                                        128) == 30 * 2 ** 20
        got = flash_attention(q, k, v, causal=True).astype(jnp.float32)
        assert got.shape == (1, 2, 8192, 128)
        with jax.default_matmul_precision("highest"):
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            ref = _reference_attention(*f32, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-2, atol=4e-3)

        def loss(attn, q, k, v):
            return jnp.sum(attn(q, k, v, causal=True).astype(
                jnp.float32) ** 2)
        gf = jax.grad(lambda *a: loss(flash_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
        with jax.default_matmul_precision("highest"):
            gr = jax.grad(lambda *a: loss(_reference_attention, *a),
                          argnums=(0, 1, 2))(*f32)
        for a, b in zip(gf, gr):
            assert a.shape == b.shape
            a, b = np.asarray(a.astype(jnp.float32)), np.asarray(b)
            assert np.linalg.norm(a - b) <= 0.02 * np.linalg.norm(b)

    def test_seq16384_k192_v128_matches_plain_attention_head_by_head(self):
        """The hybrid model's one latent layer: T = 16,384 at two widths,
        where the one-kernel backward asks for 46 MiB of scoped VMEM;
        forward and all three gradients against plain attention, a head
        at a time (a head's 16,384 x 16,384 float32 scores are 1 GB)."""
        from analytics_zoo_tpu.pallas import flash_attention as fa
        T = 16384
        q, k, v = self._qkv(T, seed=11)
        assert fa._bwd_fused_vmem_limit(1024, 1024, T, 192, 2,
                                        128) == 46 * 2 ** 20

        def flash_loss(q, k, v):
            out = fa.flash_attention(q, k, v, causal=True)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        (_, got), gf = jax.jit(jax.value_and_grad(
            flash_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

        @jax.checkpoint
        def one_head(qkv):
            qh, kh, vh = (a.astype(jnp.float32)[None, None] for a in qkv)
            return fa._reference_attention(qh, kh, vh, causal=True)[0, 0]

        def plain_loss(q, k, v):
            out = jax.lax.map(one_head, (q[0], k[0], v[0]))[None]
            return jnp.sum(out ** 2), out
        with jax.default_matmul_precision("highest"):
            (_, ref), gr = jax.jit(jax.value_and_grad(
                plain_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                                   np.asarray(ref), rtol=2e-2, atol=4e-3)
        for a, b in zip(gf, gr):
            a = np.asarray(a.astype(jnp.float32))
            b = np.asarray(b.astype(jnp.float32))
            assert np.linalg.norm(a - b) <= 0.02 * np.linalg.norm(b)

    @pytest.mark.parametrize("T,kernels", [
        # a key width of 192 lies in 256 lanes (dQ, the q, k and dk blocks):
        # the one backward kernel fits the default scoped VMEM at one 1024
        # tile and asks for 30 MiB at the fit's 8192 (PR 31; the pair
        # before); past the ceiling (25,600 tokens) the pair
        (1024, ["flash_bwd_fused_causal_mla", "flash_fwd_causal_mla"]),
        (8192, ["flash_bwd_fused_causal_mla", "flash_fwd_causal_mla"]),
        (16384, ["flash_bwd_fused_causal_mla", "flash_fwd_causal_mla"]),
        (26624, ["flash_dkv_causal_mla", "flash_dq_causal_mla",
                 "flash_fwd_causal_mla"]),
    ])
    def test_which_backward_the_two_widths_get(self, T, kernels):
        import re

        from analytics_zoo_tpu.pallas import flash_attention as fa
        block = fa._auto_block(T)
        assert fa._bwd_fused_fits(block, block, T, 192, 2, 128) \
            == (kernels[0] == "flash_bwd_fused_causal_mla")
        q = jax.ShapeDtypeStruct((2, 32, T, 192), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((2, 32, T, 128), jnp.bfloat16)

        def loss(q, k, v):
            return fa.flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, v).compile().as_text()
        assert sorted(set(re.findall(
            r"(flash_(?:fwd|bwd_fused|dq|dkv)_causal_mla)", text))) \
            == kernels
        assert text.count('custom_call_target="tpu_custom_call"') \
            == len(kernels)

    def test_small_two_width_shape_runs_the_one_kernel_backward(self):
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention, flash_attention)
        q, k, v = self._qkv(1024, seed=9)

        def loss(attn, q, k, v):
            return jnp.sum(attn(q, k, v, causal=True).astype(
                jnp.float32) ** 2)
        gf = jax.grad(lambda *a: loss(flash_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
        with jax.default_matmul_precision("highest"):
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            gr = jax.grad(lambda *a: loss(_reference_attention, *a),
                          argnums=(0, 1, 2))(*f32)
        for a, b in zip(gf, gr):
            a, b = np.asarray(a.astype(jnp.float32)), np.asarray(b)
            assert np.linalg.norm(a - b) <= 0.02 * np.linalg.norm(b)


class TestGroupedMatmulOnChip:
    """The expert layer's grouped products against a loop over the groups:
    forward and both gradients, with an empty group, a one-row group and a
    group that takes every row, at the expert model's widths."""

    @pytest.mark.parametrize("sizes", [
        [700, 0, 1, 300, 999, 256, 0, 512],       # 2768 of 4096 rows
        [0, 0, 4096, 0, 0, 0, 0, 0],              # one group, every row
        [0, 0, 0, 0, 0, 0, 0, 1],
        [0, 0, 0, 0, 0, 0, 0, 0],
    ])
    @pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)])
    def test_matches_a_loop_over_the_groups(self, sizes, k, n):
        from analytics_zoo_tpu.pallas.grouped_matmul import grouped_matmul
        m, G = 4096, len(sizes)
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        lhs = (jax.random.normal(ks[0], (m, k)) * 0.5).astype(jnp.bfloat16)
        rhs = (jax.random.normal(ks[1], (G, k, n)) * 0.05).astype(
            jnp.bfloat16)
        cot = jax.random.normal(ks[2], (m, n), jnp.float32)
        gs = jnp.asarray(sizes, jnp.int32)
        starts = np.concatenate([[0], np.cumsum(sizes)])
        rows = jnp.arange(m)
        owned = (rows < starts[-1])[:, None]

        def system(lhs, rhs):
            out = grouped_matmul(lhs, rhs, gs).astype(jnp.float32)
            return jnp.sum(jnp.where(owned, out, 0.0) * cot)

        def loop(lhs, rhs):
            out = jnp.zeros((m, n), jnp.float32)
            for g in range(G):
                own = ((rows >= starts[g]) & (rows < starts[g + 1]))[:, None]
                out = out + jnp.where(own, lhs @ rhs[g], 0.0)
            return jnp.sum(out * cot)

        got = jax.jit(jax.value_and_grad(system, argnums=(0, 1)))(lhs, rhs)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(jax.value_and_grad(loop, argnums=(0, 1)))(
                lhs.astype(jnp.float32), rhs.astype(jnp.float32))
        scale = max(1.0, abs(float(want[0])))
        assert abs(float(got[0]) - float(want[0])) <= 0.02 * scale
        for a, b in zip(got[1], want[1]):
            a = np.asarray(a.astype(jnp.float32))
            a = np.where(np.asarray(owned), a, 0.0) if a.shape[0] == m \
                and a.ndim == 2 else a
            b = np.asarray(b)
            assert np.isfinite(a).all()
            assert np.linalg.norm(a - b) <= 0.02 * np.linalg.norm(b) + 1e-6


class TestDeltaRuleOnChip:
    """The chunked gated delta rule (`pallas/delta_rule.py`) at the hybrid
    model's fit shape, T = 16,384 and 32 heads of 128: the kernels
    `delta_prepare_fwd`, `kda_chunk_fwd`, `kda_chunk_bwd` and
    `delta_prepare_bwd` compiled by the chip's compiler and held, output
    and all five gradients, to the token-by-token recurrence
    of the benchmark's plain reference (float32, nested so that its
    gradient keeps 128 states and not 16,384), at the assumed
    initialisation's decay and at four times it: no inf, no NaN."""

    def _inputs(self, decay, dtype, T=16384, n=32, d=128, seed=3):
        ks = jax.random.split(jax.random.PRNGKey(seed), 7)
        q = jax.random.normal(ks[0], (n, T, d))
        k = jax.random.normal(ks[1], (n, T, d))
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.nn.silu(jax.random.normal(ks[2], (n, T, d)))
        # rates log-uniform in [1, 16) a head, steps in (0.001, 0.1) a
        # channel, a token's own softplus argument around them
        rate = jnp.exp(jax.random.uniform(ks[3], (n, 1, 1), minval=0.0,
                                          maxval=np.log(16.0)))
        step = jnp.exp(jax.random.uniform(ks[4], (n, 1, d),
                                          minval=np.log(1e-3),
                                          maxval=np.log(1e-1)))
        raw = step + jnp.log(-jnp.expm1(-step)) \
            + 0.5 * jax.random.normal(ks[5], (n, T, d))
        g = -decay * rate * jax.nn.softplus(raw)
        beta = jax.nn.sigmoid(jax.random.normal(ks[6], (n, T)))
        return (q.astype(dtype), k.astype(dtype), v.astype(dtype),
                g.astype(jnp.float32), beta.astype(jnp.float32))

    @staticmethod
    def _reference(q, k, v, g, beta):
        from benchmark.reference import kimi_linear as reference
        f32 = [a.astype(jnp.float32) for a in (q, k, v)]
        with jax.default_matmul_precision("highest"):
            return reference._delta_rule(
                *(jnp.moveaxis(a, 0, 1)[None] for a in f32),
                jnp.exp(jnp.moveaxis(g, 0, 1)[None]),
                jnp.moveaxis(beta, 0, 1)[None], True)[0].transpose(1, 0, 2)

    @pytest.mark.parametrize("decay", [1.0, 4.0])
    def test_seq16384_forward_and_five_gradients_match_the_recurrence(
            self, decay):
        from analytics_zoo_tpu.pallas import delta_rule as dr
        args = self._inputs(decay, jnp.bfloat16)
        cot = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

        def loss(fn):
            def inner(*a):
                out = fn(*a).astype(jnp.float32)
                return jnp.sum(out * cot), out
            return jax.jit(jax.value_and_grad(inner, argnums=(0, 1, 2, 3, 4),
                                              has_aux=True))
        (_, got), gs = loss(lambda *a: dr.gated_delta_rule(*a, chunk=64))(
            *args)
        (_, want), gr = loss(self._reference)(*args)
        rel = [float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))]
        assert bool(jnp.isfinite(got).all())
        # error that the state carries along: the last quarter against
        # the first
        quarter = got.shape[1] // 4
        by_quarter = [float(jnp.linalg.norm(
            (got - want)[:, i * quarter:(i + 1) * quarter])
            / jnp.linalg.norm(want[:, i * quarter:(i + 1) * quarter]))
            for i in range(4)]
        for a, b in zip(gs, gr):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            assert bool(jnp.isfinite(a).all())
            rel.append(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)))
        print(f"kda_onchip decay={decay} rel_err out,q,k,v,g,beta="
              f"{[round(r, 5) for r in rel]} out_by_quarter="
              f"{[round(r, 5) for r in by_quarter]}")
        assert rel[0] < 0.02 and by_quarter[3] < 0.03
        assert max(rel[1:]) < 0.05, rel

    def test_float32_forward_as_the_forward_check_runs_it(self):
        from analytics_zoo_tpu.pallas import delta_rule as dr
        args = self._inputs(1.0, jnp.float32, T=2048, n=8)
        got = jax.jit(lambda *a: dr.gated_delta_rule(*a, chunk=64))(*args)
        want = jax.jit(self._reference)(*args)
        assert got.dtype == jnp.float32
        assert float(jnp.linalg.norm(got - want)
                     / jnp.linalg.norm(want)) < 0.01

    def test_compiled_step_names_both_kernels_for_the_metrics(self):
        import json
        import os
        import re

        from analytics_zoo_tpu.pallas import delta_rule as dr
        from benchmark import trace_reduce
        x = jax.ShapeDtypeStruct((8, 512, 128), jnp.bfloat16)
        f = jax.ShapeDtypeStruct((8, 512, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((8, 512), jnp.float32)
        text = jax.jit(jax.grad(lambda *a: dr.gated_delta_rule(
            *a, chunk=64).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4))).lower(x, x, x, f, b).compile().as_text()
        kernels = [trace_reduce.op_name(re.sub(r"^\s*(ROOT )?", "", ln))
                   for ln in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln]
        assert sum("kda_chunk_fwd" in k for k in kernels) == 1, kernels
        assert sum("kda_chunk_bwd" in k for k in kernels) == 1, kernels
        # the chunk's preparation by its own two kernels (8 rows are one
        # group, so nothing is computed again here), and nothing of XLA's
        # triangular solve
        assert sum("delta_prepare_fwd" in k for k in kernels) == 1, kernels
        assert sum("delta_prepare_bwd" in k for k in kernels) == 1, kernels
        assert "InvertDiagBlocksLowerTriangular" not in text
        metrics_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), "benchmark", "layer_metrics")
        patterns = {}
        for metric in ("kda_time_share", "kda_roofline",
                       "kda_prepare_time_share"):
            with open(os.path.join(metrics_dir, metric + ".json")) as fh:
                patterns[metric] = re.compile(json.load(fh)["pattern"])
        for k in kernels:
            scan = bool(patterns["kda_time_share"].search(k))
            assert scan == bool(patterns["kda_roofline"].search(k)), k
            # every Pallas call is the scan's or the preparation's
            assert scan != bool(
                patterns["kda_prepare_time_share"].search(k)), k
            assert scan == ("kda_chunk" in k), k


class TestChunkedForwardOnChip:
    """PR 29: the forward walks its 1024 x 1024 DMA tile in two chunks of
    512 columns, at the two shapes the benchmark's cells run: compiled by
    the chip's compiler at the cells' head-batches and held, output and
    log-sum-exp, to plain attention."""

    @pytest.mark.parametrize("B,H,T,D,causal,padded", [
        (16, 12, 2048, 64, False, True),     # the seq-2048 fit: 192
        (2, 16, 4096, 128, True, False),     # the seq-4096 decoder fit: 32
    ])
    def test_cells_shapes_match_reference(self, B, H, T, D, causal, padded):
        from analytics_zoo_tpu.pallas import flash_attention as fa
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in _qkv(B=B, H=H, T=T, D=D, seed=7))
        mask = jnp.zeros((B, 1, 1, T), jnp.float32)
        if padded:      # every sequence another length, as the fit's batch
            lengths = T - 64 * jnp.arange(B)
            mask = jnp.where(jnp.arange(T)[None, :] < lengths[:, None],
                             0.0, -1e9)[:, None, None, :]
        got, res = jax.jit(lambda q, k, v, m: fa._flash_fwd(
            q, k, v, m, jnp.zeros((1, 1), jnp.int32), 0.0, 1024, 1024,
            False, causal))(q, k, v, mask)
        assert fa._fwd_chunk(1024) == 512

        def plain(q, k, v, m):      # one sequence at a time: [H, T, T] f32
            s = jnp.einsum("hqd,hkd->hqk", q, k,
                           preferred_element_type=jnp.float32) / np.sqrt(D)
            s = s + m
            if causal:
                s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
            w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            return (jnp.einsum("hqk,hkd->hqd", w, v),
                    jax.nn.logsumexp(s, axis=-1))
        ref, ref_lse = jax.lax.map(lambda a: plain(*a),
                                   (q, k, v, mask[:, 0]))
        np.testing.assert_allclose(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(ref.astype(jnp.float32)), rtol=2e-2, atol=4e-3)
        np.testing.assert_allclose(
            np.asarray(res[-1].reshape(B, H, T)), np.asarray(ref_lse),
            rtol=1e-3, atol=2e-3)


class TestFitOnChip:
    def test_one_fit_step_through_estimator(self):
        import optax

        from analytics_zoo_tpu.common.context import (init_orca_context,
                                                      stop_orca_context)
        from analytics_zoo_tpu.learn.estimator import Estimator
        from analytics_zoo_tpu.models.bert import BERTClassifier
        from analytics_zoo_tpu.ops import objectives
        stop_orca_context()          # drop any CPU-mesh context
        init_orca_context(cluster_mode="local")
        model = BERTClassifier(num_classes=2, vocab=128, hidden_size=64,
                               n_block=2, n_head=2, seq_len=64,
                               intermediate_size=128)
        est = Estimator.from_keras(
            model, optimizer=optax.adamw(1e-4),
            loss=objectives.get("sparse_categorical_crossentropy",
                                from_logits=True))
        rs = np.random.RandomState(0)
        n, T = 16, 64
        data = {"x": [rs.randint(0, 128, (n, T)).astype(np.int32),
                      np.ones((n, T), np.float32)],
                "y": rs.randint(0, 2, (n,)).astype(np.int32)}
        h = est.fit(data, epochs=1, batch_size=8, steps_per_run=2,
                    mixed_precision=True)
        assert np.isfinite(h["loss"][0])
        assert jax.devices()[0].platform == "tpu"

    def test_sharded_train_step_mesh1_on_chip(self):
        """build_sharded_train_step at mesh=1 ON the chip: Mosaic/GSPMD
        interactions the CPU suite can't see."""
        import optax

        from analytics_zoo_tpu.common.context import (get_context,
                                                      init_orca_context,
                                                      stop_orca_context)
        from analytics_zoo_tpu.ops import objectives
        from analytics_zoo_tpu.parallel.sharding import (
            build_sharded_train_step, shard_batch, shard_params)
        stop_orca_context()
        init_orca_context(cluster_mode="local")
        mesh = get_context().mesh
        rs = np.random.RandomState(0)
        params = {"w": jnp.asarray(rs.randn(16, 4).astype(np.float32)),
                  "b": jnp.zeros((4,), jnp.float32)}

        def apply_fn(p, xb, training=False, rng=None):
            return xb @ p["w"] + p["b"]

        loss_obj = objectives.get("sparse_categorical_crossentropy",
                                  from_logits=True)
        opt = optax.adamw(1e-3)
        params = shard_params(params, mesh)
        opt_state = opt.init(params)
        step = build_sharded_train_step(apply_fn, loss_obj, opt)
        xb = shard_batch(rs.randn(8, 16).astype(np.float32), mesh)
        yb = shard_batch(rs.randint(0, 4, (8,)).astype(np.int32), mesh)
        params, opt_state, loss = step(params, opt_state, xb, yb,
                                       jax.random.PRNGKey(0))
        assert np.isfinite(float(loss))

    def test_lazy_embeddings_fit_on_chip(self):
        """lazy_embeddings=True through Estimator.fit on the real chip:
        the XLA row-adam scatter path (no fused kernels)."""
        from analytics_zoo_tpu.common.context import (init_orca_context,
                                                      stop_orca_context)
        from analytics_zoo_tpu.learn.estimator import Estimator
        from analytics_zoo_tpu.models.recommendation import NeuralCF
        stop_orca_context()
        init_orca_context(cluster_mode="local")
        ncf = NeuralCF(user_count=500, item_count=200, class_num=2,
                       mf_embed=8, user_embed=8, item_embed=8,
                       hidden_layers=(16, 8))
        est = Estimator.from_keras(
            ncf.model, optimizer="adam",
            loss="sparse_categorical_crossentropy")
        rs = np.random.RandomState(0)
        n = 256
        x = np.stack([rs.randint(1, 500, n), rs.randint(1, 200, n)],
                     axis=1).astype(np.int32)
        y = rs.randint(0, 2, n).astype(np.int32)
        h = est.fit((x, y), epochs=2, batch_size=64, lazy_embeddings=True)
        assert np.isfinite(h["loss"]).all()
        assert h["loss"][-1] <= h["loss"][0] + 0.1  # training, not diverging

    def test_stacked_bert_fit_on_chip(self):
        """BERT(stacked=True) through Estimator.fit on the real chip:
        lax.scan over stacked block params + Mosaic dropout kernels
        inside the scan body — interactions the CPU parity tests can't
        exercise."""
        import optax

        from analytics_zoo_tpu.common.context import (init_orca_context,
                                                      stop_orca_context)
        from analytics_zoo_tpu.learn.estimator import Estimator
        from analytics_zoo_tpu.models.bert import BERTClassifier
        from analytics_zoo_tpu.ops import objectives
        stop_orca_context()
        init_orca_context(cluster_mode="local")
        rs = np.random.RandomState(0)
        model = BERTClassifier(
            num_classes=2, vocab=500, hidden_size=64, n_block=3, n_head=4,
            seq_len=32, intermediate_size=128, stacked=True)
        est = Estimator.from_keras(
            model, optimizer=optax.adamw(1e-3),
            loss=objectives.get("sparse_categorical_crossentropy",
                                from_logits=True))
        n = 64
        data = {"x": [rs.randint(0, 500, (n, 32)).astype(np.int32),
                      np.ones((n, 32), np.float32)],
                "y": rs.randint(0, 2, (n,)).astype(np.int32)}
        h = est.fit(data, epochs=2, batch_size=16, mixed_precision=True,
                    steps_per_run=2)
        assert np.isfinite(h["loss"]).all()
        assert h["loss"][-1] <= h["loss"][0] + 0.1  # training, not diverging

    def test_fused_optimizer_fit_on_chip(self):
        """fit(fused_optimizer=True) ON the chip: the Pallas fused-Adam
        sweep lowers through Mosaic (the CPU suite only ever exercises
        the interpreter), updates in place via input_output_aliases,
        and must reproduce the plain optax path's losses. Mixed bucket
        spectrum on purpose: embedding (singleton big leaf), stacked
        matmuls, sub-tile biases."""
        from analytics_zoo_tpu.common.context import (init_orca_context,
                                                      stop_orca_context)
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        stop_orca_context()
        init_orca_context(cluster_mode="local")

        def mk():
            m = Sequential()
            m.add(L.Embedding(300, 32, input_shape=(8,)))
            m.add(L.Flatten())
            m.add(L.Dense(64, activation="relu"))
            m.add(L.Dense(64, activation="relu"))
            m.add(L.Dense(2))
            m.compile(optimizer="adamw",
                      loss="sparse_categorical_crossentropy")
            return m

        rs = np.random.RandomState(0)
        x = rs.randint(0, 300, (256, 8)).astype(np.float32)
        y = rs.randint(0, 2, 256).astype(np.int32)
        h = mk().fit(x, y, batch_size=64, nb_epoch=2, fused_optimizer=True,
                     mixed_precision=True, steps_per_run=2)
        assert np.isfinite(h["loss"]).all()
        # numerics must match the plain optax path on the same chip
        h2 = mk().fit(x, y, batch_size=64, nb_epoch=2,
                      mixed_precision=True, steps_per_run=2)
        np.testing.assert_allclose(h["loss"], h2["loss"], rtol=2e-3)


class TestOnChipPipelines:
    """End-to-end subsystem drives that only a real chip exercises the
    same way production does: TFRecord streaming into fit, and the
    serving loop's bucketed jit predict."""

    def test_streaming_tfrecord_fit_on_chip(self, tmp_path):
        from analytics_zoo_tpu.data import tfrecord as tfr
        from analytics_zoo_tpu.data.dataset import TPUDataset
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        from analytics_zoo_tpu.learn.estimator import Estimator
        rs = np.random.RandomState(0)
        recs = []
        for _ in range(96):
            x = rs.randn(8).astype(np.float32)
            # learnable label (a function of x), so the loss-decrease
            # assertion tests optimization, not memorization noise
            recs.append(tfr.encode_example(
                {"x": x,
                 "y": np.asarray([float(x.sum() > 0)], np.float32)}))
        path = str(tmp_path / "t.tfrecord")
        tfr.write_tfrecord(path, recs)
        ds = TPUDataset.from_tfrecord(
            path, lambda ex: (ex["x"], ex["y"]), batch_size=32)
        m = Sequential([L.Dense(8, input_shape=(8,), activation="relu"),
                        L.Dense(1, activation="sigmoid")])
        est = Estimator.from_keras(m, optimizer="adam",
                                   loss="binary_crossentropy")
        hist = est.fit(ds, epochs=4)
        assert hist["loss"][-1] < hist["loss"][0]

    def test_serving_loop_on_chip(self):
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        from analytics_zoo_tpu.serving import (ClusterServing,
                                               InferenceModel, InputQueue,
                                               MemoryBroker)
        m = Sequential([L.Dense(3, input_shape=(4,))])
        m.ensure_built(np.zeros((1, 4), np.float32))
        im = InferenceModel()
        im.load_keras(m)
        broker = MemoryBroker()
        serving = ClusterServing(im, broker).start()
        try:
            q = InputQueue(broker)
            inputs = [np.full(4, i, np.float32) for i in range(5)]
            outs = q.predict_batch(inputs, timeout_s=120)
            assert len(outs) == 5
            # values, not just shapes: results must pair with THEIR input
            direct = np.asarray(m.predict(np.stack(inputs),
                                          batch_per_thread=5))
            for o, want in zip(outs, direct):
                np.testing.assert_allclose(np.asarray(o), want,
                                           rtol=1e-5, atol=1e-6)
        finally:
            serving.stop()


class TestLargeBlocks:
    """Auto block sizing picks min(T, 1024) — verify numerics at a seq
    length that exercises the 1024-wide tiles fwd AND bwd."""

    def test_seq2048_matches_reference(self):
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention, flash_attention)
        q, k, v = _qkv(B=1, H=2, T=2048)
        got = np.asarray(flash_attention(q, k, v))
        ref = np.asarray(_reference_attention(q, k, v))
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)

    def test_seq2048_grads_match_reference(self):
        from analytics_zoo_tpu.pallas.flash_attention import (
            _reference_attention, flash_attention)
        q, k, v = _qkv(B=1, H=2, T=2048, seed=3)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(_reference_attention(q, k, v) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-2, atol=5e-3)


class TestDecodeAttentionOnChip:
    """The generative decode-step kernel (`pallas/decode_attention.py`)
    vs its exact reference — the CPU suite only ever runs the reference
    path, so the Mosaic lowering (pool read in place, SMEM lengths,
    online softmax across k-blocks) is exercised here only."""

    def _pool(self, S=8, H=4, L=256, D=64, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (S, H, D), jnp.float32) * 0.3
        k = jax.random.normal(ks[1], (S, H, L, D), jnp.float32) * 0.3
        v = jax.random.normal(ks[2], (S, H, L, D), jnp.float32) * 0.3
        return q, k, v

    def test_matches_reference_mixed_lengths(self):
        from analytics_zoo_tpu.pallas.decode_attention import (
            _reference_decode_attention, decode_attention)
        q, k, v = self._pool()
        # spans both k-blocks; includes length 1 (single live position)
        # and a fully-masked second block
        lengths = jnp.asarray([1, 7, 64, 128, 129, 200, 255, 256],
                              jnp.int32)
        got = np.asarray(decode_attention(q, k, v, lengths, kv_bucket=256))
        ref = np.asarray(_reference_decode_attention(q, k, v, lengths,
                                                     kv_bucket=256))
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)

    def test_bucket_window_ignores_pool_tail(self):
        from analytics_zoo_tpu.pallas.decode_attention import (
            _reference_decode_attention, decode_attention)
        q, k, v = self._pool(seed=1)
        lengths = jnp.asarray([3, 9, 17, 33, 48, 64, 64, 64], jnp.int32)
        # kv_bucket < L: positions >= 64 must never be read; poisoning
        # the tail makes any out-of-window access visible as NaN
        k = k.at[:, :, 64:].set(jnp.nan)
        v = v.at[:, :, 64:].set(jnp.nan)
        got = np.asarray(decode_attention(q, k, v, lengths, kv_bucket=64))
        ref = np.asarray(_reference_decode_attention(q, k, v, lengths,
                                                     kv_bucket=64))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)


class TestPagedDecodeAttentionOnChip:
    """`paged_decode_attention` under Mosaic: block tables ride in as a
    scalar-prefetch operand and the k/v index maps dereference them. The
    CPU suite runs the kernel through the interpreter only."""

    def _case(self, S, H, D, block_len, n_kb, num_blocks, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (S, H, D), jnp.float32) * 0.3
        k = jax.random.normal(ks[1], (num_blocks, H, block_len, D),
                              jnp.float32) * 0.3
        v = jax.random.normal(ks[2], (num_blocks, H, block_len, D),
                              jnp.float32) * 0.3
        # every slot's logical blocks scattered over the pool, no sharing
        perm = np.random.RandomState(seed).permutation(num_blocks)
        tables = jnp.asarray(perm[:S * n_kb].reshape(S, n_kb), jnp.int32)
        return q, k, v, tables

    def test_matches_reference_scattered_blocks(self):
        from analytics_zoo_tpu.pallas.decode_attention import (
            _reference_paged_decode_attention, paged_decode_attention)
        q, k, v, tables = self._case(S=8, H=4, D=64, block_len=64, n_kb=4,
                                     num_blocks=40)
        # length 1, block boundaries, a fully-masked trailing block
        lengths = jnp.asarray([1, 7, 64, 65, 128, 129, 255, 256], jnp.int32)
        got = np.asarray(paged_decode_attention(q, k, v, tables, lengths,
                                                kv_bucket=256))
        ref = np.asarray(_reference_paged_decode_attention(
            q, k, v, tables, lengths, kv_bucket=256))
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)

    def test_bucket_reads_only_its_table_prefix(self):
        from analytics_zoo_tpu.pallas.decode_attention import (
            _reference_paged_decode_attention, paged_decode_attention)
        q, k, v, tables = self._case(S=8, H=4, D=64, block_len=64, n_kb=4,
                                     num_blocks=40, seed=1)
        # kv_bucket covers the first 2 table entries: poison every block
        # the later entries name, so any read past the bucket shows as NaN
        late = np.asarray(tables)[:, 2:].reshape(-1)
        k = k.at[late].set(jnp.nan)
        v = v.at[late].set(jnp.nan)
        lengths = jnp.asarray([3, 9, 17, 33, 64, 100, 127, 128], jnp.int32)
        got = np.asarray(paged_decode_attention(q, k, v, tables, lengths,
                                                kv_bucket=128))
        ref = np.asarray(_reference_paged_decode_attention(
            q, k, v, tables, lengths, kv_bucket=128))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)

    def test_engine_default_geometry(self):
        """The shapes DecodeServing runs today: TinyDecoder's 2 heads of
        8 dims over block_len-16 blocks (`ServingConfig.decode_block_len`)."""
        from analytics_zoo_tpu.pallas.decode_attention import (
            _reference_paged_decode_attention, paged_decode_attention)
        q, k, v, tables = self._case(S=4, H=2, D=8, block_len=16, n_kb=4,
                                     num_blocks=33, seed=2)
        lengths = jnp.asarray([1, 16, 17, 64], jnp.int32)
        got = np.asarray(paged_decode_attention(q, k, v, tables, lengths,
                                                kv_bucket=64))
        ref = np.asarray(_reference_paged_decode_attention(
            q, k, v, tables, lengths, kv_bucket=64))
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)


class TestFusedAdamOnChip:
    """The fused Adam kernel at BERT-base leaf shapes, 1-row bias leaves
    included (`_block_rows` on a (1, 768) view), and at the NCF bench's
    64-wide embedding table (lanes pad to 128 in VMEM: the block budget
    must count them, or Mosaic runs out of scoped VMEM), against optax."""

    def test_bert_leaf_shapes_match_optax(self):
        import optax

        from analytics_zoo_tpu.pallas.fused_adam import fused_adam_step
        rs = np.random.RandomState(0)
        shapes = {"qkv": (768, 2304), "bias": (768,), "ln": (768,),
                  "cls": (768, 2), "odd": (3, 5, 11), "pos": (128, 768),
                  "ncf_table": (138001, 64)}
        p = {k: jnp.asarray(rs.randn(*s), jnp.float32) * 0.1
             for k, s in shapes.items()}
        g = jax.tree_util.tree_map(lambda a: a * 0.01 + 1e-3, p)
        z = jax.tree_util.tree_map(jnp.zeros_like, p)
        step = jax.jit(lambda p, m, v, g, t: fused_adam_step(
            p, m, v, g, t, lr=1e-3, weight_decay=0.01))
        cur, mu, nu = p, z, z
        for t in (1, 2, 3):
            cur, mu, nu = step(cur, mu, nu, g, t)
        opt = optax.adamw(1e-3, weight_decay=0.01)
        ref, state = p, opt.init(p)
        for _ in range(3):
            upd, state = opt.update(g, state, ref)
            ref = optax.apply_updates(ref, upd)
        for name in shapes:
            np.testing.assert_allclose(np.asarray(cur[name]),
                                       np.asarray(ref[name]),
                                       rtol=1e-5, atol=1e-6)


class TestSegmentAdamOnChip:
    @pytest.mark.xfail(
        strict=True, raises=ValueError,
        reason="ROADMAP D12: the (1, dim) row blocks of "
               "pallas/segment_update.py break Mosaic's block rule (last "
               "two block dims multiples of (8, 128) or the whole array "
               "extent); the kernel raises on TPU and has no fallback")
    def test_matches_row_adam_update(self):
        from analytics_zoo_tpu.learn.lazy_embedding import (
            LazyEmbeddingSpec, row_adam_update)
        from analytics_zoo_tpu.pallas.segment_update import \
            segment_adam_update
        rs = np.random.RandomState(1)
        V, D, B = 1000, 64, 256                  # NCF-bench embedding width
        table = jnp.asarray(rs.randn(V, D), jnp.float32)
        z = jnp.zeros((V, D))
        ids = jnp.asarray(rs.randint(0, V, B), jnp.int32)   # duplicates
        rows = jnp.asarray(rs.randn(B, D), jnp.float32)
        g_table = jnp.zeros((V, D)).at[ids].add(rows)
        spec = LazyEmbeddingSpec(path=("t",), ids_fn=None, lr=1e-3)
        rt, rm, rv = row_adam_update(spec, table, z, z, g_table, ids,
                                     jnp.asarray(1, jnp.int32))
        ft, fm, fv = jax.jit(lambda *a: segment_adam_update(
            *a, 1, lr=1e-3))(table, z, z, ids, rows)
        touched = np.zeros(V, bool)
        touched[np.asarray(ids)] = True
        assert (np.asarray(ft)[~touched] == np.asarray(table)[~touched]).all()
        for ref, got in ((rt, ft), (rm, fm), (rv, fv)):
            np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                       rtol=1e-5, atol=1e-7)


class TestFusedDropout:
    """Pallas in-kernel-RNG dropout (`pallas/dropout.py`): determinism,
    mask/grad bit-identity (the VJP regenerates, never stores), and the
    unbiased u8 default."""

    def test_pallas_deterministic_and_scaled(self, monkeypatch):
        from analytics_zoo_tpu.pallas.dropout import fused_dropout
        monkeypatch.setenv("ZOO_DROPOUT_IMPL", "pallas")
        x = jnp.ones((256, 384), jnp.float32)
        a = np.asarray(fused_dropout(x, 0.1, seed=jnp.int32(11)))
        b = np.asarray(fused_dropout(x, 0.1, seed=jnp.int32(11)))
        np.testing.assert_array_equal(a, b)
        assert abs((a != 0).mean() - 0.9) < 0.02
        np.testing.assert_allclose(a[a != 0], 1.0 / 0.9, rtol=1e-6)

    def test_pallas_grad_regenerates_same_mask(self, monkeypatch):
        from analytics_zoo_tpu.pallas.dropout import fused_dropout
        monkeypatch.setenv("ZOO_DROPOUT_IMPL", "pallas")
        x = jnp.ones((128, 256), jnp.float32)
        seed = jnp.int32(5)
        out = np.asarray(fused_dropout(x, 0.2, seed=seed))
        g = np.asarray(jax.grad(
            lambda x: jnp.sum(fused_dropout(x, 0.2, seed=seed)))(x))
        np.testing.assert_array_equal(g != 0, out != 0)

    def test_u8_default_on_tpu(self):
        import os
        from analytics_zoo_tpu.pallas.dropout import fused_dropout
        assert os.environ.get("ZOO_DROPOUT_IMPL") is None
        x = jnp.ones((128, 256), jnp.bfloat16)
        out = np.asarray(fused_dropout(x, 0.1, rng=jax.random.PRNGKey(0)),
                         np.float32)
        t = round(0.9 * 256)
        np.testing.assert_allclose(out[out != 0], 256.0 / t, rtol=1e-2)


class TestKernelNamesOnChip:
    def test_compiled_flash_fit_step_names_each_kernel(self):
        """The compiled train step of a flash fit holds one instruction
        named after each kernel (`pl.pallas_call(name=...)`): the
        benchmark's per-kernel shares rest on these names, and the
        pattern of the accepted `flash_time_share` still matches all of
        them."""
        import json
        import os
        import re

        import optax

        from analytics_zoo_tpu.learn import trainer
        from analytics_zoo_tpu.models.bert import BERTClassifier
        from analytics_zoo_tpu.ops import objectives
        from benchmark import trace_reduce
        T = 2048       # 1024x1024 tiles: one backward kernel, chunked
        model = BERTClassifier(num_classes=2, vocab=128, hidden_size=128,
                               n_block=1, n_head=2, seq_len=T,
                               intermediate_size=128, use_flash=True)
        rs = np.random.RandomState(0)
        xb = [jnp.asarray(rs.randint(0, 128, (2, T)).astype(np.int32)),
              jnp.ones((2, T), jnp.float32)]
        yb = jnp.asarray(rs.randint(0, 2, (2,)).astype(np.int32))
        model.ensure_built(xb, jax.random.PRNGKey(0))
        opt = optax.adamw(1e-4)
        step = trainer.build_train_step(
            model.apply, objectives.get("sparse_categorical_crossentropy",
                                        from_logits=True),
            opt, mixed_precision=True)
        text = step.lower(model.params, opt.init(model.params), xb, yb,
                          jax.random.PRNGKey(1)).compile().as_text()
        kernels = [trace_reduce.op_name(re.sub(r"^\s*(ROOT )?", "", ln))
                   for ln in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln]
        for word in ("flash_fwd", "flash_bwd_fused"):
            assert sum(word in k for k in kernels) == 1, (word, kernels)
        metrics_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), "benchmark", "layer_metrics")

        def matched(metric):
            with open(os.path.join(metrics_dir, metric + ".json")) as fh:
                pattern = re.compile(json.load(fh)["pattern"])
            return sum(bool(pattern.search(k)) for k in kernels)
        assert matched("flash_time_share") == len(kernels) == 2, kernels
        for metric, n in (("flash_fwd_time_share", 1),
                          ("flash_bwd_fused_time_share", 1),
                          ("flash_dq_time_share", 0),
                          ("flash_dkv_time_share", 0)):
            assert matched(metric) == n, (metric, kernels)


class TestShortConvOnChip:
    """The KDA layer's q/k/v stage (`pallas/short_conv.py`) at the hybrid
    model's fit shape, one of q, k, v `[1, 16384, 4096]` as 32 heads of
    128: `qkv_short_conv_fwd` and `qkv_short_conv_bwd` compiled by the
    chip's compiler and held, rows and both gradients, to XLA's
    `_conv_unit` in float32 on the same values."""

    def _sides(self, dtype, scale, T=16384, n=32, w=128):
        from analytics_zoo_tpu.keras.linear_attention import (
            _L2_EPS, KimiDeltaAttention)
        from analytics_zoo_tpu.pallas import short_conv as sc
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        x = jax.random.normal(ks[0], (1, T, n * w)).astype(dtype)
        taps = jax.random.uniform(ks[1], (4, n * w), minval=-0.5, maxval=0.5)
        cot = jax.random.normal(ks[2], (n, T, w)).astype(dtype)
        layer = KimiDeltaAttention(2304, n, w, name="kda_onchip")
        assert sc.short_conv_fits(x.shape, n, 4, None)

        def both(fn):
            def run(x, taps, cot):
                rows, vjp = jax.vjp(fn, x, taps)
                return (rows, *vjp(cot))
            return jax.jit(run)
        f32 = jnp.float32
        got = both(lambda x, t: sc.short_conv_rows(x, t, n, scale, _L2_EPS))(
            x, taps, cot)
        want = both(lambda x, t: layer._conv_unit(x, t, scale))(
            x.astype(f32), taps, cot.astype(f32))
        rel = []
        for a, b in zip(got, want):
            assert a.shape == b.shape and bool(jnp.isfinite(a).all())
            rel.append(float(jnp.linalg.norm(a.astype(f32) - b)
                             / jnp.linalg.norm(b)))
        assert got[0].dtype == got[1].dtype == dtype
        print(f"short_conv_onchip {jnp.dtype(dtype).name} scale={scale} "
              f"rel_err rows,d_projection,d_taps={rel}")
        return rel

    @pytest.mark.parametrize("scale", [128 ** -0.5, None],
                             ids=["q_norm_and_scale", "v_plain"])
    def test_seq16384_bfloat16_rows_and_both_gradients(self, scale):
        rows, d_projection, d_taps = self._sides(jnp.bfloat16, scale)
        # one rounding of a result to bfloat16: 2^-9 an element
        assert rows < 2.0 ** -9 and d_projection < 2.0 ** -9
        assert d_taps < 1e-4                  # summed in float32

    @pytest.mark.parametrize("scale", [1.0, None],
                             ids=["k_norm", "v_plain"])
    def test_float32_as_the_forward_check_runs_it(self, scale):
        assert max(self._sides(jnp.float32, scale, T=4096)) < 1e-5
