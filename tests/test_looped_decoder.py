"""The looped causal language model (`models/looped_decoder.py`), its
layers (RMS norm, rotary positions, the decoder block) and the blockwise
language-model loss, on the CPU at the tiny preset of the benchmark
configuration's `rehearsal` group (hidden 64, 2 heads x 32, 2 layers, 4
passes, vocabulary 211, T = 128), against the plain float32 reference
under `benchmark/reference/` (which shares no code with `keras/`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.layers import RMSNormalization
from analytics_zoo_tpu.keras.transformer import (TransformerDecoderBlock,
                                                 apply_rotary, rotary_tables)
from analytics_zoo_tpu.models.looped_decoder import LoopedDecoderLM
from analytics_zoo_tpu.ops import objectives
from analytics_zoo_tpu.ops.objectives import ProjectedLogits
from benchmark import harness
from benchmark.models import ouro_lm as family
from benchmark.reference import ouro_lm as reference

CELL = "ouro-2.6b.fit-seq4096"
T = 128


@pytest.fixture(scope="module")
def tiny():
    cell = harness.load_cell(CELL, rehearse=True)
    config, traffic = cell["config"], cell["traffic"]
    model = family.build(config, traffic)
    params = family.init_params(model, jax.random.PRNGKey(7))
    # every leaf off its initial value, so that no norm scale is 1, no
    # bias 0, and a dropped or swapped leaf shows
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(8), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape)
        for a, k in zip(leaves, keys)])
    batch = family.step_batch(config, traffic, 3, 2)
    return config, traffic, model, params, batch


LOSS_BLOCK_TOKENS = objectives._LOSS_BLOCK_TOKENS


@pytest.fixture(autouse=True)
def small_loss_blocks(monkeypatch):
    """The tiny step's 256 tokens in three blocks, the last one padded."""
    monkeypatch.setattr(objectives, "_LOSS_BLOCK_TOKENS", 96)


def _loss():
    return objectives.get("sparse_categorical_crossentropy",
                          from_logits=True)


class TestAgainstTheReference:
    def test_preset_is_the_issues(self, tiny):
        config, traffic, model, params, _ = tiny
        assert (config["hidden_size"], config["num_attention_heads"],
                config["head_dim"], config["num_hidden_layers"],
                config["total_ut_steps"], config["vocab_size"],
                traffic["seq_len"]) == (64, 2, 32, 2, 4, 211, T)
        # stack_block_params' layout: one [N, ...] buffer a tensor
        assert params["blocks"]["attn"]["qkv_kernel"].shape == (2, 64, 192)

    def test_logits_and_gates(self, tiny):
        config, _, model, params, batch = tiny
        logits, gates = jax.jit(model.forward)(params, batch["x"])
        want_logits, want_gates = reference.reference_forward(
            params, batch["x"], config)
        assert logits.shape == (2, T, 211) and gates.shape == (2, T, 4)
        np.testing.assert_allclose(logits, want_logits, atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(gates, want_gates, atol=1e-5)
        assert float(jnp.std(gates)) > 1e-3       # not a constant
        # apply (inference) is the forward's logits
        np.testing.assert_array_equal(
            jax.jit(lambda p, a: model.apply(p, a))(params, batch["x"]),
            logits)

    def test_loss_and_every_gradient_leaf(self, tiny):
        config, _, model, params, batch = tiny
        loss_fn = _loss()
        loss, grads = jax.jit(jax.value_and_grad(lambda p: loss_fn(
            batch["y"], model.apply(p, batch["x"], training=True))))(params)
        want_loss, want = family.reference_loss_and_grads(params, batch,
                                                          config)
        assert float(loss) == pytest.approx(float(want_loss), abs=1e-5)
        whole = np.sqrt(sum(float(jnp.sum(g ** 2))
                            for g in jax.tree_util.tree_leaves(want)))
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
            err = float(jnp.linalg.norm(g - w))
            name = jax.tree_util.keystr(path)
            if "exit_gate" in name:
                # the gate enters neither the logits nor the loss
                assert float(jnp.abs(g).max()) == 0.0 == \
                    float(jnp.abs(w).max()), name
            else:
                assert err <= 2e-4 * float(jnp.linalg.norm(w)) \
                    + 1e-6 * whole, (name, err)

    def test_reference_gradients_by_the_sequence_are_the_whole_batchs(
            self, tiny):
        """The step check's reference adds up `value_and_grad` sequence by
        sequence: the same numbers as the batch differentiated whole, and
        not those of a batch with a sequence left out."""
        config, _, _, params, batch = tiny
        loss, grads = family.reference_loss_and_grads(params, batch, config)
        whole = jax.value_and_grad(lambda p: reference.reference_loss(
            p, batch, config))(params)
        first = jax.value_and_grad(lambda p: reference.reference_loss(
            p, {k: v[:1] for k, v in batch.items()}, config))(params)
        for got, want, other in zip(
                jax.tree_util.tree_leaves((loss, grads)),
                jax.tree_util.tree_leaves(whole),
                jax.tree_util.tree_leaves(first)):
            np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)
            if float(jnp.abs(want).max()) > 0:
                assert float(jnp.abs(want - other).max()) \
                    > 1e-3 * float(jnp.abs(want).max())

    def test_the_step_check_sees_the_timed_batch(self):
        cell = harness.load_cell(CELL, rehearse=False)
        assert cell["config"]["reference_check"]["step_samples"] \
            == cell["traffic"]["batch_size"] == 2

    @pytest.mark.parametrize("fault", sorted(family.FAULTS))
    def test_each_fault_of_the_reference_moves_the_logits(self, tiny, fault):
        config, _, model, params, batch = tiny
        good = reference.reference_logits(params, batch["x"], config)
        bad = reference.reference_logits(params, batch["x"], config,
                                         **family.FAULTS[fault])
        assert float(jnp.sqrt(jnp.mean((good - bad) ** 2))) > 0.01


class TestTheLoop:
    def _unrolled(self, model, params, ids, n_pass):
        """The same layers as plain Python loops: n_pass x N block calls
        on indexed (tied) weights, the final norm after every pass."""
        h = jnp.take(params["word_embeddings"], ids, axis=0)
        rotary = rotary_tables(ids.shape[1], model.head_dim,
                               model.rope_theta)
        for _ in range(n_pass):
            for i in range(model.n_block):
                bp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                h = model.block.call(bp, [h, rotary])
            h = model.final_norm.call(params["final_norm"], h)
        return h @ params["lm_head_kernel"]

    def test_one_pass_is_the_plain_stack(self, tiny):
        config, traffic, _, params, batch = tiny
        one = family.build(dict(config, total_ut_steps=1), traffic)
        np.testing.assert_allclose(
            one.apply(params, batch["x"]),
            self._unrolled(one, params, batch["x"], 1), atol=1e-5)

    def test_four_passes_sum_the_gradients_of_tied_weights(self, tiny):
        _, _, model, params, batch = tiny
        loss_fn = _loss()

        def looped(p):
            return loss_fn(batch["y"],
                           model.apply(p, batch["x"], training=True))

        def unrolled(p):
            return loss_fn(batch["y"],
                           self._unrolled(model, p, batch["x"], 4))
        g1, g2 = jax.grad(looped)(params), jax.grad(unrolled)(params)
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-4)
        # and not the gradient of one use alone
        one_use = jax.grad(lambda p: loss_fn(batch["y"], self._unrolled(
            model, p, batch["x"], 1)))(params)
        a = g1["blocks"]["ffn_up_kernel"]
        assert float(jnp.linalg.norm(a - one_use["blocks"]["ffn_up_kernel"])
                     ) > 0.1 * float(jnp.linalg.norm(a))

    def test_recomputation_changes_no_number(self, tiny):
        config, traffic, model, params, batch = tiny
        plain = family.build(config, dict(traffic, model_kwargs={
            "use_flash": False, "remat": False}))
        assert model.remat and not plain.remat
        loss_fn = _loss()
        g = [jax.grad(lambda p, m=m: loss_fn(batch["y"], m.apply(
            p, batch["x"], training=True)))(params) for m in (model, plain)]
        for a, b in zip(*map(jax.tree_util.tree_leaves, g)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)

    def test_later_tokens_do_not_move_earlier_logits(self, tiny):
        _, _, model, params, batch = tiny
        ids = np.array(batch["x"])
        other = ids.copy()
        other[:, 70:] = (other[:, 70:] + 5) % 211
        a, ga = model.forward(params, ids)
        b, gb = model.forward(params, other)
        np.testing.assert_array_equal(a[:, :70], b[:, :70])
        np.testing.assert_array_equal(ga[:, :70], gb[:, :70])
        assert float(jnp.abs(a[:, 70:] - b[:, 70:]).max()) > 1e-3

    def test_gauges_say_what_was_built(self, tiny):
        from analytics_zoo_tpu.observability.registry import get_registry
        _, _, model, _, _ = tiny
        reg = get_registry()
        assert reg.get("model_loop_passes").value(model=model.name) == 4
        assert reg.get("model_layer_applications").value(
            model=model.name) == 8
        assert reg.get("model_recompute").value(model=model.name) == 1

    def test_scopes_land_in_the_steps_op_names(self, tiny):
        _, _, model, params, batch = tiny
        loss_fn = _loss()
        text = jax.jit(jax.grad(lambda p: loss_fn(batch["y"], model.apply(
            p, batch["x"], training=True)))).lower(params).as_text(
                debug_info=True)
        for scope in ("looplm/pass", "looplm/block/attention",
                      "looplm/block/ffn", "looplm/final_norm",
                      "loss/blockwise_nll"):
            assert scope in text, scope


class TestBlockwiseLoss:
    @pytest.mark.parametrize("block_tokens", [1, 7, 96, 256, 10_000])
    def test_value_and_gradients_are_the_plain_losses(self, block_tokens,
                                                      monkeypatch):
        monkeypatch.setattr(objectives, "_LOSS_BLOCK_TOKENS", block_tokens)
        rs = np.random.RandomState(0)
        feats = jnp.asarray(rs.randn(2, 128, 16), jnp.float32)
        kernel = jnp.asarray(rs.randn(16, 37), jnp.float32)
        y = rs.randint(0, 37, (2, 128))
        loss = _loss()

        def f_plain(f, k):
            return loss(y, f @ k)

        def f_blocked(f, k):
            return loss(y, ProjectedLogits(f, k))
        np.testing.assert_allclose(f_blocked(feats, kernel),
                                   f_plain(feats, kernel), rtol=1e-6)
        for a, b in zip(jax.grad(f_blocked, (0, 1))(feats, kernel),
                        jax.grad(f_plain, (0, 1))(feats, kernel)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)

    def test_the_block_size_as_it_is_run(self, monkeypatch):
        """2 x 1100 tokens at the tiny vocabulary: two whole blocks of the
        constant's 1024 and a padded remainder."""
        monkeypatch.setattr(objectives, "_LOSS_BLOCK_TOKENS",
                            LOSS_BLOCK_TOKENS)
        rs = np.random.RandomState(1)
        feats = jnp.asarray(rs.randn(2, 1100, 16), jnp.float32)
        kernel = jnp.asarray(rs.randn(16, 211), jnp.float32)
        y = rs.randint(0, 211, (2, 1100))
        loss = _loss()
        blocked = jax.value_and_grad(lambda f, k: loss(
            y, ProjectedLogits(f, k)), (0, 1))
        assert "f32[1024,211]" in str(jax.make_jaxpr(blocked)(feats, kernel))
        plain = jax.value_and_grad(lambda f, k: loss(y, f @ k), (0, 1))
        for a, b in zip(jax.tree_util.tree_leaves(blocked(feats, kernel)),
                        jax.tree_util.tree_leaves(plain(feats, kernel))):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)

    def test_one_blocks_logits_are_the_most_that_is_formed(self, monkeypatch):
        monkeypatch.setattr(objectives, "_LOSS_BLOCK_TOKENS", 128)
        feats = jax.ShapeDtypeStruct((4, 256, 16), jnp.bfloat16)
        kernel = jax.ShapeDtypeStruct((16, 512), jnp.bfloat16)
        y = jnp.zeros((4, 256), jnp.int32)
        loss = _loss()
        jaxpr = str(jax.make_jaxpr(jax.grad(lambda f, k: loss(
            y, ProjectedLogits(f, k)), (0, 1)))(feats, kernel))
        assert "f32[128,512]" in jaxpr          # a block of tokens
        assert "[1024,512]" not in jaxpr and "[4,256,512]" not in jaxpr

    def test_other_losses_form_the_logits_and_probabilities_are_refused(self):
        pl = ProjectedLogits(jnp.ones((2, 3, 4)), jnp.ones((4, 5)))
        assert pl.materialize().shape == (2, 3, 5)
        with pytest.raises(ValueError, match="from_logits"):
            objectives.get("sparse_categorical_crossentropy")(
                jnp.zeros((2, 3), jnp.int32), pl)
        # a pytree of its two arrays: the trainer can cast or donate it
        assert len(jax.tree_util.tree_leaves(pl)) == 2


class TestLayers:
    def test_rms_norm(self):
        x = jnp.asarray(np.random.RandomState(0).randn(3, 5, 16) * 4,
                        jnp.float32)
        layer = RMSNormalization(1e-6)
        p = {"gamma": jnp.linspace(0.5, 2.0, 16)}
        want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6) \
            * p["gamma"]
        np.testing.assert_allclose(layer.call(p, x), want, rtol=1e-5)
        assert set(layer.build(None, (None, 5, 16))) == {"gamma"}
        # bfloat16 in, bfloat16 out, the statistic taken in float32
        out = layer.call({"gamma": p["gamma"].astype(jnp.bfloat16)},
                         x.astype(jnp.bfloat16))
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(out.astype(jnp.float32), want, rtol=0.03,
                                   atol=0.03)

    def test_rotary_keeps_norms_and_depends_on_distance_alone(self):
        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(1, 1, 64, 32), jnp.float32)
        cos, sin = rotary_tables(64, 32, theta=1e6)
        assert cos.shape == sin.shape == (64, 16)
        r = apply_rotary(q, cos, sin)
        np.testing.assert_allclose(jnp.linalg.norm(r, axis=-1),
                                   jnp.linalg.norm(q, axis=-1), rtol=1e-5)
        np.testing.assert_allclose(r[:, :, 0], q[:, :, 0], atol=1e-6)
        # the same vector at two positions: its product with itself
        # rotated depends on the distance between them alone
        same = jnp.broadcast_to(q[:, :, :1], q.shape)
        rs_ = apply_rotary(same, cos, sin)
        dots = jnp.einsum("bhqd,bhkd->bhqk", rs_, rs_)[0, 0]
        np.testing.assert_allclose(dots[3, 10], dots[20, 27], rtol=1e-4)
        np.testing.assert_allclose(dots[0, 40], dots[23, 63], rtol=1e-4)

    def test_decoder_block_norms_and_no_bias(self):
        blk = TransformerDecoderBlock(32, 2, 48, name="blk")
        p = blk.build(jax.random.PRNGKey(0), (None, 16, 32))
        assert {k for k in p if k.endswith("_norm")} == {
            "attn_in_norm", "attn_out_norm", "ffn_in_norm", "ffn_out_norm"}
        assert not [k for k in jax.tree_util.tree_leaves_with_path(p)
                    if "bias" in jax.tree_util.keystr(k[0])]
        x = jnp.asarray(np.random.RandomState(2).randn(2, 16, 32),
                        jnp.float32)
        rotary = rotary_tables(16, 16)
        y = blk.call(p, [x, rotary])
        assert y.shape == x.shape
        np.testing.assert_array_equal(
            y, blk.ffn_branch(p, blk.attention_branch(p, x, rotary)))
        # causal: the first 8 positions do not see the last 8
        x2 = x.at[:, 8:].add(1.0)
        np.testing.assert_array_equal(blk.call(p, [x2, rotary])[:, :8],
                                      y[:, :8])


class TestOnTheFitPath:
    def test_estimator_fit_mixed_precision_steps_per_run(self, tiny):
        import optax

        from analytics_zoo_tpu import init_orca_context
        from analytics_zoo_tpu.learn.estimator import Estimator
        config, traffic, _, params, _ = tiny
        init_orca_context(cluster_mode="local")
        model = family.build(config, traffic)
        model.params = jax.tree_util.tree_map(jnp.copy, params)
        # the test process has 8 virtual devices: a batch they can share
        data, n = family.fit_data(config, dict(
            traffic, batch_size=8, steps_per_epoch=2), 5)
        est = Estimator.from_keras(model, optimizer=optax.adamw(1e-3),
                                   loss=_loss())
        hist = est.fit(data, epochs=4, batch_size=8, mixed_precision=True,
                       steps_per_run=2)
        losses = hist["loss"]
        assert len(losses) == 4 and np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        # float32 masters come back, the gate untouched by the loss but
        # decayed by AdamW like every leaf
        leaves = jax.tree_util.tree_leaves(est.model.params)
        assert all(a.dtype == jnp.float32 for a in leaves)
        out = est.predict(data["x"][:8], batch_per_thread=1)
        assert np.asarray(out).shape == (8, T, 211)

    def test_fit_data_can_be_learned_and_labels_are_the_next_ids(self, tiny):
        config, traffic, _, _, _ = tiny
        data, n = family.fit_data(config, traffic, 11)
        assert n == traffic["batch_size"] * traffic["steps_per_epoch"]
        assert data["x"].shape == data["y"].shape == (n, T)
        np.testing.assert_array_equal(data["x"][:, 1:], data["y"][:, :-1])
        # every second token is a fixed function of the one before
        follows = {}
        for a, b in zip(data["x"][:, 0::2].ravel(), data["y"][:, 0::2].ravel()):
            assert follows.setdefault(int(a), int(b)) == int(b)
        same_seed, _ = family.fit_data(config, traffic, 11)
        np.testing.assert_array_equal(same_seed["x"], data["x"])

    def test_int8_rewrite_reaches_every_matmul_kernel(self, tiny):
        from analytics_zoo_tpu.serving.quantization import \
            quantize_model_params
        _, _, model, params, batch = tiny
        q = quantize_model_params(model, jax.device_get(params))
        assert "lm_head_kernel_q" in q and "lm_head_kernel" not in q
        assert q["blocks"]["ffn_gate_kernel_q"].dtype == np.int8
        assert q["blocks"]["attn"]["qkv_kernel_q"].shape == (2, 64, 192)
        got = model.apply(q, batch["x"])
        want = model.apply(params, batch["x"])
        err = float(jnp.sqrt(jnp.mean((got - want) ** 2)))
        assert 1e-4 < err < 0.2 * float(jnp.std(want))
