"""The routed expert layer (`keras/moe.py`), its grouped products
(`pallas/grouped_matmul.py`, through the Pallas interpreter) and the model
built from them (`models/moe_decoder.py`) against the plain reference
(`benchmark/reference/kanana_moe.py`) at small sizes: logits and every
gradient leaf, the shares of an expert-parallel layer adding up to the
uncut layer, and no token-slot dropped under any imbalance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.moe import MoEFeedForward, route
from analytics_zoo_tpu.models.moe_decoder import MoEDecoderLM
from analytics_zoo_tpu.observability.registry import get_registry
from analytics_zoo_tpu.pallas.grouped_matmul import (_work_items,
                                                     grouped_matmul)
from benchmark.reference import kanana_moe as reference

CFG = dict(num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, kv_lora_rank=32, rope_theta=1e6, rms_norm_eps=1e-6,
           num_experts_per_tok=3, norm_topk_prob=True,
           routed_scaling_factor=2.448, experts_held=[4, 8])
H, WIDTH, ROUTED = 64, 32, 16


def _model(held=(4, 8), **kw):
    return MoEDecoderLM(
        vocab=211, hidden_size=H, n_layer=3, n_head=2, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=96, moe_intermediate_size=WIDTH,
        n_routed_experts=ROUTED, num_experts_per_tok=3, n_shared_experts=2,
        experts_held=held, routed_scaling_factor=2.448, rope_theta=1e6,
        name="moedec_test", **kw)


def _ids(n=2, T=64, seed=0):
    return np.random.default_rng(seed).integers(0, 211, (n, T),
                                                dtype=np.int32)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# -- the grouped products ----------------------------------------------------
def _loop(lhs, rhs, sizes):
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    rows, start = jnp.arange(lhs.shape[0]), 0
    for g, size in enumerate(sizes):
        own = ((rows >= start) & (rows < start + size))[:, None]
        out = out + jnp.where(own, lhs @ rhs[g], 0.0)
        start += size
    return out


@pytest.mark.parametrize("sizes", [
    [100, 0, 1, 300, 200],      # an empty group, a one-row group, a tail
    [768, 0, 0, 0, 0],          # one group takes every row
    [0, 0, 0, 0, 0],            # nothing held: the grid has no step
    [256, 256, 0, 0, 256],      # groups that end on tile edges
    [0, 7, 0, 0, 0],
])
def test_grouped_matmul_kernels_match_a_loop_over_the_groups(sizes):
    rng = np.random.default_rng(0)
    m, k, n = 768, 256, 384
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32) * 0.1
    gs = jnp.asarray(sizes, jnp.int32)
    owned = (jnp.arange(m) < sum(sizes))[:, None]

    def system(l, r):
        return jnp.sum(jnp.where(owned, grouped_matmul(
            l, r, gs, interpret=True), 0.0) ** 2)

    got = jnp.where(owned, grouped_matmul(lhs, rhs, gs, interpret=True), 0.0)
    np.testing.assert_allclose(got, _loop(lhs, rhs, sizes), atol=1e-4)
    g_sys = jax.grad(system, argnums=(0, 1))(lhs, rhs)
    g_ref = jax.grad(lambda l, r: jnp.sum(_loop(l, r, sizes) ** 2),
                     argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(jnp.where(owned, g_sys[0], 0.0), g_ref[0],
                               atol=2e-3)
    np.testing.assert_allclose(g_sys[1], g_ref[1], atol=2e-3)


def test_grouped_matmul_off_the_chip_is_the_ragged_product():
    rng = np.random.default_rng(1)
    lhs = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(3, 16, 8)), jnp.float32)
    sizes = [10, 0, 25]
    np.testing.assert_allclose(
        grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32)),
        _loop(lhs, rhs, sizes), atol=1e-5)


def test_the_work_follows_the_group_sizes():
    """16 held experts of 128 under even routing: the kernels' grid has
    about (0.75 N / tile) + 16 steps, not the 6 N / tile of the buffer."""
    n_tokens, k, tile = 2048, 6, 256
    m = n_tokens * k
    sizes = jnp.full((16,), n_tokens * k // 128, jnp.int32)     # 96 each
    (_, group_of, tile_of), n_items = _work_items(sizes, m, tile)
    held_rows = int(sizes.sum())
    assert held_rows == m // 8
    assert held_rows // tile <= int(n_items) <= held_rows // tile + 16
    assert int(n_items) < (m // tile) // 2
    # every item's tile holds rows of its group
    ends = np.cumsum(np.asarray(sizes))
    for w in range(int(n_items)):
        g, t = int(group_of[w]), int(tile_of[w])
        assert t * tile < ends[g] and (t + 1) * tile > ends[g] - 96


# -- the expert layer ---------------------------------------------------------
def _layer(held, **kw):
    kw.setdefault("shared_width", 2 * WIDTH)
    return MoEFeedForward(H, WIDTH, ROUTED, 3, experts_held=held,
                          routed_scaling_factor=2.448, **kw)


def _whole_params(seed=0):
    return _layer((0, ROUTED)).build(jax.random.PRNGKey(seed))


def _share_of(params, held):
    """The parameter tree one chip would hold of the whole layer's."""
    out = dict(params)
    out["experts"] = {k: v[held[0]:held[1]]
                      for k, v in params["experts"].items()}
    return out


def _reference_layer(params, u, held, **fault):
    with jax.default_matmul_precision("highest"):
        return reference._moe(u, params, CFG, held, None, fault, False)[0]


def test_router_weighs_by_the_scores_and_chooses_by_score_plus_bias():
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(5, H)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(H, ROUTED)), jnp.float32) * 0.1
    bias = jnp.zeros((ROUTED,)).at[3].set(10.0)
    experts, w = route(u, kernel, bias, 3, 2.448)
    assert (np.asarray(experts) == 3).any(axis=1).all()   # the bias chose
    scores = jax.nn.sigmoid(u @ kernel)
    chosen = jnp.take_along_axis(scores, experts, axis=1)
    np.testing.assert_allclose(
        w, chosen / chosen.sum(axis=1, keepdims=True) * 2.448, rtol=1e-5)
    np.testing.assert_allclose(w.sum(axis=1), 2.448, rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 ranges of experts_held: the routed parts, with the shared experts
    counted once, sum to the uncut reference's whole layer, forward and
    the input's gradient."""
    params = _whole_params()
    u = jnp.asarray(np.random.default_rng(3).normal(size=(2, 32, H)),
                    jnp.float32)
    cot = jnp.asarray(np.random.default_rng(4).normal(size=(2, 32, H)),
                      jnp.float32)

    def shares(u):
        total = jnp.zeros_like(u)
        for first in range(0, ROUTED, 2):
            held = (first, first + 2)
            layer = _layer(held)
            total = total + layer.routed(_share_of(params, held), u)
        from analytics_zoo_tpu.keras.transformer import gated_ffn
        return total + gated_ffn(params["shared"], u, jax.nn.silu)

    def whole(u):
        return _reference_layer(params, u, (0, ROUTED))

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(
            lambda a: jnp.sum(shares(a) * cot))(u)
    want, g_want = jax.value_and_grad(lambda a: jnp.sum(whole(a) * cot))(u)
    assert abs(float(got) - float(want)) <= 1e-4 * abs(float(want)) + 1e-4
    assert _rel(g_got, g_want) < 1e-5
    with jax.default_matmul_precision("highest"):
        assert _rel(shares(u), whole(u)) < 1e-5


@pytest.mark.parametrize("targets,held_slots", [
    ((5,), None),           # every token in ONE held group, others as chosen
    ((4, 5, 6), 288),       # every slot of every token held: the buffer full
    ((12, 13, 14), 0),      # no slot lands here: the kernels' grid is empty
])
def test_no_token_slot_is_dropped_under_any_imbalance(targets, held_slots):
    """A router bias that sends every token to the same experts, held here
    or absent. All match the reference, whatever the imbalance: there is
    no capacity, and every slot that chose a held expert is computed."""
    held = (4, 8)
    params = _whole_params(1)
    params["router"]["bias"] = jnp.zeros((ROUTED,)).at[
        jnp.asarray(targets)].set(100.0)
    u = jnp.asarray(np.random.default_rng(5).normal(size=(2, 48, H)),
                    jnp.float32)
    layer = _layer(held)
    share = _share_of(params, held)
    experts, _ = layer.routing(share, u)
    for t in targets:
        assert (np.asarray(experts) == t).any(axis=1).all()
    _, _, sizes, held_mask = layer._dispatch(experts)
    assert int(held_mask.sum()) == int(sizes.sum())
    if held_slots is None:
        assert int(sizes[targets[0] - held[0]]) == 96     # all 96 tokens
    else:
        assert int(sizes.sum()) == held_slots
    with jax.default_matmul_precision("highest"):
        got = layer.call(share, u)
    want = _reference_layer(share, u, held)
    assert _rel(got, want) < 1e-5


def test_expert_layer_rejects_a_range_it_cannot_hold():
    with pytest.raises(ValueError, match="experts_held"):
        _layer((12, 20))


# -- the model ----------------------------------------------------------------
def test_model_matches_the_plain_reference_on_logits_and_every_gradient():
    model = _model()
    params = model.build(jax.random.PRNGKey(0))
    ids = _ids()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: model.apply(p, x))(params, ids)
    want, own = jax.jit(lambda p, x: reference.reference_forward(
        p, x, CFG))(params, ids)
    np.testing.assert_allclose(got, want, atol=2e-5)
    choice = jax.jit(model.expert_choice)(params, ids)
    assert np.mean(np.sort(choice, -1) == np.sort(own, -1)) > 0.99

    batch = {"x": ids, "y": _ids(seed=1)}

    def system_loss(p):
        logp = jax.nn.log_softmax(model.apply(p, batch["x"]), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, batch["y"][..., None], axis=-1))

    with jax.default_matmul_precision("highest"):
        g_sys = jax.jit(jax.grad(system_loss))(params)
    g_ref = jax.jit(jax.grad(lambda p: reference.reference_loss(
        p, batch, CFG)))(params)
    flat_sys = jax.tree_util.tree_leaves_with_path(g_sys)
    flat_ref = jax.tree_util.tree_leaves(g_ref)
    assert len(flat_sys) == len(flat_ref) == 28
    for (path, a), b in zip(flat_sys, flat_ref):
        name = jax.tree_util.keystr(path)
        if "bias" in name:      # the choice's bias: exactly zero, both sides
            assert not np.asarray(a).any() and not np.asarray(b).any()
        else:
            assert _rel(a, b) < 1e-4, name


def test_a_handed_choice_replaces_the_references_indices_and_nothing_else():
    """A router bias moves the choice and never the weights: the
    reference handed THAT choice, on the tree without the bias, gives what
    the reference with the bias gives routing freely, and returns its own
    (other) choice beside it; one held expert dropped moves the result."""
    model = _model()
    params = model.build(jax.random.PRNGKey(0))
    ids = _ids()
    biased = jax.tree_util.tree_map(lambda a: a, params)
    bias = jax.random.normal(jax.random.PRNGKey(3), (2, ROUTED)) * 0.3
    biased["moe_blocks"]["ffn"]["router"]["bias"] = bias
    want, theirs = reference.reference_forward(biased, ids, CFG)
    got, own = reference.reference_forward(params, ids, CFG, choice=theirs)
    np.testing.assert_allclose(got, want, atol=1e-6)
    free, own_free = reference.reference_forward(params, ids, CFG)
    # the first layer's input is the same, so its own choice is the free
    # one; the handed choice was another, and moved the logits
    np.testing.assert_array_equal(own[0], own_free[0])
    assert np.mean(np.sort(own, -1) == np.sort(theirs, -1)) < 0.9
    assert float(jnp.abs(got - free).max()) > 1e-3
    loss, own_l = reference.reference_loss_and_choice(
        params, {"x": ids, "y": _ids(seed=1)}, CFG, choice=theirs)
    np.testing.assert_array_equal(own_l, own)
    assert loss == reference.reference_loss(
        biased, {"x": ids, "y": _ids(seed=1)}, CFG)
    lost = reference.reference_forward(params, ids, CFG,
                                       held_expert_dropped=True)[0]
    assert float(jnp.abs(lost - free).max()) > 1e-3


def test_training_output_is_unformed_logits_and_remat_changes_no_number():
    from analytics_zoo_tpu.ops.objectives import ProjectedLogits
    ids = _ids()
    params = _model().build(jax.random.PRNGKey(0))
    outs = []
    for remat in (True, False):
        model = _model(remat=remat)
        pred = model.apply(params, ids, training=True)
        assert isinstance(pred, ProjectedLogits)
        outs.append(jax.grad(lambda p: jnp.sum(model.apply(
            p, ids, training=True).materialize() ** 2))(params))
    for a, b in zip(*map(jax.tree_util.tree_leaves, outs)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_the_choice_counts_every_slot_and_the_gauges_say_what_is_held():
    model = _model()
    params = model.build(jax.random.PRNGKey(0))
    ids = _ids()
    choice = np.asarray(jax.jit(model.expert_choice)(params, ids))
    assert choice.shape == (2,) + ids.shape + (3,)
    counts = np.stack([np.bincount(layer.reshape(-1), minlength=ROUTED)
                       for layer in choice])
    assert counts.shape == (2, ROUTED)
    assert (counts.sum(axis=1) == ids.size * 3).all()
    snap = get_registry().snapshot()

    def gauge(name):
        return [s["value"] for s in snap[name]["series"]
                if s["labels"].get("model") == "moedec_test"][0]
    assert gauge("model_experts_routed") == ROUTED
    assert gauge("model_experts_held") == 4
    assert gauge("model_experts_per_token") == 3
    assert gauge("model_shared_experts") == 2
    assert gauge("model_layer_applications") == 3
    assert gauge("model_recompute") == 1
    assert gauge("model_recompute_attention_kernel") == 1    # no flash here


def test_int8_rewrite_reaches_the_new_kernels_and_leaves_the_experts():
    from analytics_zoo_tpu.serving.quantization import quantize_model_params
    model = _model()
    params = jax.device_get(model.build(jax.random.PRNGKey(0)))
    q = quantize_model_params(model, params)
    attn = q["moe_blocks"]["attn"]
    for name in ("q_kernel", "kv_a_kernel", "kv_b_kernel", "out_kernel"):
        assert name + "_q" in attn and name not in attn
    assert "lm_head_kernel_q" in q
    assert set(q["moe_blocks"]["ffn"]["experts"]) == {
        "gate_kernel", "up_kernel", "down_kernel"}
    ids = _ids()
    got = model.apply(q, ids)
    want = model.apply(params, ids)
    assert 1e-4 < _rel(got, want) < 0.2


def test_fit_through_the_estimator_lowers_the_loss():
    import optax

    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.ops import objectives
    model = _model()
    model.params = model.build(jax.random.PRNGKey(0))
    x = _ids(n=16, T=32)
    est = Estimator.from_keras(
        model, optimizer=optax.adamw(1e-2),
        loss=objectives.get("sparse_categorical_crossentropy",
                            from_logits=True))
    hist = est.fit({"x": x, "y": np.roll(x, -1, axis=1)}, epochs=4,
                   batch_size=8, mixed_precision=True)
    assert np.isfinite(hist["loss"]).all()
    assert hist["loss"][-1] < hist["loss"][0]


# -- a per-layer mixer pattern (linear and latent attention) -----------------
from benchmark.reference import kimi_linear as hybrid_reference  # noqa: E402

HYBRID_CFG = dict(
    CFG, num_experts_per_token=3, num_hidden_layers=4,
    first_k_dense_replace=1, rms_norm_eps=1e-5, experts_held=[4, 8],
    linear_attn_config={"full_attn_layers": [3], "kda_layers": [1, 2, 4],
                        "num_heads": 2, "head_dim": 16})
HYBRID_MIXERS = ["linear", "linear", "latent", "linear"]


def _hybrid(**kw):
    kw.setdefault("mixers", HYBRID_MIXERS)
    kw.setdefault("linear_attention", dict(n_head=2, head_dim=16,
                                           v_head_dim=24, chunk=16))
    return MoEDecoderLM(
        vocab=211, hidden_size=H, n_layer=len(kw["mixers"]), n_head=2,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=WIDTH,
        n_routed_experts=ROUTED, num_experts_per_tok=3, n_shared_experts=2,
        experts_held=(4, 8), routed_scaling_factor=2.448, rms_eps=1e-5,
        rotary=False, name="hybrid_test", **kw)


def test_a_mixer_pattern_is_runs_of_neighbours_in_the_layers_own_order():
    from analytics_zoo_tpu.models.moe_decoder import _runs
    assert _runs(["latent"] * 3, 1) == [
        ("dense_blocks", "latent", "dense", 0, 1),
        ("moe_blocks", "latent", "moe", 1, 2)]
    # the benchmark's cut of the hybrid: layers 1-5 as published
    assert _runs(["linear", "linear", "linear", "latent", "linear"], 1) == [
        ("blocks_0_linear_dense", "linear", "dense", 0, 1),
        ("blocks_1_linear_moe", "linear", "moe", 1, 2),
        ("blocks_3_latent_moe", "latent", "moe", 3, 1),
        ("blocks_4_linear_moe", "linear", "moe", 4, 1)]
    params = jax.eval_shape(_hybrid().build, jax.random.PRNGKey(0))
    assert sorted(k for k in params if "blocks" in k) == [
        "blocks_0_linear_dense", "blocks_1_linear_moe",
        "blocks_2_latent_moe", "blocks_3_linear_moe"]
    assert params["blocks_1_linear_moe"]["attn"]["q_kernel"].shape \
        == (1, H, 32)
    assert "q_conv" not in params["blocks_2_latent_moe"]["attn"]
    assert [r[1:] for r in hybrid_reference.layer_runs(HYBRID_CFG)] \
        == [(m, f, n) for _, m, f, _, n in _hybrid().runs]
    with pytest.raises(ValueError, match="mixers"):
        _hybrid(mixers=["latent", "windowed", "latent", "latent"])


def test_latent_attention_alone_is_the_model_it_was_bit_for_bit():
    """No pattern, or a pattern of latent attention alone: the tree, the
    names and the logits of the expert model (`dense_blocks`,
    `moe_blocks`), held to its own reference as before."""
    ids = _ids()
    plain, named = _model(), _model(mixers=["latent"] * 3)
    a = plain.build(jax.random.PRNGKey(0))
    b = named.build(jax.random.PRNGKey(0))
    assert sorted(a) == ["dense_blocks", "final_norm", "lm_head_kernel",
                         "moe_blocks", "word_embeddings"]
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(plain.apply(a, ids), named.apply(b, ids))
    text = [jax.jit(m.apply).lower(a, ids).as_text() for m in (plain, named)]
    assert text[0] == text[1]


def test_rotary_is_a_switch_and_the_columns_stay_as_content():
    ids = _ids()
    params = _model().build(jax.random.PRNGKey(0))
    without = _model(rotary=False)
    assert without._embed(params, ids)[1] is None      # no tables are made
    got = without.apply(params, ids)
    assert _rel(got, _model().apply(params, ids)) > 1e-3
    # the shared key head still enters the scores
    dropped = jax.tree_util.tree_map(lambda a: a, params)
    dropped["moe_blocks"]["attn"]["kv_a_kernel"] = \
        params["moe_blocks"]["attn"]["kv_a_kernel"].at[:, :, 32:].set(0.0)
    assert _rel(without.apply(dropped, ids), got) > 1e-4


def test_hybrid_model_matches_its_reference_on_logits_and_every_leaf():
    model = _hybrid()
    params = model.build(jax.random.PRNGKey(0))
    ids = _ids(T=48)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: model.apply(p, x))(params, ids)
    want, own = jax.jit(lambda p, x: hybrid_reference.reference_forward(
        p, x, HYBRID_CFG))(params, ids)
    np.testing.assert_allclose(got, want, atol=5e-5)
    choice = jax.jit(model.expert_choice)(params, ids)
    assert choice.shape == own.shape == (3, 2, 48, 3)
    assert np.mean(np.sort(choice, -1) == np.sort(own, -1)) > 0.99
    batch = {"x": ids, "y": _ids(T=48, seed=1)}

    def system_loss(p):
        logp = jax.nn.log_softmax(model.apply(p, batch["x"]), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, batch["y"][..., None], axis=-1))

    with jax.default_matmul_precision("highest"):
        g_sys = jax.jit(jax.grad(system_loss))(params)
    g_ref = jax.jit(jax.grad(lambda p: hybrid_reference.reference_loss(
        p, batch, HYBRID_CFG)))(params)
    flat_sys = jax.tree_util.tree_leaves_with_path(g_sys)
    flat_ref = jax.tree_util.tree_leaves(g_ref)
    assert len(flat_sys) == len(flat_ref)
    seen_kda = 0
    for (path, a), b in zip(flat_sys, flat_ref):
        name = jax.tree_util.keystr(path)
        if "'bias'" in name and "router" in name:
            assert not np.asarray(a).any() and not np.asarray(b).any()
        else:
            assert _rel(a, b) < 3e-4, name
        seen_kda += "A_log" in name or "dt_bias" in name or "_conv" in name
    assert seen_kda == 3 * 5        # three runs with linear layers


def test_hybrid_remat_changes_no_number_and_the_gauges_say_what_is_built():
    ids = _ids(T=32)
    params = _hybrid().build(jax.random.PRNGKey(0))
    grads = [jax.grad(lambda p: jnp.sum(_hybrid(remat=remat).apply(
        p, ids, training=True).materialize() ** 2))(params)
        for remat in (True, False)]
    for a, b in zip(*map(jax.tree_util.tree_leaves, grads)):
        assert float(jnp.linalg.norm(a - b)) \
            <= 1e-4 * float(jnp.linalg.norm(b)) + 1e-9
    snap = get_registry().snapshot()

    def gauge(name):
        return [s["value"] for s in snap[name]["series"]
                if s["labels"].get("model") == "hybrid_test"][0]
    assert gauge("model_layers_linear") == 3
    assert gauge("model_layers_full") == 1
    assert gauge("model_layer_applications") == 4
    assert gauge("model_linear_chunk") == 16
    assert gauge("model_linear_state_bytes") == 4 * 2 * 16 * 24
    assert gauge("model_experts_held") == 4


def _kernel_calls(jaxpr, counts):
    """`pallas_call`s of a jaxpr and of everything nested in it, by the
    kernel's name; a scan's or a checkpoint's body counts once."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] = counts.get(eqn.params["name"], 0) + 1
            continue
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    _kernel_calls(sub, counts)
    return counts


def test_the_recurrences_output_crosses_a_layers_checkpoint_by_its_own_name():
    from analytics_zoo_tpu.keras.linear_attention import RECURRENCE_OUT_NAME
    from analytics_zoo_tpu.pallas import flash_attention as fa
    assert RECURRENCE_OUT_NAME not in (fa.FLASH_OUT_NAME, fa.FLASH_LSE_NAME)
    # latent attention alone keeps the policy it had, the same object
    assert _model().kept is fa.save_flash_residuals
    # 16 rows of batch x heads: two groups of 8 under their own checkpoints
    kw = dict(mixers=["linear", "linear"], n_dense_layer=1,
              linear_attention=dict(n_head=8, head_dim=16, v_head_dim=24,
                                    chunk=16, interpret=True))
    ids = _ids(T=32)

    def calls(model):
        params = jax.eval_shape(model.build, jax.random.PRNGKey(0))
        return _kernel_calls(jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(model.apply(p, ids, training=True)
                              .materialize() ** 2)))(params).jaxpr, {})

    kept = _hybrid(**kw)
    assert kept.kept is not fa.save_flash_residuals
    # a run (dense, expert) of one layer each: the forward kernels (the
    # chunks' preparation and the pass over them) in the forward pass and
    # once more, a group of heads at a time, in the backward pass; the
    # layer's own recomputation does not run them
    assert calls(kept) == {"delta_prepare_fwd": 4, "kda_chunk_fwd": 4,
                           "kda_chunk_bwd": 2, "delta_prepare_bwd": 2}
    dropped = _hybrid(**kw)
    dropped.kept = fa.save_flash_residuals
    assert calls(dropped) == {"delta_prepare_fwd": 6, "kda_chunk_fwd": 6,
                              "kda_chunk_bwd": 2, "delta_prepare_bwd": 2}


def test_int8_rewrite_reaches_the_linear_layers_projections():
    from analytics_zoo_tpu.serving.quantization import quantize_model_params
    model = _hybrid()
    params = jax.device_get(model.build(jax.random.PRNGKey(0)))
    q = quantize_model_params(model, params)
    attn = q["blocks_0_linear_dense"]["attn"]
    for name in ("q_kernel", "k_kernel", "v_kernel", "out_kernel"):
        assert name + "_q" in attn and name not in attn
    for name in ("q_conv", "A_log", "dt_bias", "decay_b_kernel",
                 "beta_kernel"):
        assert name in attn                 # gates and filters keep theirs
    ids = _ids(T=32)
    assert 1e-4 < _rel(model.apply(q, ids), model.apply(params, ids)) < 0.3


def test_the_shares_add_up_at_the_hybrids_router_32_ranges_of_8():
    """The 256-wide router, 8 a token, 32 ranges of 8 held experts: the
    routed parts, with the shared expert counted once, sum to the uncut
    reference's whole layer, forward and the input's gradient."""
    from analytics_zoo_tpu.keras.transformer import gated_ffn
    cfg = dict(CFG, num_experts_per_tok=8, routed_scaling_factor=2.446)

    def layer(held):
        return MoEFeedForward(H, 16, 256, 8, experts_held=held,
                              shared_width=16, routed_scaling_factor=2.446)
    params = layer((0, 256)).build(jax.random.PRNGKey(0))
    u = jnp.asarray(np.random.default_rng(3).normal(size=(1, 24, H)),
                    jnp.float32)
    cot = jnp.asarray(np.random.default_rng(4).normal(size=(1, 24, H)),
                      jnp.float32)

    def shares(u):
        total = jnp.zeros_like(u)
        for first in range(0, 256, 8):
            held = (first, first + 8)
            total = total + layer(held).routed(_share_of(params, held), u)
        return total + gated_ffn(params["shared"], u, jax.nn.silu)

    def whole(u):
        with jax.default_matmul_precision("highest"):
            return reference._moe(u, params, cfg, (0, 256), None, {},
                                  False)[0]

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(
            lambda a: jnp.sum(shares(a) * cot))(u)
    want, g_want = jax.value_and_grad(lambda a: jnp.sum(whole(a) * cot))(u)
    assert abs(float(got) - float(want)) <= 1e-4 * abs(float(want)) + 1e-4
    assert _rel(g_got, g_want) < 1e-5


def test_hybrid_fit_through_the_estimator_lowers_the_loss():
    import optax

    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.ops import objectives
    model = _hybrid()
    model.params = model.build(jax.random.PRNGKey(0))
    x = _ids(n=16, T=32)
    est = Estimator.from_keras(
        model, optimizer=optax.adamw(1e-2),
        loss=objectives.get("sparse_categorical_crossentropy",
                            from_logits=True))
    hist = est.fit({"x": x, "y": np.roll(x, -1, axis=1)}, epochs=4,
                   batch_size=8, mixed_precision=True)
    assert np.isfinite(hist["loss"]).all()
    assert hist["loss"][-1] < hist["loss"][0]
