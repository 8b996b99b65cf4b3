"""The configuration `smallthinker-21b-a3b` and its cell
`smallthinker-21b-a3b.fit-seq16384-swa`: the file against the published
config (every width unchanged, three cuts of a stated deployment), found in
BENCHMARK.json by name; the parameter count of the cut; the family's FLOP
and work counts against counts made by hand; the new per-layer metrics'
files and their kernel-name patterns; the system against the plain
reference at a tiny size (logits, loss and the whole gradient); the four
shares of an expert layer against the uncut layer; every fault of the
reference; and the parameter trees of the families the benchmark had,
which this configuration's options leave as they were. The tiny cell runs
through the benchmark's own command in
`test_benchmark_rehearse_program_metrics.py`, as every cell does."""

import hashlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "smallthinker-21b-a3b.fit-seq16384-swa"
CONFIG = "smallthinker-21b-a3b"
NEW_METRICS = ["swa_moe_fit_mfu", "swa_flash_time_share",
               "swa_flash_attention_roofline", "swa_moe_experts_time_share",
               "swa_moe_held_slot_share", "swa_moe_rows_time_share",
               "swa_global_flash_time_share"]

# https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/
# config.json, the catalog row's `config`
_LAYOUT = [0, 1, 1, 1] * 13
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": _LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": _LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as fh:
        return json.load(fh)


def _traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "fit-seq16384-swa.json")) as fh:
        return json.load(fh)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_every_published_key_is_unchanged_but_the_three_cuts():
    cfg = _config()
    differ = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers", "moe_num_primary_experts",
                      "vocab_size"} == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # one whole period of the layer pattern (no leading dense layers), 16
    # routed experts a layer of 64, a quarter of the vocabulary
    assert cfg["num_hidden_layers"] == 4
    assert cfg["moe_num_primary_experts"] == 16 and cfg["router_width"] == 64
    assert cfg["experts_held"] == [0, 16]
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    from benchmark.models import smallthinker_moe
    assert smallthinker_moe._mixers(cfg) == ["global", "window", "window",
                                             "window"]
    for word in ("4 TPU v5e chips", "expert-parallel", "pipeline",
                 "a quarter", "first four layers", "656.53 M", "10.50 GB"):
        assert word in cfg["deployment"], word
    assert set(cfg["assumed"]) >= {
        "router_input", "hidden_act", "qk_norm", "window", "rotary",
        "router", "initializer", "optimizer", "loss", "packing"}
    assert cfg["hidden_act"] == "relu"
    assert "float32" in cfg["precision"]["router"]
    # widths are never cut
    widths = {"hidden_size", "moe_ffn_hidden_size", "num_attention_heads",
              "num_key_value_heads", "moe_num_active_primary_experts",
              "head_dim", "sliding_window_size"}
    for key in cfg["reduced"]:
        assert not key.endswith(("_dim", "_rank")) and key not in widths


def test_the_entries_are_found_by_name_with_the_cells_traffic():
    bench = _bench()
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    cfg = _config()
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert cfg["name"] == CONFIG and cfg["family"] == "smallthinker_moe"
    t = _traffic()
    assert (t["kind"], t["seq_len"], t["batch_size"]) == ("fit", 16384, 1)
    assert t["fit_kwargs"] == {"mixed_precision": True,
                               "steps_per_run": t["steps_per_epoch"]}
    assert t["model_kwargs"] == {"use_flash": True, "remat": True}
    assert t["mesh_axes"] == {} and t["trace_epochs"] == 2
    # the rehearsal's window is shorter than its sequence
    assert t["rehearsal"]["seq_len"] \
        > cfg["rehearsal"]["sliding_window_size"]
    assert cfg["fit"]["optimizer"] == {"optax": "adamw",
                                       "kwargs": {"learning_rate": 0.0001}}
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "fit-seq16384-swa", 1)
    assert len(cell["why"]) <= 200 and "16,384" in cell["why"]
    assert f"{t['steps_per_epoch']}-step" in cell["why"]


def _count(tree):
    import jax
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def test_the_parameter_count_is_the_deployments_share():
    import jax

    from benchmark.models import smallthinker_moe
    model = smallthinker_moe.build(_config(), _traffic())
    shapes = jax.eval_shape(model.build, jax.random.PRNGKey(0))
    assert sorted(k for k in shapes if "blocks" in k) == [
        "blocks_0_global_moe", "blocks_1_window_moe"]
    attn = shapes["blocks_1_window_moe"]["attn"]
    # q 2560 x 3584, k and v 2560 x 512, o 3584 x 2560; no q/k norm
    assert set(attn) == {"q_kernel", "k_kernel", "v_kernel", "out_kernel"}
    assert attn["q_kernel"].shape == (3, 2560, 3584)
    assert _count(attn) == 3 * 20_971_520
    ffn = shapes["blocks_1_window_moe"]["ffn"]
    assert set(ffn) == {"router", "experts"}            # no shared expert
    assert set(ffn["router"]) == {"kernel"}             # no bias
    assert ffn["router"]["kernel"].shape == (3, 2560, 64)
    assert ffn["experts"]["gate_kernel"].shape == (3, 16, 2560, 768)
    assert shapes["lm_head_kernel"].shape == (2560, 37984)     # untied
    assert shapes["word_embeddings"].shape == (37984, 2560)
    assert _count(shapes) == 656_529_920
    # 16 bytes a parameter: 10.50 GB
    assert 10.50e9 < 16 * _count(shapes) < 10.51e9
    # the gauges the program sets at build, which the trace's readers and
    # PERF.md name the layers by
    from analytics_zoo_tpu.observability.registry import get_registry
    snap = get_registry().snapshot()
    gauges = {name: [x["value"] for x in snap[name]["series"]
                     if x["labels"].get("model") == model.name][-1]
              for name in ("model_layers_window", "model_attention_window",
                           "model_layers_nope", "model_router_softmax",
                           "model_layers_gqa", "model_layers_full",
                           "model_experts_held")}
    assert gauges == {"model_layers_window": 3,
                      "model_attention_window": 4096,
                      "model_layers_nope": 1, "model_router_softmax": 1,
                      "model_layers_gqa": 4, "model_layers_full": 1,
                      "model_experts_held": 16}


def test_flops_and_kernel_work_against_a_hand_count():
    from benchmark import metrics
    from benchmark.models import smallthinker_moe as st
    cfg, t = _config(), _traffic()
    # a layer's attention 20,971,520 and router 2560 * 64 = 163,840; 6 x 16
    # / 64 = 1.5 held experts a token of 3 * 2560 * 768 = 5,898,240; the
    # head over the slice 2560 * 37,984 = 97,239,040
    weights = 4 * (20_971_520 + 163_840 + 1.5 * 5_898_240) + 97_239_040
    pairs = 16384 ** 2 / 2 + 3 * 58_722_304
    assert st.flops_per_sample(cfg, t) \
        == 6 * 16384 * weights + 12 * pairs * 28 * 128
    assert 34.6e12 < st.flops_per_sample(cfg, t) < 34.8e12
    work = st.kernel_work_per_sample(cfg, t)
    assert set(work) == {"window_attention", "experts"}
    a = work["window_attention"]
    assert a["flops"] == 7 * 2.0 * 3 * 58_722_304 * 28 * 128
    # K, V and their gradients once a K/V head (4), the rest a query head
    assert a["bytes"] == 3 * (2.0 * 16384 * 128 * (6 * 28 + 6 * 4)
                              + 8.0 * 16384 * 28)
    assert metrics.roofline_percent(a["flops"], a["bytes"],
                                    2 * a["flops"] / 197e12, "TPU v5 lite",
                                    chips=1) == pytest.approx(50.0)
    e = work["experts"]
    assert e["flops"] == 18.0 * 4 * 1.5 * 16384 * 2560 * 768
    assert e["bytes"] == 2.0 * 4 * (2 * 16 * 3 * 2560 * 768
                                    + 5 * 1.5 * 16384 * 2560)


OPS_WINDOW = ["flash_fwd_causal_gqa_window.7@tpu_custom_call",
              "flash_bwd_fused_causal_gqa_window.3@tpu_custom_call",
              "flash_dq_causal_gqa_window.1@tpu_custom_call",
              "flash_dkv_causal_gqa_window.1@tpu_custom_call"]
OPS_GLOBAL = ["flash_fwd_causal_gqa.7@tpu_custom_call",
              "flash_bwd_fused_causal_gqa.3@tpu_custom_call",
              "flash_dkv_causal_gqa@tpu_custom_call"]
OPS_ROWS = ["moe_rows_gather.2@tpu_custom_call",
            "moe_rows_combine_pack.4@tpu_custom_call"]
OPS_OTHER = ["flash_fwd_causal_mla.7@tpu_custom_call",
             "flash_fwd_causal.15@tpu_custom_call",
             "flash_fwd.3@tpu_custom_call",
             "moe_gmm_fwd.11@tpu_custom_call", "fusion.521"]
# what each new metric's pattern matches of the names above
MATCHED = {"swa_flash_time_share": OPS_WINDOW,
           "swa_flash_attention_roofline": OPS_WINDOW,
           "swa_moe_experts_time_share": ["moe_gmm_fwd.11@tpu_custom_call"],
           "swa_moe_rows_time_share": OPS_ROWS,
           "swa_global_flash_time_share": OPS_GLOBAL}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_files_load_and_are_reported_in_the_cell_alone(name):
    from benchmark import harness
    cell = harness.load_cell(CELL, rehearse=False)
    entry = {m["name"]: m for m in cell["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "fit_samples_per_s" and entry["unit"] == "%"
    spec = harness.reader_spec(entry)
    assert spec["what"]
    if "pattern" in spec:
        matched = [o for o in OPS_WINDOW + OPS_GLOBAL + OPS_ROWS + OPS_OTHER
                   if re.search(spec["pattern"], o)]
        assert matched == MATCHED[name]
    if spec["reader"] == "trace_op_roofline":
        assert spec["work"] == "window_attention"
    for other in [w["name"] for w in _bench()["workloads"] if w["name"]
                  != CELL]:
        assert name not in {m["name"] for m in harness.load_cell(
            other, False)["per_layer"]}


def test_the_family_imports_without_the_programs_model():
    """These benchmark files may be laid over a checkout of the program
    that lacks this family's layers: the family has to import there (and
    fail at `build`, at once), so it names the program's model inside
    `build` alone."""
    code = ("import sys; from benchmark.models import smallthinker_moe; "
            "assert 'analytics_zoo_tpu' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True)


@pytest.mark.parametrize("key, value", [
    ("experts_held", [0, 8]), ("moe_primary_router_apply_softmax", False),
    ("tie_word_embeddings", True), ("hidden_act", "silu"),
    ("rope_layout", [1] * 52), ("sliding_window_layout", [0, 2] * 26)])
def test_build_refuses_what_the_layers_do_not_have(key, value):
    from benchmark.models import smallthinker_moe
    with pytest.raises(ValueError, match="smallthinker_moe"):
        smallthinker_moe.build(dict(_config(), **{key: value}), _traffic())


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model (hidden 64, 4 layers, 4 held of 16 experts,
    window 64) at seeded weights on two 256-token sequences, with the
    system's logits and its expert choice. The weights are the model's own
    normal(0.02) draw, embedding included: at width 64 the attention
    branch gives a unit input about 0.03, so nothing collapses the
    routing, and an embedding at normal(1.0) (`init_params`) would leave
    each branch's part of the logits too small to tell a fault by."""
    import jax

    from benchmark import harness
    from benchmark.models import kanana_moe, smallthinker_moe
    cell = harness.load_cell(CELL, rehearse=True)
    config, traffic = cell["config"], cell["traffic"]
    model = smallthinker_moe.build(config, traffic)
    params = jax.jit(model.build)(jax.random.PRNGKey(3))
    batch = smallthinker_moe.step_batch(config, traffic, 5, 2)
    # the same program returns the logits and every layer's choice
    logits = smallthinker_moe.system_outputs(model, params, batch["x"])
    return (config, model, params, batch, logits,
            kanana_moe._system_choice)


def test_init_params_draws_the_embedding_at_unit_scale_alone(tiny):
    """`init_params` is the model's own draw from the same key with the
    embedding scaled from normal(0.02) to normal(1.0)."""
    import jax

    from benchmark.models import smallthinker_moe
    _, model, params, _, _, _ = tiny
    drawn = smallthinker_moe.init_params(model, jax.random.PRNGKey(3))
    emb = np.asarray(drawn.pop("word_embeddings"))
    np.testing.assert_allclose(
        emb, np.asarray(params["word_embeddings"]) * 50.0, rtol=1e-6)
    assert abs(float(emb.std()) - smallthinker_moe.EMBEDDING_STD) < 0.02
    jax.tree_util.tree_map(np.testing.assert_array_equal, drawn,
                           {k: v for k, v in params.items()
                            if k != "word_embeddings"})


def test_the_system_is_the_reference_logits_loss_and_the_gradient(tiny):
    """float32 on the CPU, the system's own forward and gradient (no
    kernels off the TPU) against the reference at the system's choice."""
    import jax

    from analytics_zoo_tpu.ops import objectives
    from benchmark.reference import smallthinker_moe as reference
    config, model, params, batch, logits, choice = tiny
    want, own = jax.jit(lambda p, x: reference.reference_forward(
        p, x, config))(params, batch["x"])
    assert np.array_equal(np.asarray(own), choice)
    np.testing.assert_allclose(logits, np.asarray(want), atol=2e-5,
                               rtol=1e-4)
    loss = objectives.get("sparse_categorical_crossentropy",
                          from_logits=True)
    sys_loss, sys_grads = jax.jit(jax.value_and_grad(lambda p: loss(
        batch["y"], model.apply(p, batch["x"], training=True))))(params)
    (ref_loss, _), ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.reference_loss_and_choice(
            p, batch, config, choice=choice), has_aux=True))(params)
    assert abs(float(sys_loss) - float(ref_loss)) < 1e-5
    flat_sys = jax.tree_util.tree_leaves_with_path(sys_grads)
    flat_ref = jax.tree_util.tree_leaves(ref_grads)
    assert len(flat_sys) == len(flat_ref)
    for (path, g), r in zip(flat_sys, flat_ref):
        g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
        assert np.linalg.norm(g - r) <= 1e-4 * np.linalg.norm(r) + 1e-9, \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("fault", [
    "router_after_attention", "sigmoid_router", "silu_experts",
    "window_dropped", "rotary_in_global", "rotary_dropped",
    "gqa_interleaved", "causal_mask_dropped", "reference_bfloat16"])
def test_every_fault_moves_the_reference_and_fails_a_tight_check(tiny,
                                                                 fault):
    from benchmark import compare
    from benchmark.models import smallthinker_moe
    config, model, params, batch, logits, _ = tiny
    broken = smallthinker_moe.reference_outputs(params, batch["x"], config,
                                                **{fault: True})
    err = compare.errors(logits, broken)
    assert not compare.within(err, {"atol": 1e-5, "rms": 1e-6}), err
    assert err["rms_err"] > 5e-5, err
    assert set(smallthinker_moe.FAULTS) <= {
        "router_after_attention", "sigmoid_router", "silu_experts",
        "window_dropped", "rotary_in_global", "rotary_dropped",
        "gqa_interleaved", "causal_mask_dropped", "reference_bfloat16"}


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips' shares of a 64-expert layer (there is no shared
    expert), each the system's expert layer told its range and routing on
    the block's input, add up to the reference's layer holding all 64."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.keras.moe import MoEFeedForward
    from benchmark.reference import smallthinker_moe as reference
    H, I, E, k = 32, 16, 64, 6
    config = {"moe_num_active_primary_experts": k}

    def layer(held, name):
        return MoEFeedForward(H, I, E, k, experts_held=held, init="normal",
                              hidden_act="relu", router_score="softmax",
                              name=name)
    params = layer(None, "whole").build(jax.random.PRNGKey(0))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 24, H))
    a = jax.random.normal(jax.random.PRNGKey(3), (2, 24, H))
    scores, idx = reference._route(a, params, config, {})
    total = 0.0
    for first in range(0, E, 16):
        p = dict(params, experts=jax.tree_util.tree_map(
            lambda t: t[first:first + 16], params["experts"]))
        part = jax.jit(lambda p: layer((first, first + 16), "share").call(
            p, u, route_from=a))(p)
        total = total + part
        np.testing.assert_allclose(np.asarray(part), np.asarray(
            reference._moe(u, scores, idx, p, (first, first + 16), {},
                           False)), atol=1e-5)
    uncut = reference._moe(u, scores, idx, params, (0, E), {}, False)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=1e-5)
    assert float(jnp.abs(uncut).max()) > 1e-3


# names and shapes of every parameter of each family the benchmark had, as
# the tree before the sliding window, the softmax router and the router
# before attention existed built them (the sha256 of the sorted (path,
# shape, dtype) list, 16 hex digits; leaves)
TREES = {"lfm2-8b-a1b.fit-seq16384-conv": ("41d141ecb9b05745", 33),
         "kanana-2-30b-a3b.fit-seq8192-b2": ("0b45963540ec91b6", 28),
         "kimi-linear-48b-a3b.fit-seq16384-b1": ("55ded8aeb76b466e", 91),
         "ouro-2.6b.fit-seq4096": ("d6b00af76d4502be", 14)}


@pytest.mark.parametrize("cell", sorted(TREES))
def test_the_other_families_build_the_trees_they_built(cell):
    import importlib

    import jax

    from benchmark import harness
    loaded = harness.load_cell(cell, rehearse=False)
    family = importlib.import_module("benchmark.models."
                                     + loaded["config"]["family"])
    model = family.build(loaded["config"], loaded["traffic"])
    shapes = jax.eval_shape(model.build, jax.random.PRNGKey(0))
    leaves = [(jax.tree_util.keystr(p), tuple(a.shape), str(a.dtype))
              for p, a in jax.tree_util.tree_leaves_with_path(shapes)]
    assert (hashlib.sha256(repr(leaves).encode()).hexdigest()[:16],
            len(leaves)) == TREES[cell]
    moe = getattr(model, "moe", None)
    if moe is not None:
        # their routers score by a sigmoid and read the FFN's own input
        assert moe.score == "sigmoid"
        assert not any(b.route_before_attention
                       for b in model.blocks.values())
