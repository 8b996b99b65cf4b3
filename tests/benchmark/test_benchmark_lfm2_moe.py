"""The configuration `lfm2-8b-a1b` and its cell
`lfm2-8b-a1b.fit-seq16384-conv`: the file against the published config
(every width unchanged, three cuts of a stated deployment), found in
BENCHMARK.json by name; the parameter count of the cut; the family's FLOP
and work counts against counts made by hand; the new per-layer metrics'
files and their kernel-name patterns; the system against the plain
reference at a tiny size (logits, loss and every gradient leaf); the four
shares of an expert layer against the uncut layer; every fault of the
reference; and the tiny cell through the benchmark's own command with
`--rehearse`."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm2-8b-a1b.fit-seq16384-conv"
CONFIG = "lfm2-8b-a1b"
NEW_METRICS = ["conv_hybrid_fit_mfu", "gated_conv_time_share",
               "gated_conv_roofline", "gqa_flash_time_share",
               "gqa_flash_attention_roofline", "conv_moe_experts_time_share",
               "conv_moe_experts_roofline", "conv_moe_held_slot_share"]

# https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json, the
# catalog row's `config`
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as fh:
        return json.load(fh)


def _traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "fit-seq16384-conv.json")) as fh:
        return json.load(fh)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _tiny():
    from benchmark import harness
    cell = harness.load_cell(CELL, rehearse=True)
    return cell["config"], cell["traffic"]


def test_every_published_key_is_unchanged_but_the_three_cuts():
    cfg = _config()
    differ = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers", "num_experts",
                      "vocab_size"} == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # the guide's floors: the two dense layers and four after them (one
    # whole period of 1 attention : 3 conv), 8 routed experts a layer, an
    # eighth of the vocabulary (a quarter here)
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] == 4
    assert cfg["num_experts"] == 8 and cfg["router_width"] == 32
    assert cfg["experts_held"] == [0, 8]
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    from benchmark.models import lfm2_moe
    assert lfm2_moe._mixers(cfg) == ["conv", "conv", "gqa", "conv", "conv",
                                     "conv"]
    for word in ("4 TPU v5e chips", "expert-parallel", "pipeline",
                 "a quarter", "first six layers", "568.65 M"):
        assert word in cfg["deployment"], word
    assert set(cfg["assumed"]) >= {
        "head_dim", "tie_word_embeddings", "conv_split", "conv_filter",
        "qk_norm", "rotary", "router", "expert_bias", "dense_ffn",
        "initializer", "optimizer", "loss", "packing"}
    assert "1e-6" in cfg["assumed"]["router"]
    assert cfg["router_norm_eps"] == 1e-6
    assert "float32" in cfg["precision"]["filter"]
    assert "float32" in cfg["precision"]["router"]


def test_reduced_never_names_a_width():
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads",
              "num_experts_per_tok", "conv_L_cache", "head_dim"}
    for key in _config()["reduced"]:
        assert not key.endswith(("_dim", "_rank")), key
        assert key not in widths, key


def test_the_configuration_entry_is_found_by_name():
    bench = _bench()
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    cfg = _config()
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert cfg["name"] == CONFIG and cfg["family"] == "lfm2_moe"
    assert len(entry["why"]) <= 200


def test_the_cell_is_the_issues_traffic():
    t = _traffic()
    assert (t["kind"], t["seq_len"], t["batch_size"]) == ("fit", 16384, 1)
    assert t["fit_kwargs"] == {"mixed_precision": True,
                               "steps_per_run": t["steps_per_epoch"]}
    assert t["model_kwargs"] == {"use_flash": True, "remat": True}
    assert t["mesh_axes"] == {} and t["trace_epochs"] == 2
    assert (t["rehearsal"]["seq_len"], t["rehearsal"]["steps_per_epoch"]) \
        == (128, 3)
    fit = _config()["fit"]
    assert fit["optimizer"] == {"optax": "adamw",
                                "kwargs": {"learning_rate": 0.0001}}
    assert fit["loss"]["name"] == "sparse_categorical_crossentropy"
    cell = {w["name"]: w for w in _bench()["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "fit-seq16384-conv", 1)
    assert len(cell["why"]) <= 200 and "16,384" in cell["why"]
    assert f"{t['steps_per_epoch']}-step" in cell["why"]


def _count(tree):
    import jax
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def test_the_parameter_count_is_the_deployments_share():
    import jax

    from benchmark.models import lfm2_moe
    model = lfm2_moe.build(_config(), _traffic())
    shapes = jax.eval_shape(model.build, jax.random.PRNGKey(0))
    assert sorted(k for k in shapes if "blocks" in k) == [
        "blocks_0_conv_dense", "blocks_2_gqa_moe", "blocks_3_conv_moe"]
    assert "lm_head_kernel" not in shapes                   # tied
    # in 2048 x 6144, out 2048 x 2048, three taps a channel
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    assert _count(shapes["blocks_0_conv_dense"]["attn"]) == 2 * conv
    assert _count(shapes["blocks_3_conv_moe"]["attn"]) == 3 * conv
    # q, o 2048 x 2048; k, v 2048 x 512; the two norms' weights of 64
    gqa = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert _count(shapes["blocks_2_gqa_moe"]["attn"]) == gqa
    experts = shapes["blocks_3_conv_moe"]["ffn"]["experts"]
    assert experts["gate_kernel"].shape == (3, 8, 2048, 1792)
    assert experts["down_kernel"].shape == (3, 8, 1792, 2048)
    assert shapes["blocks_3_conv_moe"]["ffn"]["router"]["kernel"].shape \
        == (3, 2048, 32)
    assert "shared" not in shapes["blocks_3_conv_moe"]["ffn"]
    assert _count(shapes["blocks_0_conv_dense"]["ffn"]) == 2 * 3 * 2048 * 7168
    assert shapes["word_embeddings"].shape == (16384, 2048)
    assert _count(shapes) == 568_647_936
    # 16 bytes a parameter: 9.10 GB
    assert 9.09e9 < 16 * _count(shapes) < 9.11e9


def test_flops_per_sample_against_a_hand_count():
    from benchmark.models import lfm2_moe
    cfg, t = _config(), _traffic()
    # a conv layer's matmul weights: 2048*6144 + 2048*2048  = 16,777,216
    # the attention layer's: 2 * 2048*2048 + 2 * 2048*512   = 10,485,760
    # a dense layer's SwiGLU: 3 * 2048 * 7168               = 44,040,192
    # an expert layer outside its mixer: router 2048*32 = 65,536; one held
    #   expert a token (4 * 8 / 32) of 3*2048*1792          = 11,075,584
    # the tied head over the slice: 2048 * 16384            = 33,554,432
    weights = 5 * 16_777_216 + 10_485_760 + 2 * 44_040_192 \
        + 4 * 11_075_584 + 33_554_432
    assert weights == 260_308_992
    attention = 3 * 1 * 16384 ** 2 * 32 * (64 + 64)
    got = lfm2_moe.flops_per_sample(cfg, t)
    assert got == 6 * 16384 * weights + attention
    assert 28.8e12 < got < 29.0e12
    assert 0.11 < attention / got < 0.12
    assert lfm2_moe.flops_per_sample(
        dict(cfg, num_experts=16, experts_held=[0, 16]), t) - got \
        == 6 * 16384 * 4 * 11_010_048


def test_kernel_work_is_each_kernels_own_job():
    from benchmark import metrics
    from benchmark.models import lfm2_moe
    cfg, t = _config(), _traffic()
    work = lfm2_moe.kernel_work_per_sample(cfg, t)
    assert set(work) == {"gated_conv", "gqa_attention", "experts"}
    tokens = 16384 * 5
    # forward 6144 read + 2048 written; backward 6144 + 2048 read and 6144
    # written; bfloat16, a token and conv layer
    assert work["gated_conv"]["bytes"] == 2.0 * (8192 + 14336) * tokens
    assert work["gated_conv"]["flops"] == (8 + 23) * 2048.0 * tokens
    g = work["gated_conv"]
    # the bytes decide: 4.5 ms a step
    assert 4.4e-3 < g["bytes"] / 819e9 < 4.6e-3
    assert g["flops"] / 197e12 < 1e-4
    a = work["gqa_attention"]
    assert a["flops"] == 7.0 * 16384 ** 2 * 32 * 64
    # K, V and their gradients once a K/V head (8), the rest a query head
    assert a["bytes"] == 2.0 * 16384 * 64 * (6 * 32 + 6 * 8) \
        + 8.0 * 16384 * 32
    assert a["flops"] / 197e12 > 10 * a["bytes"] / 819e9
    assert metrics.roofline_percent(a["flops"], a["bytes"],
                                    2 * a["flops"] / 197e12, "TPU v5 lite",
                                    chips=1) == pytest.approx(50.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_files_load_and_are_reported_in_the_cell_alone(name):
    from benchmark import harness
    cell = harness.load_cell(CELL, rehearse=False)
    entry = {m["name"]: m for m in cell["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "fit_samples_per_s" and entry["unit"] == "%"
    spec = harness.reader_spec(entry)
    assert spec["reader"] in ("harness", "trace_op_share",
                              "trace_op_roofline", "registry_gauge")
    assert spec["what"]
    if spec["reader"] == "trace_op_roofline":
        assert spec["work"] in ("gated_conv", "gqa_attention", "experts")
    others = [w["name"] for w in _bench()["workloads"] if w["name"] != CELL]
    for other in others:
        assert name not in {m["name"] for m in harness.load_cell(
            other, False)["per_layer"]}
    # the accepted per-layer metrics with a workloads list are not the
    # new cell's
    accepted = [m["name"] for m in _bench()["per_layer"]
                if "workloads" in m and m["name"] not in NEW_METRICS]
    assert not set(accepted) & {m["name"] for m in cell["per_layer"]}


OPS_CONV = ["gated_conv_fwd.5@tpu_custom_call",
            "gated_conv_bwd.2@tpu_custom_call"]
OPS_GQA = ["flash_fwd_causal_gqa.7@tpu_custom_call",
           "flash_bwd_fused_causal_gqa.3@tpu_custom_call",
           "flash_dq_causal_gqa.1@tpu_custom_call",
           "flash_dkv_causal_gqa.1@tpu_custom_call"]
OPS_OTHER = ["qkv_short_conv_fwd.7@tpu_custom_call",
             "qkv_short_conv_bwd.1@tpu_custom_call",
             "kda_chunk_fwd.5@tpu_custom_call",
             "moe_gmm_fwd.11@tpu_custom_call",
             "flash_fwd_causal_mla.7@tpu_custom_call",
             "flash_fwd_causal.15@tpu_custom_call",
             "flash_bwd_fused_causal.9@tpu_custom_call",
             "flash_fwd.3@tpu_custom_call", "fusion.521", "gather.3"]


def test_experts_work_is_the_held_experts_job_under_even_routing():
    from benchmark.models import lfm2_moe
    cfg, t = _config(), _traffic()
    e = lfm2_moe.experts_work(cfg, t)
    # 4 expert layers; T rows a layer (4 x 8 / 32 slots a token); 18 H I
    # operations a row; the 8 held experts' weights twice and 5 row
    # vectors a row, bfloat16
    assert e["flops"] == 18.0 * 4 * 16384 * 2048 * 1792
    assert e["bytes"] == 2.0 * 4 * (2 * 8 * 3 * 2048 * 1792
                                    + 5 * 16384 * 2048)
    # the operations decide: 22 ms a step at the bf16 peak
    assert 0.021 < e["flops"] / 197e12 < 0.023
    assert e["bytes"] / 819e9 < e["flops"] / 197e12


def test_kernel_patterns_match_the_new_names_and_no_accepted_one():
    from benchmark import harness
    patterns = harness.op_patterns_for(
        harness.load_cell(CELL, rehearse=False)["per_layer"])
    ops = OPS_CONV + OPS_GQA + OPS_OTHER

    def matched(pattern):
        return [o for o in ops if re.search(pattern, o)]
    assert matched(patterns["gated_conv_time_share"]) == OPS_CONV
    assert patterns["gated_conv_roofline"] \
        == patterns["gated_conv_time_share"]
    assert matched(patterns["gqa_flash_time_share"]) == OPS_GQA
    assert patterns["gqa_flash_attention_roofline"] \
        == patterns["gqa_flash_time_share"]
    assert matched(patterns["conv_moe_experts_time_share"]) \
        == ["moe_gmm_fwd.11@tpu_custom_call"]
    assert patterns["conv_moe_experts_roofline"] \
        == patterns["conv_moe_experts_time_share"]
    # no accepted kernel-name pattern of another cell reads the new
    # kernels, except the catch-alls of Pallas calls
    for other in [w["name"] for w in _bench()["workloads"] if w["name"]
                  != CELL]:
        for name, pattern in harness.op_patterns_for(harness.load_cell(
                other, False)["per_layer"]).items():
            if "@tpu_custom_call$|" in pattern:
                continue
            assert not any(re.search(pattern, o) for o in OPS_CONV), name


def test_the_family_imports_without_the_programs_model():
    """The driver lays this PR's benchmark files over the parent's
    checkout: the family has to import there (and fail at `build`, at
    once), so it names the program's model inside `build` alone."""
    code = ("import sys; from benchmark.models import lfm2_moe; "
            "assert 'analytics_zoo_tpu' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True)


def _tiny_model(seed=0, bias=0.1):
    """The rehearsal's model, its expert layers' bias (zeros as built)
    drawn in +-`bias`, so that the bias moves the choice."""
    import jax

    from benchmark.models import lfm2_moe
    config, traffic = _tiny()
    model = lfm2_moe.build(config, traffic)
    params = lfm2_moe.init_params(model, jax.random.PRNGKey(seed))
    router = params["blocks_3_conv_moe"]["ffn"]["router"]
    assert not router["bias"].any()
    for i, name in enumerate(("blocks_2_gqa_moe", "blocks_3_conv_moe")):
        r = params[name]["ffn"]["router"]
        r["bias"] = jax.random.uniform(jax.random.PRNGKey(100 + i),
                                       r["bias"].shape, minval=-bias,
                                       maxval=bias)
    return config, traffic, model, params


def test_the_system_is_the_reference_logits_loss_and_every_gradient():
    """float32 on the CPU, the system's own forward and gradient (no
    kernels off the TPU) against the reference at the system's choice."""
    import jax

    from analytics_zoo_tpu.ops import objectives
    from benchmark.models import lfm2_moe
    from benchmark.reference import lfm2_moe as reference
    config, traffic, model, params = _tiny_model(seed=3)
    batch = lfm2_moe.step_batch(config, traffic, 5, 2)
    choice = model.expert_choice(params, batch["x"])
    want, own = reference.reference_forward(params, batch["x"], config)
    assert np.array_equal(np.asarray(own), np.asarray(choice))
    got = model.apply(params, batch["x"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)
    loss = objectives.get("sparse_categorical_crossentropy",
                          from_logits=True)
    sys_loss, sys_grads = jax.value_and_grad(lambda p: loss(
        batch["y"], model.apply(p, batch["x"], training=True)))(params)
    (ref_loss, _), ref_grads = jax.value_and_grad(
        lambda p: reference.reference_loss_and_choice(
            p, batch, config, choice=choice), has_aux=True)(params)
    assert abs(float(sys_loss) - float(ref_loss)) < 1e-5
    flat_sys = jax.tree_util.tree_leaves_with_path(sys_grads)
    flat_ref = jax.tree_util.tree_leaves(ref_grads)
    assert len(flat_sys) == len(flat_ref)
    for (path, g), r in zip(flat_sys, flat_ref):
        name = jax.tree_util.keystr(path)
        g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
        if name.endswith("['bias']"):           # chooses, never weighs
            assert not g.any() and not r.any(), name
            continue
        assert np.linalg.norm(g - r) <= 1e-4 * np.linalg.norm(r) + 1e-9, \
            name


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips' shares of a 32-expert layer (what no chip computes
    alike: there is no shared expert), each the system's expert layer
    told its range, add up to the reference's layer holding all 32."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.keras.moe import MoEFeedForward
    from benchmark.reference import lfm2_moe as reference
    H, I, E, k = 32, 16, 32, 4
    config = {"num_experts_per_tok": k, "routed_scaling_factor": 1.0}
    whole = MoEFeedForward(H, I, E, k, init="normal", norm_eps=1e-6,
                           name="moe_whole")
    params = whole.build(jax.random.PRNGKey(0))
    params["router"]["bias"] = jax.random.uniform(
        jax.random.PRNGKey(1), (E,), minval=-0.1, maxval=0.1)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 24, H))
    total = 0.0
    for first in range(0, E, 8):
        share = MoEFeedForward(H, I, E, k, experts_held=(first, first + 8),
                               init="normal", norm_eps=1e-6,
                               name=f"moe_share_{first}")
        p = dict(params, experts=jax.tree_util.tree_map(
            lambda a: a[first:first + 8], params["experts"]))
        total = total + share.call(p, u)
        part, _ = reference._moe(u, p, config, (first, first + 8), None, {},
                                 False)
        np.testing.assert_allclose(np.asarray(share.call(p, u)),
                                   np.asarray(part), atol=1e-5)
    uncut, _ = reference._moe(u, params, config, (0, E), None, {}, False)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=1e-5)
    assert float(jnp.abs(uncut).max()) > 1e-3


@pytest.mark.parametrize("fault", [
    "gates_dropped", "filter_dropped", "filter_anticausal",
    "qk_norm_dropped", "rotary_dropped", "gqa_interleaved",
    "expert_bias_in_weights", "causal_mask_dropped", "reference_bfloat16"])
def test_every_fault_moves_the_reference_and_fails_a_tight_check(fault):
    """The seven of FAULTS and the two the step check alone tells on the
    chip (the configuration's `reference_check.why`)."""
    from benchmark import compare
    from benchmark.models import lfm2_moe
    step_only = {"qk_norm_dropped", "expert_bias_in_weights"}
    assert (fault in lfm2_moe.FAULTS) != (fault in step_only)
    config, traffic, model, params = _tiny_model(seed=1)
    x = lfm2_moe.check_inputs(config, traffic, 7, 2)
    got = lfm2_moe.system_outputs(model, params, x)
    want = lfm2_moe.reference_outputs(params, x, config)
    assert compare.within(compare.errors(got, want),
                          {"atol": 1e-5, "rms": 1e-6})
    broken = lfm2_moe.reference_outputs(params, x, config, **{fault: True})
    err = compare.errors(got, broken)
    assert not compare.within(err, {"atol": 1e-5, "rms": 1e-6}), err
    assert err["rms_err"] > 5e-5, err


@pytest.mark.parametrize("key, value", [
    ("experts_held", [0, 16]), ("conv_bias", True),
    ("norm_topk_prob", False), ("use_expert_bias", False),
    ("tie_word_embeddings", False), ("head_dim", 32),
    ("layer_types", ["conv", "sliding_attention"] * 12)])
def test_build_refuses_what_the_layers_do_not_have(key, value):
    from benchmark.models import lfm2_moe
    with pytest.raises(ValueError, match="lfm2_moe"):
        lfm2_moe.build(dict(_config(), **{key: value}), _traffic())


def test_tiny_cell_prints_the_contracts_last_line():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 23), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["device"]["platform"] == "cpu"
    assert line["metrics"]["fit_compiles_in_window"]["value"] == 0
    # a rehearsal's numbers never stand under a device metric's name; the
    # program's own gauge is read on any device
    assert set(NEW_METRICS) & set(line["metrics"]) \
        == {"conv_moe_held_slot_share"}
    assert 0 < line["metrics"]["conv_moe_held_slot_share"]["value"] < 100
    for check in ("reference_check ", "step_check ", "moe_routing ",
                  "moe_choice_agreement_by_layer ",
                  "moe_step_choice_agreement_by_layer "):
        said = [ln for ln in lines if ln.startswith(check)]
        assert said, check
        if check.endswith("_check "):
            assert said[0].endswith("ok=True"), said
    losses = json.loads([ln for ln in lines if ln.startswith(
        "epoch_losses ")][0].split(" ", 1)[1])
    assert losses[-1] < losses[0]
