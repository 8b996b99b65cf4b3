"""The configuration `kimi-linear-48b-a3b` and its cell
`kimi-linear-48b-a3b.fit-seq16384-b1`: the file against the published config
(every width unchanged, three cuts of a stated deployment), the family's
FLOP and work counts against counts made by hand, the new per-layer
metrics' files and their kernel-name patterns, every fault of the plain
reference, and the tiny cell through the benchmark's own command with
`--rehearse`."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kimi-linear-48b-a3b.fit-seq16384-b1"
NEW_METRICS = ["hybrid_fit_mfu", "kda_time_share", "kda_roofline",
               "hybrid_mla_flash_time_share"]
ACCEPTED_CELLS = ["bert-base.fit-seq128", "ncf-ml20m.fit-b1m",
                  "bert-base-pos2048.fit-seq2048-flash",
                  "ouro-2.6b.fit-seq4096", "kanana-2-30b-a3b.fit-seq8192-b2"]

# https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/
# config.json, the keys that say something of the model's shape (the
# catalog row's `config`)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as fh:
        return json.load(fh)


def _traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "fit-seq16384-b1.json")) as fh:
        return json.load(fh)


def test_every_published_key_is_unchanged_but_the_three_cuts():
    cfg = _config()
    differ = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers", "num_experts",
                      "vocab_size"} == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # the guide's floors: the dense layer and four expert layers (one whole
    # period of 3 : 1), 8 routed experts a layer, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["num_experts"] == 8 and cfg["router_width"] == 256
    assert cfg["experts_held"] == [0, 8]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    from benchmark.models import kimi_linear
    assert kimi_linear._mixers(cfg) == ["linear", "linear", "linear",
                                        "latent", "linear"]
    for word in ("32 TPU v5e chips", "expert-parallel", "pipeline",
                 "a thirty-second", "first five layers"):
        assert word in cfg["deployment"], word
    assert set(cfg["assumed"]) >= {
        "short_conv", "qk_norm", "gates", "decay", "output_norm",
        "latent_attention", "router_bias", "shared_experts", "initializer",
        "optimizer", "loss", "linear_chunk"}
    assert "float32" in cfg["precision"]["decay"]
    assert "float32" in cfg["precision"]["router"]


def test_reduced_never_names_a_width():
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_experts_per_token",
              "num_shared_experts", "linear_attn_config", "head_dim"}
    for key in _config()["reduced"]:
        assert not key.endswith(("_dim", "_rank")), key
        assert key not in widths, key


def test_the_cell_is_the_issues_traffic():
    t = _traffic()
    assert (t["kind"], t["seq_len"], t["batch_size"]) == ("fit", 16384, 1)
    assert t["fit_kwargs"] == {"mixed_precision": True,
                               "steps_per_run": t["steps_per_epoch"]}
    assert t["model_kwargs"] == {"use_flash": True, "remat": True}
    assert t["mesh_axes"] == {} and t["trace_epochs"] == 2
    fit = _config()["fit"]
    assert fit["optimizer"] == {"optax": "adamw",
                                "kwargs": {"learning_rate": 0.0001}}
    assert fit["loss"]["name"] == "sparse_categorical_crossentropy"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("kimi-linear-48b-a3b", "fit-seq16384-b1", 1)
    assert len(cell["why"]) <= 200 and "16,384" in cell["why"]
    entry = {c["name"]: c for c in bench["configs"]}["kimi-linear-48b-a3b"]
    assert entry["reduced"] == _config()["reduced"]
    assert entry["source"] == _config()["source"]
    assert entry["source"].startswith("https://huggingface.co/moonshotai/")
    assert bench["workloads"][-1] is cell and bench["configs"][-1] is entry
    assert [m["name"] for m in bench["per_layer"][-4:]] == NEW_METRICS


def test_the_parameter_count_is_the_deployments_share():
    import jax

    from benchmark.models import kimi_linear
    model = kimi_linear.build(_config(), _traffic())
    shapes = jax.eval_shape(model.build, jax.random.PRNGKey(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert sorted(k for k in shapes if "blocks" in k) == [
        "blocks_0_linear_dense", "blocks_1_linear_moe",
        "blocks_3_latent_moe", "blocks_4_linear_moe"]
    # q, k, v, o; two low-rank gates; beta; three 4-tap filters; A_log,
    # dt_bias, the gate's bias and the norm's weight
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 \
        + 3 * 4 * 4096 + 32 + 4096 + 4096 + 128
    assert count(shapes["blocks_0_linear_dense"]["attn"]) == kda
    assert 39.4e6 < kda < 39.6e6
    latent = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304   # 29.1 M
    assert count(shapes["blocks_3_latent_moe"]["attn"]) == latent + 512
    experts = shapes["blocks_1_linear_moe"]["ffn"]["experts"]
    assert experts["gate_kernel"].shape == (2, 8, 2304, 1024)
    assert experts["down_kernel"].shape == (2, 8, 1024, 2304)
    assert shapes["blocks_1_linear_moe"]["ffn"]["router"]["kernel"].shape \
        == (2, 2304, 256)
    assert count(shapes["blocks_4_linear_moe"]["ffn"]["shared"]) \
        == 3 * 2304 * 1024
    assert count(shapes["blocks_0_linear_dense"]["ffn"]) == 3 * 2304 * 9216
    assert shapes["word_embeddings"].shape == (20480, 2304)
    assert 602.0e6 < count(shapes) < 603.0e6


def test_flops_per_sample_against_a_hand_count():
    from benchmark.models import kimi_linear
    cfg, t = _config(), _traffic()
    # a KDA layer's matmul weights: q, k, v, o 4 * 2304*4096 = 37,748,736;
    #   the two low-rank gates 2 * (2304*128 + 128*4096) = 1,638,400; beta
    #   2304*32 = 73,728                                      = 39,460,864
    # the latent layer's: 2304*6144 + 2304*576 + 512*8192 + 4096*2304
    #                                                         = 29,114,368
    # the dense layer's SwiGLU: 3 * 2304 * 9216               = 63,700,992
    # an expert layer outside its mixer: router 2304*256 = 589,824; shared
    #   3*2304*1024 = 7,077,888; 0.25 held experts of 7,077,888 each
    #   = 1,769,472                                           =  9,437,184
    # the head over the slice: 2304 * 20480                   = 47,185,920
    weights = 4 * 39_460_864 + 29_114_368 + 63_700_992 + 4 * 9_437_184 \
        + 47_185_920
    assert weights == 335_593_472
    attention = 3 * 1 * 16384 ** 2 * 32 * (192 + 128)
    recurrence = 3 * 7 * 128 * 128 * 32 * 16384 * 4
    assert recurrence == 721_554_505_728
    got = kimi_linear.flops_per_sample(cfg, t)
    assert got == 6 * 16384 * weights + attention + recurrence
    assert 0.19 < attention / got < 0.21 and recurrence / got < 0.02
    # the held experts enter through the expectation k * held / width
    assert kimi_linear.flops_per_sample(
        dict(cfg, num_experts=16, experts_held=[0, 16]), t) - got \
        == 6 * 16384 * 4 * 1_769_472


def test_kernel_work_is_the_chunk_kernels_own_job():
    from benchmark import metrics
    from benchmark.models import kimi_linear
    cfg, t = _config(), _traffic()
    work = kimi_linear.kernel_work_per_sample(cfg, t)
    # no entry that no metric reads (REVIEW, PR 32)
    assert set(work) == {"kda"}
    per_token_head = 16384 * 32 * 4
    # a token's share of a chunk of 64, bfloat16: W, QG, KD 256 each, U~
    # 256, P 128, exp(G_C) 128 * 4 / 64 = 8: 1160; forward + O 256 = 1416;
    # backward: 1160 read, the state 128 * 128 * 2 / 64 = 512, dO 256, and
    # the six gradients 1160 written = 3088
    # operations: forward 3 products of 2 * 128 * 128 and one of
    # 2 * 64 * 128 = 114,688; backward 7 and 2 = 262,144
    assert work["kda"] == {"flops": 376_832.0 * per_token_head,
                           "bytes": 4504.0 * per_token_head}
    k = work["kda"]
    # the bytes decide: 11.5 ms a step against 4.0 ms
    assert 11.4e-3 < k["bytes"] / 819e9 < 11.7e-3
    assert 3.9e-3 < k["flops"] / 197e12 < 4.1e-3
    assert metrics.roofline_percent(k["flops"], k["bytes"],
                                    2 * k["bytes"] / 819e9, "TPU v5 lite",
                                    chips=1) == pytest.approx(50.0)
    # the kernels' second forward (states written) is what keeps the share
    # under 70%
    assert 4504 / (4504 + 1416 + 512) == pytest.approx(0.700, abs=1e-3)
    # a chunk of 128 halves the states' and the decay's share of a token
    # (the decay is moved three times, P twice as wide as often)
    wide = kimi_linear.kda_work(dict(cfg, linear_chunk=128), t)
    assert wide["bytes"] == (4504.0 - 256 - 3 * 4 + 3 * 128) * per_token_head


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_files_load_and_are_reported_in_the_cell_alone(name):
    from benchmark import harness
    cell = harness.load_cell(CELL, rehearse=False)
    entry = {m["name"]: m for m in cell["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "fit_samples_per_s" and entry["unit"] == "%"
    spec = harness.reader_spec(entry)
    assert spec["reader"] in ("harness", "trace_op_share",
                              "trace_op_roofline")
    assert spec["what"]
    if spec["reader"] == "trace_op_roofline":
        assert spec["work"] == "kda"
    for other in ACCEPTED_CELLS:
        assert name not in {m["name"] for m in harness.load_cell(
            other, False)["per_layer"]}
    for theirs in ("flash_time_share", "causal_flash_time_share", "fit_mfu",
                   "looplm_fit_mfu", "moe_fit_mfu", "mla_flash_time_share",
                   "moe_experts_time_share", "moe_held_slot_share"):
        assert theirs not in {m["name"] for m in cell["per_layer"]}


OPS_NEW = ["kda_chunk_fwd.5@tpu_custom_call",
           "kda_chunk_bwd.2@tpu_custom_call"]
OPS_MLA = ["flash_fwd_causal_mla.7@tpu_custom_call",
           "flash_dq_causal_mla.3@tpu_custom_call",
           "flash_dkv_causal_mla.4@tpu_custom_call",
           "flash_bwd_fused_causal_mla.2@tpu_custom_call"]
OPS_OTHER = ["moe_gmm_fwd.11@tpu_custom_call",
             "moe_gmm_dlhs.5@tpu_custom_call",
             "moe_gmm_drhs.6@tpu_custom_call", "flash_fwd.3@tpu_custom_call",
             "flash_bwd_fused.4@tpu_custom_call",
             "flash_dq.1@tpu_custom_call", "flash_dkv.2@tpu_custom_call",
             "flash_fwd_causal.15@tpu_custom_call",
             "flash_bwd_fused_causal.9@tpu_custom_call",
             "flash_dq_causal.1@tpu_custom_call",
             "flash_dkv_causal.1@tpu_custom_call", "fusion.521", "gather.3",
             "kda_gates_fusion.3"]


def test_kernel_patterns_match_the_new_names_and_none_of_the_accepted():
    from benchmark import harness
    patterns = harness.op_patterns_for(
        harness.load_cell(CELL, rehearse=False)["per_layer"])
    ops = OPS_NEW + OPS_MLA + OPS_OTHER

    def matched(pattern):
        return [o for o in ops if re.search(pattern, o)]
    assert matched(patterns["kda_time_share"]) == OPS_NEW
    assert patterns["kda_roofline"] == patterns["kda_time_share"]
    assert matched(patterns["hybrid_mla_flash_time_share"]) == OPS_MLA
    # what the expert cell's share of the same kernels matches
    theirs = harness.op_patterns_for(harness.load_cell(
        "kanana-2-30b-a3b.fit-seq8192-b2", False)["per_layer"])
    assert patterns["hybrid_mla_flash_time_share"] \
        == theirs["mla_flash_time_share"]


def test_the_family_imports_without_the_programs_model():
    """The driver lays this PR's benchmark files over the parent's
    checkout: the family has to import there (and fail at `build`, at
    once), so it names the program's model inside `build` alone."""
    code = ("import sys; from benchmark.models import kimi_linear; "
            "assert 'analytics_zoo_tpu' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True)


def test_every_fault_moves_the_reference_at_a_small_size():
    import jax
    import numpy as np

    from benchmark import harness
    from benchmark.models import kimi_linear
    cell = harness.load_cell(CELL, rehearse=True)
    config, traffic = cell["config"], cell["traffic"]
    # a rehearsal's KDA values are not as wide as its keys
    lin = config["linear_attn_config"]
    assert lin["v_head_dim"] != lin["head_dim"]
    assert traffic["seq_len"] >= 2 * config["linear_chunk"]
    model = kimi_linear.build(config, traffic)
    params = kimi_linear.init_params(model, jax.random.PRNGKey(0))
    x = kimi_linear.check_inputs(config, traffic, 7, 2)
    want = kimi_linear.reference_outputs(params, x, config)
    assert sorted(kimi_linear.FAULTS) == [
        "beta_dropped", "causal_mask_dropped", "decay_dropped",
        "decay_per_head", "out_gate_dropped", "qk_norm_dropped",
        "shared_experts_dropped", "short_conv_dropped"]
    # the reference's ninth fault, which no forward limit can tell on the
    # chip (the configuration's `reference_check.why`), moves it too
    faults = dict(kimi_linear.FAULTS, rotary_applied={"rotary_applied": True})
    for name, fault in faults.items():
        broken = kimi_linear.reference_outputs(params, x, config, **fault)
        assert np.sqrt(np.mean((broken - want) ** 2)) > 2e-5, name


def test_the_step_tells_its_own_choice_and_the_reference_is_handed_it():
    import jax
    import numpy as np

    from benchmark import harness
    from benchmark.models import kanana_moe, kimi_linear
    from benchmark.runners import fit
    cell = harness.load_cell(CELL, rehearse=True)
    config, traffic = cell["config"], cell["traffic"]
    model = kimi_linear.build(config, traffic)
    params = kimi_linear.init_params(model, jax.random.PRNGKey(0))
    # 8 sequences: the test process has an 8-device data-parallel mesh
    batch = kimi_linear.step_batch(config, traffic, 7, 8)
    fit.system_step(kimi_linear, model, config, traffic, params, batch, 8)
    jax.effects_barrier()
    second = kimi_linear._second_program_choice(params, batch["x"], config)
    layers, per_layer = len(second), second[0].reshape(-1, second.shape[-1])
    # every expert layer told its choice twice: the forward pass and the
    # recomputation; the timed model's routing tells nothing
    assert len(kimi_linear._told) == 2 * layers
    assert all(t.shape == per_layer.shape for t in kimi_linear._told)
    assert "routing" not in vars(model.moe)
    # the fit shuffles its batch: a told sequence finds its own place
    own = kimi_linear._step_choice(params, batch["x"], config)
    agree = kanana_moe._agreement(own.reshape(layers, *per_layer.shape),
                                  second.reshape(layers, *per_layer.shape))
    assert min(agree) > 0.97
    # what the step told wins over the second program, sequence by
    # sequence, and a later telling over an earlier one
    moved = per_layer.copy()
    moved[:5] = (moved[:5] + 1) % config["router_width"]
    kimi_linear._told.append(moved)
    own = kimi_linear._step_choice(params, batch["x"], config)
    assert np.array_equal(own[0].reshape(per_layer.shape), moved)
    # a second stepped model starts the list anew
    kimi_linear.without_dropout(model, config, traffic)
    assert kimi_linear._told == []


@pytest.mark.parametrize("key, value", [
    ("moe_renormalize", False), ("mla_use_nope", False),
    ("num_expert_group", 2), ("moe_router_activation_func", "softmax"),
    ("q_lora_rank", 1536), ("experts_held", [0, 16]),
    ("linear_attn_config", dict(PUBLISHED["linear_attn_config"],
                                kda_layers=[1, 2]))])
def test_build_refuses_what_the_layers_do_not_have(key, value):
    from benchmark.models import kimi_linear
    with pytest.raises(ValueError, match="kimi_linear"):
        kimi_linear.build(dict(_config(), **{key: value}), _traffic())


def test_tiny_cell_prints_the_contracts_last_line():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 17), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["device"]["platform"] == "cpu"
    assert line["metrics"]["fit_compiles_in_window"]["value"] == 0
    # a rehearsal's numbers never stand under a device metric's name
    assert not set(NEW_METRICS) & set(line["metrics"])
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
