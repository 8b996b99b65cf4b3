"""The configuration `kanana-2-30b-a3b` and its cell
`kanana-2-30b-a3b.fit-seq8192-b2`: the file against the published config
(every width unchanged, three cuts of a stated deployment), the family's
FLOP and work counts against counts made by hand, the new per-layer
metrics' files and their kernel-name patterns, the new reader, and the
tiny cell through the benchmark's own command with `--rehearse`."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kanana-2-30b-a3b.fit-seq8192-b2"
NEW_METRICS = ["moe_fit_mfu", "mla_flash_time_share",
               "mla_flash_fwd_time_share", "mla_flash_bwd_time_share",
               "mla_flash_attention_roofline", "moe_experts_time_share",
               "moe_experts_roofline", "moe_held_slot_share"]
ACCEPTED_CELLS = ["bert-base.fit-seq128", "ncf-ml20m.fit-b1m",
                  "bert-base-pos2048.fit-seq2048-flash",
                  "ouro-2.6b.fit-seq4096"]

# https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/
# config.json, the keys that say something of the model's shape (the
# catalog row's `config`)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana-2-30b-a3b.json")) as fh:
        return json.load(fh)


def _traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "fit-seq8192-b2.json")) as fh:
        return json.load(fh)


def test_every_published_key_is_unchanged_but_the_three_cuts():
    cfg = _config()
    differ = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers", "n_routed_experts",
                      "vocab_size"} == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # the guide's floors: the dense layer and four expert layers, at least
    # 8 routed experts a layer, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["n_routed_experts"] == 16 and cfg["router_width"] == 128
    assert cfg["experts_held"] == [0, 16]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for word in ("8 TPU v5e chips", "expert-parallel", "pipeline",
                 "a quarter"):
        assert word in cfg["deployment"], word
    assert set(cfg["assumed"]) >= {"router_bias", "rotary", "initializer",
                                   "optimizer", "loss"}
    assert "float32" in cfg["precision"]["router"]


def test_reduced_never_names_a_width():
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_experts_per_tok",
              "n_shared_experts"}
    for key in _config()["reduced"]:
        assert not key.endswith(("_dim", "_rank")), key
        assert key not in widths, key


def test_the_cell_is_the_issues_traffic():
    t = _traffic()
    assert (t["kind"], t["seq_len"], t["batch_size"]) == ("fit", 8192, 2)
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
        == ("kanana-2-30b-a3b", "fit-seq8192-b2", 1)
    entry = {c["name"]: c for c in bench["configs"]}["kanana-2-30b-a3b"]
    assert entry["reduced"] == _config()["reduced"]
    assert entry["source"] == _config()["source"] and len(entry["source"]) == 85


def test_the_parameter_count_is_the_deployments_share():
    import jax

    from benchmark.models import kanana_moe
    model = kanana_moe.build(_config(), _traffic())
    shapes = jax.eval_shape(model.build, jax.random.PRNGKey(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048   # 26.35 M
    assert count(shapes["dense_blocks"]["attn"]) == attn + 512
    experts = shapes["moe_blocks"]["ffn"]["experts"]
    assert experts["gate_kernel"].shape == (4, 16, 2048, 768)
    assert experts["down_kernel"].shape == (4, 16, 768, 2048)
    assert count(experts) == 4 * 16 * 3 * 2048 * 768             # 302 M
    assert shapes["moe_blocks"]["ffn"]["router"]["kernel"].shape \
        == (4, 2048, 128)
    assert count(shapes["moe_blocks"]["ffn"]["shared"]) == 4 * 3 * 2048 * 1536
    assert shapes["word_embeddings"].shape == (16032, 2048)
    assert 575.5e6 < count(shapes) < 576.5e6


def test_flops_per_sample_against_a_hand_count():
    from benchmark.models import kanana_moe
    cfg, t = _config(), _traffic()
    # attention's projections, a layer:  2048*6144 + 2048*576 + 512*8192
    #                                    + 4096*2048           = 26,345,472
    # the dense layer's SwiGLU: 3 * 2048 * 6144               = 37,748,736
    # an expert layer outside attention: router 2048*128 = 262,144; shared
    #   3*2048*1536 = 9,437,184; 0.75 held experts of 3*2048*768 =
    #   4,718,592 each = 3,538,944                            = 13,238,272
    # the head over the slice: 2048 * 16032                   = 32,833,536
    weights = 5 * 26_345_472 + 37_748_736 + 4 * 13_238_272 + 32_833_536
    assert weights == 255_262_720
    attention = 3 * 5 * 8192 ** 2 * 32 * (192 + 128)
    assert attention == 10_307_921_510_400
    got = kanana_moe.flops_per_sample(cfg, t)
    assert got == 6 * 8192 * weights + attention == 22_854_594_723_840
    assert 0.44 < attention / got < 0.46
    # depth enters through the layers alone; the held experts through
    # the expectation k * held / width
    assert kanana_moe.flops_per_sample(
        dict(cfg, n_routed_experts=32, experts_held=[0, 32]), t) - got \
        == 6 * 8192 * 4 * 3_538_944


def test_kernel_work_and_its_roofline_ceilings():
    from benchmark import metrics
    from benchmark.models import kanana_moe
    cfg, t = _config(), _traffic()
    work = kanana_moe.kernel_work_per_sample(cfg, t)
    assert set(work) == {"attention", "experts"}
    # q and dq 192 wide, k_nope read three times over and dk written at
    # 128, v, dv, O twice and dO at 128: 3*192 + 3*128 + 5*128 values a
    # head and token; the shared rotary key of 64 read twice, written once
    assert work["attention"] == {
        "flops": 3.0 * 5 * 8192 ** 2 * 32 * 320,
        "bytes": 2.0 * 5 * 8192 * (32 * 1600 + 192)}
    # the operations decide at T = 8192
    a = work["attention"]
    assert a["flops"] / 197e12 > 10 * a["bytes"] / 819e9
    # 0.75 T rows a layer, 18 * 2048 * 768 operations a row
    rows = 0.75 * 8192
    e = work["experts"]
    assert e["flops"] == 18.0 * 4 * rows * 2048 * 768
    assert e["bytes"] == 2.0 * 4 * (2 * 16 * 3 * 2048 * 768 / 2
                                    + 5 * rows * 2048)
    assert e["flops"] / 197e12 > e["bytes"] / 819e9
    # grouped products that ran the forward twice at the peak read 9/12
    twelve = 12.0 / 9.0 * e["flops"] / 197e12
    assert metrics.roofline_percent(e["flops"], e["bytes"], twelve,
                                    "TPU v5 lite", chips=1) \
        == pytest.approx(75.0)


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
    for other in ACCEPTED_CELLS:
        assert name not in {m["name"] for m in harness.load_cell(
            other, False)["per_layer"]}
    for theirs in ("flash_time_share", "causal_flash_time_share",
                   "fit_mfu", "looplm_fit_mfu"):
        assert theirs not in {m["name"] for m in cell["per_layer"]}


OPS_NEW = ["flash_fwd_causal_mla.7@tpu_custom_call",
           "flash_dq_causal_mla.3@tpu_custom_call",
           "flash_dkv_causal_mla.4@tpu_custom_call",
           "flash_bwd_fused_causal_mla.2@tpu_custom_call",
           "moe_gmm_fwd.11@tpu_custom_call",
           "moe_gmm_dlhs.5@tpu_custom_call",
           "moe_gmm_drhs.6@tpu_custom_call"]
OPS_ACCEPTED = ["flash_fwd.3@tpu_custom_call",
                "flash_bwd_fused.4@tpu_custom_call",
                "flash_dq.1@tpu_custom_call", "flash_dkv.2@tpu_custom_call",
                "flash_fwd_causal.15@tpu_custom_call",
                "flash_bwd_fused_causal.9@tpu_custom_call",
                "flash_dq_causal.1@tpu_custom_call",
                "flash_dkv_causal.1@tpu_custom_call"]


def test_kernel_patterns_match_the_new_names_and_none_of_the_accepted():
    from benchmark import harness
    patterns = harness.op_patterns_for(
        harness.load_cell(CELL, rehearse=False)["per_layer"])
    ops = OPS_NEW + OPS_ACCEPTED + ["fusion.521", "gather.3"]

    def matched(metric):
        return [o for o in ops if re.search(patterns[metric], o)]
    assert matched("mla_flash_fwd_time_share") == OPS_NEW[:1]
    assert matched("mla_flash_bwd_time_share") == OPS_NEW[1:4]
    assert matched("mla_flash_time_share") == OPS_NEW[:4]
    assert matched("moe_experts_time_share") == OPS_NEW[4:]
    assert patterns["mla_flash_attention_roofline"] \
        == patterns["mla_flash_time_share"]
    assert patterns["moe_experts_roofline"] \
        == patterns["moe_experts_time_share"]


def test_the_accepted_cells_patterns_match_what_they_matched():
    """Each accepted cell's per-kernel patterns, on its OWN kernels' names:
    as before this configuration came."""
    from benchmark import harness
    flash = harness.op_patterns_for(harness.load_cell(
        "bert-base-pos2048.fit-seq2048-flash", False)["per_layer"])
    ouro = harness.op_patterns_for(harness.load_cell(
        "ouro-2.6b.fit-seq4096", False)["per_layer"])

    def matched(pattern, ops):
        return [o for o in ops if re.search(pattern, o)]
    own_flash, own_ouro = OPS_ACCEPTED[:4], OPS_ACCEPTED[4:]
    assert matched(flash["flash_time_share"], own_flash) == own_flash
    assert matched(flash["flash_fwd_time_share"], own_flash) == own_flash[:1]
    assert matched(flash["flash_bwd_fused_time_share"], own_flash) \
        == own_flash[1:2]
    assert matched(flash["flash_dq_time_share"], own_flash) == own_flash[2:3]
    assert matched(flash["flash_dkv_time_share"], own_flash) == own_flash[3:]
    assert matched(ouro["causal_flash_time_share"], own_ouro) == own_ouro
    assert matched(ouro["causal_flash_fwd_time_share"], own_ouro) \
        == own_ouro[:1]
    assert matched(ouro["causal_flash_bwd_time_share"], own_ouro) \
        == own_ouro[1:]


def test_registry_gauge_reader_reads_a_gauge_or_nothing():
    from benchmark import harness
    from benchmark.readers import registry_gauge
    spec = harness.reader_spec({"name": "moe_held_slot_share"})
    snap = {"moe_held_slot_share": {"kind": "gauge", "series": [
        {"labels": {"model": "m"}, "value": 12.75}]}}
    assert registry_gauge.read(spec, {"registry_after": snap}) == 12.75
    # the parent's program has no such gauge: nothing, and no error
    assert registry_gauge.read(spec, {"registry_after": {}}) is None
    assert registry_gauge.read(spec, {}) is None
    assert registry_gauge.read(dict(spec, labels={"model": "other"}),
                               {"registry_after": snap}) is None


def test_the_family_imports_without_the_programs_model():
    """The driver lays this PR's benchmark files over the parent's
    checkout: the family has to import there (and fail at `build`, at
    once), so it names the program's model inside `build` alone."""
    code = ("import sys; from benchmark.models import kanana_moe; "
            "assert 'analytics_zoo_tpu' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True)


def test_every_fault_moves_the_reference_at_a_small_size():
    import jax
    import numpy as np

    from benchmark import harness
    from benchmark.models import kanana_moe
    cell = harness.load_cell(CELL, rehearse=True)
    config, traffic = cell["config"], cell["traffic"]
    model = kanana_moe.build(config, traffic)
    params = kanana_moe.init_params(model, jax.random.PRNGKey(0))
    x = kanana_moe.check_inputs(config, traffic, 7, 2)
    want = kanana_moe.reference_outputs(params, x, config)
    assert sorted(kanana_moe.FAULTS) == [
        "causal_mask_dropped", "held_expert_dropped", "kv_norm_dropped",
        "rope_key_dropped", "routed_scale_dropped",
        "shared_experts_dropped"]
    for name, fault in kanana_moe.FAULTS.items():
        broken = kanana_moe.reference_outputs(params, x, config, **fault)
        assert np.sqrt(np.mean((broken - want) ** 2)) > 1e-3, name


@pytest.mark.parametrize("key, value", [
    ("norm_topk_prob", False), ("rope_interleave", False), ("n_group", 2),
    ("scoring_func", "softmax"), ("q_lora_rank", 1536),
    ("experts_held", [0, 8])])
def test_build_refuses_what_the_layers_do_not_have(key, value):
    from benchmark.models import kanana_moe
    with pytest.raises(ValueError, match="kanana_moe"):
        kanana_moe.build(dict(_config(), **{key: value}), _traffic())


def test_the_step_check_follows_the_systems_choice_and_holds_its_floor():
    """The step check's reference is handed the choice of the step's own
    forward (bfloat16 copies under mixed precision): its gradient is then
    no further from the system's than the freely routing reference's, a
    lost expert shows on the experts' leaves, and an agreement under the
    floor makes the loss not a number."""
    import jax
    import numpy as np

    from benchmark import compare, harness
    from benchmark.models import kanana_moe
    from benchmark.reference import kanana_moe as reference
    from benchmark.runners import fit
    cell = harness.load_cell(CELL, rehearse=True)
    config, traffic = cell["config"], cell["traffic"]
    assert 0 < config["reference_check"]["choice_agreement_floor"] < 1
    assert 0.9 <= _config()["reference_check"]["choice_agreement_floor"] < 1
    model = kanana_moe.build(config, traffic)
    params = kanana_moe.init_params(model, jax.random.PRNGKey(3))
    # 8 sequences: the test process has an 8-device data-parallel mesh
    batch = kanana_moe.step_batch(config, traffic, 3, 8)
    loss, grads = fit.system_step(kanana_moe, model, config, traffic,
                                  params, batch, 8)
    choice = kanana_moe._step_choice(params, batch["x"], config)
    assert choice.shape == (2, 8, traffic["seq_len"], 3)
    tight = compare.step_errors(
        loss, grads, *kanana_moe.reference_loss_and_grads(
            params, batch, config))
    free = compare.step_errors(loss, grads, *jax.device_get(
        jax.value_and_grad(lambda p: reference.reference_loss(
            p, batch, config))(params)))
    assert tight["grad_leaf_rel_err"] <= free["grad_leaf_rel_err"] + 1e-3
    assert tight["grad_leaf_rel_err"] < 0.05 and tight["grad_rel_err"] < 0.02
    lost = compare.step_errors(
        loss, grads, *kanana_moe.reference_loss_and_grads(
            params, batch, config, held_expert_dropped=True))
    assert lost["grad_leaf_rel_err"] > 0.4
    strict = dict(config, reference_check=dict(
        config["reference_check"], choice_agreement_floor=1.01))
    bad_loss, _ = kanana_moe.reference_loss_and_grads(params, batch, strict)
    assert np.isnan(bad_loss)
    assert not compare.step_within(
        compare.step_errors(loss, grads, bad_loss, grads),
        config["reference_check"])


def test_tiny_cell_prints_the_contracts_last_line():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 11), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=420)
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["device"]["platform"] == "cpu"
    assert line["metrics"]["fit_compiles_in_window"]["value"] == 0
    # a rehearsal's numbers never stand under a device metric's name; the
    # routing gauge is the program's own count
    assert set(NEW_METRICS) & set(line["metrics"]) == {"moe_held_slot_share"}
    assert 0 < line["metrics"]["moe_held_slot_share"]["value"] < 100
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
