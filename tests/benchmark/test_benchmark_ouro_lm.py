"""The configuration `ouro-2.6b` and its cell `ouro-2.6b.fit-seq4096`:
the file against the published config (every width unchanged, one cut),
the family's FLOP and work counts against counts made by hand, the new
per-layer metrics' files, and the tiny cell through the benchmark's own
command with `--rehearse`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ouro-2.6b.fit-seq4096"
NEW_METRICS = ["looplm_fit_mfu", "causal_flash_time_share",
               "causal_flash_attention_roofline",
               "causal_flash_fwd_time_share", "causal_flash_bwd_time_share"]

# https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json, the
# keys that say something of the model's shape
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as fh:
        return json.load(fh)


def _traffic():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "fit-seq4096.json")) as fh:
        return json.load(fh)


def test_every_published_key_is_unchanged_but_the_depth():
    cfg = _config()
    differ = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(cfg["reduced"])
    # the cut: 6 to 8 whole layers, the published count beside it
    assert 6 <= cfg["num_hidden_layers"] <= 8
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert "pipeline" in cfg["deployment"]
    # what the published config does not fix is said to be assumed
    assert set(cfg["assumed"]) >= {"norm_placement", "final_norm",
                                   "exit_gate", "projections", "rotary",
                                   "optimizer"}


def test_reduced_never_names_a_width():
    widths = {"hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads", "vocab_size",
              "total_ut_steps"}
    for key in _config()["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")), key
        assert key not in widths, key


def test_the_cell_is_the_issues_traffic():
    t = _traffic()
    assert (t["kind"], t["seq_len"], t["batch_size"]) == ("fit", 4096, 2)
    assert t["steps_per_epoch"] in (5, 6, 7)
    assert t["fit_kwargs"] == {"mixed_precision": True,
                               "steps_per_run": t["steps_per_epoch"]}
    assert t["model_kwargs"] == {"use_flash": True, "remat": True}
    assert t["mesh_axes"] == {} and t["trace_epochs"] == 2
    fit = _config()["fit"]
    assert fit["optimizer"] == {"optax": "adamw",
                                "kwargs": {"learning_rate": 0.0001}}
    assert fit["loss"]["name"] == "sparse_categorical_crossentropy"


def test_flops_per_sample_against_a_hand_count():
    from benchmark.models import ouro_lm
    cfg = dict(_config(), num_hidden_layers=8)
    # a layer application: QKV and output 4 * 2048^2 = 16,777,216; gate,
    # up and down 3 * 2048 * 5632 = 34,603,008; together 51,380,224
    # 32 applications: 1,644,167,168; the head 2048 * 49152 = 100,663,296
    # 6 per weight and token, 4096 tokens:    42,880,953,483,264
    # attention: 6 * 32 * 4096^2 * 2048     =  6,597,069,766,656
    got = ouro_lm.flops_per_sample(cfg, _traffic())
    assert got == 6 * 4096 * (32 * 51_380_224 + 100_663_296) \
        + 6_597_069_766_656
    assert got == 49_478_023_249_920
    assert 0.13 < 6_597_069_766_656 / got < 0.14    # causal attention
    # depth enters through the applications alone
    assert ouro_lm.flops_per_sample(dict(cfg, num_hidden_layers=6),
                                    _traffic()) \
        == 6 * 4096 * (24 * 51_380_224 + 100_663_296) \
        + 6 * 24 * 4096 ** 2 * 2048


def test_attention_work_and_its_roofline_ceiling():
    from benchmark import metrics
    from benchmark.models import ouro_lm
    cfg = dict(_config(), num_hidden_layers=8)
    work = ouro_lm.kernel_work_per_sample(cfg, _traffic())
    assert set(work) == {"attention"}
    # six products on the lower triangle; 12 arrays of 4096 * 2048
    # bfloat16 values an application
    assert work["attention"] == {
        "flops": 6.0 * 32 * 4096 ** 2 * 2048,
        "bytes": 12.0 * 32 * 4096 * 2048 * 2}
    # the causal half of the BERT family's 12 L T^2 H
    assert work["attention"]["flops"] == metrics.\
        attention_train_work_per_sample(
            num_hidden_layers=32, hidden_size=2048, seq_len=4096,
            bytes_per_value=2)["flops"] / 2
    # kernels that ran the program's seven products at the peak would
    # read 6/7 of the roofline, never over 100
    flops = work["attention"]["flops"]
    seven = 7.0 / 6.0 * flops / 197e12
    assert metrics.roofline_percent(flops, work["attention"]["bytes"],
                                    seven, "TPU v5 lite", chips=1) \
        == pytest.approx(600.0 / 7.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_files_load_and_are_reported_in_the_cell_alone(name):
    from benchmark import harness
    cell = harness.load_cell(CELL, rehearse=False)
    entry = {m["name"]: m for m in cell["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "fit_samples_per_s" and entry["unit"] == "%"
    spec = harness.reader_spec(entry)
    assert spec["reader"] in ("harness", "trace_op_share",
                              "trace_op_roofline") and spec["what"]
    other = harness.load_cell("bert-base-pos2048.fit-seq2048-flash", False)
    assert name not in {m["name"] for m in other["per_layer"]}
    assert "flash_time_share" not in {m["name"] for m in cell["per_layer"]}


def test_kernel_patterns_match_the_causal_names_and_no_others():
    import re

    from benchmark import harness
    cell = harness.load_cell(CELL, rehearse=False)
    patterns = harness.op_patterns_for(cell["per_layer"])
    ops = ["flash_fwd_causal.15@tpu_custom_call",
           "flash_bwd_fused_causal.9@tpu_custom_call",
           "jvp_flash_dq_causal_.3@tpu_custom_call",
           "transpose_jvp_flash_dkv_causal__.2@tpu_custom_call",
           "fusion.521", "flash_fwd.3@tpu_custom_call"]

    def matched(metric):
        return [o for o in ops if re.search(patterns[metric], o)]
    assert matched("causal_flash_fwd_time_share") == ops[:1]
    assert matched("causal_flash_bwd_time_share") == ops[1:4]
    assert matched("causal_flash_time_share") == ops[:4] + ops[5:]
    assert patterns["causal_flash_attention_roofline"] \
        == patterns["causal_flash_time_share"]


def test_the_family_imports_without_the_programs_model():
    """The driver lays this PR's benchmark files over the parent's
    checkout: the family has to import there (and fail at `build`, at
    once), so it names the program's model inside `build` alone."""
    code = ("import sys; from benchmark.models import ouro_lm; "
            "assert 'analytics_zoo_tpu' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True)


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
    # a rehearsal's numbers never stand under a device metric's name
    assert not set(NEW_METRICS) & set(line["metrics"])
    for check in ("reference_check ", "step_check "):
        said = [ln for ln in lines if ln.startswith(check)]
        assert said and said[0].endswith("ok=True"), said
    losses = json.loads([ln for ln in lines if ln.startswith(
        "epoch_losses ")][0].split(" ", 1)[1])
    assert losses[-1] < losses[0]
