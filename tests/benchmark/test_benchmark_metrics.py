"""The metric arithmetic: the FLOP count behind fit_mfu against a count
made by hand, and the peaks table."""

import pytest

from benchmark import metrics


def test_bert_base_seq128_flops_against_a_hand_count():
    # per block: QKV 3*768^2 + out 768^2 + FFN 2*768*3072 = 7,077,888
    # encoder: 6 * 12 * 7,077,888 * 128 tokens    = 65,229,815,808
    # attention: 12 * 12 * 128^2 * 768            =  1,811,939,328
    # pooler + classifier: 6 * (768^2 + 768*2)    =      3,548,160
    got = metrics.transformer_train_flops_per_sample(
        num_hidden_layers=12, hidden_size=768, intermediate_size=3072,
        seq_len=128, num_labels=2)
    assert got == 65_229_815_808 + 1_811_939_328 + 3_548_160


def test_seq2048_attention_is_three_tenths_of_the_flops():
    kw = dict(num_hidden_layers=12, hidden_size=768, intermediate_size=3072,
              num_labels=2)
    got = metrics.transformer_train_flops_per_sample(seq_len=2048, **kw)
    attention = 12 * 12 * 2048 ** 2 * 768
    assert got == 6 * 12 * 7_077_888 * 2048 + attention + 3_548_160
    assert 0.30 < attention / got < 0.31


def test_attention_work_and_its_roofline_against_a_hand_count():
    # one sequence of 2048 tokens, 12 layers, hidden 768, bfloat16:
    # 12 * 12 * 2048^2 * 768 operations; 12 arrays of 2048 * 768 values
    # a layer, 2 bytes each
    work = metrics.attention_train_work_per_sample(
        num_hidden_layers=12, hidden_size=768, seq_len=2048,
        bytes_per_value=2)
    assert work == {"flops": 463_856_467_968.0, "bytes": 452_984_832.0}
    # 768 such sequences in 7.30 s of kernels on one v5e (PERF.md, by
    # hand from a trace): the operations decide, a quarter of the peak
    share = metrics.roofline_percent(768 * work["flops"], 768 * work["bytes"],
                                     7.30, "TPU v5 lite", chips=1)
    assert share == pytest.approx(100 * 768 * 463_856_467_968 / 197e12 / 7.30)
    assert 24.0 < share < 25.5
    # four chips, each busy as long with a quarter of four times the work
    assert metrics.roofline_percent(4 * 768 * work["flops"], 0.0, 7.30,
                                    "TPU v5 lite", chips=4) \
        == pytest.approx(share)
    # few operations to a byte: the bytes decide
    assert metrics.roofline_percent(1e9, 819e9, 2.0, "TPU v5 lite", chips=1) \
        == pytest.approx(50.0)


def test_fit_mfu_is_flops_times_rate_over_the_tables_peak():
    flops = 67_045_303_296.0
    mfu = metrics.mfu_percent(flops, 1451.3, "TPU v5 lite", chips=1)
    assert mfu == pytest.approx(100 * flops * 1451.3 / 197e12)
    assert 49.0 < mfu < 50.0
    assert metrics.mfu_percent(flops, 4 * 1451.3, "TPU v5 lite", chips=4) \
        == pytest.approx(mfu)


def test_an_unlisted_device_kind_is_an_error_not_a_default():
    with pytest.raises(LookupError):
        metrics.peak_for("cpu")
    with pytest.raises(LookupError):
        metrics.mfu_percent(1.0, 1.0, "TPU v9", chips=1)


def test_peaks_table_names_its_source_and_the_v5e_rows():
    peaks = metrics.load_peaks()
    assert "Google Cloud" in peaks["source"]
    row = peaks["devices"]["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
