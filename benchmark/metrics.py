"""Metric arithmetic of the benchmark: FLOP counts and peaks.

Kept with the benchmark so that no change to `analytics_zoo_tpu/utils/`
can move `fit_mfu`. The FLOP function follows
`utils/profiling.transformer_train_flops` at commit 03d96a9 (6 FLOPs per
matmul weight per token forward+backward, plus the attention score and
context products), with the weights counted from the configuration's
sizes instead of from the live parameter tree."""

from __future__ import annotations

import json
import os
from typing import Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def transformer_train_flops_per_sample(*, num_hidden_layers: int,
                                       hidden_size: int,
                                       intermediate_size: int, seq_len: int,
                                       num_labels: int) -> float:
    """Forward+backward FLOPs the algorithm needs for one sequence of a
    BERT-style classifier: 6 per encoder matmul weight per token (QKV,
    attention output, two FFN matmuls), 12*L*T^2*H for scores and context
    (4*T^2*H forward, times 3 with the backward), and the pooler and
    classifier once per sequence. Embedding gathers, LayerNorm, softmax,
    gelu, biases and recomputation are not counted."""
    per_layer = 4 * hidden_size * hidden_size \
        + 2 * hidden_size * intermediate_size
    encoder = 6.0 * num_hidden_layers * per_layer * seq_len
    attention = attention_train_work_per_sample(
        num_hidden_layers=num_hidden_layers, hidden_size=hidden_size,
        seq_len=seq_len, bytes_per_value=2)["flops"]
    head = 6.0 * (hidden_size * hidden_size + hidden_size * num_labels)
    return encoder + attention + head


def attention_train_work_per_sample(*, num_hidden_layers: int,
                                    hidden_size: int, seq_len: int,
                                    bytes_per_value: int) -> Dict[str, float]:
    """What the attention of one sequence needs forward+backward, however
    it is computed: `flops` = 12*L*T^2*H (scores and context, 4*T^2*H
    forward, twice that backward); `bytes` = the least a kernel that
    keeps the [T, T] scores on the chip moves through HBM: forward reads
    Q, K, V and writes O, backward reads Q, K, V, O, dO and writes dQ,
    dK, dV, 12 arrays of T*H values a layer. A backward that computes
    the scores again does more operations than are counted here, and
    the row statistics it keeps are not counted either, so a share of
    the roofline made from these reads low, never high."""
    return {"flops": 12.0 * num_hidden_layers * seq_len * seq_len
            * hidden_size,
            "bytes": 12.0 * num_hidden_layers * seq_len * hidden_size
            * bytes_per_value}


def load_peaks() -> Dict:
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        return json.load(fh)


def peak_for(device_kind: str, what: str = "bf16_flops_per_s") -> float:
    """Peak of one chip from the benchmark's own table. An unlisted
    `device_kind` is an error, never a default: a rate over another
    chip's peak is not a utilization."""
    devices = load_peaks()["devices"]
    if device_kind not in devices:
        raise LookupError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"(listed: {sorted(devices)})")
    return float(devices[device_kind][what])


def roofline_percent(flops: float, hbm_bytes: float, seconds: float,
                     device_kind: str, chips: int) -> float:
    """Share of its roofline that a piece of work reached: the least
    time `chips` chips need for `flops` operations and `hbm_bytes` bytes
    (the longer of the two at the table's peaks) over the `seconds` it
    took on each."""
    least_s = max(flops / peak_for(device_kind),
                  hbm_bytes / peak_for(device_kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / (chips * seconds)


def mfu_percent(flops_per_sample: float, samples_per_s: float,
                device_kind: str, chips: int) -> float:
    return 100.0 * flops_per_sample * samples_per_s \
        / (chips * peak_for(device_kind))
