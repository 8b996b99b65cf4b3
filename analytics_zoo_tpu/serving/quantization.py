"""Int8 post-training quantization for inference.

Parity target: the reference's int8 inference engine
(`zoo/src/main/scala/com/intel/analytics/zoo/pipeline/inference/
OpenVinoInferenceSupportive.scala:34-57` — `loadOpenVinoIRInt8*`, VNNI;
validated by `zoo/src/test/.../inference/OpenVINOInt8Suite.scala:301`).
TPU-native redesign: instead of a separate IR + runtime, the SAME keras
param pytree is rewritten in place — weight leaves become symmetric
per-output-channel int8 (`kernel_q` + f32 `kernel_scale`) and the layer's
own `call` dispatches to an int8 MXU path (`lax.dot_general` /
`conv_general_dilated` with int8 operands and `preferred_element_type=
int32`), with dynamic per-tensor activation quantization. Embedding
tables quantize per row (gather → dequantize only the touched rows).

Entry points:
- `quantize_model_params(model, params)` → quantized pytree for any
  Sequential/Model/ZooModel built from the stock layer library.
- `InferenceModel.load_keras(..., quantize="int8")` (serving façade).
- `write_int8_sidecar(run_dir, version, model, ...)` /
  `load_int8_sidecar(...)` — the post-training quantization pass as a
  CHECKPOINT SIDECAR (ISSUE 12): per-output-channel scales + int8
  weights persisted beside `model.<version>` so serving loads the
  pre-calibrated artifact instead of re-quantizing per restart
  (producers: `fit_keras(int8_sidecar=True)` and
  `scripts/quantize_checkpoint.py`).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-12


# ---------------------------------------------------------------------------
# int8 compute paths (used by the layers' quantized dispatch)
# ---------------------------------------------------------------------------
def quantize_activations(x):
    """Dynamic symmetric per-tensor activation quantization: scalar scale
    from the batch's abs-max (data-dependent scalars are jit-safe)."""
    sx = jnp.maximum(jnp.max(jnp.abs(x)) / 127.0, _EPS)
    x_q = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    return x_q, sx


def int8_matmul(x, w_q, w_scale):
    """y ≈ x @ (w_q * w_scale): int8×int8→int32 on the MXU, dequantized
    with the product of the activation and per-channel weight scales."""
    x_q, sx = quantize_activations(x)
    y = jax.lax.dot_general(
        x_q, w_q, (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * (sx * w_scale)


def int8_conv(x, w_q, w_scale, **conv_kwargs):
    """Weight-only int8 for convolutions: int8 weights dequantize to bf16
    at use (4× fewer weight bytes from HBM) and the conv itself runs on
    the bf16 MXU path. Measured on v5e: XLA's true int8×int8 conv
    lowering runs ~1.6× SLOWER than bf16 (no VNNI-style win to copy —
    `OpenVinoInferenceSupportive.scala:34` is an avx512-vnni play), while
    weight-only keeps full conv throughput; activations stay unquantized
    so conv accuracy is better than the Dense path's."""
    w = w_q.astype(jnp.bfloat16) * w_scale.astype(jnp.bfloat16)
    # same invariant as the f32 conv path (_match_param_dtype): float
    # inputs follow the weights; integer inputs error loudly rather than
    # silently serving on unscaled 0-255 pixel values
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.bfloat16)
    y = jax.lax.conv_general_dilated(x, w, **conv_kwargs)
    return y.astype(jnp.float32)


def dequantize_rows(table_q, scale, ids):
    """Embedding path: gather int8 rows, dequantize only what was read."""
    return table_q[ids].astype(jnp.float32) * scale[ids][..., None]


def maybe_int8_matmul(x, params, key: str):
    """`x @ params[key] `, taking the int8 MXU path when the quantized
    form (`<key>_q` + `<key>_scale`) is present — the dispatch hook for
    raw-matmul layers (transformer blocks, BERT task heads) that do not
    go through the keras Dense layer."""
    if key + "_q" in params:
        return int8_matmul(x, params[key + "_q"], params[key + "_scale"])
    return x @ params[key]


# raw (non-Dense-layer) matmul kernels that have a maybe_int8_matmul
# call site; ONLY these are rewritten — blanket *_kernel matching would
# break layers that read their kernels directly (e.g. Highway's
# transform_kernel)
_RAW_INT8_KERNELS = frozenset({
    "qkv_kernel", "out_kernel", "ffn_in_kernel", "ffn_out_kernel",
    "pooler_kernel", "cls_kernel", "ner_kernel", "qa_kernel",
    "ffn_gate_kernel", "ffn_up_kernel", "ffn_down_kernel", "lm_head_kernel",
    "q_kernel", "kv_a_kernel", "kv_b_kernel", "k_kernel", "v_kernel",
})


def _quantize_raw_kernels(tree):
    """Recursively rewrite known raw matmul kernels ([in, out] leaves) in
    a param tree — reaches inside composite layers (transformer blocks)
    the layer-walk cannot see."""
    if not isinstance(tree, dict):
        return tree
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        if k in _RAW_INT8_KERNELS and not isinstance(v, dict) \
                and np.ndim(v) == 2:
            q, scale = _quantize_tensor(v, (0,))
            out[k + "_q"], out[k + "_scale"] = q, scale
        elif k in _RAW_INT8_KERNELS and not isinstance(v, dict) \
                and np.ndim(v) == 3:
            # stacked encoder (`BERT(stacked=True)`): [L, in, out] — the
            # scan body slices dim 0, so quantize per (layer, out_channel)
            # and the sliced leaves ([in, out] int8 + [out] scale) hit
            # the same int8_matmul path as the unstacked form
            q, scale = _quantize_tensor(v, (1,))
            out[k + "_q"], out[k + "_scale"] = q, scale
        else:
            out[k] = _quantize_raw_kernels(v)
    return out


# ---------------------------------------------------------------------------
# param-tree rewrite
# ---------------------------------------------------------------------------
def _quantize_tensor(w, reduce_axes) -> Dict[str, Any]:
    """Symmetric int8 over `reduce_axes`; scale keeps the other axes."""
    w = np.asarray(w, np.float32)
    amax = np.maximum(np.abs(w).max(axis=reduce_axes, keepdims=True), _EPS)
    scale = (amax / 127.0).astype(np.float32)
    q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    return q, np.squeeze(scale, axis=reduce_axes)


def _iter_layers(model):
    layers = getattr(model, "layers", None)
    if layers is None:
        layers = getattr(model, "_layers", None)
    return layers or []


def quantize_model_params(model, params) -> Dict[str, Any]:
    """Rewrite a built model's param pytree with int8 weights for every
    Dense / conv-family / Embedding layer (recursing into nested
    Sequential/Model containers). Layers with no int8 path (BatchNorm,
    recurrent cells, LayerNorm, ...) keep f32 — they are bandwidth-thin
    next to the matmuls."""
    from analytics_zoo_tpu.keras import transformer as tfm
    from analytics_zoo_tpu.keras.engine import Model, Sequential
    from analytics_zoo_tpu.keras.layers import Dense, Embedding, _ConvND

    out = dict(params)
    # BERT task models carry the encoder + raw head kernels with no
    # layer list (`models/bert._BERTTask._ordered_layers` is empty by
    # design): rewrite their subtrees structurally, not by global name
    # matching — a user layer with a same-named 2-D param elsewhere must
    # never be touched.
    from analytics_zoo_tpu.models.bert import _BERTTask
    if isinstance(model, _BERTTask):
        out[model.bert.name] = _quantize_raw_kernels(
            out.get(model.bert.name, {}))
        for head in ("cls_kernel", "ner_kernel", "qa_kernel"):
            if head in out and not isinstance(out[head], dict) \
                    and np.ndim(out[head]) == 2:
                q, scale = _quantize_tensor(out[head], (0,))
                del out[head]
                out[head + "_q"], out[head + "_scale"] = q, scale
    from analytics_zoo_tpu.models.looped_decoder import LoopedDecoderLM
    from analytics_zoo_tpu.models.moe_decoder import MoEDecoderLM
    if isinstance(model, (LoopedDecoderLM, MoEDecoderLM)):
        # no layer list either, and the whole tree is the model's own
        # (an expert layer's router and routed experts have no int8 path
        # and keep their type: their leaves are not named as raw kernels)
        return _quantize_raw_kernels(out)
    for layer in _iter_layers(model):
        sub = out.get(layer.name)
        if sub is None:
            continue
        if isinstance(layer, (Sequential, Model)):
            out[layer.name] = quantize_model_params(layer, sub)
        elif isinstance(layer, (tfm.MultiHeadSelfAttention,
                                tfm.TransformerEncoderBlock,
                                tfm.TransformerLayer, tfm.BERT)):
            out[layer.name] = _quantize_raw_kernels(sub)
        elif isinstance(layer, Dense):
            q, scale = _quantize_tensor(sub["kernel"], (0,))
            new = {k: v for k, v in sub.items() if k != "kernel"}
            new["kernel_q"], new["kernel_scale"] = q, scale
            out[layer.name] = new
        elif isinstance(layer, _ConvND):
            k = np.asarray(sub["kernel"])
            q, scale = _quantize_tensor(k, tuple(range(k.ndim - 1)))
            new = {kk: v for kk, v in sub.items() if kk != "kernel"}
            new["kernel_q"], new["kernel_scale"] = q, scale
            out[layer.name] = new
        elif isinstance(layer, Embedding):
            q, scale = _quantize_tensor(sub["embeddings"], (1,))
            out[layer.name] = {"embeddings_q": q,
                               "embeddings_scale": scale}
    return out


# ---------------------------------------------------------------------------
# int8 artifacts — quantize once, ship the small file
# ---------------------------------------------------------------------------
def save_quantized(model, path: str, params=None) -> Dict[str, Any]:
    """Quantize and persist as an int8 artifact: the counterpart of the
    reference SHIPPING int8 OpenVINO IR files rather than quantizing at
    every load (`OpenVinoInferenceSupportive.scala:34`). ~4× smaller
    than the f32 checkpoint; loads into a FRESH architecture instance
    via `load_quantized`. Reuses the engine's save_weights artifact
    protocol (npz + structure + layer-order sidecars)."""
    from analytics_zoo_tpu.models.common import ZooModel
    net = model.model if isinstance(model, ZooModel) else model
    if params is None:
        params = net.params
    if params is None:
        raise ValueError("Model has no parameters; fit or load first")
    q = quantize_model_params(net, jax.device_get(params))
    net.save_weights(path, params=q)
    return q


def sidecar_path(run_dir: str, version: int) -> str:
    """Canonical name of a checkpoint's int8 sidecar artifact (the
    `.npz` + `.structure.json` pair `learn/checkpoint.save_pytree`
    writes under this stem)."""
    import os
    return os.path.join(run_dir, f"model.{version}.int8")


def write_int8_sidecar(run_dir: str, version: int, model,
                       params=None) -> str:
    """The post-training quantization pass, persisted: calibrate
    symmetric per-output-channel scales from the checkpointed weights
    and write the rewritten (int8 + scale) pytree as a sidecar beside
    `model.<version>` — same atomic write-then-rename + CRC discipline
    as the checkpoint itself, so a torn sidecar is invisible and
    serving falls back to quantize-at-load. Returns the sidecar stem
    path. `params` defaults to the checkpoint's own params (loaded from
    disk), so the sidecar always describes exactly the version it sits
    beside."""
    from analytics_zoo_tpu.learn.checkpoint import (load_pytree,
                                                    save_pytree)
    from analytics_zoo_tpu.models.common import ZooModel
    net = model.model if isinstance(model, ZooModel) else model
    if params is None:
        import os
        params = load_pytree(os.path.join(run_dir, f"model.{version}"))
        # an offline pass (scripts/quantize_checkpoint.py) runs in a
        # fresh process whose auto-numbered layer names differ from the
        # checkpointing process's — remap onto this instance before the
        # layer walk (the trainer hook passes its own live params,
        # whose names already match)
        remap = getattr(net, "_remap_loaded", None)
        if remap is not None:
            params = remap(params)
    q = quantize_model_params(net, jax.device_get(params))
    path = sidecar_path(run_dir, version)
    save_pytree(path, q)
    try:
        from analytics_zoo_tpu.observability.registry import get_registry
        get_registry().counter(
            "quantized_checkpoints_total",
            "int8 checkpoint sidecars written by the post-training "
            "quantization pass").inc()
    except Exception:  # noqa: BLE001 — telemetry only
        pass
    return path


def load_int8_sidecar(run_dir: str, version: int):
    """The quantized pytree a `write_int8_sidecar` pass persisted, or
    None when the sidecar is absent or fails its CRC (the caller falls
    back to quantize-at-load — a torn sidecar costs a calibration, not
    the serve)."""
    import os

    from analytics_zoo_tpu.learn.checkpoint import (CorruptCheckpointError,
                                                    load_pytree)
    path = sidecar_path(run_dir, version)
    if not os.path.exists(path + ".npz"):
        return None
    try:
        return load_pytree(path)
    except (OSError, ValueError, KeyError, CorruptCheckpointError):
        return None


def load_quantized(model, path: str):
    """Load an int8 artifact onto `model`'s architecture → param pytree
    (remapped to this instance's layer names; the model itself is left
    untouched). Feed to `InferenceModel.load_keras(model, params=...)`
    or `model.apply` directly — layers dispatch on the quantized keys."""
    from analytics_zoo_tpu.models.common import ZooModel
    net = model.model if isinstance(model, ZooModel) else model
    return net.load_weights_tree(path)
