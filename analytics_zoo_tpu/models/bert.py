"""BERT task models + TF-checkpoint import.

Reference: TFPark text estimators — `BERTClassifier`
(`pyzoo/zoo/tfpark/text/estimator/bert_classifier.py:64`), `BERTNER`,
`BERTSQuAD` over a shared BERT `model_fn` (`bert_base.py:115`). Here each is
a thin head over the native `keras.transformer.BERT` layer, trained by the
shared pjit trainer — no TF session, no estimator graph export.

`load_tf_checkpoint` imports Google-format BERT checkpoints (the reference
feeds `init_checkpoint` into its model_fn) by mapping TF1 variable names
(`bert/encoder/layer_0/attention/self/query/...`) onto the native fused-QKV
parameter tree; q/k/v kernels concatenate into the one [D, 3D] matmul the
MXU wants."""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.keras.engine import KerasNet
from analytics_zoo_tpu.keras.transformer import BERT, _dropout
from analytics_zoo_tpu.serving.quantization import maybe_int8_matmul


class _BERTTask(KerasNet):
    """Shared plumbing: BERT encoder + task head, optimizer defaults from
    the reference (AdamWeightDecay lr 5e-5)."""

    def __init__(self, bert: BERT, name=None):
        super().__init__(name)
        self.bert = bert

    def default_compile(self, lr: float = 5e-5, total_steps: int = -1,
                        loss: str = "sparse_categorical_crossentropy",
                        metrics=("accuracy",)):
        from analytics_zoo_tpu.ops.objectives import get as get_loss
        from analytics_zoo_tpu.ops.optimizers import adam_weight_decay
        self.compile(adam_weight_decay(lr, warmup_portion=0.1,
                                       total_steps=total_steps),
                     get_loss(loss, from_logits=True), list(metrics))
        return self

    def load_tf_checkpoint(self, ckpt_path: str) -> "_BERTTask":
        if self.params is None:
            raise RuntimeError("Build the model first (ensure_built or fit)")
        self.params[self.bert.name] = load_tf_checkpoint(
            self.bert, ckpt_path, self.params[self.bert.name])
        return self

    # No sidecar remap: param keys are stable (the encoder is always named
    # "bert" when constructed by the task classes), so saved trees load by
    # exact key. A custom-named user BERT must keep its name across
    # save/load.
    def _ordered_layers(self):
        return []


class BERTClassifier(_BERTTask):
    """Sequence classification (`bert_classifier.py:64`): pooled output ->
    dropout -> Dense(num_classes) logits."""

    def __init__(self, num_classes: int, bert: Optional[BERT] = None,
                 dropout: float = 0.1, **bert_kw):
        bert = bert or BERT(pooled_only=True, name="bert", **bert_kw)
        bert.pooled_only = True
        super().__init__(bert)
        self.num_classes = num_classes
        self.dropout = dropout

    def build(self, rng, input_shape=None):
        k1, k2 = jax.random.split(rng)
        seq = (None, self.bert.seq_len)
        return {
            self.bert.name: self.bert.build(k1, [seq, seq, seq]),
            "cls_kernel": jax.random.normal(
                k2, (self.bert.hidden_size, self.num_classes)) * 0.02,
            "cls_bias": jnp.zeros((self.num_classes,), jnp.float32),
        }

    def apply(self, params, inputs, *, training=False, rng=None):
        sub = None
        if rng is not None:
            rng, sub = jax.random.split(rng)
        pooled = self.bert.call(params[self.bert.name], inputs,
                                training=training, rng=sub)
        with jax.named_scope("bert/pooler_head"):
            if training and rng is not None and self.dropout > 0:
                pooled = _dropout(rng, self.dropout, pooled)
            return maybe_int8_matmul(pooled, params, "cls_kernel") \
                + params["cls_bias"]

    def compute_output_shape(self, input_shape):
        return (None, self.num_classes)


class BERTNER(_BERTTask):
    """Token classification (`bert_ner.py`): sequence output ->
    per-token Dense(num_entities) logits."""

    def __init__(self, num_entities: int, bert: Optional[BERT] = None,
                 **bert_kw):
        bert = bert or BERT(name="bert", **bert_kw)
        bert.pooled_only = False
        super().__init__(bert)
        self.num_entities = num_entities

    def build(self, rng, input_shape=None):
        k1, k2 = jax.random.split(rng)
        seq = (None, self.bert.seq_len)
        return {
            self.bert.name: self.bert.build(k1, [seq, seq, seq]),
            "ner_kernel": jax.random.normal(
                k2, (self.bert.hidden_size, self.num_entities)) * 0.02,
            "ner_bias": jnp.zeros((self.num_entities,), jnp.float32),
        }

    def apply(self, params, inputs, *, training=False, rng=None):
        seq_out, _ = self.bert.call(params[self.bert.name], inputs,
                                    training=training, rng=rng)
        return maybe_int8_matmul(seq_out, params, "ner_kernel") \
            + params["ner_bias"]

    def compute_output_shape(self, input_shape):
        return (None, self.bert.seq_len, self.num_entities)


class BERTSQuAD(_BERTTask):
    """Extractive QA (`bert_squad.py`): sequence output -> start/end logits
    ([B, T] each)."""

    def __init__(self, bert: Optional[BERT] = None, **bert_kw):
        bert = bert or BERT(name="bert", **bert_kw)
        bert.pooled_only = False
        super().__init__(bert)

    def build(self, rng, input_shape=None):
        k1, k2 = jax.random.split(rng)
        seq = (None, self.bert.seq_len)
        return {
            self.bert.name: self.bert.build(k1, [seq, seq, seq]),
            "qa_kernel": jax.random.normal(
                k2, (self.bert.hidden_size, 2)) * 0.02,
            "qa_bias": jnp.zeros((2,), jnp.float32),
        }

    def apply(self, params, inputs, *, training=False, rng=None):
        seq_out, _ = self.bert.call(params[self.bert.name], inputs,
                                    training=training, rng=rng)
        logits = maybe_int8_matmul(seq_out, params, "qa_kernel") \
            + params["qa_bias"]
        return logits[..., 0], logits[..., 1]      # start, end

    def compute_output_shape(self, input_shape):
        T = self.bert.seq_len
        return [(None, T), (None, T)]


# ---------------------------------------------------------------------------
# Google TF1 BERT checkpoint import
# ---------------------------------------------------------------------------
def load_tf_checkpoint(bert: BERT, ckpt_path: str,
                       params: Dict) -> Dict:
    """Map `bert/...` TF1 variables onto the native param tree. Returns a
    new tree with imported weights (shapes validated); raises on missing
    variables."""
    import tensorflow as tf  # baked into the image; CPU-only use here
    reader = tf.train.load_checkpoint(ckpt_path)

    def get(name):
        full = f"bert/{name}"
        if not reader.has_tensor(full):
            raise KeyError(f"checkpoint missing {full}")
        return np.asarray(reader.get_tensor(full))

    p = jax.tree_util.tree_map(np.asarray, params)  # mutable copy
    if bert.stacked:
        # import targets the per-block naming; convert the stacked tree
        # out and back (`keras/transformer.py` converters)
        from analytics_zoo_tpu.keras.transformer import unstack_block_params
        p = unstack_block_params(p, bert.n_block, bert.name)
    p["word_embeddings"] = get("embeddings/word_embeddings")
    p["position_embeddings"] = get("embeddings/position_embeddings")
    p["token_type_embeddings"] = get("embeddings/token_type_embeddings")
    p["emb_ln"] = {"gamma": get("embeddings/LayerNorm/gamma"),
                   "beta": get("embeddings/LayerNorm/beta")}
    p["pooler_kernel"] = get("pooler/dense/kernel")
    p["pooler_bias"] = get("pooler/dense/bias")
    for i, blk in enumerate(bert.blocks):
        base = f"encoder/layer_{i}"
        q = get(f"{base}/attention/self/query/kernel")
        k = get(f"{base}/attention/self/key/kernel")
        v = get(f"{base}/attention/self/value/kernel")
        qb = get(f"{base}/attention/self/query/bias")
        kb = get(f"{base}/attention/self/key/bias")
        vb = get(f"{base}/attention/self/value/bias")
        bp = dict(p[blk.name])
        bp["attn"] = {
            "qkv_kernel": np.concatenate([q, k, v], axis=1),
            "qkv_bias": np.concatenate([qb, kb, vb]),
            "out_kernel": get(f"{base}/attention/output/dense/kernel"),
            "out_bias": get(f"{base}/attention/output/dense/bias"),
        }
        bp["ln1"] = {"gamma": get(f"{base}/attention/output/LayerNorm/gamma"),
                     "beta": get(f"{base}/attention/output/LayerNorm/beta")}
        bp["ffn_in_kernel"] = get(f"{base}/intermediate/dense/kernel")
        bp["ffn_in_bias"] = get(f"{base}/intermediate/dense/bias")
        bp["ffn_out_kernel"] = get(f"{base}/output/dense/kernel")
        bp["ffn_out_bias"] = get(f"{base}/output/dense/bias")
        bp["ln2"] = {"gamma": get(f"{base}/output/LayerNorm/gamma"),
                     "beta": get(f"{base}/output/LayerNorm/beta")}
        p[blk.name] = bp
    # shape validation against the existing tree
    if bert.stacked:
        from analytics_zoo_tpu.keras.transformer import stack_block_params
        p = stack_block_params(p, bert.n_block, bert.name)
    ref_shapes = jax.tree_util.tree_map(np.shape, params)
    new_shapes = jax.tree_util.tree_map(np.shape, p)
    if ref_shapes != new_shapes:
        raise ValueError("checkpoint shapes do not match the model config")
    return p
