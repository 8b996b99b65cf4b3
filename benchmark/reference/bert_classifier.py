"""Plain reference forward of the BERT sequence classifier.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, following Devlin et al. 2018
and google-research/bert `modeling.py`: word + position + token-type
embeddings, LayerNorm (eps 1e-12), then per block post-norm
self-attention (scores / sqrt(head), additive -10000 mask, softmax) and
a gelu (tanh approximation) FFN, each with a residual and a LayerNorm,
then tanh pooler over the first token and a linear classifier. No
dropout, no kernels, no batching tricks. Independent of
`analytics_zoo_tpu/keras/`: it only reads the parameter tree by name
(the fused [H, 3H] QKV matrix is split into its three parts).

`drop_residual_in_block` leaves out one attention residual; it exists so
that the check that the comparison CAN fail has something to drop."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block_params(bert_params, prefix: str, i: int):
    if "blocks" in bert_params:         # stacked layout: [L, ...] leaves
        return jax.tree_util.tree_map(lambda a: a[i], bert_params["blocks"])
    return bert_params[f"{prefix}_block{i}"]


def reference_logits(params, ids, attention_mask, config,
                     drop_residual_in_block: Optional[int] = None
                     ):
    """[B, num_labels] float32 logits for int32 `ids` [B, T] and a {0,1}
    `attention_mask` [B, T]."""
    n_layers = config["num_hidden_layers"]
    n_head = config["num_attention_heads"]
    hidden = config["hidden_size"]
    head = hidden // n_head
    eps = config.get("layer_norm_eps", 1e-12)
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        bp = params["bert"]
        ids = jnp.asarray(ids, jnp.int32)
        B, T = ids.shape
        h = (bp["word_embeddings"][ids]
             + bp["position_embeddings"][None, :T]
             + bp["token_type_embeddings"][jnp.zeros_like(ids)])
        h = _layer_norm(h, bp["emb_ln"], eps)
        add_mask = (1.0 - jnp.asarray(attention_mask, jnp.float32)
                    )[:, None, None, :] * -10000.0
        def heads(x):
            return x.reshape(B, T, n_head, head).transpose(0, 2, 1, 3)

        def block(h, p, keep_residual):
            a = p["attn"]
            wq, wk, wv = jnp.split(a["qkv_kernel"], 3, axis=1)
            bq, bk, bv = jnp.split(a["qkv_bias"], 3)
            q, k, v = heads(h @ wq + bq), heads(h @ wk + bk), \
                heads(h @ wv + bv)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(head)
            probs = jax.nn.softmax(scores + add_mask, axis=-1)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, hidden)
            attn_out = ctx @ a["out_kernel"] + a["out_bias"]
            h = _layer_norm(h + attn_out if keep_residual else attn_out,
                            p["ln1"], eps)
            ffn = _gelu_tanh(h @ p["ffn_in_kernel"] + p["ffn_in_bias"])
            ffn = ffn @ p["ffn_out_kernel"] + p["ffn_out_bias"]
            return _layer_norm(h + ffn, p["ln2"], eps)

        # under `jax.grad` a block keeps only its input and computes its
        # [B, heads, T, T] scores again in the backward pass: the same
        # numbers, and a reference gradient at T=2048 fits the chip
        block = jax.checkpoint(block, static_argnums=(2,))
        for i in range(n_layers):
            h = block(h, _block_params(bp, "bert", i),
                      drop_residual_in_block != i)
        pooled = jnp.tanh(h[:, 0] @ bp["pooler_kernel"] + bp["pooler_bias"])
        return pooled @ params["cls_kernel"] + params["cls_bias"]


def reference_loss(params, batch, config, **fault):
    """Mean softmax cross-entropy of the reference's logits on one
    training batch `{"x": [ids, mask], "y": labels}`, float32: what
    `jax.value_and_grad` of the training-step check differentiates."""
    ids, mask = batch["x"]
    logp = jax.nn.log_softmax(
        reference_logits(params, ids, mask, config, **fault), axis=-1)
    y = jnp.asarray(batch["y"], jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))
