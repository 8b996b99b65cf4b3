"""A looped causal language model on the fit path.

A decoder whose whole stack of blocks is applied `n_pass` times to the same
hidden state with the SAME weights (a universal-transformer loop): the
parameters of `n_block` layers do the arithmetic of `n_pass * n_block`, so
activations, recomputation and the backward's length are `n_pass` times a
plain decoder's, and every weight's gradient is the sum over its `n_pass`
uses. One forward:

    h = E[ids]
    for r in 1..n_pass:   h = RMSNorm_f(Block_N(... Block_1(h)))
                          gate_r = sigmoid(h w_g + b_g)
    logits = h W_head                      (after the last pass; untied)

The final norm closes every pass, and its output is what the next pass
starts from. The exit gate is computed after every pass and returned by
`forward`; with no early exit it enters neither the logits nor the loss.
Blocks are `keras.transformer.TransformerDecoderBlock`s.

TPU-first layout, as `keras.transformer.BERT(stacked=True)`: the blocks'
parameters are ONE `[n_block, ...]` buffer per tensor (`stack_block_params`'
layout), `lax.scan`ned over the blocks inside a `lax.scan` over the passes,
so the block compiles once and its gradients are born stacked. With `remat`
every layer application is a `jax.checkpoint`: the backward keeps, per
application (`n_pass * n_block` of them), the [B, T, H] input and, with
`use_flash`, the attention kernel's two residuals, its [B, heads, T, D]
output and its [B * heads, 1, T] log-sum-exp
(`pallas.flash_attention.save_flash_residuals`), and computes the rest
again: norms, projections, rotary positions and the FFN, but no second
forward kernel call. In training `apply` hands the loss `ProjectedLogits` (the
hidden state and the head's kernel), so the [B, T, vocab] logits are never
formed whole (`ops/objectives.py`); in inference it returns the logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.engine import KerasNet
from analytics_zoo_tpu.keras.layers import RMSNormalization
from analytics_zoo_tpu.keras.transformer import (TransformerDecoderBlock,
                                                 rotary_tables)
from analytics_zoo_tpu.observability.registry import get_registry
from analytics_zoo_tpu.ops.objectives import ProjectedLogits
from analytics_zoo_tpu.pallas.flash_attention import save_flash_residuals
from analytics_zoo_tpu.serving.quantization import maybe_int8_matmul


class LoopedDecoderLM(KerasNet):
    """Token ids [B, T] -> next-token logits [B, T, vocab]. Fit it with
    `sparse_categorical_crossentropy(from_logits=True)` on labels [B, T]
    (the ids shifted by one)."""

    def __init__(self, vocab: int, hidden_size: int, n_block: int,
                 n_head: int, intermediate_size: int, n_pass: int = 1,
                 rope_theta: float = 10000.0, rms_eps: float = 1e-6,
                 hidden_act: str = "silu", use_flash: bool = False,
                 remat: bool = True, name=None):
        super().__init__(name)
        self.vocab, self.hidden_size = vocab, hidden_size
        self.n_block, self.n_pass = n_block, n_pass
        self.head_dim = hidden_size // n_head
        self.rope_theta = rope_theta
        self.remat = remat
        self.block = TransformerDecoderBlock(
            hidden_size, n_head, intermediate_size, hidden_act=hidden_act,
            rms_eps=rms_eps, use_flash=use_flash, name=self.name + "_block")
        self.final_norm = RMSNormalization(rms_eps,
                                           name=self.name + "_final_norm")
        gauge = get_registry().gauge
        gauge("model_loop_passes", "passes a looped model makes over its "
              "stack of blocks").set(n_pass, model=self.name)
        gauge("model_layer_applications", "block applications in one "
              "forward (passes x blocks)").set(n_pass * n_block,
                                               model=self.name)
        gauge("model_recompute", "1 if every block application is "
              "recomputed in the backward pass").set(int(remat),
                                                     model=self.name)
        gauge("model_recompute_attention_kernel", "1 if the backward pass "
              "runs the attention forward again, 0 if its output is kept "
              "(no recomputation, or the flash kernel's residuals saved "
              "across it)").set(int(remat and not use_flash),
                                model=self.name)

    def build(self, rng, input_shape=None):
        k_emb, k_head, k_gate, *k_blocks = jax.random.split(
            rng, 3 + self.n_block)
        h_shape = (None, None, self.hidden_size)
        return {
            "word_embeddings": jax.random.normal(
                k_emb, (self.vocab, self.hidden_size)) * 0.02,
            "blocks": jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[self.block.build(k, h_shape) for k in k_blocks]),
            "final_norm": self.final_norm.build(rng, h_shape),
            "exit_gate": {
                "kernel": jax.random.normal(
                    k_gate, (self.hidden_size, 1)) * 0.02,
                "bias": jnp.zeros((1,), jnp.float32)},
            "lm_head_kernel": jax.random.normal(
                k_head, (self.hidden_size, self.vocab)) * 0.02,
        }

    def hidden_and_gates(self, params, ids):
        """The last pass's normed hidden state [B, T, H] and every pass's
        exit gate [B, T, n_pass] (float32)."""
        ids = jnp.asarray(ids, jnp.int32)
        h = jnp.take(params["word_embeddings"], ids, axis=0)
        rotary = rotary_tables(ids.shape[1], self.head_dim, self.rope_theta)

        def apply_block(bp, hh):
            with jax.named_scope("looplm/block/attention"):
                hh = self.block.attention_branch(bp, hh, rotary)
            with jax.named_scope("looplm/block/ffn"):
                return self.block.ffn_branch(bp, hh)

        if self.remat:
            apply_block = jax.checkpoint(apply_block,
                                         policy=save_flash_residuals)

        def one_pass(hh, _):
            with jax.named_scope("looplm/pass"):
                hh, _ = jax.lax.scan(
                    lambda a, bp: (apply_block(bp, a), None),
                    hh, params["blocks"])
                with jax.named_scope("looplm/final_norm"):
                    hh = self.final_norm.call(params["final_norm"], hh)
                gate = jax.nn.sigmoid(
                    hh.astype(jnp.float32)
                    @ params["exit_gate"]["kernel"].astype(jnp.float32)
                    + params["exit_gate"]["bias"].astype(jnp.float32))
            return hh, gate[..., 0]

        h, gates = jax.lax.scan(one_pass, h, None, length=self.n_pass)
        return h, jnp.moveaxis(gates, 0, -1)

    def forward(self, params, ids):
        """(logits [B, T, vocab], exit gates [B, T, n_pass])."""
        h, gates = self.hidden_and_gates(params, ids)
        return maybe_int8_matmul(h, params, "lm_head_kernel"), gates

    def apply(self, params, inputs, *, training=False, rng=None):
        if not training:
            return self.forward(params, inputs)[0]
        h, _ = self.hidden_and_gates(params, inputs)
        return ProjectedLogits(h, params["lm_head_kernel"])

    def compute_output_shape(self, input_shape):
        return (None, input_shape[1], self.vocab)

    def _ordered_layers(self):
        return []
