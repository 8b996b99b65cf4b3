"""A causal language model of pre-norm blocks with latent attention and
routed experts, on the fit path.

    h = E[ids]
    Block(h):  h = h + MLA(RMSNorm_1(h));  h = h + FFN(RMSNorm_2(h))
    the first `n_dense_layer` blocks: FFN = a dense gated FFN
    every later block:               FFN = shared experts + routed experts
    logits = RMSNorm_f(h) W_head                              (untied)

Blocks are `keras.transformer.PreNormDecoderBlock`s over
`keras.latent_attention.LatentSelfAttention` and `keras.transformer.
GatedFFN` or `keras.moe.MoEFeedForward`. The model may be ONE chip's share
of an expert-parallel deployment: `experts_held` is the range of every
layer's routed experts that live here (`keras/moe.py`), and `vocab` the
slice of the vocabulary whose embedding rows and head columns live here;
everything else is whole.

TPU-first layout, as `models/looped_decoder.py`: the blocks of a kind are
ONE `[n, ...]` buffer per tensor, `lax.scan`ned, so each kind compiles once
and its gradients are born stacked. With `remat` every layer is a
`jax.checkpoint` that keeps its [B, T, H] input and, with `use_flash`, the
attention kernel's output and log-sum-exp
(`pallas.flash_attention.save_flash_residuals`), and computes the rest
again in the backward pass: norms, projections, the router's choice, the
dispatch and the expert products, but no second attention forward. In
training `apply` hands the loss `ProjectedLogits`, so the [B, T, vocab]
logits are never formed whole (`ops/objectives.py`).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.engine import KerasNet
from analytics_zoo_tpu.keras.latent_attention import LatentSelfAttention
from analytics_zoo_tpu.keras.layers import RMSNormalization
from analytics_zoo_tpu.keras.moe import MoEFeedForward
from analytics_zoo_tpu.keras.transformer import (GatedFFN,
                                                 PreNormDecoderBlock,
                                                 rotary_tables)
from analytics_zoo_tpu.observability.registry import get_registry
from analytics_zoo_tpu.ops.objectives import ProjectedLogits
from analytics_zoo_tpu.pallas.flash_attention import save_flash_residuals
from analytics_zoo_tpu.serving.quantization import maybe_int8_matmul


class MoEDecoderLM(KerasNet):
    """Token ids [B, T] -> next-token logits [B, T, vocab]. Fit it with
    `sparse_categorical_crossentropy(from_logits=True)` on labels [B, T]
    (the ids shifted by one)."""

    def __init__(self, vocab: int, hidden_size: int, n_layer: int,
                 n_head: int, kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 n_routed_experts: int, num_experts_per_tok: int,
                 n_shared_experts: int = 0, n_dense_layer: int = 1,
                 experts_held: Optional[Tuple[int, int]] = None,
                 routed_scaling_factor: float = 1.0,
                 rope_theta: float = 10000.0, rms_eps: float = 1e-6,
                 hidden_act: str = "silu", use_flash: bool = False,
                 remat: bool = True, name=None):
        super().__init__(name)
        if not 0 <= n_dense_layer < n_layer:
            raise ValueError("MoEDecoderLM needs at least one expert layer "
                             f"after its {n_dense_layer} dense ones")
        self.vocab, self.hidden_size = vocab, hidden_size
        self.n_dense, self.n_moe = n_dense_layer, n_layer - n_dense_layer
        self.rope_dim, self.rope_theta = qk_rope_head_dim, rope_theta
        self.remat = remat
        init = jax.nn.initializers.normal(0.02)

        def attention(tag):
            return LatentSelfAttention(
                hidden_size, n_head, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, rms_eps=rms_eps,
                use_flash=use_flash, init=init,
                name=f"{self.name}_{tag}_attn")

        self.moe = MoEFeedForward(
            hidden_size, moe_intermediate_size, n_routed_experts,
            num_experts_per_tok, experts_held=experts_held,
            shared_width=n_shared_experts * moe_intermediate_size,
            routed_scaling_factor=routed_scaling_factor,
            hidden_act=hidden_act, init=init, name=self.name + "_moe")
        self.dense_block = PreNormDecoderBlock(
            attention("dense"),
            GatedFFN(hidden_size, intermediate_size, hidden_act, init=init,
                     name=self.name + "_dense_ffn"),
            rms_eps, name=self.name + "_dense_block")
        self.moe_block = PreNormDecoderBlock(
            attention("moe"), self.moe, rms_eps,
            name=self.name + "_moe_block")
        self.final_norm = RMSNormalization(rms_eps,
                                           name=self.name + "_final_norm")
        gauge = get_registry().gauge
        for gname, doc, value in (
                ("model_experts_routed", "routed experts of an expert "
                 "layer: the router's width", n_routed_experts),
                ("model_experts_held", "routed experts of each layer that "
                 "this chip holds", self.moe.n_held),
                ("model_experts_per_token", "routed experts a token "
                 "chooses", num_experts_per_tok),
                ("model_shared_experts", "shared experts of an expert "
                 "layer", n_shared_experts),
                ("model_layer_applications", "block applications in one "
                 "forward (passes x blocks)", n_layer),
                ("model_recompute", "1 if every block application is "
                 "recomputed in the backward pass", int(remat)),
                ("model_recompute_attention_kernel", "1 if the backward "
                 "pass runs the attention forward again, 0 if its output "
                 "is kept (no recomputation, or the flash kernel's "
                 "residuals saved across it)",
                 int(remat and not use_flash))):
            gauge(gname, doc).set(value, model=self.name)

    def build(self, rng, input_shape=None):
        k_emb, k_head, *k_blocks = jax.random.split(
            rng, 2 + self.n_dense + self.n_moe)
        h_shape = (None, None, self.hidden_size)

        def stacked(block, keys):
            # each block from its own key, every tensor born [n, ...]
            return jax.vmap(lambda k: block.build(k, h_shape))(
                jnp.stack(keys))

        p = {
            "word_embeddings": jax.random.normal(
                k_emb, (self.vocab, self.hidden_size)) * 0.02,
            "moe_blocks": stacked(self.moe_block, k_blocks[self.n_dense:]),
            "final_norm": self.final_norm.build(rng, h_shape),
            "lm_head_kernel": jax.random.normal(
                k_head, (self.hidden_size, self.vocab)) * 0.02,
        }
        if self.n_dense:
            p["dense_blocks"] = stacked(self.dense_block,
                                        k_blocks[:self.n_dense])
        return p

    def _scan_blocks(self, params, h, rotary, per_moe_layer=None):
        """The blocks over h [B, T, H]; `per_moe_layer(block params, layer
        input)`, where given, is stacked over the expert layers and
        returned beside the hidden state."""

        def layer(block, scope):
            def apply_block(bp, hh):
                with jax.named_scope(scope):
                    return block.ffn_branch(
                        bp, block.attention_branch(bp, hh, rotary))
            if self.remat:
                return jax.checkpoint(apply_block,
                                      policy=save_flash_residuals)
            return apply_block

        if self.n_dense:
            dense = layer(self.dense_block, "moedec/dense_block")
            h, _ = jax.lax.scan(lambda a, bp: (dense(bp, a), None), h,
                                params["dense_blocks"])
        moe = layer(self.moe_block, "moedec/moe_block")

        def moe_step(a, bp):
            seen = None if per_moe_layer is None else per_moe_layer(bp, a)
            return moe(bp, a), seen

        return jax.lax.scan(moe_step, h, params["moe_blocks"])

    def _embed(self, params, ids):
        """(embedded ids [B, T, H], the rotary tables of T positions)."""
        ids = jnp.asarray(ids, jnp.int32)
        return (jnp.take(params["word_embeddings"], ids, axis=0),
                rotary_tables(ids.shape[1], self.rope_dim, self.rope_theta))

    def hidden(self, params, ids):
        """The final norm's output [B, T, H]."""
        h, rotary = self._embed(params, ids)
        h, _ = self._scan_blocks(params, h, rotary)
        with jax.named_scope("moedec/final_norm"):
            return self.final_norm.call(params["final_norm"], h)

    def expert_choice(self, params, ids):
        """The routed experts every token chose in every expert layer of
        one forward, [expert layers, B, T, experts per token] int32, out
        of all `n_routed_experts`, whatever is held here."""
        h, rotary = self._embed(params, ids)
        block = self.moe_block

        def choice(bp, hh):
            hh = block.attention_branch(bp, hh, rotary)
            experts, _ = self.moe.routing(
                bp["ffn"], block.norm.call(bp["ffn_norm"], hh))
            return experts.reshape(h.shape[:2] + (-1,))

        return self._scan_blocks(params, h, rotary, choice)[1]

    def routing_counts(self, params, ids):
        """Token-slots per routed expert in every expert layer of one
        forward, [expert layers, n_routed_experts] int32; each row sums to
        tokens x experts per token."""
        choice = self.expert_choice(params, ids)
        return (choice.reshape(choice.shape[0], -1, 1) == jnp.arange(
            self.moe.n_routed, dtype=choice.dtype)).sum(axis=1,
                                                        dtype=jnp.int32)

    def forward(self, params, ids):
        """Logits [B, T, vocab]."""
        return maybe_int8_matmul(self.hidden(params, ids), params,
                                 "lm_head_kernel")

    def apply(self, params, inputs, *, training=False, rng=None):
        if not training:
            return self.forward(params, inputs)
        return ProjectedLogits(self.hidden(params, inputs),
                               params["lm_head_kernel"])

    def compute_output_shape(self, input_shape):
        return (None, input_shape[1], self.vocab)

    def _ordered_layers(self):
        return []
