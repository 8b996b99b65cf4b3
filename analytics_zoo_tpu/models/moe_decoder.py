"""A causal language model of pre-norm blocks whose token mixer is latent
attention, a linear-attention layer, a gated short convolution or
grouped-query attention, layer by layer, over a dense FFN or routed
experts, on the fit path.

    h = E[ids]
    Block_l(h):  h = h + Mixer_l(RMSNorm_1(h));  h = h + FFN_l(RMSNorm_2(h))
    Mixer_l: `mixers[l]`: "latent" = multi-head latent attention (MLA),
             "linear" = Kimi Delta Attention (KDA), "conv" = LFM2's gated
             short convolution, "gqa" = grouped-query attention with
             per-head q/k norms; latent everywhere unless `mixers` is given
    FFN_l:   the first `n_dense_layer` blocks a dense gated FFN, every
             later block shared experts + routed experts
    logits = RMSNorm_f(h) W_head     (untied; with `tie_embeddings`
                                      W_head = E^T, the embedding's slice)

Blocks are `keras.transformer.PreNormDecoderBlock`s over
`keras.latent_attention.LatentSelfAttention`, `keras.linear_attention.
KimiDeltaAttention`, `keras.gated_conv.GatedShortConv` or
`keras.grouped_attention.GroupedQueryAttention` and
`keras.transformer.GatedFFN` or `keras.moe.MoEFeedForward`. The model may
be ONE chip's share of an expert-parallel deployment: `experts_held` is
the range of every layer's routed experts that live here (`keras/moe.py`),
and `vocab` the slice of the vocabulary whose embedding rows and head
columns live here; everything else is whole.

TPU-first layout, as `models/looped_decoder.py`: neighbouring layers of one
(mixer, FFN) kind are ONE `[n, ...]` buffer per tensor, `lax.scan`ned, so
each run compiles once and its gradients are born stacked; the runs follow
one another in the layers' own order (`_runs`). Latent attention alone
gives the two runs `dense_blocks` and `moe_blocks`; a model with other
mixers names a run `blocks_<first layer>_<mixer>_<ffn>`. With `remat` every
layer is a `jax.checkpoint` that keeps its [B, T, H] input and the
attention kernel's output (with `use_flash` the flash kernel's and its
log-sum-exp, `pallas.flash_attention.save_flash_residuals`; the linear
layer's recurrence output always, by its own name), and computes the rest
again in the backward pass: norms, projections, convolutions and gates, the
router's choice, the dispatch and the expert products, but no second
attention forward. In training `apply` hands the loss `ProjectedLogits`, so
the [B, T, vocab] logits are never formed whole (`ops/objectives.py`).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

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
from analytics_zoo_tpu.pallas.flash_attention import (FLASH_LSE_NAME,
                                                      FLASH_OUT_NAME,
                                                      save_flash_residuals)
from analytics_zoo_tpu.serving.quantization import maybe_int8_matmul


def _runs(mixers, n_dense):
    """The layers as runs of neighbours of one (mixer, FFN) kind:
    [(parameter name, mixer, "dense" | "moe", first layer, layers)]. Latent
    attention alone keeps the two names a tree of such a model has had
    (`dense_blocks`, `moe_blocks`)."""
    kinds = [(m, "dense" if l < n_dense else "moe")
             for l, m in enumerate(mixers)]
    runs, first = [], 0
    for l in range(1, len(kinds) + 1):
        if l == len(kinds) or kinds[l] != kinds[first]:
            mixer, ffn = kinds[first]
            name = f"{ffn}_blocks" if set(mixers) == {"latent"} \
                else f"blocks_{first}_{mixer}_{ffn}"
            runs.append((name, mixer, ffn, first, l - first))
            first = l
    return runs


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
                 remat: bool = True, rotary: bool = True,
                 mixers: Optional[Sequence[str]] = None,
                 linear_attention: Optional[Dict] = None,
                 conv: Optional[Dict] = None, gqa: Optional[Dict] = None,
                 tie_embeddings: bool = False, router_eps: float = 1e-20,
                 name=None):
        """`mixers`: one of "latent" / "linear" / "conv" / "gqa" a layer,
        in the layers' order (None: latent everywhere); `linear_attention`:
        the linear layers' own arguments (`keras.linear_attention.
        KimiDeltaAttention`: n_head, head_dim, conv_size, chunk, ...);
        `conv`: the conv layers' (`keras.gated_conv.GatedShortConv`:
        conv_size); `gqa`: the grouped-query layers' (n_kv_head,
        head_dim; `n_head` query heads; their rotary tables are head_dim
        wide, and a model has no latent layer beside them);
        `rotary=False` builds the latent layers without positions;
        `tie_embeddings`: the head is the embedding's transpose;
        `router_eps`: added to the chosen scores' sum (`keras.moe.route`)."""
        super().__init__(name)
        if not 0 <= n_dense_layer < n_layer:
            raise ValueError("MoEDecoderLM needs at least one expert layer "
                             f"after its {n_dense_layer} dense ones")
        mixers = list(mixers or ["latent"] * n_layer)
        kinds = {"latent", "linear", "conv", "gqa"}
        if len(mixers) != n_layer or set(mixers) - kinds \
                or {"latent", "gqa"} <= set(mixers):
            raise ValueError(f"MoEDecoderLM: mixers {mixers} must name one "
                             f"of {sorted(kinds)} for each of the {n_layer} "
                             "layers, never latent beside gqa")
        self.vocab, self.hidden_size = vocab, hidden_size
        self.rope_dim = gqa["head_dim"] if "gqa" in mixers \
            else qk_rope_head_dim
        self.rope_theta, self.tie = rope_theta, tie_embeddings
        self.remat, self.rotary = remat, rotary
        # what a layer's checkpoint keeps: the attention kernels' outputs
        self.kept = save_flash_residuals
        init = jax.nn.initializers.normal(0.02)

        def mixer(kind, tag):
            if kind == "linear":
                # imported where a model has one: the other families'
                # start-up path does not pay for it
                from analytics_zoo_tpu.keras.linear_attention import \
                    KimiDeltaAttention
                return KimiDeltaAttention(
                    hidden_size, rms_eps=rms_eps, init=init,
                    name=f"{self.name}_{tag}_kda", **(linear_attention or {}))
            if kind == "conv":
                from analytics_zoo_tpu.keras.gated_conv import GatedShortConv
                return GatedShortConv(hidden_size, init=init,
                                      name=f"{self.name}_{tag}_conv",
                                      **(conv or {}))
            if kind == "gqa":
                from analytics_zoo_tpu.keras.grouped_attention import \
                    GroupedQueryAttention
                return GroupedQueryAttention(
                    hidden_size, n_head, rms_eps=rms_eps,
                    use_flash=use_flash, init=init,
                    name=f"{self.name}_{tag}_gqa", **gqa)
            return LatentSelfAttention(
                hidden_size, n_head, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, rms_eps=rms_eps,
                use_flash=use_flash, init=init, rotary=rotary,
                name=f"{self.name}_{tag}_attn")

        self.moe = MoEFeedForward(
            hidden_size, moe_intermediate_size, n_routed_experts,
            num_experts_per_tok, experts_held=experts_held,
            shared_width=n_shared_experts * moe_intermediate_size,
            routed_scaling_factor=routed_scaling_factor,
            hidden_act=hidden_act, init=init, norm_eps=router_eps,
            name=self.name + "_moe")
        dense_ffn = GatedFFN(hidden_size, intermediate_size, hidden_act,
                             init=init, name=self.name + "_dense_ffn")
        # one block a (mixer, FFN) kind; a run of neighbouring layers of
        # one kind is scanned as one stack
        self.blocks, self.runs = {}, _runs(mixers, n_dense_layer)
        for _, kind, ffn, _, _ in self.runs:
            if (kind, ffn) not in self.blocks:
                tag = ffn if kind == "latent" else f"{kind}_{ffn}"
                self.blocks[kind, ffn] = PreNormDecoderBlock(
                    mixer(kind, tag), self.moe if ffn == "moe" else dense_ffn,
                    rms_eps, name=f"{self.name}_{tag}_block")
        self.final_norm = RMSNormalization(rms_eps,
                                           name=self.name + "_final_norm")
        n_linear = mixers.count("linear")
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
                 "forward (passes x blocks), whatever each block's mixer",
                 n_layer),
                ("model_layers_linear", "layers whose mixer is a linear-"
                 "attention layer (a state, no keys and values)", n_linear),
                ("model_layers_full", "layers whose mixer is (latent or "
                 "grouped-query) softmax attention over the whole sequence",
                 mixers.count("latent") + mixers.count("gqa")),
                ("model_layers_conv", "layers whose mixer is a gated short "
                 "convolution (a K - 1 token history, no keys and values)",
                 mixers.count("conv")),
                ("model_layers_gqa", "layers whose mixer is grouped-query "
                 "attention (K/V heads serving several query heads)",
                 mixers.count("gqa")),
                ("model_recompute", "1 if every block application is "
                 "recomputed in the backward pass", int(remat)),
                ("model_recompute_attention_kernel", "1 if the backward "
                 "pass runs the attention forward again, 0 if its output "
                 "is kept (no recomputation, or the flash kernel's "
                 "residuals saved across it)",
                 int(remat and not use_flash))):
            gauge(gname, doc).set(value, model=self.name)
        if n_linear:
            from analytics_zoo_tpu.keras.linear_attention import \
                RECURRENCE_OUT_NAME
            self.kept = jax.checkpoint_policies.save_only_these_names(
                FLASH_OUT_NAME, FLASH_LSE_NAME, RECURRENCE_OUT_NAME)
            layer = self.blocks[next(k for k in self.blocks
                                     if k[0] == "linear")].attn
            gauge("model_linear_chunk", "tokens of a chunk of the linear-"
                  "attention layers' recurrence").set(layer.chunk,
                                                      model=self.name)
            gauge("model_linear_state_bytes", "bytes of one linear-"
                  "attention layer's state a sequence (heads x dk x dv "
                  "float32), whatever the sequence's length").set(
                      4 * layer.n_head * layer.dk * layer.dv,
                      model=self.name)

    def build(self, rng, input_shape=None):
        n_layer = sum(run[4] for run in self.runs)
        k_emb, k_head, *k_blocks = jax.random.split(rng, 2 + n_layer)
        h_shape = (None, None, self.hidden_size)
        p = {
            "word_embeddings": jax.random.normal(
                k_emb, (self.vocab, self.hidden_size)) * 0.02,
            "final_norm": self.final_norm.build(rng, h_shape),
        }
        if not self.tie:
            p["lm_head_kernel"] = jax.random.normal(
                k_head, (self.hidden_size, self.vocab)) * 0.02
        for name, kind, ffn, first, n in self.runs:
            # each block from its own key, every tensor born [n, ...]
            p[name] = jax.vmap(
                lambda k: self.blocks[kind, ffn].build(k, h_shape))(
                    jnp.stack(k_blocks[first:first + n]))
        return p

    def _scan_blocks(self, params, h, rotary, per_moe_layer=None):
        """The blocks over h [B, T, H]; `per_moe_layer(block, block params,
        layer input)`, where given, is stacked over the expert layers, in
        the layers' order, and returned beside the hidden state."""
        seen = []
        for name, kind, ffn, _, _ in self.runs:
            block = self.blocks[kind, ffn]

            def apply_block(bp, hh, block=block, scope=f"moedec/{ffn}_block"):
                with jax.named_scope(scope):
                    return block.ffn_branch(
                        bp, block.attention_branch(bp, hh, rotary))

            layer = jax.checkpoint(apply_block, policy=self.kept) \
                if self.remat else apply_block

            def step(a, bp, block=block, layer=layer, watch=ffn == "moe"
                     and per_moe_layer is not None):
                return layer(bp, a), (per_moe_layer(block, bp, a)
                                      if watch else None)

            h, out = jax.lax.scan(step, h, params[name])
            if out is not None:
                seen.append(out)
        if len(seen) > 1:
            return h, jnp.concatenate(seen)
        return h, (seen[0] if seen else None)

    def _embed(self, params, ids):
        """(embedded ids [B, T, H], the rotary tables of T positions, or
        None where the latent layers have no positions)."""
        ids = jnp.asarray(ids, jnp.int32)
        return (jnp.take(params["word_embeddings"], ids, axis=0),
                rotary_tables(ids.shape[1], self.rope_dim, self.rope_theta)
                if self.rotary else None)

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

        def choice(block, bp, hh):
            hh = block.attention_branch(bp, hh, rotary)
            experts, _ = self.moe.routing(
                bp["ffn"], block.norm.call(bp["ffn_norm"], hh))
            return experts.reshape(h.shape[:2] + (-1,))

        return self._scan_blocks(params, h, rotary, choice)[1]

    def forward(self, params, ids):
        """Logits [B, T, vocab]."""
        if self.tie:
            return self.hidden(params, ids) @ params["word_embeddings"].T
        return maybe_int8_matmul(self.hidden(params, ids), params,
                                 "lm_head_kernel")

    def apply(self, params, inputs, *, training=False, rng=None):
        if not training:
            return self.forward(params, inputs)
        head = params["word_embeddings"].T if self.tie \
            else params["lm_head_kernel"]
        return ProjectedLogits(self.hidden(params, inputs), head)

    def compute_output_shape(self, input_shape):
        return (None, input_shape[1], self.vocab)

    def _ordered_layers(self):
        return []
