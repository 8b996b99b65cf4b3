"""Transformer and BERT as Keras-style layers.

The reference ships a GPT-style `TransformerLayer`
(`keras/layers/TransformerLayer.scala:56`) and a full BERT encoder as a Keras
layer (`keras/layers/BERT.scala:66`), both assembled from per-gate JVM tensor
ops. This build is TPU-first:

- fused QKV projection — one [d, 3d] matmul per block feeds the MXU instead of
  three small ones;
- attention computed in bf16-friendly einsums with f32 softmax accumulation;
  the Pallas flash-attention kernel (`analytics_zoo_tpu/pallas/
  flash_attention.py`) drops in for long sequences;
- additive attention masks broadcast [B, 1, 1, T] so GSPMD can shard B and
  heads without re-layout;
- post-norm residual blocks matching BERT semantics (gelu FFN, LayerNorm
  eps 1e-12);
- a decoder block of today's kind beside them (`TransformerDecoderBlock`):
  RMS norm before and after each branch, bias-free causal self-attention
  with rotary positions, a gated FFN; the causal mask is a flag of the
  flash kernels, never a [T, T] operand;
- its pre-norm sibling (`PreNormDecoderBlock`: one RMS norm before each
  branch), whose attention and FFN are layers handed to it: latent
  attention (`keras/latent_attention.py`), a dense `GatedFFN` or an expert
  layer (`keras/moe.py`).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.engine import Layer
from analytics_zoo_tpu.keras.layers import (LayerNormalization,
                                            RMSNormalization, get_activation,
                                            get_init)
from analytics_zoo_tpu.pallas.dropout import fused_dropout
from analytics_zoo_tpu.pallas.flash_attention import (_reference_attention,
                                                      flash_attention,
                                                      save_flash_residuals)
from analytics_zoo_tpu.serving.quantization import maybe_int8_matmul


def _dropout(rng, rate: float, x):
    """Shared inverted dropout (same semantics as layers.Dropout). On TPU
    this draws uint8 bytes instead of uint32 bits — 4x less unfusible RNG
    HBM traffic, which profiling shows is the entire dropout tax at
    BERT-base scale (docs/ROOFLINE.md)."""
    return fused_dropout(x, rate, rng=rng)


def dot_product_attention(q, k, v, mask=None, dropout_rng=None,
                          dropout_rate: float = 0.0, use_flash: bool = False):
    """q,k,v: [B, H, T, Dh]; mask: additive [B, 1, 1, T] or [B,1,T,T].
    Softmax statistics in f32 regardless of input dtype. With use_flash the
    Pallas kernel runs forward AND backward (custom VJP); attention dropout
    happens inside the kernel (bits regenerated in the backward pass)."""
    no_drop = dropout_rng is None or dropout_rate == 0.0
    if use_flash:
        seed = None
        if not no_drop:
            seed = jax.random.randint(dropout_rng, (), 0, 2 ** 31 - 1)
        return flash_attention(q, k, v, mask=mask,
                               dropout_rate=0.0 if no_drop
                               else dropout_rate,
                               dropout_seed=seed)
    if no_drop:
        return _reference_attention(q, k, v, mask)
    depth = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(depth)
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = scores + mask
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    weights = _dropout(dropout_rng, dropout_rate, weights)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


class MultiHeadSelfAttention(Layer):
    """Fused-QKV self attention (`TransformerLayer.scala` attention part)."""

    def __init__(self, hidden_size: int, n_head: int,
                 attn_dropout: float = 0.0, output_dropout: float = 0.0,
                 use_flash: bool = False, **kw):
        super().__init__(**kw)
        if hidden_size % n_head:
            raise ValueError(f"hidden_size {hidden_size} not divisible by "
                             f"n_head {n_head}")
        self.hidden_size = hidden_size
        self.n_head = n_head
        self.head_dim = hidden_size // n_head
        self.attn_dropout = attn_dropout
        self.output_dropout = output_dropout
        self.use_flash = use_flash

    def build(self, rng, input_shape):
        k1, k2 = jax.random.split(rng)
        init = get_init("glorot_uniform")
        return {
            "qkv_kernel": init(k1, (self.hidden_size, 3 * self.hidden_size),
                               jnp.float32),
            "qkv_bias": jnp.zeros((3 * self.hidden_size,), jnp.float32),
            "out_kernel": init(k2, (self.hidden_size, self.hidden_size),
                               jnp.float32),
            "out_bias": jnp.zeros((self.hidden_size,), jnp.float32),
        }

    def call(self, params, x, *, training=False, rng=None, mask=None):
        if isinstance(x, (list, tuple)):
            x, mask = x
        B, T, D = x.shape
        qkv = maybe_int8_matmul(x, params, "qkv_kernel") \
            + params["qkv_bias"]
        qkv = qkv.reshape(B, T, 3, self.n_head, self.head_dim)
        q, k, v = [jnp.transpose(qkv[:, :, i], (0, 2, 1, 3)) for i in range(3)]
        drop_rng = None
        if training and rng is not None and self.attn_dropout > 0:
            rng, drop_rng = jax.random.split(rng)
        ctx = dot_product_attention(q, k, v, mask=mask, dropout_rng=drop_rng,
                                    dropout_rate=self.attn_dropout,
                                    use_flash=self.use_flash)
        ctx = jnp.transpose(ctx, (0, 2, 1, 3)).reshape(B, T, D)
        out = maybe_int8_matmul(ctx, params, "out_kernel") \
            + params["out_bias"]
        if training and rng is not None and self.output_dropout > 0:
            out = _dropout(rng, self.output_dropout, out)
        return out

    def compute_output_shape(self, input_shape):
        if isinstance(input_shape, list):
            return input_shape[0]
        return input_shape


class TransformerEncoderBlock(Layer):
    """Post-norm BERT block: x + MHA → LN → x + FFN(gelu) → LN
    (`BERT.scala` block; `TransformerLayer.scala:56`)."""

    def __init__(self, hidden_size: int, n_head: int,
                 intermediate_size: Optional[int] = None,
                 hidden_dropout: float = 0.1, attn_dropout: float = 0.1,
                 hidden_act: str = "gelu", use_flash: bool = False, **kw):
        super().__init__(**kw)
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.attn = MultiHeadSelfAttention(
            hidden_size, n_head, attn_dropout=attn_dropout,
            output_dropout=hidden_dropout, use_flash=use_flash,
            name=self.name + "_attn")
        self.ln1 = LayerNormalization(name=self.name + "_ln1")
        self.ln2 = LayerNormalization(name=self.name + "_ln2")
        self.act = get_activation(hidden_act)
        self.hidden_dropout = hidden_dropout

    def build(self, rng, input_shape):
        shape = input_shape[0] if isinstance(input_shape, list) else input_shape
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        init = get_init("glorot_uniform")
        return {
            "attn": self.attn.build(k1, shape),
            "ln1": self.ln1.build(k2, shape),
            "ln2": self.ln2.build(k3, shape),
            "ffn_in_kernel": init(
                k4, (self.hidden_size, self.intermediate_size), jnp.float32),
            "ffn_in_bias": jnp.zeros((self.intermediate_size,), jnp.float32),
            "ffn_out_kernel": init(
                jax.random.fold_in(k4, 1),
                (self.intermediate_size, self.hidden_size), jnp.float32),
            "ffn_out_bias": jnp.zeros((self.hidden_size,), jnp.float32),
        }

    def call(self, params, x, *, training=False, rng=None, mask=None):
        if isinstance(x, (list, tuple)):
            x, mask = x
        r1 = r2 = None
        if rng is not None:
            rng, r1, r2 = jax.random.split(rng, 3)
        # the scopes name the program's parts (device time by scope,
        # `observability/device_time.py`); they change no operation
        with jax.named_scope("bert/block/attention"):
            a = self.attn.call(params["attn"], x, training=training,
                               rng=r1, mask=mask)
        with jax.named_scope("bert/block/attention_output_norm"):
            x = self.ln1.call(params["ln1"], x + a)
        with jax.named_scope("bert/block/ffn"):
            h = self.act(maybe_int8_matmul(x, params, "ffn_in_kernel")
                         + params["ffn_in_bias"])
            h = maybe_int8_matmul(h, params, "ffn_out_kernel") \
                + params["ffn_out_bias"]
            if training and r2 is not None and self.hidden_dropout > 0:
                h = _dropout(r2, self.hidden_dropout, h)
        with jax.named_scope("bert/block/ffn_output_norm"):
            return self.ln2.call(params["ln2"], x + h)

    def compute_output_shape(self, input_shape):
        if isinstance(input_shape, list):
            return input_shape[0]
        return input_shape


def rotary_tables(seq_len: int, head_dim: int, theta: float = 10000.0):
    """cos and sin of the rotary angles, each [seq_len, head_dim / 2]
    float32: position t turns the pair (i, i + head_dim/2) by
    t * theta^(-2i / head_dim) (Su et al. 2021, no scaling)."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x, cos, sin, interleaved: bool = False):
    """Rotary positions on x [B, H, T, Dh] over all Dh dimensions;
    computed in float32 and returned in x's type. The pairing is
    rotate-half (dimension i with i + Dh/2) or, with `interleaved`,
    neighbours (2i with 2i + 1, as a checkpoint with `rope_interleave`
    stores them): the interleaved input is de-interleaved first, so the
    OUTPUT is in rotate-half order either way, [the turned first members |
    the turned second members]. Scores do not depend on the order of the
    columns as long as q and k share it."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    if interleaved:
        a, b = x32[..., 0::2], x32[..., 1::2]
    else:
        a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def gated_ffn(params, u, act):
    """`down(act(gate u) * (up u))` from the three bias-free kernels
    `ffn_gate_kernel`, `ffn_up_kernel`, `ffn_down_kernel` of `params`
    (their int8 forms where present), in u's type."""
    f = act(maybe_int8_matmul(u, params, "ffn_gate_kernel")) \
        * maybe_int8_matmul(u, params, "ffn_up_kernel")
    return maybe_int8_matmul(f.astype(u.dtype), params,
                             "ffn_down_kernel").astype(u.dtype)


def gated_ffn_params(rng, hidden_size: int, width: int, init):
    """The three kernels `gated_ffn` reads."""
    k1, k2, k3 = jax.random.split(rng, 3)
    return {"ffn_gate_kernel": init(k1, (hidden_size, width), jnp.float32),
            "ffn_up_kernel": init(k2, (hidden_size, width), jnp.float32),
            "ffn_down_kernel": init(k3, (width, hidden_size), jnp.float32)}


class CausalSelfAttention(Layer):
    """Bias-free causal self-attention with rotary positions on q and k
    (fused [d, 3d] QKV, as many K/V heads as query heads). `call` takes
    `[x, (cos, sin)]`: the rotary tables are made once a forward
    (`rotary_tables`) and shared by every block. With `use_flash` the
    Pallas kernels run with their `causal` flag; else the exact path
    materialises the triangular mask."""

    def __init__(self, hidden_size: int, n_head: int,
                 use_flash: bool = False, **kw):
        super().__init__(**kw)
        if hidden_size % n_head:
            raise ValueError(f"hidden_size {hidden_size} not divisible by "
                             f"n_head {n_head}")
        self.hidden_size = hidden_size
        self.n_head = n_head
        self.head_dim = hidden_size // n_head
        self.use_flash = use_flash

    def build(self, rng, input_shape):
        k1, k2 = jax.random.split(rng)
        init = get_init("glorot_uniform")
        return {
            "qkv_kernel": init(k1, (self.hidden_size, 3 * self.hidden_size),
                               jnp.float32),
            "out_kernel": init(k2, (self.hidden_size, self.hidden_size),
                               jnp.float32),
        }

    def call(self, params, x, *, training=False, rng=None):
        x, (cos, sin) = x
        B, T, D = x.shape
        qkv = maybe_int8_matmul(x, params, "qkv_kernel").astype(x.dtype)
        qkv = qkv.reshape(B, T, 3, self.n_head, self.head_dim)
        q, k, v = [jnp.transpose(qkv[:, :, i], (0, 2, 1, 3))
                   for i in range(3)]
        q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        if self.use_flash:
            ctx = flash_attention(q, k, v, causal=True)
        else:
            ctx = _reference_attention(q, k, v, causal=True)
        ctx = jnp.transpose(ctx, (0, 2, 1, 3)).reshape(B, T, D)
        return maybe_int8_matmul(ctx, params, "out_kernel").astype(x.dtype)

    def compute_output_shape(self, input_shape):
        return input_shape[0]


class TransformerDecoderBlock(Layer):
    """Decoder block with sandwich norms (four RMS norms a block):
    `h + RMSNorm(Attn(RMSNorm(h)))`, then `h + RMSNorm(FFN(RMSNorm(h)))`
    with the gated FFN `down(act(gate u) * (up u))`; no bias anywhere, no
    dropout. `call` takes `[h, (cos, sin)]`; the two branches are methods
    of their own so that a model can name each in its trace."""

    def __init__(self, hidden_size: int, n_head: int, intermediate_size: int,
                 hidden_act: str = "silu", rms_eps: float = 1e-6,
                 use_flash: bool = False, **kw):
        super().__init__(**kw)
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.attn = CausalSelfAttention(hidden_size, n_head,
                                        use_flash=use_flash,
                                        name=self.name + "_attn")
        self.norm = RMSNormalization(rms_eps, name=self.name + "_norm")
        self.act = get_activation(hidden_act)

    def build(self, rng, input_shape):
        shape = input_shape[0] if isinstance(input_shape, list) else input_shape
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        init = get_init("glorot_uniform")
        wide = (self.hidden_size, self.intermediate_size)
        p = {name: self.norm.build(rng, shape)
             for name in ("attn_in_norm", "attn_out_norm", "ffn_in_norm",
                          "ffn_out_norm")}
        p.update({
            "attn": self.attn.build(k1, shape),
            "ffn_gate_kernel": init(k2, wide, jnp.float32),
            "ffn_up_kernel": init(k3, wide, jnp.float32),
            "ffn_down_kernel": init(k4, wide[::-1], jnp.float32),
        })
        return p

    def attention_branch(self, params, h, rotary):
        a = self.attn.call(params["attn"], [
            self.norm.call(params["attn_in_norm"], h), rotary])
        return h + self.norm.call(params["attn_out_norm"], a)

    def ffn_branch(self, params, h):
        u = self.norm.call(params["ffn_in_norm"], h)
        return h + self.norm.call(params["ffn_out_norm"],
                                  gated_ffn(params, u, self.act))

    def call(self, params, x, *, training=False, rng=None):
        h, rotary = x
        return self.ffn_branch(params,
                               self.attention_branch(params, h, rotary))

    def compute_output_shape(self, input_shape):
        return input_shape[0]


class GatedFFN(Layer):
    """Bias-free gated feed-forward layer, `down(act(gate u) * (up u))`
    (SwiGLU with `silu`)."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 hidden_act: str = "silu", init="glorot_uniform", **kw):
        super().__init__(**kw)
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.act = get_activation(hidden_act)
        self.init = get_init(init)

    def build(self, rng, input_shape=None):
        return gated_ffn_params(rng, self.hidden_size,
                                self.intermediate_size, self.init)

    def call(self, params, u, *, training=False, rng=None):
        return gated_ffn(params, u, self.act)


class PreNormDecoderBlock(Layer):
    """Pre-norm decoder block, two RMS norms: `h + Attn(RMSNorm(h))`, then
    `h + FFN(RMSNorm(h))`. The attention is any layer whose `call` takes
    `[x, (cos, sin)]` (`CausalSelfAttention`,
    `keras.latent_attention.LatentSelfAttention`), the FFN any layer over
    `[B, T, H]` (`GatedFFN`, `keras.moe.MoEFeedForward`). `call` takes
    `[h, (cos, sin)]`; the two branches are methods of their own so that a
    model can name each in its trace (`branches` runs both in order).

    `route_before_attention`: the FFN is an expert layer whose router reads
    the block's normalised input, the tensor the attention reads, and not
    the FFN's own (SmallThinker's router placed before attention): `a =
    RMSNorm_1(h)`, `h' = h + Attn(a)`, `h' + FFN(RMSNorm_2(h'); route
    from a)`."""

    def __init__(self, attn: Layer, ffn: Layer, rms_eps: float = 1e-6,
                 route_before_attention: bool = False, **kw):
        super().__init__(**kw)
        self.attn, self.ffn = attn, ffn
        self.route_before_attention = route_before_attention
        self.norm = RMSNormalization(rms_eps, name=self.name + "_norm")

    def build(self, rng, input_shape):
        shape = input_shape[0] if isinstance(input_shape, list) else input_shape
        k1, k2 = jax.random.split(rng)
        return {"attn_norm": self.norm.build(rng, shape),
                "ffn_norm": self.norm.build(rng, shape),
                "attn": self.attn.build(k1, shape),
                "ffn": self.ffn.build(k2, shape)}

    def attention_branch(self, params, h, rotary, a=None):
        """`h + Attn(a)`, `a` the block's normalised input (computed here
        unless given)."""
        if a is None:
            a = self.norm.call(params["attn_norm"], h)
        return h + self.attn.call(params["attn"], [a, rotary])

    def ffn_branch(self, params, h, route_from=None):
        u = self.norm.call(params["ffn_norm"], h)
        if route_from is None:
            return h + self.ffn.call(params["ffn"], u)
        return h + self.ffn.call(params["ffn"], u, route_from=route_from)

    def branches(self, params, h, rotary):
        """The block on h: both branches, the router handed the block's
        normalised input where it reads that."""
        if not self.route_before_attention:
            return self.ffn_branch(params,
                                   self.attention_branch(params, h, rotary))
        a = self.norm.call(params["attn_norm"], h)
        return self.ffn_branch(
            params, self.attention_branch(params, h, rotary, a), route_from=a)

    def call(self, params, x, *, training=False, rng=None):
        h, rotary = x
        return self.branches(params, h, rotary)

    def compute_output_shape(self, input_shape):
        return input_shape[0]


class TransformerLayer(Layer):
    """Decoder-less transformer stack over embedded inputs
    (`TransformerLayer.scala:56`): word+position embeddings + N blocks."""

    def __init__(self, vocab: int, seq_len: int, n_block: int = 12,
                 hidden_size: int = 768, n_head: int = 12,
                 embedding_drop: float = 0.1, hidden_drop: float = 0.1,
                 attn_drop: float = 0.1, use_flash: bool = False, **kw):
        super().__init__(**kw)
        self.vocab, self.seq_len = vocab, seq_len
        self.hidden_size = hidden_size
        self.embedding_drop = embedding_drop
        self.blocks = [
            TransformerEncoderBlock(hidden_size, n_head,
                                    hidden_dropout=hidden_drop,
                                    attn_dropout=attn_drop,
                                    use_flash=use_flash,
                                    name=f"{self.name}_block{i}")
            for i in range(n_block)]

    def build(self, rng, input_shape):
        k0, k1, *ks = jax.random.split(rng, 2 + len(self.blocks))
        p = {
            "word_embeddings": jax.random.normal(
                k0, (self.vocab, self.hidden_size)) * 0.02,
            "position_embeddings": jax.random.normal(
                k1, (self.seq_len, self.hidden_size)) * 0.02,
        }
        h_shape = (None, self.seq_len, self.hidden_size)
        for blk, k in zip(self.blocks, ks):
            p[blk.name] = blk.build(k, h_shape)
        return p

    def call(self, params, x, *, training=False, rng=None):
        ids = jnp.asarray(x, jnp.int32)
        h = (jnp.take(params["word_embeddings"], ids, axis=0)
             + params["position_embeddings"][None, :ids.shape[1]])
        if training and rng is not None and self.embedding_drop > 0:
            rng, sub = jax.random.split(rng)
            h = _dropout(sub, self.embedding_drop, h)
        for blk in self.blocks:
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            h = blk.call(params[blk.name], h, training=training, rng=sub)
        return h

    def compute_output_shape(self, input_shape):
        return (input_shape[0], self.seq_len, self.hidden_size)


def stack_block_params(params: dict, n_block: int, prefix: str) -> dict:
    """Convert an UNSTACKED BERT param tree (per-block subtrees named
    `{prefix}_block{i}`) to the stacked layout (`blocks` = one [L, ...]
    buffer per tensor). Inverse: `unstack_block_params`. Used to move
    imported artifacts (TF-checkpoint weights load into the unstacked
    naming) onto a `stacked=True` encoder."""
    per_block = [params[f"{prefix}_block{i}"] for i in range(n_block)]
    out = {k: v for k, v in params.items()
           if not k.startswith(prefix + "_block")}
    out["blocks"] = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *per_block)
    return out


def unstack_block_params(params: dict, n_block: int, prefix: str) -> dict:
    """Inverse of `stack_block_params`."""
    out = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(n_block):
        out[f"{prefix}_block{i}"] = jax.tree_util.tree_map(
            lambda x, _i=i: x[_i], params["blocks"])
    return out


# What `BERT(remat=True)` keeps of a checkpointed block: the matmul outputs
# with no batch dims (none: every dot of the block carries the batch) and,
# with `use_flash`, the attention kernel's output and log-sum-exp, so that
# the backward pass computes the block again but not the kernel. One policy
# for every recomputed fit in the package (`models/looped_decoder.py` uses
# the second half alone).
_REMAT_POLICY = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    save_flash_residuals)


class BERT(Layer):
    """BERT encoder as a layer (`keras/layers/BERT.scala:66`). Inputs:
    [token_ids, token_type_ids, attention_mask] (position ids are implicit);
    outputs (sequence_output, pooled_output) — or just pooled when
    `pooled_only=True` for graph use."""

    def __init__(self, vocab: int = 30522, hidden_size: int = 768,
                 n_block: int = 12, n_head: int = 12,
                 seq_len: int = 512, intermediate_size: int = 3072,
                 type_vocab: int = 2, hidden_drop: float = 0.1,
                 attn_drop: float = 0.1, pooled_only: bool = False,
                 use_flash: bool = False, remat: bool = False,
                 stacked: bool = False, **kw):
        super().__init__(**kw)
        self.vocab, self.hidden_size = vocab, hidden_size
        self.seq_len, self.type_vocab = seq_len, type_vocab
        self.hidden_drop = hidden_drop
        self.pooled_only = pooled_only
        self.remat = remat
        self.stacked = stacked
        self.n_block = n_block
        self.blocks = [
            TransformerEncoderBlock(hidden_size, n_head, intermediate_size,
                                    hidden_dropout=hidden_drop,
                                    attn_dropout=attn_drop,
                                    use_flash=use_flash,
                                    name=f"{self.name}_block{i}")
            for i in range(n_block)]
        self.emb_ln = LayerNormalization(name=self.name + "_emb_ln")

    def build(self, rng, input_shape):
        keys = jax.random.split(rng, 5 + len(self.blocks))
        p = {
            "word_embeddings": jax.random.normal(
                keys[0], (self.vocab, self.hidden_size)) * 0.02,
            "position_embeddings": jax.random.normal(
                keys[1], (self.seq_len, self.hidden_size)) * 0.02,
            "token_type_embeddings": jax.random.normal(
                keys[2], (self.type_vocab, self.hidden_size)) * 0.02,
            "emb_ln": self.emb_ln.build(
                keys[3], (None, None, self.hidden_size)),
            "pooler_kernel": get_init("glorot_uniform")(
                keys[4], (self.hidden_size, self.hidden_size), jnp.float32),
            "pooler_bias": jnp.zeros((self.hidden_size,), jnp.float32),
        }
        h_shape = (None, self.seq_len, self.hidden_size)
        per_block = [blk.build(k, h_shape)
                     for blk, k in zip(self.blocks, keys[5:])]
        if self.stacked:
            # ONE [L, ...] buffer per block tensor; `call` lax.scans the
            # block over dim 0. Why: (a) gradients are BORN stacked, so
            # the optimizer phase is ~15 big streaming fusions instead of
            # 12x13 small ones (the per-tensor Adam sweep measured 37
            # ms/step on BERT-base, 21% of the seq-128 step — and
            # repacking per-leaf grads after the fact costs the saving
            # back, docs/ROOFLINE.md round 5); (b) the block compiles
            # ONCE instead of 12 times. Same math, same init as the
            # unstacked form (`stack_block_params` converts either way).
            p["blocks"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per_block)
        else:
            for blk, bp in zip(self.blocks, per_block):
                p[blk.name] = bp
        return p

    def _scan_blocks(self, stacked_params, h, mask, training, rng):
        """lax.scan the (single, shared-code) encoder block over the
        leading [L, ...] dim of the stacked params — identical math to
        the unstacked loop (per-layer weights, per-layer dropout keys),
        one compiled block body, gradients accumulated directly into the
        stacked buffers by scan's transpose."""
        blk = self.blocks[0]

        def run_block(bp, hh, key):
            fn = lambda p, a, m, r: blk.call(  # noqa: E731
                p, [a, m], training=training, rng=r)
            if self.remat:
                fn = jax.checkpoint(fn, policy=_REMAT_POLICY)
            return fn(bp, hh, mask, key)

        if rng is not None:
            layer_keys = jax.random.split(rng, self.n_block)

            def body(hh, xs):
                bp, key = xs
                return run_block(bp, hh, key), None

            h, _ = jax.lax.scan(body, h, (stacked_params, layer_keys))
        else:
            h, _ = jax.lax.scan(
                lambda hh, bp: (run_block(bp, hh, None), None),
                h, stacked_params)
        return h

    @staticmethod
    def make_mask(attention_mask) -> jax.Array:
        """[B, T] {0,1} → additive [B, 1, 1, T] (matches the reference's
        -10000 masked-logit convention, `BERT.scala`)."""
        m = jnp.asarray(attention_mask, jnp.float32)
        return (1.0 - m)[:, None, None, :] * -10000.0

    def call(self, params, x, *, training=False, rng=None):
        if isinstance(x, (list, tuple)):
            if len(x) == 3:
                ids, token_type, attn_mask = x
            elif len(x) == 2:
                ids, attn_mask = x
                token_type = jnp.zeros_like(ids)
            else:
                raise ValueError("BERT expects [ids, (token_type), mask]")
        else:
            ids = x
            token_type = jnp.zeros_like(ids)
            attn_mask = jnp.ones_like(ids)
        ids = jnp.asarray(ids, jnp.int32)
        token_type = jnp.asarray(token_type, jnp.int32)
        T = ids.shape[1]
        with jax.named_scope("bert/embeddings"):
            h = (jnp.take(params["word_embeddings"], ids, axis=0)
                 + params["position_embeddings"][None, :T]
                 + jnp.take(params["token_type_embeddings"], token_type,
                            axis=0))
            h = self.emb_ln.call(params["emb_ln"], h)
            if training and rng is not None and self.hidden_drop > 0:
                rng, sub = jax.random.split(rng)
                h = _dropout(sub, self.hidden_drop, h)
            mask = self.make_mask(attn_mask)
        if self.stacked:
            h = self._scan_blocks(params["blocks"], h, mask, training, rng)
        else:
            for blk in self.blocks:
                sub = None
                if rng is not None:
                    rng, sub = jax.random.split(rng)
                if self.remat:
                    # activation rematerialization per block: save only
                    # what `_REMAT_POLICY` names, recompute the rest in
                    # the backward pass. Trades ~1/3 more FLOPs
                    # on the block for O(1) blocks of live activations,
                    # unlocking batch sizes (and seq lengths) the
                    # non-remat program cannot fit.
                    h = jax.checkpoint(
                        lambda p, hh, mm, rr, _blk=blk: _blk.call(
                            p, [hh, mm], training=training, rng=rr),
                        policy=_REMAT_POLICY)(
                            params[blk.name], h, mask, sub)
                else:
                    h = blk.call(params[blk.name], [h, mask],
                                 training=training, rng=sub)
        with jax.named_scope("bert/pooler_head"):
            pooled = jnp.tanh(maybe_int8_matmul(h[:, 0], params,
                                                "pooler_kernel")
                              + params["pooler_bias"])
        if self.pooled_only:
            return pooled
        return h, pooled

    def compute_output_shape(self, input_shape):
        first = input_shape[0] if isinstance(input_shape, list) else input_shape
        if self.pooled_only:
            return (first[0], self.hidden_size)
        return [(first[0], first[1], self.hidden_size),
                (first[0], self.hidden_size)]
