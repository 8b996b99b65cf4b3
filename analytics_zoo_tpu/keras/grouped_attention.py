"""Grouped-query causal self-attention with per-head q/k norms (LFM2's
attention layer) as a Keras-style layer.

    q = x Wq -> [T, heads, d];  k = x Wk, v = x Wv -> [T, kv heads, d]
    q, k <- RMSNorm over each head's d columns (one weight [d] for q, one
            for k), then rotary positions over all d (rotate-half pairs)
    o_h  = softmax(q_h k_{h // group}^T / sqrt(d) + causal) v_{h // group}
    out  = concat_h(o_h) Wo

No bias anywhere. The attention runs through `pallas.flash_attention`,
whose kernels read K/V head h // group for query head h: K and V are never
repeated to every query head, on the chip or off it (the exact path reads
them grouped too).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.engine import Layer
from analytics_zoo_tpu.keras.layers import RMSNormalization, get_init
from analytics_zoo_tpu.keras.transformer import apply_rotary
from analytics_zoo_tpu.pallas.flash_attention import (_reference_attention,
                                                      flash_attention)
from analytics_zoo_tpu.serving.quantization import maybe_int8_matmul


class GroupedQueryAttention(Layer):
    """Causal self-attention of `n_head` query heads over `n_kv_head` K/V
    heads, each `head_dim` wide. `call` takes `[x, (cos, sin)]` with the
    rotary tables of `head_dim` (`keras.transformer.rotary_tables`),
    shared by every block."""

    def __init__(self, hidden_size: int, n_head: int, n_kv_head: int,
                 head_dim: int, rms_eps: float = 1e-5,
                 use_flash: bool = False, init="glorot_uniform", **kw):
        super().__init__(**kw)
        if n_head % n_kv_head:
            raise ValueError(f"{n_kv_head} K/V heads do not divide "
                             f"{n_head} query heads into groups")
        self.hidden_size, self.n_head, self.n_kv = (hidden_size, n_head,
                                                    n_kv_head)
        self.head_dim, self.use_flash = head_dim, use_flash
        self.init = get_init(init)
        self.qk_norm = RMSNormalization(rms_eps, name=self.name + "_qk_norm")

    def build(self, rng, input_shape=None):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        H, f32 = self.hidden_size, jnp.float32
        q_cols = self.n_head * self.head_dim
        kv_cols = self.n_kv * self.head_dim
        head = (None, None, self.head_dim)
        return {
            "q_kernel": self.init(k1, (H, q_cols), f32),
            "k_kernel": self.init(k2, (H, kv_cols), f32),
            "v_kernel": self.init(k3, (H, kv_cols), f32),
            "q_norm": self.qk_norm.build(rng, head),
            "k_norm": self.qk_norm.build(rng, head),
            "out_kernel": self.init(k4, (q_cols, H), f32),
        }

    def call(self, params, x, *, training=False, rng=None):
        x, (cos, sin) = x
        B, T, _ = x.shape

        def heads(a):                   # [B, T, n * d] -> [B, n, T, d]
            return a.reshape(B, T, -1, self.head_dim).transpose(0, 2, 1, 3)

        with jax.named_scope("gqa/qkv_proj"):
            q, k, v = (heads(maybe_int8_matmul(x, params, name)
                             .astype(x.dtype))
                       for name in ("q_kernel", "k_kernel", "v_kernel"))
        with jax.named_scope("gqa/qk_norm_rope"):
            q = apply_rotary(self.qk_norm.call(params["q_norm"], q), cos, sin)
            k = apply_rotary(self.qk_norm.call(params["k_norm"], k), cos, sin)
        with jax.named_scope("gqa/attention"):
            if self.use_flash:
                ctx = flash_attention(q, k, v, causal=True)
            else:
                ctx = _reference_attention(q, k, v, causal=True)
        with jax.named_scope("gqa/out_proj"):
            ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, -1)
            return maybe_int8_matmul(ctx, params,
                                     "out_kernel").astype(x.dtype)

    def compute_output_shape(self, input_shape):
        return input_shape[0]
