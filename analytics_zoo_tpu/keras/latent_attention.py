"""Multi-head latent attention (DeepSeek-V2's MLA) as a Keras-style layer.

Keys and values are not projected from the hidden state directly but from
a low-rank latent of it, and the rotary part of the key is ONE head that
all query heads share:

    q            = x Wq                   -> [T, heads, nope + rope]
    [c | kr]     = x Wkva                 -> [T, rank], [T, rope]
    [k_nope | v] = RMSNorm(c) Wkvb        -> [T, heads, nope], [T, heads, v]
    q_rope, kr   = rotary positions over the `rope` columns, where the
                   layer has them (`rotary`)
    k            = [k_nope | kr for every head];  q = [q_nope | q_rope]
    out          = softmax(q k^T / sqrt(nope + rope) + causal) v  Wo

so queries and keys are `nope + rope` wide and values `v` wide (192 and
128 in the published models): the attention runs through
`pallas.flash_attention` at two head widths. No bias anywhere, no query
compression (`q_lora_rank` null). With `rotary=False` (a config's
`mla_use_nope`: a hybrid model whose linear-attention layers carry the
order) no position is applied anywhere: the `rope` columns stay, as
content, and the one shared key head enters the scores as it is. The
training path forms k and v whole; the latent cache row `[c | kr]` that
makes the form worth having when serving is not built here (ROADMAP M6:
left are that row as a cache entry and the decode step that reads it).
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


class LatentSelfAttention(Layer):
    """Causal multi-head latent self-attention. `call` takes
    `[x, (cos, sin)]` with the rotary tables of `rope_head_dim`
    (`keras.transformer.rotary_tables(T, rope_head_dim, theta)`), shared
    by every block. The rotary columns are neighbouring pairs
    (2i, 2i + 1), as a checkpoint with `rope_interleave` keeps them; they
    are de-interleaved into rotate-half order, the same for q and k
    (`apply_rotary`). With `rotary=False` the tables are not read (None
    will do) and the columns keep their order."""

    def __init__(self, hidden_size: int, n_head: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, rms_eps: float = 1e-6,
                 use_flash: bool = False, init="glorot_uniform",
                 rotary: bool = True, **kw):
        super().__init__(**kw)
        self.hidden_size, self.n_head = hidden_size, n_head
        self.rank = kv_lora_rank
        self.nope, self.rope, self.v_dim = (qk_nope_head_dim,
                                            qk_rope_head_dim, v_head_dim)
        self.use_flash, self.rotary = use_flash, rotary
        self.init = get_init(init)
        self.kv_norm = RMSNormalization(rms_eps, name=self.name + "_kv_norm")

    def build(self, rng, input_shape=None):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        H, n = self.hidden_size, self.n_head
        return {
            "q_kernel": self.init(k1, (H, n * (self.nope + self.rope)),
                                  jnp.float32),
            "kv_a_kernel": self.init(k2, (H, self.rank + self.rope),
                                     jnp.float32),
            "kv_norm": self.kv_norm.build(rng, (None, None, self.rank)),
            "kv_b_kernel": self.init(
                k3, (self.rank, n * (self.nope + self.v_dim)), jnp.float32),
            "out_kernel": self.init(k4, (n * self.v_dim, H), jnp.float32),
        }

    def call(self, params, x, *, training=False, rng=None):
        x, tables = x
        B, T, _ = x.shape
        n = self.n_head

        def heads(a):                   # [B, T, n * w] -> [B, n, T, w]
            return a.reshape(B, T, n, -1).transpose(0, 2, 1, 3)

        def rotary(a):
            if not self.rotary:
                return a
            return apply_rotary(a, *tables, interleaved=True)

        with jax.named_scope("mla/q_proj"):
            q = heads(maybe_int8_matmul(x, params, "q_kernel")
                      .astype(x.dtype))
            q = jnp.concatenate([q[..., :self.nope],
                                 rotary(q[..., self.nope:])], axis=-1)
        with jax.named_scope("mla/kv_compress"):
            ckr = maybe_int8_matmul(x, params, "kv_a_kernel").astype(x.dtype)
            c = self.kv_norm.call(params["kv_norm"], ckr[..., :self.rank])
            kr = rotary(ckr[:, None, :, self.rank:])        # [B, 1, T, rope]
        with jax.named_scope("mla/kv_expand"):
            kv = heads(maybe_int8_matmul(c, params, "kv_b_kernel")
                       .astype(x.dtype))
            k = jnp.concatenate(
                [kv[..., :self.nope],
                 jnp.broadcast_to(kr, (B, n, T, self.rope))], axis=-1)
            v = kv[..., self.nope:]
        with jax.named_scope("mla/attention"):
            if self.use_flash:
                ctx = flash_attention(q, k, v, causal=True)
            else:
                ctx = _reference_attention(q, k, v, causal=True)
        with jax.named_scope("mla/out_proj"):
            ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, n * self.v_dim)
            return maybe_int8_matmul(ctx, params,
                                     "out_kernel").astype(x.dtype)

    def compute_output_shape(self, input_shape):
        return input_shape[0]
