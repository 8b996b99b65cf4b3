"""Kimi Delta Attention (KDA) as a Keras-style layer: a linear attention
whose state is not keys and values but one [dk, dv] matrix a head, decayed
channel by channel and corrected by the delta rule.

    q, k, v = SiLU(conv(x Wq)), SiLU(conv(x Wk)), SiLU(conv(x Wv))
              conv: causal, depthwise (one filter of `conv_size` taps a
              channel), over time, zero history before token 0, no bias
    q = q / ||q||_2 * dk^-0.5;  k = k / ||k||_2          per head and token
    g     = -exp(A_log[h]) * softplus((x Wfa) Wfb + dt_bias)   float32, <= 0
            the per-CHANNEL log-decay [T, heads, dk]; alpha = exp(g)
    beta  = sigmoid(x Wb)                                      [T, heads]
    per head, S_0 = 0:
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
    gate  = (x Wga) Wgb + b_g                                  [T, heads, dv]
    y_t   = RMSNorm_dv(o_t) * sigmoid(gate_t)       one norm weight [dv]
    out   = y Wo

The recurrence runs chunked, forward and backward
(`pallas/delta_rule.py`: on the TPU what a chunk needs of itself in the
kernels `delta_prepare_fwd` and `delta_prepare_bwd`, a chunk in VMEM, and
the pass over the chunks in the kernels `kda_chunk_fwd` and
`kda_chunk_bwd`; off the TPU, and for a chunk or widths the preparation's
kernels do not take, the preparation in XLA for all chunks at once; the
benchmark's per-kernel metrics match the four names); the decay is
accumulated and exponentiated in float32 whatever the step's type. The layer's state is [heads, dk, dv]
float32 a sequence, whatever its length; the training path starts every
sequence from zero and returns no state (a cache entry, snapshots and a
decode step are what serving would add: ROADMAP M6).

The stage between the projections and the recurrence (the filter, SiLU,
the L2 norm, and the way from [B, T, heads * d] to the recurrence's rows
[B * heads, T, d]) runs on one of two paths, and the input decides which
(`KimiDeltaAttention._qkv_rows`; no option, no environment variable): on
the TPU (or under `interpret=True`), with heads a multiple of 128 wide and
a sequence of whole tiles of 16 tokens, the kernels `qkv_short_conv_fwd` /
`qkv_short_conv_bwd` of `pallas/short_conv.py`, which filter in float32
with a tile of tokens in VMEM, write rows directly and keep the projection
and the taps alone for the gradient; off the TPU and for every other
width or length `_conv_unit`, XLA, in the step's type, under a
`jax.checkpoint` that keeps as little. `_conv_unit` is what the tests
hold the kernels to.

The recurrence's output carries the name `RECURRENCE_OUT_NAME`
(`jax.ad_checkpoint.checkpoint_name`): a `jax.checkpoint` whose policy
saves that name (`models/moe_decoder.py`'s does) keeps it, as a flash
kernel's output is kept, and the backward pass then computes the chunks
again once (group of heads by group of heads, `gated_delta_rule`), not
twice.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.keras.engine import Layer
from analytics_zoo_tpu.keras.layers import RMSNormalization, get_init
from analytics_zoo_tpu.pallas.delta_rule import gated_delta_rule
from analytics_zoo_tpu.pallas.short_conv import (short_conv_fits,
                                                 short_conv_rows)
from analytics_zoo_tpu.serving.quantization import maybe_int8_matmul


def causal_depthwise_conv(x, taps):
    """x [B, T, C], taps [K, C] -> [B, T, C]: y_t = sum_i taps[i] *
    x_{t - (K - 1) + i}, with zeros before token 0."""
    K, T = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, i:i + T] * taps[i] for i in range(K))


# the recurrence's output [B * heads, T, dv], for a checkpoint policy to keep
RECURRENCE_OUT_NAME = "linear_attention_recurrence_out"
# added to the squared length of a query or key before its root
_L2_EPS = 1e-6


def _unit(x):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1,
                                       keepdims=True) + _L2_EPS)


class KimiDeltaAttention(Layer):
    """[B, T, hidden] -> [B, T, hidden]; `call` also takes `[x, anything]`
    so that it stands where `keras.transformer.PreNormDecoderBlock` puts
    an attention layer (it has no positions to be told). `head_dim` is the
    width of queries and keys and the low rank of the decay's and the
    output gate's projections, `v_head_dim` the width of values (the same
    unless given), `chunk` the tokens of a chunk of the recurrence: no
    width of the model, it changes no number beyond rounding."""

    def __init__(self, hidden_size: int, n_head: int, head_dim: int,
                 conv_size: int = 4, v_head_dim: Optional[int] = None,
                 rms_eps: float = 1e-5, chunk: int = 64,
                 init="glorot_uniform", interpret: Optional[bool] = None,
                 **kw):
        super().__init__(**kw)
        self.hidden_size, self.n_head = hidden_size, n_head
        self.dk, self.dv = head_dim, v_head_dim or head_dim
        self.conv_size, self.chunk = conv_size, chunk
        self.interpret = interpret
        self.init = get_init(init)
        self.out_norm = RMSNormalization(rms_eps,
                                         name=self.name + "_out_norm")

    def build(self, rng, input_shape=None):
        keys = iter(jax.random.split(rng, 16))
        H, n, f32 = self.hidden_size, self.n_head, jnp.float32
        qk, vv = n * self.dk, n * self.dv

        def taps(width):
            # one 4-tap filter a channel: uniform(+-1/sqrt(taps)), what a
            # depthwise Conv1d of the published code starts from
            bound = 1.0 / math.sqrt(self.conv_size)
            return jax.random.uniform(next(keys), (self.conv_size, width),
                                      f32, -bound, bound)

        # decay rates log-uniform in [1, 16) a head; a step log-uniform in
        # (0.001, 0.1) a channel, stored as its inverse softplus
        step = jnp.exp(jax.random.uniform(next(keys), (qk,), f32,
                                          math.log(1e-3), math.log(1e-1)))
        return {
            "q_kernel": self.init(next(keys), (H, qk), f32),
            "k_kernel": self.init(next(keys), (H, qk), f32),
            "v_kernel": self.init(next(keys), (H, vv), f32),
            "q_conv": taps(qk), "k_conv": taps(qk), "v_conv": taps(vv),
            "decay_a_kernel": self.init(next(keys), (H, self.dk), f32),
            "decay_b_kernel": self.init(next(keys), (self.dk, qk), f32),
            "A_log": jnp.log(jax.random.uniform(next(keys), (n,), f32,
                                                1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "beta_kernel": self.init(next(keys), (H, n), f32),
            "gate_a_kernel": self.init(next(keys), (H, self.dk), f32),
            "gate_b_kernel": self.init(next(keys), (self.dk, vv), f32),
            "gate_bias": jnp.zeros((vv,), f32),
            "out_norm": self.out_norm.build(rng, (None, None, self.dv)),
            "out_kernel": self.init(next(keys), (vv, H), f32),
        }

    def log_decay(self, params, x):
        """g [B, T, heads, dk] float32, <= 0."""
        B, T, _ = x.shape
        low = (x @ params["decay_a_kernel"]).astype(x.dtype)
        raw = jnp.dot(low, params["decay_b_kernel"],
                      preferred_element_type=jnp.float32) \
            + params["dt_bias"].astype(jnp.float32)
        rate = jnp.exp(params["A_log"].astype(jnp.float32))
        return -rate[:, None] * jax.nn.softplus(raw).reshape(
            B, T, self.n_head, self.dk)

    def _rows(self, a):                 # [B, T, n, w] -> [B * n, T, w]
        B, T, n, w = a.shape
        return a.transpose(0, 2, 1, 3).reshape(B * n, T, w)

    def _conv_unit(self, projected, taps, unit_scale):
        """One of q, k, v after its projection [B, T, n * w]: convolution,
        SiLU and, for q and k (`unit_scale` given), the L2 norm times the
        scale; as rows [B * n, T, w] of the recurrence."""
        B, T, _ = projected.shape
        a = jax.nn.silu(causal_depthwise_conv(
            projected, taps.astype(projected.dtype))).reshape(
                B, T, self.n_head, -1)
        if unit_scale is not None:
            a = (_unit(a) * unit_scale).astype(projected.dtype)
        return self._rows(a)

    def _qkv_rows(self, projected, taps, unit_scale):
        """`_conv_unit` by the kernels where they take the projection's
        shape (`short_conv_fits`), in XLA everywhere else."""
        if short_conv_fits(projected.shape, self.n_head, taps.shape[0],
                           self.interpret):
            return short_conv_rows(projected, taps, self.n_head, unit_scale,
                                   _L2_EPS, self.interpret)
        # a checkpoint of its own: a gradient keeps the projection and not
        # the dozen [B, T, n * d] arrays, a third of them float32, between
        # it and the recurrence (the kernels' gradient keeps as little)
        return jax.checkpoint(self._conv_unit, static_argnums=(2,))(
            projected, taps, unit_scale)

    def _gates(self, params, x):
        """(g rows [B * n, T, dk] float32, beta rows [B * n, T] float32)."""
        B, T, _ = x.shape
        beta = jax.nn.sigmoid(jnp.dot(x, params["beta_kernel"],
                                      preferred_element_type=jnp.float32))
        return (self._rows(self.log_decay(params, x)),
                beta.transpose(0, 2, 1).reshape(B * self.n_head, T))

    def _gated_norm(self, params, o, x):
        """o rows [B * n, T, dv], x [B, T, H] -> y [B, T, n * dv]."""
        B, T, _ = x.shape
        n, cdt = self.n_head, x.dtype
        gate = (((x @ params["gate_a_kernel"]).astype(cdt)
                 @ params["gate_b_kernel"]).astype(cdt)
                + params["gate_bias"]).reshape(B, T, n, self.dv)
        o = o.reshape(B, n, T, self.dv).transpose(0, 2, 1, 3)
        y = self.out_norm.call(params["out_norm"], o) \
            * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(cdt)
        return y.reshape(B, T, n * self.dv)

    def call(self, params, x, *, training=False, rng=None):
        if isinstance(x, (list, tuple)):
            x = x[0]
        cdt = x.dtype
        # the elementwise stages are checkpoints of their own: a gradient
        # keeps their inputs (the projections, x, the recurrence's output)
        with jax.named_scope("kda/qkv_proj"):
            q = maybe_int8_matmul(x, params, "q_kernel").astype(cdt)
            k = maybe_int8_matmul(x, params, "k_kernel").astype(cdt)
            v = maybe_int8_matmul(x, params, "v_kernel").astype(cdt)
        with jax.named_scope("kda/short_conv"):
            q = self._qkv_rows(q, params["q_conv"], self.dk ** -0.5)
            k = self._qkv_rows(k, params["k_conv"], 1.0)
            v = self._qkv_rows(v, params["v_conv"], None)
        with jax.named_scope("kda/gates"):
            g, beta = jax.checkpoint(self._gates)(params, x)
        o = checkpoint_name(
            gated_delta_rule(q, k, v, g, beta, chunk=self.chunk,
                             interpret=self.interpret), RECURRENCE_OUT_NAME)
        with jax.named_scope("kda/out_gate_norm"):
            y = jax.checkpoint(self._gated_norm)(params, o, x)
        with jax.named_scope("kda/out_proj"):
            return maybe_int8_matmul(y, params, "out_kernel").astype(cdt)

    def compute_output_shape(self, input_shape):
        return input_shape[0] if isinstance(input_shape, list) \
            else input_shape
