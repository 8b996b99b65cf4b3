"""LFM2's gated short-convolution mixer as a Keras-style layer: a token
mixer with no attention and no state but the last K - 1 tokens.

    [B | C | x] = u W_in            W_in [H, 3H], split in this order
    v_t = B_t * x_t
    c_t = sum_i taps[i] * v_{t - (K - 1) + i}     causal, depthwise (one
                                                  filter of K taps a
                                                  channel), zero history
                                                  before token 0, no bias
    y_t = C_t * c_t
    out = y W_out                   W_out [H, H]

No bias, no activation, no norm anywhere (the published `Lfm2ShortConv`,
`conv_L_cache` K = 3, `conv_bias` false).

The stage between the projections runs on one of two paths, and the input
decides which (no option, no environment variable): on the TPU (or under
`interpret=True`), with channels in whole lane tiles and a sequence of
whole tiles of 16 tokens, the kernels `gated_conv_fwd` / `gated_conv_bwd`
of `pallas/gated_conv.py`, which filter and gate in float32 with a tile of
tokens in VMEM and keep the in-projection's output and the taps alone for
the gradient; off the TPU and for every other shape `gated_filter`, XLA,
in float32 and back to the step's type, under a `jax.checkpoint` that
keeps as little. `gated_filter` is what the tests hold the kernels to.

The training path starts every sequence from zero history and returns
none; the K - 1 tokens a sequence and layer that serving would carry
between steps are a cache entry of their own kind (ROADMAP M6).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.engine import Layer
from analytics_zoo_tpu.keras.layers import get_init
from analytics_zoo_tpu.keras.linear_attention import causal_depthwise_conv
from analytics_zoo_tpu.pallas.gated_conv import gated_conv, gated_conv_fits
from analytics_zoo_tpu.serving.quantization import maybe_int8_matmul


def gated_filter(bcx, taps):
    """bcx [B, T, 3C] = [B | C | x], taps [K, C] -> C * conv(B * x)
    [B, T, C] in bcx's type, computed in float32: the kernels' oracle."""
    f32 = jnp.float32
    b, c, x = jnp.split(bcx.astype(f32), 3, axis=-1)
    return (c * causal_depthwise_conv(b * x, taps.astype(f32))).astype(
        bcx.dtype)


class GatedShortConv(Layer):
    """[B, T, hidden] -> [B, T, hidden]; `call` also takes `[x, anything]`
    so that it stands where `keras.transformer.PreNormDecoderBlock` puts an
    attention layer (it has no positions to be told)."""

    def __init__(self, hidden_size: int, conv_size: int = 3,
                 init="glorot_uniform", interpret: Optional[bool] = None,
                 **kw):
        super().__init__(**kw)
        self.hidden_size, self.conv_size = hidden_size, conv_size
        self.interpret = interpret
        self.init = get_init(init)

    def build(self, rng, input_shape=None):
        k_in, k_conv, k_out = jax.random.split(rng, 3)
        H, f32 = self.hidden_size, jnp.float32
        # one K-tap filter a channel: uniform(+-1/sqrt(K)), what a
        # depthwise Conv1d of the published code starts from
        bound = 1.0 / math.sqrt(self.conv_size)
        return {
            "in_kernel": self.init(k_in, (H, 3 * H), f32),
            "conv": jax.random.uniform(k_conv, (self.conv_size, H), f32,
                                       -bound, bound),
            "out_kernel": self.init(k_out, (H, H), f32),
        }

    def filter(self, bcx, taps):
        """`gated_filter` by the kernels where they take the shape, in
        XLA everywhere else."""
        if gated_conv_fits(bcx.shape, taps.shape[0], self.interpret):
            return gated_conv(bcx, taps, self.interpret)
        # a checkpoint of its own: a gradient keeps the projection and not
        # the float32 arrays between it and y (the kernels keep as little)
        return jax.checkpoint(gated_filter)(bcx, taps)

    def call(self, params, x, *, training=False, rng=None):
        if isinstance(x, (list, tuple)):
            x = x[0]
        with jax.named_scope("conv/in_proj"):
            bcx = maybe_int8_matmul(x, params, "in_kernel").astype(x.dtype)
        with jax.named_scope("conv/gated_filter"):
            y = self.filter(bcx, params["conv"])
        with jax.named_scope("conv/out_proj"):
            return maybe_int8_matmul(y, params, "out_kernel").astype(x.dtype)

    def compute_output_shape(self, input_shape):
        return input_shape[0] if isinstance(input_shape, list) \
            else input_shape
