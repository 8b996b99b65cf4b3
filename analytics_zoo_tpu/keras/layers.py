"""Keras-style layer library on raw jax.lax/jax.nn.

TPU-native re-design of the reference's Keras1 layer set
(`zoo/.../pipeline/api/keras/layers/*.scala`, ~130 layers; python mirror
`pyzoo/zoo/pipeline/api/keras/layers/`). Layers are pure: `build` returns a
parameter pytree, `call` is a jax-traceable function — the whole model fuses
into one XLA program instead of the reference's per-layer JVM graph walk.

Shape conventions: channels_last (NHWC / NWC) is the default — it is the
layout the TPU MXU wants — with `dim_ordering="th"` accepted for source
compatibility and transposed on the fly. `input_shape` excludes the batch dim.
Weight init follows Keras: glorot_uniform kernels, orthogonal recurrent
kernels, zero biases.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.keras.engine import Layer, Params, Shape
from analytics_zoo_tpu.pallas.dropout import fused_dropout

# ---------------------------------------------------------------------------
# Initializers & activations
# ---------------------------------------------------------------------------
_INITS = {
    "glorot_uniform": jax.nn.initializers.glorot_uniform(),
    "glorot_normal": jax.nn.initializers.glorot_normal(),
    "he_normal": jax.nn.initializers.he_normal(),
    "he_uniform": jax.nn.initializers.he_uniform(),
    "lecun_normal": jax.nn.initializers.lecun_normal(),
    "orthogonal": jax.nn.initializers.orthogonal(),
    "zeros": jax.nn.initializers.zeros,
    "ones": jax.nn.initializers.ones,
    "uniform": jax.nn.initializers.uniform(0.05),
    "normal": jax.nn.initializers.normal(0.05),
}


def get_init(name_or_fn) -> Callable:
    if callable(name_or_fn):
        return name_or_fn
    key = str(name_or_fn).lower()
    if key not in _INITS:
        raise ValueError(f"Unsupported initializer: {name_or_fn}")
    return _INITS[key]


_ACTIVATIONS: Dict[str, Callable] = {
    "relu": jax.nn.relu,
    "relu6": jax.nn.relu6,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "hard_sigmoid": jax.nn.hard_sigmoid,
    "softmax": lambda x: jax.nn.softmax(x, axis=-1),
    "log_softmax": lambda x: jax.nn.log_softmax(x, axis=-1),
    "softplus": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "elu": jax.nn.elu,
    "selu": jax.nn.selu,
    "gelu": jax.nn.gelu,
    "swish": jax.nn.silu,
    "silu": jax.nn.silu,
    "leaky_relu": jax.nn.leaky_relu,
    "linear": lambda x: x,
}


def get_activation(name_or_fn) -> Callable:
    if name_or_fn is None:
        return lambda x: x
    if callable(name_or_fn):
        return name_or_fn
    key = str(name_or_fn).lower()
    if key not in _ACTIVATIONS:
        raise ValueError(f"Unsupported activation: {name_or_fn}")
    return _ACTIVATIONS[key]


def _match_param_dtype(x, ref):
    """Float operands follow the parameter dtype so mixed-precision (bf16)
    params see matching MXU operands. Integer inputs pass through untouched
    — casting float-encoded ids to bf16 silently corrupts values > 256."""
    if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != ref.dtype:
        return x.astype(ref.dtype)
    return x


# ---------------------------------------------------------------------------
# Core layers
# ---------------------------------------------------------------------------
class Dense(Layer):
    """`keras/layers/Dense.scala`. Applies to the last axis (any rank)."""

    def __init__(self, output_dim: int, activation=None, use_bias: bool = True,
                 init="glorot_uniform", W_regularizer=None, b_regularizer=None,
                 **kw):
        super().__init__(**kw)
        self.output_dim = output_dim
        self.activation = get_activation(activation)
        self.use_bias = use_bias
        self.init = get_init(init)

    def build(self, rng, input_shape):
        in_dim = input_shape[-1]
        kernel = self.init(rng, (in_dim, self.output_dim), jnp.float32)
        p = {"kernel": kernel}
        if self.use_bias:
            p["bias"] = jnp.zeros((self.output_dim,), jnp.float32)
        return p

    def call(self, params, x, *, training=False, rng=None):
        if "kernel_q" in params:   # int8 serving path (serving/quantization)
            from analytics_zoo_tpu.serving.quantization import int8_matmul
            y = int8_matmul(x, params["kernel_q"], params["kernel_scale"])
        else:
            x = _match_param_dtype(x, params["kernel"])
            y = x @ params["kernel"]
        if self.use_bias:
            y = y + params["bias"]
        return self.activation(y)

    def compute_output_shape(self, input_shape):
        return tuple(input_shape[:-1]) + (self.output_dim,)


class Activation(Layer):
    def __init__(self, activation, **kw):
        super().__init__(**kw)
        self.activation = get_activation(activation)

    def call(self, params, x, *, training=False, rng=None):
        return self.activation(x)


class Dropout(Layer):
    """`keras/layers/Dropout.scala`: inverted dropout, active only in
    training."""

    def __init__(self, p: float, **kw):
        super().__init__(**kw)
        self.rate = float(p)

    def call(self, params, x, *, training=False, rng=None):
        if not training or self.rate <= 0.0:
            return x
        if rng is None:
            raise ValueError(f"{self.name}: dropout in training needs an rng")
        return fused_dropout(x, self.rate, rng=rng)


class Flatten(Layer):
    def call(self, params, x, *, training=False, rng=None):
        return x.reshape((x.shape[0], -1))

    def compute_output_shape(self, input_shape):
        return (input_shape[0], int(np.prod([d for d in input_shape[1:]])))


class Reshape(Layer):
    """`keras/layers/Reshape.scala`: target shape excludes batch; one -1
    allowed."""

    def __init__(self, target_shape: Sequence[int], **kw):
        super().__init__(**kw)
        self.target_shape = tuple(target_shape)

    def call(self, params, x, *, training=False, rng=None):
        return x.reshape((x.shape[0],) + self.target_shape)

    def compute_output_shape(self, input_shape):
        known = int(np.prod([d for d in input_shape[1:]]))
        tgt = list(self.target_shape)
        if -1 in tgt:
            fill = known // int(-np.prod(tgt))
            tgt[tgt.index(-1)] = fill
        return (input_shape[0],) + tuple(tgt)


class Permute(Layer):
    """Dims are 1-indexed over non-batch axes (Keras contract)."""

    def __init__(self, dims: Sequence[int], **kw):
        super().__init__(**kw)
        self.dims = tuple(dims)

    def call(self, params, x, *, training=False, rng=None):
        return jnp.transpose(x, (0,) + self.dims)

    def compute_output_shape(self, input_shape):
        return (input_shape[0],) + tuple(input_shape[d] for d in self.dims)


class RepeatVector(Layer):
    def __init__(self, n: int, **kw):
        super().__init__(**kw)
        self.n = n

    def call(self, params, x, *, training=False, rng=None):
        return jnp.repeat(x[:, None, :], self.n, axis=1)

    def compute_output_shape(self, input_shape):
        return (input_shape[0], self.n, input_shape[1])


class Squeeze(Layer):
    """BigDL-style utility (`keras/layers/Squeeze.scala`); dim excludes
    batch (1-indexed over non-batch axes)."""

    def __init__(self, dim: int, **kw):
        super().__init__(**kw)
        self.dim = dim

    def call(self, params, x, *, training=False, rng=None):
        return jnp.squeeze(x, axis=self.dim)

    def compute_output_shape(self, input_shape):
        s = list(input_shape)
        del s[self.dim]
        return tuple(s)


class ExpandDim(Layer):
    def __init__(self, dim: int, **kw):
        super().__init__(**kw)
        self.dim = dim

    def call(self, params, x, *, training=False, rng=None):
        return jnp.expand_dims(x, axis=self.dim)

    def compute_output_shape(self, input_shape):
        s = list(input_shape)
        s.insert(self.dim, 1)
        return tuple(s)


class Select(Layer):
    """`keras/layers/Select.scala`: pick index `index` along `dim`."""

    def __init__(self, dim: int, index: int, **kw):
        super().__init__(**kw)
        self.dim, self.index = dim, index

    def call(self, params, x, *, training=False, rng=None):
        return jnp.take(x, self.index, axis=self.dim)

    def compute_output_shape(self, input_shape):
        s = list(input_shape)
        del s[self.dim]
        return tuple(s)


class Narrow(Layer):
    """`keras/layers/Narrow.scala`: slice `length` elements from `offset`
    along `dim`."""

    def __init__(self, dim: int, offset: int, length: int = 1, **kw):
        super().__init__(**kw)
        self.dim, self.offset, self.length = dim, offset, length

    def call(self, params, x, *, training=False, rng=None):
        return jax.lax.slice_in_dim(x, self.offset, self.offset + self.length,
                                    axis=self.dim)

    def compute_output_shape(self, input_shape):
        s = list(input_shape)
        s[self.dim] = self.length
        return tuple(s)


class Merge(Layer):
    """`keras/layers/Merge.scala`: combine a list of inputs.
    mode ∈ {sum, mul, ave, max, concat, dot, cos}."""

    def __init__(self, mode: str = "sum", concat_axis: int = -1, **kw):
        super().__init__(**kw)
        self.mode = mode
        self.concat_axis = concat_axis

    def call(self, params, xs, *, training=False, rng=None):
        if self.mode == "sum":
            out = xs[0]
            for x in xs[1:]:
                out = out + x
            return out
        if self.mode == "mul":
            out = xs[0]
            for x in xs[1:]:
                out = out * x
            return out
        if self.mode == "ave":
            return sum(xs) / len(xs)
        if self.mode == "max":
            out = xs[0]
            for x in xs[1:]:
                out = jnp.maximum(out, x)
            return out
        if self.mode == "concat":
            return jnp.concatenate(xs, axis=self.concat_axis)
        if self.mode == "dot":
            a, b = xs
            return jnp.sum(a * b, axis=-1, keepdims=True)
        if self.mode == "cos":
            a, b = xs
            an = a / jnp.clip(jnp.linalg.norm(a, axis=-1, keepdims=True),
                              1e-7, None)
            bn = b / jnp.clip(jnp.linalg.norm(b, axis=-1, keepdims=True),
                              1e-7, None)
            return jnp.sum(an * bn, axis=-1, keepdims=True)
        raise ValueError(f"Unsupported merge mode: {self.mode}")

    def compute_output_shape(self, input_shapes):
        if self.mode in ("sum", "mul", "ave", "max"):
            return input_shapes[0]
        if self.mode == "concat":
            out = list(input_shapes[0])
            axis = self.concat_axis
            out[axis] = sum(s[axis] for s in input_shapes)
            return tuple(out)
        if self.mode in ("dot", "cos"):
            return (input_shapes[0][0], 1)
        raise ValueError(f"Unsupported merge mode: {self.mode}")


def merge(inputs, mode="sum", concat_axis=-1, name=None):
    """Functional helper matching pyzoo's `merge`
    (`keras/layers/topology.py`)."""
    return Merge(mode=mode, concat_axis=concat_axis, name=name)(inputs)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------
class Embedding(Layer):
    """`keras/layers/Embedding.scala`: int ids → dense vectors. On TPU the
    lookup is a one-hot matmul for tiny vocabs or a gather for large ones —
    XLA picks; weights live f32, output follows compute dtype upstream."""

    def __init__(self, input_dim: int, output_dim: int, init="uniform",
                 weights: Optional[np.ndarray] = None, trainable: bool = True,
                 **kw):
        super().__init__(**kw)
        self.input_dim, self.output_dim = input_dim, output_dim
        self.init = get_init(init)
        self.weights = weights
        self.trainable = trainable

    def build(self, rng, input_shape):
        if self.weights is not None:
            table = jnp.asarray(self.weights, jnp.float32)
            if table.shape != (self.input_dim, self.output_dim):
                raise ValueError(
                    f"{self.name}: pretrained weights shape {table.shape} != "
                    f"({self.input_dim}, {self.output_dim})")
        else:
            table = self.init(rng, (self.input_dim, self.output_dim),
                              jnp.float32)
        return {"embeddings": table}

    def call(self, params, x, *, training=False, rng=None):
        ids = jnp.asarray(x, jnp.int32)
        if "embeddings_q" in params:   # int8 serving path
            from analytics_zoo_tpu.serving.quantization import \
                dequantize_rows
            return dequantize_rows(params["embeddings_q"],
                                   params["embeddings_scale"], ids)
        table = params["embeddings"]
        if not self.trainable:
            table = jax.lax.stop_gradient(table)
        return jnp.take(table, ids, axis=0)

    def compute_output_shape(self, input_shape):
        return tuple(input_shape) + (self.output_dim,)


class WordEmbedding(Embedding):
    """`keras/layers/WordEmbedding.scala`: frozen pretrained embeddings."""

    def __init__(self, embedding_matrix: np.ndarray, **kw):
        vocab, dim = np.shape(embedding_matrix)
        super().__init__(vocab, dim, weights=np.asarray(embedding_matrix),
                         trainable=False, **kw)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------
class BatchNormalization(Layer):
    """`keras/layers/BatchNormalization.scala`. Moving stats are non-gradient
    state: training steps receive them back through `call_and_state` and the
    trainer merges them into params (outside the gradient path)."""

    stateful = True

    def __init__(self, epsilon: float = 1e-3, momentum: float = 0.99,
                 axis: int = -1, **kw):
        super().__init__(**kw)
        self.epsilon, self.momentum, self.axis = epsilon, momentum, axis

    def build(self, rng, input_shape):
        dim = input_shape[self.axis]
        return {"gamma": jnp.ones((dim,), jnp.float32),
                "beta": jnp.zeros((dim,), jnp.float32),
                "moving_mean": jnp.zeros((dim,), jnp.float32),
                "moving_var": jnp.ones((dim,), jnp.float32)}

    def _norm_axis(self, ndim):
        return ndim - 1 if self.axis == -1 else self.axis

    def _reshape_stat(self, s, ndim):
        """Broadcast (C,) stats against the normalized axis wherever it is."""
        shape = [1] * ndim
        shape[self._norm_axis(ndim)] = -1
        return s.reshape(shape)

    def _stats(self, params, x, training):
        axis = self._norm_axis(jnp.ndim(x))
        reduce_axes = tuple(i for i in range(jnp.ndim(x)) if i != axis)
        if training:
            mean = jnp.mean(x, axis=reduce_axes)
            var = jnp.var(x, axis=reduce_axes)
        else:
            mean, var = params["moving_mean"], params["moving_var"]
        return mean, var

    def _apply(self, params, x, mean, var):
        nd = jnp.ndim(x)
        inv = jax.lax.rsqrt(self._reshape_stat(var, nd) + self.epsilon)
        return ((x - self._reshape_stat(mean, nd)) * inv
                * self._reshape_stat(params["gamma"], nd)
                + self._reshape_stat(params["beta"], nd))

    def call(self, params, x, *, training=False, rng=None):
        mean, var = self._stats(params, x, training)
        return self._apply(params, x, mean, var)

    def call_and_state(self, params, x, *, training=False, rng=None):
        mean, var = self._stats(params, x, training)
        y = self._apply(params, x, mean, var)
        if not training:
            return y, {}
        m = self.momentum
        updates = {
            "moving_mean": m * params["moving_mean"]
            + (1.0 - m) * jax.lax.stop_gradient(mean),
            "moving_var": m * params["moving_var"]
            + (1.0 - m) * jax.lax.stop_gradient(var),
        }
        return y, updates


class LayerNormalization(Layer):
    """BERT-style layer norm over the last axis (`TransformerLayer.scala`
    LayerNorm)."""

    def __init__(self, epsilon: float = 1e-12, **kw):
        super().__init__(**kw)
        self.epsilon = epsilon

    def build(self, rng, input_shape):
        dim = input_shape[-1]
        return {"gamma": jnp.ones((dim,), jnp.float32),
                "beta": jnp.zeros((dim,), jnp.float32)}

    def call(self, params, x, *, training=False, rng=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.epsilon)
        return y * params["gamma"] + params["beta"]


class RMSNormalization(Layer):
    """Root-mean-square norm over the last axis, scale only:
    `x / sqrt(mean(x^2) + eps) * gamma` (Zhang & Sennrich 2019; the norm
    of today's decoder blocks). The statistic is taken in float32 whatever
    the input's type, and the result goes back to the input's type."""

    def __init__(self, epsilon: float = 1e-6, **kw):
        super().__init__(**kw)
        self.epsilon = epsilon

    def build(self, rng, input_shape):
        return {"gamma": jnp.ones((input_shape[-1],), jnp.float32)}

    def call(self, params, x, *, training=False, rng=None):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
        return y.astype(x.dtype) * params["gamma"]


# ---------------------------------------------------------------------------
# Convolutions & pooling (channels_last native)
# ---------------------------------------------------------------------------
def _to_channels_last(x, dim_ordering, spatial_rank):
    if dim_ordering == "th":
        perm = (0,) + tuple(range(2, 2 + spatial_rank)) + (1,)
        return jnp.transpose(x, perm)
    return x


def _from_channels_last(x, dim_ordering, spatial_rank):
    if dim_ordering == "th":
        perm = (0, spatial_rank + 1) + tuple(range(1, spatial_rank + 1))
        return jnp.transpose(x, perm)
    return x


class _ConvND(Layer):
    spatial_rank = 2
    dn = ("NHWC", "HWIO", "NHWC")

    def __init__(self, nb_filter: int, kernel_size: Sequence[int],
                 activation=None, subsample: Sequence[int] = None,
                 border_mode: str = "valid", dim_ordering: str = "tf",
                 use_bias: bool = True, init="glorot_uniform",
                 groups: int = 1, **kw):
        super().__init__(**kw)
        self.nb_filter = nb_filter
        self.kernel_size = tuple(kernel_size)
        self.activation = get_activation(activation)
        self.strides = tuple(subsample or (1,) * self.spatial_rank)
        if border_mode not in ("valid", "same"):
            raise ValueError(f"Unsupported border_mode: {border_mode}")
        self.padding = border_mode.upper()
        self.dim_ordering = dim_ordering
        self.use_bias = use_bias
        self.init = get_init(init)
        self.groups = int(groups)

    def build(self, rng, input_shape):
        if self.dim_ordering == "th":
            in_ch = input_shape[1]
        else:
            in_ch = input_shape[-1]
        if in_ch % self.groups or self.nb_filter % self.groups:
            raise ValueError(
                f"groups={self.groups} must divide in_ch={in_ch} and "
                f"nb_filter={self.nb_filter}")
        kshape = self.kernel_size + (in_ch // self.groups, self.nb_filter)
        p = {"kernel": self.init(rng, kshape, jnp.float32)}
        if self.use_bias:
            p["bias"] = jnp.zeros((self.nb_filter,), jnp.float32)
        return p

    def call(self, params, x, *, training=False, rng=None):
        x = _to_channels_last(x, self.dim_ordering, self.spatial_rank)
        if "kernel_q" in params:   # int8 serving path (serving/quantization)
            from analytics_zoo_tpu.serving.quantization import int8_conv
            y = int8_conv(x, params["kernel_q"], params["kernel_scale"],
                          window_strides=self.strides,
                          padding=self.padding, dimension_numbers=self.dn,
                          feature_group_count=self.groups)
        else:
            # conv requires matching operand dtypes; float inputs follow
            # the kernel (under mixed precision the params are bf16 while
            # e.g. an on-device normalization Lambda produces f32).
            # Integer inputs still error loudly — silently casting raw
            # uint8 images would train on unscaled 0-255 values.
            x = _match_param_dtype(x, params["kernel"])
            y = jax.lax.conv_general_dilated(
                x, params["kernel"], window_strides=self.strides,
                padding=self.padding, dimension_numbers=self.dn,
                feature_group_count=self.groups)
        if self.use_bias:
            y = y + params["bias"]
        y = self.activation(y)
        return _from_channels_last(y, self.dim_ordering, self.spatial_rank)

    def _spatial_out(self, size, k, s):
        if size is None:
            return None
        if self.padding == "SAME":
            return -(-size // s)
        return (size - k) // s + 1

    def compute_output_shape(self, input_shape):
        if self.dim_ordering == "th":
            spatial = input_shape[2:]
            out = tuple(self._spatial_out(d, k, s) for d, k, s in
                        zip(spatial, self.kernel_size, self.strides))
            return (input_shape[0], self.nb_filter) + out
        spatial = input_shape[1:-1]
        out = tuple(self._spatial_out(d, k, s) for d, k, s in
                    zip(spatial, self.kernel_size, self.strides))
        return (input_shape[0],) + out + (self.nb_filter,)


class Convolution2D(_ConvND):
    """`keras/layers/Convolution2D.scala`."""

    def __init__(self, nb_filter, nb_row, nb_col, **kw):
        super().__init__(nb_filter, (nb_row, nb_col), **kw)


class Convolution1D(_ConvND):
    spatial_rank = 1
    dn = ("NWC", "WIO", "NWC")

    def __init__(self, nb_filter, filter_length, **kw):
        super().__init__(nb_filter, (filter_length,), **kw)


class Convolution3D(_ConvND):
    spatial_rank = 3
    dn = ("NDHWC", "DHWIO", "NDHWC")

    def __init__(self, nb_filter, kernel_dim1, kernel_dim2, kernel_dim3, **kw):
        super().__init__(nb_filter, (kernel_dim1, kernel_dim2, kernel_dim3),
                         **kw)


# keras2-flavoured aliases (`keras2/layers/`)
Conv1D = Convolution1D
Conv2D = Convolution2D
Conv3D = Convolution3D


class _PoolND(Layer):
    spatial_rank = 2
    reducer = "max"

    def __init__(self, pool_size=None, strides=None, border_mode="valid",
                 dim_ordering="tf", **kw):
        super().__init__(**kw)
        self.pool_size = tuple(pool_size or (2,) * self.spatial_rank)
        self.strides = tuple(strides or self.pool_size)
        self.padding = border_mode.upper()
        self.dim_ordering = dim_ordering

    def call(self, params, x, *, training=False, rng=None):
        x = _to_channels_last(x, self.dim_ordering, self.spatial_rank)
        window = (1,) + self.pool_size + (1,)
        strides = (1,) + self.strides + (1,)
        if self.reducer == "max":
            y = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window,
                                      strides, self.padding)
        else:
            ones = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                         window, strides, self.padding)
            y = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides,
                                      self.padding) / ones
        return _from_channels_last(y, self.dim_ordering, self.spatial_rank)

    def _spatial_out(self, size, k, s):
        if size is None:
            return None
        if self.padding == "SAME":
            return -(-size // s)
        return (size - k) // s + 1

    def compute_output_shape(self, input_shape):
        if self.dim_ordering == "th":
            spatial = input_shape[2:]
            out = tuple(self._spatial_out(d, k, s) for d, k, s in
                        zip(spatial, self.pool_size, self.strides))
            return input_shape[:2] + out
        spatial = input_shape[1:-1]
        out = tuple(self._spatial_out(d, k, s) for d, k, s in
                    zip(spatial, self.pool_size, self.strides))
        return (input_shape[0],) + out + (input_shape[-1],)


class MaxPooling2D(_PoolND):
    pass


class AveragePooling2D(_PoolND):
    reducer = "avg"


class MaxPooling1D(_PoolND):
    spatial_rank = 1

    def __init__(self, pool_length: int = 2, stride: Optional[int] = None,
                 **kw):
        super().__init__((pool_length,),
                         (stride,) if stride else None, **kw)


class AveragePooling1D(MaxPooling1D):
    reducer = "avg"


class _GlobalPool(Layer):
    spatial_axes: Tuple[int, ...] = (1, 2)
    reducer = "max"

    def __init__(self, dim_ordering="tf", **kw):
        super().__init__(**kw)
        self.dim_ordering = dim_ordering

    def call(self, params, x, *, training=False, rng=None):
        axes = self.spatial_axes if self.dim_ordering == "tf" else \
            tuple(a + 1 for a in self.spatial_axes)
        fn = jnp.max if self.reducer == "max" else jnp.mean
        return fn(x, axis=axes)

    def compute_output_shape(self, input_shape):
        if self.dim_ordering == "tf":
            return (input_shape[0], input_shape[-1])
        return (input_shape[0], input_shape[1])


class GlobalMaxPooling2D(_GlobalPool):
    pass


class GlobalAveragePooling2D(_GlobalPool):
    reducer = "avg"


class GlobalMaxPooling1D(_GlobalPool):
    spatial_axes = (1,)


class GlobalAveragePooling1D(_GlobalPool):
    spatial_axes = (1,)
    reducer = "avg"


class ZeroPadding2D(Layer):
    def __init__(self, padding=(1, 1), dim_ordering="tf", **kw):
        super().__init__(**kw)
        self.pad = tuple(padding)
        self.dim_ordering = dim_ordering

    def call(self, params, x, *, training=False, rng=None):
        ph, pw = self.pad
        if self.dim_ordering == "tf":
            return jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
        return jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)))

    def compute_output_shape(self, input_shape):
        s = list(input_shape)
        if self.dim_ordering == "tf":
            s[1] += 2 * self.pad[0]; s[2] += 2 * self.pad[1]
        else:
            s[2] += 2 * self.pad[0]; s[3] += 2 * self.pad[1]
        return tuple(s)


class UpSampling2D(Layer):
    def __init__(self, size=(2, 2), dim_ordering="tf", **kw):
        super().__init__(**kw)
        self.size = tuple(size)
        self.dim_ordering = dim_ordering

    def call(self, params, x, *, training=False, rng=None):
        sh, sw = self.size
        if self.dim_ordering == "tf":
            return jnp.repeat(jnp.repeat(x, sh, axis=1), sw, axis=2)
        return jnp.repeat(jnp.repeat(x, sh, axis=2), sw, axis=3)

    def compute_output_shape(self, input_shape):
        s = list(input_shape)
        if self.dim_ordering == "tf":
            s[1] *= self.size[0]; s[2] *= self.size[1]
        else:
            s[2] *= self.size[0]; s[3] *= self.size[1]
        return tuple(s)


# ---------------------------------------------------------------------------
# Recurrent layers — lax.scan over time; weights packed per-gate for one
# fused matmul per step (MXU-friendly), unlike the reference's per-gate JVM
# tensor ops (`keras/layers/LSTM.scala`, `GRU.scala`, `SimpleRNN.scala`).
# ---------------------------------------------------------------------------
class _Recurrent(Layer):
    n_gates = 1

    def __init__(self, output_dim: int, activation="tanh",
                 inner_activation="hard_sigmoid", return_sequences=False,
                 go_backwards=False, init="glorot_uniform",
                 inner_init="orthogonal", **kw):
        super().__init__(**kw)
        self.output_dim = output_dim
        self.activation = get_activation(activation)
        self.inner_activation = get_activation(inner_activation)
        self.return_sequences = return_sequences
        self.go_backwards = go_backwards
        self.init = get_init(init)
        self.inner_init = get_init(inner_init)

    def build(self, rng, input_shape):
        in_dim = input_shape[-1]
        k1, k2 = jax.random.split(rng)
        return {
            "kernel": self.init(
                k1, (in_dim, self.n_gates * self.output_dim), jnp.float32),
            "recurrent": self.inner_init(
                k2, (self.output_dim, self.n_gates * self.output_dim),
                jnp.float32),
            "bias": jnp.zeros((self.n_gates * self.output_dim,), jnp.float32),
        }

    def initial_state(self, batch):
        return jnp.zeros((batch, self.output_dim), jnp.float32)

    def step(self, params, carry, x_t):
        raise NotImplementedError

    def call(self, params, x, *, training=False, rng=None):
        if self.go_backwards:
            x = jnp.flip(x, axis=1)
        x = _match_param_dtype(x, params["kernel"])
        batch = x.shape[0]
        xs = jnp.swapaxes(x, 0, 1)  # [T, B, F] for scan

        def body(carry, x_t):
            carry, out = self.step(params, carry, x_t)
            return carry, out

        carry0 = self.initial_state(batch)
        # carry must match the step output dtype for scan (bf16 params →
        # bf16 hidden state)
        carry0 = jax.tree_util.tree_map(
            lambda a: a.astype(params["kernel"].dtype), carry0)
        _, outs = jax.lax.scan(body, carry0, xs)
        if self.return_sequences:
            seq = jnp.swapaxes(outs, 0, 1)
            return jnp.flip(seq, axis=1) if self.go_backwards else seq
        return outs[-1]

    def compute_output_shape(self, input_shape):
        if self.return_sequences:
            return (input_shape[0], input_shape[1], self.output_dim)
        return (input_shape[0], self.output_dim)


class SimpleRNN(_Recurrent):
    n_gates = 1

    def step(self, params, h, x_t):
        h_new = self.activation(
            x_t @ params["kernel"] + h @ params["recurrent"] + params["bias"])
        return h_new, h_new


class LSTM(_Recurrent):
    """Gate order i, f, c, o (Keras convention)."""
    n_gates = 4

    def initial_state(self, batch):
        z = jnp.zeros((batch, self.output_dim), jnp.float32)
        return (z, z)

    def step(self, params, carry, x_t):
        h, c = carry
        z = x_t @ params["kernel"] + h @ params["recurrent"] + params["bias"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        i = self.inner_activation(i)
        f = self.inner_activation(f)
        o = self.inner_activation(o)
        g = self.activation(g)
        c_new = f * c + i * g
        h_new = o * self.activation(c_new)
        return (h_new, c_new), h_new


class GRU(_Recurrent):
    """Gate order z, r, h (Keras convention). `reset_after=True` applies the
    recurrent bias inside the reset gate product (torch/CuDNN semantics),
    needed for exact torch-weight conversion."""
    n_gates = 3

    def __init__(self, *args, reset_after: bool = False, **kw):
        super().__init__(*args, **kw)
        self.reset_after = reset_after

    def build(self, rng, input_shape):
        p = super().build(rng, input_shape)
        if self.reset_after:
            p["recurrent_bias"] = jnp.zeros(
                (self.n_gates * self.output_dim,), jnp.float32)
        return p

    def step(self, params, h, x_t):
        d = self.output_dim
        xz = x_t @ params["kernel"] + params["bias"]
        hz = h @ params["recurrent"]
        if self.reset_after:
            hz = hz + params["recurrent_bias"]
        z = self.inner_activation(xz[:, :d] + hz[:, :d])
        r = self.inner_activation(xz[:, d:2 * d] + hz[:, d:2 * d])
        hh = self.activation(xz[:, 2 * d:] + r * hz[:, 2 * d:])
        h_new = z * h + (1.0 - z) * hh
        return h_new, h_new


class Bidirectional(Layer):
    """`keras/layers/Bidirectional.scala`: wraps a recurrent layer;
    merge_mode ∈ {concat, sum, mul, ave}."""

    def __init__(self, layer: _Recurrent, merge_mode: str = "concat", **kw):
        super().__init__(**kw)
        import copy
        self.forward = layer
        self.backward = copy.deepcopy(layer)
        self.backward.name = layer.name + "_bwd"
        self.backward.go_backwards = not layer.go_backwards
        self.merge_mode = merge_mode

    def build(self, rng, input_shape):
        k1, k2 = jax.random.split(rng)
        return {"forward": self.forward.build(k1, input_shape),
                "backward": self.backward.build(k2, input_shape)}

    def call(self, params, x, *, training=False, rng=None):
        f = self.forward.call(params["forward"], x, training=training)
        b = self.backward.call(params["backward"], x, training=training)
        if self.merge_mode == "concat":
            return jnp.concatenate([f, b], axis=-1)
        if self.merge_mode == "sum":
            return f + b
        if self.merge_mode == "mul":
            return f * b
        if self.merge_mode == "ave":
            return (f + b) / 2.0
        raise ValueError(f"Unsupported merge_mode: {self.merge_mode}")

    def compute_output_shape(self, input_shape):
        out = list(self.forward.compute_output_shape(input_shape))
        if self.merge_mode == "concat":
            out[-1] *= 2
        return tuple(out)


class TimeDistributed(Layer):
    """Apply an inner layer to every timestep (`keras/layers/
    TimeDistributed.scala`). Implemented by folding time into batch — one big
    matmul instead of T small ones."""

    def __init__(self, layer: Layer, **kw):
        super().__init__(**kw)
        self.layer = layer

    def build(self, rng, input_shape):
        inner_shape = (input_shape[0],) + tuple(input_shape[2:])
        return self.layer.build(rng, inner_shape)

    def call(self, params, x, *, training=False, rng=None):
        b, t = x.shape[0], x.shape[1]
        flat = x.reshape((b * t,) + x.shape[2:])
        y = self.layer.call(params, flat, training=training, rng=rng)
        return y.reshape((b, t) + y.shape[1:])

    def compute_output_shape(self, input_shape):
        inner_shape = (input_shape[0],) + tuple(input_shape[2:])
        inner_out = self.layer.compute_output_shape(inner_shape)
        return (input_shape[0], input_shape[1]) + tuple(inner_out[1:])


# `LayerNorm.scala` exposes layer normalization under this name too
LayerNorm = LayerNormalization

# Extended Keras1-parity set (advanced activations, noise, conv variants,
# ConvLSTM, LRN, torch-style elementwise, ...) lives in layers_ext but is
# part of this namespace — the reference exposes one flat layer namespace.
from analytics_zoo_tpu.keras.layers_ext import *  # noqa: E402,F401,F403
