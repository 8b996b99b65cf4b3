"""Loss objectives with the reference's compile-string registry.

Mirrors the 15-objective library of `zoo/.../pipeline/api/keras/objectives/`
and the exact string registry of `KerasUtils.toBigDLCriterion`
(`keras/layers/utils/KerasUtils.scala:180-203`) — same strings, same aliases,
same error on unknown names. Implemented as pure jax functions (class instances
are stateless callables), reduction = mean over the batch, computed in float32
regardless of input dtype so bf16 activations don't destabilize training.

Conventions (Keras semantics, as the reference follows Keras):
- probability-space crossentropies by default; `from_logits=True` fuses the
  softmax/sigmoid for numerical stability (preferred on TPU).
- `sparse_categorical_crossentropy` takes 0-based integer labels
  (`SparseCategoricalCrossEntropy.scala` zeroBasedLabel=true default).
- hinge losses expect targets in {-1, 1}.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jax.Array
EPS = 1e-7


def _f32(x) -> Array:
    return jnp.asarray(x, jnp.float32)


def _align(y_true, y_pred):
    """Align a rank-off-by-one target with a trailing size-1 prediction dim
    (or vice versa). Without this, `[B] - [B, 1]` silently broadcasts to
    `[B, B]` and the loss optimizes toward the global mean."""
    y_true, y_pred = _f32(y_true), _f32(y_pred)
    if y_true.ndim == y_pred.ndim - 1 and y_pred.shape[-1] == 1:
        y_true = y_true[..., None]
    elif y_pred.ndim == y_true.ndim - 1 and y_true.shape[-1] == 1:
        y_pred = y_pred[..., None]
    return y_true, y_pred


class ProjectedLogits(NamedTuple):
    """Logits that a model hands to its loss unformed: `features`
    [..., H] and the head's `kernel` [H, V], with logits = features @
    kernel. A language model's training output: [B, T, V] logits in
    float32 (and their cotangent) are gigabytes at a real vocabulary,
    while `SparseCategoricalCrossEntropy` needs them one block of tokens
    at a time. Any other loss calls `materialize()`."""
    features: Array
    kernel: Array

    def materialize(self) -> Array:
        return jnp.dot(self.features, self.kernel,
                       preferred_element_type=jnp.float32)


# tokens whose logits the blockwise loss forms at a time: at a vocabulary
# of 49,152 one block is 0.2 GB in float32, and the matmul still has 1024
# rows for the MXU
_LOSS_BLOCK_TOKENS = 1024


def _blockwise_sparse_nll(pred: ProjectedLogits, labels: Array) -> Array:
    """Sum over all tokens of -log softmax(features @ kernel)[label],
    float32, formed `_LOSS_BLOCK_TOKENS` tokens at a time: a `lax.scan`
    over token blocks whose body is recomputed in the backward pass
    (`jax.checkpoint`), so one block's [block, V] logits are the most
    that is ever live, forward or backward. The kernel's gradient is
    summed over the blocks by the scan's transpose."""
    feats = pred.features.reshape(-1, pred.features.shape[-1])
    labels = labels.reshape(-1)
    n = feats.shape[0]
    block = min(_LOSS_BLOCK_TOKENS, n)
    pad = (-n) % block
    weight = jnp.ones((n,), jnp.float32)
    if pad:
        feats = jnp.pad(feats, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        weight = jnp.pad(weight, (0, pad))

    @jax.checkpoint
    def block_nll(kernel, f, y, w):
        logits = jnp.dot(f, kernel, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(w * (jax.nn.logsumexp(logits, axis=-1) - picked))

    def body(total, xs):
        return total + block_nll(pred.kernel, *xs), None

    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (feats.reshape(-1, block, feats.shape[-1]),
         labels.reshape(-1, block), weight.reshape(-1, block)))
    return total


class Objective:
    """Base class: a callable loss(y_true, y_pred) -> scalar."""

    def __call__(self, y_true: Array, y_pred: Array) -> Array:
        raise NotImplementedError

    def __repr__(self):
        return type(self).__name__


class MeanSquaredError(Objective):
    def __call__(self, y_true, y_pred):
        y_true, y_pred = _align(y_true, y_pred)
        return jnp.mean(jnp.square(y_pred - y_true))


class MeanAbsoluteError(Objective):
    def __call__(self, y_true, y_pred):
        y_true, y_pred = _align(y_true, y_pred)
        return jnp.mean(jnp.abs(y_pred - y_true))


class MeanAbsolutePercentageError(Objective):
    def __call__(self, y_true, y_pred):
        y_true, y_pred = _align(y_true, y_pred)
        diff = jnp.abs(y_pred - y_true) / jnp.clip(jnp.abs(y_true), EPS, None)
        return 100.0 * jnp.mean(diff)


class MeanSquaredLogarithmicError(Objective):
    def __call__(self, y_true, y_pred):
        y_true, y_pred = _align(y_true, y_pred)
        a = jnp.log1p(jnp.clip(y_pred, EPS, None))
        b = jnp.log1p(jnp.clip(y_true, EPS, None))
        return jnp.mean(jnp.square(a - b))


class BinaryCrossEntropy(Objective):
    def __init__(self, from_logits: bool = False):
        self.from_logits = from_logits

    def __call__(self, y_true, y_pred):
        y_true, y_pred = _align(y_true, y_pred)
        if self.from_logits:
            # stable: max(x,0) - x*y + log1p(exp(-|x|))
            x = y_pred
            per = jnp.maximum(x, 0) - x * y_true + jnp.log1p(jnp.exp(-jnp.abs(x)))
        else:
            p = jnp.clip(y_pred, EPS, 1.0 - EPS)
            per = -(y_true * jnp.log(p) + (1.0 - y_true) * jnp.log1p(-p))
        return jnp.mean(per)


class CategoricalCrossEntropy(Objective):
    """One-hot targets over the last axis."""

    def __init__(self, from_logits: bool = False):
        self.from_logits = from_logits

    def __call__(self, y_true, y_pred):
        y_true, y_pred = _align(y_true, y_pred)
        if self.from_logits:
            logp = jax.nn.log_softmax(y_pred, axis=-1)
        else:
            p = y_pred / jnp.clip(jnp.sum(y_pred, -1, keepdims=True), EPS, None)
            logp = jnp.log(jnp.clip(p, EPS, 1.0))
        return jnp.mean(-jnp.sum(y_true * logp, axis=-1))


class SparseCategoricalCrossEntropy(Objective):
    """Integer (0-based) class labels (`SparseCategoricalCrossEntropy.scala`).
    A prediction that arrives as `ProjectedLogits` (a language model's
    training output) is never formed whole: the mean is taken a block
    of tokens at a time, to the same number."""

    def __init__(self, from_logits: bool = False):
        self.from_logits = from_logits

    def __call__(self, y_true, y_pred):
        if isinstance(y_pred, ProjectedLogits):
            if not self.from_logits:
                raise ValueError("ProjectedLogits are logits: compile the "
                                 "loss with from_logits=True")
            labels = jnp.asarray(y_true, jnp.int32)
            with jax.named_scope("loss/blockwise_nll"):
                return _blockwise_sparse_nll(y_pred, labels) / labels.size
        y_pred = _f32(y_pred)
        labels = jnp.asarray(y_true, jnp.int32)
        if labels.ndim == y_pred.ndim:  # squeeze trailing [*, 1] label dim
            labels = jnp.squeeze(labels, -1)
        if self.from_logits:
            logp = jax.nn.log_softmax(y_pred, axis=-1)
        else:
            logp = jnp.log(jnp.clip(y_pred, EPS, 1.0))
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(-picked)


class Hinge(Objective):
    def __call__(self, y_true, y_pred):
        y_true, y_pred = _align(y_true, y_pred)
        return jnp.mean(jnp.maximum(1.0 - y_true * y_pred, 0.0))


class SquaredHinge(Objective):
    def __call__(self, y_true, y_pred):
        y_true, y_pred = _align(y_true, y_pred)
        return jnp.mean(jnp.square(jnp.maximum(1.0 - y_true * y_pred, 0.0)))


class RankHinge(Objective):
    """Pairwise ranking hinge for text matching (`objectives/RankHinge.scala`):
    batch rows alternate positive/negative samples; loss =
    max(0, margin - (score_pos - score_neg)) per pair."""

    def __init__(self, margin: float = 1.0):
        self.margin = margin

    def __call__(self, y_true, y_pred):
        del y_true  # ordering carries the supervision
        s = _f32(y_pred).reshape(-1)
        pos, neg = s[0::2], s[1::2]
        return jnp.mean(jnp.maximum(self.margin - pos + neg, 0.0))


class KullbackLeiblerDivergence(Objective):
    def __call__(self, y_true, y_pred):
        y_true = jnp.clip(_f32(y_true), EPS, 1.0)
        y_pred = jnp.clip(_f32(y_pred), EPS, 1.0)
        return jnp.mean(jnp.sum(y_true * jnp.log(y_true / y_pred), axis=-1))


class Poisson(Objective):
    def __call__(self, y_true, y_pred):
        y_true, y_pred = _align(y_true, y_pred)
        return jnp.mean(y_pred - y_true * jnp.log(y_pred + EPS))


class CosineProximity(Objective):
    def __call__(self, y_true, y_pred):
        y_true = _f32(y_true)
        y_pred = _f32(y_pred)
        t = y_true / jnp.clip(jnp.linalg.norm(y_true, axis=-1, keepdims=True), EPS, None)
        p = y_pred / jnp.clip(jnp.linalg.norm(y_pred, axis=-1, keepdims=True), EPS, None)
        return -jnp.mean(jnp.sum(t * p, axis=-1))


# ---------------------------------------------------------------------------
# Registry — exact strings of `KerasUtils.toBigDLCriterion`
# (`KerasUtils.scala:180-203`).
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Callable[[], Objective]] = {
    "binary_crossentropy": BinaryCrossEntropy,
    "categorical_crossentropy": CategoricalCrossEntropy,
    "mse": MeanSquaredError,
    "mean_squared_error": MeanSquaredError,
    "mae": MeanAbsoluteError,
    "mean_absolute_error": MeanAbsoluteError,
    "hinge": Hinge,
    "mape": MeanAbsolutePercentageError,
    "mean_absolute_percentage_error": MeanAbsolutePercentageError,
    "msle": MeanSquaredLogarithmicError,
    "mean_squared_logarithmic_error": MeanSquaredLogarithmicError,
    "squared_hinge": SquaredHinge,
    "sparse_categorical_crossentropy": SparseCategoricalCrossEntropy,
    "kld": KullbackLeiblerDivergence,
    "kullback_leibler_divergence": KullbackLeiblerDivergence,
    "cosine_proximity": CosineProximity,
    "poisson": Poisson,
    "rank_hinge": RankHinge,
}


def get(loss: Any, **kwargs) -> Objective:
    """Resolve a loss from its compile string (or pass through an Objective /
    plain callable). Raises on unknown strings, matching the reference's
    IllegalArgumentException."""
    if isinstance(loss, Objective):
        return loss
    if callable(loss):
        wrapped = loss

        class _Fn(Objective):
            def __call__(self, y_true, y_pred):
                return wrapped(y_true, y_pred)
        return _Fn()
    key = str(loss).lower()
    if key not in _REGISTRY:
        raise ValueError(f"Unsupported loss: {loss}")
    return _REGISTRY[key](**kwargs)
