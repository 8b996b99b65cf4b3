"""A routed expert layer that is told which experts it holds.

    s        = sigmoid(u Wg)              float32, [N, n_routed_experts]
    choice   = top-k of (s + b)           b: the router's correction bias; it
                                          chooses, it does not weigh
    w_e      = s_e / (sum of s over the k chosen + eps) * scale
                                          eps: 1e-20 unless given (LFM2's
                                          published code adds 1e-6)
    MoE(u)   = SwiGLU^shared(u) + sum over the chosen e HELD HERE of
               w_e * SwiGLU^e(u)

Under expert parallelism a layer's experts are spread over chips; this
layer is one chip's part. It routes over all `n_routed_experts` (the
router, its normalisation over all k chosen and the shared experts are
whole on every chip) and computes what its own experts, the contiguous
range `experts_held`, add. What the absent experts would have added is
left out: on one chip there is no exchange, and nothing here stands in for
the other chips. With `experts_held` the whole range it is the whole layer.

Dispatch is drop-free: no capacity factor. The N x k token-slots are sorted
by expert, held experts first, into a buffer that is as long as the worst
case needs (every slot held: N x k rows), and the three grouped products
(`pallas.grouped_matmul`) follow the group sizes: they visit the rows of
the held experts, about N x k x held / n_routed of them under even routing,
and never a row of an absent expert. Dispatch and combine are row gathers
in both directions (a slot's position in the sorted buffer and its inverse
are both known), never a scatter-add; the buffer's places past the held
slots are never written by the products, so what leaves the buffer is
selected by the slots' `held` mask (`_gather_sum`).

The router's matmul, sigmoid and top-k run in float32 at the highest
matmul precision whatever the step's type, as the published code has it:
the choice is discontinuous, and a bfloat16 score flips it for many tokens.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.engine import Layer
from analytics_zoo_tpu.keras.layers import get_activation, get_init
from analytics_zoo_tpu.keras.transformer import gated_ffn, gated_ffn_params
from analytics_zoo_tpu.pallas.grouped_matmul import grouped_matmul


def route(u, kernel, bias, top_k: int, scale: float, eps: float = 1e-20):
    """Token features u [N, H] -> (experts [N, k] int32, weights [N, k]
    float32): sigmoid scores in float32, the k largest of score + bias
    chosen, weighed by the scores alone (normalised over the k, `eps`
    added to their sum, times `scale`)."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + eps)
    return experts.astype(jnp.int32), w * scale


def _gather_sum(rows, index, keep, weight=None):
    """sum over j of rows[index[:, j]], where keep[:, j] and times
    weight[:, j] where given: [R, H] rows, [N, k] indices -> [N, H]
    float32. k gathers of N rows each, added up: no [N, k, H] array is
    formed (with k = 6 on the sublanes it would be laid out again on every
    reshape to and from [N * k, H]), and a row that is not kept is
    SELECTED away, never multiplied (it may be unwritten memory)."""
    acc = jnp.zeros((index.shape[0], rows.shape[1]), jnp.float32)
    for j in range(index.shape[1]):
        piece = jnp.where(keep[:, j, None], rows[index[:, j]],
                          jnp.zeros((), rows.dtype)).astype(jnp.float32)
        if weight is not None:
            piece = piece * weight[:, j, None]
        acc = acc + piece
    return acc


@jax.custom_vjp
def _to_sorted(x, order, position, held):
    """Dispatch: x [N, H] -> [N * k, H], row `order[p] // k` of x at
    sorted place p (slot `order[p]` is token `order[p] // k`'s). Places
    past the held slots hold absent slots' tokens: real rows that no
    grouped product reads. `position` [N, k] is the inverse of `order`
    and `held` [N, k] says which slots chose an expert held here: the
    gradient is the gather back through them, a token's held slots added
    up (the places of the others were never written)."""
    return x[order // position.shape[1]]


def _to_sorted_fwd(x, order, position, held):
    return _to_sorted(x, order, position, held), (position, held)


def _to_sorted_bwd(res, g):
    position, held = res
    return _gather_sum(g, position, held).astype(g.dtype), None, None, None


_to_sorted.defvjp(_to_sorted_fwd, _to_sorted_bwd)


@jax.custom_vjp
def _from_sorted(ys, weights, order, position, held):
    """Combine: the sorted buffer's rows ys [N * k, H] back to their
    tokens, each held slot's row times its weight [N, k], added up:
    [N, H] float32. The gradient reaches ys by ONE gather of the tokens'
    cotangent rows (row `order[p] // k` at place p, times its slot's
    weight) and the weights by a row-wise product in sorted order."""
    return _gather_sum(ys, position, held, weights)


def _from_sorted_fwd(ys, weights, order, position, held):
    return _from_sorted(ys, weights, order, position, held), (
        ys, weights, order, position, held)


def _from_sorted_bwd(res, g):
    ys, weights, order, position, held = res
    rows = g.astype(ys.dtype)[order // position.shape[1]]   # [N * k, H]
    d_ys = rows * weights.reshape(-1)[order][:, None].astype(ys.dtype)
    # a held slot's weight moves the output along its expert's row; the
    # rows of the places past the held slots are unwritten: selected away
    along = jnp.sum(ys.astype(jnp.float32) * rows.astype(jnp.float32),
                    axis=1)
    d_w = jnp.where(held, along[position], 0.0)
    return d_ys, d_w.astype(weights.dtype), None, None, None


_from_sorted.defvjp(_from_sorted_fwd, _from_sorted_bwd)


class MoEFeedForward(Layer):
    """[B, T, H] -> [B, T, H]: shared experts plus this chip's part of the
    routed experts (module docstring). `experts_held` = (first, end) is a
    contiguous range of the `n_routed_experts`; None holds all of them.
    `shared_width` is the width of the shared experts taken as ONE gated
    FFN (`n_shared_experts * moe_intermediate_size`); 0 has none.
    `norm_eps` is added to the chosen scores' sum (`route`)."""

    def __init__(self, hidden_size: int, expert_width: int,
                 n_routed_experts: int, num_experts_per_tok: int,
                 experts_held: Optional[Tuple[int, int]] = None,
                 shared_width: int = 0, routed_scaling_factor: float = 1.0,
                 hidden_act: str = "silu", init="glorot_uniform",
                 norm_eps: float = 1e-20, **kw):
        super().__init__(**kw)
        first, end = experts_held or (0, n_routed_experts)
        if not 0 <= first < end <= n_routed_experts:
            raise ValueError(f"experts_held {experts_held} is no range of "
                             f"the {n_routed_experts} routed experts")
        self.hidden_size, self.expert_width = hidden_size, expert_width
        self.n_routed, self.top_k = n_routed_experts, num_experts_per_tok
        self.first, self.n_held = first, end - first
        self.shared_width = shared_width
        self.scale, self.norm_eps = routed_scaling_factor, norm_eps
        self.act = get_activation(hidden_act)
        self.init = get_init(init)

    def build(self, rng, input_shape=None):
        k_r, k_s, k_e = jax.random.split(rng, 3)
        H = self.hidden_size
        # every held expert's three kernels, each drawn from its own key,
        # as ONE [held, ...] array a tensor
        experts = jax.vmap(lambda k: gated_ffn_params(
            k, H, self.expert_width, self.init))(
                jax.random.split(k_e, self.n_held))
        p = {
            "router": {
                "kernel": self.init(k_r, (H, self.n_routed), jnp.float32),
                # a leaf of zeros: it shifts the choice alone, so its
                # gradient is exactly zero
                "bias": jnp.zeros((self.n_routed,), jnp.float32)},
            # [held, H, I], [held, H, I], [held, I, H]
            "experts": {name[len("ffn_"):]: kernel
                        for name, kernel in experts.items()},
        }
        if self.shared_width:
            p["shared"] = gated_ffn_params(k_s, H, self.shared_width,
                                           self.init)
        return p

    def routing(self, params, u):
        """(experts [N, k], weights [N, k]) of u [..., H]'s N tokens."""
        r = params["router"]
        return route(u.reshape(-1, self.hidden_size), r["kernel"], r["bias"],
                     self.top_k, self.scale, self.norm_eps)

    def _dispatch(self, experts):
        """The sorted buffer's bookkeeping from the choice [N, k]: `order`
        [N * k] (sorted place -> slot), `position` [N, k] (slot -> sorted
        place), the held experts' group sizes [n_held] and which slots are
        held [N, k]. Held slots come first, by expert; absent ones last."""
        local = experts - self.first
        held = jnp.logical_and(local >= 0, local < self.n_held)
        key = jnp.where(held, local, self.n_held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        position = jnp.argsort(order).astype(jnp.int32).reshape(held.shape)
        sizes = (key[:, None] == jnp.arange(self.n_held, dtype=key.dtype)
                 ).sum(axis=0, dtype=jnp.int32)
        return order, position, sizes, held

    def routed(self, params, u):
        """This chip's part of the routed experts' sum, u [B, T, H] ->
        [B, T, H]."""
        x = u.reshape(-1, self.hidden_size)
        with jax.named_scope("moe/router"):
            experts, weights = self.routing(params, u)
        with jax.named_scope("moe/dispatch"):
            order, position, sizes, held = self._dispatch(experts)
            xs = _to_sorted(x, order, position, held)
        with jax.named_scope("moe/experts"):
            e = params["experts"]
            f = self.act(grouped_matmul(xs, e["gate_kernel"], sizes)) \
                * grouped_matmul(xs, e["up_kernel"], sizes)
            ys = grouped_matmul(f.astype(x.dtype), e["down_kernel"], sizes)
        with jax.named_scope("moe/combine"):
            out = _from_sorted(ys, weights, order, position, held)
        return out.astype(u.dtype).reshape(u.shape)

    def call(self, params, u, *, training=False, rng=None):
        out = self.routed(params, u)
        if self.shared_width:
            with jax.named_scope("moe/shared_experts"):
                out = out + gated_ffn(params["shared"], u, self.act)
        return out
