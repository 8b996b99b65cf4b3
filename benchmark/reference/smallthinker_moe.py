"""Plain reference of the sliding-window / NoPE expert language model
(family `smallthinker_moe`, SmallThinker-21B-A3B): forward, loss and,
through `jax.value_and_grad`, gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, following the published config
and the family's description (what the config does not fix is listed in
the configuration's file under `assumed`). `x` is [T, H]:

    RMSNorm(x)  = x * rsqrt(mean(x^2) + eps) * w                (eps 1e-6)
    Attn_l(a):  q = a Wq -> [T, heads, d];  k = a Wk, v = a Wv -> [T, kv, d]
                (no bias, no q/k norm)
                global layer (`sliding_window_layout[l]` 0): no positions;
                    o_h = softmax_{j <= i}(q_h,i . k_{h // group},j / sqrt(d))
                window layer (1): q, k <- rotary(theta) over all d,
                    rotate-half pairs; the softmax over i - W < j <= i
                out = concat_h(o_h) Wo
    Route(a):   p = softmax(a Wr) over the router's width;  E = top-k of p
                g_e = p_e / sum over E of p                 (no bias)
    ReGLU(u)    = W2( relu(W1 u) * (W3 u) )
    MoE(u)      = sum over e in E HELD HERE of g_e * ReGLU_e(u)  (no shared)
    Block_l(x): a = RMSNorm_1(x);  (E, g) = Route(a);  h = x + Attn_l(a)
                x' = h + MoE(RMSNorm_2(h); E, g)
    Model(ids): x = E[ids];  blocks;  logits = RMSNorm_f(x) W_head  (untied)
    Loss:       mean next-token cross-entropy over the (sliced) vocabulary

Independent of `analytics_zoo_tpu/keras/` and of the kernels: it only reads
the parameter tree by name (a run of neighbouring layers of one kind is one
stacked [n, ...] subtree `blocks_<first layer>_<global | window>_moe`,
walked by a `lax.scan`); the norm is `benchmark/reference/kanana_moe.py`'s
and rotary `benchmark/reference/lfm2_moe.py`'s. Departures that change no
number: K and V are indexed for every query head (a gather, no grouped
product); the attention runs head by head and, within a head, 4096 queries
at a time against every key under an explicit mask (a head's 16,384 x
16,384 float32 scores are 1 GB); the held experts are a loop over all
tokens with a 0-or-weight mask (kanana's); in `reference_loss` every
layer, head, block of queries and expert is a `jax.checkpoint`, and the
loss is taken over 4096 positions at a time, each a `jax.checkpoint`, so
that the [T, vocabulary] logits and their gradient are never whole.

`experts_held`, `choice`: as kanana's reference (the range of the router's
experts the tree holds; indices that replace the router's own top-k, never
its scores or weights).

The faults exist so that the check that the comparison CAN fail has
something to break: `router_after_attention` (the router reads
RMSNorm_2(h), the experts' input, as other expert models have it),
`sigmoid_router` (p = sigmoid(a Wr)), `silu_experts` (SwiGLU in place of
ReGLU), `window_dropped` (the window layers attend causally over the whole
sequence), `rotary_in_global` (the global layer gets rotary too),
`rotary_dropped` (the window layers get none), `gqa_interleaved` (query
head h reads K/V head h % kv, not h // group), `causal_mask_dropped` and
`reference_bfloat16` (everything in bfloat16 at the default matmul
precision: the nearest precision below the one the configuration
states)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.kanana_moe import _rms_norm
from benchmark.reference.lfm2_moe import _rotary

_QUERIES_AT_ONCE = 4096         # of a head of the attention layer
_POSITIONS_AT_ONCE = 4096       # of the loss's logits


def layer_runs(config):
    """[(subtree name, "global" | "window", layers)] in the layers' order:
    neighbouring layers of one kind are one stacked subtree."""
    kinds = ["window" if w else "global" for w in
             config["sliding_window_layout"][:config["num_hidden_layers"]]]
    runs, first = [], 0
    for l in range(1, len(kinds) + 1):
        if l == len(kinds) or kinds[l] != kinds[first]:
            runs.append((f"blocks_{first}_{kinds[first]}_moe", kinds[first],
                         l - first))
            first = l
    return runs


def _attention(a, p, config, kind, fault, recompute):
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, W = config["head_dim"], config["sliding_window_size"]
    B, T, _ = a.shape
    Tq = max(b for b in range(1, _QUERIES_AT_ONCE + 1) if T % b == 0)
    q = (a @ p["q_kernel"]).reshape(B, T, n, d)
    k = (a @ p["k_kernel"]).reshape(B, T, kv, d)
    v = (a @ p["v_kernel"]).reshape(B, T, kv, d)
    positioned = kind == "window" and not fault.get("rotary_dropped") \
        or kind == "global" and fault.get("rotary_in_global")
    if positioned:
        q = _rotary(q, config["rope_theta"])
        k = _rotary(k, config["rope_theta"])
    banded = kind == "window" and not fault.get("window_dropped")
    heads = jnp.arange(n)
    of = heads % kv if fault.get("gqa_interleaved") else heads // (n // kv)
    k, v = k[:, :, of], v[:, :, of]                     # [B, T, n, d]
    cols = jnp.arange(T)

    def one_head(qkv):
        qh, kh, vh = qkv                                # [B, T, d]

        def some_queries(block):
            first, q_b = block                          # [B, Tq, d]
            scores = jnp.einsum("bqd,bkd->bqk", q_b, kh) / math.sqrt(d)
            rows = first + jnp.arange(Tq)
            seen = jnp.ones((Tq, T), bool)
            if not fault.get("causal_mask_dropped"):
                seen = cols[None, :] <= rows[:, None]
            if banded:
                seen = jnp.logical_and(seen,
                                       cols[None, :] > rows[:, None] - W)
            scores = jnp.where(seen, scores, -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd",
                              jax.nn.softmax(scores, axis=-1), vh)

        blocks = qh.reshape(B, T // Tq, Tq, d).transpose(1, 0, 2, 3)
        out = jax.lax.map(jax.checkpoint(some_queries) if recompute
                          else some_queries, (jnp.arange(0, T, Tq), blocks))
        return out.transpose(1, 0, 2, 3).reshape(B, T, d)

    ctx = jax.lax.map(jax.checkpoint(one_head) if recompute else one_head,
                      tuple(t.transpose(2, 0, 1, 3) for t in (q, k, v)))
    return ctx.transpose(1, 2, 0, 3).reshape(B, T, n * d) @ p["out_kernel"]


def _route(a, p, config, fault):
    """(p [B, T, width], the router's own top-k [B, T, k]) of a."""
    logits = a @ p["router"]["kernel"]
    scores = jax.nn.sigmoid(logits) if fault.get("sigmoid_router") \
        else jax.nn.softmax(logits, axis=-1)
    k = config["moe_num_active_primary_experts"]
    return scores, jax.lax.top_k(scores, k)[1]


def _reglu(u, w, silu=False):
    act = jax.nn.silu if silu else jax.nn.relu
    return (act(u @ w["gate_kernel"]) * (u @ w["up_kernel"])) \
        @ w["down_kernel"]


def _moe(u, scores, idx, p, held, fault, recompute):
    """MoE(u) [B, T, H] at the choice `idx` [B, T, k] with the router's
    scores [B, T, width]: the held experts' weighted sum."""
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / w.sum(axis=-1, keepdims=True)

    def one_expert(acc, ep):
        e, weights = ep
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)   # 0 or w_e
        return acc + mine[..., None] * _reglu(
            u, weights, fault.get("silu_experts")), None

    if recompute:
        one_expert = jax.checkpoint(one_expert)
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          (jnp.arange(held[0], held[1]), p["experts"]))
    return out


def reference_hidden(params, ids, config, *, experts_held=None, choice=None,
                     recompute: bool = False, **fault):
    """(RMSNorm_f of the last block's output [B, T, H], the router's own
    choice [layers, B, T, k]) for int32 `ids` [B, T], in the precision of
    the fault (float32 at the highest matmul precision without one)."""
    eps = config["rms_norm_eps"]
    held = tuple(experts_held or config["experts_held"])
    low = fault.get("reference_bfloat16")
    dtype = jnp.bfloat16 if low else jnp.float32

    def block(kind):
        def apply(x, p_and_choice):
            p, layer_choice = p_and_choice
            a = _rms_norm(x, p["attn_norm"], eps)
            h = x + _attention(a, p["attn"], config, kind, fault, recompute)
            u = _rms_norm(h, p["ffn_norm"], eps)
            scores, own = _route(u if fault.get("router_after_attention")
                                 else a, p["ffn"], config, fault)
            idx = own if layer_choice is None else layer_choice
            return h + _moe(u, scores, idx, p["ffn"], held, fault,
                            recompute), own
        return jax.checkpoint(apply) if recompute else apply

    params = jax.tree_util.tree_map(lambda t: jnp.asarray(t, dtype), params)
    x = params["word_embeddings"][jnp.asarray(ids, jnp.int32)]
    if choice is not None:
        choice = jnp.asarray(choice, jnp.int32)
    own, seen = [], 0
    for name, kind, n in layer_runs(config):
        mine = None if choice is None else choice[seen:seen + n]
        x, chosen = jax.lax.scan(block(kind), x, (params[name], mine))
        own.append(chosen)
        seen += n
    return (_rms_norm(x, params["final_norm"], eps), jnp.concatenate(own),
            params["lm_head_kernel"])


def _precision(fault):
    return jax.default_matmul_precision(
        "default" if fault.get("reference_bfloat16") else "highest")


def reference_forward(params, ids, config, **kw):
    """(logits [B, T, vocab] float32, the router's own choice
    [layers, B, T, k]) for int32 `ids` [B, T]."""
    with _precision(kw):
        x, own, head = reference_hidden(params, ids, config, **kw)
        return (x @ head).astype(jnp.float32), own


def reference_loss_and_choice(params, batch, config, **kw):
    """(mean next-token cross-entropy over the vocabulary the tree holds,
    the router's own choice) on one training batch `{"x": ids [B, T], "y":
    next ids [B, T]}`, float32: what `jax.value_and_grad(..., has_aux=True)`
    of the training-step check differentiates."""
    with _precision(kw):
        x, own, head = reference_hidden(params, batch["x"], config,
                                        recompute=True, **kw)
        y = jnp.asarray(batch["y"], jnp.int32)
        B, T, H = x.shape
        P = max(b for b in range(1, _POSITIONS_AT_ONCE + 1) if T % b == 0)

        @jax.checkpoint
        def some_positions(total, xy):
            xb, yb = xy                                 # [B, P, H], [B, P]
            logp = jax.nn.log_softmax((xb @ head).astype(jnp.float32),
                                      axis=-1)
            return total - jnp.take_along_axis(logp, yb[..., None],
                                               axis=-1).sum(), None

        total, _ = jax.lax.scan(
            some_positions, jnp.zeros((), jnp.float32),
            (x.reshape(B, T // P, P, H).transpose(1, 0, 2, 3),
             y.reshape(B, T // P, P).transpose(1, 0, 2)))
        return total / (B * T), own


def reference_loss(params, batch, config, **kw):
    return reference_loss_and_choice(params, batch, config, **kw)[0]
