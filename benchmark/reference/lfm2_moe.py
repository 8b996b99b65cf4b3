"""Plain reference of the hybrid conv/attention expert language model
(family `lfm2_moe`, `model_type` `lfm2_moe`): forward, loss and, through
`jax.value_and_grad`, gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, following the equations of ISSUE
36 (what the published config does not fix is listed in the
configuration's file under `assumed`). `x` is [T, H]:

    RMSNorm(x)  = x * rsqrt(mean(x^2) + eps) * w                (eps 1e-5)
    Conv(x):    [B | C | x~] = x W_in          W_in [H, 3H], in this order
                v_t = B_t * x~_t
                c_t = sum_{i < K} w_i * v_{t - (K - 1) + i}     depthwise,
                      one K-tap filter a channel, v_{<0} = 0 (K = 3)
                y_t = C_t * c_t;  out = y W_out    (no bias, no activation)
    GQA(x):     q = x Wq -> [T, heads, d];  k = x Wk, v = x Wv -> [T, kv, d]
                q, k <- RMSNorm over each head's d (one weight [d] each),
                        then rotary(theta) over all d, rotate-half pairs
                o_h = softmax(q_h k_{h // group}^T / sqrt(d) + causal)
                      v_{h // group};  out = concat_h(o_h) Wo
    SwiGLU(u)   = W2( silu(W1 u) * (W3 u) )
    Router(u):  s = sigmoid(u Wr), [T, router width];  E = top-k of (s + b)
                (b: the expert bias, for the choice alone)
                g_e = s_e / (sum over E of s + 1e-6) * scale
    MoE(u)      = sum over e in E HELD HERE of g_e * SwiGLU_e(u)  (no shared)
    Block_l(h): h = h + Mixer_l(RMSNorm_1(h));  h = h + FFN_l(RMSNorm_2(h))
                Mixer_l = Conv where `layer_types[l]` is "conv", else GQA;
                FFN_l = SwiGLU for l < num_dense_layers, else MoE
    Model(ids): h = E[ids];  blocks;  logits = RMSNorm_f(h) E^T   (tied)
    Loss:       mean next-token cross-entropy over the (sliced) vocabulary

Independent of `analytics_zoo_tpu/keras/` and of the kernels: it only reads
the parameter tree by name (a run of neighbouring layers of one kind is
one stacked [n, ...] subtree `blocks_<first layer>_<mixer>_<ffn>`, walked
by a `lax.scan`); the norm and SwiGLU are `benchmark/reference/
kanana_moe.py`'s. Departures that change no number: K and V are indexed
for every query head (a gather, no grouped product); the attention runs
head by head and, within a head, 4096 queries at a time (a head's 16,384 x
16,384 float32 scores are 1 GB); the held experts are a loop over all
tokens with a 0-or-weight mask (kanana's); in `reference_loss` every
layer, head, block of queries and expert is a `jax.checkpoint`.

`experts_held`, `choice`: as kanana's reference (the range of the router's
experts the tree holds; indices that replace the router's own top-k, never
its scores or weights).

The faults exist so that the check that the comparison CAN fail has
something to break: `gates_dropped` (B = C = 1), `filter_dropped` (the
filter the identity), `filter_anticausal` (the taps look ahead: c_t =
sum_i w_i v_{t + (K - 1) - i}), `qk_norm_dropped`, `rotary_dropped`,
`gqa_interleaved` (query head h reads K/V head h % kv, not h // group),
`expert_bias_in_weights` (the weights normalise s + b), `causal_mask_dropped`
and `reference_bfloat16` (everything in bfloat16 at the default matmul
precision: the nearest precision below the one the configuration
states)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.kanana_moe import _rms_norm, _swiglu

_QUERIES_AT_ONCE = 4096         # of a head of the attention layer
_ROUTER_EPS = 1e-6              # the published normalisation's


def layer_runs(config):
    """[(subtree name, "conv" | "gqa", "dense" | "moe", layers)] in the
    layers' order: neighbouring layers of one kind are one stacked subtree."""
    kinds = [("conv" if t == "conv" else "gqa",
              "dense" if l < config["num_dense_layers"] else "moe")
             for l, t in enumerate(
                 config["layer_types"][:config["num_hidden_layers"]])]
    runs, first = [], 0
    for l in range(1, len(kinds) + 1):
        if l == len(kinds) or kinds[l] != kinds[first]:
            runs.append((f"blocks_{first}_{kinds[first][0]}_{kinds[first][1]}",
                         *kinds[first], l - first))
            first = l
    return runs


def _conv(x, p, config, fault, recompute):
    H, T = x.shape[-1], x.shape[1]
    bcx = x @ p["in_kernel"]
    b, c, v = bcx[..., :H], bcx[..., H:2 * H], bcx[..., 2 * H:]
    if not fault.get("gates_dropped"):
        v = b * v
    taps = p["conv"]                                    # [K, H]
    K = taps.shape[0]
    if fault.get("filter_dropped"):
        f = v
    elif fault.get("filter_anticausal"):
        padded = jnp.pad(v, ((0, 0), (0, K - 1), (0, 0)))
        f = sum(padded[:, K - 1 - i:K - 1 - i + T] * taps[i]
                for i in range(K))
    else:
        padded = jnp.pad(v, ((0, 0), (K - 1, 0), (0, 0)))
        f = sum(padded[:, i:i + T] * taps[i] for i in range(K))
    if not fault.get("gates_dropped"):
        f = c * f
    return f @ p["out_kernel"]


def _rotary(x, theta):
    """Rotary positions on x [B, T, heads, d], pairs (i, i + d/2)."""
    T, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    angle = (jnp.arange(T, dtype=jnp.float32)[:, None] * freq)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _gqa(x, p, config, fault, recompute):
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps = config["head_dim"], config["norm_eps"]
    B, T, _ = x.shape
    Tq = max(b for b in range(1, _QUERIES_AT_ONCE + 1) if T % b == 0)
    q = (x @ p["q_kernel"]).reshape(B, T, n, d)
    k = (x @ p["k_kernel"]).reshape(B, T, kv, d)
    v = (x @ p["v_kernel"]).reshape(B, T, kv, d)
    if not fault.get("qk_norm_dropped"):
        q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
    if not fault.get("rotary_dropped"):
        q = _rotary(q, config["rope_theta"])
        k = _rotary(k, config["rope_theta"])
    heads = jnp.arange(n)
    of = heads % kv if fault.get("gqa_interleaved") else heads // (n // kv)
    k, v = k[:, :, of], v[:, :, of]                     # [B, T, n, d]
    cols = jnp.arange(T)

    def one_head(qkv):
        qh, kh, vh = qkv                                # [B, T, d]

        def some_queries(block):
            first, q_b = block                          # [B, Tq, d]
            scores = jnp.einsum("bqd,bkd->bqk", q_b, kh) / math.sqrt(d)
            if not fault.get("causal_mask_dropped"):
                rows = first + jnp.arange(Tq)
                scores = jnp.where(cols[None, :] <= rows[:, None], scores,
                                   -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd",
                              jax.nn.softmax(scores, axis=-1), vh)

        blocks = qh.reshape(B, T // Tq, Tq, d).transpose(1, 0, 2, 3)
        out = jax.lax.map(jax.checkpoint(some_queries) if recompute
                          else some_queries, (jnp.arange(0, T, Tq), blocks))
        return out.transpose(1, 0, 2, 3).reshape(B, T, d)

    ctx = jax.lax.map(jax.checkpoint(one_head) if recompute else one_head,
                      tuple(a.transpose(2, 0, 1, 3) for a in (q, k, v)))
    return ctx.transpose(1, 2, 0, 3).reshape(B, T, n * d) @ p["out_kernel"]


def _moe(u, p, config, held, choice, fault, recompute):
    """(MoE(u), the router's own choice [B, T, k]) on u [B, T, H]."""
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])     # [B, T, width]
    bias = p["router"]["bias"]
    own = jax.lax.top_k(scores + bias, config["num_experts_per_tok"])[1]
    idx = own if choice is None else choice
    if fault.get("expert_bias_in_weights"):
        scores = scores + bias
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + _ROUTER_EPS) \
        * config["routed_scaling_factor"]

    def one_expert(acc, ep):
        e, weights = ep
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)   # 0 or w_e
        return acc + mine[..., None] * _swiglu(u, weights), None

    if recompute:
        one_expert = jax.checkpoint(one_expert)
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          (jnp.arange(held[0], held[1]), p["experts"]))
    return out, own


def reference_forward(params, ids, config, *, experts_held=None, choice=None,
                      recompute: bool = False, **fault):
    """(logits [B, T, vocab] float32, the router's own choice
    [expert layers, B, T, k]) for int32 `ids` [B, T]."""
    eps = config["norm_eps"]
    held = tuple(experts_held or config["experts_held"])
    mixers = {"conv": _conv, "gqa": _gqa}
    low = fault.get("reference_bfloat16")
    dtype = jnp.bfloat16 if low else jnp.float32

    def block(mixer, ffn):
        def apply(h, p_and_choice):
            p, layer_choice = p_and_choice
            h = h + mixers[mixer](_rms_norm(h, p["attn_norm"], eps),
                                  p["attn"], config, fault, recompute)
            u = _rms_norm(h, p["ffn_norm"], eps)
            if ffn == "dense":
                return h + _swiglu(u, p["ffn"], "ffn_"), None
            out, own = _moe(u, p["ffn"], config, held, layer_choice, fault,
                            recompute)
            return h + out, own
        return jax.checkpoint(apply) if recompute else apply

    with jax.default_matmul_precision("default" if low else "highest"):
        params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype),
                                        params)
        emb = params["word_embeddings"]
        h = emb[jnp.asarray(ids, jnp.int32)]
        if choice is not None:
            choice = jnp.asarray(choice, jnp.int32)
        own, seen = [], 0
        for name, mixer, ffn, n in layer_runs(config):
            mine = None
            if ffn == "moe" and choice is not None:
                mine = choice[seen:seen + n]
            h, chosen = jax.lax.scan(block(mixer, ffn), h,
                                     (params[name], mine))
            if ffn == "moe":
                own.append(chosen)
                seen += n
        logits = _rms_norm(h, params["final_norm"], eps) @ emb.T
        return logits.astype(jnp.float32), jnp.concatenate(own)


def reference_loss_and_choice(params, batch, config, **kw):
    """(mean next-token cross-entropy over the vocabulary the tree holds,
    the router's own choice) on one training batch `{"x": ids [B, T], "y":
    next ids [B, T]}`, float32: what `jax.value_and_grad(..., has_aux=True)`
    of the training-step check differentiates."""
    logits, own = reference_forward(params, batch["x"], config,
                                    recompute=True, **kw)
    logp = jax.nn.log_softmax(logits, axis=-1)
    y = jnp.asarray(batch["y"], jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1)), own


def reference_loss(params, batch, config, **kw):
    return reference_loss_and_choice(params, batch, config, **kw)[0]
