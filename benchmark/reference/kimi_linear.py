"""Plain reference of the hybrid linear-attention expert language model
(family `kimi_linear`, `model_type` `kimi_linear`): forward, loss and,
through `jax.value_and_grad`, gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, following the equations of ISSUE
32 (what the published config does not fix is listed in the
configuration's file under `assumed`). `x` is [T, H]:

    RMSNorm(x)  = x / sqrt(mean(x^2) + eps) * w
    KDA(x), heads h, widths d_k (queries, keys) and d_v (values):
      q, k, v  = SiLU(conv(x Wq)), SiLU(conv(x Wk)), SiLU(conv(x Wv))
                 conv: causal, depthwise (one filter of 4 taps a channel),
                 over time, zero history before token 0, no bias
      q = q / ||q||_2 * d_k^-0.5 ;  k = k / ||k||_2     (per head and token;
                 ||.||_2 = sqrt(sum of squares + 1e-6))
      g        = -exp(A_log[h]) * softplus((x Wfa) Wfb + dt_bias)
                 [T, heads, d_k], <= 0: the per-CHANNEL log-decay;
                 alpha = exp(g)
      beta     = sigmoid(x Wb)                            [T, heads]
      per head, S_0 = 0 in R^{d_k x d_v}:
          S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
          o_t = S_t^T q_t
      gate     = (x Wga) Wgb + b_g                        [T, heads, d_v]
      y_t      = RMSNorm_{d_v}(o_t) * sigmoid(gate_t)     (one weight [d_v])
      out      = y Wo
    MLA(x):  q        = x Wq                 -> [T, heads, nope + rope]
             [c | kr] = x Wkva               -> [T, rank], [T, rope]  (kr:
                                                ONE head for all heads)
             [k_nope | v] = RMSNorm_kv(c) Wkvb
             k = [k_nope | kr for every head]
             Attn = softmax(q k^T / sqrt(nope + rope) + causal) v;  out = Attn Wo
             NO rotary (`mla_use_nope`): the `rope` columns enter the
             scores as they are
    Router, MoE, SwiGLU: as `benchmark/reference/kanana_moe.py` (sigmoid
             scores, top-k of score + bias, weights normalised over the k
             chosen and scaled; shared expert + the chosen experts HELD HERE)
    Block_l(h):  h = h + Mixer_l(RMSNorm_1(h));  h = h + FFN_l(RMSNorm_2(h))
             Mixer_l = MLA where l (from 1) is in
             `linear_attn_config.full_attn_layers`, else KDA; FFN_l = SwiGLU
             for l <= first_k_dense_replace, else MoE
    Model(ids):  h = E[ids];  blocks;  logits = RMSNorm_f(h) W_head
    Loss:        mean next-token cross-entropy over the (sliced) vocabulary

KDA here is the TOKEN-BY-TOKEN recurrence exactly as written: a `lax.scan`
over T that carries S; no chunks, no triangular solve, nothing of the
system's algebra (`analytics_zoo_tpu/pallas/delta_rule.py`). It calls
`benchmark/reference/kanana_moe.py` for the norm, SwiGLU and the expert
layer, and imports nothing from `analytics_zoo_tpu/keras/`: it only reads
the parameter tree by name (a run of neighbouring layers of one kind is one
stacked [n, ...] subtree `blocks_<first layer>_<mixer>_<ffn>`, walked by a
`lax.scan`). Departures that change no number: the scan over T is nested
(an outer scan over blocks of 128 tokens, the inner one a
`jax.checkpoint` in `reference_loss`, so that `value_and_grad` at
T = 16,384 keeps 128 states of 2 MB a layer and not 16,384; 8 tokens'
bodies are written out a trip of the inner loop; a step's two
contractions over d_k, k_t^T S and S^T q_t, are a product and a sum in
float32 and not 32 one-row matrix products a step, which took the chip
most of the 100 s a gradient of the first version needed); the latent
layer's causal mask is applied head by head and, within a head, 4096
queries at a time (a head's 16,384 x 16,384 float32 scores are 1 GB); a
layer is a `jax.checkpoint` in `reference_loss`; the held experts are a
loop over all tokens with a 0-or-weight mask (kanana's).

`experts_held`, `choice`: as kanana's reference (the range of the router's
experts the tree holds; indices that replace the router's own top-k, never
its scores or weights).

The faults exist so that the check that the comparison CAN fail has
something to break: `decay_dropped` (alpha = 1), `decay_per_head` (alpha
replaced by its mean over a head's channels: the scalar-gate delta rule
this model is NOT), `beta_dropped` (beta = 1), `short_conv_dropped` (the
convolution the identity), `qk_norm_dropped`, `out_gate_dropped`
(sigmoid(gate) = 1), `rotary_applied` (the latent layer with kanana's
rotary), `shared_experts_dropped`, `causal_mask_dropped`."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.kanana_moe import _moe, _rms_norm, _rotary, _swiglu

_TOKENS_PER_BLOCK = 128         # of the nested scan over T
_TOKENS_UNROLLED = 8            # loop bodies written out: fewer, longer trips
_QUERIES_AT_ONCE = 4096         # of a head of the latent layer


def layer_runs(config):
    """[(subtree name, "linear" | "latent", "dense" | "moe", layers)] in the
    layers' order: neighbouring layers of one kind are one stacked subtree."""
    full = set(config["linear_attn_config"]["full_attn_layers"])
    kinds = [("latent" if l in full else "linear",
              "dense" if l <= config["first_k_dense_replace"] else "moe")
             for l in range(1, config["num_hidden_layers"] + 1)]
    runs, first = [], 0
    for l in range(1, len(kinds) + 1):
        if l == len(kinds) or kinds[l] != kinds[first]:
            runs.append((f"blocks_{first}_{kinds[first][0]}_{kinds[first][1]}",
                         *kinds[first], l - first))
            first = l
    return runs


def _conv(x, taps):
    """Causal depthwise convolution over time: x [B, T, C], taps [K, C]."""
    K, T = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, i:i + T] * taps[i] for i in range(K))


def _delta_rule(q, k, v, alpha, beta, recompute):
    """The recurrence, token by token: q, k, alpha [B, T, n, dk], v
    [B, T, n, dv], beta [B, T, n] -> o [B, T, n, dv]."""
    B, T, n, dk = q.shape
    block = max(b for b in range(1, _TOKENS_PER_BLOCK + 1) if T % b == 0)

    def token(S, x):                                    # S [B, n, dk, dv]
        q_t, k_t, v_t, a_t, b_t = x
        S = a_t[..., None] * S
        u = b_t[..., None] * (v_t - jnp.sum(k_t[..., None] * S, axis=-2))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.sum(q_t[..., None] * S, axis=-2)

    def tokens(S, xs):
        return jax.lax.scan(token, S, xs, unroll=_TOKENS_UNROLLED)

    if recompute:
        tokens = jax.checkpoint(tokens)
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((T // block, block)
                                             + a.shape[:1] + a.shape[2:])
               for a in (q, k, v, alpha, beta))
    S0 = jnp.zeros((B, n, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(tokens, S0, xs)                 # [blocks, block, B, .]
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1)


def _kda(x, p, config, fault, recompute):
    lin = config["linear_attn_config"]
    n, dk = lin["num_heads"], lin["head_dim"]
    B, T, _ = x.shape

    def heads(a):
        return a.reshape(B, T, n, -1)

    def unit(a):
        if fault.get("qk_norm_dropped"):
            return a
        return a / jnp.sqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True)
                            + 1e-6)

    def short_conv(a, taps):
        if not fault.get("short_conv_dropped"):
            a = _conv(a, taps)
        return heads(jax.nn.silu(a))

    q = unit(short_conv(x @ p["q_kernel"], p["q_conv"])) * dk ** -0.5
    k = unit(short_conv(x @ p["k_kernel"], p["k_conv"]))
    v = short_conv(x @ p["v_kernel"], p["v_conv"])
    g = -jnp.exp(p["A_log"])[:, None] * heads(jax.nn.softplus(
        (x @ p["decay_a_kernel"]) @ p["decay_b_kernel"] + p["dt_bias"]))
    alpha = jnp.exp(g)
    if fault.get("decay_dropped"):
        alpha = jnp.ones_like(alpha)
    if fault.get("decay_per_head"):
        alpha = jnp.broadcast_to(jnp.mean(alpha, axis=-1, keepdims=True),
                                 alpha.shape)
    beta = jax.nn.sigmoid(x @ p["beta_kernel"])
    if fault.get("beta_dropped"):
        beta = jnp.ones_like(beta)
    o = _delta_rule(q, k, v, alpha, beta, recompute)
    y = _rms_norm(o, p["out_norm"], config["rms_norm_eps"])
    if not fault.get("out_gate_dropped"):
        y = y * jax.nn.sigmoid(heads(
            (x @ p["gate_a_kernel"]) @ p["gate_b_kernel"] + p["gate_bias"]))
    return y.reshape(B, T, -1) @ p["out_kernel"]


def _mla(x, p, config, fault, recompute):
    n = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank = config["kv_lora_rank"]
    B, T, _ = x.shape
    Tq = max(b for b in range(1, _QUERIES_AT_ONCE + 1) if T % b == 0)

    def heads(a):                       # [B, T, n * w] -> [n, B, T, w]
        return a.reshape(B, T, n, -1).transpose(2, 0, 1, 3)

    q = heads(x @ p["q_kernel"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    ckr = x @ p["kv_a_kernel"]
    c, kr = _rms_norm(ckr[..., :rank], p["kv_norm"],
                      config["rms_norm_eps"]), ckr[..., rank:]
    if fault.get("rotary_applied"):
        q_rope = _rotary(q_rope, config["rope_theta"])
        kr = _rotary(kr, config["rope_theta"])
    kv = heads(c @ p["kv_b_kernel"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    cols = jnp.arange(T)

    def one_head(qkv):
        qn, qr, kn, vv = qkv                            # [B, T, .]

        def some_queries(block):
            first, qn_b, qr_b = block                   # [B, Tq, .]
            scores = (jnp.einsum("bqd,bkd->bqk", qn_b, kn)
                      + jnp.einsum("bqd,bkd->bqk", qr_b, kr)) \
                / math.sqrt(nope + rope)
            if not fault.get("causal_mask_dropped"):
                rows = first + jnp.arange(Tq)
                scores = jnp.where(cols[None, :] <= rows[:, None], scores,
                                   -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd",
                              jax.nn.softmax(scores, axis=-1), vv)

        def blocks(a):                  # [B, T, w] -> [T / Tq, B, Tq, w]
            return a.reshape(B, T // Tq, Tq, -1).transpose(1, 0, 2, 3)

        out = jax.lax.map(jax.checkpoint(some_queries) if recompute
                          else some_queries,
                          (jnp.arange(0, T, Tq), blocks(qn), blocks(qr)))
        return out.transpose(1, 0, 2, 3).reshape(B, T, -1)

    ctx = jax.lax.map(jax.checkpoint(one_head) if recompute else one_head,
                      (q_nope, q_rope, k_nope, v))      # [n, B, T, v]
    return ctx.transpose(1, 2, 0, 3).reshape(B, T, -1) @ p["out_kernel"]


def reference_forward(params, ids, config, *, experts_held=None, choice=None,
                      recompute: bool = False, **fault):
    """(logits [B, T, vocab] float32, the router's own choice
    [expert layers, B, T, k]) for int32 `ids` [B, T]."""
    eps = config["rms_norm_eps"]
    held = tuple(experts_held or config["experts_held"])
    moe_config = dict(config, num_experts_per_tok=config[
        "num_experts_per_token"])
    mixers = {"linear": _kda, "latent": _mla}

    def block(mixer, ffn):
        def apply(h, p_and_choice):
            p, layer_choice = p_and_choice
            h = h + mixers[mixer](_rms_norm(h, p["attn_norm"], eps),
                                  p["attn"], config, fault, recompute)
            u = _rms_norm(h, p["ffn_norm"], eps)
            if ffn == "dense":
                return h + _swiglu(u, p["ffn"], "ffn_"), None
            out, own = _moe(u, p["ffn"], moe_config, held, layer_choice,
                            fault, recompute)
            return h + out, own
        return jax.checkpoint(apply) if recompute else apply

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        h = params["word_embeddings"][jnp.asarray(ids, jnp.int32)]
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
        return _rms_norm(h, params["final_norm"], eps) \
            @ params["lm_head_kernel"], jnp.concatenate(own)


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
