"""Plain reference of the latent-attention expert language model (family
`kanana_moe`): forward, loss and, through `jax.value_and_grad`, gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, following the equations of ISSUE
30 (what the published config does not fix is listed in the
configuration's file under `assumed`). `x` is [T, H]:

    RMSNorm(x)   = x / sqrt(mean(x^2) + eps) * g
    MLA(x):  q        = x Wq                 -> [T, heads, nope + rope]
             [c | kr] = x Wkva               -> [T, rank], [T, rope]  (kr:
                                                ONE head for all heads)
             [k_nope | v] = RMSNorm_kv(c) Wkvb -> [T, heads, nope], [T, heads, v]
             q_rope, kr = rotary(theta) over the rope columns, pairs
                          (2i, 2i + 1) (rope_interleave), written out as
                          [turned first members | turned second members]
             k = [k_nope | kr for every head];  q = [q_nope | q_rope]
             Attn = softmax(q k^T / sqrt(nope + rope) + causal) v
             out  = Attn Wo                     (no bias anywhere)
    SwiGLU_w(u)  = Wdown( silu(Wgate u) * (Wup u) )
    Router(u):   s = sigmoid(u Wg), [T, router width]
                 choice = top-k of (s + b)      (the bias chooses, it does
                                                 not weigh)
                 w_e = s_e / (sum over the k chosen of s + 1e-20) * scale
    MoE(u)       = SwiGLU^shared(u) + sum over the chosen e HELD HERE of
                   w_e * SwiGLU^e(u)
    Block_0(h):  h = h + MLA(RMSNorm_1(h));  h = h + SwiGLU(RMSNorm_2(h))
    Block_l(h):  h = h + MLA(RMSNorm_1(h));  h = h + MoE(RMSNorm_2(h))
    Model(ids):  h = E[ids];  blocks;  logits = RMSNorm_f(h) W_head
    Loss:        mean next-token cross-entropy over the (sliced) vocabulary

`experts_held` = (first, end) is the range of the router's experts whose
weights the tree holds (one chip's share of an expert-parallel layer); what
the absent experts would add is left out, as in the system. With the whole
range it is the uncut layer.

No kernels, no grouped product, no cache. Independent of
`analytics_zoo_tpu/keras/`: it only reads the parameter tree by name (the
stacked [N, ...] block leaves are walked by a `lax.scan`). Departures that
change no number: the held experts are a loop (`lax.scan`) over ALL tokens
with a 0-or-weight mask per token; the causal mask is materialised [T, T]
and the attention computed head by head (`lax.map`: 32 heads x 8192 x 8192
float32 scores are 8.6 GB whole, one head is 268 MB), the score's two parts
summed (`q_nope k_nope^T + q_rope kr^T`) so that the shared key is never
repeated; and, in `reference_loss` alone, every layer, every head and every
expert of the loop is a `jax.checkpoint`, so that `value_and_grad` of one
8192-token sequence fits the chip beside the system's own parameters.

`choice`, where given ([expert layers, B, T, k] int32), replaces the
router's own top-k indices (never its scores or weights): the means to
compare at the SYSTEM's choice, where a last-bit difference upstream would
flip the sixth and seventh expert of a token. The family's training-step
check hands it over (`benchmark/models/kanana_moe.py`); the forward and
`reference_loss_and_choice` return the router's OWN choice beside their
result, so that the share that agrees is counted and held to a floor.

The faults exist so that the check that the comparison CAN fail has
something to break: `shared_experts_dropped`, `routed_scale_dropped`
(the routed scaling factor left out), `rope_key_dropped` (the shared
rotary key zeroed), `causal_mask_dropped`, `kv_norm_dropped`,
`held_expert_dropped` (in every expert layer the held expert that most
token-slots chose adds nothing: one group of the grouped products lost,
the one a capacity limit would cut first)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, p, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * p["gamma"]


def _rotary(x, theta):
    """Rotary positions on x [..., T, d], neighbouring pairs (2i, 2i+1)."""
    T, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def _swiglu(u, p, prefix=""):
    return (jax.nn.silu(u @ p[prefix + "gate_kernel"])
            * (u @ p[prefix + "up_kernel"])) @ p[prefix + "down_kernel"]


def _mla(x, p, config, fault, recompute):
    n = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, theta = config["kv_lora_rank"], config["rope_theta"]
    B, T, _ = x.shape

    def heads(a):                       # [B, T, n * w] -> [n, B, T, w]
        return a.reshape(B, T, n, -1).transpose(2, 0, 1, 3)

    q = heads(x @ p["q_kernel"])
    q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], theta)
    ckr = x @ p["kv_a_kernel"]
    c, kr = ckr[..., :rank], _rotary(ckr[..., rank:], theta)    # [B, T, .]
    if not fault.get("kv_norm_dropped"):
        c = _rms_norm(c, p["kv_norm"], config["rms_norm_eps"])
    if fault.get("rope_key_dropped"):
        kr = jnp.zeros_like(kr)
    kv = heads(c @ p["kv_b_kernel"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    seen = jnp.tril(jnp.ones((T, T), bool))

    def one_head(qkv):
        qn, qr, kn, vv = qkv                            # [B, T, .]
        scores = (jnp.einsum("bqd,bkd->bqk", qn, kn)
                  + jnp.einsum("bqd,bkd->bqk", qr, kr)) \
            / math.sqrt(nope + rope)
        if not fault.get("causal_mask_dropped"):
            scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1),
                          vv)

    ctx = jax.lax.map(jax.checkpoint(one_head) if recompute else one_head,
                      (q_nope, q_rope, k_nope, v))      # [n, B, T, v]
    return ctx.transpose(1, 2, 0, 3).reshape(B, T, -1) @ p["out_kernel"]


def _moe(u, p, config, held, choice, fault, recompute):
    """(MoE(u), the choice [B, T, k]) on u [B, T, H]."""
    k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])     # [B, T, width]
    own = jax.lax.top_k(scores + p["router"]["bias"], k)[1]
    idx = own if choice is None else choice
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    if not fault.get("routed_scale_dropped"):
        w = w * config["routed_scaling_factor"]

    ids = jnp.arange(held[0], held[1])
    if fault.get("held_expert_dropped"):
        fullest = ids[jnp.argmax(jnp.sum(idx[..., None] == ids,
                                         axis=(0, 1, 2)))]
        w = jnp.where(idx == fullest, 0.0, w)

    def one_expert(acc, ep):
        e, weights = ep
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)   # 0 or w_e
        return acc + mine[..., None] * _swiglu(u, weights), None

    if recompute:
        one_expert = jax.checkpoint(one_expert)
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          (ids, p["experts"]))
    if "shared" in p and not fault.get("shared_experts_dropped"):
        out = out + _swiglu(u, p["shared"], "ffn_")
    return out, own


def reference_forward(params, ids, config, *, experts_held=None, choice=None,
                      recompute: bool = False, **fault):
    """(logits [B, T, vocab] float32, the router's own choice
    [expert layers, B, T, k]) for int32 `ids` [B, T]."""
    eps = config["rms_norm_eps"]
    held = tuple(experts_held or config["experts_held"])

    def attention(h, p):
        return h + _mla(_rms_norm(h, p["attn_norm"], eps), p["attn"],
                        config, fault, recompute)

    def dense_block(h, p):
        h = attention(h, p)
        return h + _swiglu(_rms_norm(h, p["ffn_norm"], eps), p["ffn"],
                           "ffn_"), None

    def moe_block(h, p_and_choice):
        p, layer_choice = p_and_choice
        h = attention(h, p)
        out, own = _moe(_rms_norm(h, p["ffn_norm"], eps), p["ffn"], config,
                        held, layer_choice, fault, recompute)
        return h + out, own

    if recompute:
        dense_block = jax.checkpoint(dense_block)
        moe_block = jax.checkpoint(moe_block)
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        h = params["word_embeddings"][jnp.asarray(ids, jnp.int32)]
        if "dense_blocks" in params:
            h, _ = jax.lax.scan(dense_block, h, params["dense_blocks"])
        if choice is not None:
            choice = jnp.asarray(choice, jnp.int32)
        h, own = jax.lax.scan(moe_block, h, (params["moe_blocks"], choice))
        return _rms_norm(h, params["final_norm"], eps) \
            @ params["lm_head_kernel"], own


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
