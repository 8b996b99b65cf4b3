"""Plain reference of the looped causal language model (family
`ouro_lm`): forward, loss and, through `jax.value_and_grad`, gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, following the equations of the
configuration's file (ISSUE 26 wrote them out; what the published config
does not fix is listed there under `assumed`). `x` is [T, H]:

    RMSNorm(x)  = x / sqrt(mean(x^2) + eps) * g
    Attn(x)     = softmax(q k^T / sqrt(Dh) + causal) v  Wo,   q, k, v = x Wq,
                  x Wk, x Wv with rotary positions on q and k over all Dh
                  dimensions (theta, rotate-half pairing i with i + Dh/2)
    FFN(u)      = Wdown( silu(Wgate u) * (Wup u) )
    Block(h)    : h = h + RMSNorm_2(Attn(RMSNorm_1(h)))
                  h = h + RMSNorm_4(FFN(RMSNorm_3(h)))
    Model(ids)  : h = E[ids]; for r in 1..R: h = RMSNorm_f(Block_N(..
                  Block_1(h))), gate_r = sigmoid(h w_g + b_g);
                  logits = h W_head after pass R

No bias in any projection, no dropout, a materialised [T, T] causal mask,
no kernels, no cache. Independent of `analytics_zoo_tpu/keras/`: it only
reads the parameter tree by name (the fused [H, 3H] QKV matrix is split
into its three parts; the stacked [N, ...] block leaves are walked by a
`lax.scan`, and so are the passes).

The one departure, in `reference_loss` alone: every layer application is a
`jax.checkpoint`, so that `value_and_grad` of one 4096-token sequence
through 32 applications fits the chip beside the system's own parameters
(unrecomputed it holds about 19 GB). The numbers are the same.

The three faults exist so that the check that the comparison CAN fail has
something to break: `drop_pass` runs one pass fewer, `final_norm_once`
takes the final norm out of the loop (applied after the last pass only),
`no_causal_mask` lets every query see every key."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, p, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * p["gamma"]


def _rotate(x, theta):
    """Rotary positions on x [B, heads, T, Dh]."""
    T, dh = x.shape[-2], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _block(h, p, config, no_causal_mask):
    n_head = config["num_attention_heads"]
    head = config["head_dim"]
    eps = config["rms_norm_eps"]
    B, T, hidden = h.shape

    def heads(x):
        return x.reshape(B, T, n_head, head).transpose(0, 2, 1, 3)

    u = _rms_norm(h, p["attn_in_norm"], eps)
    wq, wk, wv = jnp.split(p["attn"]["qkv_kernel"], 3, axis=1)
    q = _rotate(heads(u @ wq), config["rope_theta"])
    k = _rotate(heads(u @ wk), config["rope_theta"])
    v = heads(u @ wv)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(head)
    if not no_causal_mask:
        seen = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(seen, scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, hidden)
    h = h + _rms_norm(ctx @ p["attn"]["out_kernel"], p["attn_out_norm"], eps)
    u = _rms_norm(h, p["ffn_in_norm"], eps)
    f = (jax.nn.silu(u @ p["ffn_gate_kernel"]) * (u @ p["ffn_up_kernel"])) \
        @ p["ffn_down_kernel"]
    return h + _rms_norm(f, p["ffn_out_norm"], eps)


def reference_forward(params, ids, config, *, drop_pass: bool = False,
                      final_norm_once: bool = False,
                      no_causal_mask: bool = False, recompute: bool = False):
    """(logits [B, T, vocab], exit gates [B, T, passes]) in float32 for
    int32 `ids` [B, T]."""
    eps = config["rms_norm_eps"]
    n_pass = config["total_ut_steps"] - (1 if drop_pass else 0)
    block = lambda h, p: _block(h, p, config, no_causal_mask)  # noqa: E731
    if recompute:
        block = jax.checkpoint(block)
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        h = params["word_embeddings"][jnp.asarray(ids, jnp.int32)]

        def one_pass(h, _):
            # Block_N(... Block_1(h)), a scan over the stacked [N, ...]
            # leaves
            h, _ = jax.lax.scan(lambda a, p: (block(a, p), None), h,
                                params["blocks"])
            if not final_norm_once:
                h = _rms_norm(h, params["final_norm"], eps)
            gate = jax.nn.sigmoid(h @ params["exit_gate"]["kernel"]
                                  + params["exit_gate"]["bias"])
            return h, gate[..., 0]

        # the passes are a scan too: the program holds one block, and the
        # weights' gradients add up pass by pass and not all at the end
        h, gates = jax.lax.scan(one_pass, h, None, length=n_pass)
        if final_norm_once:     # a faulty model's gates are not compared
            h = _rms_norm(h, params["final_norm"], eps)
        return h @ params["lm_head_kernel"], jnp.moveaxis(gates, 0, -1)


def reference_logits(params, ids, config, **fault):
    return reference_forward(params, ids, config, **fault)[0]


def reference_loss(params, batch, config, **fault):
    """Mean next-token cross-entropy of the last pass's logits on one
    training batch `{"x": ids [B, T], "y": next ids [B, T]}`, float32:
    what `jax.value_and_grad` of the training-step check differentiates."""
    logits = reference_logits(params, batch["x"], config, recompute=True,
                              **fault)
    logp = jax.nn.log_softmax(logits, axis=-1)
    y = jnp.asarray(batch["y"], jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))
