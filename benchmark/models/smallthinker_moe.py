"""Model family `smallthinker_moe`: how the benchmark builds
`models/moe_decoder.MoEDecoderLM` with SmallThinker's layer pattern (a
global attention layer with no positions, NoPE, then sliding-window
attention layers with rotary, as `sliding_window_layout` and `rope_layout`
give them; every layer an expert layer of ReGLU experts without a shared
one, routed by a softmax over all experts that reads the block's
normalised input, before attention; an untied head) from a configuration
file, makes its weights and token data from a seed, and checks it against
the plain reference. The configuration's layouts are the published lists
of all layers; the family applies the first `num_hidden_layers`. As in
`lfm2_moe.py` a configuration may be ONE chip's share of an expert-parallel
deployment (`moe_num_primary_experts` counts the experts held here,
`experts_held` names their range, `router_width` the published count the
router still has, `vocab_size` the slice held), and the expert choice is
compared the same way: the forward check routes freely on both sides and
counts the agreement, the training-step check hands the reference the
step's own choice, which the stepped model's routing tells the host, and
holds the agreement to a floor. The same functions as `lfm2_moe.py`, so
`runners/fit.py` runs it as it stands; nothing here names a
configuration."""

from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.models import kanana_moe, ouro_lm
from benchmark.reference import smallthinker_moe as reference

fit_data = ouro_lm.fit_data
step_batch = ouro_lm.step_batch
check_inputs = ouro_lm.check_inputs
system_outputs = kanana_moe.system_outputs


# the embedding's standard deviation at the seed's weights, where every
# other matrix is drawn at normal(0.02) (the configuration's
# `assumed.initializer` says why)
EMBEDDING_STD = 1.0


def init_params(model, key):
    """`ouro_lm.init_params` with the embedding drawn at normal(1.0): at
    normal(0.02) the seed's attention layers, a near-even average over
    thousands of keys with a gain of about 1.2, swamp each token's own
    direction in the residual stream from the second layer on, and the
    whole sequence routes to one set of six experts that the seed picks,
    so the held experts' work swings between a sixth and a third of the
    token-slots from seed to seed. One jitted call, as there."""
    import jax

    def build(k):
        p = model.build(k)
        # `MoEDecoderLM.build` draws the embedding at normal(0.02)
        return dict(p, word_embeddings=p["word_embeddings"]
                    * (EMBEDDING_STD / 0.02))

    return jax.jit(build)(key)


def _mixers(config):
    return ["window" if w else "global" for w in
            config["sliding_window_layout"][:config["num_hidden_layers"]]]


def build(config, traffic):
    from analytics_zoo_tpu.models.moe_decoder import MoEDecoderLM
    first, end = config["experts_held"]
    layout = config["sliding_window_layout"]
    if end - first != config["moe_num_primary_experts"] \
            or not config["norm_topk_prob"] \
            or not config["moe_primary_router_apply_softmax"] \
            or config["tie_word_embeddings"] \
            or config["rope_layout"] != layout or set(layout) - {0, 1} \
            or config["rope_scaling"] is not None \
            or config["hidden_act"] != "relu":
        raise ValueError("smallthinker_moe: experts_held must span the "
                         "moe_num_primary_experts held here, and the family "
                         "has softmax scores normalised over the chosen, "
                         "ReGLU experts, an untied head, rotary exactly on "
                         "the sliding-window layers and no rotary scaling")
    return MoEDecoderLM(
        vocab=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"], kv_lora_rank=0,
        qk_nope_head_dim=0, qk_rope_head_dim=config["head_dim"],
        v_head_dim=config["head_dim"], intermediate_size=0,
        moe_intermediate_size=config["moe_ffn_hidden_size"],
        n_routed_experts=config["router_width"],
        num_experts_per_tok=config["moe_num_active_primary_experts"],
        n_shared_experts=0, n_dense_layer=0, experts_held=(first, end),
        rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"],
        hidden_act="relu", mixers=_mixers(config),
        gqa={"n_kv_head": config["num_key_value_heads"],
             "head_dim": config["head_dim"], "qk_norm": False,
             "window": config["sliding_window_size"]},
        router_score="softmax", route_before_attention=True,
        **traffic.get("model_kwargs", {}))


def _held_share(config):
    """Routed experts a token is expected to find here under even
    routing: k x held / router width (1.5 at 6 x 16 / 64)."""
    return config["moe_num_active_primary_experts"] \
        * config["moe_num_primary_experts"] / config["router_width"]


def _attention_params(config):
    """q and o H x heads d, k and v H x kv heads d."""
    H, d = config["hidden_size"], config["head_dim"]
    return 2 * H * config["num_attention_heads"] * d \
        + 2 * H * config["num_key_value_heads"] * d


def band_pairs(T, window):
    """(query, key) pairs a sliding-window layer attends over T positions:
    row i sees min(i + 1, window) keys."""
    w = min(window, T)
    return w * (w + 1) // 2 + (T - w) * w


def flops_per_sample(config, traffic):
    """Forward+backward FLOPs the algorithm needs for one sequence: 6 per
    matmul weight per token in every layer's q, k, v and o and router, and
    in the EXPECTED k x held / width (1.5) held routed experts a token,
    which is what even routing sends here; the untied head over the
    vocabulary held, once; the attention's 6 products (scores, context;
    scores again, dW, dQ, dK, dV counted as the other language cells count
    theirs: 6) over the global layers' lower triangle, T^2 / 2, and the
    window layers' band (`band_pairs`), 2 x heads x d each a pair.
    Recomputation, the embedding gather, norms, rotary, softmax, top-k,
    sort and gathers are not counted, so a share of the peak made from
    this cannot read over 100%."""
    T, H = traffic["seq_len"], config["hidden_size"]
    mixers = _mixers(config)
    L = config["num_hidden_layers"]
    per_layer = _attention_params(config) + H * config["router_width"] \
        + 3 * H * config["moe_ffn_hidden_size"] * _held_share(config)
    weights = L * per_layer + H * config["vocab_size"]
    pairs = mixers.count("global") * T * T / 2 + mixers.count("window") \
        * band_pairs(T, config["sliding_window_size"])
    attention = 12.0 * pairs * config["num_attention_heads"] \
        * config["head_dim"]
    return 6.0 * T * weights + attention


def window_attention_work(config, traffic):
    """What the sliding-window attention of one sequence needs
    forward+backward in the model's window layers, however it is computed:
    `flops` = 2 products forward (scores, context) and 5 backward (scores
    again, dW, dQ, dK, dV) over the band's pairs (`band_pairs`), 2 x d
    each a query head; `bytes` = the least a kernel that keeps the scores
    on the chip moves through HBM in bfloat16, K and V and their gradients
    ONCE A K/V HEAD: forward q, k, v read and O written, backward q, k, v,
    O, dO read and dq, dk, dv written; the float32 log-sum-exp row once
    written and once read. The program computes the tiles an edge of the
    band crosses whole and writes dK and dV a query head in float32 before
    a group's sum: neither is counted, so a share of the roofline made from
    these reads low, never high."""
    T, d = traffic["seq_len"], config["head_dim"]
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    L = _mixers(config).count("window")
    pairs = band_pairs(T, config["sliding_window_size"])
    per_layer_bytes = 2.0 * T * d * (2 * n + 2 * kv) \
        + 2.0 * T * d * (4 * n + 4 * kv) + 2 * 4.0 * T * n
    return {"flops": 14.0 * L * pairs * n * d,
            "bytes": L * per_layer_bytes}


def experts_work(config, traffic):
    """`kanana_moe.experts_work` for this family: what the held routed
    experts of one sequence need forward+backward under EVEN routing, rows
    = T x k x held / width token-slots a layer (1.5 T at 6 x 16 / 64),
    each through three matrices of H x I forward and both gradients,
    `flops` = 18 H I a row; `bytes` = the held experts' weights read once
    forward and once backward and the rows in and out of the layer,
    bfloat16. A run's own row count differs by `swa_moe_held_slot_share`
    / 25; the intermediates, the weights' gradient written and the
    recomputed forward are not counted."""
    T, H, I = traffic["seq_len"], config["hidden_size"], \
        config["moe_ffn_hidden_size"]
    L = config["num_hidden_layers"]
    rows = T * _held_share(config)
    weights = config["moe_num_primary_experts"] * 3 * H * I
    return {"flops": 18.0 * L * rows * H * I,
            "bytes": 2.0 * L * (2 * weights / traffic["batch_size"]
                                + 5 * rows * H)}


def kernel_work_per_sample(config, traffic):
    return {"window_attention": window_attention_work(config, traffic),
            "experts": experts_work(config, traffic)}


# the traffic the stepped model was last built from (`without_dropout`),
# for `_second_program_choice` to build that model again, and every choice
# the stepped model's routing has told the host since, in the order told
_step_traffic = None
_told = []


def without_dropout(model, config, traffic):
    """The model of the training-step check: a second build of the same
    model (there is no dropout rate to zero) whose routing TELLS THE HOST
    each choice it makes (`lfm2_moe.without_dropout`'s arrangement). The
    timed model is not this one."""
    global _step_traffic
    import jax
    _step_traffic = traffic
    stepped = build(config, traffic)
    routing = stepped.moe.routing
    del _told[:]

    def telling(params, u):
        experts, weights = routing(params, u)
        jax.debug.callback(lambda e: _told.append(np.asarray(e)), experts)
        return experts, weights

    stepped.moe.routing = telling
    return stepped


def reference_outputs(params, x, config, **fault):
    """The plain reference on `x`, as one jitted program, routing freely;
    the share of token-slots on which its choice is the system's goes to
    an earlier line, layer by layer."""
    import jax
    logits, own = jax.jit(lambda p, a: reference.reference_forward(
        p, a, config, **fault))(params, x)
    own, system = np.asarray(own), kanana_moe._system_choice
    if not fault and system is not None and system.shape == own.shape:
        harness.log("moe_choice_agreement_by_layer " + " ".join(
            f"{v:.5f}" for v in kanana_moe._agreement(system, own)))
    return np.asarray(logits)


def _second_program_choice(params, ids, config):
    """`lfm2_moe._second_program_choice`: `expert_choice` of the model as
    the step check builds it, on the bfloat16 copies the step sees under
    `mixed_precision`."""
    import jax
    import jax.numpy as jnp
    model = build(config, _step_traffic)
    mixed = _step_traffic.get("fit_kwargs", {}).get("mixed_precision")

    def choice(p, a):
        if mixed:
            p = jax.tree_util.tree_map(
                lambda v: v.astype(jnp.bfloat16)
                if v.dtype == jnp.float32 else v, p)
        return model.expert_choice(p, a)

    return np.asarray(jax.jit(choice)(params, ids))


def _step_choice(params, ids, config):
    """The expert choice [expert layers, n, T, k] of the system's training
    step on `ids`, as the step's own routing told it
    (`lfm2_moe._step_choice`, whose words hold here)."""
    import jax
    second = _second_program_choice(params, ids, config)
    jax.effects_barrier()
    layers, n, T, k = second.shape
    own = second.copy().reshape(layers * n, T, k)
    candidates = second.reshape(layers * n, T, k)
    tellings = [0] * layers
    for told in list(_told):
        if told.shape != (n * T, k):
            continue
        for sequence in told.reshape(n, T, k):
            at = int(np.argmax(kanana_moe._agreement(
                candidates, [sequence] * len(candidates))))
            own[at] = sequence
            tellings[at // n] += 1
    own = own.reshape(second.shape)
    harness.log("moe_step_choice sequences_told_by_layer="
                + ",".join(map(str, tellings))
                + " slots_the_second_program_chose_otherwise_by_layer="
                + " ".join(f"{1 - v:.5f}" for v in kanana_moe._agreement(
                    own.reshape(layers, -1, k),
                    second.reshape(layers, -1, k))))
    return own


def reference_loss_and_grads(params, batch, config, **fault):
    """float32 `jax.value_and_grad` of the plain reference's loss AT THE
    TRAINING STEP'S OWN EXPERT CHOICE, one sequence at a time and averaged
    on the host, the agreement of the reference's own router printed layer
    by layer and held to `reference_check.choice_agreement_floor`
    (`lfm2_moe.reference_loss_and_grads`)."""
    import gc
    import jax
    gc.collect()
    choice = _step_choice(params, batch["x"], config)
    one = jax.jit(jax.value_and_grad(
        lambda p, b, c: reference.reference_loss_and_choice(
            p, b, config, choice=c, **fault), has_aux=True))
    n = len(batch["x"])
    loss, grads, own = 0.0, None, []
    for i in range(n):
        (seq_loss, seq_own), seq_grads = jax.device_get(one(
            params, {k: v[i:i + 1] for k, v in batch.items()},
            choice[:, i:i + 1]))
        loss += float(seq_loss) / n
        own.append(seq_own)
        seq_grads = jax.tree_util.tree_map(lambda g: g / n, seq_grads)
        grads = seq_grads if grads is None else jax.tree_util.tree_map(
            np.add, grads, seq_grads)
    agree = kanana_moe._agreement(choice, np.concatenate(own, axis=1))
    floor = config["reference_check"]["choice_agreement_floor"]
    if not fault:
        harness.log("moe_step_choice_agreement_by_layer "
                    + " ".join(f"{v:.5f}" for v in agree)
                    + f" floor={floor}")
    return (loss if min(agree) >= floor else float("nan")), grads


# faults of the reference that the forward check tells on the chip (the
# configuration's `reference_check.why` has the readings), which
# `selfcheck.py` holds every entry to failing
FAULTS = {name: {name: True} for name in (
    "router_after_attention", "sigmoid_router", "window_dropped",
    "reference_bfloat16")}
