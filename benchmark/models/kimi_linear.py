"""Model family `kimi_linear`: how the benchmark builds
`models/moe_decoder.MoEDecoderLM` with a per-layer mixer pattern (Kimi
Delta Attention layers, three to one latent-attention layer without
rotary; a leading dense layer, then routed-expert layers with a shared
expert; `model_type` `kimi_linear`) from a configuration file, makes its
weights and token data from a seed, and checks it against the plain
reference. The configuration's `linear_attn_config` lists the layers of
each kind, counted from 1; the family applies the lists to layers 1 to
`num_hidden_layers`. As in `kanana_moe.py` a configuration may be ONE
chip's share of an expert-parallel deployment (`num_experts` counts the
experts held here, `experts_held` names their range, `router_width` the
published count the router still has, `vocab_size` the slice held), and
the expert choice is compared the same way: the forward check routes
freely on both sides and counts the agreement, the training-step check
hands the reference the step's own choice and holds the agreement to a
floor. The same functions as `kanana_moe.py`, so `runners/fit.py` runs it
as it stands; nothing here names a configuration."""

from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.models import kanana_moe, ouro_lm
from benchmark.reference import kimi_linear as reference

init_params = ouro_lm.init_params
fit_data = ouro_lm.fit_data
step_batch = ouro_lm.step_batch
check_inputs = ouro_lm.check_inputs


def _mixers(config):
    full = set(config["linear_attn_config"]["full_attn_layers"])
    return ["latent" if l in full else "linear"
            for l in range(1, config["num_hidden_layers"] + 1)]


def build(config, traffic):
    from analytics_zoo_tpu.models.moe_decoder import MoEDecoderLM
    first, end = config["experts_held"]
    lin = config["linear_attn_config"]
    layers = range(1, config["num_hidden_layers"] + 1)
    if end - first != config["num_experts"] \
            or config["moe_layer_freq"] != 1 \
            or config["num_expert_group"] != 1 \
            or config["q_lora_rank"] is not None \
            or config["moe_router_activation_func"] != "sigmoid" \
            or not config["moe_renormalize"] or not config["mla_use_nope"] \
            or any((l in lin["kda_layers"]) == (l in lin["full_attn_layers"])
                   for l in layers):
        raise ValueError("kimi_linear: experts_held must span the "
                         "num_experts held here, every layer must be "
                         "listed as a KDA or as a full-attention layer, "
                         "and the family has sigmoid scores renormalised "
                         "over the chosen, one group, every layer after "
                         "the dense ones an expert layer, no query latent "
                         "and latent attention without rotary")
    linear = dict(n_head=lin["num_heads"], head_dim=lin["head_dim"],
                  conv_size=lin["short_conv_kernel_size"],
                  chunk=config["linear_chunk"])
    if "v_head_dim" in lin:             # a rehearsal's: values not dk wide
        linear["v_head_dim"] = lin["v_head_dim"]
    return MoEDecoderLM(
        vocab=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=config["router_width"],
        num_experts_per_tok=config["num_experts_per_token"],
        n_shared_experts=config["num_shared_experts"],
        n_dense_layer=config["first_k_dense_replace"],
        experts_held=(first, end),
        routed_scaling_factor=config["routed_scaling_factor"],
        rope_theta=config["rope_theta"], rms_eps=config["rms_norm_eps"],
        hidden_act=config["hidden_act"], rotary=False,
        mixers=_mixers(config), linear_attention=linear,
        **traffic.get("model_kwargs", {}))


def _held_share(config):
    """Routed experts a token is expected to find here under even
    routing: k x held / router width (0.25 at 8 x 8 / 256)."""
    return config["num_experts_per_token"] * config["num_experts"] \
        / config["router_width"]


def _kda_widths(config):
    lin = config["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin.get("v_head_dim",
                                                      lin["head_dim"])


def _kda_params(config):
    """Matmul weights of one KDA layer: q, k, v and output projections, the
    two low-rank gates (rank = the head width) and beta; the 4-tap filters
    and the vectors are no matmuls."""
    H = config["hidden_size"]
    n, dk, dv = _kda_widths(config)
    return 2 * H * n * dk + 2 * H * n * dv \
        + dk * (2 * H + n * dk + n * dv) + H * n


def _latent_params(config):
    H, n = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return H * n * (nope + rope) + H * (config["kv_lora_rank"] + rope) \
        + config["kv_lora_rank"] * n * (nope + config["v_head_dim"]) \
        + n * config["v_head_dim"] * H


def flops_per_sample(config, traffic):
    """Forward+backward FLOPs the algorithm needs for one sequence: 6 per
    matmul weight per token in every mixer's projections (`_kda_params`,
    `_latent_params`), the dense layers' gated FFN, and in every expert
    layer the router, the shared expert and the EXPECTED k x held / width
    (0.25) held routed experts a token, which is what even routing sends
    here (a run's own share is the gauge `moe_held_slot_share`); the head
    over the vocabulary held, once; the latent layers' products on the
    lower triangle (`attention_work`) and the KDA layers' recurrence by its
    own count (`_recurrence_flops`). Recomputation, the chunked form's
    solve, the embedding gather, norms, convolutions, gates' elementwise parts,
    softmax, top-k, sort and gathers are not counted, so a share of the
    peak made from this cannot read over 100%."""
    T, H = traffic["seq_len"], config["hidden_size"]
    mixers = _mixers(config)
    n_dense = config["first_k_dense_replace"]
    n_moe = config["num_hidden_layers"] - n_dense
    I = config["moe_intermediate_size"]
    per_moe = H * config["router_width"] \
        + 3 * H * I * config["num_shared_experts"] \
        + 3 * H * I * _held_share(config)
    weights = mixers.count("linear") * _kda_params(config) \
        + mixers.count("latent") * _latent_params(config) \
        + n_dense * 3 * H * config["intermediate_size"] \
        + n_moe * per_moe + H * config["vocab_size"]
    return 6.0 * T * weights + attention_work(config, traffic)["flops"] \
        + _recurrence_flops(config, traffic)


def attention_work(config, traffic):
    """What the causal latent attention of one sequence needs
    forward+backward in the model's latent layers (one of five here):
    `kanana_moe.attention_work`'s count, `3 L T^2 heads x (qk width + v
    width)` operations and the least bfloat16 bytes with the shared key
    columns read once."""
    return kanana_moe.attention_work(
        dict(config, num_hidden_layers=_mixers(config).count("latent"),
             qk_head_dim=config["qk_nope_head_dim"]
             + config["qk_rope_head_dim"]), traffic)


def _recurrence_flops(config, traffic):
    """What the RECURRENCE of the KDA layers of one sequence needs
    forward+backward, whatever computes it: per token, head and layer
    7 dk dv operations forward (the decay dk dv, k^T S, the rank-one update
    and S^T q 2 dk dv each) and twice that backward."""
    n, dk, dv = _kda_widths(config)
    return 3.0 * 7 * dk * dv * traffic["seq_len"] * n \
        * _mixers(config).count("linear")


def kda_work(config, traffic):
    """What the chunk kernels' OWN job needs in the KDA layers of one
    sequence, each run once (`pallas/delta_rule.py`: `kda_chunk_fwd`,
    `kda_chunk_bwd`, chunks of C tokens), from their operands. Per token,
    head and layer, operations: forward three products against the state
    (2 dk dv each) and one inside the chunk (2 C dv), backward seven and
    two; `bytes`, the arrays in bfloat16: forward W, Q exp(G), K exp(G_C -
    G) (dk wide), U~ (dv), P (C) and the float32 exp(G_C) once a chunk
    read, O written; backward the six again, the state each chunk started
    from (dk x dv once a chunk) and dO read, five gradients and the
    decay's written. What prepares the operands (cumulative decay, the
    score-like matrices, the triangular solve: XLA's, under other names)
    is in NEITHER the work nor the matched time: this is the kernels'
    roofline, not the layer's. The forward kernel's second run in the
    backward pass (with the states written) is not counted, so the share
    cannot pass (fwd + bwd) / (2 fwd + states + bwd) = 70% at the cell's
    sizes; the bytes decide (11.5 ms a step against 4.0 of operations)."""
    n, dk, dv = _kda_widths(config)
    C = config["linear_chunk"]
    operands = 2 * (3 * dk + dv + C) + 4 * dk / C
    forward = operands + 2 * dv
    backward = 2 * operands + 2 * dk * dv / C + 2 * dv
    per_token_head = traffic["seq_len"] * n * _mixers(config).count("linear")
    return {"flops": 2.0 * dv * (10 * dk + 3 * C) * per_token_head,
            "bytes": float(forward + backward) * per_token_head}


def kernel_work_per_sample(config, traffic):
    return {"kda": kda_work(config, traffic)}


# the traffic the stepped model was last built from (`without_dropout`),
# for `_second_program_choice` to build that model again, and every choice
# the stepped model's routing has told the host since, in the order told
_step_traffic = None
_told = []


def without_dropout(model, config, traffic):
    """The model of the training-step check: a second build of the same
    model (there is no dropout rate to zero) whose routing TELLS THE HOST
    each choice it makes (`jax.debug.callback`: once a layer in the forward
    pass and once in its recomputation), so that the reference can be
    handed the choice of the training step itself (`_step_choice`). The
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


# the system's forward, every expert layer's choice beside it (kept in
# `kanana_moe._system_choice`) and the routing gauges set from it
system_outputs = kanana_moe.system_outputs


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
    """`kanana_moe._step_choice`: `expert_choice` of the model as the step
    check builds it, on the bfloat16 copies the step sees under
    `mixed_precision`. A SECOND program on the step's operands: XLA fuses
    its bfloat16 roundings otherwise than the training step's, so a few
    token-slots in a hundred choose otherwise."""
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
    step on `ids`, as the step's own routing told it (`without_dropout`).
    Each told sequence belongs to the (layer, sequence) of the second
    program's choice it agrees with most (others agree with it by chance
    only; the fit may have shuffled the batch), and a later telling (the
    recomputation's, whose graph the gradient is taken through) replaces
    an earlier one. What nothing was told of keeps the second program's
    choice. An earlier line says how many token-slots the two programs
    chose differently, layer by layer."""
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
    (`kanana_moe.reference_loss_and_grads`, whose words on why hold here:
    top-8 of 256 is as discontinuous a choice as top-6 of 128)."""
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


# every one moves the logits past the forward check's limits on the chip
# (the configuration's `reference_check.why` has the readings), which
# `selfcheck.py` holds every entry to. The reference has one more,
# `rotary_applied` (the latent layer with kanana's rotary), which ISSUE 32
# lists and no FORWARD limit can tell at random weights: one latent layer
# of five under a nearly uniform softmax reads rms 0.0194 where the system
# reads 0.0170-0.0192. The STEP check tells it (82.6% on the latent layer's
# `q_kernel` against `grad_leaf_rel`; `reference_check.step_why`), read by
# a builder's tool: `selfcheck.py` has no list of faults for the step alone.
FAULTS = {name: {name: True} for name in (
    "decay_dropped", "decay_per_head", "beta_dropped", "short_conv_dropped",
    "qk_norm_dropped", "out_gate_dropped", "shared_experts_dropped",
    "causal_mask_dropped")}
