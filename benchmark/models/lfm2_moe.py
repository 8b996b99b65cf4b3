"""Model family `lfm2_moe`: how the benchmark builds
`models/moe_decoder.MoEDecoderLM` with a per-layer mixer pattern (LFM2's
gated short-convolution layers and grouped-query attention layers; two
leading dense layers, then routed-expert layers without a shared expert;
the head tied to the embedding; `model_type` `lfm2_moe`) from a
configuration file, makes its weights and token data from a seed, and
checks it against the plain reference. The configuration's `layer_types`
is the published list of all layers; the family applies its first
`num_hidden_layers`. As in `kimi_linear.py` a configuration may be ONE
chip's share of an expert-parallel deployment (`num_experts` counts the
experts held here, `experts_held` names their range, `router_width` the
published count the router still has, `vocab_size` the slice held), and
the expert choice is compared the same way: the forward check routes
freely on both sides and counts the agreement, the training-step check
hands the reference the step's own choice, which the stepped model's
routing tells the host, and holds the agreement to a floor. The same
functions as `kimi_linear.py`, so `runners/fit.py` runs it as it stands;
nothing here names a configuration."""

from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.models import kanana_moe, ouro_lm
from benchmark.reference import lfm2_moe as reference

init_params = ouro_lm.init_params
fit_data = ouro_lm.fit_data
step_batch = ouro_lm.step_batch
check_inputs = ouro_lm.check_inputs


def _mixers(config):
    return ["conv" if t == "conv" else "gqa"
            for t in config["layer_types"][:config["num_hidden_layers"]]]


def build(config, traffic):
    from analytics_zoo_tpu.models.moe_decoder import MoEDecoderLM
    first, end = config["experts_held"]
    if end - first != config["num_experts"] or config["conv_bias"] \
            or not config["norm_topk_prob"] \
            or not config["use_expert_bias"] \
            or not config["tie_word_embeddings"] \
            or config["hidden_size"] != config["num_attention_heads"] \
            * config["head_dim"] or set(config["layer_types"]) \
            - {"conv", "full_attention"}:
        raise ValueError("lfm2_moe: experts_held must span the num_experts "
                         "held here, every layer must be conv or "
                         "full_attention, and the family has bias-free "
                         "filters, sigmoid scores with an expert bias "
                         "normalised over the chosen, heads x head_dim the "
                         "hidden size and the head tied to the embedding")
    return MoEDecoderLM(
        vocab=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"], kv_lora_rank=0,
        qk_nope_head_dim=0, qk_rope_head_dim=config["head_dim"],
        v_head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=config["router_width"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=0, n_dense_layer=config["num_dense_layers"],
        experts_held=(first, end),
        routed_scaling_factor=config["routed_scaling_factor"],
        rope_theta=config["rope_theta"], rms_eps=config["norm_eps"],
        hidden_act="silu", mixers=_mixers(config),
        conv={"conv_size": config["conv_L_cache"]},
        gqa={"n_kv_head": config["num_key_value_heads"],
             "head_dim": config["head_dim"]},
        tie_embeddings=True, router_eps=config["router_norm_eps"],
        **traffic.get("model_kwargs", {}))


def _held_share(config):
    """Routed experts a token is expected to find here under even
    routing: k x held / router width (1 at 4 x 8 / 32)."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["router_width"]


def _conv_params(config):
    """Matmul weights of one conv layer: in 3H x H, out H x H; the filters
    are no matmul."""
    return 4 * config["hidden_size"] ** 2


def _gqa_params(config):
    H, d = config["hidden_size"], config["head_dim"]
    return 2 * H * config["num_attention_heads"] * d \
        + 2 * H * config["num_key_value_heads"] * d


def flops_per_sample(config, traffic):
    """Forward+backward FLOPs the algorithm needs for one sequence: 6 per
    matmul weight per token in every mixer's projections (`_conv_params`,
    `_gqa_params`), the dense layers' gated FFN, and in every expert layer
    the router and the EXPECTED k x held / width (1) held routed experts a
    token, which is what even routing sends here; the tied head over the
    vocabulary held, once; the attention layers' products on the lower
    triangle (`gqa_attention_work`'s 7 products counted as the other
    language cells count theirs: 6). Recomputation, the embedding gather,
    norms, rotary, the filters and gates, softmax, top-k, sort and
    gathers are not counted, so a share of the peak made from this cannot
    read over 100%."""
    T, H = traffic["seq_len"], config["hidden_size"]
    mixers = _mixers(config)
    n_dense = config["num_dense_layers"]
    n_moe = config["num_hidden_layers"] - n_dense
    per_moe = H * config["router_width"] \
        + 3 * H * config["moe_intermediate_size"] * _held_share(config)
    weights = mixers.count("conv") * _conv_params(config) \
        + mixers.count("gqa") * _gqa_params(config) \
        + n_dense * 3 * H * config["intermediate_size"] \
        + n_moe * per_moe + H * config["vocab_size"]
    attention = 3.0 * mixers.count("gqa") * T * T \
        * config["num_attention_heads"] * 2 * config["head_dim"]
    return 6.0 * T * weights + attention


def gated_conv_work(config, traffic):
    """What the gated filter kernels' OWN job needs in the conv layers of
    one sequence, each run once (`pallas/gated_conv.py`: `gated_conv_fwd`,
    `gated_conv_bwd`), from their operands, bfloat16: `bytes` forward the
    in-projection's 3H columns read and y's H written, backward those 3H
    and dy's H read and d[B|C|x]'s 3H written, a token and layer;
    `flops` forward 2K + 2 a channel and token (B * x, the K taps, C * c),
    backward 6K + 5 (v and c again, dC, dc, the filter's transpose, the
    taps' gradient, dB, dx). The forward kernel's second run, in the
    layer's recomputation, is not counted, so the share cannot pass
    (4 + 7) / (4 + 4 + 7) = 73%; the bytes decide."""
    T, H, K = traffic["seq_len"], config["hidden_size"], \
        config["conv_L_cache"]
    per_token = T * _mixers(config).count("conv")
    return {"flops": float((2 * K + 2) + (6 * K + 5)) * H * per_token,
            "bytes": 2.0 * (4 + 7) * H * per_token}


def gqa_attention_work(config, traffic):
    """What the grouped-query causal attention of one sequence needs
    forward+backward in the model's attention layers, however it is
    computed: `flops` = 2 products forward (scores, context) and 5
    backward (scores again, dW, dQ, dK, dV) on the lower triangle, 2 x
    T^2 / 2 x d each a query head; `bytes` = the least a kernel that keeps
    the scores on the chip moves through HBM in bfloat16, K and V and their
    gradients ONCE A K/V HEAD: forward q, k, v read and O written,
    backward q, k, v, O, dO read and dq, dk, dv written; the float32
    log-sum-exp row once written and once read. The program computes the
    tiles the diagonal crosses whole and writes dK and dV a query head in
    float32 before a group's sum: neither is counted, so a share of the
    roofline made from these reads low, never high."""
    T, d = traffic["seq_len"], config["head_dim"]
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    L = _mixers(config).count("gqa")
    per_layer_bytes = 2.0 * T * d * (2 * n + 2 * kv) \
        + 2.0 * T * d * (4 * n + 4 * kv) + 2 * 4.0 * T * n
    return {"flops": 7.0 * L * T * T * n * d,
            "bytes": L * per_layer_bytes}


def experts_work(config, traffic):
    """`kanana_moe.experts_work` for this family: what the held routed
    experts of one sequence need forward+backward under EVEN routing, rows
    = T x k x held / width token-slots a layer (T: 4 x 8 / 32), each
    through three matrices of H x I forward and both gradients, `flops` =
    18 H I a row; `bytes` = the held experts' weights read once forward and
    once backward and the rows in and out of the layer, bfloat16. A run's
    own row count differs by `conv_moe_held_slot_share` / 25; the
    intermediates, the weights' gradient written and the recomputed
    forward are not counted."""
    T, H, I = traffic["seq_len"], config["hidden_size"], \
        config["moe_intermediate_size"]
    n_moe = config["num_hidden_layers"] - config["num_dense_layers"]
    rows = T * _held_share(config)
    weights = config["num_experts"] * 3 * H * I
    return {"flops": 18.0 * n_moe * rows * H * I,
            "bytes": 2.0 * n_moe * (2 * weights / traffic["batch_size"]
                                    + 5 * rows * H)}


def kernel_work_per_sample(config, traffic):
    return {"gated_conv": gated_conv_work(config, traffic),
            "gqa_attention": gqa_attention_work(config, traffic),
            "experts": experts_work(config, traffic)}


# the traffic the stepped model was last built from (`without_dropout`),
# for `_second_program_choice` to build that model again, and every choice
# the stepped model's routing has told the host since, in the order told
_step_traffic = None
_told = []


def without_dropout(model, config, traffic):
    """The model of the training-step check: a second build of the same
    model (there is no dropout rate to zero) whose routing TELLS THE HOST
    each choice it makes (`kimi_linear.without_dropout`'s arrangement).
    The timed model is not this one."""
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


def system_outputs(model, params, x):
    """`kanana_moe.system_outputs`, the routing gauges set from its choice
    unless the weights are a quantized tree (with the head tied no
    `lm_head_kernel` tells the two apart: an int8 kernel's `_q` does)."""
    import jax
    out = kanana_moe.system_outputs(model, params, x)
    names = {getattr(k, "key", "") for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0] for k in path}
    if not any(str(n).endswith("_q") for n in names):
        kanana_moe._routing_gauges(model, kanana_moe._system_choice)
    return out


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
    """`kimi_linear._second_program_choice`: `expert_choice` of the model
    as the step check builds it, on the bfloat16 copies the step sees
    under `mixed_precision`."""
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
    (`kimi_linear._step_choice`, whose words hold here)."""
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
    (`kimi_linear.reference_loss_and_grads`)."""
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
# `selfcheck.py` holds every entry to. The reference has two more that
# ISSUE 36 lists, `qk_norm_dropped` and `expert_bias_in_weights`, which no
# FORWARD limit can tell at random weights and the STEP check tells
# (`reference_check.step_why`), read by a builder's tool: `selfcheck.py`
# has no list of faults for the step alone.
FAULTS = {name: {name: True} for name in (
    "gates_dropped", "filter_dropped", "filter_anticausal",
    "rotary_dropped", "gqa_interleaved", "causal_mask_dropped",
    "reference_bfloat16")}
