"""Model family `kanana_moe`: how the benchmark builds
`models/moe_decoder.MoEDecoderLM` (a causal language model of pre-norm
blocks with latent attention, leading dense layers and then routed-expert
layers with shared experts; `model_type` `deepseek_v3`) from a
configuration file, makes its weights and token data from a seed, and
checks it against the plain reference. A configuration of this family may
be ONE chip's share of an expert-parallel deployment: `n_routed_experts`
then counts the experts held here, `experts_held` names their range,
`router_width` the published count the router still has, and `vocab_size`
the slice of the vocabulary held. The same functions as `ouro_lm.py`, so
`runners/fit.py` runs it as it stands; what differs is the expert choice:
the forward check routes freely on both sides and counts the agreement,
the training-step check hands the reference the step's own choice and
holds the agreement to a floor (`reference_loss_and_grads`). A
configuration of this family is a new file under `benchmark/configs/`;
nothing here names one."""

from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.models import ouro_lm
from benchmark.reference import kanana_moe as reference

# the weights in one jitted call; full sequences whose every second token is
# a fixed permutation of the one before, ids drawn from the vocabulary the
# configuration holds; the step check's batch; the forward check's ids
init_params = ouro_lm.init_params
fit_data = ouro_lm.fit_data
step_batch = ouro_lm.step_batch
check_inputs = ouro_lm.check_inputs


def build(config, traffic):
    from analytics_zoo_tpu.models.moe_decoder import MoEDecoderLM
    first, end = config["experts_held"]
    if end - first != config["n_routed_experts"] \
            or config["qk_head_dim"] != config["qk_nope_head_dim"] \
            + config["qk_rope_head_dim"] or config["moe_layer_freq"] != 1 \
            or config["n_group"] != 1 or config["q_lora_rank"] is not None \
            or config["scoring_func"] != "sigmoid" \
            or not config["norm_topk_prob"] or not config["rope_interleave"]:
        raise ValueError("kanana_moe: experts_held must span the "
                         "n_routed_experts held here, and the family has "
                         "sigmoid scores normalised over the chosen, one "
                         "group, every layer after the dense ones an expert "
                         "layer, no query latent and interleaved rotary "
                         "pairs")
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
        num_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        n_dense_layer=config["first_k_dense_replace"],
        experts_held=(first, end),
        routed_scaling_factor=config["routed_scaling_factor"],
        rope_theta=config["rope_theta"],
        rms_eps=config["rms_norm_eps"], hidden_act=config["hidden_act"],
        **traffic.get("model_kwargs", {}))


def _held_share(config):
    """Routed experts a token is expected to find here under even
    routing: k x held / router width (0.75 at 6 x 16 / 128)."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["router_width"]


def _attention_params(config):
    H, n = config["hidden_size"], config["num_attention_heads"]
    return H * n * config["qk_head_dim"] \
        + H * (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        + config["kv_lora_rank"] * n * (config["qk_nope_head_dim"]
                                        + config["v_head_dim"]) \
        + n * config["v_head_dim"] * H


def flops_per_sample(config, traffic):
    """Forward+backward FLOPs the algorithm needs for one sequence: 6 per
    matmul weight per token in the latent attention's four projections
    (every layer), the dense layers' gated FFN, and in every expert layer
    the router, the shared experts and the EXPECTED k x held / width (0.75)
    held routed experts a token, which is what even routing sends here (a
    run's own share is the gauge `moe_held_slot_share`); the head over the
    vocabulary held, once; and the attention's products
    (`attention_work`). Recomputation, the embedding gather, norms, rotary
    positions, softmax, top-k, sort and gathers are not counted, so a
    share of the peak made from this cannot read over 100%."""
    T, H = traffic["seq_len"], config["hidden_size"]
    n_dense = config["first_k_dense_replace"]
    n_moe = config["num_hidden_layers"] - n_dense
    I = config["moe_intermediate_size"]
    per_moe = H * config["router_width"] \
        + 3 * H * I * config["n_shared_experts"] \
        + 3 * H * I * _held_share(config)
    weights = config["num_hidden_layers"] * _attention_params(config) \
        + n_dense * 3 * H * config["intermediate_size"] \
        + n_moe * per_moe + H * config["vocab_size"]
    return 6.0 * T * weights + attention_work(config, traffic)["flops"]


def attention_work(config, traffic):
    """What the causal latent attention of one sequence needs
    forward+backward, however it is computed: `flops` = 3 L T^2 heads x
    (qk width + v width): the three score-side products (scores, dQ, dK)
    at the key width and the three value-side ones (context, dV, dP) at
    the value width, on the lower triangle; `bytes` = the least a kernel
    that keeps the scores on the chip moves through HBM in bfloat16, the
    shared rotary key ONCE and not once a head: forward reads q, k_nope,
    kr, v and writes O; backward reads q, k_nope, kr, v, O, dO and writes
    dq, dk_nope, dkr, dv. The program repeats the rotary key for every
    head, computes the scores again in each backward kernel, the
    diagonal's tiles whole and its 192-wide rows in 256 lanes: none of
    that is counted, so a share of the roofline made from these reads
    low, never high."""
    T, n = traffic["seq_len"], config["num_attention_heads"]
    L = config["num_hidden_layers"]
    qk, nope, rope, v = (config["qk_head_dim"], config["qk_nope_head_dim"],
                         config["qk_rope_head_dim"], config["v_head_dim"])
    per_head = 3 * qk + 3 * nope + 5 * v    # q, dq | k_nope x3 | v, dv, O x2, dO
    return {"flops": 3.0 * L * T * T * n * (qk + v),
            "bytes": 2.0 * L * T * (n * per_head + 3 * rope)}


def experts_work(config, traffic):
    """What the held routed experts of one sequence need forward+backward
    under EVEN routing: rows = T x k x held / width token-slots a layer
    (0.75 T), each through three matrices of H x I forward and both
    gradients, `flops` = 18 H I a row; `bytes` = the held experts' weights
    read once forward and once backward (a step's, shared by the batch's
    sequences) and the rows in and out of the layer (forward in and out;
    backward the input, the output's gradient and the input's gradient),
    bfloat16. A run's own row count differs by `moe_held_slot_share` /
    12.5; the intermediates between the three products, the weights'
    gradient written and the recomputed forward are not counted."""
    T, H, I = traffic["seq_len"], config["hidden_size"], \
        config["moe_intermediate_size"]
    n_moe = config["num_hidden_layers"] - config["first_k_dense_replace"]
    rows = T * _held_share(config)
    weights = config["n_routed_experts"] * 3 * H * I
    return {"flops": 18.0 * n_moe * rows * H * I,
            "bytes": 2.0 * n_moe * (2 * weights / traffic["batch_size"]
                                    + 5 * rows * H)}


def kernel_work_per_sample(config, traffic):
    return {"attention": attention_work(config, traffic),
            "experts": experts_work(config, traffic)}


# the traffic the stepped model was last built from (`without_dropout`),
# for `reference_loss_and_grads` to run the step's own forward again
_step_traffic = None


def without_dropout(model, config, traffic):
    """The model of the training-step check: a second build of the same
    model (there is no dropout rate to zero)."""
    global _step_traffic
    _step_traffic = traffic
    return build(config, traffic)


# the expert choice [expert layers, n, T, k] of the system's last forward
# (`system_outputs`), for `reference_outputs` to count the share of
# token-slots on which the reference chose alike
_system_choice = None


def _agreement(system, own):
    """By expert layer, the share of the system's token-slots
    [layers, n, T, k] whose expert the reference chose too for that
    token."""
    return [float(np.mean((a[..., :, None] == b[..., None, :]).any(-1)))
            for a, b in zip(system, own)]


def _routing_gauges(model, choice):
    """From the forward check's choice: the share of all token-slots that
    chose an expert held here (12.5% under even routing) and, over the held
    experts, the fullest one's load over the mean, in the worst layer."""
    from analytics_zoo_tpu.observability.registry import get_registry
    moe = model.moe
    counts = np.stack([np.bincount(layer.reshape(-1), minlength=moe.n_routed)
                       for layer in choice])
    held = counts[:, moe.first:moe.first + moe.n_held]
    share = 100.0 * held.sum() / counts.sum()
    skew = float(np.max(held.max(axis=1) / np.maximum(held.mean(axis=1),
                                                      1e-9)))
    gauge = get_registry().gauge
    gauge("moe_held_slot_share", "% of all token-slots, in the model's "
          "last checked forward, that chose a routed expert held on this "
          "chip").set(share, model=model.name)
    gauge("moe_expert_load_max_over_mean", "token-slots of the fullest "
          "held expert over the held experts' mean, in the worst layer of "
          "the model's last checked forward").set(skew, model=model.name)
    harness.log(f"moe_routing held_slot_share_pct={share:.3f} "
                f"expert_load_max_over_mean={skew:.3f} held_slots_by_layer="
                f"{held.sum(axis=1).tolist()} of {counts.sum(axis=1)[0]}")


def system_outputs(model, params, x):
    """The system's forward: the model's own `apply`, jitted, inference
    mode, float32 at jax's default matmul precision: [n, T, vocab]
    logits. The same program returns every expert layer's choice, from
    which the routing gauges are set (unless the weights are a quantized
    tree, whose choice is not the model's)."""
    import jax
    global _system_choice
    logits, choice = jax.jit(lambda p, a: (
        model.apply(p, a, training=False),
        model.expert_choice(p, a)))(params, x)
    _system_choice = np.asarray(choice)
    if "lm_head_kernel" in params:
        _routing_gauges(model, _system_choice)
    return np.asarray(logits)


def reference_outputs(params, x, config, **fault):
    """The plain reference on `x`, as one jitted program (the precision
    context is applied while it is traced), routing freely; the share of
    token-slots on which its choice is the system's goes to an earlier
    line, layer by layer."""
    import jax
    logits, own = jax.jit(lambda p, a: reference.reference_forward(
        p, a, config, **fault))(params, x)
    own = np.asarray(own)
    if not fault and _system_choice is not None \
            and _system_choice.shape == own.shape:
        harness.log("moe_choice_agreement_by_layer " + " ".join(
            f"{v:.5f}" for v in _agreement(_system_choice, own)))
    return np.asarray(logits)


def _step_choice(params, ids, config):
    """The expert choice [expert layers, n, T, k] of the system's training
    step on `ids`: `expert_choice` of the model as the step check builds
    it (`without_dropout`'s traffic: flash kernels, recomputation), on the
    parameters as the step sees them (bfloat16 copies of the float32
    leaves under `mixed_precision`, `learn/trainer.py`)."""
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


def reference_loss_and_grads(params, batch, config, **fault):
    """float32 `jax.value_and_grad` of the plain reference's loss AT THE
    SYSTEM'S EXPERT CHOICE, taken ONE SEQUENCE AT A TIME and averaged on
    the host, as `ouro_lm.reference_loss_and_grads` (every sequence has
    as many labels; the sequence is an argument of the jitted program;
    the stepped model is collected first).

    Top-k is a discontinuous choice: routing freely, the float32 reference
    and the bfloat16 step choose another expert for a few token-slots in a
    hundred, which moves whole rows between the experts' weight gradients
    and the router's (a fifth of those leaves, PERF.md section 6) and
    would hide a lost expert. So the reference is handed the indices the
    step's own forward chose (`_step_choice`; never its scores or
    weights). The share of token-slots on which the reference's own
    router chose alike goes to an earlier line, layer by layer, and is
    held to `reference_check.choice_agreement_floor`: under it the loss
    returned is not a number, and the step check fails. The forward check
    (`reference_outputs`) routes freely on both sides."""
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
    agree = _agreement(choice, np.concatenate(own, axis=1))
    floor = config["reference_check"]["choice_agreement_floor"]
    if not fault:
        harness.log("moe_step_choice_agreement_by_layer "
                    + " ".join(f"{v:.5f}" for v in agree)
                    + f" floor={floor}")
    return (loss if min(agree) >= floor else float("nan")), grads


FAULTS = {"shared_experts_dropped": {"shared_experts_dropped": True},
          "routed_scale_dropped": {"routed_scale_dropped": True},
          "rope_key_dropped": {"rope_key_dropped": True},
          "causal_mask_dropped": {"causal_mask_dropped": True},
          "kv_norm_dropped": {"kv_norm_dropped": True},
          "held_expert_dropped": {"held_expert_dropped": True}}
