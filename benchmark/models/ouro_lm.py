"""Model family `ouro_lm`: how the benchmark builds
`models/looped_decoder.LoopedDecoderLM` (a causal language model whose
stack of decoder blocks runs `total_ut_steps` times over shared weights)
from a configuration file, makes its weights and token data from a seed,
and checks it against the plain reference. The same functions as
`bert_classifier.py`, so `runners/fit.py` runs it as it stands. A
configuration of this family is a new file under `benchmark/configs/`;
nothing here names one."""

from __future__ import annotations

import numpy as np

from benchmark.reference import ouro_lm as reference


def build(config, traffic):
    from analytics_zoo_tpu.models.looped_decoder import LoopedDecoderLM
    if config["hidden_size"] != config["num_attention_heads"] \
            * config["head_dim"] or config["num_key_value_heads"] \
            != config["num_attention_heads"]:
        raise ValueError("ouro_lm: heads x head_dim must be the hidden "
                         "size, with as many K/V heads as query heads")
    return LoopedDecoderLM(
        vocab=config["vocab_size"], hidden_size=config["hidden_size"],
        n_block=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        n_pass=config["total_ut_steps"], rope_theta=config["rope_theta"],
        rms_eps=config["rms_norm_eps"], hidden_act=config["hidden_act"],
        **traffic.get("model_kwargs", {}))


def init_params(model, key):
    """All weights on the device in ONE jitted call from the seed's key
    (float32 masters, the type they are trained in)."""
    import jax
    return jax.jit(lambda k: model.build(k))(key)


def fit_data(config, traffic, seed):
    """{"x": ids [n, T], "y": the next ids [n, T]}: `steps_per_epoch`
    batches of full sequences (packed documents, no padding). Every second
    token is drawn at random and the one after it is a fixed permutation
    (the seed's) of it, so half of the next tokens can be learned and
    'last loss below first' is a property of training, not of luck."""
    n = traffic["batch_size"] * traffic["steps_per_epoch"]
    T, V = traffic["seq_len"], config["vocab_size"]
    rng = np.random.default_rng([int(seed), 13])
    follows = rng.permutation(V).astype(np.int32)
    ids = np.empty((n, T + 2), np.int32)
    ids[:, 0::2] = rng.integers(0, V, size=(n, T // 2 + 1), dtype=np.int32)
    ids[:, 1::2] = follows[ids[:, 0::2]]
    return {"x": ids[:, :T], "y": ids[:, 1:T + 1]}, n


def step_batch(config, traffic, seed, n):
    """The one batch of the training-step check: `n` full sequences of
    another seed. Thousands of tokens with as many different labels: the
    tokens' gradients do not cancel as a two-label batch's do."""
    batch, _ = fit_data(config, dict(traffic, batch_size=n,
                                     steps_per_epoch=1), int(seed) + 1)
    return batch


def flops_per_sample(config, traffic):
    """Forward+backward FLOPs the algorithm needs for one sequence:
    6 per matmul weight per token in each of the R x N layer applications
    (QKV and output 4 H^2, gate, up and down 3 H I) and in the head (H V,
    once); the causal half of the scores and context products, 6 T^2 H an
    application (2 T^2 H forward on the lower triangle, twice that
    backward). Recomputation, the embedding gather, norms, rotary
    positions, softmax and the exit gate are not counted, so a share of
    the peak made from this cannot read over 100%."""
    T, H = traffic["seq_len"], config["hidden_size"]
    apps = config["total_ut_steps"] * config["num_hidden_layers"]
    per_app = 4 * H * H + 3 * H * config["intermediate_size"]
    return 6.0 * T * (apps * per_app + H * config["vocab_size"]) \
        + attention_work(config, traffic)["flops"]


def attention_work(config, traffic):
    """What the causal attention of one sequence needs forward+backward,
    however it is computed: `flops` = 6 R N T^2 H, the six T x T x Dh
    products a head needs (scores and context forward; dV, dP, dQ, dK
    backward) on the lower triangle; `bytes` = the least a kernel that
    keeps the scores on the chip moves through HBM, 12 arrays of T x H
    bfloat16 values an application (forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV). The program
    computes the scores a second time in the backward (seven products),
    and the tiles the diagonal crosses whole: neither is counted, so a
    share of the roofline made from these reads low, never high (perfect
    kernels read 6/7 = 86%)."""
    T, H = traffic["seq_len"], config["hidden_size"]
    apps = config["total_ut_steps"] * config["num_hidden_layers"]
    return {"flops": 6.0 * apps * T * T * H, "bytes": 12.0 * apps * T * H * 2}


def kernel_work_per_sample(config, traffic):
    return {"attention": attention_work(config, traffic)}


def check_inputs(config, traffic, seed, n):
    """[n, seq_len] int32 ids of the forward check."""
    return np.random.default_rng([int(seed) + 1, 3]).integers(
        0, config["vocab_size"], size=(n, traffic["seq_len"]),
        dtype=np.int32)


def without_dropout(model, config, traffic):
    """The model of the training-step check: a second build of the same
    model (there is no dropout rate to zero)."""
    return build(config, traffic)


def system_outputs(model, params, x):
    """The system's forward: the model's own `apply`, jitted, inference
    mode, float32 at jax's default matmul precision: [n, T, vocab]
    logits of the last pass."""
    import jax
    return np.asarray(jax.jit(
        lambda p, a: model.apply(p, a, training=False))(params, x))


def reference_outputs(params, x, config, **fault):
    """The plain reference on `x`, as one jitted program (the precision
    context is applied while it is traced)."""
    import jax
    return np.asarray(jax.jit(lambda p, a: reference.reference_logits(
        p, a, config, **fault))(params, x))


def reference_loss_and_grads(params, batch, config, **fault):
    """float32 `jax.value_and_grad` of the plain reference's loss, taken
    ONE SEQUENCE AT A TIME and averaged on the host: every sequence has
    as many labels, so the mean of the sequences' means is the batch's,
    and one sequence's backward is what fits the chip beside the
    parameters. The system's step sees the batch whole, so a fault along
    its batch axis (a sequence left out, a wrong mean) shows. The
    sequence is an argument of the jitted program and never a constant in
    it: a program that holds the seed's data compiles anew for every
    seed. The model that the system's step trained is garbage in a
    reference cycle by now (a model holds its jitted step, which holds
    the model): collect it first."""
    import gc
    import jax
    gc.collect()
    one = jax.jit(jax.value_and_grad(lambda p, b: reference.reference_loss(
        p, b, config, **fault)))
    n = len(batch["x"])
    loss, grads = 0.0, None
    for i in range(n):
        seq_loss, seq_grads = jax.device_get(one(
            params, {k: v[i:i + 1] for k, v in batch.items()}))
        loss += float(seq_loss) / n
        seq_grads = jax.tree_util.tree_map(lambda g: g / n, seq_grads)
        grads = seq_grads if grads is None else jax.tree_util.tree_map(
            np.add, grads, seq_grads)
    return loss, grads


FAULTS = {"one_pass_dropped": {"drop_pass": True},
          "final_norm_out_of_the_loop": {"final_norm_once": True},
          "causal_mask_dropped": {"no_causal_mask": True}}
