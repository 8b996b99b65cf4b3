"""Model family `bert_classifier`: how the benchmark builds
`models/bert.BERTClassifier` from a configuration file, makes its
weights, data and requests from a seed, and checks it against the plain
reference. A configuration of this family is a new file under
`benchmark/configs/`; nothing here names one."""

from __future__ import annotations

import numpy as np

from benchmark import metrics
from benchmark.reference import bert_classifier as reference


def build(config, traffic):
    from analytics_zoo_tpu.models.bert import BERTClassifier
    return BERTClassifier(
        num_classes=config["num_labels"],
        dropout=config.get("classifier_dropout", 0.1),
        vocab=config["vocab_size"], hidden_size=config["hidden_size"],
        n_block=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        seq_len=config["max_position_embeddings"],
        intermediate_size=config["intermediate_size"],
        type_vocab=config["type_vocab_size"],
        hidden_drop=config["hidden_dropout_prob"],
        attn_drop=config["attention_probs_dropout_prob"],
        **traffic.get("model_kwargs", {}))


def init_params(model, key):
    """All weights on the device in ONE jitted call from the seed's key
    (float32 masters, the type they are trained in)."""
    import jax
    return jax.jit(lambda k: model.build(k))(key)


def fit_data(config, traffic, seed):
    """{"x": [ids, mask], "y": labels}: `steps_per_epoch` batches of
    random token ids. The label is carried by the first token (one of
    two ids), so that the loss has something to learn and 'last below
    first' is a property of training, not of luck."""
    n = traffic["batch_size"] * traffic["steps_per_epoch"]
    T = traffic["seq_len"]
    rng = np.random.default_rng([int(seed), 11])
    y = rng.integers(0, config["num_labels"], size=n, dtype=np.int32)
    ids = rng.integers(config["num_labels"], config["vocab_size"],
                       size=(n, T), dtype=np.int32)
    ids[:, 0] = y
    return {"x": [ids, np.ones((n, T), np.float32)], "y": y}, n


def step_batch(config, traffic, seed, n):
    """The one batch of the training-step check: `n` sequences that all
    carry label 0. With both labels in so small a batch the samples'
    gradients nearly cancel, and how nearly depends on the seed: the
    relative error of what is left read from 1% to 18% (my chip runs,
    PR 23). One label makes them add up."""
    batch, _ = fit_data(config, dict(traffic, batch_size=n,
                                     steps_per_epoch=1), int(seed) + 1)
    batch["x"][0][:, 0] = 0
    batch["y"][:] = 0
    return batch


def flops_per_sample(config, traffic):
    return metrics.transformer_train_flops_per_sample(
        num_hidden_layers=config["num_hidden_layers"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        seq_len=traffic["seq_len"], num_labels=config["num_labels"])


def kernel_work_per_sample(config, traffic):
    """Operations and least HBM bytes of one sequence's attention,
    forward+backward, by the name a `trace_op_roofline` metric asks for;
    activations are bfloat16 under mixed precision."""
    mixed = traffic.get("fit_kwargs", {}).get("mixed_precision", False)
    return {"attention": metrics.attention_train_work_per_sample(
        num_hidden_layers=config["num_hidden_layers"],
        hidden_size=config["hidden_size"], seq_len=traffic["seq_len"],
        bytes_per_value=2 if mixed else 4)}


def check_inputs(config, traffic, seed, n):
    """[n, seq_len] int32 ids of the forward check."""
    return np.random.default_rng([int(seed) + 1, 3]).integers(
        0, config["vocab_size"], size=(n, traffic["seq_len"]),
        dtype=np.int32)


def without_dropout(model, config, traffic):
    """The model of the training-step check: dropout draws from the
    system's own random stream, which no reference can follow, so the
    step that is held to the reference runs a second build of the same
    model with every rate at 0 (the parameter tree is the same)."""
    return build(dict(config, hidden_dropout_prob=0.0,
                      classifier_dropout=0.0,
                      attention_probs_dropout_prob=0.0), traffic)


def system_outputs(model, params, x):
    """The system's forward: the model's own `apply`, jitted, inference
    mode, float32 at jax's default matmul precision."""
    import jax
    return np.asarray(jax.jit(
        lambda p, a: model.apply(p, a, training=False))(params, x))


def reference_outputs(params, x, config, **fault):
    """The plain reference on `x`, as one jitted program (the precision
    context is applied while it is traced)."""
    import jax
    return np.asarray(jax.jit(lambda p, a: reference.reference_logits(
        p, a, np.ones(a.shape, np.float32), config, **fault))(params, x))


def reference_loss_and_grads(params, batch, config, **fault):
    """float32 `jax.value_and_grad` of the plain reference's loss. The
    batch is an argument of the jitted program and never a constant in
    it: a program that holds the seed's data compiles anew for every
    seed (55 s of every run's set-up, my chip runs, PR 23)."""
    import jax
    return jax.jit(jax.value_and_grad(lambda p, b: reference.reference_loss(
        p, b, config, **fault)))(params, batch)


FAULTS = {"dropped_residual": {"drop_residual_in_block": 1}}
