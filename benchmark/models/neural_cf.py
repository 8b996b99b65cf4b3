"""Model family `neural_cf`: `models/recommendation.NeuralCF` from a
configuration file, with weights, data and the reference check made
from a seed."""

from __future__ import annotations

import numpy as np

from benchmark.reference import neural_cf as reference


def build(config, traffic):
    from analytics_zoo_tpu.models.recommendation import NeuralCF
    return NeuralCF(
        user_count=config["user_count"], item_count=config["item_count"],
        class_num=config["class_num"], user_embed=config["user_embed"],
        item_embed=config["item_embed"], mf_embed=config["mf_embed"],
        hidden_layers=tuple(config["hidden_layers"]),
        include_mf=config.get("include_mf", True)).model


def init_params(model, key):
    import jax
    return jax.jit(lambda k: model.build(k, (None, 2)))(key)


def fit_data(config, traffic, seed):
    """(x [n, 2] int32 of 1-based (user, item), y [n] int32). The label
    is the parity of the user id: learnable by the user tables, so the
    loss falls from ln 2."""
    n = traffic["batch_size"] * traffic["steps_per_epoch"]
    rng = np.random.default_rng([int(seed), 12])
    x = np.empty((n, 2), np.int32)
    x[:, 0] = rng.integers(1, config["user_count"] + 1, size=n)
    x[:, 1] = rng.integers(1, config["item_count"] + 1, size=n)
    return (x, (x[:, 0] % config["class_num"]).astype(np.int32)), n


def step_batch(config, traffic, seed, n):
    """The one batch of the training-step check: `n` pairs whose users
    all have an even id, so that every label is 0 and the samples'
    gradients add up instead of nearly cancelling (see the BERT family's
    `step_batch`)."""
    (x, y), _ = fit_data(config, dict(traffic, batch_size=n,
                                      steps_per_epoch=1), int(seed) + 1)
    x[:, 0] = np.maximum(2, x[:, 0] - x[:, 0] % 2)
    return x, (x[:, 0] % config["class_num"]).astype(np.int32)


def flops_per_sample(config, traffic):
    """NCF is bound by memory, not by the MXU; there is no byte model
    yet, so no utilization is reported for it."""
    return None


def kernel_work_per_sample(config, traffic):
    """No kernel of this model has an operation and byte count yet."""
    return {}


def check_inputs(config, traffic, seed, n):
    rng = np.random.default_rng([int(seed), 13])
    return np.stack([rng.integers(1, config["user_count"] + 1, size=n),
                     rng.integers(1, config["item_count"] + 1, size=n)],
                    axis=1).astype(np.int32)


def without_dropout(model, config, traffic):
    """NeuralCF has no dropout: the training-step check steps the model
    itself (a second build would name its layers anew)."""
    return model


def system_outputs(model, params, x):
    import jax
    return np.asarray(jax.jit(
        lambda p, a: model.apply(p, a, training=False))(params, x))


def reference_outputs(params, x, config, **fault):
    import jax
    return np.asarray(jax.jit(lambda p, a: reference.reference_probs(
        p, a, config, **fault))(params, x))


def reference_loss_and_grads(params, batch, config, **fault):
    """float32 `jax.value_and_grad` of the plain reference's loss. The
    batch is an argument of the jitted program and never a constant in
    it: a program that holds the seed's data compiles anew for every
    seed (55 s of every run's set-up, my chip runs, PR 23)."""
    import jax
    return jax.jit(jax.value_and_grad(lambda p, b: reference.reference_loss(
        p, b, config, **fault)))(params, batch)


FAULTS = {"dropped_gmf_branch": {"drop_gmf": True}}
