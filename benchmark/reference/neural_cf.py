"""Plain reference forward of NeuralCF (He et al. 2017, as the
reference's `NeuralCF.scala:60-97` builds it): an MLP tower over the
concatenated user and item embeddings (relu Dense stack), a GMF branch
(elementwise product of a second pair of embeddings), both concatenated
into a softmax over the classes. float32 under
`jax.default_matmul_precision("highest")`. Independent of
`analytics_zoo_tpu/keras/`: it reads the parameter tree by name; the
Dense layers carry creation-order names (`dense_<n>`), so they are taken
in the order of their numbers, the last being the head.

`drop_gmf` zeroes the GMF branch, for the check that the comparison can
fail."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def reference_probs(params, pairs, config, drop_gmf: bool = False):
    """[B, class_num] probabilities for int (user_id, item_id) `pairs`
    [B, 2] (1-based ids, tables sized count+1)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        pairs = jnp.asarray(pairs, jnp.int32)
        user, item = pairs[:, 0], pairs[:, 1]
        dense = sorted((k for k in params if k.startswith("dense_")),
                       key=lambda k: int(k.rsplit("_", 1)[1]))
        assert len(dense) == len(config["hidden_layers"]) + 1, dense
        x = jnp.concatenate(
            [params["ncf_mlp_user"]["embeddings"][user],
             params["ncf_mlp_item"]["embeddings"][item]], axis=-1)
        for name in dense[:-1]:
            x = jax.nn.relu(x @ params[name]["kernel"] + params[name]["bias"])
        if config.get("include_mf", True):
            gmf = (params["ncf_mf_user"]["embeddings"][user]
                   * params["ncf_mf_item"]["embeddings"][item])
            if drop_gmf:
                gmf = jnp.zeros_like(gmf)
            x = jnp.concatenate([x, gmf], axis=-1)
        head = params[dense[-1]]
        return jax.nn.softmax(x @ head["kernel"] + head["bias"], axis=-1)


def reference_loss(params, batch, config, **fault):
    """Mean sparse categorical cross-entropy of the reference's
    probabilities on one training batch `(pairs, labels)`, float32."""
    pairs, y = batch
    probs = reference_probs(params, pairs, config, **fault)
    y = jnp.asarray(y, jnp.int32)
    return -jnp.mean(jnp.log(
        jnp.take_along_axis(probs, y[:, None], axis=-1)))
