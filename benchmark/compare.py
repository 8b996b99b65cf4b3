"""The comparison that decides `correct`: the system's outputs against
the plain float32 reference's, by two numbers written in the
configuration's file with their reason. `atol` bounds the largest
absolute error of any one value; `rms` bounds the root-mean-square error
over all values compared, which is far steadier than a maximum and is
what separates the stated precision from a coarser one (int8 weights
move the rms by several times while many single values still pass).

The training step is held to the reference the same way
(`step_errors`): the loss of the first step, the gradient of all
parameters as one vector, and the gradient leaf by leaf, so that one
dropped or wrong leaf shows however small its share of the whole is."""

from __future__ import annotations

from typing import Dict

import numpy as np


def errors(got, want) -> Dict[str, float]:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return {"max_abs_err": float("inf"), "rms_err": float("inf")}
    diff = got - want
    return {"max_abs_err": float(np.max(np.abs(diff))),
            "rms_err": float(np.sqrt(np.mean(diff ** 2)))}


def within(err: Dict[str, float], chk: Dict) -> bool:
    return bool(err["max_abs_err"] <= chk["atol"]
                and err["rms_err"] <= chk["rms"])


# a leaf whose reference gradient is this small a share of the whole
# gradient's norm is noise over noise (BERT's key bias has a gradient of
# exactly zero in exact arithmetic): it counts in the whole, not alone
LEAF_FLOOR = 1e-3


def step_errors(loss, grads, ref_loss, ref_grads) -> Dict[str, float]:
    """The system's first training step against the reference's
    `value_and_grad`: `loss_abs_err`; `grad_rel_err` = |g - g_ref| /
    |g_ref| over all parameters as one vector; `grad_leaf_rel_err` = the
    largest such ratio over single leaves (those above LEAF_FLOOR)."""
    import jax
    got = [np.asarray(g, np.float64)
           for g in jax.tree_util.tree_leaves(grads)]
    want = [np.asarray(g, np.float64)
            for g in jax.tree_util.tree_leaves(ref_grads)]
    bad = {"loss_abs_err": float("inf"), "grad_rel_err": float("inf"),
           "grad_leaf_rel_err": float("inf")}
    if len(got) != len(want) or any(a.shape != b.shape or
                                    not np.isfinite(a).all()
                                    for a, b in zip(got, want)):
        return bad
    diff2 = np.array([np.sum((a - b) ** 2) for a, b in zip(got, want)])
    ref2 = np.array([np.sum(b ** 2) for b in want])
    total = float(ref2.sum())
    if not total > 0 or not np.isfinite(loss):
        return bad
    alone = ref2 >= (LEAF_FLOOR ** 2) * total
    return {"loss_abs_err": float(abs(float(loss) - float(ref_loss))),
            "grad_rel_err": float(np.sqrt(diff2.sum() / total)),
            "grad_leaf_rel_err":
                float(np.sqrt((diff2[alone] / ref2[alone]).max()))}


def step_within(err: Dict[str, float], chk: Dict) -> bool:
    return bool(err["loss_abs_err"] <= chk["loss_atol"]
                and err["grad_rel_err"] <= chk["grad_rel"]
                and err["grad_leaf_rel_err"] <= chk["grad_leaf_rel"])
