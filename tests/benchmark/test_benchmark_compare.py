"""The comparison that decides `correct`, on hand-made numbers: the
outputs' largest and rms error, and the training step's loss, whole
gradient and single leaves."""

import numpy as np
import pytest

from benchmark import compare

CHK = {"atol": 0.02, "rms": 0.008, "loss_atol": 0.01, "grad_rel": 0.05,
       "grad_leaf_rel": 0.2}


def _grads():
    rng = np.random.default_rng(0)
    return {"big": rng.normal(size=(64, 8)), "small": 0.05 * rng.normal(size=8),
            "noise": 1e-6 * rng.normal(size=8)}


def test_outputs_are_held_to_a_largest_and_an_rms_error():
    want = np.zeros((4, 2))
    got = want + 0.004
    err = compare.errors(got, want)
    assert err == {"max_abs_err": pytest.approx(0.004),
                   "rms_err": pytest.approx(0.004)}
    assert compare.within(err, CHK)
    got[0, 0] = 0.03                    # one value too far
    assert not compare.within(compare.errors(got, want), CHK)
    # many values a little off: every one inside atol, the rms outside
    assert not compare.within(compare.errors(want + 0.01, want), CHK)


@pytest.mark.parametrize("got", [np.zeros((4, 3)), np.full((4, 2), np.nan)])
def test_a_wrong_shape_or_a_non_finite_output_is_outside_any_tolerance(got):
    assert not compare.within(compare.errors(got, np.zeros((4, 2))), CHK)


def test_the_same_step_has_no_error():
    g = _grads()
    err = compare.step_errors(0.7, g, 0.7, g)
    assert err == {"loss_abs_err": 0.0, "grad_rel_err": 0.0,
                   "grad_leaf_rel_err": 0.0}
    assert compare.step_within(err, CHK)


@pytest.mark.parametrize("scale,leaf_err", [(0.0, 1.0), (0.5, 0.5)])
def test_one_small_leaf_dropped_or_halved_shows_alone_not_in_the_whole(
        scale, leaf_err):
    ref = _grads()
    got = dict(ref, small=ref["small"] * scale)
    err = compare.step_errors(0.7, got, 0.7, ref)
    assert err["grad_rel_err"] < CHK["grad_rel"]       # lost in the whole
    assert err["grad_leaf_rel_err"] == pytest.approx(leaf_err)
    assert not compare.step_within(err, CHK)


def test_a_leaf_of_noise_counts_in_the_whole_and_not_alone():
    ref = _grads()
    got = dict(ref, noise=-ref["noise"])        # 200% off, of nothing
    err = compare.step_errors(0.7, got, 0.7, ref)
    assert err["grad_leaf_rel_err"] == 0.0 and err["grad_rel_err"] < 1e-5
    assert compare.step_within(err, CHK)


def test_a_wrong_loss_or_a_non_finite_gradient_fails_the_step():
    ref = _grads()
    assert not compare.step_within(
        compare.step_errors(0.72, ref, 0.7, ref), CHK)
    bad = dict(ref, big=ref["big"] * np.inf)
    assert not compare.step_within(
        compare.step_errors(0.7, bad, 0.7, ref), CHK)
    assert not compare.step_within(
        compare.step_errors(0.7, {"big": ref["big"]}, 0.7, ref), CHK)


@pytest.mark.parametrize("family,cell", [
    ("bert_classifier", "bert-base.fit-seq128"),
    ("neural_cf", "ncf-ml20m.fit-b1m")])
def test_the_reference_step_program_holds_nothing_of_the_seed(family, cell):
    """Two seeds' batches lower to the same program, so the persistent
    cache finds it again: data closed over as constants made every new
    seed compile for a minute in set-up."""
    import importlib

    import jax

    from benchmark import harness
    loaded = harness.load_cell(cell, rehearse=True)
    config, traffic = loaded["config"], loaded["traffic"]
    fam = importlib.import_module("benchmark.models." + family)
    seen = []
    real_jit = jax.jit

    def lowering_jit(fn, *a, **kw):
        jitted = real_jit(fn, *a, **kw)

        def call(*args):
            seen.append(jitted.lower(*args).as_text())
            return jitted(*args)
        return call

    model = fam.build(config, traffic)
    params = fam.init_params(model, harness.seed_key(1))
    jax.jit = lowering_jit
    try:
        for seed in (3, 2 ** 31 + 11):
            fam.reference_loss_and_grads(
                params, fam.step_batch(config, traffic, seed, 4), config)
    finally:
        jax.jit = real_jit
    assert len(seen) == 2 and seen[0] == seen[1]
