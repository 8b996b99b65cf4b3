"""The chunked gated delta rule (`pallas/delta_rule.py`, its kernels through
the Pallas interpreter) and the KDA layer built on it
(`keras/linear_attention.py`) against the token-by-token recurrence at small
sizes: output and every gradient (q, k, v, g, beta and every leaf), at a
sequence that is no multiple of the chunk, at a decay that overflows any
form that divides by it, with beta = 0 (the state only decays) and alpha = 1
(the plain delta rule); the chunk length changes no number."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.linear_attention import (_L2_EPS,
                                                      KimiDeltaAttention,
                                                      causal_depthwise_conv)
from analytics_zoo_tpu.pallas import delta_rule as dr
from analytics_zoo_tpu.pallas import short_conv as sc
from benchmark.reference import kimi_linear as reference


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(N=4, T=80, dk=32, dv=16, decay=1.0, seed=0, beta=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (N, T, dk))
    k = jax.random.normal(ks[1], (N, T, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (N, T, dv))
    # the assumed initialisation's range: rates in [1, 16) x steps to 0.1
    g = -decay * jax.random.uniform(ks[3], (N, T, dk), minval=0.0, maxval=1.6)
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (N, T))) if beta is None \
        else jnp.full((N, T), beta)
    return q, k, v, g, b


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _value_and_grads(fn, args):
    def loss(*a):
        return jnp.sum(jnp.sin(fn(*a)))
    return fn(*args), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["plain_scan", "kernels_interpreted"])
@pytest.mark.parametrize("what, kw", [
    ("assumed_decay_T_no_multiple_of_the_chunk", {}),
    ("decay_four_times_stronger", {"decay": 4.0}),
    ("decay_that_overflows_a_division", {"decay": 20.0}),
    ("beta_zero_the_state_only_decays", {"beta": 0.0}),
    ("alpha_one_the_plain_delta_rule", {"decay": 0.0}),
])
def test_chunked_matches_the_recurrence_in_output_and_five_gradients(
        what, kw, interpret):
    args = _inputs(**kw)
    got, got_grads = _value_and_grads(
        lambda *a: dr.gated_delta_rule(*a, chunk=32, interpret=interpret),
        args)
    want, want_grads = _value_and_grads(dr.recurrent_delta_rule, args)
    assert bool(jnp.isfinite(got).all())
    if kw.get("beta") == 0.0:
        assert float(jnp.abs(want).max()) == 0.0    # nothing is ever written
        assert float(jnp.abs(got).max()) == 0.0
    else:
        assert _rel(got, want) < 1e-5
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.linalg.norm(a - b)) \
            <= 1e-4 * float(jnp.linalg.norm(b)) + 1e-6, (what, name)


def test_the_chunk_length_changes_no_number():
    args = _inputs(T=128, decay=4.0)
    outs = [dr.gated_delta_rule(*args, chunk=c) for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        assert _rel(o, outs[0]) < 1e-5


def test_kernels_and_the_plain_scan_agree_on_every_gradient():
    prepared = dr.chunk_prepare(*_inputs(N=8, T=64, dk=128, dv=128), 32)

    def loss(interpret):
        return lambda *a: jnp.sum(jnp.cos(dr.chunk_scan(
            *a, interpret=interpret)))
    plain = jax.grad(loss(None), argnums=tuple(range(6)))(*prepared)
    kernel = jax.grad(loss(True), argnums=tuple(range(6)))(*prepared)
    for a, b in zip(kernel, plain):
        assert _rel(a, b) < 1e-5


_DECAYS = [
    ("assumed_decay", {}),
    ("decay_four_times_stronger", {"decay": 4.0}),
    ("decay_that_overflows_a_division", {"decay": 20.0}),
    ("g_minus_100_a_token_and_channel", {"g": -100.0}),
    ("alpha_one_the_plain_delta_rule", {"decay": 0.0}),
    ("beta_zero_the_state_only_decays", {"beta": 0.0}),
]


def _prepare_inputs(chunk, g=None, **kw):
    q, k, v, log_decay, beta = _inputs(N=2, T=2 * chunk, dk=128, dv=128, **kw)
    if g is not None:
        log_decay = jnp.full_like(log_decay, g)
    return q, k, v, log_decay, beta


def _close(got, want, what):
    assert bool(jnp.isfinite(got).all()), what
    assert float(jnp.linalg.norm(got - want)) \
        <= 2e-5 * float(jnp.linalg.norm(want)) + 1e-6, what


@pytest.mark.parametrize("chunk, what, kw",
                         [(64, *case) for case in _DECAYS]
                         + [(32, *_DECAYS[1])],
                         ids=[c[0] for c in _DECAYS] + ["chunk_32"])
def test_prepare_kernels_match_xla_in_six_results_and_five_gradients(
        chunk, what, kw):
    """`delta_prepare_fwd` and `delta_prepare_bwd` through the interpreter
    against XLA's `chunk_prepare` and `jax.vjp` of it."""
    args = _prepare_inputs(chunk, **kw)
    want, want_vjp = jax.vjp(lambda *a: dr.chunk_prepare(*a, chunk), *args)
    got, got_vjp = jax.vjp(
        lambda *a: dr._prepare_kernels(*a, chunk, True), *args)
    names = "W U~ Qexp(G) Kexp(G_C-G) P exp(G_C)".split()
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        _close(a, b, (what, name))
    cotangents = tuple(jax.random.normal(jax.random.PRNGKey(7 + i), a.shape)
                       for i, a in enumerate(want))
    for name, a, b in zip("q k v g beta".split(), got_vjp(cotangents),
                          want_vjp(cotangents)):
        assert a.shape == b.shape, name
        _close(a, b, (what, "d" + name))


@pytest.mark.parametrize("chunk, dk, fits", [
    (64, 128, True), (32, 128, True), (16, 256, True),
    (24, 128, False),            # no power of two: its halves are not whole
    (8, 128, False),             # under a sub-block
    (64, 96, False),             # the compiler wants whole lane tiles
])
def test_the_shapes_decide_which_preparation_runs(chunk, dk, fits):
    assert dr._prepare_fits(chunk, dk, 128, False) is fits
    assert dr._prepare_fits(chunk, dk, 128, True) is (chunk in (16, 32, 64))


def test_a_chunk_the_kernels_do_not_take_is_prepared_in_xla(monkeypatch):
    """A chunk of 24: `_prepare` hands XLA's results back, whatever the
    backend, and the scan's kernels take them as they take the others."""
    args = _inputs(N=2, T=48, dk=128, dv=128)
    monkeypatch.setattr(dr, "_prepare_kernels", None)   # would raise if run
    for a, b in zip(dr._prepare(*args, 24, True),
                    dr.chunk_prepare(*args, 24)):
        np.testing.assert_array_equal(a, b)
    got, got_grads = _value_and_grads(
        lambda *a: dr.gated_delta_rule(*a, chunk=24, interpret=True), args)
    want, want_grads = _value_and_grads(dr.recurrent_delta_rule, args)
    assert _rel(got, want) < 1e-5
    for a, b in zip(got_grads, want_grads):
        assert _rel(a, b) < 1e-4


def test_rows_are_taken_a_group_at_a_time_and_nothing_moves():
    args = _inputs(N=2 * dr._ROWS_AT_ONCE, T=64)
    got, got_grads = _value_and_grads(
        lambda *a: dr.gated_delta_rule(*a, chunk=32), args)
    halves = [_value_and_grads(lambda *a: dr.gated_delta_rule(*a, chunk=32),
                               tuple(a[s] for a in args))
              for s in (slice(0, dr._ROWS_AT_ONCE),
                        slice(dr._ROWS_AT_ONCE, None))]
    assert _rel(got, jnp.concatenate([h[0] for h in halves])) < 1e-6
    for i, a in enumerate(got_grads):
        assert _rel(a, jnp.concatenate([h[1][i] for h in halves])) < 1e-5


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["xla_prepares", "kernels_interpreted"])
def test_no_exponent_is_positive_at_any_decay(interpret):
    """At 100 a token and channel every form that divides by the
    cumulative decay is inf / inf; the pairwise blocks and the blocks
    relative to a boundary (XLA), and the halves relative to theirs (the
    kernels), are exact zeros and ones."""
    q, k, v, g, beta = _inputs(T=64, decay=0.0)
    g = jnp.full_like(g, -100.0)
    got, grads = _value_and_grads(
        lambda *a: dr.gated_delta_rule(*a, chunk=64, interpret=interpret),
        (q, k, v, g, beta))
    want = dr.recurrent_delta_rule(q, k, v, g, beta)
    assert bool(jnp.isfinite(got).all()) and _rel(got, want) < 1e-5
    assert all(bool(jnp.isfinite(a).all()) for a in grads)


def test_causal_depthwise_conv_is_a_filter_a_channel_over_the_past():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 5))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 5))
    got = np.asarray(causal_depthwise_conv(x, taps))
    x, taps = np.asarray(x), np.asarray(taps)
    for t in range(9):
        want = sum(taps[i] * x[:, t - 3 + i] for i in range(4)
                   if t - 3 + i >= 0)
        np.testing.assert_allclose(got[:, t], want, rtol=1e-5, atol=1e-6)


CFG = {"linear_attn_config": {"num_heads": 2, "head_dim": 16},
       "rms_norm_eps": 1e-5}


def _layer(**kw):
    return KimiDeltaAttention(48, 2, 16, v_head_dim=24, chunk=16,
                              init=jax.nn.initializers.normal(0.3),
                              name="kda_test", **kw)


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["plain_scan", "kernels_interpreted"])
def test_layer_matches_the_token_by_token_reference_on_every_leaf(interpret):
    layer = _layer(interpret=interpret)
    params = layer.build(jax.random.PRNGKey(0))
    assert params["q_conv"].shape == (4, 32)
    assert params["v_kernel"].shape == (48, 48)          # values 24 wide
    assert params["A_log"].shape == (2,) and params["dt_bias"].shape == (32,)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 48))
    cot = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 48))

    def system(p, x):
        return jnp.sum(layer.call(p, x) * cot)

    def plain(p, x):
        return jnp.sum(reference._kda(x, p, CFG, {}, False) * cot)
    assert _rel(layer.call(params, x),
                reference._kda(x, params, CFG, {}, False)) < 1e-5
    got = jax.grad(system, argnums=(0, 1))(params, x)
    want = jax.grad(plain, argnums=(0, 1))(params, x)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    assert len(flat) == 17                                # 16 leaves and x
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(b)) > 0, path
        assert _rel(a, b) < 2e-4, path


def test_layer_stands_where_a_block_puts_its_attention():
    layer = _layer()
    params = layer.build(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 48))
    np.testing.assert_array_equal(layer.call(params, x),
                                  layer.call(params, [x, None]))
    assert layer.compute_output_shape([(1, 24, 48), None]) == (1, 24, 48)
    # the assumed initialisation: decay rates in [1, 16), steps in
    # (0.001, 0.1) through the inverse softplus
    big = KimiDeltaAttention(64, 8, 64, name="kda_init").build(
        jax.random.PRNGKey(5))
    rate = np.exp(np.asarray(big["A_log"]))
    step = np.log1p(np.exp(np.asarray(big["dt_bias"])))
    assert 1.0 <= rate.min() and rate.max() < 16.0
    assert 1e-3 <= step.min() * 1.0001 and step.max() <= 0.1 * 1.0001
    assert float(np.abs(big["q_conv"]).max()) <= 0.5


def test_bfloat16_keeps_the_decay_in_float32():
    layer = _layer()
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                    layer.build(jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 48), jnp.bfloat16)
    g = layer.log_decay(params, x)
    assert g.dtype == jnp.float32 and float(g.max()) <= 0.0
    out = layer.call(params, x)
    assert out.dtype == jnp.bfloat16 and bool(jnp.isfinite(out).all())
    full = layer.call(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), params), x.astype(jnp.float32))
    assert _rel(out.astype(jnp.float32), full) < 0.05


# ------------------------------------------ the q/k/v stage as kernels

_STAGE = {"q_norm_and_scale": 128 ** -0.5, "k_norm": 1.0, "v_plain": None}


def _stage_inputs(dtype, B=2, T=96, n=2, w=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    projected = jax.random.normal(ks[0], (B, T, n * w)).astype(dtype)
    taps = jax.random.uniform(ks[1], (4, n * w), minval=-0.5, maxval=0.5)
    cot = jax.random.normal(ks[2], (B * n, T, w)).astype(dtype)
    return projected, taps, cot


def _wide_layer(**kw):
    return KimiDeltaAttention(64, 2, 128, chunk=16, name="kda_wide",
                              init=jax.nn.initializers.normal(0.1), **kw)


def _stage_pair(projected, taps, cot, scale, tile=32):
    """((rows, d projection, d taps) of the kernels through the
    interpreter, the same of XLA's `_conv_unit`)."""
    layer = _wide_layer()
    sides = []
    for fn in (lambda x, t: sc.short_conv_rows(x, t, 2, scale, _L2_EPS,
                                                True, tile=tile),
               lambda x, t: layer._conv_unit(x, t, scale)):
        rows, vjp = jax.vjp(fn, projected, taps)
        sides.append((rows, *vjp(cot)))
    return sides


@pytest.mark.parametrize("which", list(_STAGE))
def test_short_conv_kernels_match_xla_in_float32(which):
    """`qkv_short_conv_fwd` / `qkv_short_conv_bwd` against `_conv_unit` and
    `jax.vjp` of it: B = 2 (rows head-major), three tiles of 32 tokens."""
    got, want = _stage_pair(*_stage_inputs(jnp.float32), _STAGE[which])
    for name, a, b in zip(("rows", "d_projection", "d_taps"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a).all()), name
        assert _rel(a, b) < 2e-6, (which, name)


@pytest.mark.parametrize("which", list(_STAGE))
def test_short_conv_kernels_match_xla_within_bfloat16_rounding(which):
    """bfloat16 in and out: the kernels filter in float32, so they stand
    no further from `_conv_unit` in float32 on the same inputs than ONE
    rounding of the result (2^-9 an element), and no further than
    `_conv_unit` in bfloat16 does."""
    projected, taps, cot = _stage_inputs(jnp.bfloat16)
    f32 = jnp.float32
    got, xla = _stage_pair(projected, taps, cot, _STAGE[which])
    _, exact = _stage_pair(projected.astype(f32), taps, cot.astype(f32),
                           _STAGE[which])
    for name, a, b, c in zip(("rows", "d_projection", "d_taps"), got, xla,
                             exact):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        ours, theirs = _rel(a.astype(f32), c), _rel(b.astype(f32), c)
        assert ours < 2.0 ** -9, (which, name, ours)
        assert ours <= theirs, (which, name, ours, theirs)


@pytest.mark.parametrize("tile", [16, 32, 96])
def test_short_conv_history_is_zeros_then_the_tile_before(tile):
    """Token 0 sees three zeros, and a tile's first tokens see the last
    tokens of the tile before: against the filter written out in numpy, at
    every token, with rows [B * n, T, w] head-major."""
    projected, taps, _ = _stage_inputs(jnp.float32, T=96, seed=3)
    rows = np.asarray(sc.short_conv_rows(projected, taps, 2, None, _L2_EPS,
                                         True, tile=tile))
    x, f = np.asarray(projected, np.float64), np.asarray(taps, np.float64)
    c = np.zeros_like(x)
    for t in range(96):
        for i in range(4):
            if t - 3 + i >= 0:
                c[:, t] += f[i] * x[:, t - 3 + i]
    want = (c / (1.0 + np.exp(-c))).reshape(2, 96, 2, 128).transpose(
        0, 2, 1, 3).reshape(4, 96, 128)
    np.testing.assert_allclose(rows, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("shape, heads, taps, interpret, fits", [
    ((1, 16384, 4096), 32, 4, True, True),      # the benchmark's
    ((2, 96, 256), 2, 4, True, True),
    ((2, 96, 256), 2, 4, None, False),          # off the TPU
    ((2, 40, 32), 2, 4, True, False),           # heads 16 wide
    ((2, 96, 192), 2, 4, True, False),          # heads 96 wide
    ((2, 100, 256), 2, 4, True, False),         # no whole tiles of 16
    ((2, 96, 256), 2, 12, True, False),         # more history than 8 rows
])
def test_the_shapes_decide_which_qkv_stage_runs(shape, heads, taps,
                                                interpret, fits):
    assert sc.short_conv_fits(shape, heads, taps, interpret) is fits


def test_a_head_the_kernels_do_not_take_runs_xla(monkeypatch):
    """The small layer of this file (heads 16 and 24 wide) under
    `interpret=True`: the stage is XLA's, the recurrence's kernels run."""
    import analytics_zoo_tpu.keras.linear_attention as la
    monkeypatch.setattr(la, "short_conv_rows", None)    # would raise if run
    layer, plain = _layer(interpret=True), _layer()
    params = layer.build(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 48))
    assert _rel(layer.call(params, x), plain.call(params, x)) < 1e-5
    with pytest.raises(TypeError):
        _wide_layer(interpret=True).call(
            _wide_layer().build(jax.random.PRNGKey(0)),
            jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64)))


@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 2e-4),
                                          (jnp.bfloat16, 0.05)],
                         ids=["float32", "bfloat16"])
def test_layer_agrees_on_both_paths_in_output_and_every_gradient(dtype,
                                                                 limit):
    """Heads 128 wide: `interpret=True` takes the stage's kernels (and the
    recurrence's), `None` on the CPU is XLA all the way."""
    kernels, xla = _wide_layer(interpret=True), _wide_layer()
    params = jax.tree_util.tree_map(
        lambda a: a.astype(dtype), xla.build(jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 64)).astype(dtype)
    cot = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 64))

    def loss(layer):
        return lambda p, x: jnp.sum(
            layer.call(p, x).astype(jnp.float32) * cot)
    f32 = jnp.float32
    assert _rel(kernels.call(params, x).astype(f32),
                xla.call(params, x).astype(f32)) < limit
    got = jax.grad(loss(kernels), argnums=(0, 1))(params, x)
    want = jax.grad(loss(xla), argnums=(0, 1))(params, x)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype, path
        assert _rel(a.astype(f32), b.astype(f32)) < limit, path
