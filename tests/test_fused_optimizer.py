"""Fused Pallas optimizer + sparse embedding-gradient kernels (ISSUE 9).

The whole suite runs the REAL kernel code through the Pallas interpreter
(`_resolve_interpret`: off-TPU backends auto-select interpret mode), so
the CPU rig exercises the exact block walk Mosaic compiles on a chip.

Covered:
- kernel parity vs optax (fp32 exact-ish, bf16 params, decoupled weight
  decay, schedules, scalar/odd-shaped leaves);
- segment path: touched-rows-only semantics (untouched rows BITWISE
  unchanged), duplicate-id segment sums, parity vs `row_adam_update`;
- fused fit == plain fit losses (dense, multi-step, lazy, sharded on
  the conftest 8-device mesh), config/env engagement, no-twin fallback;
- donation stays in-place + leak_check flat over steps;
- lowering failure raises out of the fit, never plain optax (real
  Mosaic failure on the CPU backend via interpret=False);
- compile-cache keying: fused vs unfused never share an executable;
- auto-resume: bitwise continuation with fused state, actionable error
  on a toggled restore;
- `update_cost` is the seven-pass floor of the sweep;
- the `check_pallas_cost` lint is clean over the package (tier-1 guard:
  every pallas_call carries a cost_estimate).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from analytics_zoo_tpu.keras import Sequential
from analytics_zoo_tpu.keras import layers as L
from analytics_zoo_tpu.learn import trainer
from analytics_zoo_tpu.learn.trainer import fit_keras
from analytics_zoo_tpu.ops.optimizers import (FusedAdamState, as_fused,
                                              fused_adam)
from analytics_zoo_tpu.ops.optimizers import get as get_optimizer
from analytics_zoo_tpu.pallas import fused_adam as fused_mod
from analytics_zoo_tpu.pallas.fused_adam import (fused_adam_step,
                                                 update_cost)
from analytics_zoo_tpu.pallas.segment_update import (segment_adam_update,
                                                     segment_compact)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(rs, shapes, dtype=jnp.float32):
    return {f"p{i}": jnp.asarray(rs.randn(*s) if s else rs.randn(),
                                 dtype) for i, s in enumerate(shapes)}


def _optax_reference(params, grads, steps, opt):
    state = opt.init(params)
    for _ in range(steps):
        updates, state = opt.update(grads, state, params)
        params = optax.apply_updates(params, updates)
    return params


class TestKernelParity:
    SHAPES = [(64, 256), (7,), (3, 5, 11), ()]

    def test_adam_fp32_matches_optax(self):
        rs = np.random.RandomState(0)
        p = _tree(rs, self.SHAPES)
        g = jax.tree_util.tree_map(lambda a: a * 0.01 + 1e-3, p)
        z = jax.tree_util.tree_map(jnp.zeros_like, p)
        mu, nu = z, z
        cur = p
        for t in range(1, 4):       # multi-step: bias correction moves
            cur, mu, nu = fused_adam_step(cur, mu, nu, g, t, lr=1e-3)
        ref = _optax_reference(p, g, 3, optax.adam(1e-3))
        for k in p:
            np.testing.assert_allclose(np.asarray(cur[k]),
                                       np.asarray(ref[k]),
                                       rtol=1e-6, atol=1e-7)

    def test_adamw_decoupled_decay_matches_optax(self):
        rs = np.random.RandomState(1)
        p = _tree(rs, [(32, 128), (128,)])
        g = jax.tree_util.tree_map(lambda a: a * 0.02, p)
        z = jax.tree_util.tree_map(jnp.zeros_like, p)
        new, _, _ = fused_adam_step(p, z, z, g, 1, lr=1e-3, eps=1e-6,
                                    weight_decay=0.01)
        ref = _optax_reference(p, g, 1, optax.adamw(1e-3, eps=1e-6,
                                                    weight_decay=0.01))
        for k in p:
            np.testing.assert_allclose(np.asarray(new[k]),
                                       np.asarray(ref[k]),
                                       rtol=1e-6, atol=1e-7)

    def test_bf16_params_f32_moments(self):
        rs = np.random.RandomState(2)
        p = _tree(rs, [(16, 128)], jnp.bfloat16)
        g = jax.tree_util.tree_map(lambda a: a * 0.01, p)
        z = {"p0": jnp.zeros((16, 128), jnp.float32)}
        new, mu, nu = fused_adam_step(p, z, z, g, 1, lr=1e-2)
        assert new["p0"].dtype == jnp.bfloat16
        assert mu["p0"].dtype == jnp.float32
        ref = _optax_reference(
            jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p),
            jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), g),
            1, optax.adam(1e-2))
        np.testing.assert_allclose(
            np.asarray(new["p0"], np.float32), np.asarray(ref["p0"]),
            rtol=2e-2, atol=2e-3)   # bf16 write-back tolerance

    def test_schedule_lr(self):
        sched = optax.linear_schedule(1e-2, 1e-3, 10)
        rs = np.random.RandomState(3)
        p = _tree(rs, [(8, 128)])
        g = jax.tree_util.tree_map(lambda a: a * 0.1, p)
        opt = fused_adam(learning_rate=sched)
        state = opt.init(p)
        new, state = opt.fused_apply(g, state, p)
        ref = _optax_reference(p, g, 1, optax.adam(sched))
        np.testing.assert_allclose(np.asarray(new["p0"]),
                                   np.asarray(ref["p0"]),
                                   rtol=1e-6, atol=1e-7)

    def test_update_keeps_optax_contract(self):
        # the (init, update) surface returns an updates TREE any generic
        # optax consumer can apply_updates — the fused_apply fast path
        # and the contract path must land on the same parameters
        rs = np.random.RandomState(4)
        p = _tree(rs, [(8, 128), (5,)])
        g = jax.tree_util.tree_map(lambda a: a * 0.1, p)
        opt = fused_adam(1e-3)
        updates, s1 = opt.update(g, opt.init(p), p)
        via_updates = optax.apply_updates(p, updates)
        direct, s2 = opt.fused_apply(g, opt.init(p), p)
        for k in p:
            np.testing.assert_allclose(np.asarray(via_updates[k]),
                                       np.asarray(direct[k]),
                                       rtol=1e-6, atol=1e-7)
        assert int(s1.count) == int(s2.count) == 1


class TestFusedTransformation:
    def test_state_mirrors_scale_by_adam(self):
        # (count, mu, nu) field-for-field: sharding rule tables and
        # checkpoint layouts treat the fused state like stock Adam's
        p = {"w": jnp.ones((4, 128))}
        st = fused_adam(1e-3).init(p)
        assert isinstance(st, FusedAdamState)
        assert st._fields == ("count", "mu", "nu")
        assert st.mu["w"].shape == (4, 128)

    def test_registry_get_passes_fused_through(self):
        opt = fused_adam(1e-3)
        assert get_optimizer(opt) is opt

    def test_as_fused_maps_exact_twins_only(self):
        assert as_fused(get_optimizer("adam"), "adam") is not None
        assert as_fused(get_optimizer("adamw"), "adamw") is not None
        assert as_fused(get_optimizer("sgd"), "sgd") is None
        # instance compiles carry closures we must not guess at
        assert as_fused(optax.adam(5e-4), None) is None
        fused = fused_adam(1e-3)
        assert as_fused(fused, None) is fused


class TestBlockBudget:
    def test_budget_counts_lanes_as_vmem_pads_them(self):
        """7 operands, double-buffered, must sit well under Mosaic's 16 MB
        scoped-VMEM limit at the width VMEM holds (lanes padded to 128).
        The NCF bench's 64-wide table ran out of it on the chip when the
        budget used the unpadded width."""
        for rows, cols in ((138001, 64), (27001, 64), (30522, 768),
                           (768, 3072), (1, 768), (64, 2)):
            bm = fused_mod._block_rows(rows, cols)
            lanes = -(-cols // 128) * 128
            assert 14 * bm * lanes * 4 <= 8 * 2 ** 20, (rows, cols, bm)
            assert bm == rows or bm % 8 == 0


class TestSegmentPath:
    def test_untouched_rows_bitwise_unchanged(self):
        rs = np.random.RandomState(0)
        V, D, B = 64, 16, 12
        table = jnp.asarray(rs.randn(V, D), jnp.float32)
        mu = jnp.asarray(rs.rand(V, D), jnp.float32)
        nu = jnp.asarray(rs.rand(V, D), jnp.float32)
        ids = jnp.asarray([3, 9, 3, 17, 9, 9, 40, 41, 42, 3, 17, 63],
                          jnp.int32)
        rows = jnp.asarray(rs.randn(B, D), jnp.float32)
        t2, m2, n2 = jax.jit(lambda *a: segment_adam_update(
            *a, 1, lr=1e-3))(table, mu, nu, ids, rows)
        touched = np.zeros(V, bool)
        touched[np.asarray(ids)] = True
        for new, old in ((t2, table), (m2, mu), (n2, nu)):
            a, b = np.asarray(new), np.asarray(old)
            assert (a[~touched] == b[~touched]).all(), \
                "untouched rows must be untouched BYTES"
            assert (a[touched] != b[touched]).any()

    def test_matches_row_adam_update(self):
        from analytics_zoo_tpu.learn.lazy_embedding import (
            LazyEmbeddingSpec, row_adam_update)
        rs = np.random.RandomState(1)
        V, D, B = 50, 8, 16
        table = jnp.asarray(rs.randn(V, D), jnp.float32)
        z = jnp.zeros((V, D))
        ids = jnp.asarray(rs.randint(0, V, B), jnp.int32)
        rows = jnp.asarray(rs.randn(B, D), jnp.float32)
        g_table = jnp.zeros((V, D)).at[ids].add(rows)  # dense equivalent
        spec = LazyEmbeddingSpec(path=("t",), ids_fn=None, lr=1e-3)
        rt, rm, rv = row_adam_update(spec, table, z, z, g_table, ids,
                                     jnp.asarray(1, jnp.int32))
        ft, fm, fv = segment_adam_update(table, z, z, ids, rows, 1,
                                         lr=1e-3)
        # same math; the duplicate-id sums reduce in a different order
        # (sorted segments vs dense scatter-add), so fp-tolerance
        for ref, got in ((rt, ft), (rm, fm), (rv, fv)):
            np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                       rtol=1e-6, atol=1e-9)

    def test_segment_compact_sums_duplicates(self):
        ids = jnp.asarray([5, 2, 5, 9, 2, 5], jnp.int32)
        rows = jnp.arange(6 * 3, dtype=jnp.float32).reshape(6, 3)
        uids, valid, g = segment_compact(ids, rows)
        uids, valid, g = (np.asarray(uids), np.asarray(valid),
                          np.asarray(g))
        n = int(valid.sum())
        assert n == 3
        assert uids[:n].tolist() == [2, 5, 9]
        np.testing.assert_allclose(g[0], np.asarray(rows[1] + rows[4]))
        np.testing.assert_allclose(g[1],
                                   np.asarray(rows[0] + rows[2] + rows[5]))
        np.testing.assert_allclose(g[2], np.asarray(rows[3]))
        # the redirected tail points at the LAST valid slot (safe target)
        assert (uids[n:] == uids[n - 1]).all()


def _dense_model(optimizer="adam"):
    m = Sequential()
    m.add(L.Dense(32, activation="relu", input_shape=(16,)))
    m.add(L.Dense(4))
    m.compile(optimizer=optimizer, loss="mse")
    return m


def _dense_data(n=128):
    rs = np.random.RandomState(5)
    x = rs.randn(n, 16).astype(np.float32)
    return x, (x @ rs.randn(16, 4)).astype(np.float32)


FIT_KW = dict(batch_size=32, seed=7, shuffle=False, distributed=False,
              device_cache=False, prefetch=False)


class TestFusedFit:
    def test_losses_match_plain_fit(self):
        x, y = _dense_data()
        h_plain = fit_keras(_dense_model(), x, y, epochs=3,
                            fused_optimizer=False, **FIT_KW)
        h_fused = fit_keras(_dense_model(), x, y, epochs=3,
                            fused_optimizer=True, **FIT_KW)
        np.testing.assert_allclose(h_fused["loss"], h_plain["loss"],
                                   rtol=1e-5)

    def test_multistep_and_refit_hit_cache(self):
        x, y = _dense_data()
        m = _dense_model()
        fit_keras(m, x, y, epochs=1, steps_per_run=2,
                  fused_optimizer=True, **FIT_KW)
        cached = m._train_cache
        fit_keras(m, x, y, epochs=1, steps_per_run=2,
                  fused_optimizer=True, **FIT_KW)
        assert m._train_cache is cached
        h = fit_keras(m, x, y, epochs=8, fused_optimizer=True, **FIT_KW)
        assert h["loss"][-1] < h["loss"][0]

    def test_env_engages_fused(self, monkeypatch):
        # ZOO_FUSED_OPT=1 must swap the state tree to FusedAdamState —
        # observable through the checkpoint layout marker
        monkeypatch.setenv("ZOO_FUSED_OPT", "1")
        x, y = _dense_data(64)
        m = _dense_model()
        calls = []
        real = trainer._pick_one_step

        def spy(*a, **kw):
            calls.append(a[6] if len(a) > 6 else kw.get("fused"))
            return real(*a, **kw)
        monkeypatch.setattr(trainer, "_pick_one_step", spy)
        fit_keras(m, x, y, epochs=1, **FIT_KW)
        assert calls == [True]

    def test_no_twin_optimizer_falls_back_with_warning(self, caplog):
        x, y = _dense_data(64)
        m = _dense_model(optimizer=optax.adam(5e-4))  # instance: no twin
        with caplog.at_level("WARNING"):
            h = fit_keras(m, x, y, epochs=1, fused_optimizer=True,
                          **FIT_KW)
        assert np.isfinite(h["loss"][0])
        assert any("no exact fused twin" in r.message
                   for r in caplog.records)

    def test_mixed_precision_composes(self):
        x, y = _dense_data()
        h = fit_keras(_dense_model(), x, y, epochs=4, mixed_precision=True,
                      fused_optimizer=True, **FIT_KW)
        assert h["loss"][-1] < h["loss"][0]


class TestLazyFusedFit:
    def _emb_model(self, with_set_ids=True):
        from analytics_zoo_tpu.learn.lazy_embedding import LazyEmbeddingSpec
        m = Sequential()
        emb = L.Embedding(50, 8, input_shape=(4,))
        m.add(emb)
        m.compile(optimizer="adam", loss="mse")
        kw = {}
        if with_set_ids:
            kw["set_ids_fn"] = lambda xb, ids: jnp.reshape(
                ids.astype(xb.dtype), (-1, 4))
        m.lazy_embedding_specs = [LazyEmbeddingSpec(
            (emb.name, "embeddings"),
            lambda xb: jnp.reshape(jnp.asarray(xb, jnp.int32), (-1,)),
            **kw)]
        return m, emb

    def _emb_data(self, lo=0, hi=40):
        rs = np.random.RandomState(6)
        x = rs.randint(lo, hi, (64, 4)).astype(np.float32)
        return x, rs.randn(64, 4, 8).astype(np.float32)

    @pytest.mark.parametrize("with_set_ids", [True, False])
    def test_matches_lazy_unfused(self, with_set_ids):
        # set_ids_fn declared → rows-reindexed backward (no dense
        # cotangent); without it → dense-grad gather fallback. Same
        # numbers either way.
        x, y = self._emb_data()
        m1, _ = self._emb_model(with_set_ids)
        h1 = fit_keras(m1, x, y, epochs=2, lazy_embeddings=True,
                       **FIT_KW)
        m2, _ = self._emb_model(with_set_ids)
        h2 = fit_keras(m2, x, y, epochs=2, lazy_embeddings=True,
                       fused_optimizer=True, **FIT_KW)
        np.testing.assert_allclose(h2["loss"], h1["loss"], rtol=1e-5)

    def test_untouched_rows_bitwise_through_fit(self):
        # ids drawn from [0, 40): rows 40..49 must be BIT-identical to
        # the initial table after a whole fused fit
        x, y = self._emb_data(lo=0, hi=40)
        m, emb = self._emb_model()
        m.ensure_built(x[:32], jax.random.PRNGKey(7))
        init_rows = np.asarray(
            m.params[emb.name]["embeddings"])[40:].copy()
        fit_keras(m, x, y, epochs=2, lazy_embeddings=True,
                  fused_optimizer=True, **FIT_KW)
        final_rows = np.asarray(m.params[emb.name]["embeddings"])[40:]
        np.testing.assert_array_equal(final_rows, init_rows)


class TestShardedFused:
    @pytest.fixture()
    def fsdp_ctx(self):
        from analytics_zoo_tpu.common import context as ctx_mod
        prev = ctx_mod._GLOBAL["context"]
        yield ctx_mod.init_zoo_context(data=2, fsdp=4)
        ctx_mod._GLOBAL["context"] = prev

    def _model(self):
        m = Sequential([L.Dense(64, input_shape=(32,)), L.Dense(8)])
        m.compile(optimizer="adam", loss="mse")
        return m

    def _data(self, n=128):
        rs = np.random.RandomState(8)
        x = rs.randn(n, 32).astype(np.float32)
        return x, (x @ rs.randn(32, 8)).astype(np.float32)

    KW = dict(batch_size=16, seed=7, shuffle=False, device_cache=False,
              prefetch=False)

    def test_sharded_fused_matches_sharded_plain(self, fsdp_ctx):
        x, y = self._data()
        h1 = fit_keras(self._model(), x, y, epochs=2, sharding_rules=True,
                       **self.KW)
        h2 = fit_keras(self._model(), x, y, epochs=2, sharding_rules=True,
                       fused_optimizer=True, **self.KW)
        np.testing.assert_allclose(h2["loss"], h1["loss"], rtol=1e-5)

    def test_state_stays_rule_sharded(self, fsdp_ctx):
        from analytics_zoo_tpu.parallel.sharding import param_specs
        x, y = self._data()
        m = self._model()
        fit_keras(m, x, y, epochs=1, sharding_rules=True,
                  fused_optimizer=True, **self.KW)
        specs = param_specs(m.params, fsdp_ctx.mesh)
        for leaf, spec in zip(jax.tree_util.tree_leaves(m.params),
                              jax.tree_util.tree_leaves(specs)):
            assert leaf.sharding.spec == spec

    def test_donation_preserved(self, fsdp_ctx):
        from analytics_zoo_tpu.observability.memwatch import leak_check
        from analytics_zoo_tpu.ops import objectives
        from analytics_zoo_tpu.parallel.sharding import tree_shardings
        mesh = fsdp_ctx.mesh
        m = self._model()
        x, y = self._data()
        m.ensure_built(x[:16])
        opt = fused_adam(1e-3)
        p_sh = tree_shardings(m.params, mesh)
        params = trainer._put_with_shardings(m.params, p_sh)
        state = opt.init(params)
        o_sh = tree_shardings(state, mesh)
        state = trainer._put_with_shardings(state, o_sh)
        step = trainer.build_train_step(
            m.apply, objectives.get("mse"), opt, fused=True,
            shardings=trainer._step_shardings(mesh, p_sh, o_sh))
        xb = trainer._put_batch(x[:16], mesh)
        yb = trainer._put_batch(y[:16], mesh)
        rng = jax.random.PRNGKey(0)
        old_leaf = jax.tree_util.tree_leaves(params)[0]
        params, state, loss = step(params, state, xb, yb, rng)
        jax.block_until_ready(loss)
        assert old_leaf.is_deleted(), \
            "input param buffer survived the donated fused step"
        with leak_check(tolerance_bytes=1 << 18):
            for _ in range(4):
                params, state, loss = step(params, state, xb, yb, rng)
            jax.block_until_ready(loss)

    def test_sharded_fused_auto_resume_bitwise(self, fsdp_ctx, tmp_path):
        x, y = self._data()
        kw = dict(self.KW, sharding_rules=True, fused_optimizer=True)
        h_full = fit_keras(self._model(), x, y, epochs=4, **kw)
        m_a = self._model()
        m_a.set_checkpoint(str(tmp_path))
        fit_keras(m_a, x, y, epochs=2, **kw)
        m_b = self._model()
        m_b.set_checkpoint(str(tmp_path))
        h_res = fit_keras(m_b, x, y, epochs=4, auto_resume=True, **kw)
        assert h_res["loss"] == h_full["loss"][2:]


class TestNoFallback:
    def test_kernel_that_fails_to_lower_raises(self, monkeypatch, caplog):
        # interpret=False on the CPU backend IS a real Mosaic lowering
        # failure. A fit that was asked for the fused step must die on
        # it — running plain optax under the fused name is how an A/B
        # ends up comparing optax with optax.
        monkeypatch.setattr(fused_mod, "_resolve_interpret",
                            lambda interpret: False)
        x, y = _dense_data()
        with pytest.raises(Exception, match="(?i)interpret|mosaic|tpu"):
            fit_keras(_dense_model(), x, y, epochs=1,
                      fused_optimizer=True, **FIT_KW)
        assert not [r for r in caplog.records
                    if "falling back to plain optax" in r.message]

    def test_env_request_raises_too(self, monkeypatch):
        monkeypatch.setenv("ZOO_FUSED_OPT", "1")
        monkeypatch.setattr(fused_mod, "_resolve_interpret",
                            lambda interpret: False)
        x, y = _dense_data()
        with pytest.raises(Exception, match="(?i)interpret|mosaic|tpu"):
            fit_keras(_dense_model(), x, y, epochs=1, **FIT_KW)

    def test_interpreter_is_never_resolved_on_a_tpu_backend(
            self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert fused_mod._resolve_interpret(None) is False
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert fused_mod._resolve_interpret(None) is True


class TestCompileCacheKeying:
    def test_toggle_never_shares_an_executable(self, tmp_path):
        # same model/shapes, fused on/off: the AOT disk keys must
        # differ — a hit on the other mode's entry would run the other
        # mode's program. New entries appear for each mode; a re-fit in
        # the same mode adds none (its own entry hits).
        cc = str(tmp_path / "cc")

        def entries():
            return {f for f in os.listdir(cc)
                    if not f.startswith("xla")} if os.path.isdir(cc) \
                else set()

        x, y = _dense_data(64)
        fit_keras(_dense_model(), x, y, epochs=1, fused_optimizer=True,
                  compile_cache_dir=cc, **FIT_KW)
        after_fused = entries()
        assert after_fused, "fused fit persisted no executable"
        fit_keras(_dense_model(), x, y, epochs=1, fused_optimizer=False,
                  compile_cache_dir=cc, **FIT_KW)
        after_plain = entries()
        assert after_plain > after_fused, \
            "unfused fit hit the fused entry (stale executable)"
        fit_keras(_dense_model(), x, y, epochs=1, fused_optimizer=True,
                  compile_cache_dir=cc, **FIT_KW)
        assert entries() == after_plain, \
            "fused re-fit missed its own cached executable"


class TestAutoResumeFused:
    def test_bitwise_continuation(self, tmp_path):
        x, y = _dense_data()
        kw = dict(FIT_KW, fused_optimizer=True)
        h_full = fit_keras(_dense_model(), x, y, epochs=4, **kw)
        m_a = _dense_model()
        m_a.set_checkpoint(str(tmp_path))
        fit_keras(m_a, x, y, epochs=2, **kw)
        m_b = _dense_model()
        m_b.set_checkpoint(str(tmp_path))
        h_res = fit_keras(m_b, x, y, epochs=4, auto_resume=True, **kw)
        assert h_res["loss"] == h_full["loss"][2:]

    def test_toggled_restore_refuses(self, tmp_path):
        x, y = _dense_data(64)
        m_a = _dense_model()
        m_a.set_checkpoint(str(tmp_path))
        fit_keras(m_a, x, y, epochs=1, fused_optimizer=True, **FIT_KW)
        m_b = _dense_model()
        m_b.set_checkpoint(str(tmp_path))
        with pytest.raises(ValueError, match="fused_optimizer toggled"):
            fit_keras(m_b, x, y, epochs=2, auto_resume=True,
                      fused_optimizer=False, **FIT_KW)


class TestRooflineAccounting:
    def test_update_cost_is_the_seven_pass_floor(self):
        p = {"w": jnp.zeros((100, 64), jnp.float32),
             "h": jnp.zeros((100, 64), jnp.bfloat16)}
        _, b = update_cost(p)
        n = 100 * 64
        # f32 leaf: g + 2(m,v) reads + (m,v) writes f32, p rw → 28n;
        # bf16 leaf: p rw at 2 bytes → 24n
        assert b == n * (4 + 2 * 4 + 4 * 4) + n * (4 + 2 * 2 + 4 * 4)


class TestPallasCostLint:
    def test_every_pallas_call_carries_cost_estimate(self):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import check_pallas_cost
            errors = check_pallas_cost.check(REPO)
        finally:
            sys.path.pop(0)
        assert errors == [], "\n".join(errors)

    def test_lint_catches_a_bare_call(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import check_pallas_cost
            bad = tmp_path / "k.py"
            bad.write_text("out = pl.pallas_call(kern, grid=(1,),\n"
                           "    out_shape=s)(x)\n")
            errs = check_pallas_cost.check_file(str(bad))
            assert len(errs) == 1 and "cost_estimate" in errs[0]
            ok = tmp_path / "ok.py"
            ok.write_text("out = pl.pallas_call(kern,\n"
                          "    cost_estimate=pl.CostEstimate(flops=1,\n"
                          "        bytes_accessed=1, transcendentals=0),\n"
                          "    )(x)\n")
            assert check_pallas_cost.check_file(str(ok)) == []
            waived = tmp_path / "w.py"
            waived.write_text(
                "out = pl.pallas_call(kern)(x)"
                "  # pallas-cost-ok: scratch-only microbench\n")
            assert check_pallas_cost.check_file(str(waived)) == []
        finally:
            sys.path.pop(0)
