"""Training-loop efficiency + determinism contracts.

The hot loop must be asynchronous: at most ONE device→host sync per epoch
(`trainer._materialize`), batches prefetched off-thread, optional k-step
`lax.scan` fusion, and no implicit transfers inside the jitted step
(SURVEY §5 determinism/race items; the reference's engine owns its hot
loop, `Topology.scala:1160-1337`)."""

import jax
import numpy as np
import pytest

from analytics_zoo_tpu.keras import Sequential
from analytics_zoo_tpu.keras import layers as L
from analytics_zoo_tpu.learn import trainer


def _toy_model():
    import optax
    m = Sequential()
    m.add(L.Dense(16, activation="relu", input_shape=(8,)))
    m.add(L.Dense(1))
    m.compile(optimizer=optax.adam(1e-2), loss="mse")
    return m


def _toy_data(n=256):
    rs = np.random.RandomState(0)
    x = rs.randn(n, 8).astype(np.float32)
    return x, (x @ rs.randn(8, 1)).astype(np.float32)


class TestHostSyncBudget:
    def test_one_sync_per_epoch(self, monkeypatch):
        calls = []
        real = trainer._materialize
        monkeypatch.setattr(trainer, "_materialize",
                            lambda x: calls.append(1) or real(x))
        x, y = _toy_data()
        m = _toy_model()
        m.fit(x, y, batch_size=32, nb_epoch=3)
        # exactly one materialization per epoch — the loop never calls
        # float(loss) per step
        assert len(calls) == 3

    def test_one_sync_per_epoch_multistep(self, monkeypatch):
        calls = []
        real = trainer._materialize
        monkeypatch.setattr(trainer, "_materialize",
                            lambda x: calls.append(1) or real(x))
        x, y = _toy_data()
        m = _toy_model()
        m.fit(x, y, batch_size=32, nb_epoch=2, steps_per_run=4)
        assert len(calls) == 2


class TestMultiStepRun:
    def test_converges_and_counts_iterations(self):
        x, y = _toy_data()
        m = _toy_model()
        h = m.fit(x, y, batch_size=32, nb_epoch=20, steps_per_run=4)
        assert h["loss"][-1] < h["loss"][0] * 0.2

    def test_short_final_group(self):
        # 6 batches with steps_per_run=4 → groups of 4 and 2; both compile
        # and the whole dataset is consumed
        x, y = _toy_data(192)          # 6 batches of 32
        m = _toy_model()
        h = m.fit(x, y, batch_size=32, nb_epoch=2, steps_per_run=4)
        assert len(h["loss"]) == 2

    def test_matches_single_step_numerics(self):
        # same seed → the k-step scan must produce the same parameters as
        # k separate dispatches (shuffle off to align batch order)
        x, y = _toy_data(128)
        ma, mb = _toy_model(), _toy_model()
        ha = ma.fit(x, y, batch_size=32, nb_epoch=2, shuffle=False, seed=7)
        hb = mb.fit(x, y, batch_size=32, nb_epoch=2, shuffle=False, seed=7,
                    steps_per_run=4)
        np.testing.assert_allclose(ha["loss"], hb["loss"], rtol=1e-5)
        pa = np.asarray(ma.predict(x, batch_per_thread=32))
        pb = np.asarray(mb.predict(x, batch_per_thread=32))
        np.testing.assert_allclose(pa, pb, rtol=1e-5, atol=1e-6)


class TestMixedPrecision:
    def test_bf16_compute_converges(self):
        x, y = _toy_data()
        m = _toy_model()
        h = m.fit(x, y, batch_size=32, nb_epoch=20, mixed_precision=True)
        assert h["loss"][-1] < h["loss"][0] * 0.3
        # master params stay f32
        for leaf in jax.tree_util.tree_leaves(m.params):
            assert leaf.dtype == np.float32

    def test_float_encoded_ids_not_corrupted(self):
        # nnframes assembles id features as float32; under mixed precision
        # the trainer must NOT cast them to bf16 (bf16 rounds 1000 → 1000±4
        # → wrong embedding rows). Gradient of a gather at id 1000 must
        # land on row 1000 exactly.
        import jax.numpy as jnp
        import optax

        def apply_fn(params, xb, training=False, rng=None):
            ids = xb.astype(jnp.int32)          # layer-level int cast
            return params["table"][ids]

        table = jnp.zeros((1200, 4), jnp.float32)
        opt = optax.sgd(1.0)
        step = trainer.build_train_step(
            apply_fn, lambda y, p: jnp.sum(p), opt, mixed_precision=True)
        params = {"table": table}
        ids = np.full((8,), 1001.0, np.float32)   # bf16(1001) == 1000
        y = np.zeros((8, 4), np.float32)
        params, _, _ = step(params, opt.init(params), jnp.asarray(ids),
                            jnp.asarray(y), jax.random.PRNGKey(0))
        moved = np.flatnonzero(
            np.abs(np.asarray(params["table"])).sum(axis=1))
        assert moved.tolist() == [1001]


class TestDeviceCache:
    def test_matches_streamed_path_numerics(self):
        # shuffle off → identical batch order → identical losses between
        # the device-resident one-dispatch epoch and the streamed path
        x, y = _toy_data(128)
        ma, mb = _toy_model(), _toy_model()
        ha = ma.fit(x, y, batch_size=32, nb_epoch=3, shuffle=False, seed=3,
                    device_cache=False)
        hb = mb.fit(x, y, batch_size=32, nb_epoch=3, shuffle=False, seed=3,
                    device_cache=True)
        np.testing.assert_allclose(ha["loss"], hb["loss"], rtol=1e-5)

    def test_data_transferred_once_across_fits(self):
        x, y = _toy_data(128)
        m = _toy_model()
        m.fit(x, y, batch_size=32, nb_epoch=1, device_cache=True)
        first = m._device_data
        h = m.fit(x, y, batch_size=32, nb_epoch=2, device_cache=True)
        assert m._device_data is first          # cache hit, no re-put
        assert len(h["loss"]) == 2
        assert np.isfinite(h["loss"]).all()

    def test_shuffled_device_epochs_converge(self):
        x, y = _toy_data()
        m = _toy_model()
        h = m.fit(x, y, batch_size=32, nb_epoch=20, device_cache=True)
        assert h["loss"][-1] < h["loss"][0] * 0.3


class TestDeterminism:
    def test_seeded_fit_reproducible(self):
        # SURVEY §5: end-to-end seeded reproducibility of a 2-epoch run
        x, y = _toy_data()
        runs = []
        for _ in range(2):
            m = _toy_model()
            h = m.fit(x, y, batch_size=32, nb_epoch=2, seed=13)
            runs.append((h["loss"],
                         jax.tree_util.tree_leaves(
                             jax.device_get(m.params))))
        assert runs[0][0] == runs[1][0]
        for a, b in zip(runs[0][1], runs[1][1]):
            np.testing.assert_array_equal(a, b)

    def test_prebuilt_model_empty_dataset_still_errors(self):
        # the shape probe is skipped for prebuilt models; an empty dataset
        # must still raise, not silently run 0 steps
        import numpy as np
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        from analytics_zoo_tpu.learn.trainer import fit_keras
        m = Sequential([L.Dense(1, input_shape=(4,))])
        m.compile("sgd", "mse")
        m.ensure_built(np.zeros((1, 4), np.float32))
        # streaming (lazy) path: factory yields no full batches
        with pytest.raises(ValueError, match="no full batches"):
            fit_keras(m, None, None, batch_size=64, epochs=1,
                      batch_iter_factory=lambda epoch: iter(()))

    def test_step_runs_under_transfer_guard(self):
        # once params/batch live on device, the jitted step must not
        # trigger implicit host transfers (SURVEY §5 race/determinism)
        import optax

        from analytics_zoo_tpu.ops import objectives
        m = _toy_model()
        x, y = _toy_data(64)
        m.ensure_built(x[:32])
        opt = optax.adam(1e-3)
        step = trainer.build_train_step(
            m.apply, objectives.get("mse"), opt)
        params = jax.device_put(m.params)
        opt_state = jax.device_put(opt.init(params))
        xb = jax.device_put(x[:32])
        yb = jax.device_put(y[:32])
        rng = jax.device_put(jax.random.PRNGKey(0))
        with jax.transfer_guard("disallow"):
            params, opt_state, loss = step(params, opt_state, xb, yb, rng)
            jax.block_until_ready(loss)

    def test_params_stay_on_device_after_fit(self):
        x, y = _toy_data(64)
        m = _toy_model()
        m.fit(x, y, batch_size=32, nb_epoch=1)
        for leaf in jax.tree_util.tree_leaves(m.params):
            assert isinstance(leaf, jax.Array)

    def test_recompile_invalidates_train_cache(self):
        # compile() with a new loss must not reuse the jitted step closed
        # over the old loss
        import optax
        x, y = _toy_data(64)
        m = _toy_model()
        m.fit(x, y, batch_size=32, nb_epoch=1)
        assert hasattr(m, "_train_cache")
        m.compile(optimizer=optax.adam(1e-2), loss="mae")
        assert not hasattr(m, "_train_cache")
        h = m.fit(x, y, batch_size=32, nb_epoch=1)
        assert np.isfinite(h["loss"][0])

    def test_refit_after_fit_is_safe(self):
        # fit donates parameter buffers; a second fit must not read
        # donated/deleted arrays
        x, y = _toy_data(64)
        m = _toy_model()
        m.fit(x, y, batch_size=32, nb_epoch=1)
        h = m.fit(x, y, batch_size=32, nb_epoch=1)
        assert np.isfinite(h["loss"][0])
        np.asarray(m.predict(x, batch_per_thread=32))


class TestPrefetcher:
    def test_exhausts_when_queue_full_at_end(self):
        # regression: END sentinel must arrive even when the queue is full
        items = list(range(10))
        out = list(trainer._Prefetcher(iter(items), lambda v: v, depth=2))
        assert out == items

    def test_propagates_worker_error(self):
        def bad(v):
            if v == 3:
                raise RuntimeError("boom")
            return v

        pf = trainer._Prefetcher(iter(range(5)), bad, depth=2)
        with pytest.raises(RuntimeError, match="boom"):
            list(pf)
