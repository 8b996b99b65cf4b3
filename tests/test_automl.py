"""AutoML + Zouwu tests: search engine semantics, feature transformer,
forecaster models, AutoTS end-to-end, anomaly detectors. Small data/epochs —
the reference's automl tests also run single-host tiny trials."""

import statistics

import numpy as np
import pandas as pd
import pytest

from analytics_zoo_tpu.automl import (SearchEngine, hp,
                                      TimeSequenceFeatureTransformer)
from analytics_zoo_tpu.automl.search import _expand
from analytics_zoo_tpu.automl.recipe import Recipe


def make_df(n=160, freq="h"):
    rng = np.random.RandomState(0)
    t = pd.date_range("2020-01-01", periods=n, freq=freq)
    value = np.sin(np.arange(n) * 0.3) + rng.randn(n) * 0.05
    return pd.DataFrame({"datetime": t, "value": value})


class TestSearchSpace:
    def test_grid_expansion_and_dedupe(self):
        space = {"a": hp.grid_search([1, 2]), "b": hp.grid_search([3, 4]),
                 "c": 7}
        configs = _expand(space, num_samples=2)
        assert len(configs) == 4  # dedupe: no samplers -> 4 unique
        assert {(c["a"], c["b"]) for c in configs} == \
            {(1, 3), (1, 4), (2, 3), (2, 4)}
        assert all(c["c"] == 7 for c in configs)

    def test_samplers(self):
        space = {"u": hp.uniform(0, 1), "l": hp.loguniform(1e-4, 1e-1),
                 "i": hp.randint(2, 5), "ch": hp.choice([10, 20])}
        cfgs = _expand(space, num_samples=20, seed=1)
        assert all(0 <= c["u"] <= 1 for c in cfgs)
        assert all(1e-4 <= c["l"] <= 1e-1 for c in cfgs)
        assert all(c["i"] in (2, 3, 4) for c in cfgs)
        assert all(c["ch"] in (10, 20) for c in cfgs)


class TestSearchEngine:
    def _quad_fn(self, config, data, budget):
        return {"mse": (config["x"] - 3) ** 2 + 1.0 / budget}

    def test_finds_best(self):
        eng = SearchEngine(metric="mse", mode="min")
        eng.compile(None, self._quad_fn,
                    search_space={"x": hp.grid_search([0, 1, 2, 3, 4])})
        eng.run()
        assert eng.get_best_config()["x"] == 3

    def test_asha_promotes_best(self):
        eng = SearchEngine(metric="mse", scheduler="asha", eta=2,
                           grace_budget=1, max_budget=8)
        eng.compile(None, self._quad_fn,
                    search_space={"x": hp.grid_search(list(range(8)))})
        trials = eng.run()
        best = eng.get_best_trials(1)[0]
        assert best.config["x"] == 3
        assert best.budget == 8          # promoted to max budget
        # most trials stopped early
        assert sum(t.budget == 8 for t in trials) < len(trials)

    def test_failed_trials_tolerated(self):
        def fn(config, data, budget):
            if config["x"] == 1:
                raise RuntimeError("boom")
            return {"mse": config["x"]}
        eng = SearchEngine(metric="mse")
        eng.compile(None, fn, search_space={"x": hp.grid_search([0, 1, 2])})
        trials = eng.run()
        assert sum(not t.ok for t in trials) == 1
        assert eng.get_best_config()["x"] == 0


def _sleepy_fn(config, data, budget):
    # module-level so the spawn process pool can pickle it
    import time as _t
    _t.sleep(0.25)
    return {"mse": (config["x"] - 3) ** 2}


def _bowl_fn(config, data, budget):
    return {"mse": (config["x"] - 0.5) ** 2 + (config["y"] - 0.5) ** 2}


_BOWL_SPACE = {"x": hp.uniform(-2.0, 2.0), "y": hp.uniform(-2.0, 2.0)}


def _best_on_the_bowl(search_alg, seed, budget=48):
    eng = SearchEngine(metric="mse", num_samples=budget, seed=seed,
                       backend="serial", search_alg=search_alg)
    eng.compile(None, _bowl_fn, search_space=_BOWL_SPACE).run()
    assert len(eng.trials) == budget
    return eng.get_best_trials(1)[0].metric


class TestParallelSearch:
    def test_wall_clock_scales_with_workers(self):
        import time as _t
        space = {"x": hp.grid_search(list(range(8)))}
        t0 = _t.perf_counter()
        SearchEngine(metric="mse", backend="serial").compile(
            None, _sleepy_fn, search_space=space).run()
        serial = _t.perf_counter() - t0
        t0 = _t.perf_counter()
        eng = SearchEngine(metric="mse", backend="local", n_workers=8)
        eng.compile(None, _sleepy_fn, search_space=space).run()
        parallel = _t.perf_counter() - t0
        assert eng.get_best_config()["x"] == 3
        assert parallel < serial * 0.5, (serial, parallel)

    def test_process_backend(self):
        space = {"x": hp.grid_search([1, 2, 3, 4])}
        eng = SearchEngine(metric="mse", backend="process", n_workers=2)
        eng.compile(None, _sleepy_fn, search_space=space).run()
        assert eng.get_best_config()["x"] == 3

    def test_asha_rungs_parallel(self):
        eng = SearchEngine(metric="mse", scheduler="asha", eta=2,
                           grace_budget=1, max_budget=8, n_workers=8)
        eng.compile(None, lambda c, d, b: {"mse": (c["x"] - 3) ** 2 + 1.0 / b},
                    search_space={"x": hp.grid_search(list(range(8)))})
        eng.run()
        assert eng.get_best_config()["x"] == 3

    def test_ray_backend_falls_back_without_ray(self, caplog):
        import logging
        with caplog.at_level(logging.WARNING, "analytics_zoo_tpu.automl"):
            eng = SearchEngine(metric="mse", backend="ray")
        assert eng.backend in ("local", "ray")
        try:
            import ray  # noqa: F401
        except ImportError:
            assert eng.backend == "local"
            assert any("ray" in r.message for r in caplog.records)


class TestTPESearch:
    def test_tpe_beats_random_on_fixed_budget(self):
        """Over 32 seeds, not at one: a search is a random variable, and
        at a single seed either sampler can win. The objective is a
        separable bowl because TPE models each dimension on its own: its
        per-dimension densities are the right model there, and the good
        quarter it samples around closes in on the minimum, where
        uniform draws stay uniform. (On a curved valley such as
        Rosenbrock's the dimensions are coupled and this sampler has no
        edge: it won 7 of 16 seeds there.) Four disjoint ranges of 32
        seeds read medians 3.6 to 12 times lower than random's and 22
        to 27 seeds won; the limits stand inside that."""
        seeds = range(32)
        tpe = [_best_on_the_bowl("tpe", s) for s in seeds]
        rand = [_best_on_the_bowl(None, s) for s in seeds]
        assert 2 * statistics.median(tpe) < statistics.median(rand), \
            (statistics.median(tpe), statistics.median(rand))
        won = sum(t < r for t, r in zip(tpe, rand))
        assert won >= 20, won

    def test_tpe_keeps_grid_dims(self):
        # grid keys must appear in every TPE-suggested config (as
        # categoricals), not just in the startup expansion
        space = {"cell": hp.grid_search(["a", "b"]),
                 "x": hp.uniform(0.0, 1.0)}

        def fn(config, data, budget):
            return {"mse": (0.0 if config["cell"] == "b" else 1.0)
                    + config["x"]}

        eng = SearchEngine(metric="mse", num_samples=12, seed=1,
                           backend="serial", search_alg="tpe")
        eng.compile(None, fn, search_space=space).run()
        assert all(t.ok for t in eng.trials), \
            [t.error for t in eng.trials if not t.ok]
        assert all("cell" in t.config for t in eng.trials)
        assert eng.get_best_config()["cell"] == "b"

    def test_tpe_with_asha_rejected(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            SearchEngine(metric="mse", scheduler="asha", search_alg="tpe")

    def test_bayes_beats_random_on_fixed_budget(self):
        """Over 8 seeds, on the same bowl: a smooth quadratic is what an
        RBF-kernel surrogate fits from a few dozen points, so expected
        improvement lands next to the minimum while uniform draws do
        not. Three disjoint ranges of 8 seeds read medians 56 to 121
        times lower than random's and 8, 8 and 7 seeds won, so a
        factor of 10 and 6 seeds of 8 leave room for a BLAS that rounds
        another way."""
        seeds = range(8)
        gp = [_best_on_the_bowl("bayes", s) for s in seeds]
        rand = [_best_on_the_bowl(None, s) for s in seeds]
        assert 10 * statistics.median(gp) < statistics.median(rand), \
            (statistics.median(gp), statistics.median(rand))
        won = sum(g < r for g, r in zip(gp, rand))
        assert won >= 6, won

    def test_bayes_handles_mixed_space(self):
        # categoricals one-hot encode; loguniform encodes in log space
        space = {"cell": hp.grid_search(["a", "b"]),
                 "lr": hp.loguniform(1e-5, 1e-1),
                 "n": hp.randint(1, 8)}

        def fn(config, data, budget):
            import math
            return {"mse": (0.0 if config["cell"] == "b" else 1.0)
                    + abs(math.log10(config["lr"]) + 3) + config["n"] * 0.1}

        eng = SearchEngine(metric="mse", num_samples=24, seed=2,
                           backend="serial", search_alg="bayes")
        eng.compile(None, fn, search_space=space).run()
        assert all(t.ok for t in eng.trials), \
            [t.error for t in eng.trials if not t.ok]
        best = eng.get_best_config()
        assert best["cell"] == "b"
        assert 1e-5 <= best["lr"] <= 1e-1

    def test_bayes_with_asha_rejected(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            SearchEngine(metric="mse", scheduler="asha",
                         search_alg="bayes")

    def test_process_backend_rejects_closures(self):
        captured = []

        def closure_fn(config, data, budget):
            captured.append(config)
            return {"mse": 0.0}

        eng = SearchEngine(metric="mse", backend="process", n_workers=2)
        eng.compile(None, closure_fn,
                    search_space={"x": hp.grid_search([1, 2, 3])})
        with pytest.raises(ValueError, match="picklable"):
            eng.run()

    def test_tpe_handles_choice_and_randint(self):
        import math
        space = {"cell": hp.choice(["lstm", "gru"]),
                 "units": hp.randint(8, 64),
                 "lr": hp.loguniform(1e-4, 1e-1)}

        def fn(config, data, budget):
            base = 0.0 if config["cell"] == "gru" else 1.0
            return {"mse": base + abs(config["units"] - 32) / 32
                    + abs(math.log10(config["lr"]) + 2)}

        eng = SearchEngine(metric="mse", num_samples=40, seed=3,
                           backend="serial", search_alg="tpe")
        eng.compile(None, fn, search_space=space).run()
        best = eng.get_best_config()
        assert best["cell"] == "gru"
        assert 8 <= best["units"] < 64


class TestFeatureTransformer:
    def test_shapes_and_inverse(self):
        df = make_df(100)
        tf = TimeSequenceFeatureTransformer(past_seq_len=5, future_seq_len=2)
        x, y = tf.fit_transform(df)
        assert x.shape == (94, 5, tf.feature_dim)
        assert y.shape == (94, 2)
        # inverse scaling recovers original target values
        raw = df["value"].values
        y0 = tf.post_processing(y)
        np.testing.assert_allclose(y0[0], raw[5:7], atol=1e-5)

    def test_transform_without_y(self):
        df = make_df(50)
        tf = TimeSequenceFeatureTransformer(past_seq_len=4)
        tf.fit_transform(df)
        x = tf.transform(df, is_train=False)
        assert x.shape[0] == 47  # no horizon clipped

    def test_state_roundtrip(self):
        df = make_df(60)
        tf = TimeSequenceFeatureTransformer(past_seq_len=3)
        x, _ = tf.fit_transform(df)
        tf2 = TimeSequenceFeatureTransformer.from_state(tf.state())
        np.testing.assert_allclose(tf2.transform(df, is_train=False),
                                   tf.transform(df, is_train=False))

    def test_unknown_feature_raises(self):
        with pytest.raises(ValueError, match="Unknown datetime feature"):
            TimeSequenceFeatureTransformer(
                selected_features=["NOPE"]).fit_transform(make_df(30))

    def test_too_short_series_raises(self):
        with pytest.raises(ValueError, match="too short"):
            TimeSequenceFeatureTransformer(
                past_seq_len=40).fit_transform(make_df(20))


class TestModels:
    def _xy(self, n=64, L=6, F=3, horizon=1, seed=0):
        rng = np.random.RandomState(seed)
        x = rng.randn(n, L, F).astype(np.float32)
        y = x[:, -1, :1] * 0.5 + 0.1 * rng.randn(n, 1).astype(np.float32)
        return x, (y if horizon == 1 else np.repeat(y, horizon, 1))

    def test_vanilla_lstm_learns(self):
        from analytics_zoo_tpu.automl.models import build_vanilla_lstm
        x, y = self._xy()
        m = build_vanilla_lstm({"lstm_1_units": 8, "lstm_2_units": 8,
                                "dropout_1": 0.0, "dropout_2": 0.0,
                                "lr": 3e-3},
                               (6, 3))
        h = m.fit(x, y, batch_size=32, nb_epoch=25)
        assert h["loss"][-1] < h["loss"][0]
        assert np.asarray(m.predict(x, batch_per_thread=64)).shape == (64, 1)

    def test_seq2seq_shapes(self):
        from analytics_zoo_tpu.automl.models import build_seq2seq
        x, y = self._xy(horizon=3)
        m = build_seq2seq({"latent_dim": 8}, (6, 3), output_dim=1, horizon=3)
        m.fit(x, y, batch_size=32, nb_epoch=1)
        assert np.asarray(m.predict(x, batch_per_thread=64)).shape == (64, 3)

    def test_build_model_seq2seq_horizon(self):
        from analytics_zoo_tpu.automl.models import build_model
        x, y = self._xy(horizon=3)
        m = build_model({"model": "Seq2Seq", "latent_dim": 8}, (6, 3),
                        output_dim=3)
        m.fit(x, y, batch_size=32, nb_epoch=1)
        assert np.asarray(m.predict(x, batch_per_thread=64)).shape == (64, 3)

    def test_tcn_learns(self):
        from analytics_zoo_tpu.automl.models import build_tcn
        x, y = self._xy(L=8)
        m = build_tcn({"hidden_units": 8, "levels": 2, "kernel_size": 2},
                      (8, 3))
        h = m.fit(x, y, batch_size=32, nb_epoch=8)
        assert h["loss"][-1] < h["loss"][0]

    def test_causal_conv_is_causal(self):
        import jax
        from analytics_zoo_tpu.automl.models import CausalConv1D
        layer = CausalConv1D(4, kernel_size=3, dilation=2)
        params = layer.build(jax.random.PRNGKey(0), (None, 10, 2))
        x = np.random.RandomState(0).randn(1, 10, 2).astype(np.float32)
        y0 = np.asarray(layer.call(params, x))
        x2 = x.copy()
        x2[:, 7:] += 10.0   # future change
        y1 = np.asarray(layer.call(params, x2))
        np.testing.assert_allclose(y0[:, :7], y1[:, :7], atol=1e-6)
        assert not np.allclose(y0[:, 7:], y1[:, 7:])

    def test_mtnet_shapes(self):
        from analytics_zoo_tpu.automl.models import (build_mtnet,
                                                     mtnet_past_seq_len)
        cfg = {"time_step": 3, "long_num": 2, "cnn_hid_size": 8}
        L = mtnet_past_seq_len(cfg)
        assert L == 9
        rng = np.random.RandomState(0)
        x = rng.randn(32, L, 2).astype(np.float32)
        y = rng.randn(32, 1).astype(np.float32)
        m = build_mtnet(cfg, feature_dim=2)
        m.fit(x, y, batch_size=16, nb_epoch=1)
        assert np.asarray(m.predict(x, batch_per_thread=64)).shape == (32, 1)

    def test_tcmf_recovers_low_rank_panel(self):
        from analytics_zoo_tpu.automl.models import TCMF
        rng = np.random.RandomState(0)
        F = rng.randn(12, 3)
        t = np.arange(60)
        X = np.stack([np.sin(0.2 * t), np.cos(0.2 * t), 0.01 * t])
        y = (F @ X).astype(np.float32)
        tcmf = TCMF(rank=6, ar_lags=6, steps=800, lr=0.1)
        tcmf.fit(y[:, :48])
        pred = tcmf.predict(12)
        assert pred.shape == (12, 12)
        denom = np.mean(np.abs(y[:, 48:])) + 1e-6
        rel = np.mean(np.abs(pred - y[:, 48:])) / denom
        assert rel < 0.5, f"relative error {rel}"


class TestForecasters:
    def test_lstm_forecaster(self):
        from analytics_zoo_tpu.zouwu import LSTMForecaster
        rng = np.random.RandomState(0)
        x = rng.randn(48, 4, 2).astype(np.float32)
        y = x[:, -1, :1]
        f = LSTMForecaster(feature_dim=2, past_seq_len=4)
        f.fit(x, y, epochs=3)
        assert f.predict(x).shape == (48, 1)
        assert "mse" in f.evaluate(x, y)

    def test_tcmf_forecaster(self):
        from analytics_zoo_tpu.zouwu import TCMFForecaster
        rng = np.random.RandomState(0)
        y = rng.randn(5, 40).astype(np.float32)
        f = TCMFForecaster(rank=3, steps=50)
        f.fit({"id": np.arange(5), "y": y})
        out = f.predict(horizon=7)
        assert out["prediction"].shape == (5, 7)


class TestAutoTS:
    def test_end_to_end_search_and_pipeline(self, tmp_path):
        from analytics_zoo_tpu.zouwu import AutoTSTrainer, TSPipeline

        class TinyRecipe(Recipe):
            num_samples = 1
            training_iteration = 2

            def search_space(self):
                return {"model": "VanillaLSTM",
                        "lstm_1_units": hp.grid_search([4, 8]),
                        "lstm_2_units": 4,
                        "lr": 3e-3, "batch_size": 32, "past_seq_len": 4,
                        "epochs": 2}

        df = make_df(140)
        trainer = AutoTSTrainer(horizon=1)
        ts = trainer.fit(df.iloc[:110], df.iloc[110:], recipe=TinyRecipe())
        pred = ts.predict(df.iloc[110:])
        assert pred.shape[0] == len(df.iloc[110:]) - 4 + 1
        ev = ts.evaluate(df.iloc[110:], metrics=["mse", "smape"])
        assert set(ev) == {"mse", "smape"}
        # save/load roundtrip predicts identically
        path = str(tmp_path / "tsp")
        ts.save(path)
        ts2 = TSPipeline.load(path)
        np.testing.assert_allclose(ts2.predict(df.iloc[110:]), pred,
                                   atol=1e-5)
        # incremental fit runs
        ts2.fit(df.iloc[100:], epoch_num=1)


class TestAnomaly:
    def test_ae_detector_flags_spikes(self):
        from analytics_zoo_tpu.zouwu import AEDetector
        rng = np.random.RandomState(0)
        y = np.sin(np.arange(400) * 0.2) + rng.randn(400) * 0.05
        y[150] += 8.0
        y[300] -= 8.0
        det = AEDetector(roll_len=16, ratio=0.05, epochs=10)
        det.fit(y)
        idx = det.anomaly_indexes(y)
        # windows covering the spikes get flagged
        assert any(135 <= i <= 150 for i in idx)
        assert any(285 <= i <= 300 for i in idx)

    def test_threshold_detector_reexport(self):
        from analytics_zoo_tpu.zouwu import ThresholdDetector
        det = ThresholdDetector(ratio=0.1)
        truth = np.zeros(100)
        pred = np.zeros(100)
        pred[10] = 5.0
        det.fit(truth, pred)
        assert det.score(truth, pred)[10] == 1
