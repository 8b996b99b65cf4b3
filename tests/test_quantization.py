"""Int8 post-training quantization (serving/quantization.py).

Parity: the reference's int8 inference engine
(`OpenVinoInferenceSupportive.scala:34-57`, `OpenVINOInt8Suite.scala:301`
— load-int8-model + predict equivalence). Here: quantize → serve through
InferenceModel, bounded accuracy drift vs f32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras import Sequential
from analytics_zoo_tpu.keras import layers as L
from analytics_zoo_tpu.serving.inference_model import InferenceModel
from analytics_zoo_tpu.serving.quantization import (
    int8_matmul, quantize_model_params)


class TestKernels:
    def test_int8_matmul_close_to_f32(self):
        rs = np.random.RandomState(0)
        x = rs.randn(16, 64).astype(np.float32)
        w = (rs.randn(64, 32) * 0.1).astype(np.float32)
        amax = np.abs(w).max(axis=0, keepdims=True)
        scale = (amax / 127.0).astype(np.float32)
        w_q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
        y = np.asarray(int8_matmul(jnp.asarray(x), jnp.asarray(w_q),
                                   jnp.asarray(scale[0])))
        ref = x @ w
        # per-tensor act + per-channel weight int8: ~1% relative error
        err = np.abs(y - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err < 0.02, f"int8 matmul error {err}"


def _trained_classifier():
    rs = np.random.RandomState(1)
    # separable 4-class problem so top-1 is meaningful
    centers = rs.randn(4, 16).astype(np.float32) * 3
    yc = rs.randint(0, 4, 512)
    x = centers[yc] + rs.randn(512, 16).astype(np.float32)
    m = Sequential([L.Dense(32, activation="relu", input_shape=(16,)),
                    L.Dense(4, activation="softmax")])
    m.compile("adam", "sparse_categorical_crossentropy")
    m.fit(x, yc.astype(np.int32), batch_size=64, nb_epoch=15)
    return m, x, yc


class TestModelQuantization:
    def test_param_tree_rewrite(self):
        m, _, _ = _trained_classifier()
        q = quantize_model_params(m, jax.device_get(m.params))
        for layer in m.layers:
            sub = q[layer.name]
            assert "kernel" not in sub
            assert sub["kernel_q"].dtype == np.int8
            assert sub["kernel_scale"].dtype == np.float32
            assert sub["kernel_q"].nbytes * 4 == \
                np.prod(sub["kernel_q"].shape) * 4  # int8 = 1 byte/elem
            assert "bias" in sub                    # bias stays f32

    def test_top1_drift_bounded_via_inference_model(self):
        m, x, yc = _trained_classifier()
        im_f32 = InferenceModel().load_keras(m)
        im_int8 = InferenceModel().load_keras(m, quantize="int8")
        p32 = np.asarray(im_f32.predict(x[:256]))
        p8 = np.asarray(im_int8.predict(x[:256]))
        agree = float((p32.argmax(-1) == p8.argmax(-1)).mean())
        assert agree >= 0.98, f"top-1 agreement {agree}"
        # the f32 master params on the model are untouched
        for leaf in jax.tree_util.tree_leaves(m.params):
            assert np.asarray(leaf).dtype == np.float32

    def test_conv_and_embedding_paths(self):
        rs = np.random.RandomState(2)
        m = Sequential([
            L.Embedding(500, 8, input_shape=(12,)),
            L.Convolution1D(16, 3, activation="relu"),
            L.GlobalMaxPooling1D(),
            L.Dense(3, activation="softmax"),
        ])
        ids = rs.randint(0, 500, (64, 12)).astype(np.int32)
        y = rs.randint(0, 3, 64).astype(np.int32)
        m.compile("adam", "sparse_categorical_crossentropy")
        m.fit(ids, y, batch_size=32, nb_epoch=2)
        im8 = InferenceModel().load_keras(m, quantize="int8")
        imf = InferenceModel().load_keras(m)
        p8 = np.asarray(im8.predict(ids))
        pf = np.asarray(imf.predict(ids))
        assert p8.shape == pf.shape
        assert np.isfinite(p8).all()
        # probabilities stay close in L1
        assert np.abs(p8 - pf).mean() < 0.05

    def test_int8_artifact_roundtrip(self, tmp_path):
        # save_quantized → load onto a FRESH architecture instance →
        # identical predictions to the in-memory quantized model, and
        # the artifact is ~4x smaller than an f32 checkpoint
        import os

        from analytics_zoo_tpu.serving.quantization import save_quantized

        m, x, _ = _trained_classifier()
        p_mem = np.asarray(
            InferenceModel().load_keras(m, quantize="int8").predict(x[:64]))
        qpath = str(tmp_path / "clf_int8.npz")
        save_quantized(m, qpath)

        fresh = Sequential([L.Dense(32, activation="relu",
                                    input_shape=(16,)),
                            L.Dense(4, activation="softmax")])
        fresh.ensure_built(np.zeros((1, 16), np.float32))
        im = InferenceModel().load_quantized(fresh, qpath)
        p_art = np.asarray(im.predict(x[:64]))
        np.testing.assert_allclose(p_art, p_mem, rtol=1e-5, atol=1e-6)
        # int8 leaves persisted as int8 (not upcast by the codec) — the
        # artifact's weight payload is ~4x smaller than f32
        assert os.path.exists(qpath)
        for leaf in jax.tree_util.tree_leaves(im._params):
            assert np.asarray(leaf).dtype in (np.int8, np.float32)
        q_bytes = sum(np.asarray(p).nbytes for p in
                      jax.tree_util.tree_leaves(im._params))
        f32_bytes = sum(np.asarray(p).nbytes for p in
                        jax.tree_util.tree_leaves(m.params))
        assert q_bytes < 0.5 * f32_bytes

    def test_bert_transformer_int8(self):
        # raw-kernel pass: transformer qkv/out/ffn + pooler + cls head
        # quantize and dispatch through maybe_int8_matmul
        from analytics_zoo_tpu.models.bert import BERTClassifier
        from analytics_zoo_tpu.serving.quantization import (
            quantize_model_params)
        rs = np.random.RandomState(0)
        m = BERTClassifier(num_classes=3, vocab=64, hidden_size=32,
                           n_block=2, n_head=2, seq_len=16,
                           intermediate_size=64)
        ids = rs.randint(0, 64, (8, 16)).astype(np.int32)
        mask = np.ones((8, 16), np.float32)
        m.ensure_built([ids, mask], jax.random.PRNGKey(0))

        q = quantize_model_params(m, jax.device_get(m.params))
        flat = jax.tree_util.tree_leaves_with_path(q)
        q_keys = {str(p) for p, _ in flat if "_q" in str(p)}
        assert any("qkv_kernel_q" in k for k in q_keys)
        assert any("ffn_in_kernel_q" in k for k in q_keys)
        assert any("cls_kernel_q" in k for k in q_keys)

        imf = InferenceModel().load_keras(m)
        im8 = InferenceModel().load_keras(m, quantize="int8")
        pf = np.asarray(imf.predict([ids, mask]))
        p8 = np.asarray(im8.predict([ids, mask]))
        assert p8.shape == pf.shape
        # logits stay close; argmax agreement on random-init logits is
        # noisy, so bound the relative error instead
        err = np.abs(p8 - pf).max() / (np.abs(pf).max() + 1e-9)
        assert err < 0.1, f"int8 BERT drifted {err}"

    def test_bad_mode_rejected(self):
        m, _, _ = _trained_classifier()
        with pytest.raises(ValueError, match="int8"):
            InferenceModel().load_keras(m, quantize="int4")


class TestCheckpointSidecar:
    """The productionized pass (ISSUE 12): per-output-channel scales
    calibrated once and persisted as a checkpoint sidecar, served
    without a quantize-at-load pass."""

    def _fit_with_sidecar(self, tmp_path):
        from analytics_zoo_tpu.learn.trainer import fit_keras
        m, x, yc = _trained_classifier()
        m.set_checkpoint(str(tmp_path))
        fit_keras(m, x, yc.astype(np.int32), batch_size=64, epochs=1,
                  int8_sidecar=True, prefetch=False, device_cache=False)
        return m, x

    def test_scale_roundtrip_bitwise_through_sidecar(self, tmp_path):
        """fit_keras(int8_sidecar=True) writes the sidecar at the
        checkpoint save, and every int8 weight and f32 per-channel
        scale survives the disk round trip bit for bit."""
        from analytics_zoo_tpu.learn.checkpoint import latest_checkpoint
        from analytics_zoo_tpu.observability.registry import get_registry
        from analytics_zoo_tpu.serving.quantization import \
            load_int8_sidecar
        before = get_registry().counter(
            "quantized_checkpoints_total", "").value()
        m, _ = self._fit_with_sidecar(tmp_path)
        run_dir, version = latest_checkpoint(str(tmp_path))
        q_disk = load_int8_sidecar(run_dir, version)
        assert q_disk is not None
        q_mem = quantize_model_params(m, jax.device_get(m.params))
        for a, b in zip(jax.tree_util.tree_leaves(q_disk),
                        jax.tree_util.tree_leaves(q_mem)):
            assert np.asarray(a).dtype == np.asarray(b).dtype
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert get_registry().counter(
            "quantized_checkpoints_total", "").value() > before

    def test_serving_prefers_sidecar_and_missing_falls_back(
            self, tmp_path, monkeypatch):
        """load_checkpoint(quantize="int8") serves the PRE-CALIBRATED
        artifact (no quantize_model_params call); with the sidecar
        deleted it falls back to quantize-at-load and still serves."""
        import os

        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.learn.checkpoint import latest_checkpoint
        from analytics_zoo_tpu.serving import quantization as qmod
        from analytics_zoo_tpu.serving.quantization import sidecar_path
        m, x = self._fit_with_sidecar(tmp_path)
        fresh = Sequential([L.Dense(32, activation="relu",
                                    input_shape=(16,)),
                            L.Dense(4, activation="softmax")])
        fresh.ensure_built(np.zeros((1, 16), np.float32))

        calls = []
        orig = qmod.quantize_model_params
        monkeypatch.setattr(qmod, "quantize_model_params",
                            lambda *a, **k: calls.append(1)
                            or orig(*a, **k))
        im = InferenceModel().load_checkpoint(fresh, str(tmp_path),
                                              quantize="int8")
        assert calls == [], "sidecar load re-ran the calibration pass"
        assert im.serving_dtype == "int8"
        p_side = np.asarray(im.predict(x[:32]))

        run_dir, version = latest_checkpoint(str(tmp_path))
        # root + EXPLICIT version resolves the timestamped run dir too
        # (a miss here would silently re-calibrate every restart)
        InferenceModel().load_checkpoint(fresh, str(tmp_path),
                                         version=version,
                                         quantize="int8")
        assert calls == [], "root+version call missed the sidecar"
        for suffix in (".npz", ".structure.json"):
            os.remove(sidecar_path(run_dir, version) + suffix)
        im2 = InferenceModel().load_checkpoint(fresh, str(tmp_path),
                                               quantize="int8")
        assert calls, "fallback did not quantize at load"
        assert im2.serving_dtype == "int8"
        np.testing.assert_allclose(np.asarray(im2.predict(x[:32])),
                                   p_side, rtol=1e-5, atol=1e-6)

    def test_sidecars_garbage_collect_with_their_checkpoints(
            self, tmp_path):
        """The keep=N retention contract covers the sidecar: a pruned
        checkpoint version takes its .int8 artifacts with it."""
        import os

        from analytics_zoo_tpu.learn.checkpoint import CheckpointManager
        from analytics_zoo_tpu.serving.quantization import \
            write_int8_sidecar
        m, _, _ = _trained_classifier()
        mgr = CheckpointManager(str(tmp_path), keep=2)
        host = jax.device_get(m.params)
        for it in (1, 2, 3, 4):
            mgr.save(it, host, extra={"epoch": it})
            write_int8_sidecar(mgr.run_dir, it, m, params=host)
        left = sorted(os.listdir(mgr.run_dir))
        assert not any(f.startswith(("model.1.", "model.2."))
                       for f in left), left
        assert "model.4.int8.npz" in left

    def test_offline_script_quantizes_and_reports_shrink(self, tmp_path):
        """scripts/quantize_checkpoint.py: a checkpoint + a saved
        ZooModel architecture dir → sidecar beside the newest version,
        ~4x smaller than the f32 artifact, and servable."""
        import json
        import os
        import subprocess
        import sys

        from analytics_zoo_tpu.learn.trainer import fit_keras
        from analytics_zoo_tpu.models.textclassification import \
            TextClassifier
        m = TextClassifier(class_num=2, vocab_size=30, embedding_dim=8,
                           sequence_length=6)
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 30, (64, 6)).astype(np.int32)
        y = rs.randint(0, 2, 64).astype(np.int32)
        m.model.compile("adam", "sparse_categorical_crossentropy")
        m.model.set_checkpoint(str(tmp_path / "ck"))
        fit_keras(m.model, ids, y, batch_size=32, epochs=1,
                  prefetch=False, device_cache=False)
        m.save_model(str(tmp_path / "arch"))
        root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable,
             os.path.join(root, "scripts", "quantize_checkpoint.py"),
             "--checkpoint", str(tmp_path / "ck"),
             "--model", str(tmp_path / "arch")],
            capture_output=True, text=True, env=env, cwd=root,
            timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.loads(r.stdout)
        assert out["shrink"] > 2.0, out
        fresh = TextClassifier(class_num=2, vocab_size=30,
                               embedding_dim=8, sequence_length=6)
        im = InferenceModel().load_checkpoint(
            fresh, str(tmp_path / "ck"), quantize="int8")
        assert im.serving_dtype == "int8"
        assert np.asarray(im.predict(ids[:4])).shape == (4, 2)


class TestQualityGate:
    def test_within_gate_passes_and_reports_baseline(self):
        from analytics_zoo_tpu.learn.estimator import Estimator
        m, x, yc = _trained_classifier()
        est = Estimator(m)
        res = est.evaluate((x, yc.astype(np.int32)),
                           metrics=["accuracy"], quantize="int8",
                           quality_tolerance=0.05)
        assert "accuracy" in res and "baseline_accuracy" in res
        assert abs(res["accuracy"] - res["baseline_accuracy"]) <= 0.05
        # f32 master params restored after the quantized eval
        for leaf in jax.tree_util.tree_leaves(m.params):
            assert np.asarray(leaf).dtype == np.float32

    def test_outside_gate_refuses(self):
        from analytics_zoo_tpu.learn.estimator import (
            Estimator, QuantizationQualityError)
        m, x, yc = _trained_classifier()
        est = Estimator(m)
        with pytest.raises(QuantizationQualityError,
                           match="quality gate"):
            est.evaluate((x, yc.astype(np.int32)),
                         metrics=["accuracy"], quantize="int8",
                         quality_tolerance=0.1,
                         baseline_metrics={"accuracy": 1.5})
        # a NaN metric must REFUSE, not slip through the comparison
        # (NaN > tol and NaN <= tol are both False — the gate uses the
        # negated form so unprovable means rejected)
        with pytest.raises(QuantizationQualityError,
                           match="quality gate"):
            est.evaluate((x, yc.astype(np.int32)),
                         metrics=["accuracy"], quantize="int8",
                         quality_tolerance=0.1,
                         baseline_metrics={"accuracy": float("nan")})

    def test_bad_mode_rejected(self):
        from analytics_zoo_tpu.learn.estimator import Estimator
        m, x, yc = _trained_classifier()
        with pytest.raises(ValueError, match="int8"):
            Estimator(m).evaluate((x, yc.astype(np.int32)),
                                  quantize="int4")


class TestDtypeKeyIsolation:
    def test_compile_cache_keys_and_entries_isolate_by_dtype(
            self, tmp_path, monkeypatch):
        """Toggling quantize="int8" can never load the f32 executable:
        the serving cache key carries the dtype explicitly, an int8
        warmup against a cache warmed by the f32 model COMPILES (no
        false hit), and each precision's warm restart hits only its own
        entry."""
        import analytics_zoo_tpu.compile_cache.serialization as ccser
        from analytics_zoo_tpu.compile_cache import CompileCache
        m, x, _ = _trained_classifier()
        # host params: a retarget-loaded cached executable expects its
        # stored single-device placement, not the fit's live mesh-
        # replicated NamedSharding (same convention as the PR 7
        # handoff tests)
        m.params = jax.device_get(m.params)

        calls = []
        orig = ccser.compile_lowered
        monkeypatch.setattr(ccser, "compile_lowered",
                            lambda low: calls.append(1) or orig(low))
        cache_dir = str(tmp_path / "cc")

        def make(quantize):
            return InferenceModel(
                compile_cache=CompileCache(cache_dir)).load_keras(
                    m, quantize=quantize)

        im_f = make(None)
        im_q = make("int8")
        sig = im_f._exec_sig(np.zeros((8, 16), np.float32))
        kf = im_f._cache_key(sig)
        kq = im_q._cache_key(sig)
        assert kf.digest != kq.digest
        assert kq.fields.get("dtype") == "int8"
        assert "dtype" not in kf.fields    # f32 keys stay pre-ISSUE-12

        make(None).warmup(x[0], buckets=[8])
        assert len(calls) == 1             # cold f32: one compile
        make("int8").warmup(x[0], buckets=[8])
        assert len(calls) == 2, \
            "int8 warmup reused the f32 executable (dtype key leak)"
        make(None).warmup(x[0], buckets=[8])
        make("int8").warmup(x[0], buckets=[8])
        assert len(calls) == 2             # warm: both hit their own

    def test_engine_labels_and_weight_bytes_gauge(self):
        """A non-default serving dtype labels the engine's serving_*
        series (f32 schema stays label-free), and serving_weight_bytes
        prices int8 weights ~4x under the f32 tree."""
        from analytics_zoo_tpu.observability.registry import get_registry
        from analytics_zoo_tpu.serving.server import ClusterServing
        m, _, _ = _trained_classifier()
        im_q = InferenceModel().load_keras(m, quantize="int8")
        srv_q = ClusterServing(im_q, "memory", supervise=False)
        assert srv_q._labels.get("serving_dtype") == "int8"
        reg = get_registry()
        q_bytes = reg.get("serving_weight_bytes").value(
            serving_dtype="int8")
        assert q_bytes > 0
        im_f = InferenceModel().load_keras(m)
        srv_f = ClusterServing(im_f, "memory", supervise=False)
        assert "serving_dtype" not in srv_f._labels
        f_bytes = reg.get("serving_weight_bytes").value(
            serving_dtype="float32")
        assert q_bytes < 0.5 * f_bytes
        assert srv_q.metrics()["serving_dtype"] == "int8"
        assert srv_f.metrics()["serving_dtype"] == "float32"
