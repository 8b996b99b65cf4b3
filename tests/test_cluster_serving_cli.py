"""Cluster bootstrap launcher, serving config/CLI, profiling utils."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from analytics_zoo_tpu.serving.config import ServingConfig
from analytics_zoo_tpu.utils.profiling import (StepTimer, timing,
                                               transformer_train_flops)


class TestClusterLauncher:
    def test_two_process_rendezvous_and_collective(self, tmp_path):
        from analytics_zoo_tpu.common.cluster import launch_local_cluster
        env = {"PYTHONPATH": os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + ":" + os.path.dirname(
            os.path.abspath(__file__))}
        mon = launch_local_cluster(
            "cluster_worker_entry:main", num_processes=2,
            devices_per_process=2, worker_args=[str(tmp_path)], env=env)
        codes = mon.wait(timeout=180)
        assert codes == [0, 0]
        # 2 devices x rank1 + 2 devices x rank2 = 6; all ranks agree
        vals = []
        for r in range(2):
            with open(tmp_path / f"rank{r}.txt") as fh:
                vals.append(float(fh.read()))
        assert vals == [6.0, 6.0]

    def test_two_process_estimator_fit(self, tmp_path):
        """Full distributed training through Estimator.fit across 2
        processes × 2 CPU devices: each process feeds its local data
        shard, the global batch assembles across hosts, and the loss
        history is identical on every rank AND matches a single-process
        run over the equivalently-ordered global data."""
        import json

        from analytics_zoo_tpu.common.cluster import launch_local_cluster
        env = {"PYTHONPATH": os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + ":" + os.path.dirname(
            os.path.abspath(__file__))}
        mon = launch_local_cluster(
            "cluster_fit_entry:main", num_processes=2,
            devices_per_process=2, worker_args=[str(tmp_path)], env=env)
        codes = mon.wait(timeout=300)
        assert codes == [0, 0]
        hists = []
        for r in range(2):
            with open(tmp_path / f"fit_rank{r}.json") as fh:
                hists.append(json.load(fh)["loss"])
        assert hists[0] == hists[1], "ranks diverged"
        assert hists[0][-1] < hists[0][0], "loss did not decrease"

        # single-process equivalence: global batch i = rank0's local
        # batch i rows followed by rank1's (shuffle=False order)
        from cluster_fit_entry import make_shard
        import jax
        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        from analytics_zoo_tpu.learn.estimator import Estimator
        (x0, y0), (x1, y1) = make_shard(0), make_shard(1)
        lb = 16  # 32 global / 2 processes
        xg = np.concatenate([np.concatenate([x0[i:i + lb], x1[i:i + lb]])
                             for i in range(0, len(x0), lb)])
        yg = np.concatenate([np.concatenate([y0[i:i + lb], y1[i:i + lb]])
                             for i in range(0, len(y0), lb)])
        model = Sequential([L.Dense(8, input_shape=(4,),
                                    activation="relu"), L.Dense(1)])
        model.ensure_built(np.zeros((1, 4), np.float32),
                           jax.random.PRNGKey(7))
        from analytics_zoo_tpu.data.dataset import TPUDataset
        est = Estimator.from_keras(model, optimizer="sgd", loss="mse")
        ds = TPUDataset.from_ndarrays((xg, yg), batch_size=32,
                                      shuffle=False)
        hist = est.fit(ds, epochs=3, seed=0, prefetch=False)
        np.testing.assert_allclose(hist["loss"], hists[0], rtol=1e-4)

    def test_failing_worker_terminates_cluster(self, tmp_path):
        from analytics_zoo_tpu.common.cluster import launch_local_cluster
        env = {"PYTHONPATH": os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + ":" + os.path.dirname(
            os.path.abspath(__file__))}
        # nonexistent entry fn -> workers exit nonzero -> RuntimeError
        mon = launch_local_cluster("cluster_worker_entry:nope",
                                   num_processes=2, worker_args=[],
                                   env=env)
        with pytest.raises(RuntimeError, match="exited with"):
            mon.wait(timeout=180)


class TestServingConfig:
    def test_yaml_parse(self, tmp_path):
        cfg_file = tmp_path / "config.yaml"
        cfg_file.write_text(
            "model:\n"
            "  path: /models/ncf\n"
            "params:\n"
            "  core_number: 16\n"
            "  concurrent_num: 2\n"
            "redis:\n"
            "  host: cacher\n"
            "  port: 6380\n")
        cfg = ServingConfig.load(str(cfg_file))
        assert cfg.model_path == "/models/ncf"
        assert cfg.batch_size == 16
        assert cfg.concurrent_num == 2
        assert cfg.broker_url == "redis://cacher:6380"

    def test_broker_override_and_defaults(self, tmp_path):
        cfg_file = tmp_path / "c.yaml"
        cfg_file.write_text("model:\n  path: /m\nbroker: tcp://h:7000\n")
        cfg = ServingConfig.load(str(cfg_file))
        assert cfg.broker_url == "tcp://h:7000"
        assert cfg.batch_size == 32

    def test_fallback_parser_three_level_nesting(self):
        from analytics_zoo_tpu.serving.config import _parse_simple_yaml
        parsed = _parse_simple_yaml(
            "model:\n"
            "  class: NeuralCF\n"
            "  config:\n"
            "    user_count: 200\n"
            "    item_count: 100\n"
            "  path: /m\n"
            "params:\n"
            "  core_number: 4\n"
            "top: 1\n")
        assert parsed == {
            "model": {"class": "NeuralCF",
                      "config": {"user_count": 200, "item_count": 100},
                      "path": "/m"},
            "params": {"core_number": 4},
            "top": 1}

    def test_build_model_from_zoo_dir(self, tmp_path):
        from analytics_zoo_tpu.models.textclassification import TextClassifier
        m = TextClassifier(class_num=2, vocab_size=30, embedding_dim=8,
                           sequence_length=6)
        m.model.ensure_built(np.zeros((1, 6), np.int32))
        m.save_model(str(tmp_path / "tc"))
        cfg_file = tmp_path / "c.yaml"
        cfg_file.write_text(f"model:\n  path: {tmp_path / 'tc'}\n")
        im = ServingConfig.load(str(cfg_file)).build_model()
        out = im.predict(np.zeros((3, 6), np.int32))
        assert np.asarray(out).shape == (3, 2)

    def test_mesh_block_parses_and_validates(self, tmp_path):
        """params.mesh (ISSUE 12): map and string spellings parse,
        replicated placement + mesh is a load-time error, and a typo'd
        axis name fails with the axis vocabulary."""
        import pytest as _pytest

        def load(body):
            f = tmp_path / "m.yaml"
            f.write_text("model:\n  path: /m\n" + body)
            return ServingConfig.load(str(f))

        cfg = load("params:\n  placement: sharded\n  mesh:\n"
                   "    data: 1\n    fsdp: 2\n    tensor: 4\n")
        assert cfg.mesh_axes == {"data": 1, "fsdp": 2, "tensor": 4}
        cfg = load("params:\n  placement: sharded\n"
                   "  mesh: data=1,fsdp=2,tensor=-1\n")
        assert cfg.mesh_axes == {"data": 1, "fsdp": 2, "tensor": -1}
        with _pytest.raises(ValueError, match="placement"):
            load("params:\n  mesh: tensor=2\n")
        with _pytest.raises(ValueError, match="axis"):
            load("params:\n  placement: sharded\n  mesh: tenzor=2\n")
        with _pytest.raises(ValueError, match="integer"):
            load("params:\n  placement: sharded\n  mesh: tensor=lots\n")

    def test_build_model_sharded_on_configured_mesh(self, tmp_path):
        """A sharded config with a params.mesh block serves on exactly
        that factorization (tensor axis included)."""
        from analytics_zoo_tpu.models.textclassification import \
            TextClassifier
        m = TextClassifier(class_num=2, vocab_size=32, embedding_dim=8,
                           sequence_length=6)
        m.model.ensure_built(np.zeros((1, 6), np.int32))
        m.save_model(str(tmp_path / "tc"))
        cfg_file = tmp_path / "c.yaml"
        cfg_file.write_text(
            f"model:\n  path: {tmp_path / 'tc'}\n"
            "params:\n  placement: sharded\n"
            "  mesh: data=1,fsdp=2,tensor=4\n")
        im = ServingConfig.load(str(cfg_file)).build_model()
        assert im.mesh.axis_sizes["tensor"] == 4
        assert im.mesh.axis_sizes["fsdp"] == 2
        out = im.predict(np.zeros((4, 6), np.int32))
        assert np.asarray(out).shape == (4, 2)
        im.close()

    def test_build_model_quantized_from_config(self, tmp_path):
        # config.yaml `model.quantize: int8` serves the int8 path
        import jax

        from analytics_zoo_tpu.models.textclassification import TextClassifier
        m = TextClassifier(class_num=2, vocab_size=30, embedding_dim=8,
                           sequence_length=6)
        m.model.ensure_built(np.zeros((1, 6), np.int32))
        m.save_model(str(tmp_path / "tc"))
        cfg_file = tmp_path / "c.yaml"
        cfg_file.write_text(
            f"model:\n  path: {tmp_path / 'tc'}\n  quantize: int8\n")
        im = ServingConfig.load(str(cfg_file)).build_model()
        out = im.predict(np.zeros((3, 6), np.int32))
        assert np.asarray(out).shape == (3, 2)
        dtypes = {np.asarray(leaf).dtype
                  for leaf in jax.tree_util.tree_leaves(im._params)}
        assert np.dtype(np.int8) in dtypes      # actually quantized


class TestServingCLIEndToEnd:
    def test_broker_and_start_roundtrip(self, tmp_path):
        """Full deployment shape: broker proc + serving proc + client."""
        from analytics_zoo_tpu.models.textclassification import TextClassifier
        from analytics_zoo_tpu.serving.client import InputQueue
        m = TextClassifier(class_num=2, vocab_size=30, embedding_dim=8,
                           sequence_length=6)
        m.model.ensure_built(np.zeros((1, 6), np.int32))
        m.save_model(str(tmp_path / "tc"))

        import socket
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        broker = subprocess.Popen(
            [sys.executable, "-m", "analytics_zoo_tpu.serving.cli",
             "broker", "--host", "127.0.0.1", "--port", str(port)], env=env)
        cfg_file = tmp_path / "c.yaml"
        cfg_file.write_text(
            f"model:\n  path: {tmp_path / 'tc'}\n"
            f"broker: tcp://127.0.0.1:{port}\n")
        serving = subprocess.Popen(
            [sys.executable, "-m", "analytics_zoo_tpu.serving.cli",
             "start", "--config", str(cfg_file)], env=env)
        try:
            q = InputQueue(f"tcp://127.0.0.1:{port}")
            deadline = time.time() + 120
            out = None
            while time.time() < deadline:
                try:
                    out = q.predict(np.zeros((6,), np.float32),
                                    timeout_s=10)
                    break
                except (ConnectionRefusedError, TimeoutError, OSError):
                    time.sleep(0.5)
            assert out is not None and np.asarray(out).shape == (2,)
        finally:
            serving.terminate()
            broker.terminate()
            serving.wait(timeout=10)
            broker.wait(timeout=10)


class TestProfiling:
    def test_timing_logs(self, caplog):
        import logging
        with caplog.at_level(logging.INFO,
                             logger="analytics_zoo_tpu.profiling"):
            with timing("stage"):
                pass
        assert any("stage time" in r.message for r in caplog.records)

    def test_step_timer_mfu(self):
        st = StepTimer(flops_per_step=1e9, peak_flops=1e12)
        for _ in range(3):
            with st:
                time.sleep(0.001)
        s = st.summary(batch_size=8)
        assert s["steps"] == 3 and s["samples_per_sec"] > 0
        assert 0 < s["mfu"] < 1

    def test_flops_accounting_matches_bench(self):
        f = transformer_train_flops(n_params_matmul=86e6, tokens=4096,
                                    n_layers=12, seq_len=128, hidden=768,
                                    batch=32)
        assert f > 2e12
