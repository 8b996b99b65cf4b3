"""Ops packaging: offline serving benchmark + dataset fetchers
(reference roles: `docker/cluster-serving/perf/offline-benchmark`,
`scripts/data/*/get_*.sh`). Docker builds can't run in CI here; the
entrypoint pieces the image runs are exercised directly."""

import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


class TestOfflineBenchmark:
    def test_small_run_reports_throughput(self):
        proc = _run(["scripts/perf/offline_benchmark.py", "--n", "300",
                     "--broker", "redis", "--image-size", "16"])
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["metric"] == "serving_offline_throughput"
        assert out["n_served"] == 300
        assert out["value"] > 0
        assert out["serving_metrics"]["records_served"] >= 300

    def test_memory_broker_path(self):
        proc = _run(["scripts/perf/offline_benchmark.py", "--n", "64",
                     "--broker", "memory", "--image-size", "16"])
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["broker"] == "memory" and out["n_served"] == 64


class TestDataFetchers:
    def test_synthetic_movielens_feeds_reader(self, tmp_path):
        proc = _run(["scripts/data/fetch.py", "movielens-1m",
                     str(tmp_path), "--synthetic"])
        assert proc.returncode == 0, proc.stderr[-2000:]
        path = tmp_path / "movielens-1m" / "ratings.dat"
        rows = [l.split("::") for l in path.read_text().splitlines()]
        assert len(rows) == 5000 and len(rows[0]) == 4
        ratings = np.array([int(r[2]) for r in rows])
        assert ratings.min() >= 1 and ratings.max() <= 5

    def test_synthetic_news20_layout(self, tmp_path):
        proc = _run(["scripts/data/fetch.py", "news20", str(tmp_path),
                     "--synthetic"])
        assert proc.returncode == 0, proc.stderr[-2000:]
        groups = sorted(os.listdir(tmp_path / "news20"))
        assert "comp.graphics" in groups and len(groups) == 3
        docs = os.listdir(tmp_path / "news20" / "comp.graphics")
        assert len(docs) == 20

    def test_synthetic_glove_parses(self, tmp_path):
        proc = _run(["scripts/data/fetch.py", "glove", str(tmp_path),
                     "--synthetic"])
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = (tmp_path / "glove" / "glove.6B.50d.txt").read_text() \
            .splitlines()
        parts = lines[0].split()
        assert len(parts) == 51
        float(parts[1])

    def test_synthetic_nyc_taxi_csv(self, tmp_path):
        proc = _run(["scripts/data/fetch.py", "nyc-taxi", str(tmp_path),
                     "--synthetic"])
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = (tmp_path / "nyc-taxi" / "nyc_taxi.csv").read_text() \
            .splitlines()
        assert lines[0] == "timestamp,value"
        assert len(lines) == 2001

    def test_all_synthetic(self, tmp_path):
        proc = _run(["scripts/data/fetch.py", "all", str(tmp_path),
                     "--synthetic"])
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert sorted(os.listdir(tmp_path)) == [
            "glove", "movielens-1m", "news20", "nyc-taxi"]


class TestDockerEntrypointPieces:
    def test_config_yaml_parses(self):
        from analytics_zoo_tpu.serving.config import ServingConfig
        cfg = ServingConfig.load(
            os.path.join(REPO, "docker", "serving-config.yaml"))
        assert cfg.model_path == "/opt/model"
        assert cfg.broker_url == "redis://127.0.0.1:6379"
        assert cfg.http_port == 8080
        assert cfg.batch_size == 32


class TestChipEntryPointsRefuseTheCpu:
    """The scripts that speak for the chip must not speak for the CPU:
    non-zero exit, no result, and no model built first."""

    def test_chip_smoke_exits_before_building_a_model(self):
        proc = _run(["chip_smoke.py"], timeout=120)
        assert proc.returncode != 0
        assert "no TPU backend" in proc.stderr
        assert proc.stdout.strip() == ""        # no PASS line, no JSON
        # it never reached init_orca_context, let alone a BERT build
        assert "Initialized" not in proc.stderr

