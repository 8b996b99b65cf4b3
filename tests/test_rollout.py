"""Zero-downtime model lifecycle (ISSUE 14): versioned canary rollout.

- Publish markers: the trainer's `_ckpt_save` funnel commits a marker
  only after every artifact of a version is durable; the rollout
  watcher (`latest_published_checkpoint`) only ever sees marked,
  CRC-intact versions — a mid-write kill can never publish a torn one.
- `resolve_checkpoint` under a concurrent writer (the trainer writing
  N+1 while the watcher polls): always N or N+1, never a partial dir.
- `InferenceModel.swap_params`: a same-structure swap costs ZERO XLA
  compiles (the AOT/jit caches key on params structure, not values);
  a restructured swap honestly re-warms through the bucket path.
- Heartbeat hardening: a raising `payload_fn` degrades to ready=False
  WITHOUT dropping last-known-good fields (model_version, slo_burn).
- `EngineRolloutAgent`: directive → drain → swap → canary → heartbeat
  report; a failed canary (non-finite output / golden delta) restores
  the old params and vetoes the version.
- `RolloutController`: engine-by-engine convergence driven through
  tick(), veto → fleet-wide quarantine persisted in the broker (a
  restarted controller honors it), dead-engine skip, mixed-fleet
  resume after a controller restart.
- End-to-end on an in-process fleet: trainer publishes N+1, the fleet
  converges with records answering throughout (zero loss, no NaNs),
  0 compiles for the same-structure swap; a poisoned N+2 quarantines
  fleet-wide with the old version still serving.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.learn import checkpoint as ckpt
from analytics_zoo_tpu.observability.registry import MetricsRegistry
from analytics_zoo_tpu.serving.broker import MemoryBroker, decode_ndarray
from analytics_zoo_tpu.serving.client import InputQueue
from analytics_zoo_tpu.serving.fleet import (FleetTracker,
                                             HeartbeatPublisher,
                                             engines_key)
from analytics_zoo_tpu.serving.http_frontend import FrontEnd
from analytics_zoo_tpu.serving.inference_model import InferenceModel
from analytics_zoo_tpu.serving.rollout import (EngineRolloutAgent,
                                               RolloutController,
                                               rollout_key)
from analytics_zoo_tpu.serving.server import ClusterServing

STREAM = "serving_stream"
RESULT_KEY = f"result:{STREAM}"


def _scale_params(scale):
    return {"w": np.asarray(scale, np.float32)}


def _scale_fn(p, x):
    return x * p["w"]


def _publish(mgr, version, scale):
    mgr.save(version, _scale_params(scale))
    ckpt.write_publish_marker(mgr.run_dir, version)
    return mgr.run_dir


def _scale_engine(broker, engine_id, scale=2.0, version=1, registry=None,
                  warm=True, **kw):
    im = InferenceModel().load_fn(_scale_fn, _scale_params(scale))
    if warm:
        # non-zero sample: it doubles as the agent's golden-input
        # fallback, and x=0 would make the delta gate vacuous
        im.warmup(np.full(3, 1.0, np.float32), buckets=[1, 2, 4, 8])
    kw.setdefault("batch_size", 8)
    kw.setdefault("batch_timeout_ms", 2)
    kw.setdefault("heartbeat_interval_s", 0.05)
    return ClusterServing(im, broker=broker, engine_id=engine_id,
                          registry=registry or MetricsRegistry(),
                          model_version=version, **kw)


def _wait(pred, timeout_s=20.0, interval=0.02, msg="condition"):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def _wait_results(broker, n, timeout_s=30.0):
    _wait(lambda: broker.hlen(RESULT_KEY) >= n, timeout_s,
          msg=f"{n} results")
    return broker.hgetall(RESULT_KEY)


def _beat(broker, eid, version, ready=True):
    broker.hset(engines_key(STREAM), eid, json.dumps(
        {"engine_id": eid, "ts": time.time(), "ready": ready,
         "model_version": version}))


def _tracker(broker):
    return FleetTracker(broker, STREAM, ttl_s=30.0, registry=MetricsRegistry(),
                        poll_min_interval_s=0.0)


# ---------------------------------------------------------------------------
# Publish markers
# ---------------------------------------------------------------------------
class TestPublishMarker:
    def test_unmarked_version_is_invisible_to_the_watcher(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        mgr.save(2, _scale_params(3.0))        # durable but unpublished
        assert ckpt.latest_checkpoint(str(tmp_path))[1] == 2
        assert ckpt.latest_published_checkpoint(str(tmp_path)) \
            == (mgr.run_dir, 1)
        ckpt.write_publish_marker(mgr.run_dir, 2)
        assert ckpt.latest_published_checkpoint(str(tmp_path))[1] == 2

    def test_quarantine_skip_falls_back(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _publish(mgr, 2, 3.0)
        assert ckpt.latest_published_checkpoint(
            str(tmp_path), skip_versions={"2"})[1] == 1

    def test_mid_write_kill_never_publishes(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        # the trainer funnel dies BEFORE the marker: version invisible
        with faults.injected("checkpoint.write", mode="raise"):
            with pytest.raises(Exception):
                mgr.save(2, _scale_params(3.0))
        assert ckpt.latest_published_checkpoint(str(tmp_path))[1] == 1
        # torn bytes cannot even be marked: publishing verifies the set
        with faults.injected("checkpoint.write", mode="truncate",
                             keep_fraction=0.3):
            mgr.save(3, _scale_params(4.0))
        with pytest.raises(ckpt.CorruptCheckpointError):
            ckpt.write_publish_marker(mgr.run_dir, 3)
        assert ckpt.latest_published_checkpoint(str(tmp_path))[1] == 1

    def test_marker_detects_post_publication_tearing(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _publish(mgr, 2, 3.0)
        npz = os.path.join(mgr.run_dir, "model.2.npz")
        with open(npz, "r+b") as fh:
            fh.truncate(os.path.getsize(npz) // 2)
        assert not ckpt.verify_publish_marker(mgr.run_dir, 2)
        assert ckpt.latest_published_checkpoint(str(tmp_path))[1] == 1

    def test_verify_cache_memoizes_and_invalidates(self, tmp_path):
        """The watcher's verify cache: a second poll answers from the
        memo (no re-CRC of multi-GB artifacts per tick), and a version
        whose bytes change re-verifies fresh."""
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        cache = {}
        assert ckpt.latest_published_checkpoint(
            str(tmp_path), verify_cache=cache)[1] == 1
        assert list(cache.values()) == [True]
        # memo hit: even with CRC verification broken, the cached
        # verdict answers — proof the artifact was not re-read
        real = ckpt.verify_publish_marker
        try:
            ckpt.verify_publish_marker = lambda *a: (_ for _ in ()) \
                .throw(AssertionError("re-verified a cached version"))
            assert ckpt.latest_published_checkpoint(
                str(tmp_path), verify_cache=cache)[1] == 1
        finally:
            ckpt.verify_publish_marker = real
        # bytes change (stat changes) → fresh verdict, torn → invisible
        npz = os.path.join(mgr.run_dir, "model.1.npz")
        with open(npz, "r+b") as fh:
            fh.truncate(os.path.getsize(npz) // 2)
        assert ckpt.latest_published_checkpoint(
            str(tmp_path), verify_cache=cache) is None

    def test_gc_retires_markers_with_their_version(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=1)
        for v, s in ((1, 2.0), (2, 3.0)):
            _publish(mgr, v, s)
        assert not os.path.exists(
            os.path.join(mgr.run_dir, "model.1.published.json"))
        assert os.path.exists(
            os.path.join(mgr.run_dir, "model.2.published.json"))

    def test_fit_funnel_publishes_marked_versions(self, tmp_path):
        """`fit_keras` → `_ckpt_save` commits the marker LAST: every
        epoch-boundary checkpoint a fit leaves behind is published."""
        import optax

        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        from analytics_zoo_tpu.learn.trainer import fit_keras
        m = Sequential()
        m.add(L.Dense(4, input_shape=(6,)))
        m.compile(optimizer=optax.sgd(1e-2), loss="mse")
        m.set_checkpoint(str(tmp_path))
        rs = np.random.RandomState(0)
        x = rs.randn(64, 6).astype(np.float32)
        y = rs.randn(64, 1).astype(np.float32)
        fit_keras(m, x, y, epochs=1, batch_size=32, seed=7,
                  distributed=False, prefetch=False, device_cache=False)
        found = ckpt.latest_published_checkpoint(str(tmp_path))
        assert found is not None
        run_dir, v = found
        assert ckpt.verify_publish_marker(run_dir, v)
        assert ckpt.read_publish_marker(run_dir, v)["version"] == v


class TestResolveUnderConcurrentWriter:
    def test_poller_sees_n_or_n_plus_one_never_partial(self, tmp_path):
        """The rollout watcher polling while the trainer writes N+1
        must resolve N or N+1 — and whatever it resolves must LOAD."""
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        stop = threading.Event()
        failures = []
        seen = set()

        def poller():
            while not stop.is_set():
                try:
                    run_dir, v = ckpt.resolve_checkpoint(str(tmp_path))
                    if v not in (1, 2):
                        failures.append(f"resolved version {v}")
                    params, _, _ = ckpt.load_checkpoint(run_dir, v)
                    np.testing.assert_allclose(
                        np.asarray(params["w"]), 2.0 if v == 1 else 3.0)
                    seen.add(v)
                except Exception as e:  # noqa: BLE001 — the assertion
                    failures.append(f"{type(e).__name__}: {e}")

        t = threading.Thread(target=poller, daemon=True)
        t.start()
        try:
            # the writer stalls mid-commit (between npz bytes landing
            # in the temp file and the rename), widening the window
            # the poller races against
            with faults.injected("checkpoint.write", mode="stall",
                                 delay_s=0.15):
                mgr.save(2, _scale_params(3.0))
        finally:
            time.sleep(0.1)
            stop.set()
            t.join(timeout=10)
        assert not failures, failures[:5]
        assert 1 in seen          # the poller really raced the write

    def test_truncated_writer_never_surfaces(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        with faults.injected("checkpoint.write", mode="truncate",
                             keep_fraction=0.4):
            mgr.save(2, _scale_params(3.0))
        assert ckpt.resolve_checkpoint(str(tmp_path))[1] == 1
        assert ckpt.latest_checkpoint(str(tmp_path))[1] == 1


# ---------------------------------------------------------------------------
# swap_params
# ---------------------------------------------------------------------------
class TestSwapParams:
    def test_same_structure_swap_zero_compiles_jit_path(self):
        im = InferenceModel().load_fn(_scale_fn, _scale_params(2.0))
        im.warmup(np.zeros(3, np.float32), buckets=[1, 2, 4])
        x = np.full((2, 3), 1.0, np.float32)
        np.testing.assert_allclose(im.predict(x), 2.0)
        n0 = im.compile_cache_size()
        assert im.swap_params(_scale_params(5.0)) == "same"
        np.testing.assert_allclose(im.predict(x), 5.0)
        assert im.compile_cache_size() == n0, \
            "a same-structure swap must not compile"

    def test_same_structure_swap_zero_compiles_aot_path(self, tmp_path,
                                                        monkeypatch):
        from analytics_zoo_tpu.compile_cache import CompileCache
        import analytics_zoo_tpu.compile_cache.serialization as ccser
        cache = CompileCache(str(tmp_path), registry=MetricsRegistry())
        im = InferenceModel(compile_cache=cache).load_fn(
            _scale_fn, _scale_params(2.0))
        im.warmup(np.zeros(3, np.float32), buckets=[1, 2, 4])
        calls = []
        orig = ccser.compile_lowered
        monkeypatch.setattr(ccser, "compile_lowered",
                            lambda low: (calls.append(1), orig(low))[1])
        x = np.full((2, 3), 1.0, np.float32)
        assert im.swap_params(_scale_params(4.0)) == "same"
        np.testing.assert_allclose(im.predict(x), 4.0)
        assert calls == [], "AOT path recompiled on a same-shape swap"

    def test_restructured_swap_rewarns_honestly(self):
        def fn(p, x):
            out = x * p["w"]
            if "b" in p:
                out = out + p["b"]
            return out

        im = InferenceModel().load_fn(fn, {"w": np.float32(2.0)})
        im.warmup(np.zeros(3, np.float32), buckets=[1, 2, 4])
        assert im.warmed_buckets == {1, 2, 4}
        new = {"w": np.float32(3.0), "b": np.float32(1.0)}
        assert im.swap_params(new) == "restructured"
        # the warmed buckets were re-warmed through the bucket path
        assert im.warmed_buckets == {1, 2, 4}
        x = np.full((2, 3), 1.0, np.float32)
        np.testing.assert_allclose(im.predict(x), 4.0)

    def test_dtype_change_is_restructured(self):
        im = InferenceModel().load_fn(_scale_fn, _scale_params(2.0))
        im.warmup(np.zeros(3, np.float32), buckets=[1, 2])
        bf16 = {"w": np.asarray(2.0, "bfloat16")} \
            if hasattr(np, "dtype") else None
        try:
            import jax.numpy as jnp
            new = {"w": np.asarray(jnp.asarray(2.0, jnp.bfloat16))}
        except Exception:  # noqa: BLE001 — environment without bf16
            pytest.skip("no bfloat16 on this host")
        assert im.swap_params(new) == "restructured"
        assert im.serving_dtype == "bfloat16"
        del bf16

    def test_replicated_pool_swap_reaches_every_replica(self, devices8):
        im = InferenceModel(num_replicas=2).load_fn(
            _scale_fn, _scale_params(2.0))
        try:
            x = np.full((2, 3), 1.0, np.float32)
            for _ in range(4):
                np.testing.assert_allclose(im.predict(x), 2.0)
            assert im.swap_params(_scale_params(7.0)) == "same"
            outs = [im.predict(x) for _ in range(8)]
            for o in outs:
                np.testing.assert_allclose(o, 7.0)
            stats = im.replica_stats()
            assert all(s["batches"] > 0 for s in stats), \
                "both replicas should have routed post-swap work"
        finally:
            im.close()

    def test_current_params_snapshot_restores(self):
        im = InferenceModel().load_fn(_scale_fn, _scale_params(2.0))
        x = np.full((1, 3), 1.0, np.float32)
        np.testing.assert_allclose(im.predict(x), 2.0)
        snap = im.current_params()
        im.swap_params(_scale_params(9.0))
        np.testing.assert_allclose(im.predict(x), 9.0)
        assert im.swap_params(snap) == "same"
        np.testing.assert_allclose(im.predict(x), 2.0)


# ---------------------------------------------------------------------------
# Heartbeat hardening (satellite)
# ---------------------------------------------------------------------------
class TestHeartbeatLastKnownGood:
    def test_telemetry_error_keeps_version_and_burn(self):
        broker = MemoryBroker()
        calls = {"n": 0}

        def payload():
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("telemetry hiccup")
            return {"ready": True, "model_version": 7, "slo_burn": 0.4}

        hb = HeartbeatPublisher(broker, STREAM, "e1", payload,
                                registry=MetricsRegistry())
        assert hb._publish_once()
        row = json.loads(broker.hget(engines_key(STREAM), "e1"))
        assert row["model_version"] == 7 and row["ready"] is True
        assert hb._publish_once()      # payload_fn raises this beat
        row = json.loads(broker.hget(engines_key(STREAM), "e1"))
        assert row["ready"] is False and "error" in row
        # last-known-good fields survive: no phantom version regression
        assert row["model_version"] == 7
        assert row["slo_burn"] == 0.4
        assert hb._publish_once()      # recovery restores ready
        row = json.loads(broker.hget(engines_key(STREAM), "e1"))
        assert row["ready"] is True and row["model_version"] == 7


# ---------------------------------------------------------------------------
# Engine rollout agent
# ---------------------------------------------------------------------------
class TestEngineRolloutAgent:
    def _engine_with_traffic(self, broker, mgr):
        s = _scale_engine(broker, "e1", scale=2.0, version=1,
                          supervise=False).start()
        inq = InputQueue(broker)
        for i in range(4):
            inq.enqueue(uri=f"warm{i}", t=np.full(3, 1.0, np.float32))
        _wait_results(broker, 4)
        return s

    def _agent(self, s, broker, **kw):
        kw.setdefault("poll_interval_s", 0.05)
        kw.setdefault("drain_timeout_s", 5.0)
        return EngineRolloutAgent(s, broker, registry=MetricsRegistry(),
                                  **kw)

    def _direct(self, broker, version, run_dir, target="e1"):
        broker.hset(rollout_key(STREAM), "directive", json.dumps(
            {"version": version, "run_dir": run_dir, "target": target}))

    def test_directive_swaps_canaries_and_reports(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        run_dir = _publish(mgr, 2, 3.0)
        s = self._engine_with_traffic(broker, mgr)
        try:
            agent = self._agent(s, broker)
            self._direct(broker, 2, run_dir)
            assert agent.poll_once() == "swapped"
            assert s.model_version == 2
            assert agent.last_swap["mode"] == "same"
            # the heartbeat now carries the new version (the commit)
            assert s._heartbeat_payload()["model_version"] == 2
            # traffic serves at the new scale
            inq = InputQueue(broker)
            inq.enqueue(uri="post", t=np.full(3, 1.0, np.float32))
            res = _wait_results(broker, 5)
            vals = decode_ndarray(json.loads(res["post"]))
            np.testing.assert_allclose(vals, 3.0)
        finally:
            s.stop()

    def test_directive_for_other_engine_ignored(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        run_dir = _publish(mgr, 2, 3.0)
        s = _scale_engine(broker, "e1", supervise=False)
        try:
            agent = self._agent(s, broker)
            self._direct(broker, 2, run_dir, target="other")
            assert agent.poll_once() is None
            assert s.model_version == 1
        finally:
            s.stop()

    def test_failed_canary_rolls_back_and_vetoes(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        run_dir = _publish(mgr, 3, float("nan"))   # poisoned version
        s = self._engine_with_traffic(broker, mgr)
        try:
            agent = self._agent(s, broker)
            self._direct(broker, 3, run_dir)
            assert agent.poll_once() == "vetoed"
            assert s.model_version == 1            # never reported
            veto = json.loads(broker.hget(rollout_key(STREAM),
                                          "veto:e1"))
            assert veto["version"] == 3
            assert "finite" in veto["reason"]
            # OLD params still serve
            inq = InputQueue(broker)
            inq.enqueue(uri="after", t=np.full(3, 1.0, np.float32))
            res = _wait_results(broker, 5)
            np.testing.assert_allclose(
                decode_ndarray(json.loads(res["after"])), 2.0)
            # a re-delivered directive for the vetoed version is inert
            assert agent.poll_once() is None
        finally:
            s.stop()

    def test_golden_delta_gate(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        run_dir = _publish(mgr, 2, 200.0)     # finite but wildly off
        s = self._engine_with_traffic(broker, mgr)
        try:
            agent = self._agent(s, broker, golden_tolerance=0.5)
            self._direct(broker, 2, run_dir)
            assert agent.poll_once() == "vetoed"
            assert "golden-output delta" in agent.last_swap["reason"]
            assert s.model_version == 1
        finally:
            s.stop()

    def test_unpublished_version_vetoed_on_load(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        mgr.save(2, _scale_params(3.0))       # durable, NOT published
        s = _scale_engine(broker, "e1", supervise=False)
        try:
            agent = self._agent(s, broker)
            self._direct(broker, 2, mgr.run_dir)
            assert agent.poll_once() == "vetoed"
            assert "load failed" in agent.last_swap["reason"]
        finally:
            s.stop()

    def test_canary_skips_pre_quarantined_replicas(self, devices8):
        """A chip the supervisor already pulled must not veto a healthy
        new version — its brokenness is a fact about the chip."""
        im = InferenceModel(num_replicas=2).load_fn(
            _scale_fn, _scale_params(2.0))
        try:
            x = np.full((2, 3), 1.0, np.float32)
            im.predict(x)                      # golden traffic
            assert im.quarantine_replica(1)
            broker = MemoryBroker()
            s = ClusterServing(im, broker=broker, engine_id="e1",
                               registry=MetricsRegistry(),
                               supervise=False)
            agent = self._agent(s, broker)
            old = np.asarray(im.predict(x))
            ok, reason = agent._canary(im, x, old)
            assert ok, reason
        finally:
            im.close()

    def test_swap_exception_vetoes_and_restores(self, tmp_path,
                                                monkeypatch):
        """A raising swap (device OOM, indivisible shard) must veto and
        restore like a failed canary — never leave the engine
        model-less with no veto published."""
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        run_dir = _publish(mgr, 2, 3.0)
        s = self._engine_with_traffic(broker, mgr)
        try:
            agent = self._agent(s, broker)
            orig = s.model.swap_params
            calls = {"n": 0}

            def exploding(params):
                calls["n"] += 1
                if calls["n"] == 1:
                    raise RuntimeError("device OOM mid-transfer")
                return orig(params)            # the restore succeeds

            monkeypatch.setattr(s.model, "swap_params", exploding)
            self._direct(broker, 2, run_dir)
            assert agent.poll_once() == "vetoed"
            assert "swap raised" in agent.last_swap["reason"]
            assert s.model_version == 1
            veto = json.loads(broker.hget(rollout_key(STREAM),
                                          "veto:e1"))
            assert veto["version"] == 2
            # old params still serve
            inq = InputQueue(broker)
            inq.enqueue(uri="post-oops", t=np.full(3, 1.0, np.float32))
            res = _wait_results(broker, 5)
            np.testing.assert_allclose(
                decode_ndarray(json.loads(res["post-oops"])), 2.0)
        finally:
            s.stop()

    def test_quarantined_version_never_applied(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        run_dir = _publish(mgr, 2, 3.0)
        broker.hset(rollout_key(STREAM), "quarantine",
                    json.dumps({"2": "poisoned elsewhere"}))
        s = _scale_engine(broker, "e1", supervise=False)
        try:
            agent = self._agent(s, broker)
            self._direct(broker, 2, run_dir)
            assert agent.poll_once() is None
            assert s.model_version == 1
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# Rollout controller (tick-driven)
# ---------------------------------------------------------------------------
class TestRolloutController:
    def _controller(self, broker, root, tracker, **kw):
        kw.setdefault("poll_interval_s", 0.5)
        kw.setdefault("engine_timeout_s", 30.0)
        return RolloutController(broker, STREAM, root, tracker,
                                 registry=MetricsRegistry(), **kw)

    def _directive(self, broker):
        raw = broker.hget(rollout_key(STREAM), "directive")
        return json.loads(raw) if raw else None

    def test_engine_by_engine_convergence(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _publish(mgr, 2, 3.0)
        _beat(broker, "e0", 1)
        _beat(broker, "e1", 1)
        ctrl = self._controller(broker, str(tmp_path), _tracker(broker))
        assert ctrl.tick(now=0.0) == "direct"
        assert ctrl.state == "rolling"
        d = self._directive(broker)
        assert d["target"] == "e0" and d["version"] == 2
        # e1 untouched until e0 reports the new version
        assert ctrl.tick(now=1.0) is None
        _beat(broker, "e0", 2)
        assert ctrl.tick(now=2.0) == "direct"
        assert self._directive(broker)["target"] == "e1"
        _beat(broker, "e1", 2)
        assert ctrl.tick(now=3.0) == "converged"
        assert ctrl.state == "idle" and ctrl.active_version == 2
        assert self._directive(broker) is None

    def test_veto_quarantines_fleet_wide_and_rolls_back(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _publish(mgr, 2, 3.0)
        _beat(broker, "e0", 2)       # e0 already converted
        _beat(broker, "e1", 1)
        ctrl = self._controller(broker, str(tmp_path), _tracker(broker))
        assert ctrl.tick(now=0.0) == "direct"
        assert self._directive(broker)["target"] == "e1"
        # e1's canary fails
        broker.hset(rollout_key(STREAM), "veto:e1", json.dumps(
            {"version": 2, "reason": "canary output is not finite",
             "engine_id": "e1"}))
        ctrl.tick(now=1.0)
        assert "2" in ctrl.quarantined
        # persisted fleet-wide
        q = json.loads(broker.hget(rollout_key(STREAM), "quarantine"))
        assert "2" in q
        # the next campaign walks e0 BACK to version 1
        ctrl.tick(now=2.0)
        assert ctrl.state == "rolled_back"
        d = self._directive(broker)
        assert d["target"] == "e0" and d["version"] == 1
        _beat(broker, "e0", 1)
        assert ctrl.tick(now=3.0) == "converged"
        assert ctrl.state == "idle" and ctrl.active_version == 1
        assert not ctrl.rolling_back

    def test_quarantine_survives_controller_restart(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _publish(mgr, 2, 3.0)
        broker.hset(rollout_key(STREAM), "quarantine",
                    json.dumps({"2": "poisoned"}))
        _beat(broker, "e0", 1)
        ctrl = self._controller(broker, str(tmp_path), _tracker(broker))
        assert "2" in ctrl.quarantined
        # v2 is never targeted; fleet already on the best good version
        assert ctrl.tick(now=0.0) is None
        assert ctrl.state == "idle" and ctrl.active_version == 1

    def test_dead_engine_skipped_mid_campaign(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 2, 3.0)
        _beat(broker, "e0", 1)
        _beat(broker, "e1", 1)
        ctrl = self._controller(broker, str(tmp_path), _tracker(broker))
        ctrl.tick(now=0.0)
        assert self._directive(broker)["target"] == "e0"
        # e0 SIGKILLed: its row vanishes (aged out / purged)
        broker.hdel(engines_key(STREAM), "e0")
        assert ctrl.tick(now=1.0) == "direct"
        assert self._directive(broker)["target"] == "e1"
        _beat(broker, "e1", 2)
        assert ctrl.tick(now=2.0) == "converged"
        assert ctrl.active_version == 2

    def test_wedged_engine_skipped_not_quarantined(self, tmp_path):
        """An alive engine that never converts (no agent, wedged swap)
        is skipped as a straggler — it must NOT poison the VERSION for
        the healthy rest of the fleet."""
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _publish(mgr, 2, 3.0)
        _beat(broker, "e0", 1)       # wedged: will never convert
        _beat(broker, "e1", 1)
        ctrl = self._controller(broker, str(tmp_path), _tracker(broker),
                                engine_timeout_s=5.0)
        ctrl.tick(now=0.0)
        assert self._directive(broker)["target"] == "e0"
        _beat(broker, "e0", 1)
        # timeout: e0 skipped, campaign moves on to e1
        assert ctrl.tick(now=6.0) == "direct"
        assert self._directive(broker)["target"] == "e1"
        assert "2" not in ctrl.quarantined
        _beat(broker, "e1", 2)
        assert ctrl.tick(now=7.0) == "partial"
        assert ctrl.status()["stragglers"] == {"e0": 2}
        # stable: the partial state doesn't churn
        assert ctrl.tick(now=8.0) is None
        # a NEW version gives the straggler another chance
        _publish(mgr, 3, 4.0)
        assert ctrl.tick(now=9.0) == "direct"
        d = self._directive(broker)
        assert d["version"] == 3 and d["target"] == "e0"

    def test_engine_scope_veto_skips_engine_not_version(self, tmp_path):
        """An engine that cannot LOAD a version (broken mount,
        replication lag) refuses with engine scope: the controller
        skips that engine and the campaign continues — the version is
        never quarantined for the healthy fleet."""
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _publish(mgr, 2, 3.0)
        _beat(broker, "e0", 1)
        _beat(broker, "e1", 1)
        ctrl = self._controller(broker, str(tmp_path), _tracker(broker))
        ctrl.tick(now=0.0)
        assert self._directive(broker)["target"] == "e0"
        broker.hset(rollout_key(STREAM), "veto:e0", json.dumps(
            {"version": 2, "scope": "engine", "engine_id": "e0",
             "reason": "load failed: FileNotFoundError"}))
        assert ctrl.tick(now=1.0) == "direct"
        assert self._directive(broker)["target"] == "e1"
        assert "2" not in ctrl.quarantined
        assert ctrl.status()["stragglers"] == {"e0": 2}
        _beat(broker, "e1", 2)
        assert ctrl.tick(now=2.0) == "partial"

    def test_pinned_version_quarantined_releases_pin(self, tmp_path):
        """A pin whose version gets vetoed must release — holding it
        would re-target the poisoned version forever."""
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _publish(mgr, 2, 3.0)
        _beat(broker, "e0", 1)
        ctrl = self._controller(broker, str(tmp_path), _tracker(broker))
        ctrl.request(2)
        assert self._directive(broker)["version"] == 2
        broker.hset(rollout_key(STREAM), "veto:e0", json.dumps(
            {"version": 2, "reason": "canary output is not finite",
             "engine_id": "e0"}))
        ctrl.tick(now=1.0)
        assert "2" in ctrl.quarantined
        ctrl.tick(now=2.0)
        assert ctrl.force_version is None
        # fleet settles on the best GOOD version (e0 already there)
        assert ctrl.tick(now=3.0) is None
        assert ctrl.active_version == 1

    def test_transient_resolution_error_keeps_pin(self, tmp_path,
                                                  monkeypatch):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _beat(broker, "e0", 1)
        ctrl = self._controller(broker, str(tmp_path), _tracker(broker))
        ctrl.request(1)
        assert ctrl.force_version == 1
        # an NFS blip mid-resolve must not unpin (the next tick would
        # otherwise re-roll whatever the operator backed out of)
        monkeypatch.setattr(ckpt, "resolve_checkpoint",
                            lambda *a, **k: (_ for _ in ()).throw(
                                OSError("nfs blip")))
        assert ctrl.tick(now=1.0) is None
        assert ctrl.force_version == 1

    def test_mixed_fleet_resumes_after_restart(self, tmp_path):
        """A controller killed mid-rollout and restarted: the goal
        state is derivable, so it resumes with the stragglers only."""
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 2, 3.0)
        _beat(broker, "e0", 2)
        _beat(broker, "e1", 1)
        _beat(broker, "e2", 1)
        ctrl = self._controller(broker, str(tmp_path), _tracker(broker))
        assert ctrl.tick(now=0.0) == "direct"
        assert "e0" in ctrl.converted
        assert self._directive(broker)["target"] == "e1"

    def test_request_pins_published_version(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _publish(mgr, 2, 3.0)
        _beat(broker, "e0", 2)
        ctrl = self._controller(broker, str(tmp_path), _tracker(broker))
        # manual rollback to the OLDER published version is legal
        status = ctrl.request(1)
        assert status["state"] == "rolling"
        assert status["pinned_version"] == 1
        assert self._directive(broker)["version"] == 1
        # the pin is STICKY: convergence must not re-roll the newer
        # version the operator just backed out of
        _beat(broker, "e0", 1)
        assert ctrl.tick(now=1.0) == "converged"
        assert ctrl.tick(now=2.0) is None
        assert ctrl.force_version == 1 and ctrl.active_version == 1
        # unpin resumes following the newest published version
        ctrl.request(unpin=True)
        assert ctrl.state == "rolling"
        assert self._directive(broker)["version"] == 2
        with pytest.raises(FileNotFoundError):
            ctrl.request(99)
        ctrl.quarantined["1"] = "testing"
        with pytest.raises(ValueError):
            ctrl.request(1)

    def test_state_metrics(self, tmp_path):
        reg = MetricsRegistry()
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 2, 3.0)
        _beat(broker, "e0", 1)
        ctrl = RolloutController(broker, STREAM, str(tmp_path),
                                 _tracker(broker), registry=reg)
        assert reg.get("serving_rollout_state").value() == 0.0
        ctrl.tick(now=0.0)
        assert reg.get("serving_rollout_state").value() == 1.0
        _beat(broker, "e0", 2)
        ctrl.tick(now=1.0)
        assert reg.get("serving_rollout_state").value() == 0.0
        assert reg.get("serving_rollout_transitions_total").value(
            state="converged", version="2") == 1.0


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------
class TestRolloutHTTP:
    def _get(self, url):
        import urllib.request
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                return r.status, json.loads(r.read())
        except Exception as e:
            return e.code, json.loads(e.read())

    def _post(self, url, body=b""):
        import urllib.request
        req = urllib.request.Request(url, data=body, method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, json.loads(r.read())
        except Exception as e:
            return e.code, json.loads(e.read())

    def test_404_when_unconfigured(self):
        fe = FrontEnd(MemoryBroker(), None, host="127.0.0.1", port=0,
                      registry=MetricsRegistry()).start()
        try:
            base = f"http://127.0.0.1:{fe.port}"
            assert self._get(f"{base}/rollout/status")[0] == 404
            assert self._post(f"{base}/rollout")[0] == 404
        finally:
            fe.stop()

    def test_gateway_rollout_roundtrip(self, tmp_path):
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        _beat(broker, "e0", 1)
        tracker_reg = MetricsRegistry()
        fe = FrontEnd(broker, None, host="127.0.0.1", port=0,
                      fleet_stream=STREAM, registry=tracker_reg).start()
        ctrl = RolloutController(broker, STREAM, str(tmp_path),
                                 fe.fleet, registry=MetricsRegistry())
        fe.set_rollout(ctrl)
        try:
            base = f"http://127.0.0.1:{fe.port}"
            code, status = self._get(f"{base}/rollout/status")
            assert code == 200 and status["state"] == "idle"
            # unpublished version → 404; quarantined → 409
            code, _ = self._post(f"{base}/rollout",
                                 json.dumps({"version": 42}).encode())
            assert code == 404
            ctrl.quarantined["1"] = "bad"
            code, _ = self._post(f"{base}/rollout",
                                 json.dumps({"version": 1}).encode())
            assert code == 409
            ctrl.quarantined.clear()
            code, status = self._post(
                f"{base}/rollout", json.dumps({"version": 1}).encode())
            assert code == 202
            # /healthz carries the fleet version set
            code, h = self._get(f"{base}/healthz")
            assert h["fleet"]["model_versions"] == [1]
        finally:
            fe.stop()

    def test_engine_healthz_carries_version(self):
        broker = MemoryBroker()
        s = _scale_engine(broker, "e1", version=5, warm=False,
                          supervise=False).start()
        fe = FrontEnd(broker, s, host="127.0.0.1", port=0,
                      registry=MetricsRegistry()).start()
        try:
            code, h = self._get(f"http://127.0.0.1:{fe.port}/healthz")
            assert code == 200 and h["model_version"] == 5
        finally:
            fe.stop()
            s.stop()


# ---------------------------------------------------------------------------
# Config / CLI validation
# ---------------------------------------------------------------------------
class TestRolloutConfig:
    def _load(self, tmp_path, rollout_lines):
        cfg_path = tmp_path / "config.yaml"
        lines = ["model:", "  path: /tmp/model", "params:",
                 "  engine_id: e1", "  rollout:"]
        lines += [f"    {line}" for line in rollout_lines]
        cfg_path.write_text("\n".join(lines) + "\n")
        from analytics_zoo_tpu.serving.config import ServingConfig
        return ServingConfig.load(str(cfg_path))

    def test_rollout_params_parse(self, tmp_path):
        cfg = self._load(tmp_path, ["model_dir: /ckpts",
                                    "poll_interval_s: 1.5",
                                    "golden_tolerance: 0.25",
                                    "engine_timeout_s: 90"])
        assert cfg.rollout_model_dir == "/ckpts"
        assert cfg.rollout_poll_interval_s == 1.5
        assert cfg.rollout_golden_tolerance == 0.25
        assert cfg.rollout_engine_timeout_s == 90.0

    def test_defaults_without_block(self, tmp_path):
        cfg_path = tmp_path / "c.yaml"
        cfg_path.write_text("model:\n  path: /tmp/m\n")
        from analytics_zoo_tpu.serving.config import ServingConfig
        cfg = ServingConfig.load(str(cfg_path))
        assert cfg.rollout_model_dir is None
        assert cfg.rollout_poll_interval_s == 2.0

    @pytest.mark.parametrize("lines,match", [
        (["model_dir: /x", "poll_interval_s: 0"], "poll_interval_s"),
        (["model_dir: /x", "drain_timeout_s: -1"], "drain_timeout_s"),
        (["model_dir: /x", "golden_tolerance: -0.1"],
         "golden_tolerance"),
        (["model_dir: /x", "engine_timeout_s: 0"], "engine_timeout_s"),
    ])
    def test_bad_knobs_fail_at_load(self, tmp_path, lines, match):
        with pytest.raises(ValueError, match=match):
            self._load(tmp_path, lines)


# ---------------------------------------------------------------------------
# End to end: the acceptance scenario on an in-process fleet
# ---------------------------------------------------------------------------
class TestEndToEndRollout:
    def test_fleet_converges_with_traffic_flowing(self, tmp_path):
        """Trainer publishes N+1 → the 2-engine fleet converges
        engine-by-engine with records answering throughout (every
        accepted record gets a non-NaN result — no serving gap), zero
        XLA compiles for the same-structure swap; a poisoned N+2 then
        quarantines fleet-wide with N+1 still serving."""
        broker = MemoryBroker()
        mgr = ckpt.CheckpointManager(str(tmp_path), keep=10)
        _publish(mgr, 1, 2.0)
        engines, agents = [], []
        for i in range(2):
            s = _scale_engine(broker, f"e{i}", scale=2.0, version=1,
                              supervise=False).start()
            engines.append(s)
            agents.append(EngineRolloutAgent(
                s, broker, poll_interval_s=0.05, drain_timeout_s=5.0,
                registry=MetricsRegistry()).start())
        tracker = _tracker(broker)
        ctrl = RolloutController(broker, STREAM, str(tmp_path), tracker,
                                 poll_interval_s=0.05,
                                 engine_timeout_s=60.0,
                                 registry=MetricsRegistry()).start()
        inq = InputQueue(broker)
        accepted = []
        feeding = threading.Event()
        feeding.set()

        def feeder():
            i = 0
            while feeding.is_set():
                uri = f"r{i}"
                inq.enqueue(uri=uri, t=np.full(3, 1.0, np.float32))
                accepted.append(uri)
                i += 1
                time.sleep(0.005)

        t = threading.Thread(target=feeder, daemon=True)
        t.start()
        try:
            # traffic established on v1 before the rollout begins
            _wait(lambda: broker.hlen(RESULT_KEY) >= 8,
                  msg="pre-rollout traffic")
            sizes0 = [s.model.compile_cache_size() for s in engines]
            _publish(mgr, 2, 3.0)
            _wait(lambda: all(s.model_version == 2 for s in engines),
                  timeout_s=30.0, msg="fleet convergence on v2")
            _wait(lambda: ctrl.status()["active_version"] == 2,
                  timeout_s=30.0, msg="controller active_version")
            # zero compiles: same structure, every executable kept
            sizes1 = [s.model.compile_cache_size() for s in engines]
            assert sizes1 == sizes0, \
                f"rollout compiled: {sizes0} -> {sizes1}"
            # poisoned N+2: fleet-wide quarantine, v2 keeps serving
            _publish(mgr, 3, float("nan"))
            _wait(lambda: "3" in ctrl.status()["quarantined"],
                  timeout_s=30.0, msg="fleet-wide quarantine of v3")
            _wait(lambda: all(s.model_version == 2 for s in engines),
                  timeout_s=30.0, msg="engines back on v2")
            time.sleep(0.3)          # a little post-quarantine traffic
        finally:
            feeding.clear()
            t.join(timeout=10)
            total = len(accepted)
            try:
                res = _wait_results(broker, total, timeout_s=60.0)
            finally:
                ctrl.stop()
                for a in agents:
                    a.stop()
                for s in engines:
                    s.stop()
        # strict per-record accounting: every accepted record answered,
        # every answer finite and from a REAL version (2.0 or 3.0 —
        # never the poisoned v3, never NaN): no serving gap existed
        missing = [u for u in accepted if u not in res]
        assert not missing, f"{len(missing)} records lost"
        bad = []
        for uri in accepted:
            vals = np.asarray(decode_ndarray(json.loads(res[uri])))
            if not np.all(np.isfinite(vals)):
                bad.append((uri, "NaN"))
            elif not (np.allclose(vals, 2.0) or np.allclose(vals, 3.0)):
                bad.append((uri, vals.tolist()))
        assert not bad, f"bad results: {bad[:5]}"
