"""Supervised fault tolerance (ISSUE 5): replica quarantine/revival,
broker circuit breaker + buffered sink, training auto-resume, and the
fault-injection harness that drives all of it.

Scenarios (the ISSUE's acceptance list):
- quarantine/revival round-trip on the conftest 8-device mesh;
- zero-record-loss through a broker outage (buffered writebacks);
- auto-resume producing loss-identical continuation vs an
  uninterrupted run (bitwise history equality);
- corrupt/truncated-latest-checkpoint fallback to the newest intact;
- all-replicas-quarantined -> HTTP 503 + Retry-After -> recovery;
plus the blocking-call static lint as a tier-1 gate.
"""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.observability import get_registry
from analytics_zoo_tpu.serving import (ClusterServing, InferenceModel,
                                       InputQueue, MemoryBroker, OutputQueue)
from analytics_zoo_tpu.serving.breaker import (CLOSED, OPEN, BackoffPolicy,
                                               CircuitBreaker,
                                               CircuitOpenError,
                                               ResilientBroker)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    """A chaos test must never leak an armed fault into the next test."""
    faults.clear()
    yield
    faults.clear()


def make_model(in_dim=4, out_dim=3, seed=0):
    W = np.random.RandomState(seed).randn(in_dim, out_dim).astype(np.float32)
    return W, (lambda p, x: x @ p)


def _counter_value(name, **labels):
    fam = get_registry().get(name)
    return fam.value(**labels) if fam is not None else 0.0


def _wait_until(cond, timeout_s=15.0, interval_s=0.01, msg="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval_s)
    raise AssertionError(f"timed out after {timeout_s}s waiting for {msg}")


# ---------------------------------------------------------------------------
# Fault-injection harness
# ---------------------------------------------------------------------------
class TestFaultHarness:
    def test_fire_is_noop_when_disarmed(self):
        faults.fire("nowhere.at.all", anything=1)   # must not raise

    def test_after_and_times_window(self):
        f = faults.inject("t.point", faults.Fault(after=2, times=2))
        for _ in range(2):                    # skipped by `after`
            faults.fire("t.point")
        for _ in range(2):                    # the armed window
            with pytest.raises(faults.FaultError):
                faults.fire("t.point")
        faults.fire("t.point")                # `times` exhausted
        assert f.trips == 2

    def test_match_predicate_scopes_the_fault(self):
        faults.inject("t.match",
                      faults.Fault(match=lambda c: c.get("replica") == 1))
        faults.fire("t.match", replica=0)
        with pytest.raises(faults.FaultError):
            faults.fire("t.match", replica=1)

    def test_stall_mode_sleeps(self):
        faults.inject("t.stall", faults.Fault(mode="stall", delay_s=0.08))
        t0 = time.perf_counter()
        faults.fire("t.stall")
        assert time.perf_counter() - t0 >= 0.07

    def test_truncate_mode_cuts_the_file(self, tmp_path):
        p = tmp_path / "artifact.bin"
        p.write_bytes(b"x" * 1000)
        faults.inject("t.trunc",
                      faults.Fault(mode="truncate", keep_fraction=0.5))
        faults.fire("t.trunc", path=str(p))
        assert p.stat().st_size == 500

    def test_context_manager_disarms_on_error(self):
        with pytest.raises(RuntimeError):
            with faults.injected("t.cm", faults.Fault()):
                raise RuntimeError("boom")
        assert faults.active("t.cm") is None

    def test_custom_exception(self):
        faults.inject("t.exc", faults.Fault(exc=ValueError("custom")))
        with pytest.raises(ValueError, match="custom"):
            faults.fire("t.exc")


# ---------------------------------------------------------------------------
# Circuit breaker + backoff + resilient broker
# ---------------------------------------------------------------------------
class TestCircuitBreaker:
    def test_opens_after_threshold_and_fast_fails(self):
        br = CircuitBreaker("t-open", failure_threshold=2,
                            reset_timeout_s=60)
        assert br.allow() and br.state == CLOSED
        br.record_failure()
        assert br.state == CLOSED             # one short of the threshold
        br.record_failure()
        assert br.state == OPEN
        assert not br.allow()                 # fast-fail, no probe yet

    def test_half_open_admits_exactly_one_probe(self):
        br = CircuitBreaker("t-half", failure_threshold=1,
                            reset_timeout_s=0.05)
        br.record_failure()
        assert not br.allow()
        time.sleep(0.06)
        assert br.allow()                     # the single half-open probe
        assert not br.allow()                 # concurrent calls still barred
        br.record_success()
        assert br.state == CLOSED and br.allow()

    def test_half_open_failure_reopens(self):
        br = CircuitBreaker("t-reopen", failure_threshold=1,
                            reset_timeout_s=0.05)
        br.record_failure()
        time.sleep(0.06)
        assert br.allow()
        br.record_failure()                   # the probe failed
        assert br.state == OPEN and not br.allow()

    def test_success_resets_the_failure_streak(self):
        br = CircuitBreaker("t-streak", failure_threshold=3)
        br.record_failure()
        br.record_failure()
        br.record_success()
        br.record_failure()
        br.record_failure()
        assert br.state == CLOSED             # never 3 consecutive

    def test_state_lands_in_registry(self):
        CircuitBreaker("t-metric", failure_threshold=1).record_failure()
        gauge = get_registry().get("serving_broker_breaker_state")
        assert gauge.value(broker="t-metric") == 1   # open


class TestBackoffPolicy:
    def test_capped_exponential_with_jitter(self):
        p = BackoffPolicy(initial_s=0.1, max_s=1.0, factor=2.0, jitter=0.25)
        for attempt, base in ((1, 0.1), (2, 0.2), (3, 0.4), (10, 1.0)):
            for _ in range(20):
                d = p.delay(attempt)
                assert base * 0.75 <= d <= base * 1.25, (attempt, d)

    def test_rejects_nonsense(self):
        with pytest.raises(ValueError):
            BackoffPolicy(initial_s=0)
        with pytest.raises(ValueError):
            BackoffPolicy(initial_s=1.0, max_s=0.5)


class TestResilientBroker:
    def test_guard_trips_breaker_then_fast_fails(self):
        rb = ResilientBroker(
            MemoryBroker(), role="t-rb",
            breaker=CircuitBreaker("t-rb", failure_threshold=2,
                                   reset_timeout_s=60))
        f = faults.inject("broker.xadd",
                          faults.Fault(match=lambda c: c["role"] == "t-rb"))
        for _ in range(2):
            with pytest.raises(faults.FaultError):
                rb.xadd("s", {"uri": "u", "data": {}})
        with pytest.raises(CircuitOpenError):
            rb.xadd("s", {"uri": "u", "data": {}})
        assert f.trips == 2       # the open circuit never reached the site

    def test_recovers_through_half_open_probe(self):
        rb = ResilientBroker(
            MemoryBroker(), role="t-rec",
            breaker=CircuitBreaker("t-rec", failure_threshold=1,
                                   reset_timeout_s=0.05))
        faults.inject("broker.xadd",
                      faults.Fault(times=1,
                                   match=lambda c: c["role"] == "t-rec"))
        with pytest.raises(faults.FaultError):
            rb.xadd("s", {"uri": "a", "data": {}})
        time.sleep(0.06)
        rb.xadd("s", {"uri": "b", "data": {}})     # half-open probe wins
        assert rb.breaker.state == CLOSED
        assert rb.read_group("s", "g", "c", 10, block_ms=10)

    def test_resp_error_does_not_open_circuit(self):
        from analytics_zoo_tpu.serving.broker import RESPError

        class AngryBroker(MemoryBroker):
            def xadd(self, stream, record):
                raise RESPError("ERR wrong arity")

        rb = ResilientBroker(
            AngryBroker(), role="t-resp",
            breaker=CircuitBreaker("t-resp", failure_threshold=1))
        with pytest.raises(RESPError):
            rb.xadd("s", {})
        assert rb.breaker.state == CLOSED   # app error over a live wire


# ---------------------------------------------------------------------------
# Reader reconnect + sink writeback buffering (zero record loss)
# ---------------------------------------------------------------------------
def _start_engine(im, broker, **kw):
    kw.setdefault("batch_size", 4)
    kw.setdefault("batch_timeout_ms", 2)
    return ClusterServing(im, broker=broker, **kw).start()


class TestBrokerOutage:
    def test_reader_reconnects_after_transient_outage(self):
        W, fn = make_model()
        im = InferenceModel().load_fn(fn, W)
        broker = MemoryBroker()
        before = _counter_value("serving_broker_reconnects_total",
                                role="reader")
        serving = _start_engine(
            im, broker, breaker_failure_threshold=2, breaker_reset_s=0.05)
        try:
            faults.inject(
                "broker.read_group",
                faults.Fault(times=3,
                             match=lambda c: c["role"] == "reader"))
            uri = InputQueue(broker).enqueue(
                t=np.ones((4,), np.float32))
            out = OutputQueue(broker)
            _wait_until(lambda: out.query(uri) is not None,
                        msg="result after reader outage")
            _wait_until(
                lambda: _counter_value("serving_broker_reconnects_total",
                                       role="reader") > before,
                msg="reader reconnect counter")
        finally:
            serving.stop()

    def test_zero_record_loss_through_sink_outage(self):
        """Results computed while the broker is down buffer in the sink
        and flush on reconnect — nothing is lost, nothing degrades to
        NaN."""
        W, fn = make_model()
        im = InferenceModel().load_fn(fn, W)
        broker = MemoryBroker()
        shed_before = _counter_value("serving_sink_shed_records_total")
        serving = _start_engine(
            im, broker, breaker_failure_threshold=2, breaker_reset_s=0.05)
        try:
            sink_only = faults.Fault(match=lambda c: c["role"] == "sink")
            # the sink commits through the fused writeback op (results
            # HSET + ack in one round trip) — that is the op to fail
            faults.inject("broker.writeback", sink_only)
            inq = InputQueue(broker)
            uris = [inq.enqueue(t=np.full((4,), i, np.float32))
                    for i in range(12)]
            # the engine accepts and computes everything; writebacks pile
            # into the bounded sink buffer
            _wait_until(lambda: len(serving._wb_buffer) > 0,
                        msg="sink writebacks buffering")
            faults.clear("broker.writeback")
            out = OutputQueue(broker)
            results = {}

            def _poll():
                for u in uris:
                    if u not in results:
                        r = out.query(u)
                        if r is not None:
                            results[u] = r
                return len(results) == len(uris)

            _wait_until(_poll, timeout_s=30,
                        msg="all 12 results after sink outage")
            for i, u in enumerate(uris):
                np.testing.assert_allclose(
                    results[u], np.full((4,), i, np.float32) @ W,
                    atol=1e-5)
            assert _counter_value(
                "serving_sink_shed_records_total") == shed_before
        finally:
            serving.stop()

    def test_sink_buffer_overflow_sheds_and_counts(self):
        """Past the buffer bound the OLDEST writeback is shed and
        counted; the shed records stay unacked, so redelivery serves
        them once the broker returns — bounded memory, still no loss."""
        W, fn = make_model()
        im = InferenceModel().load_fn(fn, W)
        broker = MemoryBroker(redeliver_after_s=0.5)
        shed_before = _counter_value("serving_sink_shed_records_total")
        serving = _start_engine(
            im, broker, batch_size=1, sink_buffer_batches=2,
            breaker_failure_threshold=2, breaker_reset_s=0.05)
        try:
            sink_only = faults.Fault(match=lambda c: c["role"] == "sink")
            # the sink commits through the fused writeback op (results
            # HSET + ack in one round trip) — that is the op to fail
            faults.inject("broker.writeback", sink_only)
            inq = InputQueue(broker)
            uris = [inq.enqueue(t=np.full((4,), i, np.float32))
                    for i in range(8)]
            _wait_until(
                lambda: _counter_value("serving_sink_shed_records_total")
                > shed_before,
                msg="shed counter increment")
            faults.clear("broker.writeback")
            out = OutputQueue(broker)
            _wait_until(
                lambda: all(out.query(u) is not None for u in uris),
                timeout_s=30, msg="every record served via redelivery")
        finally:
            serving.stop()


# ---------------------------------------------------------------------------
# Replica quarantine / revival
# ---------------------------------------------------------------------------
class TestQuarantineModel:
    """Router-level semantics, no engine: quarantine removes a replica
    from the routing set, revival restores it, probes use the canary."""

    def test_router_skips_quarantined_replica(self, devices8):
        W, fn = make_model()
        im = InferenceModel(num_replicas=2,
                            max_inflight_per_replica=8).load_fn(fn, W)
        try:
            x = np.ones((2, 4), np.float32)
            im.predict(x)                       # captures the canary
            assert im.quarantine_replica(0)
            assert not im.quarantine_replica(0)  # idempotent
            assert im.healthy_replicas() == 1
            assert im.quarantined_replicas() == [0]
            pends = [im.predict_async(x) for _ in range(4)]
            assert all(p.replica == 1 for p in pends)
            for p in pends:
                p.result()
            assert im.revive_replica(0)
            assert im.healthy_replicas() == 2
            replicas = {im.predict_async(x).replica for _ in range(4)}
            assert replicas == {0, 1}
        finally:
            im.close()

    def test_all_quarantined_fails_fast(self, devices8):
        from analytics_zoo_tpu.serving.inference_model import \
            NoHealthyReplicaError
        W, fn = make_model()
        im = InferenceModel(num_replicas=2).load_fn(fn, W)
        try:
            im.quarantine_replica(0)
            im.quarantine_replica(1)
            t0 = time.monotonic()
            with pytest.raises(NoHealthyReplicaError):
                im.predict_async(np.ones((2, 4), np.float32))
            assert time.monotonic() - t0 < 2.0   # no 60s router stall
        finally:
            im.close()

    def test_probe_replica_runs_canary(self, devices8):
        W, fn = make_model()
        im = InferenceModel(num_replicas=2).load_fn(fn, W)
        try:
            im.predict(np.ones((2, 4), np.float32))
            im.quarantine_replica(1)
            assert im.probe_replica(1, timeout_s=10)
        finally:
            im.close()

    def test_quarantine_redispatches_queued_work(self, devices8):
        """Work queued behind a stalled replica re-dispatches to healthy
        replicas on quarantine and still completes correctly, with every
        permit accounted for."""
        W, fn = make_model()
        im = InferenceModel(num_replicas=2,
                            max_inflight_per_replica=4).load_fn(fn, W)
        try:
            # stall replica 0's worker so routed jobs sit in its queue
            faults.inject("replica.dispatch",
                          faults.Fault(mode="stall", delay_s=0.3,
                                       match=lambda c: c["replica"] == 0))
            xs = [np.full((2, 4), i, np.float32) for i in range(6)]
            pends = [im.predict_async(x) for x in xs]
            im.quarantine_replica(0)
            for x, p in zip(xs, pends):
                np.testing.assert_allclose(p.result(), x @ W, atol=1e-5)
            _wait_until(
                lambda: all(s["inflight"] == 0
                            for s in im.replica_stats()),
                msg="all permits released after re-dispatch")
        finally:
            faults.clear()
            im.close()


class TestSupervisedEngine:
    def test_quarantine_revival_round_trip(self, devices8):
        """The acceptance scenario: a replica that starts throwing is
        quarantined within the failure threshold, traffic keeps flowing
        clean on the healthy set, and clearing the fault revives it via
        the canary probe."""
        W, fn = make_model()
        im = InferenceModel(num_replicas=4).load_fn(fn, W)
        broker = MemoryBroker()
        q_before = _counter_value("serving_replica_quarantined_total",
                                  replica="1", reason="failures")
        r_before = _counter_value("serving_replica_revivals_total",
                                  replica="1")
        # latency floor high enough that scheduler noise on a loaded
        # 2-core host can't spuriously latency-quarantine an innocent
        # replica — this test asserts EXACT counter increments
        serving = _start_engine(im, broker, batch_size=1,
                                failure_threshold=2, probe_interval_s=0.1,
                                latency_floor_ms=2000.0)
        try:
            faults.inject("replica.dispatch",
                          faults.Fault(match=lambda c: c["replica"] == 1))
            inq = InputQueue(broker)
            out = OutputQueue(broker)
            # pump singles until the router has fed replica 1 its
            # threshold of failures
            deadline = time.monotonic() + 20
            while im.healthy_replicas() == 4 and \
                    time.monotonic() < deadline:
                inq.enqueue(t=np.ones((4,), np.float32))
                time.sleep(0.01)
            assert im.healthy_replicas() == 3
            assert any(s.get("quarantined") for s in im.replica_stats())
            # the counter lands moments after the router flip (the
            # worker thread incs after quarantine_replica returns)
            _wait_until(
                lambda: _counter_value("serving_replica_quarantined_total",
                                       replica="1",
                                       reason="failures") == q_before + 1,
                msg="quarantine counter increment")
            # capacity degraded, correctness intact: fresh records are
            # all real results now
            fresh = [inq.enqueue(t=np.full((4,), i, np.float32))
                     for i in range(8)]
            _wait_until(lambda: all(out.query(u) is not None
                                    for u in fresh),
                        msg="fresh records served by healthy replicas")
            for i, u in enumerate(fresh):
                res = out.query(u)
                assert not (isinstance(res, float) and np.isnan(res)), \
                    f"record {i} degraded after quarantine"
                np.testing.assert_allclose(
                    res, np.full((4,), i, np.float32) @ W, atol=1e-5)
            # recovery: clear the fault, the canary probe revives it
            faults.clear("replica.dispatch")
            _wait_until(lambda: im.healthy_replicas() == 4,
                        msg="replica revival")
            _wait_until(
                lambda: _counter_value("serving_replica_revivals_total",
                                       replica="1") == r_before + 1,
                msg="revival counter increment")
        finally:
            serving.stop()

    def test_slow_replica_quarantined_as_latency_outlier(self, devices8):
        W, fn = make_model()
        im = InferenceModel(num_replicas=4).load_fn(fn, W)
        broker = MemoryBroker()
        serving = _start_engine(im, broker, batch_size=1,
                                failure_threshold=2, probe_interval_s=0.2,
                                latency_factor=4.0,
                                latency_floor_ms=150.0)
        try:
            # a healthy baseline first: the outlier test needs a median
            inq = InputQueue(broker)
            out = OutputQueue(broker)
            warm = [inq.enqueue(t=np.ones((4,), np.float32))
                    for _ in range(24)]
            _wait_until(lambda: all(out.query(u) is not None
                                    for u in warm),
                        msg="healthy latency baseline")
            faults.inject("replica.dispatch",
                          faults.Fault(mode="stall", delay_s=0.4,
                                       match=lambda c: c["replica"] == 2))
            # on a loaded 2-core host, scheduler noise can push an
            # INNOCENT replica past the floor too (the supervisor being
            # trigger-happy is revival's problem, not an error) — the
            # assertion is that the genuinely slow replica gets caught
            deadline = time.monotonic() + 25
            while not im.replica_stats()[2]["quarantined"] and \
                    time.monotonic() < deadline:
                inq.enqueue(t=np.ones((4,), np.float32))
                time.sleep(0.01)
            assert im.replica_stats()[2]["quarantined"] is True
        finally:
            serving.stop()


class TestAllQuarantined503:
    def test_503_retry_after_then_recovery(self, devices8):
        from analytics_zoo_tpu.serving.broker import encode_ndarray
        from analytics_zoo_tpu.serving.http_frontend import FrontEnd
        W, fn = make_model()
        im = InferenceModel(num_replicas=2).load_fn(fn, W)
        broker = MemoryBroker()
        serving = _start_engine(im, broker, batch_size=1,
                                failure_threshold=2, probe_interval_s=0.1,
                                latency_floor_ms=2000.0)
        fe = FrontEnd(broker, serving, host="127.0.0.1", port=0,
                      timeout_s=15.0).start()
        url = f"http://127.0.0.1:{fe.port}/predict"
        body = json.dumps(encode_ndarray(
            np.ones((4,), np.float32))).encode()
        try:
            faults.inject("replica.dispatch", faults.Fault())
            inq = InputQueue(broker)
            deadline = time.monotonic() + 20
            while im.healthy_replicas() > 0 and \
                    time.monotonic() < deadline:
                inq.enqueue(t=np.ones((4,), np.float32))
                time.sleep(0.01)
            assert im.healthy_replicas() == 0
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    urllib.request.Request(url, data=body), timeout=10)
            assert exc.value.code == 503
            assert int(exc.value.headers["Retry-After"]) >= 1
            # recovery: probes revive the pool, the frontend serves again
            faults.clear("replica.dispatch")
            _wait_until(lambda: im.healthy_replicas() == 2,
                        msg="pool revival")
            resp = urllib.request.urlopen(
                urllib.request.Request(url, data=body), timeout=30)
            assert resp.status == 200
            pred = json.loads(resp.read())["predictions"]
            np.testing.assert_allclose(
                pred, np.ones((4,), np.float32) @ W, atol=1e-5)
        finally:
            fe.stop()
            serving.stop()


# ---------------------------------------------------------------------------
# Checkpoint integrity: atomic writes, CRC, corrupt-latest fallback
# ---------------------------------------------------------------------------
class TestCheckpointIntegrity:
    def _save_two(self, root):
        from analytics_zoo_tpu.learn.checkpoint import CheckpointManager
        mgr = CheckpointManager(str(root))
        p1 = {"w": np.arange(4, dtype=np.float32)}
        p2 = {"w": np.arange(4, dtype=np.float32) * 2}
        mgr.save(1, p1, extra={"epoch": 1})
        mgr.save(2, p2, extra={"epoch": 2})
        return mgr, p1, p2

    def test_roundtrip_with_crc(self, tmp_path):
        from analytics_zoo_tpu.learn import checkpoint as ck
        tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
                "b": [np.ones(2, np.int32), {}]}
        ck.save_pytree(str(tmp_path / "t"), tree)
        loaded = ck.load_pytree(str(tmp_path / "t"))
        np.testing.assert_array_equal(loaded["a"], tree["a"])
        np.testing.assert_array_equal(loaded["b"][0], tree["b"][0])

    def test_corrupt_latest_falls_back_to_newest_intact(self, tmp_path):
        from analytics_zoo_tpu.learn import checkpoint as ck
        mgr, p1, _ = self._save_two(tmp_path)
        npz2 = os.path.join(mgr.run_dir, "model.2.npz")
        with open(npz2, "r+b") as fh:          # torn write / bad disk
            fh.truncate(os.path.getsize(npz2) // 2)
        found = ck.latest_checkpoint(str(tmp_path))
        assert found is not None and found[1] == 1
        params, _, meta = ck.load_checkpoint(str(tmp_path))
        np.testing.assert_array_equal(params["w"], p1["w"])
        assert meta["epoch"] == 1

    def test_bitflip_detected_by_crc(self, tmp_path):
        from analytics_zoo_tpu.learn import checkpoint as ck
        mgr, p1, _ = self._save_two(tmp_path)
        npz2 = os.path.join(mgr.run_dir, "model.2.npz")
        size = os.path.getsize(npz2)
        with open(npz2, "r+b") as fh:          # same size, flipped bytes
            fh.seek(size // 2)
            fh.write(b"\xff\xff\xff\xff")
        assert ck.latest_checkpoint(str(tmp_path))[1] == 1

    def test_truncate_fault_mid_write_falls_back(self, tmp_path):
        from analytics_zoo_tpu.learn import checkpoint as ck
        from analytics_zoo_tpu.learn.checkpoint import CheckpointManager
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"w": np.ones(3, np.float32)})
        with faults.injected("checkpoint.write",
                             faults.Fault(mode="truncate")):
            mgr.save(2, {"w": np.zeros(3, np.float32)})
        assert ck.latest_checkpoint(str(tmp_path))[1] == 1

    def test_crash_during_save_leaves_no_partial_artifact(self, tmp_path):
        from analytics_zoo_tpu.learn import checkpoint as ck
        with faults.injected("checkpoint.write",
                             faults.Fault(exc=OSError("disk full"))):
            with pytest.raises(OSError):
                ck.save_pytree(str(tmp_path / "m"), {"w": np.ones(3)})
        # nothing with the final name, and no intact-looking leftovers
        assert ck.latest_checkpoint(str(tmp_path)) is None
        assert not (tmp_path / "m.npz").exists()

    def test_torn_checkpoint_set_is_invisible(self, tmp_path):
        """A crash BETWEEN artifact commits must not leave a resumable-
        looking set: the model artifact commits LAST (the set's commit
        marker), so a version whose optimizer/meta landed but whose
        model write crashed simply does not exist to resume from."""
        from analytics_zoo_tpu.learn import checkpoint as ck
        mgr = ck.CheckpointManager(str(tmp_path))
        mgr.save(1, {"w": np.ones(3, np.float32)},
                 opt_state={"m": np.zeros(3, np.float32)},
                 extra={"epoch": 1, "epoch_finished": True})
        # first checkpoint.write fire = the optimizer artifact (commits
        # fine); the crash lands on the SECOND — the model artifact
        with faults.injected("checkpoint.write",
                             faults.Fault(after=1,
                                          exc=OSError("yanked disk"))):
            with pytest.raises(OSError):
                mgr.save(2, {"w": np.zeros(3, np.float32)},
                         opt_state={"m": np.ones(3, np.float32)},
                         extra={"epoch": 2, "epoch_finished": True})
        found = ck.find_resume_checkpoint(str(tmp_path))
        assert found is not None and found[1] == 1
        assert ck.latest_checkpoint(str(tmp_path))[1] == 1

    def test_all_corrupt_returns_none(self, tmp_path):
        from analytics_zoo_tpu.learn import checkpoint as ck
        mgr, _, _ = self._save_two(tmp_path)
        for v in (1, 2):
            with open(os.path.join(mgr.run_dir, f"model.{v}.npz"),
                      "r+b") as fh:
                fh.truncate(10)
        assert ck.latest_checkpoint(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# Training auto-resume + step watchdog
# ---------------------------------------------------------------------------
def _trainer_model():
    import optax

    from analytics_zoo_tpu.keras import Sequential
    from analytics_zoo_tpu.keras import layers as L
    m = Sequential()
    m.add(L.Dense(8, activation="relu", input_shape=(6,)))
    m.add(L.Dense(1))
    m.compile(optimizer=optax.sgd(1e-2), loss="mse")
    return m


def _trainer_data(n=128):
    rs = np.random.RandomState(3)
    x = rs.randn(n, 6).astype(np.float32)
    return x, (x @ rs.randn(6, 1)).astype(np.float32)


def _fit(model, x, y, epochs, **kw):
    from analytics_zoo_tpu.learn.trainer import fit_keras
    kw.setdefault("batch_size", 32)
    kw.setdefault("seed", 7)
    kw.setdefault("distributed", False)
    kw.setdefault("prefetch", False)
    # per-step dispatch: the watchdog/fault tests reason in steps, and
    # the auto device-cache path fuses a whole epoch into one dispatch
    kw.setdefault("device_cache", False)
    return fit_keras(model, x, y, epochs=epochs, **kw)


class TestAutoResume:
    def test_bitwise_identical_continuation(self, tmp_path):
        """Kill after epoch 2, relaunch with auto_resume=True: epochs 3-4
        must produce bitwise-identical losses to the uninterrupted run."""
        x, y = _trainer_data()
        m_full = _trainer_model()
        hist_full = _fit(m_full, x, y, epochs=4)

        m_a = _trainer_model()
        m_a.set_checkpoint(str(tmp_path))
        _fit(m_a, x, y, epochs=2)              # "killed" at this boundary

        before = _counter_value("training_resumes_total")
        m_b = _trainer_model()
        m_b.set_checkpoint(str(tmp_path))
        hist_resumed = _fit(m_b, x, y, epochs=4, auto_resume=True)
        assert hist_resumed["loss"] == hist_full["loss"][2:]
        assert _counter_value("training_resumes_total") == before + 1

    def test_moments_follow_their_layers_across_a_name_sort_flip(
            self, tmp_path, monkeypatch):
        """Saved as (dense_99, dense_100), resumed as (dense_101,
        dense_102): jax orders dict leaves by sorted key, so the saved
        pair sorts the other way round. Pouring the optimizer moments by
        position swapped the two layers' state (shape error here, silent
        corruption for equal shapes); they take the params' remap now."""
        from analytics_zoo_tpu.keras import engine
        counters = dict(engine._name_counters)
        monkeypatch.setattr(engine, "_name_counters",
                            engine.collections.defaultdict(int, counters))

        def adam_model():                       # Adam: state has moments
            m = _trainer_model()
            m.compile(optimizer="adam", loss="mse")
            return m
        x, y = _trainer_data()
        hist_full = _fit(adam_model(), x, y, epochs=3)
        engine._name_counters["Dense"] = 98
        m_a = adam_model()                      # dense_99, dense_100
        m_a.set_checkpoint(str(tmp_path))
        _fit(m_a, x, y, epochs=2)
        m_b = adam_model()                      # dense_101, dense_102
        m_b.set_checkpoint(str(tmp_path))
        hist_resumed = _fit(m_b, x, y, epochs=3, auto_resume=True)
        assert hist_resumed["loss"] == hist_full["loss"][2:]

    def test_resume_without_checkpoint_trains_fresh(self, tmp_path):
        x, y = _trainer_data()
        before = _counter_value("training_resumes_total")
        m = _trainer_model()
        m.set_checkpoint(str(tmp_path / "empty"))
        hist = _fit(m, x, y, epochs=2, auto_resume=True)
        assert len(hist["loss"]) == 2
        assert _counter_value("training_resumes_total") == before

    def test_resume_requires_checkpoint_path(self):
        x, y = _trainer_data()
        with pytest.raises(ValueError, match="set_checkpoint"):
            _fit(_trainer_model(), x, y, epochs=1, auto_resume=True)

    def test_resume_skips_corrupt_latest(self, tmp_path):
        """The newest checkpoint is torn on disk: resume falls back to
        the previous intact one and still continues bitwise."""
        import glob
        x, y = _trainer_data()
        m_full = _trainer_model()
        hist_full = _fit(m_full, x, y, epochs=3)

        m_a = _trainer_model()
        m_a.set_checkpoint(str(tmp_path))
        _fit(m_a, x, y, epochs=2)
        newest = sorted(
            glob.glob(str(tmp_path / "*" / "model.*.npz")),
            key=lambda p: int(p.rsplit(".", 2)[-2]))[-1]
        with open(newest, "r+b") as fh:
            fh.truncate(os.path.getsize(newest) // 3)
        m_b = _trainer_model()
        m_b.set_checkpoint(str(tmp_path))
        hist_resumed = _fit(m_b, x, y, epochs=3, auto_resume=True)
        # fell back to the epoch-1 boundary: epochs 2-3 re-run, and the
        # continuation still matches the uninterrupted run exactly
        assert hist_resumed["loss"] == hist_full["loss"][1:]

    def test_mid_epoch_kill_resumes_from_boundary(self, tmp_path):
        """A step fault kills the run mid-epoch (emergency checkpoint is
        mid-epoch); resume uses the newest EPOCH-BOUNDARY checkpoint so
        continuation stays loss-identical."""
        x, y = _trainer_data()
        m_full = _trainer_model()
        hist_full = _fit(m_full, x, y, epochs=4)

        m_a = _trainer_model()
        m_a.set_checkpoint(str(tmp_path))
        faults.inject(
            "trainer.step",
            faults.Fault(exc=RuntimeError("chip fell over"),
                         match=lambda c: c.get("iteration", 0) >= 9))
        with pytest.raises(RuntimeError, match="chip fell over"):
            _fit(m_a, x, y, epochs=4)          # dies mid-epoch 3
        faults.clear("trainer.step")

        m_b = _trainer_model()
        m_b.set_checkpoint(str(tmp_path))
        hist_resumed = _fit(m_b, x, y, epochs=4, auto_resume=True)
        assert hist_resumed["loss"] == hist_full["loss"][2:]


class TestStepWatchdog:
    def test_transient_step_fault_retried(self):
        x, y = _trainer_data()
        hist_clean = _fit(_trainer_model(), x, y, epochs=2)
        before = _counter_value("training_step_retries_total")
        faults.inject("trainer.step", faults.Fault(times=2))
        hist = _fit(_trainer_model(), x, y, epochs=2, step_retries=3)
        # the fault fires before dispatch, so the retried run is
        # numerically identical to the clean one
        assert hist["loss"] == hist_clean["loss"]
        assert _counter_value("training_step_retries_total") == before + 2

    def test_exhausted_retries_checkpoint_and_raise(self, tmp_path):
        from analytics_zoo_tpu.learn import checkpoint as ck
        x, y = _trainer_data()
        m = _trainer_model()
        m.set_checkpoint(str(tmp_path))
        faults.inject("trainer.step", faults.Fault(after=5))
        with pytest.raises(faults.FaultError):
            _fit(m, x, y, epochs=2, step_retries=1)
        # the give-up path wrote an emergency checkpoint
        assert ck.latest_checkpoint(str(tmp_path)) is not None

    def test_hung_step_times_out_and_retries(self):
        x, y = _trainer_data(n=64)
        m = _trainer_model()
        # warm the jitted step first: a cold retry pays XLA compilation,
        # which can itself outrun a tight watchdog budget and cancel a
        # step that already consumed its donated buffers
        _fit(m, x, y, epochs=1)
        before = _counter_value("training_step_retries_total")
        faults.inject("trainer.step",
                      faults.Fault(mode="stall", delay_s=2.0, times=1))
        hist = _fit(m, x, y, epochs=1, step_retries=2,
                    step_timeout_s=0.5)
        assert len(hist["loss"]) == 1
        assert _counter_value("training_step_retries_total") >= before + 1


# ---------------------------------------------------------------------------
# Blocking-call lint (tier-1 gate)
# ---------------------------------------------------------------------------
class TestBlockingCallLint:
    def test_serving_package_is_clean(self):
        import check_blocking_calls
        errors, n = check_blocking_calls.check(REPO)
        assert n > 10                      # actually scanned the package
        assert not errors, "\n".join(errors)

    def test_lint_catches_violations(self, tmp_path):
        import check_blocking_calls
        bad = tmp_path / "bad.py"
        bad.write_text(
            "item = q.get()\n"
            "q.put(item)\n"
            "thread.join()\n"
            "s = socket.create_connection(('h', 1))\n"
            "try:\n    pass\nexcept:\n    pass\n")
        errors = check_blocking_calls.check_file(str(bad), serving=True)
        assert len(errors) == 5
        joined = "\n".join(errors)
        for frag in (".get()", ".put(", ".join()", "create_connection",
                     "except"):
            assert frag in joined

    def test_waiver_comment_suppresses(self, tmp_path):
        import check_blocking_calls
        ok = tmp_path / "ok.py"
        ok.write_text(
            "item = q.get()  # blocking-ok: consumer owns shutdown\n"
            "q.put(item, timeout=1.0)\n"
            "q2.put_nowait(item)\n"
            "thread.join(timeout=5)\n"
            "d.get('key')\n"
            "s = socket.create_connection(('h', 1), timeout=30)\n"
            "except_this = 1\n")
        assert check_blocking_calls.check_file(str(ok), serving=True) == []

    def test_bare_except_flagged_outside_serving_too(self, tmp_path):
        import check_blocking_calls
        f = tmp_path / "x.py"
        f.write_text("q.get()\ntry:\n    pass\nexcept:\n    pass\n")
        errors = check_blocking_calls.check_file(str(f), serving=False)
        assert len(errors) == 1 and "except" in errors[0]
