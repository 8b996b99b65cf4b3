"""Deep profiling layer (ISSUE 6): roofline accounting from XLA cost
analysis, on-demand profiler capture, device-memory telemetry, and SLO
health.

Acceptance scenarios covered here:
- the accountant's MFU / HBM-utilization gauges equal the accounted
  work over the measured seconds over the device's nameplate peak;
- `POST /profile` returns a loadable trace artifact; overlapping
  captures get 409; artifact rotation is bounded; an idle capture adds
  zero steady-state machinery (and the predict path measures within
  noise of a capture-free run);
- `/healthz` flips ready → not-ready → ready through a SUPERVISOR
  quarantine/revival round trip;
- a raising gauge callback degrades to NaN + an error counter, never a
  dead scrape.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.observability import (CaptureActiveError,
                                             DeviceMemoryLeak,
                                             DeviceMemoryWatcher,
                                             MetricsReporter,
                                             ProfileCapture,
                                             RooflineAccountant,
                                             SLOObjectives, SLOTracker,
                                             StackSampler, cost_of,
                                             get_accountant, get_registry,
                                             leak_check, load_trace_events,
                                             render_prometheus)
from analytics_zoo_tpu.observability.registry import MetricsRegistry
from analytics_zoo_tpu.serving import (ClusterServing, InferenceModel,
                                       InputQueue, MemoryBroker, OutputQueue)
from analytics_zoo_tpu.serving.http_frontend import FrontEnd


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faults.clear()


def _wait_until(cond, timeout_s=15.0, interval_s=0.01, msg="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval_s)
    raise AssertionError(f"timed out waiting for {msg}")


def _get(url, timeout=10):
    return urllib.request.urlopen(url, timeout=timeout)


def _post(url, data=b"", timeout=30):
    return urllib.request.urlopen(
        urllib.request.Request(url, data=data), timeout=timeout)


# ---------------------------------------------------------------------------
# Roofline accounting
# ---------------------------------------------------------------------------
class TestCostOf:
    def test_matmul_flops_exact(self):
        m, k, n = 32, 64, 16
        f = jax.jit(lambda p, x: x @ p)
        p = np.zeros((k, n), np.float32)
        x = np.zeros((m, k), np.float32)
        c = cost_of(f.lower(p, x))
        assert c is not None
        assert c.flops == pytest.approx(2 * m * k * n, rel=0.01)
        # inputs + output must move at least once
        assert c.bytes >= 4 * (m * k + k * n + m * n)

    def test_lowered_and_compiled_agree(self):
        f = jax.jit(lambda p, x: jax.numpy.tanh(x @ p))
        p = np.zeros((16, 16), np.float32)
        x = np.zeros((4, 16), np.float32)
        low = f.lower(p, x)
        c_low = cost_of(low)
        c_comp = cost_of(low.compile())
        assert c_low.flops == c_comp.flops
        assert c_low.bytes == c_comp.bytes

    def test_garbage_degrades_to_none(self):
        assert cost_of(None) is None

        class Broken:
            def cost_analysis(self):
                raise RuntimeError("no cost model on this backend")
        assert cost_of(Broken()) is None

    def test_lowered_without_an_answer_is_compiled_when_single_device(
            self, caplog):
        """The TPU backend of the installed jax: `Lowered.cost_analysis()`
        is None, `Compiled.cost_analysis()` a dict (seen on the chip in
        PR 21, where every roofline gauge was silently absent)."""
        class Compiled:
            def cost_analysis(self):
                return {"flops": 8.0, "bytes accessed": 2.0}

        class Lowered:
            compiles = 0

            def cost_analysis(self):
                return None

            def compile(self):
                Lowered.compiles += 1
                return Compiled()
        c = cost_of(Lowered())
        assert (c.flops, c.bytes, Lowered.compiles) == (8.0, 2.0, 1)
        # a partitioned program's compiled cost is per device — the wrong
        # basis: no number, a WARNING, no compile
        with caplog.at_level("WARNING"):
            assert cost_of(Lowered(), span=4) is None
        assert Lowered.compiles == 1
        assert [r for r in caplog.records
                if "spanning 4 devices" in r.message]


@pytest.fixture()
def isolated_registry():
    """A fresh MetricsRegistry per test: the accountant math tests
    assert EXACT counter values, and the process-global registry
    accumulates roofline series from any training that ran earlier in
    the same pytest process (e.g. test_fault_tolerance's auto-resume
    fits) — cross-file contamination that made these flake depending on
    collection order."""
    return MetricsRegistry()


class TestAccountant:
    def test_account_math_and_session_roofline(self, isolated_registry,
                                               monkeypatch):
        from analytics_zoo_tpu.utils import roofline as peaks
        reg = isolated_registry
        acct = RooflineAccountant(registry=reg)
        # a deterministic denominator: achieved GB/s and TFLOP/s known
        monkeypatch.setattr(peaks, "peak_hbm", lambda device=None: 100e9)
        monkeypatch.setattr(peaks, "peak_flops", lambda device=None: 10e12)
        acct.account("train", flops=2e12, bytes_=20e9, seconds=2.0)
        assert reg.get("roofline_flops_total").value(
            kind="train") == 2e12
        assert reg.get("roofline_achieved_tflops").value(
            kind="train") == pytest.approx(1.0)
        assert reg.get("roofline_achieved_hbm_gbps").value(
            kind="train") == pytest.approx(10.0)
        # 1 TFLOP/s of a 10 TFLOP/s roofline; 10 GB/s of 100 GB/s
        assert reg.get("roofline_mfu").value(
            kind="train") == pytest.approx(0.1)
        assert reg.get("roofline_hbm_utilization").value(
            kind="train") == pytest.approx(0.1)
        snap = acct.snapshot("train")
        assert snap["mfu"] == pytest.approx(0.1)
        assert snap["hbm_utilization"] == pytest.approx(0.1)

    def test_reset_starts_gauges_clean_but_counters_accumulate(
            self, isolated_registry):
        reg = isolated_registry
        acct = RooflineAccountant(registry=reg)
        acct.account("serving", 100.0, 100.0, 1.0)
        before = reg.get("roofline_flops_total").value(kind="serving")
        acct.reset("serving")
        acct.account("serving", 300.0, 300.0, 1.0)
        assert acct.snapshot("serving")["flops"] == 300.0   # clean rate
        assert reg.get("roofline_flops_total").value(
            kind="serving") == before + 300.0               # monotonic

    def test_account_never_raises(self):
        acct = RooflineAccountant()
        acct.account("serving", -1.0, 0.0, 0.0)     # degenerate inputs
        acct.account("serving", 1.0, 1.0, -5.0)
        assert acct.snapshot("serving")["seconds"] == 0.0


class TestNoDefaultPeak:
    """An unlisted device has no peak: the old lookup handed the CPU a
    v5e's 197 TFLOP/s / 819 GB/s and every utilization divided by it."""

    def test_cpu_device_raises_listed_kind_resolves(self):
        from analytics_zoo_tpu.utils.roofline import (UnknownDeviceError,
                                                      peak_flops, peak_hbm)
        cpu = jax.devices()[0]
        assert cpu.platform == "cpu"
        with pytest.raises(UnknownDeviceError):
            peak_flops(cpu)
        with pytest.raises(UnknownDeviceError):
            peak_hbm(cpu)

        class V5e:
            device_kind = "TPU v5 lite"
        assert peak_flops(V5e()) == 197e12
        assert peak_hbm(V5e()) == 819e9

    def test_utilization_gauges_stay_unpublished(self, isolated_registry):
        reg = isolated_registry
        acct = RooflineAccountant(registry=reg)
        acct.account("train", flops=2e12, bytes_=20e9, seconds=2.0,
                     device=jax.devices()[0])
        # the achieved rates need no peak and still publish
        assert reg.get("roofline_achieved_tflops").value(
            kind="train") == pytest.approx(1.0)
        assert reg.get("roofline_mfu").label_keys() == []
        assert reg.get("roofline_hbm_utilization").label_keys() == []
        snap = acct.snapshot("train")
        assert "mfu" not in snap and "hbm_utilization" not in snap
        assert snap["achieved_tflops"] == pytest.approx(1.0)


class TestServingRoofline:
    def test_warmup_harvests_and_predict_accounts(self):
        W = np.random.RandomState(0).randn(16, 8).astype(np.float32)
        im = InferenceModel().load_fn(lambda p, x: x @ p, W)
        im.warmup(np.zeros((16,), np.float32), buckets=[1, 2, 4])
        assert len(im._exec_cost) == 3          # one cost per bucket
        acct = get_accountant()
        before = acct.snapshot("serving")["flops"]
        im.predict(np.ones((2, 16), np.float32))
        after = acct.snapshot("serving")
        bucket_cost = im._exec_cost[im._cost_key(
            np.zeros((2, 16), np.float32))]
        assert after["flops"] == pytest.approx(
            before + bucket_cost.flops)
        assert after["seconds"] > 0

    def test_replicated_pool_accounts_per_batch(self, devices8):
        W = np.random.RandomState(0).randn(8, 4).astype(np.float32)
        im = InferenceModel(num_replicas=2).load_fn(lambda p, x: x @ p, W)
        try:
            im.warmup(np.zeros((8,), np.float32), buckets=[4])
            acct = get_accountant()
            base = acct.snapshot("serving")["flops"]
            pends = [im.predict_async(np.ones((4, 8), np.float32))
                     for _ in range(4)]
            for p in pends:
                p.result()
            cost = next(iter(im._exec_cost.values()))
            assert acct.snapshot("serving")["flops"] == pytest.approx(
                base + 4 * cost.flops)
        finally:
            im.close()

    def test_unwarmed_model_pays_and_publishes_nothing(self):
        W = np.zeros((4, 2), np.float32)
        im = InferenceModel().load_fn(lambda p, x: x @ p, W)
        im.predict(np.ones((2, 4), np.float32))
        assert im._exec_cost == {}
        assert get_accountant().snapshot("serving")["seconds"] == 0.0


# ---------------------------------------------------------------------------
# On-demand capture
# ---------------------------------------------------------------------------
class TestProfileCapture:
    def test_capture_produces_loadable_artifact(self, tmp_path):
        cap = ProfileCapture(str(tmp_path), max_artifacts=4)
        f = jax.jit(lambda x: x * 2)
        art = cap.start(tag="unit")
        assert cap.active
        np.asarray(f(np.ones(8, np.float32)))
        manifest = cap.stop()
        assert not cap.active
        assert manifest["dir"] == art
        assert manifest["files"]
        events = load_trace_events(art)
        assert isinstance(events, list) and events

    def test_overlap_raises_and_lock_releases(self, tmp_path):
        cap = ProfileCapture(str(tmp_path))
        cap.start()
        with pytest.raises(CaptureActiveError):
            cap.start()
        cap.stop()
        cap.start()                       # single-flight lock released
        cap.stop()

    def test_single_flight_is_process_wide(self, tmp_path):
        """jax.profiler's session is process-global, so two ProfileCapture
        INSTANCES (the frontend's and a fit's profile_steps window) must
        share one guard — the loser gets the documented
        CaptureActiveError, not an opaque profiler failure."""
        a = ProfileCapture(str(tmp_path / "a"))
        b = ProfileCapture(str(tmp_path / "b"))
        a.start()
        try:
            with pytest.raises(CaptureActiveError):
                b.start()
        finally:
            a.stop()

    def test_rotation_bounded(self, tmp_path):
        cap = ProfileCapture(str(tmp_path), max_artifacts=2)
        for i in range(4):
            cap.start(tag=f"r{i}")
            cap.stop()
        arts = cap.artifacts()
        assert len(arts) == 2
        # newest survive
        assert arts[-1].endswith("r3")
        assert arts[0].endswith("r2")

    def test_idle_capture_adds_zero_steady_state_machinery(self):
        """Zero-overhead-when-idle is structural: an attached-but-idle
        ProfileCapture installs no hooks, runs no threads, and holds no
        profiler session — the predict path cannot pay for what does
        not exist. The timing check below is a belt-and-braces smoke
        with a deliberately loose bound (shared CI cores)."""
        W = np.random.RandomState(0).randn(32, 8).astype(np.float32)
        im = InferenceModel().load_fn(lambda p, x: x @ p, W)
        im.warmup(np.zeros((32,), np.float32), buckets=[4])
        x = np.ones((4, 32), np.float32)

        def p50(n=60):
            lat = []
            for _ in range(n):
                t0 = time.perf_counter()
                im.predict(x)
                lat.append(time.perf_counter() - t0)
            return float(np.percentile(lat, 50))

        im.predict(x)                       # warm
        base = p50()
        threads_before = {t.name for t in threading.enumerate()}
        cap = ProfileCapture(os.path.join("/tmp", "zoo-idle-probe"))
        with_idle = p50()
        assert not cap.active
        assert {t.name for t in threading.enumerate()} == threads_before
        # loose noise bound: an idle capture must not multiply latency
        assert with_idle < base * 3 + 0.005

    def test_fit_profile_steps_window(self, tmp_path):
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        from analytics_zoo_tpu.learn.estimator import Estimator
        m = Sequential([L.Dense(8, input_shape=(4,))])
        est = Estimator.from_keras(m, optimizer="sgd", loss="mse")
        x = np.random.rand(64, 4).astype(np.float32)
        y = np.random.rand(64, 8).astype(np.float32)
        hist = est.fit((x, y), epochs=1, batch_size=8,
                       profile_steps=(2, 4), profile_dir=str(tmp_path))
        arts = hist.get("profile_artifacts")
        assert arts and os.path.isdir(arts[0])
        assert load_trace_events(arts[0])

    def test_fit_profile_steps_validation(self):
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        from analytics_zoo_tpu.learn.estimator import Estimator
        m = Sequential([L.Dense(4, input_shape=(4,))])
        est = Estimator.from_keras(m, optimizer="sgd", loss="mse")
        x = np.random.rand(16, 4).astype(np.float32)
        with pytest.raises(ValueError, match="profile_steps"):
            est.fit((x, x), epochs=1, batch_size=8,
                    profile_steps=(4, 2))


class TestStackSampler:
    def test_samples_matching_threads_only(self):
        stop = threading.Event()

        def spin():
            while not stop.is_set():
                sum(range(500))

        t1 = threading.Thread(target=spin, name="serving-busy-loop",
                              daemon=True)
        t2 = threading.Thread(target=spin, name="unrelated-loop",
                              daemon=True)
        t1.start()
        t2.start()
        try:
            with StackSampler(interval_s=0.002) as sampler:
                time.sleep(0.25)
            report = sampler.report()
        finally:
            stop.set()
            t1.join(timeout=2)
            t2.join(timeout=2)
        assert "serving-busy-loop" in report["threads"]
        assert "unrelated-loop" not in report["threads"]
        top = report["threads"]["serving-busy-loop"]["top"]
        assert top and top[0]["count"] >= 1
        assert "spin" in " ".join(e["frame"] for e in top)


# ---------------------------------------------------------------------------
# Device-memory telemetry
# ---------------------------------------------------------------------------
class TestDeviceMemory:
    def test_watcher_publishes_gauges(self):
        w = DeviceMemoryWatcher(interval_s=30.0)
        snap = w.sample()
        assert snap
        g = get_registry().get("device_memory_live_bytes")
        labels = [dict(k) for k in g.label_keys()]
        assert any("device" in lbl for lbl in labels)
        peak = get_registry().get("device_memory_peak_bytes")
        assert peak is not None

    def test_watcher_thread_lifecycle(self):
        w = DeviceMemoryWatcher(interval_s=0.05)
        with w:
            time.sleep(0.15)
        assert w._thread is None

    def test_leak_check_clean(self):
        with leak_check(tolerance_bytes=1 << 20):
            r = jax.numpy.ones((128, 128)) @ jax.numpy.ones((128, 128))
            r.block_until_ready()
            del r

    def test_leak_check_detects_retained_device_bytes(self):
        keep = []
        with pytest.raises(DeviceMemoryLeak, match="grew past"):
            with leak_check(tolerance_bytes=1024):
                keep.append(jax.device_put(
                    np.ones((512, 512), np.float32)))
        keep.clear()

    def test_leak_check_reports_workload_error_not_leak(self):
        with pytest.raises(RuntimeError, match="workload"):
            with leak_check(tolerance_bytes=0):
                raise RuntimeError("workload failed")


# ---------------------------------------------------------------------------
# SLO health
# ---------------------------------------------------------------------------
class TestSLOTracker:
    def _tracker(self, **kw):
        defaults = dict(latency_ms=50.0, availability=0.99, window_s=60.0)
        defaults.update(kw)
        return SLOTracker(SLOObjectives(**defaults), min_interval_s=0.0)

    def test_validation(self):
        with pytest.raises(ValueError, match="latency_ms"):
            SLOObjectives(latency_ms=-1).validate()
        with pytest.raises(ValueError, match="availability"):
            SLOObjectives(availability=1.5).validate()
        with pytest.raises(ValueError, match="window_s"):
            SLOObjectives(latency_ms=10, window_s=0).validate()
        with pytest.raises(ValueError, match="latency_quantile"):
            SLOObjectives(latency_quantile=1.0).validate()

    def test_no_data_is_vacuously_met(self):
        r = self._tracker().evaluate(force=True)
        assert r["met"] is True
        assert r["latency"]["burn_rate"] == 0.0

    def test_first_evaluation_ignores_lifetime_history(self):
        """A first /healthz poll must not report an old, fully recovered
        outage (process-lifetime counters) as a live violation: with no
        ring baseline there is no window, so the verdict is vacuous."""
        reg = get_registry()
        hist = reg.histogram("serving_batch_ms", "e2e")
        recs = reg.counter("serving_records_total", "outcomes")
        for _ in range(50):
            hist.observe(500.0)           # hours-old slow requests
        recs.inc(1000, outcome="served")
        recs.inc(50, outcome="failed")    # hours-old failures
        r = self._tracker().evaluate(force=True)
        assert r["met"] is True
        assert r["latency"]["count"] == 0
        assert r["availability"]["burn_rate"] == 0.0

    def test_burn_rates_and_gauges(self):
        reg = get_registry()
        hist = reg.histogram("serving_batch_ms", "e2e")
        recs = reg.counter("serving_records_total", "outcomes")
        tr = self._tracker()
        tr.evaluate(force=True)              # window baseline
        for _ in range(95):
            hist.observe(10.0)
        for _ in range(5):
            hist.observe(500.0)              # 5% over a p95 target: ~at
        recs.inc(100, outcome="served")      # budget
        recs.inc(2, outcome="failed")
        r = tr.evaluate(force=True)
        lat = r["latency"]
        assert lat["observed_ms"] > 0
        assert lat["burn_rate"] == pytest.approx(1.0, rel=0.25)
        avail = r["availability"]
        # 2% failure rate against a 1% budget → burn ≈ 2
        assert avail["burn_rate"] == pytest.approx(2.0, rel=0.05)
        assert avail["met"] is False
        assert r["met"] is False
        assert reg.get("slo_burn_rate").value(
            objective="availability") == pytest.approx(2.0, rel=0.05)
        assert reg.get("slo_met").value(objective="all") == 0.0

    def test_window_slides_past_old_violations(self):
        reg = get_registry()
        hist = reg.histogram("serving_batch_ms", "e2e")
        tr = self._tracker(availability=None, window_s=0.2)
        tr.evaluate(force=True)
        for _ in range(50):
            hist.observe(500.0)              # all over target
        assert tr.evaluate(force=True)["met"] is False
        time.sleep(0.3)                      # violations age out
        tr.evaluate(force=True)              # rolls the ring
        r = tr.evaluate(force=True)
        assert r["latency"]["count"] == 0
        assert r["met"] is True

    def test_auto_evaluator_detects_without_external_polls(self, caplog):
        """Violation detection must not depend on scrape cadence: the
        engine-driven auto thread keeps the window warm and flips
        slo_met on its own."""
        import logging
        reg = get_registry()
        hist = reg.histogram("serving_batch_ms", "e2e")
        tr = self._tracker(availability=None, window_s=5.0)
        tr.start_auto(interval_s=0.05)
        try:
            time.sleep(0.12)                 # baseline samples land
            for _ in range(30):
                hist.observe(500.0)          # sustained violation
            with caplog.at_level(
                    logging.WARNING,
                    logger="analytics_zoo_tpu.observability"):
                _wait_until(
                    lambda: reg.get("slo_met").value(
                        objective="all") == 0.0,
                    timeout_s=5.0, msg="auto-evaluated SLO violation")
            assert any("SLO violated" in r.getMessage()
                       for r in caplog.records)
        finally:
            tr.stop_auto()
        assert tr._auto_thread is None

    def test_engine_drives_auto_evaluation(self, devices8):
        W, fn = _make_model()
        im = InferenceModel().load_fn(fn, W)
        broker = MemoryBroker()
        serving = ClusterServing(
            im, broker=broker, batch_size=4,
            slo=SLOObjectives(latency_ms=100.0, window_s=4.0)).start()
        try:
            assert serving.slo._auto_thread is not None
        finally:
            serving.stop()
        assert serving.slo._auto_thread is None

    def test_reporter_evaluates_and_warns_once(self, caplog):
        reg = get_registry()
        hist = reg.histogram("serving_batch_ms", "e2e")
        tr = self._tracker(availability=None)
        rep = MetricsReporter(interval_s=60.0, slo=tr)
        rep._report()                        # baseline, met
        for _ in range(20):
            hist.observe(500.0)
        import logging
        with caplog.at_level(logging.WARNING,
                             logger="analytics_zoo_tpu.observability"):
            rep._report()
            rep._report()                    # still violated: no re-warn
        warns = [r for r in caplog.records
                 if "SLO violated" in r.getMessage()]
        assert len(warns) == 1
        assert reg.get("slo_met").value(objective="all") == 0.0


# ---------------------------------------------------------------------------
# /healthz + /profile over HTTP, and the supervisor round trip
# ---------------------------------------------------------------------------
def _make_model(in_dim=4, out_dim=3, seed=0):
    W = np.random.RandomState(seed).randn(in_dim, out_dim).astype(
        np.float32)
    return W, (lambda p, x: x @ p)


class TestHealthz:
    def test_frontend_without_engine_is_alive(self):
        broker = MemoryBroker()
        fe = FrontEnd(broker, None, host="127.0.0.1", port=0).start()
        try:
            r = _get(f"http://127.0.0.1:{fe.port}/healthz")
            body = json.loads(r.read())
            assert r.status == 200
            assert body["ready"] is True and body["engine"] is None
        finally:
            fe.stop()

    def test_flips_through_supervisor_quarantine_round_trip(self,
                                                            devices8):
        """The acceptance scenario: ready → not-ready → ready driven by
        the SUPERVISOR (fault-injected dispatch failures quarantine the
        whole pool; clearing the fault lets the canary probes revive
        it), observed purely through GET /healthz."""
        W, fn = _make_model()
        im = InferenceModel(num_replicas=2).load_fn(fn, W)
        broker = MemoryBroker()
        serving = ClusterServing(
            im, broker=broker, batch_size=1, batch_timeout_ms=2,
            failure_threshold=2, probe_interval_s=0.1,
            latency_floor_ms=2000.0,
            slo=SLOObjectives(latency_ms=1000.0, window_s=30.0)).start()
        fe = FrontEnd(broker, serving, host="127.0.0.1", port=0).start()
        base = f"http://127.0.0.1:{fe.port}"
        try:
            r = _get(base + "/healthz")
            body = json.loads(r.read())
            assert r.status == 200 and body["ready"] is True
            assert body["healthy_replicas"] == 2
            assert "slo" in body          # SLO status rides the payload

            # fault every replica; pump records until the supervisor has
            # quarantined the whole pool
            faults.inject("replica.dispatch", faults.Fault())
            inq = InputQueue(broker)
            deadline = time.monotonic() + 20
            while im.healthy_replicas() > 0 and \
                    time.monotonic() < deadline:
                inq.enqueue(t=np.ones((4,), np.float32))
                time.sleep(0.01)
            assert im.healthy_replicas() == 0
            with pytest.raises(urllib.error.HTTPError) as exc:
                _get(base + "/healthz")
            assert exc.value.code == 503
            payload = json.loads(exc.value.read())
            assert payload["ready"] is False
            assert "quarantined" in payload["reason"]
            assert int(exc.value.headers["Retry-After"]) >= 1
            assert payload["supervisor"]["healthy"] == 0

            # recovery: canary probes revive the pool → ready again
            faults.clear("replica.dispatch")
            _wait_until(lambda: im.healthy_replicas() == 2,
                        msg="pool revival")
            r = _get(base + "/healthz")
            assert r.status == 200
            assert json.loads(r.read())["ready"] is True
        finally:
            fe.stop()
            serving.stop()

    def test_healthz_wrong_method_is_405(self):
        broker = MemoryBroker()
        fe = FrontEnd(broker, None, host="127.0.0.1", port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(f"http://127.0.0.1:{fe.port}/healthz")
            assert exc.value.code == 405
            assert exc.value.headers["Allow"] == "GET"
        finally:
            fe.stop()


class TestProfileEndpoint:
    @pytest.fixture()
    def frontend(self, tmp_path):
        W, fn = _make_model()
        im = InferenceModel().load_fn(fn, W)
        broker = MemoryBroker()
        serving = ClusterServing(im, broker=broker, batch_size=4,
                                 batch_timeout_ms=2).start()
        fe = FrontEnd(broker, serving, host="127.0.0.1", port=0,
                      profile_dir=str(tmp_path),
                      profile_max_artifacts=2).start()
        yield fe, serving, str(tmp_path)
        fe.stop()
        serving.stop()

    def test_post_profile_returns_loadable_artifact(self, frontend):
        fe, _serving, root = frontend
        r = _post(f"http://127.0.0.1:{fe.port}/profile?seconds=0.3")
        manifest = json.loads(r.read())
        assert r.status == 200
        assert manifest["dir"].startswith(root)
        assert manifest["files"]
        assert load_trace_events(manifest["dir"])
        # host stack report for the pipeline threads rides along
        assert "host_stacks" in manifest
        assert any(name.startswith("serving-")
                   for name in manifest["host_stacks"]["threads"])

    def test_overlapping_captures_get_409(self, frontend):
        fe, _serving, _root = frontend
        url = f"http://127.0.0.1:{fe.port}/profile"
        results = {}

        def first():
            results["r"] = _post(url + "?seconds=1.2").status

        t = threading.Thread(target=first)
        t.start()
        time.sleep(0.4)                   # first capture is running
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(url + "?seconds=0.2")
        assert exc.value.code == 409
        t.join()
        assert results["r"] == 200
        # and the single-flight lock released: a later capture works
        assert _post(url + "?seconds=0.2").status == 200

    def test_rotation_bound_holds_over_http(self, frontend):
        fe, _serving, root = frontend
        url = f"http://127.0.0.1:{fe.port}/profile?seconds=0.1"
        for _ in range(3):
            assert _post(url).status == 200
        dirs = [d for d in os.listdir(root)
                if os.path.isdir(os.path.join(root, d))]
        assert len(dirs) <= 2             # profile_max_artifacts=2

    def test_bad_seconds_is_400(self, frontend):
        fe, _serving, _root = frontend
        for q in ("seconds=abc", "seconds=-1", "seconds=9999"):
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(f"http://127.0.0.1:{fe.port}/profile?{q}")
            assert exc.value.code == 400

    def test_profile_enabled_false_is_404(self):
        broker = MemoryBroker()
        fe = FrontEnd(broker, None, host="127.0.0.1", port=0,
                      profile_enabled=False).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(f"http://127.0.0.1:{fe.port}/profile?seconds=0.1")
            assert exc.value.code == 404
            assert "disabled" in json.loads(exc.value.read())["error"]
        finally:
            fe.stop()


# ---------------------------------------------------------------------------
# Gauge callback hardening (ISSUE 6 satellite)
# ---------------------------------------------------------------------------
class TestGaugeHardening:
    def test_raising_callback_degrades_to_nan_everywhere(self):
        reg = get_registry()
        g = reg.gauge("flaky_provider")
        g.set_function(lambda: 1 / 0)
        g.set(3.0, which="good")
        # snapshot: NaN series, good series intact
        series = {tuple(sorted(s["labels"].items())): s["value"]
                  for s in g._series_snapshot()}
        assert np.isnan(series[()])
        assert series[(("which", "good"),)] == 3.0
        # value(): NaN, not a raise
        assert np.isnan(g.value())
        # Prometheus render survives and emits NaN
        text = render_prometheus(reg)
        assert "flaky_provider NaN" in text
        # reporter digest survives
        from analytics_zoo_tpu.observability import digest
        assert "flaky_provider" in digest(reg.snapshot())

    def test_errors_are_counted_per_gauge(self):
        reg = get_registry()
        g = reg.gauge("counted_flake")
        g.set_function(lambda: 1 / 0)
        before = 0.0
        fam = reg.get("observability_gauge_errors_total")
        if fam is not None:
            before = fam.value(gauge="counted_flake")
        g.value()
        g._series_snapshot()
        fam = reg.get("observability_gauge_errors_total")
        assert fam.value(gauge="counted_flake") == before + 2

    def test_snapshot_registers_error_counter_without_deadlock(self):
        reg = get_registry()
        g = reg.gauge("deadlock_probe")
        g.set_function(lambda: 1 / 0)
        # full-registry snapshot triggers the error path while iterating
        # families — must complete, not deadlock or raise
        snap = reg.snapshot()
        assert "deadlock_probe" in snap


# ---------------------------------------------------------------------------
# Config surface
# ---------------------------------------------------------------------------
class TestServingConfigSLO:
    def _load(self, tmp_path, body):
        cfg_path = tmp_path / "config.yaml"
        cfg_path.write_text(body)
        from analytics_zoo_tpu.serving.config import ServingConfig
        return ServingConfig.load(str(cfg_path))

    def test_slo_block_parses_and_builds(self, tmp_path):
        cfg = self._load(tmp_path, """
model:
  path: /tmp/nowhere
params:
  slo:
    latency_ms: 50
    latency_quantile: 0.9
    availability: 0.999
    window_s: 120
  profile_dir: /tmp/profiles
  profile_max_artifacts: 3
""")
        obj = cfg.build_slo()
        assert obj.latency_ms == 50.0
        assert obj.latency_quantile == 0.9
        assert obj.availability == 0.999
        assert obj.window_s == 120.0
        assert cfg.profile_dir == "/tmp/profiles"
        assert cfg.profile_max_artifacts == 3

    def test_no_slo_block_builds_none(self, tmp_path):
        cfg = self._load(tmp_path, "model:\n  path: /tmp/nowhere\n")
        assert cfg.build_slo() is None

    def test_bad_slo_fails_at_load(self, tmp_path):
        with pytest.raises(ValueError, match="availability"):
            self._load(tmp_path, """
model:
  path: /tmp/nowhere
params:
  slo:
    availability: 2.0
""")

    def test_bad_profile_max_artifacts_fails_at_load(self, tmp_path):
        with pytest.raises(ValueError, match="profile_max_artifacts"):
            self._load(tmp_path, """
model:
  path: /tmp/nowhere
params:
  profile_max_artifacts: 0
""")
