"""Continuous-batching generative serving (ISSUE 18): the KV slot pool,
the per-step scheduler, the decode-attention kernel's reference path,
greedy-decode parity between the continuous-batched engine and a
single-sequence reference (bitwise, including a mid-flight join), the
zero-compile guarantee on the decode request path, token streaming
through the result hash (client + SSE frontend), and the multi-row
tolerance fix in the non-streaming poll paths.

All on the conftest CPU backend; tier-1 fast."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import analytics_zoo_tpu.compile_cache.serialization as ccser
from analytics_zoo_tpu.compile_cache import CompileCache
from analytics_zoo_tpu.models.generative import TinyDecoder
from analytics_zoo_tpu.observability.registry import MetricsRegistry
from analytics_zoo_tpu.pallas.decode_attention import (
    _reference_decode_attention, decode_attention)
from analytics_zoo_tpu.serving.broker import MemoryBroker, encode_ndarray
from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
from analytics_zoo_tpu.serving.decode import (DecodeScheduler, DecodeServing,
                                              KVSlotPool, _pow2_ladder,
                                              token_row_field)
from analytics_zoo_tpu.serving.inference_model import InferenceModel


def tiny(**kw):
    kw.setdefault("vocab", 32)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 2)
    kw.setdefault("head_dim", 8)
    kw.setdefault("max_len", 64)
    return TinyDecoder(**kw)


def load_im(dec, cache_dir=None):
    im = InferenceModel(
        placement="replicated", num_replicas=1,
        compile_cache=CompileCache(str(cache_dir)) if cache_dir else None)
    im.load_generative(dec.prefill_fn, dec.step_fn, dec.init_params(0))
    return im


def reference_decode(im, dec, prompt, max_new, slots, max_kv_len,
                     prompt_buckets, kv_bucket):
    """Single-sequence greedy decode on the SAME executables, alone in
    slot 0 of a fresh pool — the parity oracle."""
    from analytics_zoo_tpu.serving.inference_model import _next_bucket
    kv = dec.init_kv(slots, max_kv_len)
    pb = _next_bucket(len(prompt), sorted(prompt_buckets))
    padded = np.zeros(pb, np.int32)
    padded[:len(prompt)] = prompt
    kv, logits = im.generative_prefill(kv, padded, len(prompt), 0)
    out = [int(np.asarray(logits).argmax())]
    pos = len(prompt)
    while len(out) < max_new:
        toks = np.zeros(slots, np.int32)
        toks[0] = out[-1]
        p = np.zeros(slots, np.int32)
        p[0] = pos
        kv, logits = im.generative_step(kv, toks, p, kv_bucket)
        out.append(int(np.asarray(logits)[0].argmax()))
        pos += 1
    return out


class TestKVSlotPool:
    def test_lease_release_and_gauge(self):
        reg = MetricsRegistry()
        pool = KVSlotPool(tiny().init_kv, slots=3, max_kv_len=16,
                          registry=reg, labels={"engine": "e1"})

        def gauge():
            (s,) = reg.snapshot()["serving_kv_slots_in_use"]["series"]
            return s["value"]

        assert pool.free_count == 3 and gauge() == 0.0
        slots = [pool.lease() for _ in range(3)]
        assert slots == [0, 1, 2]          # slot 0 leases first
        assert pool.lease() is None        # exhausted -> None, no raise
        assert pool.in_use == 3 and gauge() == 3.0
        pool.release(1)
        assert pool.free_count == 1 and gauge() == 2.0
        assert pool.lease() == 1           # freed row recycles
        with pytest.raises(ValueError):
            pool.release(7)                # out of range
        pool.release(0)
        with pytest.raises(ValueError):
            pool.release(0)                # double release

    def test_pool_buffer_is_preallocated_once(self):
        dec = tiny()
        pool = KVSlotPool(dec.init_kv, slots=4, max_kv_len=32,
                          registry=MetricsRegistry())
        assert len(pool.kv) == dec.n_layers
        for layer in pool.kv:
            assert layer["k"].shape == (4, dec.n_heads, 32, dec.head_dim)


class TestDecodeScheduler:
    def make(self, deadline_ms=None, max_prefills=None):
        return DecodeScheduler([16, 32, 64], [8, 16],
                               registry=MetricsRegistry(),
                               deadline_ms=deadline_ms,
                               max_prefills_per_step=max_prefills)

    def test_admit_caps_at_free_slots(self):
        plan = self.make().plan_step([3, 5, 7], free_slots=2,
                                     active_lengths=[])
        assert plan.admit == 2 and plan.reason == "free-slots"

    def test_pool_full_admits_nothing(self):
        plan = self.make().plan_step([3], free_slots=0, active_lengths=[9])
        assert plan.admit == 0 and plan.reason == "pool-full"
        assert self.make().plan_step([], 4, []).reason == "no-waiting"

    def test_kv_bucket_covers_longest_live_and_admitted(self):
        sched = self.make()
        # active length 20 -> bucket 32; admitting a 40-token prompt
        # (needs 41 positions) forces bucket 64
        assert sched.plan_step([], 4, [20]).kv_bucket == 32
        assert sched.plan_step([40], 4, [20]).kv_bucket == 64

    def test_deadline_budget_caps_prefills(self):
        sched = self.make(deadline_ms=20.0)
        # learned costs: a step at bucket 32 ~ 5ms, a prefill ~ 8ms
        for _ in range(20):
            sched.observe_step(32, 5.0)
            sched.observe_prefill(8, 8.0)
        # budget = 20 - 2 - 5 = 13ms -> one 8ms prefill fits, not two
        plan = sched.plan_step([3, 3, 3], free_slots=3,
                               active_lengths=[20])
        assert plan.admit == 1 and plan.reason == "deadline"
        # no in-flight sequences -> nothing to stall, pool-limited only
        plan = sched.plan_step([3, 3, 3], free_slots=3, active_lengths=[])
        assert plan.admit == 3

    def test_at_least_one_prefill_even_over_budget(self):
        sched = self.make(deadline_ms=5.0)
        for _ in range(20):
            sched.observe_step(32, 4.0)
            sched.observe_prefill(8, 50.0)
        plan = sched.plan_step([3, 3], free_slots=2, active_lengths=[10])
        assert plan.admit == 1      # starvation guard

    def test_max_prefills_per_step(self):
        plan = self.make(max_prefills=2).plan_step(
            [1, 1, 1, 1], free_slots=4, active_lengths=[])
        assert plan.admit == 2

    def test_pow2_ladder(self):
        assert _pow2_ladder(8, 64) == [8, 16, 32, 64]
        assert _pow2_ladder(4, 48) == [4, 8, 16, 32, 48]


class TestDecodeAttention:
    def test_reference_matches_full_attention(self):
        rng = np.random.default_rng(0)
        S, H, L, D = 3, 2, 32, 8
        q = rng.normal(size=(S, H, D)).astype(np.float32)
        k = rng.normal(size=(S, H, L, D)).astype(np.float32)
        v = rng.normal(size=(S, H, L, D)).astype(np.float32)
        lengths = np.array([5, 17, 32], np.int32)
        out = np.asarray(_reference_decode_attention(
            q, k, v, lengths, kv_bucket=32))
        for s in range(S):
            n = int(lengths[s])
            for h in range(H):
                scores = q[s, h] @ k[s, h, :n].T / np.sqrt(D)
                w = np.exp(scores - scores.max())
                w /= w.sum()
                expect = w @ v[s, h, :n]
                np.testing.assert_allclose(out[s, h], expect, rtol=2e-5,
                                           atol=2e-6)

    def test_bucket_window_ignores_tail(self):
        # positions past kv_bucket must not influence the result
        rng = np.random.default_rng(1)
        S, H, L, D = 2, 2, 64, 8
        q = rng.normal(size=(S, H, D)).astype(np.float32)
        k = rng.normal(size=(S, H, L, D)).astype(np.float32)
        v = rng.normal(size=(S, H, L, D)).astype(np.float32)
        lengths = np.array([4, 9], np.int32)
        a = np.asarray(decode_attention(q, k, v, lengths, kv_bucket=16))
        k2, v2 = k.copy(), v.copy()
        k2[:, :, 16:] = 7.7
        v2[:, :, 16:] = -3.3
        b = np.asarray(decode_attention(q, k2, v2, lengths, kv_bucket=16))
        np.testing.assert_array_equal(a, b)


class TestGenerativeModel:
    def test_prefill_then_steps_match_full_forward_greedy(self):
        """The incremental KV path must agree with just re-running
        prefill on the grown sequence (same math, different caching)."""
        dec = tiny()
        im = load_im(dec)
        prompt = [3, 1, 4, 1, 5]
        toks = reference_decode(im, dec, prompt, max_new=6, slots=2,
                                max_kv_len=64, prompt_buckets=[8, 16],
                                kv_bucket=64)
        # oracle: greedy via repeated prefill over the full prefix
        seq = list(prompt)
        expect = []
        for _ in range(6):
            pb = 8 if len(seq) <= 8 else 16
            padded = np.zeros(pb, np.int32)
            padded[:len(seq)] = seq
            kv = dec.init_kv(1, 64)
            _, logits = im.generative_prefill(kv, padded, len(seq), 0)
            t = int(np.asarray(logits).argmax())
            expect.append(t)
            seq.append(t)
        assert toks == expect


def start_engine(dec, im, broker, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_kv_len", 64)
    kw.setdefault("kv_buckets", [64])
    kw.setdefault("prompt_buckets", [8, 16])
    kw.setdefault("max_new_default", 6)
    im.warmup_generative(dec.init_kv, slots=kw["slots"],
                         max_kv_len=kw["max_kv_len"],
                         prompt_buckets=kw["prompt_buckets"],
                         kv_buckets=kw["kv_buckets"])
    return DecodeServing(im, dec.init_kv, broker=broker,
                         registry=MetricsRegistry(), **kw)


class TestGreedyParity:
    def test_continuous_batch_bitwise_equals_single_sequence(self):
        """Every sequence in a mixed-length continuous batch — including
        one that joins mid-flight — must emit the EXACT token stream a
        single-sequence decode of the same prompt produces. One kv
        bucket so both runs share every executable (per-slot math is
        row-independent, so slot index and co-tenants must not matter)."""
        dec = tiny()
        im = load_im(dec)
        broker = MemoryBroker()
        srv = start_engine(dec, im, broker, max_new_default=8)
        prompts = {"a": [3, 5, 7], "b": [2, 4, 6, 8, 10, 12],
                   "c": [1, 9, 11, 13]}
        inq = InputQueue(broker)
        outq = OutputQueue(broker)
        srv.start()
        try:
            uris = {n: inq.enqueue(t=np.asarray(p, np.int32), max_new=8)
                    for n, p in (("a", prompts["a"]), ("b", prompts["b"]))}
            # let a/b board first, then join c mid-flight
            deadline = time.monotonic() + 10
            while srv.stats["prefills"] < 2:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            uris["c"] = inq.enqueue(t=np.asarray(prompts["c"], np.int32),
                                    max_new=8)
            got = {}
            for name, uri in uris.items():
                deadline = time.monotonic() + 20
                while time.monotonic() < deadline:
                    r = outq.query(uri, delete=True)
                    if r is not None:
                        got[name] = [int(t) for t in r]
                        break
                    time.sleep(0.005)
        finally:
            srv.stop()
        assert set(got) == {"a", "b", "c"}
        for name, prompt in prompts.items():
            expect = reference_decode(im, dec, prompt, max_new=8, slots=4,
                                      max_kv_len=64, prompt_buckets=[8, 16],
                                      kv_bucket=64)
            assert got[name] == expect, name

    def test_eos_stops_early(self):
        dec = tiny()
        im = load_im(dec)
        prompt = [3, 5, 7]
        ref = reference_decode(im, dec, prompt, max_new=8, slots=4,
                               max_kv_len=64, prompt_buckets=[8, 16],
                               kv_bucket=64)
        eos = ref[2]                # force a cut after 3 tokens
        broker = MemoryBroker()
        srv = start_engine(dec, im, broker, max_new_default=8)
        srv.start()
        try:
            uri = InputQueue(broker).enqueue(
                t=np.asarray(prompt, np.int32), max_new=8, eos=int(eos))
            outq = OutputQueue(broker)
            deadline = time.monotonic() + 20
            r = None
            while r is None and time.monotonic() < deadline:
                r = outq.query(uri, delete=True)
                time.sleep(0.005)
        finally:
            srv.stop()
        assert [int(t) for t in r] == ref[:3]


class TestZeroCompile:
    def test_no_compiles_on_decode_request_path(self, tmp_path,
                                                monkeypatch):
        dec = tiny()
        im = load_im(dec, cache_dir=tmp_path)
        broker = MemoryBroker()
        srv = start_engine(dec, im, broker, kv_buckets=[16, 64])
        assert set(im.warmup_source.values()) == {"compiled"}
        calls = []
        orig = ccser.compile_lowered

        def spy(lowered):
            calls.append(1)
            return orig(lowered)

        monkeypatch.setattr(ccser, "compile_lowered", spy)
        inq = InputQueue(broker)
        outq = OutputQueue(broker)
        srv.start()
        try:
            uris = [inq.enqueue(t=np.asarray(p, np.int32), max_new=5)
                    for p in ([3, 5, 7], [2, 4], [1] * 12)]
            for uri in uris:
                deadline = time.monotonic() + 20
                r = None
                while r is None and time.monotonic() < deadline:
                    r = outq.query(uri, delete=True)
                    time.sleep(0.005)
                assert r is not None
        finally:
            srv.stop()
        assert calls == []          # zero fresh XLA compiles

    def test_second_process_warms_from_disk(self, tmp_path):
        dec = tiny()
        im1 = load_im(dec, cache_dir=tmp_path)
        im1.warmup_generative(dec.init_kv, slots=4, max_kv_len=64,
                              prompt_buckets=[8], kv_buckets=[64])
        assert set(im1.warmup_source.values()) == {"compiled"}
        im2 = load_im(dec, cache_dir=tmp_path)
        im2.warmup_generative(dec.init_kv, slots=4, max_kv_len=64,
                              prompt_buckets=[8], kv_buckets=[64])
        assert set(im2.warmup_source.values()) == {"cached"}


class TestTokenStreaming:
    def test_stream_tokens_incremental_and_final(self):
        dec = tiny()
        im = load_im(dec)
        broker = MemoryBroker()
        srv = start_engine(dec, im, broker)
        srv.start()
        try:
            uri = InputQueue(broker).enqueue(
                t=np.asarray([3, 5, 7], np.int32), max_new=4, stream=1)
            events = list(OutputQueue(broker).stream_tokens(
                uri, timeout_s=20))
        finally:
            srv.stop()
        done = events[-1]
        assert done["done"] and done["gen"]["finish"] == "length"
        assert [e["i"] for e in events[:-1]] == [0, 1, 2, 3]
        assert [e["t"] for e in events[:-1]] == list(done["tokens"])
        assert done["gen"]["ttft_ms"] > 0
        # rows were cleaned up after the final
        assert broker.hgetall(srv.result_key) == {}

    def test_dequeue_tolerates_partial_token_rows(self):
        """The multi-row fix: a result-hash sweep that sees only token
        rows (no final) must treat the request as still in flight — and
        never delete rows the streaming consumer has not read."""
        broker = MemoryBroker()
        outq = OutputQueue(broker)
        key = outq.result_key
        broker.hset_many(key, {
            token_row_field("job1", 0): json.dumps({"i": 0, "t": 5}),
            token_row_field("job1", 1): json.dumps({"i": 1, "t": 9})})
        assert outq.dequeue() == {}                    # not completion
        assert len(broker.hgetall(key)) == 2           # rows untouched
        blob = encode_ndarray(np.array([5, 9], np.int32))
        blob["gen"] = {"n": 2, "rows": 2, "finish": "length",
                       "ttft_ms": 1.0}
        broker.hset_many(key, {"job1": json.dumps(blob)})
        got = outq.dequeue()
        assert list(got) == ["job1"]
        np.testing.assert_array_equal(got["job1"], [5, 9])
        assert broker.hgetall(key) == {}               # rows swept too

    def test_query_cleans_token_rows_of_streamed_result(self):
        broker = MemoryBroker()
        outq = OutputQueue(broker)
        key = outq.result_key
        blob = encode_ndarray(np.array([4], np.int32))
        blob["gen"] = {"n": 1, "rows": 1, "finish": "eos", "ttft_ms": 1.0}
        broker.hset_many(key, {
            "jobq": json.dumps(blob),
            token_row_field("jobq", 0): json.dumps({"i": 0, "t": 4})})
        r = outq.query("jobq", delete=True)
        np.testing.assert_array_equal(r, [4])
        assert broker.hgetall(key) == {}


class TestSSEFrontend:
    def test_predict_stream_sse(self):
        from analytics_zoo_tpu.serving.http_frontend import FrontEnd
        dec = tiny()
        im = load_im(dec)
        broker = MemoryBroker()
        srv = start_engine(dec, im, broker)
        srv.start()
        fe = FrontEnd(broker, None, port=0).start()
        try:
            url = f"http://127.0.0.1:{fe.port}/predict?stream=1"
            body = json.dumps({"prompt": [3, 5, 7], "max_new": 4}).encode()
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/event-stream")
                raw = resp.read().decode()
        finally:
            fe.stop()
            srv.stop()
        events = [e for e in raw.split("\n\n") if e.strip()]
        tokens = [json.loads(e.split("data: ", 1)[1])
                  for e in events if not e.startswith("event:")]
        assert [t["i"] for t in tokens] == [0, 1, 2, 3]
        done = [e for e in events if e.startswith("event: done")]
        assert len(done) == 1
        payload = json.loads(done[0].split("data: ", 1)[1])
        assert payload["tokens"] == [t["t"] for t in tokens]
        assert payload["gen"]["finish"] == "length"

    def test_predict_stream_requires_prompt(self):
        from analytics_zoo_tpu.serving.http_frontend import FrontEnd
        broker = MemoryBroker()
        fe = FrontEnd(broker, None, port=0).start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{fe.port}/predict?stream=1",
                data=json.dumps({"nope": 1}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 400
        finally:
            fe.stop()


class TestEngineBehavior:
    def test_slot_reuse_and_utilization_accounting(self):
        dec = tiny()
        im = load_im(dec)
        broker = MemoryBroker()
        srv = start_engine(dec, im, broker, slots=2, max_new_default=3)
        inq = InputQueue(broker)
        outq = OutputQueue(broker)
        srv.start()
        try:
            uris = [inq.enqueue(t=np.asarray([i + 1, i + 2], np.int32),
                                max_new=3) for i in range(5)]
            for uri in uris:
                deadline = time.monotonic() + 20
                r = None
                while r is None and time.monotonic() < deadline:
                    r = outq.query(uri, delete=True)
                    time.sleep(0.005)
                assert r is not None and len(r) == 3
        finally:
            srv.stop()
        assert srv.stats["finished"] == 5    # 5 sequences over 2 slots
        assert srv.pool.in_use == 0          # all released
        assert 0.0 < srv.utilization() <= 1.0

    def test_oversized_prompt_fails_cleanly(self):
        dec = tiny()
        im = load_im(dec)
        broker = MemoryBroker()
        srv = start_engine(dec, im, broker)
        srv.start()
        try:
            uri = InputQueue(broker).enqueue(
                t=np.arange(64, dtype=np.int32))   # no room to generate
            outq = OutputQueue(broker)
            deadline = time.monotonic() + 20
            r = None
            while r is None and time.monotonic() < deadline:
                r = outq.query(uri, delete=True)
                time.sleep(0.005)
        finally:
            srv.stop()
        assert isinstance(r, float) and np.isnan(r)
        assert srv.stats["failed"] == 1

    def test_metrics_families_present(self):
        reg = MetricsRegistry()
        dec = tiny()
        im = load_im(dec)
        im.warmup_generative(dec.init_kv, slots=2, max_kv_len=64,
                             prompt_buckets=[8], kv_buckets=[64])
        srv = DecodeServing(im, dec.init_kv, broker=MemoryBroker(),
                            slots=2, max_kv_len=64, kv_buckets=[64],
                            prompt_buckets=[8], registry=reg)
        srv.start()
        try:
            uri = InputQueue(srv.broker).enqueue(
                t=np.asarray([3, 5], np.int32), max_new=3)
            outq = OutputQueue(srv.broker)
            deadline = time.monotonic() + 20
            r = None
            while r is None and time.monotonic() < deadline:
                r = outq.query(uri, delete=True)
                time.sleep(0.005)
        finally:
            srv.stop()
        names = set(reg.snapshot())
        for family in ("serving_tokens_total", "serving_ttft_ms",
                       "serving_itl_ms", "serving_kv_slots_in_use"):
            assert family in names, family


class TestGenerativeConfig:
    def test_load_generative_block(self, tmp_path):
        from analytics_zoo_tpu.serving.config import ServingConfig
        p = tmp_path / "gen.yaml"
        p.write_text(json.dumps({
            "model": {"class": "TinyDecoder",
                      "config": {"vocab": 32, "max_len": 64}},
            "params": {"generative": {
                "slots": 4, "max_kv_len": 64, "kv_buckets": [16, 64],
                "prompt_buckets": [8], "max_new_tokens": 5,
                "eos_id": 2, "max_waiting": 9, "max_prefills": 2}}}))
        cfg = ServingConfig.load(str(p))
        assert cfg.generative
        assert cfg.decode_slots == 4
        assert cfg.decode_kv_buckets == [16, 64]
        assert cfg.decode_prompt_buckets == [8]
        assert cfg.decode_max_new_tokens == 5
        assert cfg.decode_eos_id == 2
        assert cfg.decode_max_waiting == 9
        assert cfg.decode_max_prefills == 2

    def test_bucket_over_max_kv_len_rejected(self, tmp_path):
        from analytics_zoo_tpu.serving.config import ServingConfig
        p = tmp_path / "bad.yaml"
        p.write_text(json.dumps({
            "model": {"class": "TinyDecoder"},
            "params": {"generative": {"max_kv_len": 32,
                                      "kv_buckets": [64]}}}))
        with pytest.raises(ValueError, match="exceeds"):
            ServingConfig.load(str(p))

    def test_build_generative_model_contract(self, tmp_path):
        from analytics_zoo_tpu.serving.config import ServingConfig
        p = tmp_path / "gen.yaml"
        p.write_text(json.dumps({
            "model": {"class": "TinyDecoder",
                      "config": {"vocab": 32, "max_len": 64}},
            "params": {"generative": {"slots": 2, "max_kv_len": 64}}}))
        cfg = ServingConfig.load(str(p))
        im, inst = cfg.build_generative_model()
        assert isinstance(inst, TinyDecoder)
        kv = inst.init_kv(2, 64)
        padded = np.zeros(8, np.int32)
        padded[:2] = [3, 5]
        _, logits = im.generative_prefill(kv, padded, 2, 0)
        assert np.asarray(logits).shape == (32,)
