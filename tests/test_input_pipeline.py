"""Parallel streaming input pipeline (ISSUE 15).

- ShardPipeline: output is a pure function of shard order at any worker
  count, shard errors surface at their stream position naming the
  shard, residency stays bounded (workers + slack), close() never
  hangs.
- TFRecord streaming: bitwise-identical batch streams at
  pipeline_workers 1 vs 4; pipeline-fed `fit_keras` losses match an
  in-memory-fed fit of the same batch order bitwise; a torn last frame
  surfaces one error naming file + byte offset (not a hang or a silent
  short epoch); native scanner vs pure-python walk produce identical
  sample streams; vectorized `decode_example_batch` is value-identical
  to per-record `decode_example`.
- Bounded memory: the pipeline's resident high-water mark + an RSS
  probe while streaming a corpus much larger than the bound.
- Readers: read_csv/read_json fan out per file with per-file errors
  naming the file; FeatureSet's python batch path is
  pipeline-invariant.
- Stall accounting: training_input_wait_ms / training_input_bound
  publish, and the roofline snapshot carries the input-stall column.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.data import tfrecord as tfr
from analytics_zoo_tpu.data.dataset import TPUDataset
from analytics_zoo_tpu.data.pipeline import (ShardPipeline, host_shard,
                                             parallel_read,
                                             resolve_workers)


class TestShardPipeline:
    def test_output_identical_at_any_worker_count(self):
        shards = list(range(12))

        def read(s):
            # deliberately uneven timing so completion order scrambles
            time.sleep(0.002 * ((s * 7) % 5))
            return [f"s{s}-{i}" for i in range(3)]

        def run(workers):
            pipe = ShardPipeline(shards, read, workers=workers)
            try:
                return list(pipe.samples())
            finally:
                pipe.close()

        want = [f"s{s}-{i}" for s in shards for i in range(3)]
        assert run(1) == want
        assert run(3) == want
        assert run(8) == want

    def test_error_surfaces_at_stream_position_naming_shard(self):
        def read(s):
            if s == "shard-2":
                raise ValueError("decode blew up")
            return [s]

        pipe = ShardPipeline(["shard-0", "shard-1", "shard-2", "shard-3"],
                             read, workers=4)
        got = []
        with pytest.raises(ValueError, match="shard-2.*decode blew up"):
            for item in pipe.samples():
                got.append(item)
        # everything BEFORE the bad shard was delivered first —
        # deterministic error position, not a race
        assert got == ["shard-0", "shard-1"]

    def test_error_already_naming_shard_not_double_wrapped(self):
        def read(s):
            raise ValueError(f"{s}: corrupt record at offset 12")

        pipe = ShardPipeline(["f1"], read, workers=2)
        with pytest.raises(ValueError,
                           match=r"^f1: corrupt record at offset 12$"):
            list(pipe.samples())

    def test_residency_bounded_by_workers_plus_slack(self):
        workers, slack = 3, 1
        pipe = ShardPipeline(list(range(20)), lambda s: [s],
                             workers=workers, reorder_slack=slack)
        try:
            for _ in pipe.samples():
                time.sleep(0.005)      # slow consumer: pool must park
        finally:
            pipe.close()
        assert pipe.max_resident <= workers + slack, \
            f"{pipe.max_resident} resident shards for {workers} workers"

    def test_early_break_closes_cleanly(self):
        pipe = ShardPipeline(list(range(50)),
                             lambda s: (time.sleep(0.001), [s])[1:],
                             workers=4)
        for item in pipe.samples():
            if item == 3:
                break
        pipe.close()
        assert all(not t.is_alive() for t in pipe._threads)

    def test_parallel_read_orders_and_names_files(self):
        out = parallel_read([3, 1, 2], lambda v: v * 10, workers=4)
        assert out == [30, 10, 20]
        with pytest.raises(ValueError, match="item-1"):
            parallel_read(["item-0", "item-1"],
                          lambda v: (_ for _ in ()).throw(
                              ValueError("bad")) if v == "item-1" else v,
                          workers=4)

    def test_resolve_workers_precedence(self):
        assert resolve_workers(3) == 3
        assert resolve_workers(None, default=2) == 2
        assert resolve_workers(0) == 1      # explicit floor

    def test_host_shard_disjoint_union(self):
        files = [f"f{i}" for i in range(10)]
        parts = [host_shard(files, index=i, count=3) for i in range(3)]
        seen = [f for p in parts for f in p]
        assert sorted(seen) == sorted(files)
        assert len(set(seen)) == len(files)
        # deterministic per (index, count)
        assert parts[1] == host_shard(files, index=1, count=3)
        with pytest.raises(ValueError, match="no shards"):
            host_shard(files[:2], index=2, count=3)


def _write_corpus(tmp_path, n_files=6, per_file=40, dim=8, seed=0):
    rs = np.random.RandomState(seed)
    for s in range(n_files):
        recs = []
        for i in range(per_file):
            uid = s * per_file + i
            recs.append(tfr.encode_example({
                "x": rs.randn(dim).astype(np.float32),
                "uid": np.asarray([uid], np.int64),
                "y": np.asarray([uid % 2], np.float32)}))
        tfr.write_tfrecord(str(tmp_path / f"part-{s:05d}.tfrecord"), recs)
    return str(tmp_path / "part-*.tfrecord")


def _parse(ex):
    return (np.concatenate([np.asarray(ex["x"], np.float32),
                            np.asarray(ex["uid"], np.float32)]),
            np.asarray(ex["y"], np.float32))


def _stream(pattern, workers, seed=0, batch=16, shuffle_buffer=64):
    ds = TPUDataset.from_tfrecord(pattern, _parse, batch_size=batch,
                                  shuffle_buffer=shuffle_buffer,
                                  pipeline_workers=workers)
    return list(ds.iter_train(data_parallel=1, seed=seed))


class TestDeterminism:
    def test_bitwise_identical_batches_workers_1_vs_4(self, tmp_path):
        pattern = _write_corpus(tmp_path)
        a = _stream(pattern, workers=1, seed=3)
        b = _stream(pattern, workers=4, seed=3)
        assert len(a) == len(b) > 0
        for (xa, ya, ra), (xb, yb, rb) in zip(a, b):
            assert ra == rb
            assert xa.dtype == xb.dtype
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)

    def test_stream_is_pure_function_of_seed_epoch(self, tmp_path):
        pattern = _write_corpus(tmp_path)
        a = _stream(pattern, workers=4, seed=5)
        b = _stream(pattern, workers=4, seed=5)
        c = _stream(pattern, workers=4, seed=6)
        for (xa, *_), (xb, *_) in zip(a, b):
            np.testing.assert_array_equal(xa, xb)
        assert any(not np.array_equal(xa, xc)
                   for (xa, *_), (xc, *_) in zip(a, c))

    def test_pipeline_fit_losses_match_in_memory_bitwise(self, tmp_path):
        """The acceptance claim: a pipeline-fed fit and an in-memory-fed
        fit seeing the SAME batch order produce bitwise-identical
        losses — the pipeline changes where batches come from, never
        what the optimizer sees."""
        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        from analytics_zoo_tpu.learn import trainer

        zoo.init_orca_context(cluster_mode="local")
        try:
            pattern = _write_corpus(tmp_path, n_files=4, per_file=32)
            ds = TPUDataset.from_tfrecord(pattern, _parse, batch_size=16,
                                          shuffle_buffer=64,
                                          pipeline_workers=4)
            epochs = 2
            # replay source: the SAME (seed, epoch) batch stream,
            # materialized to in-memory arrays up front
            cached = {e: _stream(pattern, workers=1, seed=e)
                      for e in range(epochs)}

            def make_model():
                m = Sequential([
                    L.Dense(8, input_shape=(9,), activation="relu"),
                    L.Dense(1, activation="sigmoid")])
                m.compile("adam", "binary_crossentropy")
                return m

            h_mem = trainer.fit_keras(
                make_model(), None, None, batch_size=16, epochs=epochs,
                seed=0, device_cache=False,
                batch_iter_factory=lambda e: iter(cached[e]))
            h_pipe = trainer.fit_keras(
                make_model(), None, None, batch_size=16, epochs=epochs,
                seed=0, device_cache=False,
                batch_iter_factory=lambda e: ds.iter_train(1, seed=e))
            assert h_mem["loss"] == h_pipe["loss"], \
                (h_mem["loss"], h_pipe["loss"])
        finally:
            zoo.stop_orca_context()


class TestDecodeBatchParity:
    def test_vectorized_decode_matches_per_record(self):
        payloads = []
        rs = np.random.RandomState(0)
        for i in range(7):
            feats = {
                "f": rs.randn(5).astype(np.float32),
                "i": np.asarray([i, -i, (1 << 62) + i, -(1 << 40)],
                                np.int64),
                "b": b"blob-%d" % i,
            }
            if i % 3 == 0:          # ragged + missing columns
                feats["ragged"] = np.arange(i + 1, dtype=np.int64)
            payloads.append(tfr.encode_example(feats))
        batch = tfr.decode_example_batch(payloads)
        singles = [tfr.decode_example(p) for p in payloads]
        assert len(batch) == len(singles)
        for got, want in zip(batch, singles):
            assert set(got) == set(want)
            for k in want:
                if isinstance(want[k], list):
                    assert got[k] == want[k]
                else:
                    assert got[k].dtype == want[k].dtype
                    np.testing.assert_array_equal(got[k], want[k])

    def test_empty_batch(self):
        assert tfr.decode_example_batch([]) == []


class TestCorruptTail:
    def _truncate_last_frame(self, path, cut=5):
        blob = open(path, "rb").read()
        open(path, "wb").write(blob[:-cut])

    def test_torn_tail_names_file_and_offset(self, tmp_path):
        pattern = _write_corpus(tmp_path, n_files=3, per_file=20)
        bad = str(tmp_path / "part-00002.tfrecord")
        self._truncate_last_frame(bad)
        with pytest.raises(ValueError) as ei:
            _stream(pattern, workers=4, shuffle_buffer=1)
        msg = str(ei.value)
        assert "part-00002.tfrecord" in msg
        assert "offset" in msg
        assert "truncated" in msg

    def test_torn_tail_not_a_silent_short_epoch(self, tmp_path):
        """Batches from intact files may arrive, but the stream must
        END in the error — never quietly drop the torn shard."""
        pattern = _write_corpus(tmp_path, n_files=3, per_file=20)
        self._truncate_last_frame(str(tmp_path / "part-00001.tfrecord"))
        ds = TPUDataset.from_tfrecord(pattern, _parse, batch_size=4,
                                      shuffle=False, pipeline_workers=4)
        with pytest.raises(ValueError, match="offset"):
            for _ in ds.iter_train(1):
                pass

    def test_corrupt_mid_frame_crc_names_offset(self, tmp_path):
        pattern = _write_corpus(tmp_path, n_files=1, per_file=10)
        path = str(tmp_path / "part-00000.tfrecord")
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2 // 4 * 4 + 1] ^= 0xFF   # somewhere mid-file
        open(path, "wb").write(bytes(blob))
        with pytest.raises(ValueError) as ei:
            list(tfr.read_records(path, verify_payload=True))
        msg = str(ei.value)
        assert path in msg and ("offset" in msg or "CRC" in msg)

    def test_native_and_python_streams_identical(self, tmp_path):
        if tfr._native_lib() is None:
            pytest.skip("no compiler for the native scanner")
        pattern = _write_corpus(tmp_path, n_files=3, per_file=25)
        native = _stream(pattern, workers=4, seed=1)
        import analytics_zoo_tpu.data.tfrecord as mod
        saved = mod._native
        mod._native, mod._native_failed = None, True
        try:
            python = _stream(pattern, workers=4, seed=1)
        finally:
            mod._native, mod._native_failed = saved, False
        assert len(native) == len(python) > 0
        for (xa, ya, _), (xb, yb, _) in zip(native, python):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)


class TestBoundedMemory:
    def test_streaming_footprint_stays_bounded(self, tmp_path):
        """16 shards × ~3 MB stream through 2 workers: the resident
        high-water mark obeys workers+slack, and host RSS never grows
        by anything near the corpus size (the corpus is NOT
        materialized)."""
        rows, row_bytes = 12, 256 * 1024
        n_files = 16
        for s in range(n_files):
            recs = [tfr.encode_example({
                "x": (b"\x01" * row_bytes),
                "y": np.asarray([float(s)], np.float32)})
                for _ in range(rows)]
            tfr.write_tfrecord(str(tmp_path / f"big-{s:02d}.tfrecord"),
                               recs)
        corpus_bytes = n_files * rows * row_bytes        # ~48 MB

        def parse(ex):
            return (np.frombuffer(ex["x"][0], np.uint8)[:64]
                    .astype(np.float32),
                    np.asarray(ex["y"], np.float32))

        def rss():
            with open("/proc/self/status") as fh:
                for line in fh:
                    if line.startswith("VmRSS"):
                        return int(line.split()[1]) * 1024
            return 0

        ds = TPUDataset.from_tfrecord(
            str(tmp_path / "big-*.tfrecord"), parse, batch_size=8,
            shuffle_buffer=16, pipeline_workers=2)
        peak = {"v": rss()}
        before = peak["v"]
        stop = threading.Event()

        def sample():
            while not stop.wait(0.005):
                peak["v"] = max(peak["v"], rss())

        t = threading.Thread(target=sample, daemon=True)
        t.start()
        from analytics_zoo_tpu.data.pipeline import ShardPipeline as SP
        seen = sum(real for _, _, real in ds.iter_train(1, seed=0))
        stop.set()
        t.join(timeout=2)
        assert seen > 0
        growth = peak["v"] - before
        assert growth < corpus_bytes * 0.6, \
            f"RSS grew {growth / 1e6:.1f} MB streaming a " \
            f"{corpus_bytes / 1e6:.0f} MB corpus — not bounded"

    def test_single_worker_streams_chunkwise_not_whole_file(self,
                                                            tmp_path):
        """workers<=1 must keep the class's original contract: a corpus
        stored as ONE giant file streams a decode-chunk at a time, not
        as a fully-materialized sample list."""
        recs = [tfr.encode_example({"v": np.asarray([i], np.int64)})
                for i in range(600)]           # > _DECODE_CHUNK (256)
        tfr.write_tfrecord(str(tmp_path / "one.tfrecord"), recs)
        calls = {"n": 0}

        def parse(ex):
            calls["n"] += 1
            return np.asarray(ex["v"], np.float32), None

        ds = TPUDataset.from_tfrecord(str(tmp_path / "one.tfrecord"),
                                      parse, batch_size=4, shuffle=False,
                                      pipeline_workers=1)
        stream = ds._iter_samples(np.random.RandomState(0), ordered=True)
        next(stream)
        assert calls["n"] <= ds._DECODE_CHUNK, \
            f"{calls['n']} samples parsed for one consumed — whole " \
            "file materialized"
        stream.close()

    def test_pipeline_high_water_mark(self, tmp_path):
        pattern = _write_corpus(tmp_path, n_files=12, per_file=10)
        from analytics_zoo_tpu.data import pipeline as pl
        captured = {}
        orig = pl.ShardPipeline

        class Spy(orig):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                captured["pipe"] = self

        pl.ShardPipeline = Spy
        try:
            _stream(pattern, workers=3)
        finally:
            pl.ShardPipeline = orig
        pipe = captured["pipe"]
        assert pipe.max_resident <= pipe.workers + 1

    def test_one_giant_file_splits_into_bounded_record_ranges(self,
                                                              tmp_path):
        """A single-file corpus at workers>1 must NOT become one
        whole-file shard: the header index splits it into
        _SHARD_RECORDS ranges, so residency is bounded ranges and the
        pool still parallelizes."""
        recs = [tfr.encode_example({"v": np.asarray([i], np.int64)})
                for i in range(3000)]
        tfr.write_tfrecord(str(tmp_path / "one.tfrecord"), recs)

        def parse(ex):
            return np.asarray(ex["v"], np.float32), None

        ds = TPUDataset.from_tfrecord(str(tmp_path / "one.tfrecord"),
                                      parse, batch_size=8, shuffle=False,
                                      pipeline_workers=4)
        from analytics_zoo_tpu.data import pipeline as pl
        captured = {}
        orig = pl.ShardPipeline

        class Spy(orig):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                captured["pipe"] = self

        pl.ShardPipeline = Spy
        try:
            order = [int(v) for xb, _, _ in ds.iter_train(1)
                     for v in xb[:, 0]]
        finally:
            pl.ShardPipeline = orig
        assert order == list(range(3000 - 3000 % 8))
        pipe = captured["pipe"]
        assert len(pipe._shards) == -(-3000 // ds._SHARD_RECORDS)
        assert pipe.max_resident <= pipe.workers + 1

    def test_explicit_num_workers_wins_over_ambient_config(self,
                                                           tmp_path):
        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.common.context import get_context
        pattern = _write_corpus(tmp_path, n_files=2, per_file=4)
        zoo.init_orca_context(cluster_mode="local")
        try:
            cfg = get_context().config
            saved = getattr(cfg, "pipeline_workers", 0)
            cfg.pipeline_workers = 2
            try:
                legacy = TPUDataset.from_tfrecord(pattern, _parse,
                                                  num_workers=8)
                assert legacy._workers() == 8
                # explicit 1 = opting OUT of decode threads: config
                # must not override that either
                pinned = TPUDataset.from_tfrecord(pattern, _parse,
                                                  num_workers=1)
                assert pinned._workers() == 1
                explicit = TPUDataset.from_tfrecord(pattern, _parse,
                                                    num_workers=8,
                                                    pipeline_workers=3)
                assert explicit._workers() == 3
                ambient = TPUDataset.from_tfrecord(pattern, _parse)
                assert ambient._workers() == 2
            finally:
                cfg.pipeline_workers = saved
        finally:
            zoo.stop_orca_context()


class TestReaders:
    def test_read_csv_parallel_matches_sequential(self, tmp_path):
        import pandas as pd
        from analytics_zoo_tpu.data import readers
        for i in range(6):
            pd.DataFrame({"a": np.arange(5) + i,
                          "b": np.arange(5) * i}).to_csv(
                str(tmp_path / f"f{i}.csv"), index=False)
        seq = readers.read_csv(str(tmp_path), pipeline_workers=1).collect()
        par = readers.read_csv(str(tmp_path), pipeline_workers=4).collect()
        assert len(seq) == len(par) == 6
        for a, b in zip(seq, par):
            pd.testing.assert_frame_equal(a, b)

    def test_read_csv_error_names_file(self, tmp_path):
        import pandas as pd
        from analytics_zoo_tpu.data import readers
        pd.DataFrame({"a": [1]}).to_csv(str(tmp_path / "good.csv"),
                                        index=False)
        (tmp_path / "broken.csv").write_text("")   # EmptyDataError
        with pytest.raises(Exception, match="broken.csv"):
            readers.read_csv(str(tmp_path), pipeline_workers=4)

    def test_read_json_parallel(self, tmp_path):
        import pandas as pd
        from analytics_zoo_tpu.data import readers
        for i in range(3):
            pd.DataFrame({"v": [i, i + 1]}).to_json(
                str(tmp_path / f"f{i}.json"))
        shards = readers.read_json(str(tmp_path),
                                   pipeline_workers=3).collect()
        assert [int(s["v"].iloc[0]) for s in shards] == [0, 1, 2]

    def test_feature_set_batches_pipeline_invariant(self):
        from analytics_zoo_tpu.data.feature_set import FeatureSet
        rs = np.random.RandomState(0)
        data = {"x": rs.randn(64, 4).astype(np.float32),
                "y": rs.randint(0, 2, 64).astype(np.int32)}
        fs = FeatureSet(data)
        a = list(fs.iter_batches(8, shuffle=True, seed=2, native=False,
                                 pipeline_workers=1))
        b = list(fs.iter_batches(8, shuffle=True, seed=2, native=False,
                                 pipeline_workers=4))
        assert len(a) == len(b) == 8
        for ba, bb in zip(a, b):
            np.testing.assert_array_equal(ba["x"], bb["x"])
            np.testing.assert_array_equal(ba["y"], bb["y"])


class TestStallAccounting:
    def test_input_wait_and_bound_publish(self, tmp_path):
        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        from analytics_zoo_tpu.learn import trainer
        from analytics_zoo_tpu.observability import get_registry

        zoo.init_orca_context(cluster_mode="local")
        try:
            pattern = _write_corpus(tmp_path, n_files=4, per_file=32)
            ds = TPUDataset.from_tfrecord(pattern, _parse, batch_size=16,
                                          pipeline_workers=2)
            model = Sequential([
                L.Dense(4, input_shape=(9,), activation="relu"),
                L.Dense(1, activation="sigmoid")])
            model.compile("adam", "binary_crossentropy")
            trainer.fit_keras(
                model, None, None, batch_size=16, epochs=1, seed=0,
                batch_iter_factory=lambda e: ds.iter_train(1, seed=e))
            reg = get_registry()
            wait = reg.get("training_input_wait_ms")
            assert wait is not None
            assert wait.snapshot()["series"], \
                "no input-wait samples recorded"
            bound = reg.get("training_input_bound").value()
            assert 0.0 <= bound <= 1.0
        finally:
            zoo.stop_orca_context()

    def test_in_memory_fit_reads_not_input_bound(self):
        import analytics_zoo_tpu as zoo
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        from analytics_zoo_tpu.learn import trainer
        from analytics_zoo_tpu.observability import get_registry

        zoo.init_orca_context(cluster_mode="local")
        try:
            rs = np.random.RandomState(0)
            x = rs.randn(64, 6).astype(np.float32)
            y = (x.sum(axis=1, keepdims=True) > 0).astype(np.float32)
            model = Sequential([
                L.Dense(4, input_shape=(6,), activation="relu"),
                L.Dense(1, activation="sigmoid")])
            model.compile("adam", "binary_crossentropy")
            trainer.fit_keras(model, x, y, batch_size=16, epochs=2,
                              device_cache=True, seed=0)
            # device-cache epochs never touch a prefetch queue: the
            # gauge must read 0, not a stale streaming value
            assert get_registry().get(
                "training_input_bound").value() == 0.0
        finally:
            zoo.stop_orca_context()


class TestMetricNameLint:
    def test_new_families_required(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "check_metric_names",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
                "scripts", "check_metric_names.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.REQUIRED.get("training_input_wait_ms") == "histogram"
        assert mod.REQUIRED.get("training_input_bound") == "gauge"
