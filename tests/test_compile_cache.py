"""Persistent compilation cache (ISSUE 4): AOT executable round trips
through the disk store, key invalidation on dtype/bucket/placement
change, corruption tolerance (degrade to recompile, never raise), LRU
eviction under a byte budget, registry telemetry, the replicated
persist-once/load-N path, the `compile_cache_size` fix, config
validation, the maintenance tool, and the trainer's AOT re-run path.

All on tmp_path + the conftest 8-device CPU mesh; tier-1 fast."""

import json
import os
import time

import numpy as np
import pytest

import analytics_zoo_tpu.compile_cache.serialization as ccser
from analytics_zoo_tpu.compile_cache import (CompileCache, abstract_signature,
                                             make_key)
from analytics_zoo_tpu.keras import Sequential
from analytics_zoo_tpu.keras import layers as L
from analytics_zoo_tpu.observability.registry import MetricsRegistry
from analytics_zoo_tpu.serving.inference_model import InferenceModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def make_model(in_dim=4, out_dim=3):
    m = Sequential([L.Dense(out_dim, input_shape=(in_dim,))])
    m.ensure_built(np.zeros((1, in_dim), np.float32))
    return m


@pytest.fixture()
def compile_spy(monkeypatch):
    """Counts every fresh AOT compile; the zero-compile assertions."""
    calls = []
    orig = ccser.compile_lowered

    def spy(lowered):
        calls.append(1)
        return orig(lowered)

    monkeypatch.setattr(ccser, "compile_lowered", spy)
    return calls


class TestRoundTrip:
    def test_warm_model_zero_compiles_bitwise_equal(self, tmp_path,
                                                    compile_spy):
        model = make_model()
        reg1, reg2 = MetricsRegistry(), MetricsRegistry()
        buckets = [1, 2, 4, 8]
        im1 = InferenceModel(
            compile_cache=CompileCache(str(tmp_path), registry=reg1)
        ).load_keras(model)
        im1.warmup(np.zeros((4,), np.float32), buckets=buckets)
        assert set(im1.warmup_source.values()) == {"compiled"}
        assert len(compile_spy) == len(buckets)
        assert reg1.get("compile_cache_misses_total").value() == len(buckets)

        x = np.random.RandomState(0).randn(5, 4).astype(np.float32)
        p1 = im1.predict(x)

        # "restart": fresh model object, fresh cache handle, same dir
        compile_spy.clear()
        im2 = InferenceModel(
            compile_cache=CompileCache(str(tmp_path), registry=reg2)
        ).load_keras(model)
        im2.warmup(np.zeros((4,), np.float32), buckets=buckets)
        assert len(compile_spy) == 0, "cache-warm warmup must not compile"
        assert set(im2.warmup_source.values()) == {"cached"}
        assert reg2.get("compile_cache_hits_total").value() == len(buckets)
        assert reg2.get("compile_cache_misses_total").value() == 0
        p2 = im2.predict(x)
        assert np.array_equal(p1, p2), \
            "deserialized executable must be bitwise-identical"

    def test_unwarmed_bucket_still_serves(self, tmp_path):
        model = make_model()
        im = InferenceModel(
            compile_cache=CompileCache(str(tmp_path),
                                       registry=MetricsRegistry())
        ).load_keras(model)
        im.warmup(np.zeros((4,), np.float32), buckets=[4])
        # a bucket warmup never touched falls back to the jit path
        out = im.predict(np.ones((16, 4), np.float32))
        assert out.shape == (16, 3)

    def test_warmup_report_and_source_keys_align(self, tmp_path):
        model = make_model()
        im = InferenceModel(
            compile_cache=CompileCache(str(tmp_path),
                                       registry=MetricsRegistry())
        ).load_keras(model)
        im.warmup(np.zeros((4,), np.float32), buckets=[1, 2])
        assert set(im.warmup_report) == set(im.warmup_source) \
            == {"4:b1", "4:b2"}
        # without a cache the source map still exists, marked jit
        im2 = InferenceModel().load_keras(model)
        im2.warmup(np.zeros((4,), np.float32), buckets=[1, 2])
        assert set(im2.warmup_source.values()) == {"jit"}


class TestKeyInvalidation:
    def _warm(self, tmp_path, reg, dtype=np.float32, buckets=(4,),
              **model_kw):
        im = InferenceModel(
            compile_cache=CompileCache(str(tmp_path), registry=reg),
            **model_kw).load_fn(lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), dtype), buckets=list(buckets))
        return im

    def test_dtype_change_misses(self, tmp_path):
        reg = MetricsRegistry()
        self._warm(tmp_path, reg, dtype=np.float32)
        assert reg.get("compile_cache_misses_total").value() == 1
        self._warm(tmp_path, reg, dtype=np.int32)
        # int32 input is a different program: miss, not a wrong hit
        assert reg.get("compile_cache_misses_total").value() == 2
        self._warm(tmp_path, reg, dtype=np.float32)
        assert reg.get("compile_cache_hits_total").value() == 1

    def test_bucket_is_its_own_entry(self, tmp_path):
        reg = MetricsRegistry()
        im = self._warm(tmp_path, reg, buckets=(2, 4))
        assert im.compile_cache.stats()["entries"] == 2
        # warming only a NEW bucket misses even with the others cached
        self._warm(tmp_path, reg, buckets=(8,))
        assert reg.get("compile_cache_misses_total").value() == 3

    def test_placement_change_misses(self, tmp_path, devices8):
        reg = MetricsRegistry()
        self._warm(tmp_path, reg, buckets=(8,))
        misses0 = reg.get("compile_cache_misses_total").value()
        im = self._warm(tmp_path, reg, buckets=(8,), placement="sharded")
        assert im.placement == "sharded"
        # a GSPMD executable for the mesh never hits a single-device key
        assert reg.get("compile_cache_misses_total").value() == misses0 + 1

    def test_model_change_misses(self, tmp_path):
        reg = MetricsRegistry()
        cc = CompileCache(str(tmp_path), registry=reg)
        im1 = InferenceModel(compile_cache=cc).load_fn(
            lambda p, x: x * p, np.float32(2.0))
        im1.warmup(np.zeros((3,), np.float32), buckets=[4])
        im2 = InferenceModel(compile_cache=cc).load_fn(
            lambda p, x: x + p, np.float32(2.0))
        im2.warmup(np.zeros((3,), np.float32), buckets=[4])
        assert reg.get("compile_cache_hits_total").value() == 0
        assert reg.get("compile_cache_misses_total").value() == 2


class TestCorruption:
    def _one_entry(self, tmp_path, reg):
        im = InferenceModel(
            compile_cache=CompileCache(str(tmp_path), registry=reg)
        ).load_fn(lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), np.float32), buckets=[4])
        files = [f for f in os.listdir(tmp_path) if f.endswith(".aotc")]
        assert len(files) == 1
        return os.path.join(str(tmp_path), files[0])

    def test_truncated_entry_degrades_to_recompile(self, tmp_path,
                                                   compile_spy):
        reg = MetricsRegistry()
        path = self._one_entry(tmp_path, reg)
        with open(path, "r+b") as fh:
            fh.truncate(os.path.getsize(path) // 2)
        compile_spy.clear()
        im = InferenceModel(
            compile_cache=CompileCache(str(tmp_path), registry=reg)
        ).load_fn(lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), np.float32), buckets=[4])   # no raise
        assert im.warmup_source == {"3:b4": "compiled"}
        assert len(compile_spy) == 1
        out = im.predict(np.ones((4, 3), np.float32))
        np.testing.assert_array_equal(out, np.full((4, 3), 2.0))

    def test_garbage_bytes_degrade(self, tmp_path):
        reg = MetricsRegistry()
        path = self._one_entry(tmp_path, reg)
        with open(path, "wb") as fh:
            fh.write(b"\x00garbage" * 100)
        cc = CompileCache(str(tmp_path), registry=reg)
        key = make_key("serving", "whatever",
                       abstract_signature((np.zeros((4, 3), np.float32),)))
        assert cc.load(key) is None                  # never an exception
        # the corrupt file the digest DOES name also degrades silently
        im = InferenceModel(compile_cache=cc).load_fn(
            lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), np.float32), buckets=[4])
        assert im.warmup_source["3:b4"] == "compiled"

    def test_format_version_mismatch_degrades(self, tmp_path):
        import struct
        reg = MetricsRegistry()
        path = self._one_entry(tmp_path, reg)
        blob = bytearray(open(path, "rb").read())
        struct.pack_into("<I", blob, 4, 99)      # a future format version
        open(path, "wb").write(bytes(blob))
        im = InferenceModel(
            compile_cache=CompileCache(str(tmp_path), registry=reg)
        ).load_fn(lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), np.float32), buckets=[4])   # no raise
        assert im.warmup_source["3:b4"] == "compiled"

    def test_flipped_payload_bit_fails_crc(self, tmp_path):
        reg = MetricsRegistry()
        path = self._one_entry(tmp_path, reg)
        blob = bytearray(open(path, "rb").read())
        blob[-1] ^= 0xFF                         # flip one payload bit
        open(path, "wb").write(bytes(blob))
        cc = CompileCache(str(tmp_path), registry=reg)
        im = InferenceModel(compile_cache=cc).load_fn(
            lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), np.float32), buckets=[4])
        assert im.warmup_source["3:b4"] == "compiled"
        assert reg.get("compile_cache_misses_total").value() >= 2

    def test_intact_entry_that_fails_to_deserialize_is_not_a_miss(
            self, tmp_path, monkeypatch):
        """The seed's failure mode: every entry was intact and every
        `unpack` raised (jax API drift), so a warm cache read as cold.
        That must show in its own counter, not in `misses`."""
        reg = MetricsRegistry()

        def fn(p, x):       # ONE function: its source location is keyed
            return x * p

        def warm():
            cc = CompileCache(str(tmp_path), registry=reg)
            im = InferenceModel(compile_cache=cc).load_fn(
                fn, np.float32(2.0))
            im.warmup(np.zeros((3,), np.float32), buckets=[4])
            return cc, im
        warm()
        misses = reg.get("compile_cache_misses_total").value()

        def boom(payload, target_device_id=None):
            raise TypeError("deserialize_executable() got an unexpected "
                            "keyword")
        monkeypatch.setattr(ccser, "unpack", boom)
        cc, im = warm()                                      # no raise
        assert im.warmup_source["3:b4"] == "compiled"
        assert reg.get("compile_cache_load_errors_total").value() == 1
        assert reg.get("compile_cache_misses_total").value() == misses
        assert cc.stats()["load_errors"] == 1


class TestEviction:
    def test_lru_eviction_under_tiny_budget(self, tmp_path):
        reg = MetricsRegistry()
        # learn one entry's size, then budget for ~2
        probe = CompileCache(str(tmp_path / "probe"), registry=reg)
        im = InferenceModel(compile_cache=probe).load_fn(
            lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), np.float32), buckets=[1])
        entry_bytes = probe.stats()["bytes"]
        assert entry_bytes > 0

        cc = CompileCache(str(tmp_path / "lru"),
                          max_bytes=int(entry_bytes * 2.5), registry=reg)
        im = InferenceModel(compile_cache=cc).load_fn(
            lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), np.float32), buckets=[1, 2, 4, 8])
        st = cc.stats()
        assert st["bytes"] <= int(entry_bytes * 2.5)
        assert 1 <= st["entries"] <= 2
        # the SURVIVORS are the most recently written (LRU evicts oldest)
        digests = {e["digest"] for e in cc.index()}
        sig8 = abstract_signature(np.zeros((8, 3), np.float32))
        assert im._cache_key(sig8).digest in digests

    def test_prune_and_clear(self, tmp_path):
        reg = MetricsRegistry()
        cc = CompileCache(str(tmp_path), registry=reg)
        im = InferenceModel(compile_cache=cc).load_fn(
            lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), np.float32), buckets=[1, 2, 4])
        assert cc.stats()["entries"] == 3
        cc.prune(max_bytes=cc.stats()["bytes"] - 1)
        assert cc.stats()["entries"] == 2
        cc.clear()
        assert cc.stats()["entries"] == 0
        assert reg.get("compile_cache_bytes").value() == 0


class TestReplicated:
    def test_persist_once_load_n(self, tmp_path, devices8, compile_spy):
        model = make_model()
        reg = MetricsRegistry()
        cc = CompileCache(str(tmp_path), registry=reg)
        im = InferenceModel(num_replicas=2, compile_cache=cc
                            ).load_keras(model)
        im.warmup(np.zeros((4,), np.float32), buckets=[4])
        # ONE disk entry; replica 0 compiled it, replica 1 loaded it
        assert cc.stats()["entries"] == 1
        assert im.warmup_source == {"r0:4:b4": "compiled",
                                    "r1:4:b4": "cached"}
        assert len(compile_spy) == 1
        x = np.random.RandomState(1).randn(4, 4).astype(np.float32)
        p_pool = im.predict(x)
        im.close()

        # fresh pool restart: every (replica, bucket) loads, zero compiles
        compile_spy.clear()
        reg2 = MetricsRegistry()
        im2 = InferenceModel(
            num_replicas=2,
            compile_cache=CompileCache(str(tmp_path), registry=reg2)
        ).load_keras(model)
        im2.warmup(np.zeros((4,), np.float32), buckets=[4])
        assert len(compile_spy) == 0
        assert set(im2.warmup_source.values()) == {"cached"}
        assert reg2.get("compile_cache_hits_total").value() == 2
        # both replicas produce the persisted program's exact output
        for _ in range(4):       # router alternates replicas
            assert np.array_equal(im2.predict(x), p_pool)
        im2.close()

    def test_compile_cache_size_counts_per_replica(self, devices8):
        """Satellite: replicated placement reports per-(replica, bucket)
        executables instead of -1."""
        model = make_model()
        im = InferenceModel(num_replicas=2).load_keras(model)
        im.warmup(np.zeros((4,), np.float32), buckets=[1, 2])
        n = im.compile_cache_size()
        assert n == 4, f"2 replicas x 2 buckets must count 4, got {n}"
        im.close()

    def test_metrics_surfaces_executable_count(self, tmp_path):
        from analytics_zoo_tpu.serving.broker import MemoryBroker
        from analytics_zoo_tpu.serving.server import ClusterServing
        model = make_model()
        im = InferenceModel(
            compile_cache=CompileCache(str(tmp_path),
                                       registry=MetricsRegistry())
        ).load_keras(model)
        im.warmup(np.zeros((4,), np.float32), buckets=[1, 2, 4])
        serving = ClusterServing(im, broker=MemoryBroker(),
                                 registry=MetricsRegistry())
        m = serving.metrics()
        assert m["compile_cache"]["executables"] == 3
        assert m["compile_cache"]["entries"] == 3
        assert m["compile_cache"]["misses"] == 3
        assert m["compile_cache"]["warmup_source"]["4:b1"] == "compiled"


class TestRegistryTelemetry:
    def test_all_five_families_populate(self, tmp_path):
        reg = MetricsRegistry()
        cc = CompileCache(str(tmp_path), registry=reg)
        im = InferenceModel(compile_cache=cc).load_fn(
            lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), np.float32), buckets=[4])      # miss
        im2 = InferenceModel(compile_cache=cc).load_fn(
            lambda p, x: x * p, np.float32(2.0))
        im2.warmup(np.zeros((3,), np.float32), buckets=[4])     # hit
        snap = reg.snapshot()
        assert snap["compile_cache_hits_total"]["series"][0]["value"] == 1
        assert snap["compile_cache_misses_total"]["series"][0]["value"] == 1
        assert snap["compile_cache_load_ms"]["series"][0]["count"] == 1
        assert snap["compile_cache_compile_ms"]["series"][0]["count"] == 1
        assert snap["compile_cache_bytes"]["series"][0]["value"] \
            == cc.stats()["bytes"] > 0


class TestConfigValidation:
    def _load(self, tmp_path, params_lines):
        from analytics_zoo_tpu.serving.config import ServingConfig
        cfg = tmp_path / "config.yaml"
        cfg.write_text("model:\n  path: /tmp/nope\nparams:\n"
                       + "".join(f"  {ln}\n" for ln in params_lines))
        return ServingConfig.load(str(cfg))

    def test_cache_dir_parses_with_budget(self, tmp_path):
        cfg = self._load(tmp_path, ["compile_cache_dir: /tmp/zoo-cc",
                                    "compile_cache_max_bytes: 512M"])
        assert cfg.compile_cache_dir == "/tmp/zoo-cc"
        assert cfg.compile_cache_max_bytes == 512 << 20

    def test_bad_path_rejected(self, tmp_path):
        not_a_dir = tmp_path / "somefile"
        not_a_dir.write_text("x")
        with pytest.raises(ValueError, match="not a directory"):
            self._load(tmp_path, [f"compile_cache_dir: {not_a_dir}"])

    def test_non_positive_budget_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="positive"):
            self._load(tmp_path, ["compile_cache_dir: /tmp/zoo-cc",
                                  "compile_cache_max_bytes: 0"])
        with pytest.raises(ValueError, match="positive"):
            self._load(tmp_path, ["compile_cache_dir: /tmp/zoo-cc",
                                  "compile_cache_max_bytes: -5"])

    def test_budget_without_dir_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="compile_cache_dir"):
            self._load(tmp_path, ["compile_cache_max_bytes: 1024"])

    def test_build_model_wires_cache_from_config(self, tmp_path):
        """YAML → ServingConfig → build_model: the InferenceModel comes
        back cache-backed and a rebuilt "process" warms from disk. The
        layer-naming scope is reset per build to simulate the fresh
        processes a real restart gets (mid-scope counter offsets that
        flip lexicographic key order are a designed safe-miss)."""
        from analytics_zoo_tpu.keras.engine import reset_name_scope
        from analytics_zoo_tpu.models.textclassification import \
            TextClassifier
        from analytics_zoo_tpu.serving.config import ServingConfig
        reset_name_scope()
        m = TextClassifier(class_num=2, vocab_size=30, embedding_dim=8,
                           sequence_length=6)
        m.model.ensure_built(np.zeros((1, 6), np.int32))
        m.save_model(str(tmp_path / "tc"))
        cfg_file = tmp_path / "c.yaml"
        cfg_file.write_text(
            f"model:\n  path: {tmp_path / 'tc'}\n"
            f"params:\n  compile_cache_dir: {tmp_path / 'cc'}\n"
            "  compile_cache_max_bytes: 64M\n")
        x = np.arange(3 * 6).reshape(3, 6).astype(np.int32) % 30
        outs = []
        for expect in ("compiled", "cached"):
            reset_name_scope()               # fresh-process naming
            im = ServingConfig.load(str(cfg_file)).build_model()
            assert im.compile_cache is not None
            assert im.compile_cache.max_bytes == 64 << 20
            im.warmup(np.zeros((6,), np.int32), buckets=[4])
            assert im.warmup_source["6:b4"] == expect
            outs.append(im.predict(x))
        assert np.array_equal(outs[0], outs[1])

    def test_counter_offset_hits_with_retree_adapter(self, tmp_path):
        """A mid-scope rebuild shifts every auto layer name
        ("dense_1" → "dense_2"); the canonical key still hits and the
        retree adapter maps the live params onto the stored tree —
        identical predictions, no recompile. (An offset that flips the
        sorted key order misses safely instead; small counters here
        cannot flip.)"""
        import jax
        from analytics_zoo_tpu.keras.engine import reset_name_scope
        reset_name_scope()
        reg = MetricsRegistry()
        cc = CompileCache(str(tmp_path), registry=reg)
        x = np.random.RandomState(0).randn(4, 4).astype(np.float32)
        m1 = make_model()
        im1 = InferenceModel(compile_cache=cc).load_keras(m1)
        im1.warmup(np.zeros((4,), np.float32), buckets=[4])
        assert im1.warmup_source["4:b4"] == "compiled"
        p1 = im1.predict(x)

        m2 = make_model()                    # names shifted, same arch
        assert list(m2.params) != list(m1.params), \
            "test premise: auto names must differ"
        # same weights, positionally (keys differ by the name shift)
        m2.params = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(m2.params),
            jax.tree_util.tree_leaves(m1.params))
        im2 = InferenceModel(compile_cache=cc).load_keras(m2)
        im2.warmup(np.zeros((4,), np.float32), buckets=[4])
        assert im2.warmup_source["4:b4"] == "cached"
        assert np.array_equal(im2.predict(x), p1)

    def test_cache_constructor_validates_too(self, tmp_path):
        with pytest.raises(ValueError):
            CompileCache(str(tmp_path), max_bytes=0,
                         registry=MetricsRegistry())
        f = tmp_path / "plainfile"
        f.write_text("x")
        with pytest.raises(ValueError):
            CompileCache(str(f), registry=MetricsRegistry())


class TestTool:
    def _populate(self, tmp_path):
        cc = CompileCache(str(tmp_path), registry=MetricsRegistry())
        im = InferenceModel(compile_cache=cc).load_fn(
            lambda p, x: x * p, np.float32(2.0))
        im.warmup(np.zeros((3,), np.float32), buckets=[1, 2, 4])
        return cc

    def test_ls_stats_prune_clear(self, tmp_path, capsys):
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts"))
        import compile_cache_tool as tool
        cc = self._populate(tmp_path)
        nbytes = cc.stats()["bytes"]

        assert tool.main(["ls", "--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "3 entries" in out and "serving" in out

        assert tool.main(["stats", "--dir", str(tmp_path)]) == 0
        import json
        st = json.loads(capsys.readouterr().out)
        assert st["entries"] == 3 and st["bytes"] == nbytes
        assert st["by_kind"]["serving"]["entries"] == 3

        assert tool.main(["prune", "--dir", str(tmp_path),
                          "--max-bytes", str(nbytes - 1)]) == 0
        capsys.readouterr()
        assert cc.total_bytes() < nbytes

        assert tool.main(["clear", "--dir", str(tmp_path)]) == 0
        capsys.readouterr()
        assert cc.total_bytes() == 0


@pytest.fixture()
def jax_cache_config():
    """`fit_keras(compile_cache_dir=...)` flips jax's global persistent-
    cache config (the fallback layer); restore it so later tests don't
    write XLA cache entries into a torn-down tmp dir."""
    import jax
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      prev_min)


class TestXlaCachePlacement:
    """JAX's own persistent cache (the layer under the .aotc store):
    whoever runs the process places it through JAX_COMPILATION_CACHE_DIR;
    otherwise it sits at one fixed path inside the checkout."""

    @pytest.fixture()
    def config_updates(self, monkeypatch, jax_cache_config):
        import jax
        seen = []
        orig = jax.config.update

        def spy(name, value):
            seen.append(name)
            return orig(name, value)
        monkeypatch.setattr(jax.config, "update", spy)
        return seen

    def _fit(self, cache_dir):
        from analytics_zoo_tpu.learn.trainer import fit_keras
        m = Sequential([L.Dense(2, input_shape=(4,))])
        m.compile("adam", "mse")
        fit_keras(m, np.zeros((8, 4), np.float32),
                  np.zeros((8, 2), np.float32), batch_size=8, epochs=1,
                  compile_cache_dir=cache_dir)

    def test_env_placement_is_never_overridden(self, tmp_path, monkeypatch,
                                               config_updates):
        import jax
        from analytics_zoo_tpu.common.context import init_orca_context
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        # what `import jax` does with the variable in a fresh process
        jax.config.update("jax_compilation_cache_dir", env_dir)
        config_updates.clear()
        init_orca_context()
        self._fit(str(tmp_path / "aotc"))
        assert "jax_compilation_cache_dir" not in config_updates
        assert jax.config.jax_compilation_cache_dir == env_dir
        # the .aotc store keeps its own, user-chosen directory
        assert any(f.endswith(".aotc")
                   for f in os.listdir(tmp_path / "aotc"))

    def test_default_is_one_fixed_dir_in_the_checkout(
            self, tmp_path, monkeypatch, config_updates):
        import tempfile
        import jax
        from analytics_zoo_tpu.common.context import init_orca_context
        from analytics_zoo_tpu.compile_cache import default_xla_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.chdir(tmp_path)              # not resolved from cwd
        init_orca_context()
        self._fit(str(tmp_path / "aotc"))
        want = os.path.join(REPO, ".xla_cache")
        assert default_xla_cache_dir() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert str(os.getpid()) not in want
        assert not want.startswith(tempfile.gettempdir())
        ignored = open(os.path.join(REPO, ".gitignore")).read().split()
        assert ".xla_cache/" in ignored


class TestTrainerAOT:
    def test_refit_after_cache_reset_zero_compiles(self, tmp_path,
                                                   compile_spy,
                                                   jax_cache_config):
        """Simulated trainer restart: the jitted step is rebuilt from
        scratch (the model's in-process step memo dropped), and the AOT
        cache supplies the executable without one fresh compile."""
        from analytics_zoo_tpu.learn.trainer import fit_keras
        m = Sequential([L.Dense(4, input_shape=(4,)), L.Dense(1)])
        m.compile(optimizer="sgd", loss="mse")
        x = np.random.RandomState(0).rand(32, 4).astype(np.float32)
        y = np.random.RandomState(1).rand(32, 1).astype(np.float32)
        h1 = fit_keras(m, x, y, batch_size=16, epochs=1,
                       distributed=False, device_cache=False,
                       compile_cache_dir=str(tmp_path))
        assert len(compile_spy) == 1
        step = m._train_cache[1]
        assert step.sources and \
            set(step.sources.values()) == {"compiled"}

        compile_spy.clear()
        m._train_cache = None                 # "restart": memo dropped
        h2 = fit_keras(m, x, y, batch_size=16, epochs=1,
                       distributed=False, device_cache=False,
                       compile_cache_dir=str(tmp_path))
        assert len(compile_spy) == 0, \
            "trainer re-run must load its step executable from disk"
        step2 = m._train_cache[1]
        assert set(step2.sources.values()) == {"cached"}
        assert np.isfinite(h2["loss"][0]) and np.isfinite(h1["loss"][0])

    def test_sharded_and_replicated_fits_never_collide(
            self, tmp_path, compile_spy, jax_cache_config):
        """ISSUE 7 satellite: the trainer AOT key folds in the mesh
        axis sizes + sharding-rule fingerprint. A replicated fit and an
        fsdp-sharded fit of the SAME model with IDENTICAL argument
        shapes are different programs — one cache dir must hold both
        (two compiles), and a sharded re-fit in a fresh process (step
        memo dropped) must load ITS entry with zero compiles."""
        from analytics_zoo_tpu.common import context as ctx_mod
        from analytics_zoo_tpu.learn.trainer import fit_keras
        prev = ctx_mod._GLOBAL["context"]
        try:
            ctx_mod.init_zoo_context(data=2, fsdp=4)
            import optax
            m = Sequential([L.Dense(8, input_shape=(4,)), L.Dense(4)])
            m.compile(optimizer=optax.sgd(1e-2), loss="mse")
            x = np.random.RandomState(0).rand(32, 4).astype(np.float32)
            y = np.random.RandomState(1).rand(32, 4).astype(np.float32)
            kw = dict(batch_size=16, epochs=1, device_cache=False,
                      prefetch=False, compile_cache_dir=str(tmp_path))

            fit_keras(m, x, y, sharding_rules=True, **kw)
            assert len(compile_spy) == 1
            m._train_cache = None
            fit_keras(m, x, y, **kw)               # replicated, same shapes
            assert len(compile_spy) == 2, \
                "replicated fit silently reused the sharded executable"
            m._train_cache = None
            compile_spy.clear()
            fit_keras(m, x, y, sharding_rules=True, **kw)
            assert len(compile_spy) == 0, \
                "cross-process sharded re-fit must compile nothing"
            assert set(m._train_cache[1].sources.values()) == {"cached"}
        finally:
            ctx_mod._GLOBAL["context"] = prev

    def test_mesh_factorization_is_part_of_the_key(
            self, tmp_path, compile_spy, jax_cache_config):
        """data=2×fsdp=4 and data=1×fsdp=8 cover the same 8 devices
        with the same arg shapes but different layouts: distinct
        entries."""
        from analytics_zoo_tpu.common import context as ctx_mod
        from analytics_zoo_tpu.learn.trainer import fit_keras
        prev = ctx_mod._GLOBAL["context"]
        try:
            import optax
            m = Sequential([L.Dense(8, input_shape=(4,)), L.Dense(4)])
            m.compile(optimizer=optax.sgd(1e-2), loss="mse")
            x = np.random.RandomState(0).rand(32, 4).astype(np.float32)
            y = np.random.RandomState(1).rand(32, 4).astype(np.float32)
            kw = dict(batch_size=16, epochs=1, device_cache=False,
                      prefetch=False, sharding_rules=True,
                      compile_cache_dir=str(tmp_path))
            ctx_mod.init_zoo_context(data=2, fsdp=4)
            fit_keras(m, x, y, **kw)
            n1 = len(compile_spy)
            assert n1 == 1
            m._train_cache = None
            ctx_mod.init_zoo_context(data=1, fsdp=8)
            fit_keras(m, x, y, **kw)
            assert len(compile_spy) == 2, \
                "a different mesh factorization hit the old entry"
        finally:
            ctx_mod._GLOBAL["context"] = prev

    def test_aot_step_matches_plain_jit(self, tmp_path, jax_cache_config):
        """Same data, same seed: a cache-backed fit reproduces the plain
        fit's losses exactly."""
        from analytics_zoo_tpu.learn.trainer import fit_keras

        def run(cache_dir):
            m = Sequential([L.Dense(4, input_shape=(4,)), L.Dense(1)])
            m.compile(optimizer="sgd", loss="mse")
            x = np.random.RandomState(0).rand(32, 4).astype(np.float32)
            y = np.random.RandomState(1).rand(32, 1).astype(np.float32)
            return fit_keras(m, x, y, batch_size=16, epochs=2, seed=7,
                             distributed=False, device_cache=False,
                             compile_cache_dir=cache_dir)["loss"]

        plain = run(None)
        cached = run(str(tmp_path / "cc"))
        again = run(str(tmp_path / "cc"))
        assert plain == cached == again


class TestConcurrentProcesses:
    """ISSUE 10: one compile-cache dir shared by a FLEET of engine
    processes. The store's atomic write-then-rename + CRC discipline
    must hold under real process-level races (not just threads), and a
    staggered second engine must pay loads, not compiles."""

    BUCKETS = "1,2,4"

    def _spawn(self, cache_dir, sync_dir=None):
        import subprocess
        import sys
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
        args = [sys.executable,
                os.path.join(here, "tests", "fleet_warm_entry.py"),
                str(cache_dir), self.BUCKETS]
        if sync_dir is not None:
            args.append(str(sync_dir))
        return subprocess.Popen(args, env=env, cwd=here,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)

    @staticmethod
    def _result(proc):
        out, err = proc.communicate(timeout=300)
        assert proc.returncode == 0, err
        return json.loads(out.strip().splitlines()[-1])

    def test_racing_writers_leave_one_valid_entry_per_bucket(
            self, tmp_path):
        """Two real processes warm the same cache dir at the same
        instant (sync-dir start gun fires after both finish imports):
        both serve, the store stays CRC-valid, and exactly one
        persisted executable per bucket survives."""
        from analytics_zoo_tpu.compile_cache import store as ccstore
        cache_dir = tmp_path / "cc"
        sync_dir = tmp_path / "sync"
        sync_dir.mkdir()
        procs = [self._spawn(cache_dir, sync_dir) for _ in range(2)]
        deadline = time.time() + 240
        while len([f for f in os.listdir(sync_dir)
                   if f.startswith("ready-")]) < 2:
            assert time.time() < deadline, "children never became ready"
            time.sleep(0.05)
        (sync_dir / "go").write_text("")        # the start gun
        results = [self._result(p) for p in procs]
        for r in results:
            assert r["served_shape"] == [1, 8], r
        entries = ccstore.scan_dir(str(cache_dir))
        n_buckets = len(self.BUCKETS.split(","))
        assert len(entries) == n_buckets, \
            f"expected one entry per bucket, got {entries}"
        for e in entries:
            assert "corrupt" not in e, e
            # full payload CRC verification, not just the header
            ccstore.read_entry(os.path.join(str(cache_dir), e["file"]))
        # no stray temp files from either writer
        assert not [f for f in os.listdir(cache_dir)
                    if f.startswith(".tmp-")]
        # the store is LOADABLE after the race: a fresh in-process
        # warmup pays zero compiles
        from tests.fleet_warm_entry import model_fn
        im = InferenceModel(
            compile_cache=CompileCache(str(cache_dir))
        ).load_fn(model_fn, np.full((8, 8), 0.5, np.float32))
        im.warmup(np.zeros((8,), np.float32), buckets=[1, 2, 4])
        assert set(im.warmup_source.values()) == {"cached"}

    def test_staggered_second_engine_loads_not_compiles(self, tmp_path):
        """The fleet cold-start contract: engine 1 pays the compiles,
        engine 2 (started after) loads — total cold compiles per bucket
        is 1."""
        cache_dir = tmp_path / "cc"
        first = self._result(self._spawn(cache_dir))
        n_buckets = len(self.BUCKETS.split(","))
        assert first["sources"] == {"compiled": n_buckets}, first
        second = self._result(self._spawn(cache_dir))
        assert second["sources"] == {"cached": n_buckets}, second
        assert second["cache"]["entries"] == n_buckets

    def test_reader_survives_concurrent_eviction(self, tmp_path):
        """A reader loading while another party prunes/rewrites the dir
        gets hits or misses — never an exception, never a torn entry."""
        import threading
        model = make_model()
        cache = CompileCache(str(tmp_path))
        im = InferenceModel(compile_cache=cache).load_keras(model)
        im.warmup(np.zeros((4,), np.float32), buckets=[1, 2])
        keys = list(im._aot)
        assert keys
        from analytics_zoo_tpu.compile_cache import make_key
        stop = threading.Event()
        errors = []

        def evictor():
            while not stop.is_set():
                cache.prune(0)                 # evict everything
                im2 = InferenceModel(
                    compile_cache=cache).load_keras(model)
                im2.warmup(np.zeros((4,), np.float32), buckets=[1])

        def reader():
            sample = np.zeros((1, 4), np.float32)
            key = make_key(im._fn, im._params, sample,
                           abstract_signature(sample))
            deadline = time.time() + 2.0
            while time.time() < deadline:
                try:
                    cache.load(key)            # hit or None, never raise
                except Exception as e:  # noqa: BLE001 — the assertion
                    errors.append(e)

        t_e = threading.Thread(target=evictor)
        t_r = threading.Thread(target=reader)
        t_e.start()
        t_r.start()
        t_r.join(timeout=30)
        stop.set()
        t_e.join(timeout=30)
        assert not errors, errors
