"""Parallelism tests on the 8-device virtual CPU mesh (conftest), the
single-host stand-in for a pod — the reference's `local[N]` test strategy
(SURVEY §4) mapped to TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.common.config import MeshConfig
from analytics_zoo_tpu.common.mesh import DeviceMesh
from analytics_zoo_tpu.pallas.flash_attention import _reference_attention
from analytics_zoo_tpu.parallel.ring_attention import ring_attention
from analytics_zoo_tpu.parallel.sharding import (
    TRANSFORMER_RULES, build_sharded_train_step, param_specs, shard_batch,
    shard_params)


@pytest.fixture(scope="module")
def tp_mesh():
    return DeviceMesh(MeshConfig(data=2, fsdp=2, tensor=2))


@pytest.fixture(scope="module")
def sp_mesh():
    return DeviceMesh(MeshConfig(data=2, sequence=4))


class TestShardingRules:
    def test_transformer_specs(self, tp_mesh):
        params = {"blk": {"attn": {
            "qkv_kernel": np.zeros((64, 192)),
            "qkv_bias": np.zeros((192,)),
            "out_kernel": np.zeros((64, 64)),
            "out_bias": np.zeros((64,)),
        }, "ln1": {"gamma": np.zeros((64,))}}}
        specs = param_specs(params, tp_mesh, TRANSFORMER_RULES)
        attn = specs["blk"]["attn"]
        assert attn["qkv_kernel"] == P("fsdp", "tensor")
        assert attn["qkv_bias"] == P("tensor")
        assert attn["out_kernel"] == P("tensor", "fsdp")
        assert attn["out_bias"] == P()

    def test_non_divisible_falls_back(self, tp_mesh):
        # dim 3 not divisible by tensor=2 -> axis dropped
        specs = param_specs({"x_qkv_kernel": np.zeros((6, 3))}, tp_mesh)
        assert specs["x_qkv_kernel"] == P("fsdp")

    def test_fsdp_fallback_largest_dim(self, tp_mesh):
        specs = param_specs({"some_weight": np.zeros((3, 8))}, tp_mesh)
        assert specs["some_weight"] == P(None, "fsdp")

    def test_shard_params_places_on_mesh(self, tp_mesh):
        params = {"a_qkv_kernel": np.ones((8, 12), np.float32)}
        sharded = shard_params(params, tp_mesh)
        shard_shapes = {s.data.shape
                        for s in sharded["a_qkv_kernel"].addressable_shards}
        assert shard_shapes == {(4, 6)}  # fsdp=2 x tensor=2


class TestShardedTrainStep:
    @pytest.fixture(autouse=True)
    def _partitionable_threefry(self):
        """This jax's default (`jax_threefry_partitionable=False`) lets
        GSPMD partition the dropout threefry non-value-preservingly, so
        a sharded program draws DIFFERENT masks than the single-device
        one and the trajectories diverge from step 0 (jax drift; the
        flag's whole purpose). Partitionable threefry restores the
        partitioning-invariant stream this comparison was written
        against; scoped to the test so fixed-seed draws elsewhere keep
        their legacy values."""
        prev = jax.config.jax_threefry_partitionable
        jax.config.update("jax_threefry_partitionable", True)
        yield
        jax.config.update("jax_threefry_partitionable", prev)

    def test_tp_fsdp_training_decreases_loss(self, tp_mesh):
        """End-to-end: tiny BERT sharded dp x fsdp x tp, loss goes down and
        the sharded result matches single-device training numerically."""
        from __graft_entry__ import _build_bert_classifier
        from analytics_zoo_tpu.ops import objectives

        forward, params0 = _build_bert_classifier(
            vocab=64, hidden=16, n_block=1, n_head=2, seq_len=8,
            intermediate=32, n_classes=2, rng=jax.random.PRNGKey(0))
        # host copies: the train step donates its inputs, so each run()
        # must start from fresh device buffers
        params0 = jax.tree_util.tree_map(np.asarray, params0)
        loss_obj = objectives.get("sparse_categorical_crossentropy",
                                  from_logits=True)
        opt = optax.adam(1e-2)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 64, (8, 8)).astype(np.int32)
        mask = np.ones((8, 8), np.float32)
        labels = rng.randint(0, 2, (8,)).astype(np.int32)

        def apply_fn(p, xb, training=False, rng=None):
            return forward(p, xb["ids"], xb["mask"], training=training,
                           rng=rng)

        def run(mesh):
            if mesh is None:
                params = jax.tree_util.tree_map(jnp.asarray, params0)
                xb = {"ids": jnp.asarray(ids), "mask": jnp.asarray(mask)}
                yb = jnp.asarray(labels)
            else:
                params = shard_params(params0, mesh)
                xb = shard_batch({"ids": ids, "mask": mask}, mesh)
                yb = shard_batch(labels, mesh)
            opt_state = opt.init(params)
            step = build_sharded_train_step(apply_fn, loss_obj, opt)
            losses = []
            key = jax.random.PRNGKey(1)
            for _ in range(10):
                params, opt_state, loss = step(params, opt_state, xb, yb,
                                               key)
                losses.append(float(loss))
            return losses

        sharded_losses = run(tp_mesh)
        single_losses = run(None)
        assert sharded_losses[-1] < sharded_losses[0]
        np.testing.assert_allclose(sharded_losses, single_losses,
                                   rtol=1e-4, atol=1e-5)


class TestRingAttention:
    @pytest.fixture(scope="class")
    def qkv(self):
        rng = np.random.RandomState(0)
        B, H, T, D = 4, 2, 32, 8
        return tuple(jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
                     for _ in range(3))

    def test_matches_reference_no_mask(self, sp_mesh, qkv):
        q, k, v = qkv
        out = ring_attention(q, k, v, None, mesh=sp_mesh)
        ref = _reference_attention(q, k, v, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_matches_reference_with_mask(self, sp_mesh, qkv):
        q, k, v = qkv
        mask = np.zeros((4, 32), np.float32)
        mask[:, 20:] = -10000.0
        out = ring_attention(q, k, v, jnp.asarray(mask), mesh=sp_mesh)
        ref = _reference_attention(q, k, v, jnp.asarray(mask)[:, None, None])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_with_tensor_axis_too(self, qkv):
        mesh = DeviceMesh(MeshConfig(data=2, sequence=2, tensor=2))
        q, k, v = qkv
        out = ring_attention(q, k, v, None, mesh=mesh)
        ref = _reference_attention(q, k, v, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_jit_and_grad(self, sp_mesh, qkv):
        q, k, v = qkv

        @jax.jit
        def f(q, k, v):
            return jnp.sum(ring_attention(q, k, v, None, mesh=sp_mesh) ** 2)

        g = jax.grad(f)(q, k, v)
        ref_g = jax.grad(
            lambda q, k, v: jnp.sum(
                _reference_attention(q, k, v, None) ** 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g),
                                   atol=1e-4)


class TestPipeline:
    @pytest.fixture(scope="class")
    def stages(self):
        rng = np.random.RandomState(0)
        S, d = 4, 16
        W = jnp.asarray(rng.randn(S, d, d) * 0.3, jnp.float32)
        b = jnp.asarray(rng.randn(S, d) * 0.1, jnp.float32)
        x = jnp.asarray(rng.randn(32, d), jnp.float32)
        return W, b, x

    @staticmethod
    def _stage_fn(p, x):
        return jnp.tanh(x @ p["W"] + p["b"])

    @staticmethod
    def _ref(W, b, x):
        for s in range(W.shape[0]):
            x = jnp.tanh(x @ W[s] + b[s])
        return x

    def test_forward_matches_sequential(self, stages):
        from analytics_zoo_tpu.parallel.pipeline import (
            from_microbatches, pipeline_apply, to_microbatches)
        W, b, x = stages
        mesh = DeviceMesh(MeshConfig(pipeline=4, data=2))
        mbs = to_microbatches(x, 8)
        y = from_microbatches(
            pipeline_apply(self._stage_fn, {"W": W, "b": b}, mbs, mesh))
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(self._ref(W, b, x)), atol=1e-6)

    def test_gradient_matches(self, stages):
        from analytics_zoo_tpu.parallel.pipeline import (
            pipeline_apply, to_microbatches)
        W, b, x = stages
        mesh = DeviceMesh(MeshConfig(pipeline=4, data=2))
        mbs = to_microbatches(x, 8)
        g = jax.grad(lambda W: jnp.sum(pipeline_apply(
            self._stage_fn, {"W": W, "b": b}, mbs, mesh) ** 2))(W)
        g_ref = jax.grad(
            lambda W: jnp.sum(self._ref(W, b, x) ** 2))(W)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   atol=1e-4)

    def test_single_stage_axis_fallback(self, stages):
        from analytics_zoo_tpu.parallel.pipeline import (
            from_microbatches, pipeline_apply, to_microbatches)
        W, b, x = stages
        mesh = DeviceMesh(MeshConfig(data=8))
        mbs = to_microbatches(x, 8)
        y = from_microbatches(
            pipeline_apply(self._stage_fn, {"W": W, "b": b}, mbs, mesh))
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(self._ref(W, b, x)), atol=1e-6)

    def test_microbatch_roundtrip_validation(self):
        from analytics_zoo_tpu.parallel.pipeline import to_microbatches
        with pytest.raises(ValueError):
            to_microbatches(jnp.zeros((10, 3)), 4)

    def test_microbatch_roundtrip_order(self):
        from analytics_zoo_tpu.parallel.pipeline import (from_microbatches,
                                                         to_microbatches)
        x = jnp.arange(24).reshape(12, 2)
        back = from_microbatches(to_microbatches(x, 4))
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    def test_seq_axis_spec_matches_ring_output(self):
        # ring attention output [B, H, T, D] (T sequence-sharded) feeds the
        # pipeline without resharding when seq_axis is named
        from analytics_zoo_tpu.parallel.pipeline import (from_microbatches,
                                                         pipeline_apply,
                                                         to_microbatches)
        W, b = (jnp.ones((2, 8, 8)) * 0.1, jnp.zeros((2, 8)))
        x = jnp.asarray(np.random.RandomState(0).randn(8, 16, 8), jnp.float32)
        mesh = DeviceMesh(MeshConfig(pipeline=2, sequence=2, data=2))
        mbs = to_microbatches(x, 2)
        y = from_microbatches(pipeline_apply(
            self._stage_fn, {"W": W, "b": b}, mbs, mesh,
            seq_axis="sequence"))
        ref = x
        for s in range(2):
            ref = jnp.tanh(ref @ W[s] + b[s])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)


class TestScalingEvidence:
    """Mechanical multi-chip performance evidence: per-device HLO cost and
    collective counts, dp=1 vs dp=8 (and tensor-parallel), so sharding
    regressions (e.g. a silent full rematerialization re-replicating a
    tensor) fail a test instead of only slowing real pods down."""

    def _make_step(self):
        from __graft_entry__ import _build_bert_classifier
        from analytics_zoo_tpu.ops import objectives

        forward, params0 = _build_bert_classifier(
            vocab=64, hidden=16, n_block=1, n_head=2, seq_len=8,
            intermediate=32, n_classes=2, rng=jax.random.PRNGKey(0))
        params0 = jax.tree_util.tree_map(np.asarray, params0)
        loss_obj = objectives.get("sparse_categorical_crossentropy",
                                  from_logits=True)
        opt = optax.adam(1e-2)

        def apply_fn(p, xb, training=False, rng=None):
            return forward(p, xb["ids"], xb["mask"], training=training,
                           rng=rng)

        rng = np.random.RandomState(0)
        data = {"ids": rng.randint(0, 64, (16, 8)).astype(np.int32),
                "mask": np.ones((16, 8), np.float32)}
        labels = rng.randint(0, 2, (16,)).astype(np.int32)
        return apply_fn, loss_obj, opt, params0, data, labels

    def _compiled(self, mesh):
        apply_fn, loss_obj, opt, params0, data, labels = self._make_step()
        if mesh is None:
            params = jax.tree_util.tree_map(jnp.asarray, params0)
            xb = jax.tree_util.tree_map(jnp.asarray, data)
            yb = jnp.asarray(labels)
        else:
            params = shard_params(params0, mesh)
            xb = shard_batch(data, mesh)
            yb = shard_batch(labels, mesh)
        step = build_sharded_train_step(apply_fn, loss_obj, opt)
        opt_state = opt.init(params)
        return step.lower(params, opt_state, xb, yb,
                          jax.random.PRNGKey(1)).compile()

    @staticmethod
    def _flops(compiled) -> float:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):  # older jax returns [dict]
            cost = cost[0]
        return float(cost["flops"])

    def test_dp8_per_device_flops_scale(self):
        single = self._flops(self._compiled(None))
        dp8 = self._flops(self._compiled(
            DeviceMesh(MeshConfig(data=8))))
        # per-device compute must land near single-device/8 (collective
        # and padding overhead allowed, full replication is not)
        assert dp8 < single / 8 * 1.6, \
            f"dp=8 per-device flops {dp8:.3g} vs single {single:.3g} — " \
            "batch is not actually sharded 8-ways"
        assert dp8 > single / 8 * 0.5

    def test_dp8_collectives_are_gradient_allreduce_only(self):
        hlo = self._compiled(
            DeviceMesh(MeshConfig(data=8))).as_text()
        assert "all-reduce" in hlo, "no gradient all-reduce emitted"
        # pure DP: replicated params, sharded batch — nothing should need
        # gathering or resharding
        assert "all-gather" not in hlo, \
            "unexpected all-gather in pure-DP step (param resharding?)"
        assert "all-to-all" not in hlo

    def test_tp_shards_matmul_flops(self):
        single = self._flops(self._compiled(None))
        tp = self._flops(self._compiled(
            DeviceMesh(MeshConfig(data=2, fsdp=2, tensor=2))))
        # dp×fsdp shard the batch 4-ways and tp halves the matmul work;
        # allow generous overhead but catch a fully-replicated regression
        assert tp < single / 4, \
            f"tp per-device flops {tp:.3g} vs single {single:.3g} — " \
            "tensor/fsdp sharding not reducing per-device work"


class TestGraftEntry:
    def test_dryrun_multichip(self):
        from __graft_entry__ import dryrun_multichip
        dryrun_multichip(8)

    def test_no_involuntary_rematerialization(self):
        # VERDICT r1 weak #3: the ring-attention → pipeline hand-off must
        # not force a full replicate/reshard between the two shard_maps.
        # XLA reports that failure mode as an "Involuntary full
        # rematerialization" warning from the SPMD partitioner at compile
        # time; run the pp×sp dryrun in a subprocess and assert the log is
        # clean.
        import subprocess
        import sys
        proc = subprocess.run(
            [sys.executable, "-c",
             "import jax; jax.config.update('jax_platforms', 'cpu');"
             "from __graft_entry__ import dryrun_multichip;"
             "dryrun_multichip(8); print('ok')"],
            capture_output=True, text=True, timeout=600,
            env={**__import__('os').environ,
                 "JAX_PLATFORMS": "cpu",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
            cwd=__import__('os').path.dirname(
                __import__('os').path.dirname(__file__)))
        assert "ok" in proc.stdout, proc.stderr[-2000:]
        assert "Involuntary full rematerialization" not in proc.stderr, \
            proc.stderr[-2000:]
