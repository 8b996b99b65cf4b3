"""On-chip smoke: the quickest proof that the system still starts on a TPU.

    python chip_smoke.py

One process drives the main path once through the entry points a user calls,
at the full width of BERT-base (vocab 30522, hidden 768, 12 blocks, 12 heads,
intermediate 3072; random weights from the context seed):

  fit        init_orca_context -> BERTClassifier(seq_len=128) ->
             Estimator.from_keras(optax.adamw) -> fit(mixed_precision=True,
             steps_per_run=2), twice: the second call must not compile.
  fit-flash  the same at seq_len=2048 through use_flash=True; the program the
             fit ran must hold the Mosaic kernels and no [T, T] score tensor,
             and the kernel must agree with the exact attention.
  serve      the fit stage's weights through InferenceModel.load_keras ->
             ClusterServing over an in-process broker -> InputQueue /
             OutputQueue, answers equal to model.predict.

It refuses to run without a TPU backend, lets any failing stage raise (there
is no try/except that turns a failure into exit 0), and ends its standard
output with one JSON object naming the device it ran on.
"""

from __future__ import annotations

import importlib.metadata
import json
import sys
import time

import jax
import numpy as np

BERT_BASE = dict(vocab=30522, hidden_size=768, n_block=12, n_head=12,
                 intermediate_size=3072)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA compile requests (fresh compiles and persistent-cache
    loads alike: every time a jit found nothing in memory)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **kwargs):
        if name == COMPILE_EVENT:
            self.n += 1


def _bert_data(vocab: int, seq_len: int, n: int, seed: int = 0):
    rs = np.random.RandomState(seed)
    return {"x": [rs.randint(0, vocab, (n, seq_len)).astype(np.int32),
                  np.ones((n, seq_len), np.float32)],
            "y": rs.randint(0, 2, (n,)).astype(np.int32)}


def _bert_estimator(seq_len: int, use_flash: bool, **bert_kw):
    import optax

    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.models.bert import BERTClassifier
    from analytics_zoo_tpu.ops import objectives

    model = BERTClassifier(num_classes=2, seq_len=seq_len,
                           use_flash=use_flash, **bert_kw)
    return Estimator.from_keras(
        model, optimizer=optax.adamw(1e-4),
        loss=objectives.get("sparse_categorical_crossentropy",
                            from_logits=True))


def _fit_twice(est, data, batch: int, compiles: CompileCounter, mesh,
               **extra_fit_kw):
    """Two fits of one epoch through the estimator. Returns (cold seconds,
    warm seconds, losses). Checks: finite losses that moved, no compile in the
    second call, params on every device of the mesh, every batch the trainer
    placed split over every device of the mesh."""
    from analytics_zoo_tpu.learn import trainer

    placed = []
    put_batch = trainer._put_batch

    def recording_put_batch(tree, mesh_, stacked=False):
        out = put_batch(tree, mesh_, stacked)
        placed.extend(jax.tree_util.tree_leaves(out))
        return out

    fit_kw = dict(epochs=1, batch_size=batch, steps_per_run=2,
                  mixed_precision=True, **extra_fit_kw)
    trainer._put_batch = recording_put_batch
    try:
        t0 = time.perf_counter()
        h1 = est.fit(data, **fit_kw)
        cold = time.perf_counter() - t0
        n_before = compiles.n
        t0 = time.perf_counter()
        h2 = est.fit(data, **fit_kw)
        warm = time.perf_counter() - t0
    finally:
        trainer._put_batch = put_batch

    losses = h1["loss"] + h2["loss"]
    assert np.isfinite(losses).all(), f"non-finite loss {losses}"
    assert h1["loss"][-1] != h2["loss"][-1], f"loss did not move {losses}"
    assert compiles.n == n_before, (
        f"second fit compiled {compiles.n - n_before} program(s)")

    mesh_devices = set(mesh.mesh.devices.flat)
    for leaf in jax.tree_util.tree_leaves(est.model.params):
        assert leaf.sharding.device_set == mesh_devices, (
            f"param on {leaf.sharding.device_set}, mesh {mesh_devices}")
    assert placed, "the trainer placed no batch"
    for arr in placed:
        assert arr.sharding.device_set == mesh_devices, (
            f"batch on {arr.sharding.device_set}, mesh {mesh_devices}")
        shards = {tuple((s.start, s.stop) for s in sh.index)
                  for sh in arr.addressable_shards}
        assert len(shards) == len(mesh_devices), (
            f"{len(shards)} distinct batch shards on "
            f"{len(mesh_devices)} devices")
    return cold, warm, losses


def stage_fit(compiles: CompileCounter, bert_kw=BERT_BASE, seq_len=128,
              batch=32, steps=4, mesh_axes=None, **extra_fit_kw):
    """`mesh_axes` / `extra_fit_kw` are for scripts/chip_multichip.py (the
    same stage on a four-chip mesh); the smoke itself passes neither."""
    from analytics_zoo_tpu import init_orca_context

    ctx = init_orca_context(cluster_mode="local", **(mesh_axes or {}))
    est = _bert_estimator(seq_len, use_flash=False, **bert_kw)
    data = _bert_data(bert_kw["vocab"], seq_len, batch * steps)
    cold, warm, losses = _fit_twice(est, data, batch, compiles, ctx.mesh,
                                    **extra_fit_kw)
    print(f"PASS fit mesh={ctx.mesh} seq={seq_len} batch={batch} "
          f"steps={steps} "
          f"cold={cold:.1f}s warm={warm:.1f}s "
          f"loss={losses[0]:.4f}->{losses[-1]:.4f}", flush=True)
    return est


def _lower_train_program(est):
    """StableHLO text of the jitted program the fit just ran, lowered on
    the shapes the trainer kept of its dispatch (`_StepProgram`)."""
    program = est.model._train_cache[2]
    return program.jitted.lower(*program.abstract_args).as_text()


def stage_fit_flash(compiles: CompileCounter, bert_kw=BERT_BASE,
                    seq_len=2048, batch=4, steps=4):
    import jax.numpy as jnp

    from analytics_zoo_tpu.common.context import get_context
    from analytics_zoo_tpu.pallas.flash_attention import (
        _reference_attention, flash_attention)

    est = _bert_estimator(seq_len, use_flash=True, **bert_kw)
    data = _bert_data(bert_kw["vocab"], seq_len, batch * steps)
    cold, warm, losses = _fit_twice(est, data, batch, compiles,
                                    get_context().mesh)

    text = _lower_train_program(est)
    n_kernels = text.count("tpu_custom_call")
    # one forward and at least one backward kernel per block
    assert n_kernels >= 2 * bert_kw["n_block"], (
        f"{n_kernels} Mosaic custom calls in the flash train step")
    assert f"x{seq_len}x{seq_len}x" not in text, (
        "the flash train step materializes a [T, T] score tensor: "
        "_reference_attention ran")

    # the kernel against the exact attention, at the width the step used
    head_dim = bert_kw["hidden_size"] // bert_kw["n_head"]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (1, bert_kw["n_head"], seq_len,
                                      head_dim), jnp.float32) * 0.3
               for kk in ks)
    got = np.asarray(flash_attention(q, k, v))
    ref = np.asarray(_reference_attention(q, k, v))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)

    print(f"PASS fit-flash seq={seq_len} batch={batch} steps={steps} "
          f"cold={cold:.1f}s warm={warm:.1f}s mosaic_calls={n_kernels} "
          f"loss={losses[0]:.4f}->{losses[-1]:.4f}", flush=True)


def stage_serve(est, vocab: int, seq_len=128, n_requests=8):
    from analytics_zoo_tpu.serving import (ClusterServing, InferenceModel,
                                           InputQueue, MemoryBroker,
                                           OutputQueue)

    model = est.model
    rs = np.random.RandomState(1)
    requests = [rs.randint(0, vocab, (seq_len,)).astype(np.int32)
                for _ in range(n_requests)]
    want = np.asarray(model.predict(np.stack(requests),
                                    batch_per_thread=n_requests))
    assert want.shape == (n_requests, 2) and np.isfinite(want).all()

    im = InferenceModel().load_keras(model)
    broker = MemoryBroker()
    t0 = time.perf_counter()
    serving = ClusterServing(im, broker, batch_size=n_requests).start()
    try:
        uris = InputQueue(broker).enqueue_batch(requests)
        outq = OutputQueue(broker)
        answers = {}
        deadline = time.monotonic() + 600
        while len(answers) < len(uris):
            assert time.monotonic() < deadline, (
                f"{len(answers)}/{len(uris)} answers after 600 s")
            for uri in uris:
                if uri not in answers:
                    res = outq.query(uri, delete=True)
                    if res is not None:
                        answers[uri] = res
            time.sleep(0.01)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = InputQueue(broker).predict_batch(requests, timeout_s=120)
        second = time.perf_counter() - t0
    finally:
        serving.stop()
        im.close()

    for got in ([answers[u] for u in uris], again):
        got = np.asarray(got, np.float32)
        assert got.shape == want.shape, f"{got.shape} != {want.shape}"
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-3)
    params_on = {d.platform for leaf in
                 jax.tree_util.tree_leaves(im._params) for d in leaf.devices()}
    assert params_on == {"tpu"}, f"serving params on {params_on}"
    print(f"PASS serve requests={n_requests} first_batch={first:.1f}s "
          f"second_batch={second * 1e3:.0f}ms", flush=True)


def main() -> int:
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU backend (found {jax.default_backend()!r})",
              file=sys.stderr)
        return 1
    t_start = time.perf_counter()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    from analytics_zoo_tpu.compile_cache import enable_jax_persistent_cache
    from analytics_zoo_tpu.utils.roofline import peak_flops, peak_hbm
    print(f"platform={device['platform']} device_kind={device['kind']!r} "
          f"count={device['count']} jax={jax.__version__} "
          f"jaxlib={importlib.metadata.version('jaxlib')} "
          f"libtpu={importlib.metadata.version('libtpu')}", flush=True)
    # an unlisted chip is an error here, not a v5e by default
    print(f"peak_bf16_tflops={peak_flops(dev) / 1e12:.0f} "
          f"peak_hbm_gbps={peak_hbm(dev) / 1e9:.0f} "
          f"xla_compile_cache={enable_jax_persistent_cache()}", flush=True)

    compiles = CompileCounter()
    est = stage_fit(compiles)
    stage_fit_flash(compiles)
    stage_serve(est, BERT_BASE["vocab"])
    print(f"total={time.perf_counter() - t_start:.1f}s "
          f"compile_requests={compiles.n}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
