"""Headline benchmark: BERT-base classifier training MFU, measured THROUGH the
framework (`Estimator.from_keras(...).fit(...)`), not a hand-rolled side loop
— the engine's own hot path is what's timed, matching the reference whose hot
loop is its engine (`Topology.scala:1160-1337`).

Target from BASELINE.md: >=35% MFU. Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", ...}.

One process for each chip. A chip belongs to one process at a time, so the
parent never imports jax: it runs the legs (BERT seq 128 + the input-pipeline
A/B, BERT seq 2048 flash, NCF via bench_ncf.py) as child processes strictly
one after another, each of which initializes the accelerator, measures,
prints one JSON line naming its device and exits. A leg that fails, times
out or prints no JSON fails the whole run with a non-zero exit; there is no
field that quietly turns into null. A device whose `device_kind` has no
published peak (`utils/roofline.py`; the CPU backend included) is an error
before any work is done, and a mesh's work is divided by the peak of all its
chips.

Mixed precision: `fit(mixed_precision=True)` keeps f32 masters and runs
matmuls bf16 (MXU-native). `fit(steps_per_run=k)` fuses k steps into one
lax.scan program; the prefetch thread overlaps the next group's host→device
transfer with device compute. BENCH_TINY=1 cuts every shape for a quick
check of the plumbing on the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def _mesh_peak_flops():
    """(device 0, peak bf16 FLOP/s of ALL local devices). Raises
    `UnknownDeviceError` for a device with no published peak — called
    before the measurement so a CPU run dies in seconds, not after it."""
    import jax

    from analytics_zoo_tpu.utils.roofline import peak_flops
    dev = jax.devices()[0]
    return dev, peak_flops(dev) * jax.device_count()


def _device_fields(dev) -> dict:
    import jax
    return {"device": dev.device_kind, "platform": dev.platform,
            "device_count": jax.device_count()}


def _measure_bert(peak, *, vocab, hidden, n_block, n_head, seq_len, inter,
                  batch, steps, steps_per_run, use_flash=False,
                  remat=False):
    """One BERT-classifier training measurement THROUGH Estimator.fit.
    `peak` is the whole mesh's peak FLOP/s. Returns (mfu, tokens/s,
    step_ms, final_loss, spread, flops_step)."""
    import jax
    import numpy as np
    import optax

    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.models.bert import BERTClassifier
    from analytics_zoo_tpu.ops import objectives

    drop_kw = {}
    if os.environ.get("BENCH_NODROP") == "1":   # roofline experiments
        drop_kw = dict(hidden_drop=0.0, attn_drop=0.0, dropout=0.0)
    model = BERTClassifier(
        num_classes=2, vocab=vocab, hidden_size=hidden, n_block=n_block,
        n_head=n_head, seq_len=seq_len, intermediate_size=inter,
        use_flash=use_flash, remat=remat,
        # scan-over-layers (stacked block params): collapses the Adam
        # phase but lax.scan's conservative residual saving OOMs the
        # batch-256/seq-2048 bench configs on a 16 GB chip and its
        # residual writes eat the win at batch 128 — measured wash;
        # docs/ROOFLINE.md round 5. Off by default.
        stacked=os.environ.get("BENCH_STACKED", "0") == "1", **drop_kw)
    est = Estimator.from_keras(
        model, optimizer=optax.adamw(1e-4),
        loss=objectives.get("sparse_categorical_crossentropy",
                            from_logits=True))

    rs = np.random.RandomState(0)
    n = batch * steps
    data = {"x": [rs.randint(0, vocab, (n, seq_len)).astype(np.int32),
                  np.ones((n, seq_len), np.float32)],
            "y": rs.randint(0, 2, (n,)).astype(np.int32)}
    fit_kw = dict(epochs=1, batch_size=batch, steps_per_run=steps_per_run,
                  mixed_precision=True,
                  # fused Pallas optimizer sweep (ISSUE 9): one HBM
                  # pass per leaf instead of optax's materialized-tree
                  # chain; BERT is compute-bound so the delta here is
                  # small — the A/B knob exists for the record
                  fused_optimizer=os.environ.get("BENCH_FUSED", "0") == "1")

    est.fit(data, **fit_kw)                 # warmup: compile + first epoch
    # Three timed fits of the same cached program. The fastest is the
    # reported step time; (max - min) / min of the three rides along so a
    # reader can see how far apart identical runs landed.
    times = []
    for _ in range(1 if os.environ.get("BENCH_TINY") == "1" else 3):
        t0 = time.perf_counter()
        hist = est.fit(data, **fit_kw)      # timed: cached program, real loop
        times.append(time.perf_counter() - t0)
    dt = min(times)
    spread = (max(times) - dt) / dt if len(times) > 1 else 0.0

    # Matmul params only (embeddings are gathers, not FLOPs).
    n_params = sum(int(np.prod(np.shape(p))) for p in
                   jax.tree_util.tree_leaves(model.params))
    n_emb = (vocab + seq_len + 2) * hidden
    n_matmul = n_params - n_emb
    tokens = batch * seq_len
    # fwd+bwd = 6 FLOPs/param/token; attention scores+context add
    # 12 * L * T^2 * D per batch element (fwd 4*T^2*D, x3 with bwd).
    # Remat recomputation is NOT counted as useful work (honest MFU).
    flops_step = (6 * n_matmul * tokens
                  + 12 * n_block * seq_len**2 * hidden * batch)
    mfu = flops_step * steps / dt / peak
    return (mfu, tokens * steps / dt, dt / steps * 1e3,
            float(hist["loss"][-1]), spread, flops_step)


def _run_leg(name: str, cmd, timeout: int, env) -> dict:
    """Run one leg as a child process and return the JSON object on the
    last `{`-line of its stdout. Anything else — a non-zero exit, a
    timeout, no JSON — ends the whole benchmark run with exit code 1 and
    the child's stderr tail on ours."""
    # unbuffered child stdout: the JSON line must not sit in a userspace
    # buffer if the child dies after printing it
    env = dict(env, PYTHONUNBUFFERED="1")
    try:
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout, env=env)
    except subprocess.TimeoutExpired as e:
        err = e.stderr.decode(errors="replace") \
            if isinstance(e.stderr, bytes) else (e.stderr or "")
        sys.exit(f"bench leg {name} timed out after {timeout}s:\n"
                 + "\n".join(err.strip().splitlines()[-12:]))
    lines = [ln for ln in res.stdout.strip().splitlines()
             if ln.startswith("{")]
    if res.returncode != 0 or not lines:
        sys.exit(f"bench leg {name} failed (rc={res.returncode}, "
                 f"{len(lines)} JSON lines):\n"
                 + "\n".join(res.stderr.strip().splitlines()[-12:]))
    # on our stderr at once: a later leg's failure must not take this
    # leg's numbers with it
    print(f"bench leg {name}: {lines[-1]}", file=sys.stderr, flush=True)
    return json.loads(lines[-1])


def _leg_longseq():
    """The seq-2048 flash measurement, printed as its own JSON line for
    the parent to merge. steps_per_run=24 fuses the whole epoch into one
    dispatch (one host turnaround per epoch instead of four)."""
    from analytics_zoo_tpu import init_orca_context
    init_orca_context(cluster_mode="local")
    dev, peak = _mesh_peak_flops()
    m2k, t2k, ms2k, _, _, _ = _measure_bert(
        peak, vocab=30522, hidden=768, n_block=12, n_head=12,
        seq_len=2048, inter=3072,
        batch=int(os.environ.get("BENCH_LONGSEQ_BATCH", 16)),
        steps=24, steps_per_run=24, use_flash=True,
        remat=os.environ.get("BENCH_LONGSEQ_REMAT", "0") == "1")
    print(json.dumps({
        "bert_seq2048_flash_mfu_pct": round(m2k * 100, 2),
        "bert_seq2048_tokens_per_sec": round(t2k, 1),
        "bert_seq2048_step_ms": round(ms2k, 2),
        "bert_seq2048_device": _device_fields(dev),
    }))


def fit_scaling_summary(n_devices: int, counts=None, n_samples: int = 256,
                        batch_size: int = 64, hidden: int = 128,
                        seq_len: int = 32, n_block: int = 2) -> dict:
    """Training analogue of `bench_serving.multidevice_summary` (ISSUE 7):
    a data-parallel BERT fit scaling curve over 1→n devices — one GLOBAL
    batch split across the mesh's data axis, samples/sec per device
    count, per-device peak HBM from memwatch sampled during the timed
    fit — plus an fsdp-sharded fit of the same model recording the
    1/fsdp per-device params+opt_state footprint next to the replicated
    one. `host_cores`/`efficiency_vs_host_cores` report the forced-host
    ceiling exactly as the serving curve does: an M-core box caps
    scaling near M× regardless of virtual device count; on a real pod
    the ceiling is the chip count. Requires `len(jax.devices()) >=
    n_devices` (see `__graft_entry__.dryrun_multichip` for the re-exec
    wrapper)."""
    import jax
    import numpy as np
    import optax

    from analytics_zoo_tpu.common.config import MeshConfig
    from analytics_zoo_tpu.common.context import get_context
    from analytics_zoo_tpu.common.mesh import DeviceMesh
    from analytics_zoo_tpu.learn import trainer
    from analytics_zoo_tpu.observability.memwatch import DeviceMemoryWatcher
    from analytics_zoo_tpu.ops import objectives

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from __graft_entry__ import _build_bert_classifier

    devs = jax.devices()[:n_devices]
    assert len(devs) == n_devices, (
        f"need {n_devices} devices, have {len(devs)}")
    counts = sorted({c for c in (counts or [1, 2, n_devices])
                     if 1 <= c <= n_devices and batch_size % c == 0})

    rs = np.random.RandomState(0)
    x = {"ids": rs.randint(0, 128, (n_samples, seq_len)).astype(np.int32),
         "mask": np.ones((n_samples, seq_len), np.float32)}
    y = rs.randint(0, 2, (n_samples,)).astype(np.int32)
    loss_obj = objectives.get("sparse_categorical_crossentropy",
                              from_logits=True)

    def make_model():
        from analytics_zoo_tpu.learn.estimator import Estimator
        forward, params = _build_bert_classifier(
            vocab=128, hidden=hidden, n_block=n_block, n_head=4,
            seq_len=seq_len, intermediate=2 * hidden, n_classes=2,
            rng=jax.random.PRNGKey(0))

        def apply_fn(p, xb, training=False, rng=None):
            return forward(p, xb["ids"], xb["mask"], training=training,
                           rng=rng)

        est = Estimator.from_fn(apply_fn, lambda r, s: params, loss_obj,
                                optax.adam(1e-3))
        est.model.params = params
        return est.model

    def timed_fit(model, **kw):
        """One warm fit (compiles off the clock; the model's step memo
        carries to the next call), then the measured fit under a
        fast-sampling memory watcher."""
        trainer.fit_keras(model, x, y, batch_size=batch_size, epochs=1,
                          device_cache=False, seed=0, **kw)
        watcher = DeviceMemoryWatcher(interval_s=0.02,
                                      devices=devs).start()
        t0 = time.perf_counter()
        trainer.fit_keras(model, x, y, batch_size=batch_size, epochs=1,
                          device_cache=False, seed=1, **kw)
        dt = time.perf_counter() - t0
        snap = watcher.sample()
        watcher.stop()
        peaks = {label: e.get("peak_bytes", e["live_bytes"])
                 for label, e in snap.items()}
        steps = n_samples // batch_size
        return steps * batch_size / dt, peaks

    def state_footprint(mesh, rules):
        """Deterministic per-device params+opt_state bytes under a
        layout: place a fresh model's params (replicated or
        rule-sharded) plus an Adam state exactly as fit_keras would,
        and read the ACTUAL shard bytes (`memwatch.tree_device_bytes`)."""
        from analytics_zoo_tpu.learn.trainer import (_put_replicated,
                                                     _put_with_shardings)
        from analytics_zoo_tpu.observability.memwatch import \
            tree_device_bytes
        from analytics_zoo_tpu.parallel.sharding import tree_shardings
        model = make_model()
        opt = optax.adam(1e-3)
        if rules is not None:
            params = _put_with_shardings(
                model.params, tree_shardings(model.params, mesh, rules))
            opt_state = opt.init(params)
            opt_state = _put_with_shardings(
                opt_state, tree_shardings(opt_state, mesh, rules))
        else:
            params = _put_replicated(model.params, mesh)
            opt_state = _put_replicated(opt.init(params), mesh)
        per_dev = tree_device_bytes((params, opt_state))
        return round(max(per_dev.values()))

    ctx = get_context()
    prev_mesh = ctx.mesh
    sps, peak_by_count = {}, {}
    try:
        for c in counts:
            ctx.mesh = DeviceMesh(MeshConfig(data=c), devs[:c])
            rate, peaks = timed_fit(make_model())
            sps[str(c)] = round(rate, 1)
            peak_by_count[str(c)] = round(max(peaks.values()))
        # fsdp-sharded fit on the full mesh: same model, params +
        # opt_state at ~1/fsdp per device (the footprint the replicated
        # rows above pay in full)
        full_mesh = DeviceMesh(MeshConfig(data=1, fsdp=n_devices), devs)
        ctx.mesh = full_mesh
        srate, speaks = timed_fit(make_model(), sharding_rules=True)
        from analytics_zoo_tpu.parallel.sharding import TRANSFORMER_RULES
        state_replicated = state_footprint(full_mesh, None)
        state_sharded = state_footprint(full_mesh, TRANSFORMER_RULES)
        # tensor-parallel leg (ISSUE 12): same model on a
        # (data=1 × fsdp × tensor) factorization — the rule table's
        # column/row-parallel specs live, activations sharded over
        # tensor, state still ~1/(fsdp·tensor) per device
        tp_tensor = 2 if n_devices % 2 == 0 else 1
        tp_fsdp = n_devices // tp_tensor
        tp_mesh = DeviceMesh(MeshConfig(data=1, fsdp=tp_fsdp,
                                        tensor=tp_tensor), devs)
        ctx.mesh = tp_mesh
        tprate, tppeaks = timed_fit(make_model(), sharding_rules=True)
        tp_state = state_footprint(tp_mesh, TRANSFORMER_RULES)
    finally:
        ctx.mesh = prev_mesh

    base = sps[str(counts[0])]
    speedup = sps[str(counts[-1])] / max(base, 1e-9)
    cores = os.cpu_count() or 1
    return {
        "metric": "fit_scaling",
        "devices": n_devices,
        "host_cores": cores,
        "global_batch": batch_size,
        "samples_per_sec": sps,
        "scaling_speedup": round(speedup, 2),
        "scaling_efficiency": round(speedup / max(counts[-1], 1), 3),
        # forced-host devices burn real cores (see multidevice_summary):
        # the honest ceiling on an M-core box is min(devices, M)
        "efficiency_vs_host_cores": round(
            speedup / min(counts[-1], cores), 3),
        "per_device_peak_hbm_bytes": peak_by_count,
        "sharded_fsdp": {
            "fsdp": n_devices,
            "samples_per_sec": round(srate, 1),
            "per_device_peak_hbm_bytes": round(max(speaks.values())),
            # exact params+opt_state shard bytes per device, replicated
            # vs rule-sharded on the SAME mesh — the 1/fsdp memory claim
            # as a number (whole-process peaks above include batches,
            # prefetch copies and transients)
            "params_opt_bytes_per_device_replicated": state_replicated,
            "params_opt_bytes_per_device_sharded": state_sharded,
            "params_opt_shrink": round(
                state_replicated / max(state_sharded, 1), 2),
        },
        "sharded_tp": {
            "mesh": {"data": 1, "fsdp": tp_fsdp, "tensor": tp_tensor},
            "samples_per_sec": round(tprate, 1),
            "per_device_peak_hbm_bytes": round(max(tppeaks.values())),
            "params_opt_bytes_per_device": tp_state,
            "params_opt_shrink": round(
                state_replicated / max(tp_state, 1), 2),
        },
        "note": ("forced-host devices share the host's cores: fit "
                 f"scaling here caps near {min(n_devices, cores)}x; on "
                 "a real pod each chip computes off-host, so the "
                 "ceiling is the device count"),
    }


def input_pipeline_summary(tiny: bool = False, n_files: int = 8,
                           per_file: int = 512, dim: int = 64,
                           batch_size: int = 128, workers=(1, 4)) -> dict:
    """Input-pipeline A/B (ISSUE 15): the same small fit fed three ways
    — in-memory arrays (the ceiling: zero input work per step), and a
    TFRecord corpus streamed through the parallel shard pipeline at
    `pipeline_workers` 1 vs 4 — recording samples/sec, the per-leg
    `training_input_wait_ms` p50, and the `training_input_bound`
    verdict. The acceptance claim is pipeline-fed ≥ 0.9x in-memory at
    workers≥4; the single-worker leg is the baseline that shows what
    the worker pool buys. `host_effective_parallelism` (the PR 3/10
    spin-probe convention) records how many cores the host actually
    granted — on a starved box the 4-worker leg cannot beat that
    ceiling, and the JSON self-documents it."""
    import tempfile

    import numpy as np

    from analytics_zoo_tpu.data import tfrecord as tfr
    from analytics_zoo_tpu.data.dataset import TPUDataset
    from analytics_zoo_tpu.learn import trainer
    from analytics_zoo_tpu.observability import get_registry

    if tiny:
        n_files, per_file, batch_size = 4, 96, 32

    def make_model():
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras import layers as L
        model = Sequential([
            L.Dense(128, input_shape=(dim,), activation="relu"),
            L.Dense(64, activation="relu"),
            L.Dense(1, activation="sigmoid"),
        ])
        model.compile("adam", "binary_crossentropy")
        return model

    reg = get_registry()

    def leg(factory_ds, x=None, y=None):
        """Warm fit (compiles off the clock), cleared wait histogram,
        timed fit; returns (samples/sec, wait_p50_ms, input_bound)."""
        model = make_model()
        kw: dict = dict(batch_size=batch_size, epochs=1,
                        device_cache=False)
        if factory_ds is not None:
            n = factory_ds.n_samples()
            kw["x"], kw["y"] = None, None
            kw["batch_iter_factory"] = \
                lambda epoch: factory_ds.iter_train(1, seed=epoch)
        else:
            n = len(y)
            kw["x"], kw["y"] = x, y
        trainer.fit_keras(model, seed=0, **kw)
        wait_hist = reg.get("training_input_wait_ms")
        wait_hist.child().clear()
        t0 = time.perf_counter()
        trainer.fit_keras(model, seed=1, **kw)
        dt = time.perf_counter() - t0
        steps = n // batch_size
        p50 = wait_hist.percentile(0.5)
        bound = reg.get("training_input_bound").value()
        return (steps * batch_size / dt,
                round(0.0 if p50 != p50 else p50, 3), round(bound, 4))

    with tempfile.TemporaryDirectory() as d:
        rs = np.random.RandomState(0)
        for s in range(n_files):
            recs = []
            for _ in range(per_file):
                xv = rs.randn(dim).astype(np.float32)
                # ImageNet-style encoding: the feature rides as raw
                # bytes (one wire field — decodes at memory speed) and
                # parse_fn frombuffers it, like a real image corpus;
                # a float_list here would benchmark python varint
                # walking instead of the pipeline
                recs.append(tfr.encode_example({
                    "x": xv.tobytes(),
                    "y": np.asarray([float(xv.sum() > 0)], np.float32)}))
            tfr.write_tfrecord(os.path.join(d, f"part-{s:05d}.tfrecord"),
                               recs)

        def parse(ex):
            return (np.frombuffer(ex["x"][0], np.float32),
                    np.asarray(ex["y"], np.float32))

        def make_ds(w):
            return TPUDataset.from_tfrecord(
                os.path.join(d, "part-*.tfrecord"), parse,
                batch_size=batch_size, shuffle_buffer=1024,
                pipeline_workers=w)

        x_mem, y_mem = make_ds(1).materialize()
        mem_sps, _, _ = leg(None, x=np.asarray(x_mem), y=np.asarray(y_mem))

        sps, wait_p50, bound = {}, {}, {}
        for w in workers:
            sps[str(w)], wait_p50[str(w)], bound[str(w)] = leg(make_ds(w))

    from bench_serving import _measure_host_parallelism
    host_par = round(_measure_host_parallelism(1.0), 2)

    w_hi = str(max(workers))
    w_lo = str(min(workers))
    return {
        "metric": "input_pipeline_ab",
        "corpus_records": n_files * per_file,
        "corpus_files": n_files,
        "batch_size": batch_size,
        "in_memory_samples_per_sec": round(mem_sps, 1),
        "pipeline_samples_per_sec": {k: round(v, 1)
                                     for k, v in sps.items()},
        "pipeline_vs_memory": round(sps[w_hi] / max(mem_sps, 1e-9), 3),
        "worker_speedup": round(sps[w_hi] / max(sps[w_lo], 1e-9), 2),
        "input_wait_p50_ms": wait_p50,
        "input_bound": bound,
        "host_cores": os.cpu_count() or 1,
        "host_effective_parallelism": host_par,
        "note": ("pipeline workers burn host cores: on a starved box "
                 "the multi-worker leg caps at the measured "
                 "host_effective_parallelism, not the worker count"),
    }


def _leg_bert():
    """BERT-base seq 128 through Estimator.fit, the cost-analysis roofline
    for the same program, and the input-pipeline A/B (a small fit in the
    same process)."""
    from analytics_zoo_tpu import init_orca_context

    tiny = os.environ.get("BENCH_TINY") == "1"
    if tiny:
        cfg = dict(vocab=512, hidden=128, n_block=2, n_head=2, seq_len=64,
                   inter=256, batch=8, steps=6, steps_per_run=3)
    else:
        cfg = dict(
            vocab=30522, hidden=768, n_block=12, n_head=12, seq_len=128,
            inter=3072,
            # batch 256 measured ~2-4 MFU points above 128 on v5e (more
            # work per dispatch amortizes the per-run host turnaround)
            batch=int(os.environ.get("BENCH_BATCH", 256)),
            steps=int(os.environ.get("BENCH_STEPS", 48)),
            steps_per_run=int(os.environ.get("BENCH_SPR", 24)))

    init_orca_context(cluster_mode="local")
    dev, peak = _mesh_peak_flops()

    mfu, tokens_s, step_ms, loss, spread, flops_step = _measure_bert(
        peak, use_flash=os.environ.get("BENCH_FLASH") == "1",
        remat=os.environ.get("BENCH_REMAT") == "1", **cfg)

    out = {
        "metric": "bert_base_train_mfu_via_estimator_fit",
        "value": round(mfu * 100, 2),
        "unit": "%",
        "vs_baseline": round(mfu / 0.35, 4),
        "tokens_per_sec": round(tokens_s, 1),
        "step_ms": round(step_ms, 2),
        # (max - min) / min of the timed fits, as MFU points
        "mfu_run_spread_pct": round(mfu * 100 * spread, 2),
        **_device_fields(dev),
        "final_loss": float(loss),
    }

    # cost-analysis roofline (ISSUE 6): the trainer's automatic
    # XLA-counted numbers for the SAME workload, no analytic flops
    # model. `mfu_agreement` is the acceptance check (within 10% of the
    # hand-counted headline) computed as a pure FLOP-count ratio —
    # cost flops/step over analytic flops/step — because the
    # accountant's snapshot covers only the LAST timed fit and the
    # headline the fastest of three; the timing basis cancels only in
    # the FLOP ratio.
    from analytics_zoo_tpu.observability import get_accountant
    rl = get_accountant().snapshot("train")
    out["mfu_cost_analysis_pct"] = round(rl["mfu"] * 100, 2)
    out["mfu_agreement"] = round(
        rl["flops"] / max(cfg["steps"], 1) / flops_step, 3)
    out["hbm_utilization_pct"] = round(rl["hbm_utilization"] * 100, 2)

    # Input-pipeline A/B (ISSUE 15): tfrecord-fed fit at workers 1 vs 4
    # against the in-memory ceiling, with the measured input-stall
    # gauges — the host-side leg of the roofline story.
    if os.environ.get("BENCH_INPUT", "1") == "1":
        ip = input_pipeline_summary(tiny=tiny)
        out["input_pipeline_sps_memory"] = ip["in_memory_samples_per_sec"]
        out["input_pipeline_sps_workers"] = ip["pipeline_samples_per_sec"]
        out["input_pipeline_vs_memory"] = ip["pipeline_vs_memory"]
        out["input_pipeline_worker_speedup"] = ip["worker_speedup"]
        out["input_pipeline_wait_p50_ms"] = ip["input_wait_p50_ms"]
        out["input_pipeline_input_bound"] = ip["input_bound"]
        out["input_pipeline_host_parallelism"] = \
            ip["host_effective_parallelism"]
    print(json.dumps(out))


LEGS = {"bert": _leg_bert, "longseq": _leg_longseq}


def main():
    leg = os.environ.get("BENCH_LEG")
    if leg:
        return LEGS[leg]()

    # The parent: no jax in this process, chip legs strictly in sequence.
    here = os.path.dirname(os.path.abspath(__file__))
    me = [sys.executable, os.path.abspath(__file__)]
    tiny = os.environ.get("BENCH_TINY") == "1"

    out = _run_leg("bert", me, timeout=1800,
                   env=dict(os.environ, BENCH_LEG="bert"))

    # Long-sequence headline: flash attention at seq 2048 — the regime
    # the Pallas kernels exist for (full-attention activations would not
    # fit; O(T) memory keeps the MXU busy).
    if not tiny and os.environ.get("BENCH_LONGSEQ", "1") == "1":
        out.update(_run_leg("longseq", me, timeout=1800,
                            env=dict(os.environ, BENCH_LEG="longseq")))

    # NCF throughput/HBM-utilization. BENCH_CALIBRATE=1 also runs the
    # Adam-shaped streaming sweep and the chained-matmul sweep in that
    # child and installs them as the session roofline, so the live
    # %-of-achievable gauge reads against what this chip delivered.
    if not tiny and os.environ.get("BENCH_NCF", "1") == "1":
        r = _run_leg("ncf", [sys.executable,
                             os.path.join(here, "bench_ncf.py")],
                     timeout=900, env=dict(os.environ, BENCH_CALIBRATE="1"))
        out["ncf_samples_per_sec"] = r["value"]
        out["ncf_hbm_utilization_pct"] = r["hbm_utilization_pct"]
        out["ncf_step_ms"] = r["step_ms"]
        out["ncf_step_ms_unfused"] = r["step_ms_unfused"]
        out["ncf_bound"] = r["bound"]
        out["ncf_device"] = r["device"]
        out["session_hbm_gbps"] = r["achieved_hbm_gbps"]
        out["session_mxu_tflops"] = r["achieved_mxu_tflops"]
        out["ncf_pct_of_achievable_bound"] = r["pct_of_achievable_bound"]
        # the LIVE gauge version (ISSUE 6): XLA-counted bytes over the
        # calibrated session roofline, straight from
        # roofline_hbm_utilization{kind="train"}
        out["ncf_pct_of_achievable_bound_live"] = \
            r["ncf_pct_of_achievable_bound_live"]
        out["ncf_achieved_hbm_gbps_live"] = r["ncf_achieved_hbm_gbps_live"]

    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
