"""NCF (MovieLens-scale) training throughput through `Estimator.fit` — the
other BASELINE workload (`BASELINE.json` configs[0]; reference
`pyzoo/zoo/models/recommendation/neuralcf.py:30`, `apps/recommendation-ncf`).

NCF is memory-bound, so MFU is the wrong lens (docs/ROOFLINE.md): the
MLP is ~27k matmul params while dense Adam sweeps every embedding-table
parameter (3 reads + 3 writes of p/m/v plus the gradient read = 7
array-wide passes) each step. The JSON therefore reports samples/sec
(the reference community metric) PLUS the roofline-correct utilization:
achieved HBM bytes/s over the chip's peak bandwidth, alongside the
(tiny, expected) MFU. `vs_baseline` compares against a 100k
samples/sec/chip yardstick (no absolute CPU number exists in the
reference tree — BASELINE.md).

Since ISSUE 9 the bench is an A/B: the plain optax sweep is timed
first, then the fused Pallas optimizer kernels
(`fit(fused_optimizer=True)`, the default leg) — `step_ms` /
`step_ms_unfused` / `fused_step_speedup` record the gap, and
`ncf_pct_of_achievable_bound_live` reads the trainer's roofline gauge
for the FUSED program (target ≥60 under BENCH_CALIBRATE=1).
BENCH_FUSED=0 turns leg B back into a second unfused run; BENCH_LAZY=1
adds the sparse segment path for the tables (which does not lower on TPU
yet: ROADMAP D12).

This process initializes the accelerator, so run it on its own or as a
child of a parent that stays off jax (`bench.py`). A device with no
published peak (the CPU backend included) is an error before any fit
runs; utilization is measured against all local chips.

    python bench_ncf.py
    BENCH_TINY=1 python bench_ncf.py     # cut shapes, plumbing check
"""

from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from analytics_zoo_tpu.utils.roofline import peak_flops, peak_hbm


def main():
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.models.recommendation import NeuralCF

    tiny = os.environ.get("BENCH_TINY") == "1"
    if tiny:
        users, items, n, batch, spr = 200, 100, 4096, 512, 4
    else:
        # MovieLens-20M scale: 138k users, 27k items. 4M samples = 512
        # steps/epoch in one dispatch of the device-cached epoch
        # program; data is device-resident after warmup.
        users, items = 138_000, 27_000
        n = int(os.environ.get("BENCH_N", 1 << 22))
        batch = int(os.environ.get("BENCH_BATCH", 8192))
        spr = int(os.environ.get("BENCH_SPR", 64))

    init_orca_context(cluster_mode="local")
    # unknown device_kind raises here, before any fit
    dev = jax.devices()[0]
    n_dev = jax.device_count()
    mesh_peak_hbm = peak_hbm(dev) * n_dev
    mesh_peak_flops = peak_flops(dev) * n_dev
    ncf = NeuralCF(user_count=users, item_count=items, class_num=2,
                   mf_embed=64, user_embed=64, item_embed=64,
                   hidden_layers=(128, 64, 32))
    est = Estimator.from_keras(ncf.model, optimizer="adam",
                               loss="sparse_categorical_crossentropy")

    rs = np.random.RandomState(0)
    x = np.stack([rs.randint(1, users, n), rs.randint(1, items, n)],
                 axis=1).astype(np.int32)
    y = rs.randint(0, 2, n).astype(np.int32)
    # BENCH_LAZY=1 additionally routes the tables through the sparse
    # path. UNFUSED lazy measured SLOWER than dense (XLA set-scatter
    # copies the full table — docs/ROOFLINE.md round-4 note); the FUSED
    # segment kernel (pallas/segment_update.py) removes exactly that
    # copy plus the dense-grad materialization, so lazy is worth
    # re-measuring under BENCH_LAZY=1 BENCH on real chips.
    lazy = os.environ.get("BENCH_LAZY", "0") == "1"
    base_kw = dict(epochs=1, batch_size=batch, steps_per_run=spr,
                   lazy_embeddings=lazy)

    # warmup leg A: pinned unfused — base_kw must not resolve against a
    # fleet-wide ZOO_FUSED_OPT=1, or the timed unfused leg below would
    # pay its full compile inside the measurement
    est.fit((x, y), **base_kw, fused_optimizer=False)

    # BENCH_CALIBRATE=1: measure the session's ACHIEVED bandwidth/MXU
    # rate BEFORE the timed fits and install it as the session roofline
    # (observability/roofline.py) — the live
    # `roofline_hbm_utilization{kind="train"}` gauge the timed fits
    # publish is then %-of-ACHIEVABLE, the same yardstick as the manual
    # pct_of_achievable_bound math below, with no byte model
    achieved_gbps = achieved_tflops = None
    if os.environ.get("BENCH_CALIBRATE") == "1":
        n_params_cal = sum(int(np.prod(np.shape(p))) for p in
                           jax.tree_util.tree_leaves(ncf.model.params))
        achieved_gbps = _calibrate_hbm(n_params_cal)
        achieved_tflops = _calibrate_mxu()
        from analytics_zoo_tpu.observability import set_session_roofline
        set_session_roofline(hbm_gbps=achieved_gbps,
                             tflops=achieved_tflops)

    def timed_fit(estimator, **kw):
        best = float("inf")
        h = None
        for _ in range(1 if tiny else 3):  # fastest of three timed fits
            t0 = time.perf_counter()
            h = estimator.fit((x, y), **kw)
            best = min(best, time.perf_counter() - t0)
        return best, h

    # A/B (ISSUE 9): the plain optax sweep first, then the fused Pallas
    # kernels LAST so the live roofline gauges read the fused program.
    # Fresh models per leg: the fused toggle changes the opt-state tree
    # and must not warm-start from the other leg's params.
    dt_unfused, _ = timed_fit(est, **base_kw, fused_optimizer=False)

    ncf = NeuralCF(user_count=users, item_count=items, class_num=2,
                   mf_embed=64, user_embed=64, item_embed=64,
                   hidden_layers=(128, 64, 32))
    est = Estimator.from_keras(ncf.model, optimizer="adam",
                               loss="sparse_categorical_crossentropy")
    fused = os.environ.get("BENCH_FUSED", "1") == "1"
    est.fit((x, y), **base_kw, fused_optimizer=fused)      # warmup leg B
    dt, hist = timed_fit(est, **base_kw, fused_optimizer=fused)
    steps = n // batch
    samples_s = steps * batch / dt

    # roofline accounting (docs/ROOFLINE.md):
    params = ncf.model.params
    n_params = sum(int(np.prod(np.shape(p))) for p in
                   jax.tree_util.tree_leaves(params))
    n_emb = sum(int(np.prod(np.shape(p)))
                for k, p in jax.tree_util.tree_leaves_with_path(params)
                if "embed" in str(k).lower())
    n_matmul = n_params - n_emb
    # Adam floor: read grad + read/write each of p, m, v = 7 f32 passes
    # over EVERY parameter per step, PLUS the dense embedding-gradient
    # materialization the round-5 xplane profile showed is a first-class
    # cost (docs/ROOFLINE.md NCF breakdown): a zeros broadcast + a
    # scatter-add output, each a full write pass over every embedding
    # table = 2 more passes over n_emb. Per-sample activation traffic is
    # noise next to either at MovieLens scale. The fused kernels hit
    # this floor by construction (one blocked pass); the unfused optax
    # chain runs 10-12 passes against it — that gap IS the A/B.
    # lazy mode has no dense-sweep byte count worth reporting: the
    # fused segment path touches only batch rows (a different, far
    # smaller floor), the unfused one copies whole tables.
    bytes_step = None if lazy else 4 * (7 * n_params + 2 * n_emb)
    flops_step = 6 * n_matmul * batch
    hbm_util = (None if bytes_step is None
                else (bytes_step * steps / dt) / mesh_peak_hbm)
    mfu = (flops_step * steps / dt) / mesh_peak_flops

    # calibration ran pre-fit (so the live gauges saw the session
    # roofline); here only the manual bound comparison remains
    pct_achievable = None
    if achieved_gbps is not None and bytes_step is not None:
        floor_s = bytes_step / (achieved_gbps * 1e9)
        pct_achievable = round(100 * floor_s / (dt / steps), 1)

    # the LIVE version of the same number (ISSUE 6): the trainer's
    # roofline_hbm_utilization{kind="train"} gauge — XLA-counted bytes
    # over the calibrated session roofline, zero manual math. The
    # analytic pct above and this should roughly agree; where they
    # split, XLA's count includes traffic the 7-pass model ignores,
    # and the timing bases differ (the live number covers the LAST
    # timed fit, the manual one the fastest of three).
    from analytics_zoo_tpu.observability import get_accountant, get_registry
    live = get_accountant().snapshot("train")
    live_pct = round(live["hbm_utilization"] * 100, 1)
    live_gbps = round(live["achieved_hbm_gbps"], 1)

    # one measured fused sweep, observed when the fused leg built its step
    fused_ms = None
    fs = get_registry().snapshot().get("training_fused_update_ms")
    if fs and fs.get("series"):
        fused_ms = round(fs["series"][0]["p50"], 3)

    print(json.dumps({
        "metric": "ncf_train_samples_per_sec_via_estimator_fit",
        "value": round(samples_s, 1),
        "unit": "samples/s",
        "vs_baseline": round(samples_s / 100_000.0, 4),
        "step_ms": round(dt / steps * 1e3, 3),
        "step_ms_unfused": round(dt_unfused / steps * 1e3, 3),
        "fused_optimizer": fused,
        "fused_step_speedup": round(dt_unfused / dt, 3),
        "fused_update_ms": fused_ms,
        "hbm_utilization_pct": (None if hbm_util is None
                                else round(hbm_util * 100, 2)),
        "mfu_pct": round(mfu * 100, 3),
        "bound": ("memory (row-sparse embedding updates)" if lazy
                  else "memory (Adam sweep + dense-grad "
                       "materialization; see docs/ROOFLINE.md NCF "
                       "per-op breakdown)"),
        "lazy_embeddings": lazy,
        "device": dev.device_kind,
        "platform": dev.platform,
        "device_count": n_dev,
        "achieved_hbm_gbps": achieved_gbps,
        "achieved_mxu_tflops": achieved_tflops,
        "pct_of_achievable_bound": pct_achievable,
        "ncf_pct_of_achievable_bound_live": live_pct,
        "ncf_achieved_hbm_gbps_live": live_gbps,
        "final_loss": float(hist["loss"][-1]),
    }))


def _calibrate_hbm(n_params: int, iters: int = 1000) -> float:
    """Achieved GB/s for a 7-pass (read g,p,m,v; write p,m,v) f32 sweep
    of n_params elements, `iters` iterations in one dispatch (1000
    iterations ≈ 0.7-2 s of pure sweep, so the one dispatch and readback
    in the timed window are noise)."""
    import jax.numpy as jnp

    g = jnp.full((n_params,), 1e-6, jnp.float32)
    p = jnp.zeros((n_params,), jnp.float32)
    m = jnp.zeros((n_params,), jnp.float32)
    v = jnp.zeros((n_params,), jnp.float32)

    @jax.jit
    def run(p, m, v, g):
        def body(_, carry):
            p, m, v = carry
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * (g * g)
            p = p - 1e-3 * m / (jnp.sqrt(v) + 1e-8)
            return (p, m, v)
        return jax.lax.fori_loop(0, iters, body, (p, m, v))

    r = run(p, m, v, g)
    float(jnp.sum(r[0]))                      # force completion (warm)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        r = run(p, m, v, g)
        float(jnp.sum(r[0]))
        best = min(best, time.perf_counter() - t0)
    return round(iters * 7 * 4 * n_params / best / 1e9, 1)


def _calibrate_mxu(n: int = 4096, iters: int = 400) -> float:
    """Achieved bf16 TFLOP/s for a chained n×n matmul, `iters` in one
    dispatch (~0.3-0.6 s of pure MXU work). Companion to _calibrate_hbm:
    a chip can deliver its bandwidth and still run sustained compute
    slow, so the session yardstick needs both axes."""
    import jax.numpy as jnp

    a = jnp.full((n, n), 0.01, jnp.bfloat16)
    b = jnp.full((n, n), 0.01, jnp.bfloat16)

    @jax.jit
    def run(a, b):
        # y = x.b has entries 0.01*n*x; rescale by exactly that factor so
        # the carry stays ~0.01 (a stronger scale underflows bf16 to zero
        # within ~20 iterations and the sweep times zero matrices)
        inv = jnp.asarray(1.0 / (0.01 * n), jnp.bfloat16)

        def body(_, x):
            return jnp.dot(x, b) * inv
        return jax.lax.fori_loop(0, iters, body, a)

    float(jnp.sum(run(a, b).astype(jnp.float32)))   # warm
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        float(jnp.sum(run(a, b).astype(jnp.float32)))
        best = min(best, time.perf_counter() - t0)
    return round(iters * 2 * n**3 / best / 1e12, 1)


if __name__ == "__main__":
    main()
