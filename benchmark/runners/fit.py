"""Runner of traffic kind `fit`: one `Estimator.fit` call of whole
epochs is the window.

Set-up builds the model (weights on the device from the seed in one
jitted call), checks its forward and its first training step against the
plain reference, makes the data, and runs two warm-up fits of one epoch of the exact dataset: the first
compiles (or loads from the cache) the epoch program, which is
specialised to the dataset's length, and places the data; the second
gives the steady epoch time. The window is ONE `est.fit(epochs=k)` with
k the whole number of epochs nearest to `--seconds`, timed from call to return
plus a block on the parameters. `fit_samples_per_s` = k x samples per
epoch / that time: nothing is cut mid-epoch, and the per-call host work
(optimizer init, placement) is paid once, as in a real job."""

from __future__ import annotations

import importlib
import json
import math
import time

from benchmark import compare, harness, metrics


def _optimizer(spec):
    if isinstance(spec, str):
        return spec
    import optax
    return getattr(optax, spec["optax"])(**spec.get("kwargs", {}))


def _loss(spec):
    if isinstance(spec, str):
        return spec
    from analytics_zoo_tpu.ops import objectives
    kw = {k: v for k, v in spec.items() if k != "name"}
    return objectives.get(spec["name"], **kw)


def reference_check(family, model, config, traffic, seed):
    """The system's outputs at the seeded weights on a few seeded samples
    against the plain float32 reference (`benchmark/compare.py`)."""
    chk = config["reference_check"]
    x = family.check_inputs(config, traffic, seed, chk["samples"])
    err = compare.errors(family.system_outputs(model, model.params, x),
                         family.reference_outputs(model.params, x, config))
    ok = compare.within(err, chk)
    harness.log(f"reference_check {json.dumps(err)} atol={chk['atol']} "
                f"rms={chk['rms']} ok={ok}")
    return ok


def system_step(family, model, config, traffic, params, batch, n):
    """One training step of the SYSTEM through its public entry point,
    as (loss, gradients): `Estimator.fit` of one batch of `n` samples
    with the cell's own fit arguments (mixed precision and all), the
    cell's loss, dropout off, and plain SGD at learning rate 1, so that
    the parameters move by exactly the gradient the system computed. The
    optimizer's own update rule is not held to a reference (PERF.md,
    section 7)."""
    import jax
    import jax.numpy as jnp
    import optax
    from analytics_zoo_tpu.learn.estimator import Estimator
    stepped = family.without_dropout(model, config, traffic)
    kept = model.params
    try:
        # a copy: fit may donate the buffers it is given
        stepped.params = jax.tree_util.tree_map(jnp.copy, params)
        before = jax.device_get(stepped.params)
        est = Estimator.from_keras(stepped, optimizer=optax.sgd(1.0),
                                   loss=_loss(config["fit"]["loss"]))
        kw = {k: v for k, v in traffic.get("fit_kwargs", {}).items()
              if k != "steps_per_run"}
        hist = est.fit(batch, epochs=1, batch_size=n, **kw)
        after = jax.device_get(est.model.params)
    finally:
        model.params = kept
    return float(hist["loss"][0]), jax.tree_util.tree_map(
        lambda a, b: a - b, before, after)


def step_check(family, model, config, traffic, seed):
    """The system's first training step (loss and every gradient) against
    float32 `jax.value_and_grad` of the plain reference at the same
    seeded weights and batch (`benchmark/compare.py`)."""
    chk = config["reference_check"]
    n = chk["step_samples"]
    batch = family.step_batch(config, traffic, seed, n)
    loss, grads = system_step(family, model, config, traffic,
                              model.params, batch, n)
    ref_loss, ref_grads = family.reference_loss_and_grads(
        model.params, batch, config)
    err = compare.step_errors(loss, grads, ref_loss, ref_grads)
    ok = compare.step_within(err, chk)
    harness.log(f"step_check {json.dumps(err)} loss_atol={chk['loss_atol']} "
                f"grad_rel={chk['grad_rel']} "
                f"grad_leaf_rel={chk['grad_leaf_rel']} ok={ok}")
    return ok


def run(ctx) -> str:
    import jax
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.observability.registry import get_registry

    config, traffic, clock = ctx["config"], ctx["traffic"], ctx["clock"]
    seed = ctx["seed"]
    family = importlib.import_module("benchmark.models." + config["family"])

    init_orca_context(cluster_mode="local", **traffic.get("mesh_axes", {}))
    clock.mark("context_and_backend")
    model = family.build(config, traffic)
    model.params = family.init_params(model, harness.seed_key(seed))
    jax.block_until_ready(model.params)
    clock.mark("model_init")
    ref_ok = reference_check(family, model, config, traffic, seed)
    clock.mark("reference_check")
    ref_ok &= step_check(family, model, config, traffic, seed)
    clock.mark("step_check")
    data, n = family.fit_data(config, traffic, seed)
    clock.mark("data")

    est = Estimator.from_keras(model,
                               optimizer=_optimizer(config["fit"]["optimizer"]),
                               loss=_loss(config["fit"]["loss"]))
    fit_kw = dict(batch_size=traffic["batch_size"],
                  seed=seed % (2 ** 31 - 1), **traffic.get("fit_kwargs", {}))
    losses = list(est.fit(data, epochs=1, **fit_kw)["loss"])
    jax.block_until_ready(est.model.params)
    clock.mark("warmup_fit_compile_or_cache_load")
    t0 = time.perf_counter()
    losses += est.fit(data, epochs=1, **fit_kw)["loss"]
    jax.block_until_ready(est.model.params)
    epoch_s = time.perf_counter() - t0
    clock.mark("warmup_epoch")
    clock.report()

    # the nearest whole number of epochs: with `floor`, an epoch time a
    # hair either side of seconds/k would flip k from run to run
    k = max(1, round(ctx["seconds"] / epoch_s))
    registry = get_registry()
    reg_before = registry.snapshot()
    compiles_before = ctx["compiles"].n
    setup_s = clock.total()
    t0 = time.perf_counter()
    with harness.MemorySampler() as mem:
        hist = est.fit(data, epochs=k, **fit_kw)
        jax.block_until_ready(est.model.params)
    window_s = time.perf_counter() - t0
    harness.log_memory_stats()
    compiles_in_window = ctx["compiles"].n - compiles_before
    reg_after = registry.snapshot()
    window_losses = [float(v) for v in hist["loss"]]
    losses += window_losses
    samples_per_s = k * n / window_s
    harness.log("epoch_losses " + json.dumps([round(v, 6) for v in losses]))
    harness.log(f"window epochs={k} epoch_s_warm={epoch_s:.4f} "
                f"window_s={window_s:.4f} samples_per_s={samples_per_s:.4f} "
                f"compiles_in_window={compiles_in_window}")

    finite = all(math.isfinite(v) for v in losses)
    correct = bool(ref_ok and finite and len(window_losses) == k
                   and losses[-1] < losses[0] and compiles_in_window == 0)
    values = {"fit_samples_per_s": samples_per_s, "setup_s": setup_s}
    sources = {"registry_before": reg_before, "registry_after": reg_after,
               "window_s": window_s,
               "harness": {"compiles_in_window": compiles_in_window}}
    traced = None
    if ctx["trace"]:
        flops = family.flops_per_sample(config, traffic)
        if flops is not None and not ctx["rehearse"]:
            sources["harness"]["mfu_pct"] = metrics.mfu_percent(
                flops, samples_per_s, ctx["device"]["kind"], ctx["chips"])
        traced_samples = traffic["trace_epochs"] * n
        sources["traced_work"] = {
            name: {k: v * traced_samples for k, v in work.items()}
            for name, work in family.kernel_work_per_sample(
                config, traffic).items()}
        with harness.TracedWindow(
                "in_fit_call",
                harness.op_patterns_for(ctx["per_layer"])) as traced:
            est.fit(data, epochs=traffic["trace_epochs"], **fit_kw)
            jax.block_until_ready(est.model.params)
    return harness.result_line(
        ctx, correct=correct, attempted=k, failed=0 if correct else k,
        values=values, sources=sources, traced=traced,
        sampled_memory=mem.max_bytes)
