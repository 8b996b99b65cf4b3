"""Orca-style unified Estimator — the north-star `zoo.orca.learn` entry point.

Mirrors the surface of `Estimator.from_graph/from_keras/from_torch`
(`orca/learn/tf/estimator.py:148`, `orca/learn/tf2/tf_ray_estimator.py:183`,
`orca/learn/pytorch/estimator.py:50`) and the engine-agnostic Scala Estimator
(`zoo/.../pipeline/estimator/Estimator.scala:68`). One implementation instead
of the reference's five per-engine wrappers: everything lowers to the same
pjit'd train loop (`learn/trainer.py`).

- `from_keras(model)` — a `analytics_zoo_tpu.keras` model (Sequential/Model).
- `from_fn(forward_fn, init_fn, loss, optimizer)` — the `from_graph`
  analogue: a pure forward function + parameter initializer.
- `from_torch(model, loss, optimizer)` — converts a torch.nn module's
  architecture+weights to the native layer library (the reference instead
  embeds CPython in the JVM via JEP, `TorchModel.scala:34`; on TPU the model
  must become an XLA program, so conversion replaces embedding).

Failure handling reproduces `InternalDistriOptimizer.train`'s retry loop
(`Topology.scala:1255-1337`): on a training exception, reload the latest
snapshot and resume, up to `retry_times` failures within a sliding window.

Data: accepts TPUDataset, XShards of {"x","y"}, (x, y) ndarrays, pandas
DataFrame (+feature/label cols) — the `to_dataset` conversion surface
(`orca/learn/tf/estimator.py:225-276`).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from analytics_zoo_tpu.common import triggers as tg
from analytics_zoo_tpu.common.context import get_context
from analytics_zoo_tpu.data.dataset import TPUDataset
from analytics_zoo_tpu.data.shards import XShards
from analytics_zoo_tpu.keras.engine import KerasNet
from analytics_zoo_tpu.learn import checkpoint as ckpt_mod
from analytics_zoo_tpu.learn import trainer

log = logging.getLogger("analytics_zoo_tpu.estimator")


class QuantizationQualityError(ValueError):
    """The int8-quantized model's eval metrics drifted past the
    configured tolerance from the f32 baseline — the quality gate of
    `Estimator.evaluate(..., quantize="int8", quality_tolerance=...)`
    refusing to bless a quantized artifact for serving (the
    OpenVINOInt8Suite predict-equivalence contract, made a hard
    gate)."""


def to_dataset(data, batch_size: int = -1, batch_per_thread: int = -1,
               feature_cols: Optional[Sequence[str]] = None,
               label_cols: Optional[Sequence[str]] = None) -> TPUDataset:
    """Normalize any supported data form into a TPUDataset."""
    if isinstance(data, TPUDataset):
        return data
    if isinstance(data, XShards):
        return TPUDataset.from_xshards(data, batch_size, batch_per_thread)
    try:
        import pandas as pd
        if isinstance(data, pd.DataFrame):
            if not feature_cols:
                raise ValueError("DataFrame input needs feature_cols")
            return TPUDataset.from_dataframe(data, feature_cols, label_cols,
                                             batch_size, batch_per_thread)
    except ImportError:
        pass
    return TPUDataset.from_ndarrays(data, batch_size, batch_per_thread)


class Estimator:
    """Unified estimator facade (`orca/learn/base_estimator.py:43`)."""

    def __init__(self, model: KerasNet, model_dir: Optional[str] = None):
        self.model = model
        self.model_dir = model_dir
        self._load_ckpt: Optional[Tuple[str, Optional[int]]] = None
        # (torch optimizer, torch scheduler) whose per-epoch schedule is
        # resolved at fit() time when steps_per_epoch was not given
        self._torch_optim_spec = None

    # -- constructors ------------------------------------------------------
    @staticmethod
    def from_keras(keras_model: KerasNet, model_dir: Optional[str] = None,
                   optimizer=None, loss=None, metrics=None) -> "Estimator":
        """`Estimator.from_keras`. The model may already be compiled; compile
        args given here override."""
        if optimizer is not None or loss is not None:
            keras_model.compile(optimizer or "adam", loss or "mse", metrics)
        return Estimator(keras_model, model_dir)

    @staticmethod
    def from_fn(forward_fn: Callable, init_fn: Callable,
                loss, optimizer, metrics=None,
                model_dir: Optional[str] = None) -> "Estimator":
        """`from_graph` analogue: forward_fn(params, x, training, rng) plus
        init_fn(rng, input_shape)->params."""
        model = _FnModel(forward_fn, init_fn)
        model.compile(optimizer, loss, metrics)
        return Estimator(model, model_dir)

    @staticmethod
    def from_model_fn(model_fn: Callable, init_fn: Callable,
                      optimizer="adam", metrics=None,
                      model_dir: Optional[str] = None) -> "Estimator":
        """`TFEstimator.from_model_fn` analogue (`tfpark/estimator.py:47`):
        model_fn(params, features, labels, mode, rng) returns a dict spec —
        {"loss": scalar} in "train"/"eval" mode, {"predictions": tree} in
        "predict" mode. The loss is computed INSIDE model_fn (the
        tf.estimator contract), so the compile loss is a pass-through."""
        model = _ModelFnModel(model_fn, init_fn)
        model.compile(optimizer, model._spec_loss, metrics)
        return Estimator(model, model_dir)

    @staticmethod
    def from_torch(model, loss=None, optimizer=None, metrics=None,
                   scheduler=None, steps_per_epoch: Optional[int] = None,
                   model_dir: Optional[str] = None) -> "Estimator":
        """Convert a torch.nn module (Sequential-style) into the native layer
        library, carrying its trained weights. Supported: Linear, Conv2d,
        ReLU/Tanh/Sigmoid/Softmax/GELU, MaxPool2d/AvgPool2d, Flatten,
        Dropout, BatchNorm1d/2d, Embedding, LSTM/GRU (single layer).

        `loss` may be a torch.nn loss module and `optimizer` a
        torch.optim.Optimizer (+ optional torch LR `scheduler`) — the
        reference's TorchLoss/TorchOptim interop (`TorchOptim.scala:41-60`);
        both convert once to jax/optax equivalents, so the hot path stays
        pure XLA. Per-epoch schedulers (torch's StepLR-stepped-per-epoch
        idiom) need `steps_per_epoch`; when omitted it is computed at
        fit() time from the dataset size and batch size."""
        from analytics_zoo_tpu.learn.torch_bridge import (
            convert_torch_loss, convert_torch_module,
            convert_torch_optimizer)
        native = convert_torch_module(model)
        # torch itself is importable here — convert_torch_module already ran
        import torch
        import torch.nn as nn
        if isinstance(loss, nn.Module):
            loss = convert_torch_loss(loss)
        torch_spec = None
        if isinstance(optimizer, torch.optim.Optimizer):
            if scheduler is not None and steps_per_epoch is None:
                # real steps/epoch known only at fit(); provisional now
                torch_spec = (optimizer, scheduler)
            optimizer = convert_torch_optimizer(
                optimizer, scheduler, steps_per_epoch or 1)
        elif scheduler is not None:
            raise ValueError("scheduler is only used with a torch optimizer")
        native.compile(optimizer or "adam", loss or "mse", metrics)
        est = Estimator(native, model_dir)
        est._torch_optim_spec = torch_spec
        return est

    # -- training with retry/resume ---------------------------------------
    def fit(self, data, epochs: int = 1, batch_size: Optional[int] = None,
            validation_data=None, checkpoint_trigger=None,
            feature_cols=None, label_cols=None, seed: int = 0,
            **fit_kwargs) -> Dict[str, List[float]]:
        """`fit_kwargs` pass through to the trainer loop: `steps_per_run=k`
        fuses k steps per dispatch, `mixed_precision=True` runs bf16
        compute with f32 masters, `prefetch=False` disables the
        background batch pipeline, `metrics_report_s=30` logs a periodic
        registry digest,
        `sharding_rules=True` (or a `parallel.sharding.ShardingRules`)
        runs the GSPMD-sharded fit — params/opt_state sharded over the
        mesh's fsdp axis with the same rule table serving's sharded
        placement consumes (`ZooConfig.sharded_fit` / ZOO_SHARDED_FIT=1
        is the config spelling; see
        docs/ProgrammingGuide/distributed-training.md),
        `fused_optimizer=True` swaps a stock adam/adamw for the fused
        Pallas update kernels (`ZooConfig.fused_optimizer` /
        ZOO_FUSED_OPT=1; one HBM pass per leaf, sparse segment path for
        declared embedding tables under `lazy_embeddings=True`).
        Step/loss/throughput telemetry lands in the process-wide
        `MetricsRegistry` either way (`observability/`)."""
        ds = to_dataset(data, batch_size=batch_size or 32,
                        feature_cols=feature_cols, label_cols=label_cols)
        # a pre-built TPUDataset's own batch/shuffle settings win over fit()
        # defaults (the dataset carries the contract, `tf_dataset.py:116`)
        if ds.batch_size != -1:
            batch_size = ds.batch_size
        elif batch_size is None:
            batch_size = 32
        dp = get_context().mesh.data_parallel_size
        lazy = ds.x is None  # disk-tier FeatureSet / TFRecord stream bridge
        if self._torch_optim_spec is not None:
            # per-epoch torch scheduler: now that the dataset + resolved
            # batch are known, rebuild the optax schedule with the true
            # steps/epoch. Lazy datasets step at global_batch (their
            # iter_train contract); in-memory data steps at the resolved
            # fit batch_size.
            from analytics_zoo_tpu.learn.torch_bridge import \
                convert_torch_optimizer
            topt, tsched = self._torch_optim_spec
            # multi-process fit_keras steps each process through its LOCAL
            # shard at batch_size/process_count per step, so steps/epoch is
            # n_local // per_process_batch — using the global batch here
            # would make the rebuilt schedule decay process_count× early.
            step_batch = (ds.global_batch(dp) if lazy
                          else max(1, batch_size // jax.process_count()))
            spe = max(1, ds.n_samples() // step_batch)
            self.model.optimizer = convert_torch_optimizer(
                topt, tsched, steps_per_epoch=spe)
            for cache in ("_train_cache", "_eval_cache", "_predict_cache"):
                if hasattr(self.model, cache):
                    delattr(self.model, cache)

        # callers may supply their own per-epoch batch source (nnframes
        # re-runs stochastic sample preprocessing each epoch this way
        # WITHOUT restarting fit — optimizer state must survive epochs)
        batch_iter_factory = fit_kwargs.pop("batch_iter_factory", None)
        if batch_iter_factory is None:
            batch_iter_factory = (
                (lambda epoch: ds.iter_train(dp, seed=seed + epoch))
                if lazy else None)
            if batch_iter_factory is not None:
                # datasets that read DISJOINT files per host (TFRecord
                # via pipeline.host_shard) declare it so fit_keras's
                # multi-process streaming-duplication guard admits them
                batch_iter_factory.shards_per_host = getattr(
                    ds, "shards_per_host", False)
        if lazy and self.model.params is None \
                and hasattr(ds, "first_sample"):
            # cheap shape probe: one record, not a shuffle-buffer fill
            sx, _ = ds.first_sample()
            batched = jax.tree_util.tree_map(
                lambda a: np.expand_dims(a, 0), sx)
            self.model.ensure_built(batched, jax.random.PRNGKey(seed))

        val = None
        if validation_data is not None:
            vds = to_dataset(validation_data, batch_size=batch_size,
                             feature_cols=feature_cols, label_cols=label_cols)
            val = vds.materialize()
        elif ds.val is not None:
            val = ds.val.materialize()

        cfg = get_context().config
        if self.model_dir:
            self.model.set_checkpoint(self.model_dir)
        if self._load_ckpt is not None:
            self._restore(*self._load_ckpt)
            self._load_ckpt = None

        failures: List[float] = []
        epoch_done = getattr(self, "_resume_epoch", 0)
        history: Dict[str, List[float]] = {}
        while epoch_done < epochs:
            try:
                h = trainer.fit_keras(
                    self.model, ds.x, ds.y, batch_size=batch_size,
                    epochs=epochs - epoch_done, validation_data=val,
                    shuffle=ds.shuffle,
                    checkpoint_trigger=checkpoint_trigger,
                    seed=seed + epoch_done,
                    batch_iter_factory=batch_iter_factory, **fit_kwargs)
                for k, v in h.items():
                    history.setdefault(k, []).extend(v)
                break
            except (KeyboardInterrupt, jax.errors.JaxRuntimeError):
                raise
            except ValueError:
                raise  # config errors are not retryable (IllegalArgument)
            except Exception as e:  # noqa: BLE001 — retry semantics
                now = time.time()
                failures = [t for t in failures
                            if now - t < cfg.failure.retry_time_interval_s]
                failures.append(now)
                if len(failures) > cfg.failure.retry_times:
                    log.error("Exceeded %d failures within %ds window; "
                              "giving up", cfg.failure.retry_times,
                              cfg.failure.retry_time_interval_s)
                    raise
                # counted only once the budget check passed: the final
                # fatal failure re-raises above and is NOT a recovery
                from analytics_zoo_tpu.observability import get_registry
                get_registry().counter(
                    "training_retries_total",
                    "training failures recovered by snapshot-restore "
                    "retry").inc()
                log.warning("Training failure (%s: %s); restoring latest "
                            "snapshot and retrying (%d/%d)",
                            type(e).__name__, e, len(failures),
                            cfg.failure.retry_times)
                epoch_done = self._restore_latest() or epoch_done
        self._resume_epoch = 0
        return history

    def _restore_latest(self) -> Optional[int]:
        if not self.model_dir:
            return None
        found = ckpt_mod.latest_checkpoint(self.model_dir)
        if found is None:
            return None
        params, _, meta = ckpt_mod.load_checkpoint(self.model_dir)
        self.model.params = self.model._remap_loaded(params)
        return int(meta.get("epoch", 0)) if meta else None

    def _restore(self, path: str, version: Optional[int]):
        params, _, meta = ckpt_mod.load_checkpoint(path, version)
        # remap saved layer names onto this instance's auto-generated names
        # (save order == stack order; the pytree store preserves dict order)
        self.model.params = self.model._remap_loaded(params)
        self._resume_epoch = int(meta.get("epoch", 0)) if meta else 0

    def program_scopes(self):
        """`{module: {instruction: {scope, direction, also}}}` of the last
        fit's step program, made on request from the shapes the fit
        dispatched with (`trainer.program_scopes`; what a
        `fit(profile_steps=...)` capture is joined to:
        docs/ProgrammingGuide/observability.md "Device time by scope")."""
        from analytics_zoo_tpu.learn.trainer import program_scopes
        return program_scopes(self.model)

    # -- inference ---------------------------------------------------------
    def predict(self, data, batch_per_thread: int = 32, feature_cols=None
                ) -> np.ndarray:
        ds = to_dataset(data, batch_per_thread=batch_per_thread,
                        feature_cols=feature_cols)
        x, _ = ds.materialize()
        preds = self.model.predict(x, batch_per_thread=batch_per_thread)
        return preds

    def evaluate(self, data, batch_per_thread: int = 32, metrics=None,
                 feature_cols=None, label_cols=None,
                 quantize: Optional[str] = None,
                 quality_tolerance: Optional[float] = None,
                 baseline_metrics: Optional[Dict[str, float]] = None
                 ) -> Dict[str, float]:
        """`quantize="int8"` evaluates the POST-TRAINING-QUANTIZED
        model (per-output-channel int8 weights,
        `serving/quantization.py`) instead of the f32 one, and — with
        `quality_tolerance` — enforces the quality gate: every metric
        must sit within `quality_tolerance` (absolute) of the f32
        baseline or the call raises `QuantizationQualityError`, so a
        quantized model that lost accuracy can never be blessed for
        serving. The baseline is evaluated on the spot unless
        `baseline_metrics` (a prior f32 `evaluate()` result) is
        passed; the return carries the quantized metrics plus the
        baseline as `baseline_<name>` entries."""
        if quantize is not None:
            return self._evaluate_quantized(
                data, batch_per_thread, metrics, feature_cols,
                label_cols, quantize, quality_tolerance,
                baseline_metrics)
        ds = to_dataset(data, batch_per_thread=batch_per_thread,
                        feature_cols=feature_cols, label_cols=label_cols)
        if metrics:
            # detection mAP is corpus-level (per-class global score sort) —
            # it cannot stream through the jitted metric accumulators, so
            # it takes the predict-then-evaluate path
            from analytics_zoo_tpu.models.detection_eval import DetectionMAP
            mlist = metrics if isinstance(metrics, (list, tuple)) \
                else [metrics]
            det = [m for m in mlist if isinstance(m, DetectionMAP)]
            if det:
                if len(det) != len(mlist):
                    raise ValueError(
                        "DetectionMAP cannot be mixed with streaming "
                        "metrics in one evaluate() call")
                x, y = ds.materialize()
                flat = self.model.predict(
                    x, batch_per_thread=batch_per_thread)
                out: Dict[str, float] = {}
                for i, m in enumerate(det):
                    # disambiguate repeated evaluators (e.g. VOC07 + area)
                    tag = m.name if len(det) == 1 else f"{m.name}_{i}"
                    res = m.evaluate_flat(flat, y)
                    out[tag] = res.result()[0]
                    out.update({f"AP_{n}" if len(det) == 1
                                else f"AP_{n}_{i}": ap
                                for n, ap in res.ap_by_class()})
                return out
        from analytics_zoo_tpu.ops import metrics as zmetrics
        ms = zmetrics.resolve(metrics) if metrics else None
        x, y = ds.materialize()
        if isinstance(self.model, _ModelFnModel) and not ms \
                and not self.model.metrics:
            # spec loss needs the raw features → dedicated eval path
            return self.model._evaluate_spec(x, y, batch_per_thread)
        return self.model.evaluate(x, y,
                                   batch_per_thread=batch_per_thread,
                                   metrics=ms)

    def _evaluate_quantized(self, data, batch_per_thread, metrics,
                            feature_cols, label_cols, quantize,
                            quality_tolerance,
                            baseline_metrics) -> Dict[str, float]:
        """The quantized leg of `evaluate`: f32 baseline (given or
        evaluated here), then the same evaluation with the model's
        params swapped for the int8 rewrite (the layers dispatch on the
        quantized keys; the f32 master params are restored whatever
        happens), then the tolerance gate."""
        if quantize != "int8":
            raise ValueError(
                f"Unsupported quantize={quantize!r}; only 'int8'")
        from analytics_zoo_tpu.serving.quantization import \
            quantize_model_params
        base = baseline_metrics if baseline_metrics is not None else \
            self.evaluate(data, batch_per_thread=batch_per_thread,
                          metrics=metrics, feature_cols=feature_cols,
                          label_cols=label_cols)
        if self.model.params is None:
            raise ValueError("Model has no parameters; fit or load first")
        f32_params = self.model.params
        q = quantize_model_params(self.model,
                                  jax.device_get(f32_params))
        try:
            self.model.params = q
            quantized = self.evaluate(
                data, batch_per_thread=batch_per_thread,
                metrics=metrics, feature_cols=feature_cols,
                label_cols=label_cols)
        finally:
            self.model.params = f32_params
        if quality_tolerance is not None:
            # `not (|Δ| <= tol)`, NOT `|Δ| > tol`: a NaN metric (an
            # int8 rewrite that overflowed) compares False either way,
            # and the gate must REFUSE what it cannot prove within
            # tolerance rather than bless it
            drifted = {
                name: (base[name], quantized[name])
                for name in quantized
                if name in base
                and not (abs(quantized[name] - base[name])
                         <= quality_tolerance)}
            if drifted:
                detail = ", ".join(
                    f"{n}: f32={b:.6g} int8={q_:.6g} "
                    f"(|Δ|={abs(q_ - b):.6g})"
                    for n, (b, q_) in sorted(drifted.items()))
                raise QuantizationQualityError(
                    f"int8 quantization drifted {len(drifted)} metric(s) "
                    f"past the quality gate (tolerance "
                    f"{quality_tolerance:g}): {detail}. Refusing to "
                    "bless the quantized model; raise the tolerance "
                    "only if this accuracy loss is acceptable, or keep "
                    "serving f32/bf16.")
        out = dict(quantized)
        out.update({f"baseline_{k}": v for k, v in base.items()})
        return out

    # -- persistence (`orca` save/load + load_orca_checkpoint) ------------
    def get_model(self):
        return self.model

    def save(self, path: str) -> str:
        self.model.save_weights(path)
        return path

    def load(self, path: str) -> "Estimator":
        self.model.load_weights(path)
        return self

    def load_orca_checkpoint(self, path: str,
                             version: Optional[int] = None) -> "Estimator":
        """Resume from a `model.<version>` checkpoint
        (`orca/learn/tf/estimator.py:125` semantics; version=None → latest)."""
        self._load_ckpt = (path, version)
        return self


class _ModelFnModel(KerasNet):
    """tf.estimator-style adapter: model_fn(params, features, labels, mode,
    rng) → spec dict. Training feeds labels through `apply` by closing over
    the batch (the trainer calls apply(params, x) then loss(y, out); here
    `apply` returns features untouched in predict mode and the loss path
    re-invokes model_fn with labels)."""

    def __init__(self, model_fn: Callable, init_fn: Callable):
        super().__init__()
        self.model_fn = model_fn
        self.init_fn = init_fn

    def build(self, rng, input_shape):
        return self.init_fn(rng, input_shape)

    def apply(self, params, inputs, *, training=False, rng=None):
        if training:
            # defer: loss path recombines with labels in _spec_loss via
            # the (params, features) closure the trainer maintains
            return _DeferredSpec(self, params, inputs, rng)
        spec = self.model_fn(params, inputs, None, "predict", rng)
        return spec["predictions"]

    def _spec_loss(self, y_true, deferred):
        if not isinstance(deferred, _DeferredSpec):
            # eval path delivers plain predictions; the spec loss needs the
            # raw features, so evaluation goes through evaluate() (which
            # dispatches to _evaluate_spec) or explicit compiled metrics
            raise ValueError(
                "from_model_fn: the spec loss is only computable in the "
                "training path; compile explicit metrics for validation "
                "(metrics=[...]) or call Estimator.evaluate()")
        spec = self.model_fn(deferred.params, deferred.features, y_true,
                             "train", deferred.rng)
        return spec["loss"]

    def _evaluate_spec(self, x, y, batch_per_thread: int = 32
                       ) -> Dict[str, float]:
        """Mean spec loss over batches — model_fn in eval mode."""
        import jax

        from analytics_zoo_tpu.learn import trainer as _trainer

        @jax.jit
        def batch_loss(params, xb, yb):
            spec = self.model_fn(params, xb, yb, "eval", None)
            return spec["loss"]

        total, n = 0.0, 0
        for xb, yb, _count in _trainer.iter_batches(
                x, y, batch_per_thread, shuffle=False,
                drop_remainder=False):
            total += float(batch_loss(self.params, xb, yb))
            n += 1
        return {"loss": total / max(n, 1)}

    def compute_output_shape(self, input_shape):
        return None


class _DeferredSpec:
    """Carries (params, features, rng) from apply to the loss call."""

    def __init__(self, model, params, features, rng):
        self.model = model
        self.params = params
        self.features = features
        self.rng = rng


class _FnModel(KerasNet):
    """Adapter: pure forward/init functions behave like a KerasNet so the
    shared trainer drives them (the `from_graph` lowering)."""

    def __init__(self, forward_fn: Callable, init_fn: Callable):
        super().__init__()
        self.forward_fn = forward_fn
        self.init_fn = init_fn

    def build(self, rng, input_shape):
        return self.init_fn(rng, input_shape)

    def apply(self, params, inputs, *, training=False, rng=None):
        return self.forward_fn(params, inputs, training=training, rng=rng)

    def compute_output_shape(self, input_shape):
        return None
