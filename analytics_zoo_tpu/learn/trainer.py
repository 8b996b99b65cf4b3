"""The distributed training loop — TPU-native `InternalDistriOptimizer`.

The reference's hot loop (`Topology.scala:1160-1337`, via BigDL
DistriOptimizer) does, per iteration: broadcast weights from the BlockManager,
local forward/backward per executor thread, scatter-reduce gradient slices,
per-slice optimizer update, allgather weights. Here the whole iteration is ONE
jit-compiled XLA program: parameters live replicated (or fsdp-sharded) on the
mesh, the batch is split over the mesh's batch axes, and GSPMD inserts the
gradient all-reduce over ICI automatically. Triggers, checkpoints, metrics and
the retry/resume semantics (`Topology.scala:1255-1337`) are host-side around
that one program.

Batch-size contract (`tfpark/tf_dataset.py:116-157`): training takes a GLOBAL
`batch_size` that must divide by the data-parallel size; eval/predict take
per-device `batch_per_thread`.
"""

from __future__ import annotations

import functools
import itertools
import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from analytics_zoo_tpu.common.context import get_context
from analytics_zoo_tpu.common import triggers as tg
from analytics_zoo_tpu.observability.registry import get_registry
from analytics_zoo_tpu.observability.tracing import get_tracer
from analytics_zoo_tpu.ops.objectives import ProjectedLogits

log = logging.getLogger("analytics_zoo_tpu.trainer")


class _TrainingMetrics:
    """Training telemetry published into the process-wide registry — the
    same spine the serving pipeline and HTTP frontend feed, so one
    `GET /metrics` scrape answers for both sides of the platform.
    Registration is get-or-create: repeated fits converge on the same
    families and counters accumulate across fits (that is the Prometheus
    model; per-fit views come from `MetricsRegistry.delta`)."""

    def __init__(self, registry=None):
        reg = registry if registry is not None else get_registry()
        self.step_ms = reg.histogram(
            "training_step_ms",
            "per-step wall time, averaged over each epoch's device sync")
        self.steps = reg.counter("training_steps_total",
                                 "optimizer steps run")
        self.samples = reg.counter("training_samples_total",
                                   "training samples consumed")
        self.epochs = reg.counter("training_epochs_total",
                                  "epochs completed")
        self.loss = reg.gauge("training_loss", "mean loss of the last epoch")
        self.throughput = reg.gauge("training_samples_per_sec",
                                    "last epoch's training throughput")
        self.val = reg.gauge("training_validation_metric",
                             "last validation metrics, labeled by name")
        self.resumes = reg.counter(
            "training_resumes_total",
            "training runs continued from a checkpoint by auto_resume")
        self.step_retries = reg.counter(
            "training_step_retries_total",
            "failed/hung training steps retried by the step watchdog")
        self.mesh_axis = reg.gauge(
            "training_mesh_axis_size",
            "device-mesh axis extents of the sharded fit, labeled by "
            "axis (a tensor extent > 1 means column/row-parallel "
            "placement is live)")
        self.input_wait_ms = reg.histogram(
            "training_input_wait_ms",
            "per-step wall time the training loop sat blocked on the "
            "input-pipeline prefetch queue before dispatching (device "
            "idle, host decoding — the input-stall histogram)")
        self.input_bound = reg.gauge(
            "training_input_bound",
            "fraction of the last epoch's wall time the step loop "
            "spent blocked on the prefetch queue (0 = device-bound, "
            "1 = fully input-bound; the measured verdict on whether "
            "a fit needs more pipeline_workers)")
        self.device_time_share = reg.gauge(
            "training_device_time_share",
            "share (%) of the device's operation time in the last "
            "`fit(profile_steps=...)` capture by the step program's own "
            "scopes (first three parts) and direction "
            "(`observability/device_time.py`)")
        self.fit_phase_ms = reg.histogram(
            "training_fit_phase_ms",
            "wall time of each leaf span of a fit call (`_FitTrace`), "
            "observed when the span closes: phase = the span's name "
            "without `fit.`; scope = call (once a fit call), epoch "
            "(inside an epoch, on the loop's thread) or worker (the "
            "prefetch thread, overlapping the loop)")

    def mesh_axes(self, mesh) -> None:
        """Publish the sharded fit's mesh factorization (one series per
        axis) so a scrape can tell a pure-fsdp fit from a tensor-
        parallel one without reading logs. `mesh=None` (a non-sharded
        fit) resets every axis to 1 — a later replicated fit must not
        leave a previous fit's factorization reading as live."""
        if mesh is None:
            from analytics_zoo_tpu.common.mesh import AXIS_NAMES
            sizes = {a: 1 for a in AXIS_NAMES}
        else:
            sizes = mesh.axis_sizes
        for ax, size in sizes.items():
            self.mesh_axis.set(size, axis=ax)

    def device_time_shares(self, rows) -> None:
        """The last capture's breakdown; a scope the capture before had
        and this one has not reads 0."""
        for key in self.device_time_share.label_keys():
            self.device_time_share.set(0.0, **dict(key))
        for row in rows:
            self.device_time_share.set(row["share_pct"], scope=row["scope"],
                                       direction=row["direction"])

    def epoch(self, steps: int, n_seen: int, dt: float, mean_loss: float):
        step_ms = dt / max(steps, 1) * 1e3
        self.step_ms.observe(step_ms)
        self.steps.inc(steps)
        self.samples.inc(n_seen)
        self.epochs.inc()
        self.loss.set(mean_loss)
        self.throughput.set(n_seen / max(dt, 1e-9))
        return step_ms


_fit_call_ids = itertools.count(1)   # process-wide: `fit-<n>` names a call


class _FitTrace:
    """One fit call as a span tree in the process-wide tracer
    (`cat="training"`, one `trace_id` for the call), and the one counter
    family observed at the same boundaries, `training_fit_phase_ms`:

        fit                  root: `with _FitTrace(...) as trace`
          fit.prepare ...    the call's phases, one after another: `enter`
          fit.epoch          `begin_epoch`
            fit.dispatch ... leaves inside an epoch: `with trace.phase(..)`
          fit.finish         `enter("finish")` in the `finally`

    The call's phases and its epochs follow one another and never nest,
    so each is opened by closing the one before it, and the root's exit
    closes what an exception left open. Every span is a scoped span of
    `observability/tracing.py`, so a profiler capture that runs meanwhile
    holds it as a host event on the device's clock."""

    def __init__(self, telemetry: _TrainingMetrics, **root_args):
        self._tracer = get_tracer()
        self._telemetry = telemetry
        self.trace_id = f"fit-{next(_fit_call_ids)}"
        self._root = self._span("fit", root_args)
        self._open: List[Any] = []      # the open phase or epoch

    def _span(self, name: str, args: Dict[str, Any]):
        """The root or an epoch; a streaming dataset does not say how
        many steps an epoch has, and the argument is then left out."""
        return self._tracer.span(
            name, trace_id=self.trace_id, cat="training",
            args={k: v for k, v in args.items() if v is not None})

    def __enter__(self) -> "_FitTrace":
        self._root.__enter__()
        return self

    def __exit__(self, *exc):
        self._advance(None)
        return self._root.__exit__(*exc)

    def _advance(self, span) -> None:
        while self._open:
            self._open.pop().__exit__(None, None, None)
        if span is not None:
            self._open.append(span.__enter__())

    def phase(self, phase: str, scope: str, **args):
        """A leaf span `fit.<phase>` that observes its duration in
        `training_fit_phase_ms{phase, scope}` as it closes."""
        return self._tracer.phase(
            "fit." + phase, self._telemetry.fit_phase_ms,
            trace_id=self.trace_id, cat="training", args=args,
            phase=phase, scope=scope)

    def enter(self, phase: str, **args) -> None:
        """The call's next phase (`scope="call"`)."""
        self._advance(self.phase(phase, "call", **args))

    def begin_epoch(self, epoch: int, steps: Optional[int]) -> None:
        """`fit.epoch` has no series: `training_step_ms` has one
        observation an epoch already."""
        self._advance(self._span("fit.epoch",
                                 {"epoch": epoch, "steps": steps}))

    def input_wait(self):
        """`fit.input_wait`: the loop blocked on the prefetch queue. Its
        close is the one observation of `training_input_wait_ms`."""
        return self._tracer.phase(
            "fit.input_wait", self._telemetry.input_wait_ms,
            trace_id=self.trace_id, cat="training")


# ---------------------------------------------------------------------------
# Data plumbing: numpy structures -> shard-ready batches
# ---------------------------------------------------------------------------
def _tree_len(x) -> int:
    leaves = jax.tree_util.tree_leaves(x)
    if not leaves:
        raise ValueError("Empty input data")
    return int(np.shape(leaves[0])[0])


def _tree_take(x, idx):
    return jax.tree_util.tree_map(lambda a: np.asarray(a)[idx], x)


def _num_batches(n: int, batch: int, drop_remainder: bool) -> int:
    return n // batch if drop_remainder else -(-n // batch)


def iter_batches(x, y=None, batch_size: int = 32, shuffle: bool = False,
                 seed: int = 0, drop_remainder: bool = True,
                 pad_to_batch: bool = False):
    """Yield (x_batch, y_batch, real_count) of numpy arrays. Static batch
    shapes (pad or drop) keep jit from recompiling — the TPU analogue of the
    reference's `hard_code_batch_size` (`tf_dataset.py:158-173`)."""
    n = _tree_len(x)
    idx = np.arange(n)
    if shuffle:
        np.random.RandomState(seed).shuffle(idx)
    nb = _num_batches(n, batch_size, drop_remainder and not pad_to_batch)
    for b in range(nb):
        sel = idx[b * batch_size:(b + 1) * batch_size]
        real = len(sel)
        if real < batch_size:
            if pad_to_batch:
                sel = np.concatenate([sel, np.repeat(sel[-1:],
                                                     batch_size - real)])
            else:
                continue
        xb = _tree_take(x, sel)
        yb = _tree_take(y, sel) if y is not None else None
        yield xb, yb, real


def check_global_batch(batch_size: int, dp: int, fsdp: int = 1) -> None:
    """`dp` is the full batch-splitting extent (data × fsdp — BOTH are
    batch axes, `common/mesh.BATCH_AXES`); `fsdp` names the fsdp part so
    the error can say which axis the caller actually configured."""
    if batch_size % dp != 0:
        if fsdp > 1:
            raise ValueError(
                f"global batch_size ({batch_size}) must be a multiple of "
                f"the batch-splitting extent {dp} = data ({dp // fsdp}) × "
                f"fsdp ({fsdp}) — the fsdp axis splits the batch too "
                f"(ZeRO-style sharding rides the data path). Use a "
                f"batch_size that is a multiple of {dp}, or shrink the "
                f"fsdp axis to a divisor of your batch.")
        raise ValueError(
            f"global batch_size ({batch_size}) must be a multiple of the "
            f"data-parallel size ({dp}) — the reference's total-core-number "
            f"contract (tf_dataset.py:142-147)")


def _put_batch(tree, mesh, stacked: bool = False):
    """mesh=None → single default device (non-distributed escape hatch).
    stacked=True for (steps, batch, ...) multi-step stacks.

    Multi-process (`jax.distributed`): each process passes its LOCAL batch
    shard (the per-executor-partition contract of the reference) and the
    global array is assembled across hosts — device_put cannot target
    non-addressable devices."""
    if mesh is None:
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a)), tree)
    sharding = mesh.stacked_batch_sharding() if stacked \
        else mesh.batch_sharding()
    if jax.process_count() > 1:
        batch_dim = 1 if stacked else 0

        def put(a):
            a = np.asarray(a)
            gshape = list(a.shape)
            gshape[batch_dim] *= jax.process_count()
            return jax.make_array_from_process_local_data(
                sharding, a, tuple(gshape))
        return jax.tree_util.tree_map(put, tree)
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.asarray(a), sharding), tree)


def _materialize(x):
    """THE host-sync point of the training loop: every device→host readback
    in fit_keras funnels through here so tests can count syncs (one per
    logging interval, not one per step)."""
    return jax.device_get(x)


def _step_with_watchdog(step_fn, args, retries: int,
                        timeout_s: Optional[float], retry_counter,
                        iteration: int):
    """One training step under the fault-tolerance contract
    (`Topology.scala:1255-1337`'s retry role, made local): a failed step
    is retried up to `retries` times; with `timeout_s` the step runs
    under a watchdog thread so a hung dispatch surfaces as TimeoutError
    instead of a silent stall. The `trainer.step` fault-injection point
    fires before device dispatch, so an injected failure retries without
    touching the donated parameter buffers. A REAL mid-execution failure
    may consume them — then the retry fails too and the caller's
    emergency-checkpoint path takes over."""
    import threading
    from analytics_zoo_tpu.common import faults
    attempts = 0
    while True:
        try:
            if timeout_s is None:
                faults.fire("trainer.step", iteration=iteration,
                            attempt=attempts)
                return step_fn(*args)
            box: Dict[str, Any] = {}
            cancelled = threading.Event()
            done = threading.Event()

            def run():
                try:
                    faults.fire("trainer.step", iteration=iteration,
                                attempt=attempts)
                    if cancelled.is_set():
                        return          # timed out during the stall:
                    box["out"] = step_fn(*args)   # don't consume buffers
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    box["exc"] = e
                finally:
                    done.set()

            t = threading.Thread(target=run, daemon=True,
                                 name="train-step-watchdog")
            t.start()
            if not done.wait(timeout_s):
                cancelled.set()
                # grace window before declaring it hung: a step that is
                # merely SLOW (step 0 pays XLA compilation) completes
                # here and its result is perfectly valid — retrying
                # instead would race the still-running dispatch on the
                # donated parameter buffers and abort the run
                if done.wait(timeout_s) and "out" in box:
                    log.warning(
                        "training step %d exceeded the %ss watchdog but "
                        "completed in the grace window; using its result "
                        "(raise step_timeout_s if this recurs)",
                        iteration, timeout_s)
                    return box["out"]
                raise TimeoutError(
                    f"training step {iteration} exceeded the "
                    f"{timeout_s}s watchdog")
            if "exc" in box:
                raise box["exc"]
            if "out" not in box:
                raise RuntimeError(
                    f"training step {iteration} was cancelled by an "
                    "earlier watchdog timeout")
            return box["out"]
        except Exception as e:  # noqa: BLE001 — retry policy owns this
            attempts += 1
            if attempts > retries:
                raise
            retry_counter.inc()
            log.warning(
                "training step %d failed (%s: %s); retry %d/%d",
                iteration, type(e).__name__, e, attempts, retries)


class _Prefetcher:
    """Background-thread batch prefetch: prepares + device_puts the next
    item while the device runs the current one. Depth-bounded so host
    memory stays flat. The TPU analogue of the reference FeatureSet's
    prefetching cached tier.

    Stall accounting (ISSUE 15): every consumer `__next__` times how
    long it sat blocked on the queue — that wait IS the device's input
    stall (the step can't dispatch until the batch exists). `wait_s`
    accumulates the epoch total; `wait_span()` gives the scoped span
    put around each get (the fit's `fit.input_wait`, whose close is the
    per-step histogram's observation). An always-full queue reads ~0:
    the host pipeline is keeping up."""

    _END = object()

    def __init__(self, source_iter, transfer, depth: int = 2,
                 wait_span=None):
        import queue
        import threading
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._err = None
        self._stop = False
        self._queue_mod = queue
        self._wait_span = wait_span or functools.partial(
            get_tracer().span, "fit.input_wait", cat="training")
        self.wait_s = 0.0

        def worker():
            try:
                for item in source_iter:
                    out = transfer(item)
                    while not self._stop:
                        try:
                            self._q.put(out, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if self._stop:
                        return
            except BaseException as e:   # propagate to consumer
                self._err = e
            finally:
                # blocking put with stop checks: a full queue must not
                # swallow the END sentinel (the consumer would hang)
                while not self._stop:
                    try:
                        self._q.put(self._END, timeout=0.1)
                        break
                    except queue.Full:
                        continue

        self._t = threading.Thread(target=worker, daemon=True)
        self._t.start()

    def __iter__(self):
        return self

    def __next__(self):
        span = self._wait_span()
        with span:
            item = self._q.get()
        self.wait_s += span.duration
        if item is self._END:
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self):
        """Unblock and retire the worker (early exit via end_trigger)."""
        self._stop = True
        try:
            while True:
                self._q.get_nowait()
        except self._queue_mod.Empty:
            pass


def _chunk_batches(it, k: int):
    """Group (xb, yb, real) triples into lists of up to k for multi-step
    runs. The final short group is emitted as-is (compiled separately at
    most once per distinct length)."""
    group = []
    for item in it:
        group.append(item)
        if len(group) == k:
            yield group
            group = []
    if group:
        yield group


def _stack_group(group, mesh):
    """Stack k (xb, yb, real) batches into device-resident (k, B, ...)
    arrays sharded so the batch dim stays split over the mesh."""
    xs = jax.tree_util.tree_map(lambda *a: np.stack(a),
                                *[g[0] for g in group])
    ys = None
    if group[0][1] is not None:
        ys = jax.tree_util.tree_map(lambda *a: np.stack(a),
                                    *[g[1] for g in group])
    real = sum(g[2] for g in group)
    return (_put_batch(xs, mesh, stacked=True),
            _put_batch(ys, mesh, stacked=True) if ys is not None else None,
            real, len(group))


def _resolve_sharding_rules(sharding_rules, ctx):
    """Normalize the fit's `sharding_rules` knob: None consults the
    config passthrough (`ZooConfig.sharded_fit` / env ZOO_SHARDED_FIT),
    True means the default transformer table, a `ShardingRules` passes
    through. Returns a ShardingRules or None (replicated fit)."""
    if sharding_rules is None and ctx is not None \
            and getattr(ctx.config, "sharded_fit", False):
        sharding_rules = True
    if sharding_rules is True:
        from analytics_zoo_tpu.parallel.sharding import TRANSFORMER_RULES
        return TRANSFORMER_RULES
    if sharding_rules is False:
        return None
    return sharding_rules


def _step_shardings(mesh, param_shardings, opt_shardings):
    """The layout dict `_jit_donated` pins into the step/run programs."""
    return {"params": param_shardings, "opt": opt_shardings,
            "batch": mesh.batch_sharding(),
            "stacked": mesh.stacked_batch_sharding(),
            "rep": mesh.replicated()}


def _put_with_shardings(tree, shardings):
    """device_put every leaf onto its rule-derived NamedSharding. A
    leaf already carrying the target sharding passes through as the
    same buffer, so re-placing live sharded state is free; a host leaf
    (checkpoint restore) lands DIRECTLY on the sharded layout — the
    host array goes to device_put as-is (an eager jnp.asarray would
    first materialize the FULL leaf on the default device, OOMing
    exactly the bigger-than-one-chip model this path exists for)."""
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, s), tree, shardings)


def _put_replicated(tree, mesh):
    if mesh is None:
        return jax.tree_util.tree_map(lambda a: jax.device_put(a), tree)
    sharding = mesh.replicated()
    if jax.process_count() > 1:
        # every process holds the full value (same seed) → its local
        # shard of a replicated array IS the full array
        return jax.tree_util.tree_map(
            lambda a: jax.make_array_from_process_local_data(
                sharding, np.asarray(a), np.shape(a)), tree)
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sharding), tree)


# ---------------------------------------------------------------------------
# Core train/eval step builders
# ---------------------------------------------------------------------------
def _merge_state(params, state_updates):
    """Merge stateful-layer updates (nested dict subset) into params."""
    if not state_updates:
        return params
    merged = dict(params)
    for k, v in state_updates.items():
        if isinstance(v, dict) and isinstance(merged.get(k), dict):
            merged[k] = _merge_state(merged[k], v)
        else:
            merged[k] = v
    return merged


def _cast_tree(tree, dtype, only=jnp.float32):
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == only else a, tree)


def _shard_mapped_fused(fused_apply, shardings):
    """Run the fused optimizer sweep on fsdp-LOCAL shards: the whole
    `fused_apply` call goes through one `shard_map` whose specs are the
    rule table's own PartitionSpecs, so each device's kernels walk only
    its 1/fsdp slice of (params, moments, grads) and GSPMD never
    gathers state around the Pallas custom calls. The update is
    elementwise per leaf, so any partitioning is numerically exact;
    grads arrive already reduced across the batch axes (GSPMD inserts
    the all-reduce upstream to satisfy the entry specs)."""
    p_specs = jax.tree_util.tree_map(lambda s: s.spec, shardings["params"])
    o_specs = jax.tree_util.tree_map(lambda s: s.spec, shardings["opt"])
    mesh = jax.tree_util.tree_leaves(shardings["params"])[0].mesh
    return jax.shard_map(fused_apply, mesh=mesh,
                         in_specs=(p_specs, o_specs, p_specs),
                         out_specs=(p_specs, o_specs), check_vma=False)


def _make_one_step(apply_fn, loss_fn, optimizer, apply_and_state_fn,
                   mixed_precision, shardings=None):
    # fused-kernel optimizer (ISSUE 9): the transformation carries a
    # `fused_apply(grads, state, params) -> (params, state)` fast path
    # — the Pallas kernel writes new params/moments in place, so the
    # optax updates tree (and its extra HBM passes) never exists
    fused_apply = getattr(optimizer, "fused_apply", None)
    if fused_apply is not None and shardings is not None:
        fused_apply = _shard_mapped_fused(fused_apply, shardings)

    def one_step(params, opt_state, xb, yb, rng):
        def compute_loss(p):
            if mixed_precision:
                p = _cast_tree(p, jnp.bfloat16)
                # inputs are NOT cast here: float-encoded integer id
                # features (nnframes emits float32 ids) lose exactness
                # above 256 in bf16 → silently wrong embedding rows.
                # Matmul/conv layers cast their own float operands to the
                # param dtype instead (keras/layers.py _match_param_dtype).
            if apply_and_state_fn is not None:
                pred, state_upd = apply_and_state_fn(p, xb, training=True,
                                                     rng=rng)
            else:
                pred, state_upd = apply_fn(p, xb, training=True,
                                           rng=rng), {}
            if mixed_precision and not isinstance(pred, ProjectedLogits):
                # unformed logits stay in the step's type: the loss forms
                # them block by block and accumulates in float32 itself
                pred = jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32), pred)
            return loss_fn(yb, pred), state_upd

        # the scopes land in every operation's `op_name`, which xprof
        # groups device time by
        with jax.named_scope("fit_step/forward_backward"):
            (loss, state_upd), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(params)
            if mixed_precision:
                grads = _cast_tree(grads, jnp.float32, only=jnp.bfloat16)
                # stateful updates (BatchNorm moving stats) were computed
                # from the bf16-cast params — cast back so the f32 master
                # tree never picks up bf16 leaves (dtype drift + donation
                # mismatch)
                state_upd = _cast_tree(state_upd, jnp.float32,
                                       only=jnp.bfloat16)
        with jax.named_scope("fit_step/optimizer_update"):
            if fused_apply is not None:
                params, opt_state = fused_apply(grads, opt_state, params)
            else:
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
        params = _merge_state(params, state_upd)
        return params, opt_state, loss

    return one_step


def _jit_donated(fn, shardings, batch_key: str, n_extra_out: int):
    """jit with donated (params, opt_state) buffers. `shardings` (a
    sharded fit's rule-derived layout dict, see `_step_shardings`) pins
    explicit in/out shardings: params and opt_state arrive AND leave on
    the rule table's NamedShardings (so GSPMD cannot re-layout them and
    donation stays an in-place buffer reuse — in == out is the donation
    contract), the batch on the mesh's batch axes, rng and losses
    replicated. Without it, behavior is byte-for-byte the old jit."""
    if shardings is None:
        return jax.jit(fn, donate_argnums=(0, 1))
    bsh = shardings[batch_key]
    rep = shardings["rep"]
    in_sh = (shardings["params"], shardings["opt"], bsh, bsh, rep)
    out_sh = (shardings["params"], shardings["opt"]) + (rep,) * n_extra_out
    return jax.jit(fn, donate_argnums=(0, 1),
                   in_shardings=in_sh, out_shardings=out_sh)


class _StepProgram:
    """What `program_scopes` needs of the step program a fit built, kept
    beside it in `model._train_cache`: the jitted function, the abstract
    arguments of the fit's first dispatch (shape and dtype of every leaf,
    and the sharding of a committed one; never a buffer) and, once asked for, the table. A fit
    that nobody asks pays for the tuple of shapes and nothing else: no
    lowering, no text, no parse."""

    __slots__ = ("jitted", "abstract_args", "scopes")

    def __init__(self, jitted):
        self.jitted = jitted
        self.abstract_args = None
        self.scopes = None

    def saw(self, args) -> None:
        """The arguments of a dispatch, as shapes. Another dataset's
        length under the same `_train_cache` key is another program:
        its table is made anew."""
        # an uncommitted array's placement is no part of the program: with
        # it in the shapes the lowering differs from the dispatch's and
        # the compile is a fresh one (35-64 s a cell on the chip, PR 34)
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding
                if getattr(a, "committed", False) else None), args)
        if abstract != self.abstract_args:
            self.abstract_args, self.scopes = abstract, None


def program_scopes(model) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """`{module: {instruction: {scope, direction, also}}}` of the step
    program of `model`'s last fit (`observability/device_time.py`), made
    on request: the jitted step is lowered again from the shapes the fit
    dispatched with and asked for its executable, which JAX answers from
    memory while the process still holds the fit's (else from the
    persistent cache, else by compiling), and the compiled text is
    parsed. Memoised beside the step
    in `model._train_cache`, so under its key. `{}` before any fit."""
    cached = getattr(model, "_train_cache", None)
    program = cached[2] if cached is not None else None
    if program is None or program.abstract_args is None:
        return {}
    if program.scopes is None:
        from analytics_zoo_tpu.observability.device_time import scope_table
        t0 = time.perf_counter()
        lowered = program.jitted.lower(*program.abstract_args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        text = compiled.as_text()
        t3 = time.perf_counter()
        program.scopes = scope_table(text)
        log.info("step program's scopes: lowered in %.2f s, executable in "
                 "%.2f s, text (%d bytes) in %.2f s, %d instructions "
                 "placed in %.2f s", t1 - t0, t2 - t1, len(text), t3 - t2,
                 sum(len(m) for m in program.scopes.values()),
                 time.perf_counter() - t3)
    return program.scopes


def build_train_step(apply_fn: Callable, loss_fn: Callable,
                     optimizer: optax.GradientTransformation,
                     apply_and_state_fn: Optional[Callable] = None,
                     mixed_precision: bool = False,
                     lazy_specs=None, fused: bool = False,
                     shardings=None) -> Callable:
    """One iteration as a pure function. jit + sharded inputs → GSPMD emits
    the gradient all-reduce; donation reuses parameter buffers in HBM.
    Stateful layers (BatchNorm moving stats) return updates through the aux
    channel and are merged outside the gradient path.
    mixed_precision=True keeps f32 master params and runs the fwd/bwd
    matmuls in bf16 (MXU-native). `shardings` (from `_step_shardings`)
    pins the fsdp-sharded layout explicitly — the GSPMD fit. `fused`
    selects the Pallas fused-update paths (ISSUE 9): the segment
    one-step for declared embedding tables, `fused_apply` for the rest."""
    one_step = _pick_one_step(apply_fn, loss_fn, optimizer,
                              apply_and_state_fn, mixed_precision,
                              lazy_specs, fused, shardings)
    return _jit_donated(one_step, shardings, "batch", 1)


def build_train_run(apply_fn: Callable, loss_fn: Callable,
                    optimizer: optax.GradientTransformation,
                    apply_and_state_fn: Optional[Callable] = None,
                    mixed_precision: bool = False,
                    lazy_specs=None, fused: bool = False,
                    shardings=None) -> Callable:
    """Multi-step variant: one jit'd program `lax.scan`s over a
    (k, batch, ...) stack of batches, so k steps cost ONE dispatch and ONE
    loss readback. This is the framework's hot path — the analogue of the
    reference engine owning its hot loop (`Topology.scala:1160-1337`)."""
    one_step = _pick_one_step(apply_fn, loss_fn, optimizer,
                              apply_and_state_fn, mixed_precision,
                              lazy_specs, fused, shardings)

    def train_run(params, opt_state, xs, ys, rng):
        def body(carry, batch):
            params, opt_state, rng = carry
            rng, sub = jax.random.split(rng)
            xb, yb = batch
            params, opt_state, loss = one_step(params, opt_state, xb, yb,
                                               sub)
            return (params, opt_state, rng), loss

        (params, opt_state, rng), losses = jax.lax.scan(
            body, (params, opt_state, rng), (xs, ys))
        return params, opt_state, rng, losses

    return _jit_donated(train_run, shardings, "stacked", 2)


def build_device_epoch_run(apply_fn: Callable, loss_fn: Callable,
                           optimizer: optax.GradientTransformation,
                           apply_and_state_fn: Optional[Callable] = None,
                           mixed_precision: bool = False,
                           lazy_specs=None, fused: bool = False,
                           steps: int = 1,
                           batch: int = 1, shuffle: bool = True,
                           shardings=None) -> Callable:
    """Whole-epoch program over a DEVICE-RESIDENT dataset: shuffle
    (on-device permutation), batch (on-device gather) and all `steps`
    train steps run inside ONE `lax.scan` dispatch. Eliminates every
    per-step host→device transfer and per-step dispatch, which a
    small-model step (NCF: a few ms) cannot hide."""
    one_step = _pick_one_step(apply_fn, loss_fn, optimizer,
                              apply_and_state_fn, mixed_precision,
                              lazy_specs, fused, shardings)

    def epoch_run(params, opt_state, x, y, rng):
        n = _tree_len(x)
        shuffle_rng, step_rng0 = jax.random.split(rng)
        with jax.named_scope("fit_epoch/shuffle"):
            idx = (jax.random.permutation(shuffle_rng, n) if shuffle
                   else jnp.arange(n))[:steps * batch].reshape(steps, batch)

        def body(carry, ids):
            params, opt_state, rng = carry
            rng, sub = jax.random.split(rng)
            with jax.named_scope("fit_epoch/gather_batch"):
                xb = jax.tree_util.tree_map(lambda a: a[ids], x)
                yb = (jax.tree_util.tree_map(lambda a: a[ids], y)
                      if y is not None else None)
            params, opt_state, loss = one_step(params, opt_state, xb, yb,
                                               sub)
            return (params, opt_state, rng), loss

        (params, opt_state, _), losses = jax.lax.scan(
            body, (params, opt_state, step_rng0), idx)
        return params, opt_state, losses

    return _jit_donated(epoch_run, shardings, "batch", 1)


def _epoch_safe_trigger(trigger) -> bool:
    """Triggers that only need epoch-boundary state keep their exact
    semantics under the one-dispatch-per-epoch path."""
    return trigger is None or isinstance(trigger, (tg.EveryEpoch,
                                                   tg.MaxEpoch))


def _device_cache_eligible(x, y, mesh, n_proc: int, device_cache,
                           checkpoint_trigger=None,
                           end_trigger=None) -> bool:
    """Auto device-residency: single process, single device, in-memory
    arrays small enough to pin in HBM alongside the model, and no
    trigger that needs mid-epoch granularity (iteration counters and
    loss thresholds would silently stop checking mid-epoch — only the
    explicit opt-in accepts that trade)."""
    if device_cache is False or n_proc > 1:
        return False
    if device_cache is True:
        # explicit opt-in works on any local mesh (GSPMD resolves the
        # sharded in-jit gathers); AUTO stays single-device where it is
        # an unconditional win
        return True
    if mesh is not None and mesh.n_devices > 1:
        return False
    if not (_epoch_safe_trigger(checkpoint_trigger)
            and _epoch_safe_trigger(end_trigger)):
        return False
    limit_mb = float(os.environ.get("ZOO_DEVICE_CACHE_MB", "256"))
    nbytes = sum(np.asarray(a).nbytes
                 for a in jax.tree_util.tree_leaves((x, y)))
    return nbytes <= limit_mb * 1e6


def _data_fingerprint(tree) -> tuple:
    """Cheap content key for the device-data cache: identity alone would
    train on stale device copies after in-place mutation (per-round
    negative resampling mutates y in place). Hashes head/middle/tail
    slices of every leaf — O(KB) per leaf, catches realistic refreshes
    (a mutation confined entirely between the sampled slices can still
    alias; pass a fresh array to force a re-put)."""
    import zlib
    parts = []
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(leaf)
        if not a.flags["C_CONTIGUOUS"]:
            a = np.ascontiguousarray(a)
        raw = a.reshape(-1).view(np.uint8)
        k = min(len(raw), 4096)
        mid = len(raw) // 2
        parts.append((id(leaf), a.shape, str(a.dtype),
                      zlib.crc32(raw[:k].tobytes()),
                      zlib.crc32(raw[mid:mid + k].tobytes()),
                      zlib.crc32(raw[-k:].tobytes())))
    return tuple(parts)


def _device_cached_data(model, x, y, mesh, trace):
    """device_put once per distinct (x, y) CONTENT; cached on the model
    so repeated fit calls (warm restarts, bench epochs) skip the
    transfer. Strong refs to the host arrays keep the key's ids valid.
    The transfer, or finding that there is none to make, is the fit's
    `fit.place_data` phase."""
    key = _data_fingerprint((x, y))
    cached = getattr(model, "_device_data", None)
    hit = cached is not None and cached[0] == key
    trace.enter("place_data", hit=hit, bytes=sum(
        np.asarray(a).nbytes for a in jax.tree_util.tree_leaves((x, y))))
    if hit:
        return cached[1], cached[2]
    x_dev = _put_batch(x, mesh)
    y_dev = _put_batch(y, mesh) if y is not None else None
    model._device_data = (key, x_dev, y_dev, (x, y))
    return x_dev, y_dev


def _pick_one_step(apply_fn, loss_fn, optimizer, apply_and_state_fn,
                   mixed_precision, lazy_specs, fused=False,
                   shardings=None):
    if lazy_specs:
        if fused:
            from analytics_zoo_tpu.pallas.segment_update import \
                make_fused_one_step
            return make_fused_one_step(apply_fn, loss_fn, optimizer,
                                       lazy_specs, apply_and_state_fn,
                                       mixed_precision)
        from analytics_zoo_tpu.learn.lazy_embedding import make_lazy_one_step
        return make_lazy_one_step(apply_fn, loss_fn, optimizer, lazy_specs,
                                  apply_and_state_fn, mixed_precision)
    return _make_one_step(apply_fn, loss_fn, optimizer, apply_and_state_fn,
                          mixed_precision, shardings=shardings)


def build_eval_step(apply_fn: Callable, metrics: Sequence) -> Callable:
    def eval_step(params, states, xb, yb):
        pred = apply_fn(params, xb, training=False)
        return [m.update(s, yb, pred) for m, s in zip(metrics, states)]

    return jax.jit(eval_step)


# ---------------------------------------------------------------------------
# Keras front-door: fit / evaluate / predict
# ---------------------------------------------------------------------------
def fit_keras(model, x, y=None, batch_size: int = 32, epochs: int = 1,
              validation_data=None, distributed: bool = True,
              shuffle: bool = True, checkpoint_trigger=None,
              end_trigger=None, seed: int = 0,
              batch_iter_factory: Optional[Callable] = None,
              steps_per_run: int = 1, mixed_precision: bool = False,
              prefetch: bool = True,
              prefetch_depth: Optional[int] = None,
              lazy_embeddings: bool = False,
              device_cache: Optional[bool] = None,
              fused_optimizer: Optional[bool] = None,
              sharding_rules=None,
              metrics_report_s: Optional[float] = None,
              compile_cache_dir: Optional[str] = None,
              auto_resume: bool = False,
              int8_sidecar: bool = False,
              step_retries: int = 0,
              step_timeout_s: Optional[float] = None,
              profile_steps: Optional[Tuple[int, int]] = None,
              profile_dir: Optional[str] = None
              ) -> Dict[str, List[float]]:
    """`KerasNet.fit` backend. Returns a Keras-style history dict.
    `batch_iter_factory(epoch) -> iterator of (xb, yb, real)` overrides the
    default in-memory batching (lazy/disk-tier datasets).

    The loop is fully asynchronous: batches are device_put by a prefetch
    thread while the device computes, the per-step loss stays on device,
    and the ONLY host sync is one `_materialize` per epoch (plus any
    loss-reading trigger the caller installs). `prefetch_depth` (config
    `ZooConfig.prefetch_depth` / env ZOO_PREFETCH_DEPTH, default 2)
    bounds the transferred-batch backlog; the time the step loop spends
    BLOCKED on that queue is measured per step into
    `training_input_wait_ms` and per epoch into the
    `training_input_bound` gauge — the device-wait vs host-wait
    accounting that says whether a file-backed fit needs more
    `pipeline_workers`
    (docs/ProgrammingGuide/distributed-training.md "Input pipeline"). `steps_per_run=k` fuses k
    steps into one `lax.scan` program — one dispatch per k steps —
    trading trigger granularity (checked every k iterations) for dispatch
    overhead. `mixed_precision` runs fwd/bwd in bf16 with f32 masters.
    `metrics_report_s` runs a `MetricsReporter` for the duration of the
    fit, logging a one-line registry digest at that interval.
    Step/throughput/loss telemetry always publishes to the process-wide
    `MetricsRegistry` (and mirrors to TensorBoard when `set_tensorboard`
    is on).
    `fused_optimizer=True` (config `ZooConfig.fused_optimizer` / env
    `ZOO_FUSED_OPT=1`; None consults those) swaps a default-
    hyperparameter `adam`/`adamw` compile spec for the fused Pallas
    kernels (`pallas/fused_adam.py`): the whole Adam sweep becomes one
    blocked read-(g,m,v,p)/write-(m,v,p) HBM pass per leaf, in place.
    With `lazy_embeddings=True` the declared tables additionally take
    the sparse segment path (`pallas/segment_update.py`): batch row
    grads are segment-summed and ONLY the touched rows are read or
    written — no dense table gradient is ever materialized. An
    optimizer with no fused twin keeps the plain optax path with one
    WARNING; a kernel that fails to lower raises from the first step.
    `sharding_rules` turns the fit into a GSPMD-sharded pjit program
    (the training twin of serving's sharded placement): params and
    optimizer state shard over the mesh's `fsdp` axis per the SAME
    regex→PartitionSpec table serving consumes (`parallel/sharding.
    ShardingRules`; pass True for the default transformer table, or a
    ShardingRules instance; `ZooConfig.sharded_fit` / env
    ZOO_SHARDED_FIT=1 is the config spelling), the batch stays split
    over the (data × fsdp) batch axes, and explicit in/out shardings
    pin the rule layout through the donated step/run programs — XLA
    inserts the just-in-time all-gathers and gradient reduce-scatters
    (GSPMD + ZeRO-3). Per-device params+opt_state drop to ≈ 1/fsdp of
    the replicated footprint, which is what lets a model larger than
    one chip's HBM train at all. Checkpoints save in the ordinary
    gathered host layout and restore DIRECTLY onto the rule-derived
    shardings, so a sharded fit's checkpoint loads into serving's
    sharded placement with zero resharding. Incompatible with
    `lazy_embeddings` (the per-table state re-packs the param tree
    the rule table describes) and multi-process fits (for now);
    `fused_optimizer` composes — the kernels run on the fsdp-local
    shards via `shard_map`, so the 1/fsdp state footprint is kept.
    `compile_cache_dir` (or env `ZOO_COMPILE_CACHE_DIR`) enables the
    persistent compilation cache: the jitted step/run executables are
    AOT-serialized per input signature (`compile_cache/`), so a trainer
    re-run in a fresh process loads its programs from disk instead of
    re-lowering and re-compiling. JAX's built-in persistent cache, the
    layer under it for any shape AOT serialization can't carry, is
    placed by `init_zoo_context` (`JAX_COMPILATION_CACHE_DIR`, else
    `<checkout>/.xla_cache`), never by this argument.
    `profile_steps=(start, stop)` wraps iterations [start, stop) in a
    bounded `jax.profiler` capture (`observability/capture.py`): the
    trace artifact lands in a rotated dir under `profile_dir` (or
    `$ZOO_PROFILE_DIR`, default ./zoo_profiles) and its path is
    appended to `history["profile_artifacts"]`.
    `int8_sidecar=True` runs the post-training quantization pass at
    every checkpoint save (ISSUE 12): per-output-channel scales are
    calibrated from the just-saved weights and persisted as an int8
    sidecar beside `model.<iteration>`
    (`serving/quantization.write_int8_sidecar`), so
    `InferenceModel.load_checkpoint(..., quantize="int8")` serves the
    pre-calibrated artifact with no quantize-at-load pass. A sidecar
    write failure logs one warning and never fails the fit.
    `auto_resume=True` (needs `model.set_checkpoint(...)`) scans the
    checkpoint root for the newest INTACT epoch-boundary checkpoint
    before training and continues from it: params, optimizer state,
    iteration counter and the RNG key are restored, so the continued
    run's losses are bitwise-identical to an uninterrupted run (the
    shuffle order is already `seed + epoch`-derived). A corrupt latest
    checkpoint falls back to the newest intact one
    (`learn/checkpoint.py` CRC discipline). `step_retries=N` retries a
    failed step N times before writing an emergency checkpoint and
    raising; `step_timeout_s` additionally runs each step under a
    watchdog thread so a hung dispatch surfaces as TimeoutError.
    Every call records itself as a span tree in the process-wide tracer
    (`_FitTrace`: `fit` > `fit.prepare` .. `fit.epoch` > `fit.dispatch`,
    `fit.loss_sync` ..; docs/ProgrammingGuide/observability.md "Training
    spans") and observes each leaf in `training_fit_phase_ms`; any
    profiler capture that runs meanwhile holds the same spans on the
    device's clock.
    After fit, `model.params` holds DEVICE arrays (no gratuitous
    device→host pull; save/checkpoint paths transfer on demand)."""
    ctx = get_context()
    mesh = ctx.mesh if distributed else None
    dp = mesh.data_parallel_size if mesh else 1
    shard_rules = _resolve_sharding_rules(sharding_rules, ctx)
    if shard_rules is not None:
        if mesh is None:
            if sharding_rules is None:
                # config-driven default (ZooConfig.sharded_fit) quietly
                # steps aside for an explicitly non-distributed fit;
                # only the explicit kwarg is a hard contradiction
                shard_rules = None
            else:
                raise ValueError(
                    "sharding_rules needs distributed=True (the rule "
                    "table shards over the context mesh); drop "
                    "distributed=False or the rules")
    if shard_rules is not None:
        if lazy_embeddings:
            raise NotImplementedError(
                "sharding_rules is incompatible with lazy_embeddings: "
                "the per-table state re-packs the parameter tree the "
                "rule table is written against")
        if mesh.size("fsdp") == 1 and mesh.size("tensor") == 1:
            # every rule trims to replication on such a mesh: the fit
            # runs, but fully replicated — say so instead of letting a
            # sharded_fit=True config silently deliver none of the
            # 1/fsdp memory it was turned on for
            log.warning(
                "sharding_rules requested but the mesh has fsdp=1 and "
                "tensor=1 (%s): params/opt_state will be fully "
                "replicated. Set the fsdp axis (e.g. "
                "init_orca_context(data=1, fsdp=-1) or ZOO_MESH_FSDP) "
                "to actually shard state.", mesh)
    check_global_batch(batch_size, dp,
                       fsdp=mesh.size("fsdp") if mesh else 1)
    if steps_per_run < 1:
        raise ValueError(f"steps_per_run must be >=1, got {steps_per_run}")
    # prefetch-queue depth: explicit kwarg > config (ZOO_PREFETCH_DEPTH)
    # > 2. Bounds the host batch backlog — the input side never holds
    # more than `depth` transferred batches + one decoded shard per
    # pipeline worker.
    depth = int(prefetch_depth) if prefetch_depth else \
        int(getattr(getattr(ctx, "config", None), "prefetch_depth", 0)
            or 2)

    # Multi-process: `batch_size` stays GLOBAL (the reference's total-core
    # contract); each process feeds its LOCAL data shard, sliced at
    # global/process_count per step and assembled across hosts by
    # _put_batch.
    n_proc = jax.process_count()
    local_batch = batch_size
    if n_proc > 1:
        if batch_size % n_proc:
            raise ValueError(
                f"global batch_size ({batch_size}) must divide by the "
                f"process count ({n_proc})")
        if mesh is None or dp != jax.device_count():
            # _put_batch's cross-host assembly assumes the batch (data ×
            # fsdp) axes span every device; model axes crossing process
            # boundaries would mis-assemble the global shape
            raise NotImplementedError(
                "Multi-process fit currently supports pure data-parallel "
                "meshes (data×fsdp covering all devices); got "
                f"dp={dp} of {jax.device_count()} devices")
        if shard_rules is not None:
            # rule-sharded state would live partly on non-addressable
            # devices; checkpoint gather + resume re-shard are
            # single-process for now
            raise NotImplementedError(
                "sharding_rules is single-process for now: sharded "
                "params span non-addressable devices under "
                "multi-process, which the checkpoint gather/restore "
                "paths do not handle yet")
        if batch_iter_factory is not None and not getattr(
                batch_iter_factory, "shards_per_host", False):
            # a streaming factory that does NOT declare per-host shard
            # assignment would feed every process the same records —
            # silent sample duplication. TFRecord datasets declare it
            # (`_TFRecordDataset.shards_per_host`: disjoint files per
            # host over the mesh's data axis, `pipeline.host_shard`).
            raise NotImplementedError(
                "Multi-process fit over streaming datasets needs "
                "per-host shard assignment: every process would feed "
                "the same records. Use TPUDataset.from_tfrecord (which "
                "shards files per host) or materialize a per-host "
                "shard and pass arrays instead")
        local_batch = batch_size // n_proc

    steps_per_epoch = None      # a streaming factory does not say
    if batch_iter_factory is None:
        n = _tree_len(x)
        steps_per_epoch = n // local_batch
        if n_proc > 1:
            # unequal shards would desync the per-step collectives and
            # deadlock mid-epoch; gather counts BEFORE any local raise
            # (a rank bailing early would strand the others inside this
            # very collective)
            from jax.experimental import multihost_utils
            counts = np.asarray(multihost_utils.process_allgather(
                np.asarray(n, np.int64)))
            if not (counts == counts[0]).all():
                raise ValueError(
                    "Every process must hold the same number of local "
                    f"samples; got {counts.tolist()} across ranks")
        if n < local_batch:
            raise ValueError(
                f"Dataset has {n} samples but the per-process batch is "
                f"{local_batch}; training batches are whole-batch only "
                "(static shapes). Lower batch_size or add data.")

        def batch_iter_factory(epoch):  # noqa: F811 — default factory
            return iter_batches(x, y, local_batch, shuffle=shuffle,
                                seed=seed + epoch)

        use_device_cache = _device_cache_eligible(
            x, y, mesh, n_proc, device_cache,
            checkpoint_trigger=checkpoint_trigger, end_trigger=end_trigger)
        if device_cache is True and n_proc > 1:
            raise NotImplementedError(
                "device_cache=True is single-process only (each process "
                "would pin the full global dataset); drop the flag for "
                "multi-process fits")
    else:
        use_device_cache = False
        if device_cache is True:
            raise NotImplementedError(
                "device_cache=True needs in-memory arrays; streaming "
                "datasets (TFRecord/FeatureSet/batch_iter_factory) have "
                "no host copy to pin in HBM")

    telemetry = _TrainingMetrics()
    with _FitTrace(
            telemetry, epochs=epochs, steps_per_epoch=steps_per_epoch,
            batch=batch_size, devices=mesh.n_devices if mesh else 1,
            path="device_epoch" if use_device_cache else
            "multi_step" if steps_per_run > 1 else "single_step") as trace:
        trace.enter("prepare")
        rng = jax.random.PRNGKey(seed)
        rng, init_rng = jax.random.split(rng)
        if model.params is None:
            # shape probe — skipped when already built (streaming datasets
            # prebuild from a cheap first_sample instead of paying a full
            # shuffle-buffer fill here)
            try:
                sample = next(iter(batch_iter_factory(0)))[0]
            except StopIteration:
                raise ValueError(
                    "Dataset produced no full batches; lower batch_size")
            model.ensure_built(sample, init_rng)

        optimizer = model.optimizer
        if optimizer is None:
            raise RuntimeError("Model must be compiled before fit "
                               "(`Topology.scala:139` contract)")

        # -- auto-resume (ISSUE 5): continue from the newest intact
        # epoch-boundary checkpoint instead of step 0 -------------------------
        start_epoch = 0
        iteration = 0
        resume_opt_tree = None
        resume_meta = None
        if auto_resume:
            if not model._checkpoint_path:
                raise ValueError(
                    "auto_resume=True needs a checkpoint directory; call "
                    "model.set_checkpoint(path) first")
            from analytics_zoo_tpu.learn.checkpoint import (
                find_resume_checkpoint, load_checkpoint, remap_param_subtrees)
            found = find_resume_checkpoint(model._checkpoint_path)
            if found is not None:
                run_dir, version, _ = found
                # verify=False: find_resume_checkpoint CRC-verified exactly
                # this version moments ago — no second full-file pass
                r_params, resume_opt_tree, resume_meta = load_checkpoint(
                    run_dir, version, verify=False)
                # a fresh process's auto-generated layer names differ from
                # the checkpointing process's — remap onto this instance
                remap = getattr(model, "_remap_loaded", None)
                if remap is not None:
                    model.params = remap(r_params)
                    if resume_opt_tree is not None:
                        # the moments are keyed by the same saved names
                        resume_opt_tree = remap_param_subtrees(
                            resume_opt_tree, set(r_params), remap)
                else:
                    model.params = r_params
                start_epoch = int(resume_meta.get("epoch", 0))
                iteration = int(resume_meta.get("iteration", version))
                if "rng" in resume_meta:
                    # the checkpointed key IS the key the uninterrupted run
                    # held at this boundary — restoring it (plus the
                    # seed+epoch shuffle order) is what makes continuation
                    # bitwise-identical
                    rng = jnp.asarray(
                        np.asarray(resume_meta["rng"], dtype=np.uint32))
                else:
                    log.warning(
                        "auto-resume: checkpoint has no RNG state (pre-"
                        "ISSUE-5 layout); continuing with a fresh key — "
                        "losses will diverge from the uninterrupted run")
                log.info(
                    "auto-resume: continuing from %s/model.%d "
                    "(epoch %d, iteration %d)",
                    run_dir, version, start_epoch, iteration)

        param_shardings = step_shardings = None
        if shard_rules is not None:
            from analytics_zoo_tpu.parallel.sharding import (
                check_fsdp_divisibility, tree_shardings)
            # fail at config time, not at OOM time: a large param that
            # can't shard over fsdp would silently replicate everywhere
            check_fsdp_divisibility(model.params, mesh, shard_rules)
            param_shardings = tree_shardings(model.params, mesh, shard_rules)
            # host params (fresh build or checkpoint restore) land DIRECTLY
            # on the rule layout — the resume path never materializes a
            # replicated copy
            params = _put_with_shardings(model.params, param_shardings)
        else:
            params = _put_replicated(model.params, mesh)
        lazy_specs = None
        if lazy_embeddings:
            from analytics_zoo_tpu.learn.lazy_embedding import resolve_specs
            lazy_specs = resolve_specs(model)
        # -- fused-kernel optimizer (ISSUE 9): one HBM pass per leaf ----------
        fused = fused_optimizer
        if fused is None:
            fused = bool(getattr(getattr(ctx, "config", None),
                                 "fused_optimizer", False)) \
                or os.environ.get("ZOO_FUSED_OPT", "0") == "1"
        fused = bool(fused)
        if fused:
            from analytics_zoo_tpu.ops.optimizers import as_fused
            # the twin memoizes on the model: a fresh transformation per
            # fit would change id(optimizer) in the step cache key and
            # re-jit every warm restart
            spec = getattr(model, "_optimizer_spec", None)
            tkey = (id(optimizer), str(spec))
            twin = getattr(model, "_fused_twin_cache", None)
            if twin is not None and twin[0] == tkey:
                fused_opt, warn = twin[1], False
            else:
                fused_opt, warn = as_fused(optimizer, spec), True
                model._fused_twin_cache = (tkey, fused_opt)
            if fused_opt is not None:
                optimizer = fused_opt
            elif lazy_specs:
                # the declared tables still take the sparse fused path;
                # only the rest-of-model sweep stays plain optax. One
                # WARNING per model (the no-twin result is cached): a
                # fleet-wide ZOO_FUSED_OPT=1 retrain loop must not log
                # per fit
                if warn:
                    log.warning(
                        "fused_optimizer: compiled optimizer %r has no "
                        "exact fused twin; embedding tables take the "
                        "fused segment path, the rest stays on plain "
                        "optax", spec)
            else:
                if warn:
                    log.warning(
                        "fused_optimizer requested but the compiled "
                        "optimizer (%r) has no exact fused twin (only "
                        "default-hyperparameter adam/adamw specs map); "
                        "keeping the plain optax path", spec)
                fused = False

        # the layout marker auto-resume uses to refuse a structurally
        # mismatched restore: a fused fit's state tree (FusedAdamState /
        # fused rest) differs from the stock optax chain's
        opt_layout = "fused" if getattr(optimizer, "fused_apply", None) \
            is not None else "tree"
        trace.enter("optimizer_init")
        opt_shardings = None
        if lazy_specs:
            from analytics_zoo_tpu.learn.lazy_embedding import init_state
            opt_state = _put_replicated(
                init_state(params, lazy_specs, optimizer), mesh)
        elif shard_rules is not None:
            # eager init on sharded params: elementwise leaves (Adam moments)
            # inherit their param's sharding; the explicit re-put mirrors the
            # rule table onto EVERY leaf (step counters and any moment the
            # propagation missed land replicated / rule-sharded exactly) —
            # the match_partition_rules pattern: one table resolves params
            # and optimizer state
            opt_state = optimizer.init(params)
            from analytics_zoo_tpu.parallel.sharding import tree_shardings
            opt_shardings = tree_shardings(opt_state, mesh, shard_rules)
            opt_state = _put_with_shardings(opt_state, opt_shardings)
            step_shardings = _step_shardings(mesh, param_shardings,
                                             opt_shardings)
        else:
            opt_state = _put_replicated(optimizer.init(params), mesh)
        if resume_opt_tree is not None:
            from analytics_zoo_tpu.learn.checkpoint import restore_opt_state
            saved_layout = (resume_meta or {}).get("opt_state_layout", "tree")
            if saved_layout != opt_layout:
                raise ValueError(
                    f"auto_resume: checkpoint optimizer state is "
                    f"{saved_layout!r} but this fit would build "
                    f"{opt_layout!r} (fused_optimizer toggled between "
                    "runs?); re-run with the original setting")
            restored = restore_opt_state(jax.device_get(opt_state),
                                         resume_opt_tree)
            # sharded resume: saved host leaves re-shard DIRECTLY onto the
            # rule-derived layout (no replicate-then-reshard hop)
            opt_state = _put_with_shardings(restored, opt_shardings) \
                if opt_shardings is not None else _put_replicated(restored,
                                                                  mesh)

        # Cache the jitted step on the model: repeated fit calls (warm
        # restarts, per-round loops) must hit the compile cache, not
        # rebuild a fresh closure every call.
        trace.enter("build_step")
        multi = steps_per_run > 1
        dc_steps = (_tree_len(x) // local_batch) if use_device_cache else 0
        cc_dir = compile_cache_dir if compile_cache_dir is not None \
            else os.environ.get("ZOO_COMPILE_CACHE_DIR") or None
        # sharding descriptor: mesh axis extents + the rule table's content
        # hash. Part of BOTH the in-process step memo key and the on-disk
        # AOT key — a replicated fit and an fsdp-sharded fit (or two
        # different rule tables / mesh factorizations) are different
        # programs and must never share an executable. Stable across
        # processes (no id()), so a sharded re-fit in a fresh process still
        # hits its own entries.
        shard_desc = ""
        if shard_rules is not None:
            from analytics_zoo_tpu.parallel.sharding import sharding_descriptor
            shard_desc = sharding_descriptor(mesh, shard_rules)
        if use_device_cache:
            cache_key = (id(optimizer), id(model.loss), "devcache",
                         mixed_precision, lazy_embeddings, dc_steps,
                         local_batch, shuffle, fused, cc_dir,
                         shard_desc)
        else:
            cache_key = (id(optimizer), id(model.loss), multi,
                         mixed_precision, lazy_embeddings, fused, cc_dir,
                         shard_desc)
        cached = getattr(model, "_train_cache", None)
        if cached is not None and cached[0] == cache_key:
            train_step, step_program = cached[1:]
        else:
            if use_device_cache:
                builder = functools.partial(
                    build_device_epoch_run, steps=dc_steps,
                    batch=local_batch, shuffle=shuffle)
            else:
                builder = build_train_run if multi else build_train_step
            train_step = builder(
                model.apply, model.loss, optimizer,
                apply_and_state_fn=getattr(model, "apply_and_state", None),
                mixed_precision=mixed_precision, lazy_specs=lazy_specs,
                fused=fused, shardings=step_shardings)
            step_program = _StepProgram(train_step)
            if cc_dir:
                # persistent compilation cache: AOT-serialize the step/run
                # executable per input signature — a re-run in a fresh
                # process loads its program from disk instead of
                # re-compiling (jax's own persistent cache, enabled by
                # init_zoo_context, is the layer under it)
                from analytics_zoo_tpu.compile_cache import (
                    AOTFunctionCache, fingerprint, get_cache)
                # every program discriminator the in-memory cache_key
                # carries must reach the DISK key too: a single-step
                # executable and a multi-step run with coinciding arg
                # shapes are different programs (3- vs 4-tuple outputs).
                # steps_per_run itself stays OUT: the run program scans
                # the leading axis, so k only lives in the arg shapes and
                # a tail group may legitimately hit another run's entry.
                # `fused` is an explicit key component (ISSUE 9): the fused
                # and plain programs share every arg shape, so WITHOUT it a
                # toggle could load the other mode's stale executable
                step_fp = fingerprint(
                    [model, model.loss, optimizer.update, mixed_precision,
                     lazy_embeddings, multi, bool(use_device_cache), dc_steps,
                     shuffle if use_device_cache else None,
                     fused, shard_desc])
                train_step = AOTFunctionCache(train_step, get_cache(cc_dir),
                                              step_fp, sharding=shard_desc)
            model._train_cache = (cache_key, train_step, step_program)
        ckpt_mgr = None
        if model._checkpoint_path:
            from analytics_zoo_tpu.learn.checkpoint import (CheckpointManager,
                                                            gather_tree)
            ckpt_mgr = CheckpointManager(model._checkpoint_path)
            if checkpoint_trigger is None:
                checkpoint_trigger = tg.EveryEpoch()

        writer = None
        if model._tensorboard_dir:
            from analytics_zoo_tpu.utils.tensorboard import SummaryWriter
            writer = SummaryWriter(model._tensorboard_dir + "/train")

        telemetry.mesh_axes(mesh if shard_rules is not None else None)
        reporter = None
        if metrics_report_s:
            from analytics_zoo_tpu.observability.reporter import \
                MetricsReporter
            reporter = MetricsReporter(interval_s=metrics_report_s,
                                       writer=writer).start()

        if resume_meta is not None:
            telemetry.resumes.inc()

        # on-demand profiler window (ISSUE 6): capture iterations
        # [start, stop) into a bounded, rotated artifact dir
        profiler = None
        profile_state = {"active": False, "done": False}
        if profile_steps is not None:
            p_start, p_stop = (int(profile_steps[0]), int(profile_steps[1]))
            if not (0 <= p_start < p_stop):
                raise ValueError(
                    f"profile_steps={profile_steps!r} must be (start, stop) "
                    "with 0 <= start < stop")
            from analytics_zoo_tpu.observability.capture import ProfileCapture
            profiler = ProfileCapture(
                profile_dir or os.environ.get("ZOO_PROFILE_DIR")
                or "zoo_profiles")

        def _profile_stop():
            """End the capture and say where its device time went by the
            program's own scopes (`observability/device_time.py`): the
            one place a fit asks for its step program's table."""
            manifest = profiler.stop()
            profile_state["active"] = False
            profile_state["done"] = True
            history.setdefault("profile_artifacts", []).append(
                manifest["dir"])
            log.info("profiler capture written to %s (%d files)",
                     manifest["dir"], len(manifest["files"]))
            from analytics_zoo_tpu.observability import device_time
            report = device_time.write_report(manifest["dir"],
                                              program_scopes(model))
            rows = device_time.at_depth(report["rows"], 3)
            log.info("device time by scope (%s, %.4f s of operations)\n%s",
                     report["device_source"], report["total_s"],
                     device_time.format_rows(rows))
            telemetry.device_time_shares(rows)

        def _profile_tick(it: int):
            """Crossing-edge profiler control: start when the iteration
            counter reaches `start`, stop once it reaches `stop` (multi-step
            runs cross in jumps of k — the window rounds up to run
            boundaries, same granularity trade as every trigger)."""
            if profiler is None or profile_state["done"]:
                return
            try:
                if not profile_state["active"] and it >= p_start:
                    profiler.start(tag=f"fit-it{it}")
                    profile_state["active"] = True
                elif profile_state["active"] and it >= p_stop:
                    _profile_stop()
            except Exception as e:  # noqa: BLE001 — profiling must never
                # take down the fit it watches
                log.warning("profiler capture failed: %s: %s",
                            type(e).__name__, e)
                profile_state["done"] = True

        first_iteration = iteration

        def _call_step(steps, xb, yb):
            """Every branch's train_step dispatch of `steps` steps funnels
            through the step watchdog (retries + optional timeout); with
            step_retries=0 and no timeout this is a plain call. The
            profiler edge-check runs first. All of it, with the split of
            the call's key, is the `fit.dispatch` span: the host's side of
            a step, which returns while the device still runs."""
            nonlocal rng
            with trace.phase("dispatch", "epoch", steps=steps,
                             iteration=iteration):
                rng, step_rng = jax.random.split(rng)
                args = (params, opt_state, xb, yb, step_rng)
                if iteration == first_iteration:
                    step_program.saw(args)
                _profile_tick(iteration)
                return _step_with_watchdog(
                    train_step, args, step_retries, step_timeout_s,
                    telemetry.step_retries, iteration)

        def _ckpt_extra(ep: int, finished: bool) -> Dict[str, Any]:
            """Checkpoint sidecar: everything auto-resume needs for bitwise
            continuation — epoch/iteration cursors, the live RNG key, and
            the opt-state layout marker."""
            return {"epoch": ep, "iteration": iteration,
                    "epoch_finished": finished,
                    "rng": np.asarray(jax.device_get(rng)).ravel().tolist(),
                    "opt_state_layout": opt_layout}

        def _ckpt_save(extra: Dict[str, Any]) -> None:
            with trace.phase("checkpoint", "epoch"):
                _ckpt_commit(extra)

        def _ckpt_commit(extra: Dict[str, Any]) -> None:
            """ONE checkpoint-commit funnel for every save site (mid-epoch
            trigger, epoch boundary, emergency): gather the sharded state to
            host exactly once, commit the checkpoint set, and — with
            `int8_sidecar` — run the post-training quantization pass on the
            SAME gathered params so the sidecar always matches the version
            it sits beside. Sidecar failure is one warning, never a failed
            fit (serving falls back to quantize-at-load).

            Publication (ISSUE 14) is the LAST act: the publish marker —
            what the fleet's rollout watcher keys on — commits only once
            params, opt_state AND the sidecar are all durable. A kill
            anywhere before the marker rename leaves the version resumable
            but UNPUBLISHED; a sidecar failure skips the marker too (the
            version the fleet would quantize-at-load is not the version
            the trainer meant to publish)."""
            host_params = gather_tree(params)
            ckpt_mgr.save(iteration, host_params, gather_tree(opt_state),
                          extra=extra)
            publishable = True
            if int8_sidecar:
                try:
                    from analytics_zoo_tpu.serving.quantization import \
                        write_int8_sidecar
                    write_int8_sidecar(ckpt_mgr.run_dir, iteration, model,
                                       params=host_params)
                except Exception as e:  # noqa: BLE001 — sidecar is optional
                    publishable = False
                    log.warning("int8 sidecar write failed at iteration %d "
                                "(%s: %s); serving will quantize at load "
                                "and the version stays unpublished",
                                iteration, type(e).__name__, e)
            if publishable:
                try:
                    from analytics_zoo_tpu.learn.checkpoint import \
                        write_publish_marker
                    write_publish_marker(ckpt_mgr.run_dir, iteration,
                                         extra=extra)
                except Exception as e:  # noqa: BLE001 — resume still works
                    log.warning("publish marker failed at iteration %d "
                                "(%s: %s); the version resumes but will "
                                "not roll out", iteration,
                                type(e).__name__, e)

        x_dev = y_dev = None
        if use_device_cache:
            x_dev, y_dev = _device_cached_data(model, x, y, mesh, trace)

        history: Dict[str, List[float]] = {"loss": []}
        batches = None
        epoch = start_epoch
        try:
            for epoch in range(start_epoch, epochs):
              trace.begin_epoch(epoch, steps_per_epoch)
              it0 = iteration
              losses_dev: List[Any] = []   # device values; sync at end
              t0 = time.time()
              n_seen = 0

              if use_device_cache:
                  # whole epoch in ONE dispatch over device-resident data:
                  # zero per-step host transfer. Mid-epoch (iteration) trigger
                  # checks collapse to the epoch boundary — the same
                  # granularity trade as steps_per_run=steps.
                  batches = None
                  params, opt_state, ep_losses = _call_step(
                      dc_steps, x_dev, y_dev)
                  losses_dev.append(ep_losses)
                  iteration += dc_steps
                  n_seen = dc_steps * local_batch
              else:
                # `fit.transfer` runs in the prefetch thread beside the
                # loop (scope "worker": no part of the call's wall time),
                # or without prefetch in the loop itself
                transfer_scope = "worker" if prefetch else "epoch"
                if multi:
                    def transfer(group):
                        with trace.phase("transfer", transfer_scope,
                                         steps=len(group)):
                            return _stack_group(group, mesh)
                    source = _chunk_batches(batch_iter_factory(epoch),
                                            steps_per_run)
                else:
                    def transfer(item):
                        xb, yb, real = item
                        with trace.phase("transfer", transfer_scope,
                                         steps=1):
                            return (_put_batch(xb, mesh),
                                    _put_batch(yb, mesh) if yb is not None
                                    else None,
                                    real, 1)
                    source = batch_iter_factory(epoch)
                batches = _Prefetcher(
                    source, transfer, depth=depth,
                    wait_span=trace.input_wait) if prefetch \
                    else map(transfer, source)

                for xb, yb, real, k in batches:
                    if multi:
                        params, opt_state, _, loss = _call_step(k, xb, yb)
                    else:
                        params, opt_state, loss = _call_step(k, xb, yb)
                    iteration += k
                    n_seen += real * n_proc       # local count × processes
                    losses_dev.append(loss)
                    if checkpoint_trigger or end_trigger:
                        # loss stays a device scalar: triggers that read
                        # .loss (Min/MaxLoss) force their own sync; counter
                        # triggers stay async. Without a trigger nobody
                        # reads it, and the slice would be one more
                        # dispatch a run (on the CPU backend, a wait for
                        # the run itself, outside `fit.loss_sync`)
                        last_loss = loss[-1] if multi else loss
                    if checkpoint_trigger and ckpt_mgr and checkpoint_trigger(
                            tg.TriggerState(epoch=epoch, iteration=iteration,
                                            loss=last_loss)):
                        # the meta sidecar records the opt-state layout
                        # (plus the resume cursors/RNG), so a future
                        # restore can't silently structurally mismatch a
                        # fused fit's state against a plain one.
                        # gather_tree, not bare device_get: correct (and
                        # actionably failing cross-host) for sharded leaves
                        _ckpt_save(_ckpt_extra(epoch, False))
                    if end_trigger and end_trigger(
                            tg.TriggerState(epoch=epoch, iteration=iteration,
                                            loss=last_loss)):
                        break
                if isinstance(batches, _Prefetcher):
                    batches.close()  # early break leaves the worker mid-queue
              # ONE host sync per epoch: materialize every step loss together.
              # This blocks until the last step's program has finished, so dt
              # measures device compute, not dispatch.
              if epoch == 0 and not losses_dev:
                  # prebuilt models skip the shape probe, so an empty/too-small
                  # dataset must still fail loudly rather than "train" 0 steps
                  raise ValueError(
                      "Dataset produced no full batches; lower batch_size")
              with trace.phase("loss_sync", "epoch"):
                  # the host blocked on the device
                  losses_host = _materialize(losses_dev)
              step_losses = np.concatenate(
                  [np.atleast_1d(v) for v in losses_host]) \
                  if losses_host else np.zeros((0,))
              dt = time.time() - t0
              mean_loss = float(step_losses.mean()) \
                  if len(step_losses) else 0.0
              history["loss"].append(mean_loss)
              throughput = n_seen / max(dt, 1e-9)
              step_ms = telemetry.epoch(iteration - it0, n_seen, dt, mean_loss)
              # device-wait vs host-wait verdict (ISSUE 15): the prefetch
              # queue's measured blocked time over the epoch wall time is
              # the fraction of the fit that was input-bound — a measured
              # answer, not a guess.
              input_wait_s = batches.wait_s \
                  if isinstance(batches, _Prefetcher) else 0.0
              telemetry.input_bound.set(
                  min(1.0, input_wait_s / max(dt, 1e-9)))
              if writer:
                  writer.scalar("Loss", mean_loss, iteration)
                  writer.scalar("Throughput", throughput, iteration)
                  writer.scalar("StepTime_ms", step_ms, iteration)
              log.info("Epoch %d/%d  loss=%.4f  %.0f samples/s",
                       epoch + 1, epochs, mean_loss, throughput)

              if validation_data is not None:
                  vx, vy = validation_data
                  model.params = params  # device-resident hand-off
                  with trace.phase("validation", "epoch"):
                      val = evaluate_keras(
                          model, vx, vy,
                          batch_per_thread=max(batch_size // dp, 1))
                  for k, v in val.items():
                      history.setdefault("val_" + k, []).append(v)
                      telemetry.val.set(v, name=k)
                  if writer:
                      for k, v in val.items():
                          writer.scalar("val_" + k, v, iteration)

              # epoch-boundary checkpoint trigger (EveryEpoch semantics)
              if checkpoint_trigger and ckpt_mgr and checkpoint_trigger(
                      tg.TriggerState(epoch=epoch + 1, iteration=iteration,
                                      epoch_finished=True)):
                  _ckpt_save(_ckpt_extra(epoch + 1, True))
              if end_trigger and end_trigger(
                      tg.TriggerState(epoch=epoch + 1, iteration=iteration,
                                      epoch_finished=True)):
                  break

        except Exception:
            # the step watchdog exhausted its retries, or any other mid-run
            # failure: leave an emergency checkpoint behind so auto_resume
            # (or an operator) can continue instead of restarting at step 0.
            # Best-effort — a step that died mid-execution may have consumed
            # the donated parameter buffers, in which case the last periodic
            # checkpoint on disk remains the resume point.
            if ckpt_mgr is not None and iteration > 0 \
                    and iteration not in ckpt_mgr._saved:
                # (skipped when this iteration is already on disk — an
                # emergency save would demote a boundary checkpoint's
                # metadata to mid-epoch for identical params)
                try:
                    # through the SAME commit funnel as every other save
                    # site — the emergency checkpoint gets the int8 sidecar
                    # too, so a crash can't leave a newest version serving
                    # falls back to quantize-at-load on
                    _ckpt_save(dict(_ckpt_extra(epoch, False),
                                    emergency=True))
                    log.warning("emergency checkpoint written at iteration "
                                "%d", iteration)
                except Exception as ce:  # noqa: BLE001 — already failing
                    log.warning("emergency checkpoint failed (%s: %s); the "
                                "last periodic checkpoint is the resume "
                                "point", type(ce).__name__, ce)
            raise
        finally:
            # Keep parameters on device (even on an interrupted fit, so the
            # model never points at donated/deleted buffers): repeated
            # fit/evaluate/predict chains stay in HBM; save/checkpoint
            # paths device_get on demand.
            trace.enter("finish")
            model.params = params
            if isinstance(batches, _Prefetcher):
                batches.close()
            if profiler is not None and profile_state["active"]:
                # a fit that ends (or dies) inside the window still leaves
                # a finished, loadable artifact behind
                try:
                    _profile_stop()
                except Exception:  # noqa: BLE001 — already tearing down
                    pass
            if reporter is not None:
                reporter.stop()   # logs a final digest (before writer closes)
            if writer:
                writer.close()
        return history


def _localize_params(model):
    """Multi-process eval/predict run per-rank on local devices; params
    left on the global mesh by fit must be pulled to host first (every
    rank holds the full value when replicated; FSDP-sharded params would
    need collectives → clear error instead)."""
    def pull(a):
        if isinstance(a, jax.Array) and not a.is_fully_addressable:
            if a.is_fully_replicated:
                return np.asarray(a.addressable_data(0))
            raise NotImplementedError(
                "Multi-process evaluate/predict needs replicated "
                "parameters; params are sharded across hosts")
        return a
    model.params = jax.tree_util.tree_map(pull, model.params)


def evaluate_keras(model, x, y=None, batch_per_thread: int = 32,
                   metrics=None) -> Dict[str, float]:
    ctx = get_context()
    # Multi-process: each rank evaluates ITS OWN data locally (the
    # per-partition evaluation contract) — a cross-host eval batch would
    # both duplicate every sample per rank and produce outputs on
    # non-addressable devices.
    mesh = ctx.mesh if jax.process_count() == 1 else None
    if jax.process_count() > 1:
        _localize_params(model)
    dp_local = mesh.data_parallel_size if mesh \
        else jax.local_device_count()
    batch = batch_per_thread * dp_local
    model.ensure_built(next(iter_batches(x, y, batch,
                                         drop_remainder=False,
                                         pad_to_batch=True))[0])
    ms = metrics if metrics is not None else model.metrics
    if not ms:
        from analytics_zoo_tpu.ops.metrics import Loss
        ms = [Loss(model.loss)] if model.loss else []
    if not ms:
        raise ValueError("No metrics to evaluate; compile with metrics=[...]")
    params = _put_replicated(model.params, mesh)
    # cache the jitted eval step on the model — per-epoch validation must not
    # recompile (fresh closures defeat jax.jit's cache)
    cache_key = tuple(type(m).__name__ for m in ms)
    cached = getattr(model, "_eval_cache", None)
    if cached is not None and cached[0] == cache_key:
        eval_step = cached[1]
    else:
        eval_step = build_eval_step(model.apply, ms)
        model._eval_cache = (cache_key, eval_step)
    states = [m.init() for m in ms]
    # padding batches would contaminate accumulators → mask by slicing the
    # real rows on host for the tail batch instead
    for xb, yb, real in iter_batches(x, y, batch, drop_remainder=False,
                                     pad_to_batch=False):
        xb = _put_batch(xb, mesh)
        yb = _put_batch(yb, mesh) if yb is not None else None
        states = eval_step(params, states, xb, yb)
    # tail batch: pad to the SAME full-batch shape (reuses the predict jit,
    # no extra compile, no unjitted host apply), slice the real rows, and
    # fold them into the accumulators host-side
    n = _tree_len(x)
    tail = n % batch
    if tail:
        sel = np.concatenate([np.arange(n - tail, n),
                              np.repeat([n - 1], batch - tail)])
        xb = _put_batch(jax.tree_util.tree_map(
            lambda a: np.asarray(a)[sel], x), mesh)
        yb = jax.tree_util.tree_map(
            lambda a: np.asarray(a)[sel[:tail]], y) if y is not None \
            else None
        pred = jax.device_get(_forward_jit(model)(params, xb))
        pred = jax.tree_util.tree_map(lambda a: np.asarray(a)[:tail], pred)
        states = [m.update(s, yb, pred) for m, s in zip(ms, states)]
    return {m.name: float(m.compute(s)) for m, s in zip(ms, states)}


def _forward_jit(model):
    """Cached inference forward — shared by predict and the eval tail."""
    fj = getattr(model, "_predict_cache", None)
    if fj is None:
        fj = jax.jit(lambda p, xb: model.apply(p, xb, training=False))
        model._predict_cache = fj
    return fj


def predict_keras(model, x, batch_per_thread: int = 32) -> np.ndarray:
    ctx = get_context()
    # see evaluate_keras: per-rank local prediction under multi-process
    mesh = ctx.mesh if jax.process_count() == 1 else None
    if jax.process_count() > 1:
        _localize_params(model)
    dp_local = mesh.data_parallel_size if mesh \
        else jax.local_device_count()
    batch = batch_per_thread * dp_local
    model.ensure_built(next(iter_batches(x, None, batch,
                                         drop_remainder=False,
                                         pad_to_batch=True))[0])
    params = _put_replicated(model.params, mesh)
    apply_jit = _forward_jit(model)
    outs: List[np.ndarray] = []
    for xb, _, real in iter_batches(x, None, batch, drop_remainder=False,
                                    pad_to_batch=True):
        xb = _put_batch(xb, mesh)
        pred = jax.device_get(apply_jit(params, xb))
        pred_np = jax.tree_util.tree_map(lambda a: np.asarray(a)[:real], pred)
        outs.append(pred_np)
    if isinstance(outs[0], (list, tuple)):
        return type(outs[0])(np.concatenate([o[i] for o in outs])
                             for i in range(len(outs[0])))
    return np.concatenate(outs)
