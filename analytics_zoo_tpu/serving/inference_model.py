"""InferenceModel — the multi-backend concurrent inference façade.

Reference: `pipeline/inference/InferenceModel.scala:28`: a queue of
`concurrentNum` model copies (`:62,520-624`), loaders for every engine, and
thread-safe `doPredict`. TPU-native redesign:

- No model copies: a jit-compiled function is immutable and thread-safe;
  "concurrency" is a semaphore bounding in-flight predict calls (XLA
  serializes device work; the bound keeps host-side queuing sane) — with
  `auto_scaling` the permit count grows on contention like the reference's
  queue-cloning (`:587`).
- Dynamic shapes are the TPU hazard (recompiles), so predict pads the batch
  to a power-of-two bucket and caches one executable per bucket — the
  serving analogue of `hard_code_batch_size`.
- Loaders: native Keras-style models / ZooModel zoo dirs / pure fn+params /
  torch modules (via the torch bridge). The reference's TF/OpenVINO/Caffe
  loaders map onto the native-model path (their runtimes don't exist on TPU;
  weights must be converted, cf. `learn/torch_bridge.py`).

Multi-device placement (the reference scales by one model replica per Flink
task slot; here one per chip):

- **replicated** (`num_replicas=N`): one params copy per device
  (`jax.device_put(params, device)`), one cached executable per
  (replica, bucket) — jax keys its jit cache on the committed device —
  and a least-outstanding-work router with a per-replica in-flight
  bound. Each replica owns a worker thread because XLA's CPU backend
  executes in the dispatching thread: without per-replica threads N
  chips would serialize behind one dispatcher (a real TPU dispatch is
  async, where the extra hop costs ~µs).
- **sharded** (`placement="sharded"`): for models too large for one
  chip — params land with `NamedSharding`s from the GSPMD rule table
  (`parallel/sharding.py`, fsdp fallback) over a `common/mesh.py`
  DeviceMesh, and each batch is `device_put` split along the data axes.
  One logical replica spans every device; XLA emits the collectives.
- `num_replicas=1` (the default) is the original single-device path,
  byte-for-byte: bare `device_put`, single jit, no router, no threads.
"""

from __future__ import annotations

import functools
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.serving.timer import Timer

PLACEMENTS = ("replicated", "sharded")


class NoHealthyReplicaError(RuntimeError):
    """Every replica in the pool is quarantined: the router fails FAST
    (no 60 s permit wait) so callers can park work / answer 503 instead
    of hanging behind a fully-sick pool."""


def _next_bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class PendingPrediction:
    """Async handle from `predict_async`: the device computes while the
    caller keeps dispatching; `result()` materializes the output (the one
    blocking `np.asarray`) and slices off bucket padding. `result()` is
    idempotent and thread-safe — the sink stage and a curious caller can
    both touch it."""

    def __init__(self, out, valid_n: int, timer=None,
                 dispatch_s: float = 0.0, replica: int = 0,
                 roofline_cb: Optional[Callable[[float], None]] = None):
        self._out = out
        self._n = valid_n
        self._timer = timer
        self._dispatch_s = dispatch_s
        self.replica = replica        # which model replica computed this
        self._roofline_cb = roofline_cb
        self._result = None
        self._done = False
        self._lock = threading.Lock()

    def done(self) -> bool:
        """True once the device output is ready (or already materialized);
        a done() poll never blocks — it must not share the materialize
        lock, or polling would stall for the whole device sync inside a
        concurrent result()."""
        if self._done:
            return True
        out = self._out          # racy snapshot: result() may be midway
        if out is None:          # ... in which case it is done or about to be
            return True
        try:
            return all(a.is_ready() for a in
                       jax.tree_util.tree_leaves(out))
        except AttributeError:
            # jax without Array.is_ready(): report ready rather than
            # trap a done() poll loop at forever-False — result() is
            # the authoritative sync either way
            return True

    def result(self):
        with self._lock:
            if not self._done:
                t0 = time.perf_counter()
                out = jax.tree_util.tree_map(
                    lambda a: np.asarray(a)[:self._n], self._out)
                self._out = None            # free device refs promptly
                self._result = out
                self._done = True
                busy_s = self._dispatch_s + time.perf_counter() - t0
                if self._timer is not None:
                    # model time = dispatch + materialize wait; time the
                    # handle sat unmaterialized (e.g. behind a slow sink
                    # queue) is excluded, so /metrics "predict" doesn't
                    # misattribute a broker stall to the device
                    self._timer.record(busy_s)
                if self._roofline_cb is not None:
                    # utilization accounting rides the same measured
                    # window (accountant.account never raises)
                    self._roofline_cb(busy_s)
        return self._result


class _RoutedPending:
    """PendingPrediction fulfilled by a replica worker thread:
    `predict_async` returns it before the batch has even reached the
    device; the worker attaches the device output (or the dispatch
    failure, which `result()` re-raises so the serving sink's NaN
    degradation path sees it exactly like a synchronous dispatch
    error)."""

    def __init__(self, valid_n: int, timer=None, replica: int = 0,
                 on_done: Optional[Callable[[], None]] = None,
                 roofline_cb: Optional[Callable[[float], None]] = None):
        self._n = valid_n
        self._timer = timer
        self.replica = replica
        self._on_done = on_done
        self._roofline_cb = roofline_cb
        self._event = threading.Event()
        self._out = None
        self._exc: Optional[BaseException] = None
        self._dispatch_s = 0.0
        self._result = None
        self._done = False
        self._lock = threading.Lock()

    # -- worker side -------------------------------------------------------
    def _fulfill(self, out, dispatch_s: float):
        self._out = out
        self._dispatch_s = dispatch_s
        self._event.set()

    def _fail(self, exc: BaseException):
        self._exc = exc
        self._event.set()

    # -- consumer side -----------------------------------------------------
    def done(self) -> bool:
        """Never blocks (same contract as PendingPrediction.done): False
        until the worker has dispatched, then device-readiness."""
        if self._done:
            return True
        if not self._event.is_set():
            return False
        if self._exc is not None:
            return True
        out = self._out            # racy snapshot, same as PendingPrediction
        if out is None:
            return True
        try:
            return all(a.is_ready() for a in
                       jax.tree_util.tree_leaves(out))
        except AttributeError:
            return True

    def result(self):
        with self._lock:
            if not self._done:
                # the worker sets the event on every exit path
                # (_fulfill/_fail), and abandon() sets it too
                self._event.wait()  # blocking-ok: always signalled
                try:
                    if self._exc is None:
                        t0 = time.perf_counter()
                        out = jax.tree_util.tree_map(
                            lambda a: np.asarray(a)[:self._n], self._out)
                        self._out = None
                        self._result = out
                        busy_s = self._dispatch_s \
                            + time.perf_counter() - t0
                        if self._timer is not None:
                            self._timer.record(busy_s)
                        if self._roofline_cb is not None:
                            self._roofline_cb(busy_s)
                except Exception as e:  # noqa: BLE001 — keep for re-raise
                    self._exc = e
                finally:
                    # the replica permit releases exactly once, success or
                    # failure — a leak here would wedge the router
                    self._done = True
                    cb, self._on_done = self._on_done, None
                    if cb is not None:
                        cb()
            if self._exc is not None:
                raise self._exc
        return self._result

    def _rebind(self, replica: int, on_done) -> bool:
        """Quarantine re-dispatch: point this pending at a new replica
        (and its permit-release callback) BEFORE re-enqueueing it there.
        Refused (False) once the pending is already done/abandoned — the
        old callback has run and a rebind would leak the new permit.

        NON-blocking on the pending lock: the caller holds the router
        CV, and a sink thread can sit inside `result()` holding this
        lock while waiting for the event — blocking here would deadlock
        lock-order-inverted against `result()`'s `on_done` →
        `_release_replica` (CV) path. A contended pending simply
        refuses the rebind; the caller fails it instead (NaN degrade),
        which sets the event lock-free and unblocks that waiter."""
        if not self._lock.acquire(blocking=False):
            return False
        try:
            if self._done:
                return False
            self.replica = replica
            self._on_done = on_done
            return True
        finally:
            self._lock.release()

    def abandon(self):
        """Release the replica permit WITHOUT materializing — the
        shutdown-drop path (`ClusterServing._poison` discarding queued
        work once a stage is wedged): the device result is discarded and
        the broker's redelivery owns the records, but the permit must
        come back or the replica is down a slot forever."""
        with self._lock:
            if not self._done:
                self._done = True
                self._out = None
                cb, self._on_done = self._on_done, None
                if cb is not None:
                    cb()


class _Replica:
    """One device's slot in the replicated pool: committed params, a work
    queue, and the router's book-keeping. `inflight`/`batches`/
    `quarantined` are guarded by the model's router condition variable."""

    __slots__ = ("index", "device", "params", "inflight", "batches",
                 "work_q", "thread", "quarantined")

    def __init__(self, index: int, device, params):
        self.index = index
        self.device = device
        self.params = params
        self.inflight = 0          # routed but not yet materialized
        self.batches = 0           # total batches ever routed here
        self.quarantined = False   # supervisor pulled it from the router
        self.work_q: "queue.Queue" = queue.Queue()
        self.thread: Optional[threading.Thread] = None


class _JoinedPending:
    """PendingPrediction over max_batch chunks: each chunk was dispatched
    independently; result() syncs them in order and concatenates."""

    replica = None                 # spans replicas; no single owner

    def __init__(self, parts: List[PendingPrediction]):
        self._parts = parts
        self._result = None
        self._done = False
        self._lock = threading.Lock()

    def done(self) -> bool:
        # lock-free like PendingPrediction.done(): _parts is reassigned
        # (never mutated), so a racy snapshot is safe and all([]) is True
        return self._done or all(p.done() for p in self._parts)

    def result(self):
        with self._lock:
            if not self._done:
                chunks = [p.result() for p in self._parts]
                self._result = jax.tree_util.tree_map(
                    lambda *cs: np.concatenate(cs), *chunks)
                self._parts = []
                self._done = True
        return self._result


class InferenceModel:
    def __init__(self, concurrent_num: int = 1, auto_scaling: bool = False,
                 max_batch: int = 512,
                 num_replicas: Optional[int] = 1,
                 placement: str = "replicated",
                 devices: Optional[List] = None,
                 mesh=None,
                 max_inflight_per_replica: int = 2,
                 compile_cache=None):
        """`num_replicas`: model copies, one per device. 1 (default) keeps
        the original single-device path untouched; ``"auto"``/``-1``/``0``/
        ``None`` takes every local device. `placement="sharded"` instead
        spreads ONE copy across all devices (`mesh`, or a data+fsdp
        DeviceMesh over `devices`) for models too large for a chip.
        `max_inflight_per_replica` bounds routed-but-unmaterialized
        batches per replica — the router's backpressure.

        `compile_cache`: a `compile_cache.CompileCache` — warmup then
        consults the persistent executable cache per (replica, bucket)
        before compiling (hit → deserialize in ~ms; miss → compile once,
        persist, and every later process start hits). Replicated
        placement persists ONE entry per bucket and retarget-loads it
        onto each replica's device."""
        self.concurrent_num = concurrent_num
        self.auto_scaling = auto_scaling
        self._sema = threading.BoundedSemaphore(concurrent_num) \
            if not auto_scaling else threading.Semaphore(concurrent_num)
        self._fn: Optional[Callable] = None
        self._params = None
        self.max_batch = max_batch
        self.buckets = [b for b in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)
                        if b <= max_batch] or [max_batch]
        if placement not in PLACEMENTS:
            raise ValueError(
                f"placement={placement!r} not in {PLACEMENTS}")
        self.placement = placement
        devs = list(devices) if devices is not None else jax.local_devices()
        if not devs:
            raise ValueError("no devices available")
        if num_replicas in (None, 0, -1, "auto"):
            n = len(devs) if placement == "replicated" else 1
        else:
            n = int(num_replicas)
        if n < 1:
            raise ValueError(f"num_replicas={num_replicas!r} must be >= 1 "
                             "(or 'auto'/-1 for one per local device)")
        if n > len(devs):
            raise ValueError(
                f"num_replicas={n} exceeds the {len(devs)} available "
                "device(s); lower it or pass more devices")
        if placement == "sharded":
            n = 1                  # one logical replica spans the mesh
        self.num_replicas = n
        self.devices = devs[:n] if placement == "replicated" else devs
        # explicit devices pin replica 1 too; the bare default keeps the
        # legacy uncommitted device_put (single-replica byte-for-byte)
        self._pin_single = devices is not None
        self.mesh = mesh
        self.max_inflight_per_replica = max(1, int(max_inflight_per_replica))
        self._replicas: Optional[List[_Replica]] = None
        self._replica_cv = threading.Condition()
        self._rr = 0               # round-robin tie-break cursor
        # supervision hooks (serving/supervisor.py): outcome stream and
        # the canary batch probes reuse
        self._on_replica_event: Optional[Callable[[int, bool, float],
                                                  None]] = None
        self._last_input = None        # most recent dispatched batch
        self._last_good_input = None   # most recent SUCCESSFUL batch
        self._batch_sharding = None
        self._jit: Optional[Callable] = None
        self.timer = Timer("predict")
        self.warmup_report: Dict[str, float] = {}
        self.warmup_source: Dict[str, str] = {}
        self.warmed_buckets: set = set()
        # the per-record sample the last warmup() ran with — what a
        # restructured swap_params re-warms through the bucket path
        self._warmup_sample = None
        self.compile_cache = compile_cache
        # AOT executable table, (replica index, input signature) ->
        # jax.stages.Compiled — populated only by cache-backed warmup;
        # empty ⇒ every predict path is byte-for-byte the legacy jit
        self._aot: Dict[tuple, Any] = {}
        self._model_fp: Optional[str] = None
        # serving precision (ISSUE 12): set by load_fn from the weight
        # leaves; "float32" until a model loads
        self.serving_dtype: str = "float32"
        # roofline accounting (ISSUE 6): per-bucket XLA cost-analysis
        # FLOPs/bytes harvested at warmup, charged per materialized
        # batch against the measured predict time. Empty until warmup
        # runs — an unwarmed model pays nothing on the predict path.
        self._exec_cost: Dict[tuple, Any] = {}
        self._roofline = None

    # -- loaders (`doLoad*`, InferenceModel.scala:76-318) ------------------
    def load_keras(self, model, params=None,
                   quantize: Optional[str] = None) -> "InferenceModel":
        """A native Keras-style model (Sequential/Model/ZooModel).

        `quantize="int8"` rewrites every Dense/conv/Embedding weight to
        symmetric per-channel int8 and serves through the layers' int8
        MXU path (`serving/quantization.py`) — the TPU counterpart of the
        reference's OpenVINO int8 engine
        (`OpenVinoInferenceSupportive.scala:34-57`)."""
        from analytics_zoo_tpu.models.common import ZooModel
        if isinstance(model, ZooModel):
            model = model.model
        if params is not None:
            model.params = params
        if model.params is None:
            raise ValueError("Model has no parameters; fit or load first")
        params = model.params
        if quantize is not None:
            if quantize != "int8":
                raise ValueError(
                    f"Unsupported quantize={quantize!r}; only 'int8'")
            from analytics_zoo_tpu.serving.quantization import \
                quantize_model_params
            params = quantize_model_params(model, jax.device_get(params))
        return self.load_fn(lambda p, x: model.apply(p, x, training=False),
                            params)

    def load_zoo_model(self, cls, path: str,
                       quantize: Optional[str] = None) -> "InferenceModel":
        """`doLoadBigDL` analogue: a saved ZooModel directory."""
        return self.load_keras(cls.load_model(path), quantize=quantize)

    def load_quantized(self, model, path: str) -> "InferenceModel":
        """A pre-quantized int8 artifact (written by
        `serving.quantization.save_quantized`) onto `model`'s
        architecture — the `loadOpenVinoIRInt8` shape: ship the small
        int8 file, no f32 weights needed at serve time."""
        from analytics_zoo_tpu.models.common import ZooModel
        from analytics_zoo_tpu.serving.quantization import load_quantized
        net = model.model if isinstance(model, ZooModel) else model
        return self.load_fn(
            lambda p, x: net.apply(p, x, training=False),
            load_quantized(net, path))

    def load_checkpoint(self, model, path: str,
                        version: Optional[int] = None,
                        quantize: Optional[str] = None
                        ) -> "InferenceModel":
        """Serve a TRAINING checkpoint (`learn/checkpoint.py` layout)
        on `model`'s architecture. `quantize="int8"` prefers the
        checkpoint's pre-calibrated int8 sidecar
        (`fit_keras(int8_sidecar=True)` /
        `scripts/quantize_checkpoint.py`) — the shipped-artifact shape
        of the reference's int8 OpenVINO IR — and falls back to
        quantize-at-load when no intact sidecar exists (a torn sidecar
        costs a calibration, never the serve)."""
        from analytics_zoo_tpu.learn import checkpoint as ckpt_mod
        from analytics_zoo_tpu.models.common import ZooModel
        net = model.model if isinstance(model, ZooModel) else model
        if quantize is not None:
            if quantize != "int8":
                raise ValueError(
                    f"Unsupported quantize={quantize!r}; only 'int8'")
            # ONE resolution (shared with checkpoint.load_checkpoint),
            # reused below so the fallback never re-runs the CRC scan
            found = ckpt_mod.resolve_checkpoint(path, version)
            from analytics_zoo_tpu.serving.quantization import \
                load_int8_sidecar
            q = load_int8_sidecar(*found)
            if q is not None:
                remap = getattr(net, "_remap_loaded", None)
                return self.load_fn(
                    lambda p, x: net.apply(p, x, training=False),
                    remap(q) if remap is not None else q)
            path, version = found
        params, _, _ = ckpt_mod.load_checkpoint(path, version)
        remap = getattr(net, "_remap_loaded", None)
        if remap is not None:
            params = remap(params)
        return self.load_keras(net, params=params, quantize=quantize)

    @staticmethod
    def _infer_serving_dtype(params) -> str:
        """What precision this model SERVES in, from the weight leaves:
        any int8 leaf means the quantized MXU path ("int8"), else bf16
        weights mean "bfloat16", else "float32". The label every
        `serving_*` metric/span carries when non-default, and an
        explicit component of the compile-cache key — toggling dtype
        can never load the other precision's executable."""
        dtypes = {str(getattr(leaf, "dtype", ""))
                  for leaf in jax.tree_util.tree_leaves(params)}
        if "int8" in dtypes:
            return "int8"
        if "bfloat16" in dtypes:
            return "bfloat16"
        return "float32"

    def load_fn(self, fn: Callable, params) -> "InferenceModel":
        """Pure `fn(params, x)` forward."""
        self.close()               # reload: retire any old replica pool
        self._fn = fn
        self.serving_dtype = self._infer_serving_dtype(params)
        # one jit wrapper; jax caches an executable per input shape AND
        # per committed device/sharding, so each (replica, bucket) pair
        # gets its own cached executable with no bookkeeping here
        self._jit = jax.jit(fn)
        self._aot = {}
        self._model_fp = None
        if self.compile_cache is not None:
            from analytics_zoo_tpu.compile_cache import model_fingerprint
            # fingerprint BEFORE any device placement: the key must be
            # identical across processes, and device_put order is not
            self._model_fp = model_fingerprint(fn, params)
        if self.placement == "sharded":
            if self.mesh is None:
                from analytics_zoo_tpu.common.config import MeshConfig
                from analytics_zoo_tpu.common.mesh import DeviceMesh
                # fsdp carries both roles: params shard over it (the rule
                # table's fallback axis) and it is a batch axis, so the
                # input splits across every device too
                self.mesh = DeviceMesh(MeshConfig(data=1, fsdp=-1),
                                       self.devices)
            from analytics_zoo_tpu.parallel.sharding import shard_params
            self._params = shard_params(params, self.mesh)
            self._batch_sharding = self.mesh.batch_sharding()
            dp = self.mesh.data_parallel_size
            # buckets must split evenly over the data axes: GSPMD would
            # pad an uneven split, costing more than host-side padding to
            # the next divisible bucket. When NO power-of-two bucket
            # divides (dp=6, 12, ...), rebuild the ladder from dp itself
            # — a single max-size bucket would pad every request to
            # ~max_batch rows
            kept = [b for b in self.buckets if b % dp == 0]
            if not kept:
                b = dp
                while b <= self.max_batch:
                    kept.append(b)
                    b *= 2
            self.buckets = kept or [dp]
        elif self.num_replicas > 1:
            self._replicas = []
            for i, dev in enumerate(self.devices):
                rep = _Replica(i, dev, jax.device_put(params, dev))
                rep.thread = threading.Thread(
                    target=self._replica_loop, args=(rep,),
                    name=f"infer-replica-{i}", daemon=True)
                rep.thread.start()
                self._replicas.append(rep)
        elif self._pin_single:
            self._params = jax.device_put(params, self.devices[0])
        else:
            # weights transfer ONCE at load: a host pytree here would be
            # re-uploaded on every predict (jit does not cache arg
            # transfers)
            self._params = jax.device_put(params)
        self.warmup_report = {}
        self.warmup_source = {}
        self.warmed_buckets = set()
        self._warmup_sample = None
        # fresh program, fresh roofline: the live serving gauges must
        # describe THIS model, not whatever was loaded before
        self._exec_cost = {}
        try:
            from analytics_zoo_tpu.observability.roofline import \
                get_accountant
            self._roofline = get_accountant()
            self._roofline.reset("serving")
        except Exception:  # noqa: BLE001 — telemetry only
            self._roofline = None
        return self

    # -- hot swap (ISSUE 14: zero-downtime model rollout) ------------------
    def current_params(self) -> Any:
        """The LIVE device-resident weight tree (replica 0's copy for a
        replicated pool; None until a model loads). What a rollout agent
        snapshots before `swap_params` so a failed canary restores the
        exact serving state without a disk round trip."""
        if self._replicas:
            return self._replicas[0].params
        return self._params

    @staticmethod
    def _swap_signature(tree) -> tuple:
        """Post-transfer aval signature for the swap's structure test:
        treedef + per-leaf (shape, CANONICAL dtype). jax canonicalizes
        host dtypes at `device_put` (float64 → float32 with x64 off),
        so a float64 host checkpoint swapped onto an f32 live tree
        lands as the SAME executable structure — comparing raw host
        dtypes would misread it as a restructure and pay a pointless
        recompile."""
        from jax import dtypes as jdtypes
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return (str(treedef),
                tuple((tuple(np.shape(leaf)),
                       str(jdtypes.canonicalize_dtype(
                           getattr(leaf, "dtype", None)
                           or np.asarray(leaf).dtype)))
                      for leaf in leaves))

    def swap_params(self, params) -> str:
        """Replace the served weights WITHOUT reloading the model — the
        engine-side primitive of a versioned rollout. Returns how the
        executables fared:

        - ``"same"`` — the new tree has the identical structure, leaf
          shapes and dtypes as the live one. Params are swapped in
          place (per replica device / resharded onto the mesh) and
          every cached executable — the AOT table and jax's jit cache
          both key on the params *structure*, never its values — keeps
          serving: a same-shape swap costs **zero XLA compiles**.
        - ``"restructured"`` — the tree changed shape (new layer, new
          dtype, int8⇄f32). There is no honest way to keep the old
          executables, so the model reloads through `load_fn` (fresh
          jit, fresh AOT/cost tables, fresh fingerprint) and re-warms
          the previously-warmed buckets through the existing warmup
          path — the caller pays real compiles, visibly, instead of a
          silent structure mismatch at dispatch time.

        Swapping is reference-atomic per replica: a batch already
        dispatched keeps the tree it captured; the next dispatch sees
        the new one. Callers wanting a version boundary with no mixed
        batches (the rollout agent) drain dispatch first —
        `ClusterServing.pause_intake()` + `quiesce()`."""
        if self._fn is None:
            raise RuntimeError("No model loaded; load_* before swapping")
        live = self.current_params()
        new_sig, live_sig = self._swap_signature(params), \
            self._swap_signature(live)
        if new_sig != live_sig:
            import logging
            logging.getLogger("analytics_zoo_tpu.serving").info(
                "swap_params: structure changed (%s -> %s); honest "
                "reload + re-warmup", live_sig, new_sig)
            sample, buckets = self._warmup_sample, sorted(
                self.warmed_buckets)
            self.load_fn(self._fn, params)
            if sample is not None:
                self.warmup(sample, buckets=buckets or None)
            return "restructured"
        if self.placement == "sharded" and self.mesh is not None:
            from analytics_zoo_tpu.parallel.sharding import shard_params
            self._params = shard_params(params, self.mesh)
        elif self._replicas is not None:
            with self._replica_cv:
                reps = self._replicas
                if reps is None:
                    raise RuntimeError(
                        "replica pool closed mid-swap; reload the model")
                for rep in reps:
                    rep.params = jax.device_put(params, rep.device)
        elif self._pin_single:
            self._params = jax.device_put(params, self.devices[0])
        else:
            self._params = jax.device_put(params)
        return "same"

    # -- roofline accounting (observability/roofline.py) -------------------
    @staticmethod
    def _cost_key(x) -> tuple:
        """Per-batch cost-table key: leaf shapes/dtypes only (the params
        side is fixed per model) — cheap enough for the dispatch path.
        The shared `compile_cache.key.cheap_signature` so this can never
        drift from the AOT cache's spelling."""
        from analytics_zoo_tpu.compile_cache.key import cheap_signature
        return cheap_signature(x)

    def _program_span(self) -> int:
        """Devices one forward call spans: the whole mesh for sharded
        placement, one device otherwise (each replica runs its own
        single-device program)."""
        if self.placement == "sharded" and self.mesh is not None:
            return self.mesh.n_devices
        return 1

    def _record_cost(self, batch, stages_obj):
        """Harvest per-call FLOPs/bytes from a Compiled/Lowered for this
        batch shape; silently absent when the backend has no cost
        model. Callers hand a partitioned (sharded-placement)
        EXECUTABLE to `_harvest_jit_cost` instead: its cost analysis
        counts one device's per-device module, not the logical model
        cost (`roofline.ExecCost` basis contract)."""
        try:
            key = self._cost_key(batch)
            if key in self._exec_cost:
                return
            from analytics_zoo_tpu.observability.roofline import cost_of
            c = cost_of(stages_obj, span=self._program_span())
            if c is not None:
                self._exec_cost[key] = c
        except Exception:  # noqa: BLE001 — telemetry only
            pass

    def _harvest_jit_cost(self, params, batch):
        """Jit-path warmup harvest: lowering is cheap next to the XLA
        compile warmup is already paying (`cost_of` says what a backend
        that does not cost lowered modules gets)."""
        if self._cost_key(batch) in self._exec_cost:
            return
        try:
            low = self._jit.lower(params, batch)
        except Exception:  # noqa: BLE001 — telemetry only
            return
        self._record_cost(batch, low)

    def _roofline_cb(self, x):
        """The per-batch accounting callback for a pending, or None when
        this batch shape has no harvested cost (e.g. no warmup ran)."""
        if not self._exec_cost or self._roofline is None:
            return None
        cost = self._exec_cost.get(self._cost_key(x))
        if cost is None:
            return None
        acct = self._roofline
        span = self._program_span()
        return lambda secs, _c=cost, _a=acct, _n=span: _a.account(
            "serving", _c.flops, _c.bytes, secs, n_devices=_n)

    # -- persistent compile cache (compile_cache/) -------------------------
    @staticmethod
    def _exec_sig(x) -> tuple:
        """In-process executable-table key: tree structure + per-leaf
        shape/dtype of the (bucket-padded) batch."""
        from analytics_zoo_tpu.compile_cache import abstract_signature
        return abstract_signature(x)

    def _cache_key(self, sig):
        from analytics_zoo_tpu.compile_cache import make_key
        sharding = ""
        if self.placement == "sharded" and self.mesh is not None:
            # the RULE TABLE is part of the layout, not just the mesh:
            # two tables (or two versions of the default table) can
            # place the same params differently on the same mesh, and a
            # persisted executable embeds its input layout. ONE
            # canonical spelling shared with the trainer's step key
            # (parallel/sharding.sharding_descriptor), plus the device
            # ids this executable's assignment is pinned to.
            from analytics_zoo_tpu.parallel.sharding import \
                sharding_descriptor
            sharding = sharding_descriptor(self.mesh,
                                           devices=self.devices)
        # serving_dtype is an EXPLICIT key component (the params
        # structure already differs between f32 and int8 trees, but the
        # isolation must not hinge on a fingerprint heuristic): an int8
        # reload can never deserialize the bf16/f32 executable, and
        # vice versa. Default-f32 keys stay byte-identical to pre-ISSUE
        # 12 entries (no fleet-wide cache invalidation).
        return make_key("serving", self._model_fp or "", sig,
                        placement=self.placement, sharding=sharding,
                        dtype=self.serving_dtype
                        if self.serving_dtype != "float32" else "")

    def _aot_call(self, replica_idx: int, params, x):
        """One forward through the AOT table when it has an executable
        for this (replica, signature), else through the jit wrapper —
        the ONLY dispatch point shared by all three placement paths."""
        if self._aot:
            ex = self._aot.get((replica_idx, self._exec_sig(x)))
            if ex is not None:
                return ex(params, x)
        return self._jit(params, x)

    def program_scopes(self) -> Dict[str, Dict[str, Dict[str, Any]]]:
        """`{module: {instruction: {scope, direction, also}}}` of the
        warmed AOT executables that can give their compiled text
        (`observability/device_time.py`; what `POST /profile` joins its
        capture to). `{}` where the model serves through the jit wrapper
        alone: a capture's events then read `unmatched`."""
        from analytics_zoo_tpu.observability import device_time
        tables = []
        for ex in self._aot.values():
            try:
                tables.append(device_time.scope_table(ex.as_text()))
            except Exception:  # noqa: BLE001 — a re-treed or deserialised
                continue       # executable that gives no text
        return device_time.merge_tables(tables)

    def _warm_executable(self, replica_idx: int, params, batch,
                         target_device_id=None) -> str:
        """Cache-backed warmup for one (replica, bucket): consult the
        persistent cache before compiling; returns how the executable
        was obtained ("warm" | "cached" | "compiled")."""
        from analytics_zoo_tpu.compile_cache import serialization
        sig = self._exec_sig(batch)
        if (replica_idx, sig) in self._aot:
            return "warm"
        key = self._cache_key(sig)
        ex = self.compile_cache.load(key, target_device_id=target_device_id)
        if ex is not None:
            stored = serialization.args_treedef(ex)
            live = serialization.live_treedef((params, batch))
            if stored != live:
                # same canonical structure, different auto-numbered
                # layer names (a naming-counter offset between the
                # persisting process and this one): adapt the call
                # rather than rejecting the hit
                ex = serialization.retree_call(ex, stored)
            self._aot[(replica_idx, sig)] = ex
            # AOT-cache loads are a harvest point too: deserialized
            # executables still answer cost_analysis(). A sharded
            # (partitioned) executable reports per-device cost — the
            # logical basis needs the lowered module instead
            if self._program_span() > 1:
                self._harvest_jit_cost(params, batch)
            else:
                self._record_cost(batch, ex)
            return "cached"
        t0 = time.perf_counter()
        # module-attribute call: serialization.compile_lowered is THE
        # fresh-compile funnel tests monkeypatch to assert zero compiles
        ex = serialization.compile_lowered(self._jit.lower(params, batch))
        self.compile_cache.put(  # blocking-ok: disk cache write, not a queue
            key, ex, compile_ms=(time.perf_counter() - t0) * 1e3)
        self._aot[(replica_idx, sig)] = ex
        if self._program_span() > 1:
            self._harvest_jit_cost(params, batch)
        else:
            self._record_cost(batch, ex)
        return "compiled"

    def _replica_loop(self, rep: _Replica):
        """Per-replica dispatcher: XLA:CPU executes in the calling thread,
        so each replica needs its own; on TPU the jit call returns as soon
        as the async dispatch is enqueued and this thread is just a cheap
        hop. `t0` is the router hand-off time, so `dispatch_s` covers
        queue wait + dispatch (+ compute, on synchronous backends).

        Every job's outcome + latency reports through
        `_on_replica_event` (the ReplicaSupervisor's feed) unless the
        replica is quarantined — queued-before-quarantine stragglers and
        canary probes must not double-count against or for it. The
        `replica.dispatch` fault-injection point sits where a real chip
        fault would land."""
        while True:
            try:
                job = rep.work_q.get(timeout=1.0)
            except queue.Empty:
                continue
            if job is None:
                return
            x, pending, t0 = job
            t_start = time.perf_counter() if t0 is None else t0
            # the canary the supervisor probes quarantined replicas
            # with: the input is valid whatever the replica does to it
            self._last_input = x
            try:
                faults.fire("replica.dispatch", replica=rep.index,
                            batch=rep.batches)
                if self._aot:
                    ex = self._aot.get((rep.index, self._exec_sig(x)))
                    if ex is not None:
                        # AOT executables are strict about committed
                        # placement: land the batch on this replica's
                        # device first (a no-op when already there)
                        x = jax.device_put(x, rep.device)
                        out = ex(rep.params, x)
                    else:
                        out = self._jit(rep.params, x)
                else:
                    out = self._jit(rep.params, x)
                # the PREFERRED canary: an input a replica has actually
                # handled successfully — probing with the most recent
                # raw input alone would replay a poison batch forever
                # and turn one bad input into an unrevivable pool
                self._last_good_input = x
                pending._fulfill(out, time.perf_counter() - t_start)
                self._notify_replica(rep, True,
                                     time.perf_counter() - t_start)
            except Exception as e:  # noqa: BLE001 — surfaces in result()
                pending._fail(e)
                self._notify_replica(rep, False,
                                     time.perf_counter() - t_start)

    def _notify_replica(self, rep: _Replica, ok: bool, latency_s: float):
        cb = self._on_replica_event
        if cb is None or rep.quarantined:
            return
        try:
            cb(rep.index, ok, latency_s)
        except Exception:  # noqa: BLE001 — supervision must never take
            pass           # down the dispatch path it watches

    def close(self):
        """Retire the replica pool's worker threads (no-op otherwise).
        Safe to call repeatedly; `load_fn` calls it on reload. Stop the
        serving engine BEFORE closing a model it still routes through —
        after close the model needs a fresh `load_*` to predict again."""
        with self._replica_cv:
            # swap the pool out under the router CV: a concurrent
            # predict_async either enqueued its job BEFORE this point
            # (FIFO: the worker fulfills it before seeing the pill) or
            # sees the dead pool and raises the clear closed error. The
            # notify wakes permit-blocked routers into that error now,
            # not after their 60s timeout.
            reps, self._replicas = self._replicas, None
            self._replica_cv.notify_all()
        if reps:
            for rep in reps:
                rep.work_q.put_nowait(None)
            for rep in reps:
                if rep.thread is not None:
                    rep.thread.join(timeout=5)
            # the pool was the only executor (no single-device _params):
            # a predict now must say "load first", not jit(None, x)
            if self._params is None:
                self._fn = None

    # -- router ------------------------------------------------------------
    def _acquire_replica(self, timeout: float = 60.0) -> _Replica:
        """Least-outstanding-work selection with a per-replica in-flight
        bound; round-robin tie-break so equally-idle replicas alternate
        instead of piling onto index 0. Blocks (bounded) when every
        replica is at the bound — the router's backpressure."""
        deadline = time.monotonic() + timeout
        with self._replica_cv:
            while True:
                reps = self._replicas
                if reps is None:
                    # close()/load_fn() retired the pool mid-route (the
                    # documented misuse — stop the engine first); fail
                    # with the real cause, not a NoneType iteration
                    raise RuntimeError(
                        "replica pool closed while routing; stop the "
                        "serving engine before close()/load_fn()")
                healthy = [r for r in reps if not r.quarantined]
                if not healthy:
                    # fail FAST, not after the 60s permit wait: the
                    # caller (dispatch stage / frontend) owns the
                    # park-or-503 decision
                    raise NoHealthyReplicaError(
                        f"all {len(reps)} replicas are quarantined; "
                        "waiting on canary revival")
                free = [r for r in healthy
                        if r.inflight < self.max_inflight_per_replica]
                if free:
                    lo = min(r.inflight for r in free)
                    n = len(reps)
                    rep = min((r for r in free if r.inflight == lo),
                              key=lambda r: (r.index - self._rr) % n)
                    self._rr = (rep.index + 1) % n
                    rep.inflight += 1
                    rep.batches += 1
                    return rep
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._replica_cv.wait(remaining):
                    raise TimeoutError(
                        "every model replica is at its in-flight bound "
                        f"({self.max_inflight_per_replica}); results are "
                        "not being materialized")

    def _release_replica(self, rep: _Replica):
        with self._replica_cv:
            rep.inflight -= 1
            self._replica_cv.notify()

    # -- quarantine / revival (driven by serving/supervisor.py) ------------
    def quarantine_replica(self, index: int) -> bool:
        """Pull one replica out of the routing set: the router stops
        considering it, and every job still QUEUED on it (not yet picked
        up by its worker) re-dispatches to the least-loaded healthy
        replica with its in-flight permit transferred. The job the
        worker is currently executing finishes (or fails) normally.
        Idempotent; returns True when this call made the transition."""
        with self._replica_cv:
            reps = self._replicas
            if reps is None or index >= len(reps):
                return False
            rep = reps[index]
            if rep.quarantined:
                return False
            rep.quarantined = True
            healthy = [r for r in reps if not r.quarantined]
            moved = []
            while True:
                try:
                    job = rep.work_q.get_nowait()
                except queue.Empty:
                    break
                if job is None:
                    # close() pill mid-quarantine: the worker must still
                    # see it, and it carries no permit
                    rep.work_q.put_nowait(job)
                    break
                moved.append(job)
            for x, pending, t0 in moved:
                target = min(healthy, key=lambda r: r.inflight) \
                    if healthy else None
                if target is not None and pending._rebind(
                        target.index,
                        lambda _r=target: self._release_replica(_r)):
                    # permit transfer: the quarantined slot frees now,
                    # the target's releases via the rebound callback
                    rep.inflight -= 1
                    target.inflight += 1
                    target.batches += 1
                    # t0 resets: charging the detour (queue wait on the
                    # dead replica) to the healthy target's supervised
                    # latency would read as an outlier and cascade the
                    # quarantine across the pool
                    target.work_q.put_nowait((x, pending,
                                              time.perf_counter()))
                else:
                    # no healthy replica left (or the pending already
                    # finished): fail it — the serving sink degrades the
                    # batch to NaN and the OLD permit releases through
                    # the pending's original callback
                    pending._fail(NoHealthyReplicaError(
                        "replica quarantined with no healthy peer to "
                        "re-dispatch to"))
            self._replica_cv.notify_all()
            return True

    def revive_replica(self, index: int) -> bool:
        """Return a quarantined replica to the routing set (the
        supervisor calls this after a successful canary probe)."""
        with self._replica_cv:
            reps = self._replicas
            if reps is None or index >= len(reps) \
                    or not reps[index].quarantined:
                return False
            reps[index].quarantined = False
            self._replica_cv.notify_all()
            return True

    def healthy_replicas(self) -> int:
        """Replicas currently accepting routed work (the whole model for
        the single-device and sharded paths)."""
        reps = self._replicas
        if reps is None:
            return self.num_replicas
        with self._replica_cv:
            return sum(1 for r in reps if not r.quarantined)

    def quarantined_replicas(self) -> List[int]:
        reps = self._replicas
        if reps is None:
            return []
        with self._replica_cv:
            return [r.index for r in reps if r.quarantined]

    def probe_replica_async(self, index: int, x=None):
        """Enqueue a canary batch on `index`'s worker (bypassing the
        router — a quarantined replica still drains its queue) and
        return the `_RoutedPending` WITHOUT waiting, or None when there
        is nothing to probe with. `x` defaults to the most recent batch
        any replica handled SUCCESSFULLY (falling back to the most
        recent dispatched batch when no success ever happened — e.g.
        every replica faulted from the first record): a poison input
        must not become the only canary, or revival could never
        succeed."""
        reps = self._replicas
        if reps is None or index >= len(reps):
            return None
        x = x if x is not None else (
            self._last_good_input if self._last_good_input is not None
            else self._last_input)
        if x is None:
            return None                # nothing credible to probe with
        leaves = jax.tree_util.tree_leaves(x)
        n = leaves[0].shape[0] if leaves and leaves[0].ndim > 0 else 1
        pending = _RoutedPending(n, timer=None, replica=index)
        reps[index].work_q.put_nowait((x, pending, None))
        return pending

    def probe_replica(self, index: int, x=None,
                      timeout_s: float = 10.0) -> bool:
        """Blocking canary probe: True iff the forward completes within
        the budget — the revival signal. (The supervisor uses the async
        variant so one wedged replica cannot stall the probe loop.)"""
        pending = self.probe_replica_async(index, x)
        if pending is None:
            return False
        if not pending._event.wait(timeout_s):
            return False
        try:
            pending.result()
        except Exception:  # noqa: BLE001 — a failing probe IS the signal
            return False
        return True

    def replica_inflight(self, index: int) -> int:
        """Routed-but-unmaterialized batches on one replica (live; 0 for
        the single-device and sharded paths)."""
        reps = self._replicas
        if reps is None or index >= len(reps):
            return 0
        return reps[index].inflight

    def replica_stats(self) -> List[Dict[str, Any]]:
        """Per-replica routing book-keeping for metrics/bench output."""
        if self._replicas is None:
            return [{"replica": 0, "device": str(d), "batches": None,
                     "inflight": 0}
                    for d in (self.devices[:1] if self.placement ==
                              "replicated" else self.devices)]
        with self._replica_cv:
            return [{"replica": r.index, "device": str(r.device),
                     "batches": r.batches, "inflight": r.inflight,
                     "quarantined": r.quarantined}
                    for r in self._replicas]

    def weight_bytes(self) -> int:
        """LOGICAL bytes of the loaded weight leaves (one copy's worth —
        replication and sharding don't change the number; a sharded
        jax.Array reports its global nbytes). 0 until a model loads.
        The honest byte price the `serving_weight_bytes` gauge
        publishes: int8 weights read ~4x under their f32 source."""
        if self._replicas:
            tree = self._replicas[0].params
        else:
            tree = self._params
        if tree is None:
            return 0
        return sum(int(getattr(leaf, "nbytes", 0))
                   for leaf in jax.tree_util.tree_leaves(tree))

    def placement_info(self) -> Dict[str, Any]:
        """Placement summary for `ClusterServing.metrics()` / the CLI."""
        info: Dict[str, Any] = {"placement": self.placement,
                                "num_replicas": self.num_replicas,
                                "n_devices": len(self.devices),
                                "serving_dtype": self.serving_dtype}
        if self.placement == "sharded" and self.mesh is not None:
            info["mesh"] = {a: s for a, s in self.mesh.axis_sizes.items()
                            if s != 1}
            info["data_parallel_size"] = self.mesh.data_parallel_size
        return info

    def load_keras_encrypted(self, model, path: str, secret: str,
                             salt: str = "analytics-zoo"
                             ) -> "InferenceModel":
        """Encrypted-model analogue of `doLoadBigDL(path, secret)`
        (InferenceModel.scala:121-226): decrypt an AES-GCM-sealed param
        tree and attach it to the given architecture."""
        from analytics_zoo_tpu.learn.encrypted import load_encrypted_pytree
        from analytics_zoo_tpu.models.common import ZooModel
        params = load_encrypted_pytree(path, secret, salt)
        net = model.model if isinstance(model, ZooModel) else model
        params = net._remap_loaded(params)
        return self.load_keras(model, params=params)

    def load_torch(self, torch_module) -> "InferenceModel":
        """`doLoadPyTorch` analogue: convert the module natively (the
        reference embeds CPython via JEP; on TPU the model becomes XLA)."""
        from analytics_zoo_tpu.learn.torch_bridge import convert_torch_module
        native = convert_torch_module(torch_module)
        sample_shape = getattr(native, "input_shape", None)
        if native.params is None and sample_shape is not None:
            native.ensure_built(np.zeros((1,) + tuple(sample_shape[1:]),
                                         np.float32))
        return self.load_keras(native)

    # -- predict (`doPredict`, InferenceModel.scala:520-624) ---------------
    def predict(self, x) -> np.ndarray:
        """Sync predict: dispatch + materialize. Equivalent to
        `predict_async(x).result()`."""
        return self.predict_async(x).result()

    def predict_async(self, x, valid_n: Optional[int] = None):
        """Dispatch without syncing: pad to the shape bucket (on device —
        the raw batch uploads once and extends by broadcasting its last
        row, so the dispatch thread never runs a host-side pad copy),
        hand the padded batch to the cached per-bucket executable, and
        return a `PendingPrediction` immediately. XLA computes in the
        background; the caller (the serving sink stage) materializes via
        `.result()` while the dispatch thread feeds batch N+1.

        `valid_n` marks how many leading records are real when the caller
        already stacked the batch to a bucket size (the serving decode
        stage does: stacking straight to the bucket is free — the stack
        copies every record anyway — and skips the pad entirely)."""
        if self._fn is None:
            raise RuntimeError("No model loaded")
        x = jax.tree_util.tree_map(np.asarray, x)
        leaves = jax.tree_util.tree_leaves(x)
        n = leaves[0].shape[0] if leaves[0].ndim > 0 else 1
        valid_n = n if valid_n is None else min(valid_n, n)

        if n > self.max_batch:
            # split oversize inputs into max_batch chunks, all in flight
            parts = []
            for s in range(0, n, self.max_batch):
                part = jax.tree_util.tree_map(
                    lambda a: a[s:s + self.max_batch], x)
                remain = max(0, valid_n - s)
                parts.append(self.predict_async(
                    part, valid_n=min(remain, self.max_batch)))
            return _JoinedPending(parts)

        acquired = self._sema.acquire(timeout=60)
        if not acquired:
            if not self.auto_scaling:
                raise TimeoutError("predict queue exhausted "
                                   "(concurrent_num permits busy)")
            self._sema.release()  # grow like the reference's auto-scaling
        t0 = time.perf_counter()
        try:
            bucket = _next_bucket(n, self.buckets)
            if n != bucket:
                pad = bucket - n
                x = jax.tree_util.tree_map(
                    lambda a: jnp.concatenate(
                        [jnp.asarray(a),
                         jnp.broadcast_to(jnp.asarray(a)[-1:],
                                          (pad,) + a.shape[1:])]), x)
            rcb = self._roofline_cb(x)
            if self._replicas is not None:
                # replica pool: route to the least-loaded device and
                # return immediately — its worker thread dispatches.
                # acquire AND enqueue under the router CV (an RLock, so
                # _acquire_replica re-enters): close() also swaps the
                # pool out under it, so a job can never land behind a
                # worker's stop pill and wait forever unfulfilled
                with self._replica_cv:
                    rep = self._acquire_replica()
                    pending = _RoutedPending(
                        valid_n, timer=self.timer, replica=rep.index,
                        on_done=lambda rep=rep:
                            self._release_replica(rep),
                        roofline_cb=rcb)
                    rep.work_q.put_nowait((x, pending, t0))
                return pending
            if self._batch_sharding is not None:
                # sharded placement: split the (bucket-padded, so evenly
                # divisible) batch along the data axes before the call
                x = jax.device_put(x, self._batch_sharding)
            if self._params is None:
                # a concurrent close() retired a replica pool between
                # the _fn check and here: params never existed on the
                # single-device path — fail clearly, not jit(None, x)
                raise RuntimeError(
                    "model closed mid-predict; reload before predicting")
            out = self._aot_call(0, self._params, x)
        finally:
            # the permit bounds dispatch admission, not result lifetime:
            # async callers bound in-flight results with their own queue
            # (ClusterServing's sink queue), so holding the permit until
            # result() would serialize the pipeline at concurrent_num=1
            if acquired:
                self._sema.release()
        # recorded once at result(): dispatch cost + materialize wait
        return PendingPrediction(out, valid_n, timer=self.timer,
                                 dispatch_s=time.perf_counter() - t0,
                                 roofline_cb=rcb)

    def predict_batches(self, xs: List) -> List:
        return [self.predict(x) for x in xs]

    # -- warmup (`warmup()` per-bucket pre-compile) ------------------------
    def warmup(self, sample, buckets: Optional[List[int]] = None
               ) -> "InferenceModel":
        """Pre-compile every shape bucket at load time so no XLA compile
        ever lands on the request path. `sample` is ONE record (no batch
        dim, serving dtype — executables are keyed on dtype too), e.g.
        ``np.zeros((32, 32, 3), np.float32)``, or a pytree of records for
        multi-input models. Per-bucket compile+run seconds land in
        ``self.warmup_report``; warmed buckets in ``self.warmed_buckets``."""
        if self._fn is None:
            raise RuntimeError("No model loaded")
        buckets = list(buckets) if buckets is not None else list(self.buckets)
        if self._batch_sharding is not None:
            # sharded placement only ever sees divisible buckets; all
            # indivisible → warm the smallest real bucket, not nothing
            dp = self.mesh.data_parallel_size
            buckets = [b for b in buckets if b % dp == 0] or \
                [self.buckets[0]]
        sample = jax.tree_util.tree_map(np.asarray, sample)
        self._warmup_sample = sample
        tag = "x".join(map(str, jax.tree_util.tree_leaves(sample)[0].shape)
                       ) or "scalar"
        use_cache = self._use_compile_cache()
        if self._replicas is not None:
            return self._warmup_replicas(sample, buckets, tag, use_cache)
        for b in buckets:
            batch = jax.tree_util.tree_map(
                lambda a: np.ascontiguousarray(
                    np.broadcast_to(a[None], (b,) + a.shape)), sample)
            if self._batch_sharding is not None:
                batch = jax.device_put(batch, self._batch_sharding)
            t0 = time.perf_counter()
            if use_cache:
                # persistent cache first: a hit deserializes in ~ms
                # where a miss compiles once and persists for the next
                # process. Sharded executables keep their stored device
                # assignment (the mesh is part of the key); the single-
                # device executable re-pins onto this model's device.
                src = self._warm_executable(
                    0, self._params, batch,
                    target_device_id=None if self._batch_sharding
                    is not None else self.devices[0].id)
                jax.block_until_ready(
                    self._aot[(0, self._exec_sig(batch))](
                        self._params, batch))
            else:
                src = "jit"
                # straight through the jit (not predict): warmup must
                # not pollute the serving timer percentiles
                jax.block_until_ready(self._jit(self._params, batch))
                self._harvest_jit_cost(self._params, batch)
            rkey = f"{tag}:b{b}"
            self.warmup_report[rkey] = round(time.perf_counter() - t0, 4)
            self.warmup_source[rkey] = src
            self.warmed_buckets.add(b)
        return self

    def _use_compile_cache(self) -> bool:
        return self.compile_cache is not None

    def _warmup_replicas(self, sample, buckets, tag,
                         use_cache: bool = False) -> "InferenceModel":
        """Fan warmup out across the pool: every replica's worker thread
        compiles its own (replica, bucket) executables concurrently —
        N chips warm in roughly the time one takes. Jobs bypass the
        router (no in-flight accounting: nothing else runs at load) and
        carry no timer, so percentiles stay unpolluted.

        With a compile cache, each bucket is ONE cache entry: a hit
        deserializes N times (re-pinned per replica device); a miss
        compiles per replica in parallel as before, then persists a
        single entry — "persist once, load N times"."""
        if use_cache:
            for b in buckets:
                batch = jax.tree_util.tree_map(
                    lambda a, _b=b: np.ascontiguousarray(
                        np.broadcast_to(a[None], (_b,) + a.shape)), sample)
                sig = self._exec_sig(batch)
                # replica 0 probes the cache; on a miss it compiles and
                # persists the bucket's ONE entry — which every later
                # replica then LOADS (retargeted onto its own device,
                # ~ms each) instead of re-compiling. Cold wall time ≈
                # one compile + (N-1) deserializes; warm ≈ N
                # deserializes. warmup_source shows exactly what this
                # restart paid per replica.
                for rep in self._replicas:
                    t0 = time.perf_counter()
                    src = self._warm_executable(
                        rep.index, rep.params, batch,
                        target_device_id=rep.device.id)
                    jax.block_until_ready(
                        self._aot[(rep.index, sig)](rep.params, batch))
                    rkey = f"r{rep.index}:{tag}:b{b}"
                    self.warmup_report[rkey] = round(
                        time.perf_counter() - t0, 4)
                    self.warmup_source[rkey] = src
                self.warmed_buckets.add(b)
            return self
        jobs = []
        for b in buckets:
            batch = jax.tree_util.tree_map(
                lambda a, _b=b: np.ascontiguousarray(
                    np.broadcast_to(a[None], (_b,) + a.shape)), sample)
            # one harvest per bucket (every replica runs the same
            # program; replica 0's params stand in for all)
            self._harvest_jit_cost(self._replicas[0].params, batch)
            for rep in self._replicas:
                pending = _RoutedPending(b, timer=None, replica=rep.index)
                # t0=None: the worker stamps its own start, so the report
                # is per-(replica, bucket) compile+run, not queue wait
                rep.work_q.put_nowait((batch, pending, None))
                jobs.append((rep.index, b, pending))
        for idx, b, pending in jobs:
            pending.result()
            rkey = f"r{idx}:{tag}:b{b}"
            self.warmup_report[rkey] = round(pending._dispatch_s, 4)
            self.warmup_source[rkey] = "jit"
            self.warmed_buckets.add(b)
        return self

    # -- generative decode mode (ISSUE 18) ---------------------------------
    #
    # Autoregressive serving replaces the single forward program with
    # TWO program families: a PREFILL per prompt bucket (run the padded
    # prompt, park its KV into one pool slot, emit the first token's
    # logits) and a DECODE STEP per kv bucket (one token for every slot
    # at once, windowed to the step's serving bucket). Both families go
    # through the same persistent compile cache as the forward path —
    # same `make_key` discipline (placement/sharding/dtype), with an
    # `extra=("decode", kind, bucket)` discriminator because a step's
    # INPUT signature is identical across kv buckets (the bucket is a
    # static argument baked per executable, not a shape). Warmup
    # pre-compiles every (prompt bucket × kv bucket) so the decode
    # request path performs 0 XLA compiles — the same contract the
    # compile-cache spy asserts for the forward path.

    def load_generative(self, prefill_fn: Callable, step_fn: Callable,
                        params, paged_prefill_fn: Optional[Callable] = None,
                        paged_step_fn: Optional[Callable] = None,
                        ) -> "InferenceModel":
        """Load the decode-mode program pair (see models/generative.py
        for the exact calling contract). Single-device placement only:
        the KV pool is one device buffer threaded functionally through
        every call — replicating or sharding it is a later PR's
        problem, and silently ignoring the setting would serve from one
        chip while claiming many."""
        if self.placement != "replicated" or self.num_replicas != 1:
            raise ValueError(
                "load_generative supports single-device replicated "
                f"placement only (got placement={self.placement!r}, "
                f"num_replicas={self.num_replicas})")
        self.close()
        self._fn = None
        self._jit = None
        self._aot = {}
        self.serving_dtype = self._infer_serving_dtype(params)
        self._gen_prefill_fn = prefill_fn
        self._gen_step_fn = step_fn
        self._gen_paged_prefill_fn = paged_prefill_fn
        self._gen_paged_step_fn = paged_step_fn
        # one jit wrapper per program family; "step" wrappers are built
        # per kv bucket (the bucket is static — each is its own program)
        self._gen_jit = {"prefill": jax.jit(prefill_fn)}
        self._gen_aot = {}
        self._gen_cost = {}
        self._gen_fp = None
        if self.compile_cache is not None:
            from analytics_zoo_tpu.compile_cache import model_fingerprint
            # fingerprint BEFORE device placement, like load_fn; the
            # paged fns join the fingerprint only when supplied so a
            # non-paged deployment keeps its existing cache keys
            fns = (prefill_fn, step_fn)
            if paged_prefill_fn is not None or paged_step_fn is not None:
                fns = fns + (paged_prefill_fn, paged_step_fn)
            self._gen_fp = model_fingerprint(fns, params)
        if self._pin_single:
            self._params = jax.device_put(params, self.devices[0])
        else:
            self._params = jax.device_put(params)
        self.warmup_report = {}
        self.warmup_source = {}
        self.warmed_buckets = set()
        try:
            from analytics_zoo_tpu.observability.roofline import \
                get_accountant
            self._roofline = get_accountant()
            self._roofline.reset("serving")
        except Exception:  # noqa: BLE001 — telemetry only
            self._roofline = None
        return self

    def _gen_step_jit(self, kv_bucket: int):
        key = ("step", int(kv_bucket))
        jitted = self._gen_jit.get(key)
        if jitted is None:
            jitted = jax.jit(functools.partial(self._gen_step_fn,
                                               kv_bucket=int(kv_bucket)))
            self._gen_jit[key] = jitted
        return jitted

    def _gen_paged_step_jit(self, kv_bucket: int):
        key = ("paged_step", int(kv_bucket))
        jitted = self._gen_jit.get(key)
        if jitted is None:
            jitted = jax.jit(functools.partial(
                self._gen_paged_step_fn, kv_bucket=int(kv_bucket)))
            self._gen_jit[key] = jitted
        return jitted

    def _gen_paged_prefill_jit(self, kv_bucket: int):
        key = ("paged_prefill", int(kv_bucket))
        jitted = self._gen_jit.get(key)
        if jitted is None:
            jitted = jax.jit(functools.partial(
                self._gen_paged_prefill_fn, kv_bucket=int(kv_bucket)))
            self._gen_jit[key] = jitted
        return jitted

    @staticmethod
    def _gen_bucket_key(bucket):
        """Normalize a bucket discriminator: plain int for the PR 18
        families, (chunk_bucket, kv_bucket) tuple for paged prefill."""
        if isinstance(bucket, (tuple, list)):
            return tuple(int(b) for b in bucket)
        return int(bucket)

    def _warm_gen(self, kind: str, bucket, jitted, args) -> str:
        """Cache-backed warmup for one generative program — the decode
        analogue of `_warm_executable` (same funnel: every fresh
        compile goes through `serialization.compile_lowered`)."""
        from analytics_zoo_tpu.compile_cache import make_key, serialization
        bkey = self._gen_bucket_key(bucket)
        tkey = (kind, bkey)
        if tkey in self._gen_aot:
            return "warm"
        if not self._use_compile_cache():
            # plain-jit fallback: run once so jax's own cache holds the
            # executable; dispatch stays on the jit wrapper
            jax.block_until_ready(jitted(*args))
            try:
                from analytics_zoo_tpu.observability.roofline import cost_of
                c = cost_of(jitted.lower(*args))
                if c is not None:
                    self._gen_cost[tkey] = c
            except Exception:  # noqa: BLE001 — telemetry only
                pass
            return "jit"
        sig = self._exec_sig(args)
        key = make_key("serving", self._gen_fp or "", sig,
                       placement=self.placement,
                       dtype=self.serving_dtype
                       if self.serving_dtype != "float32" else "",
                       extra=("decode", kind) + (bkey if isinstance(
                           bkey, tuple) else (bkey,)))
        ex = self.compile_cache.load(key,
                                     target_device_id=self.devices[0].id)
        src = "cached"
        if ex is not None:
            stored = serialization.args_treedef(ex)
            if stored != serialization.live_treedef(args):
                ex = serialization.retree_call(ex, stored)
        else:
            t0 = time.perf_counter()
            # module-attribute call: serialization.compile_lowered is
            # THE fresh-compile funnel the 0-compile tests monkeypatch
            ex = serialization.compile_lowered(jitted.lower(*args))
            self.compile_cache.put(  # blocking-ok: disk cache write
                key, ex, compile_ms=(time.perf_counter() - t0) * 1e3)
            src = "compiled"
        self._gen_aot[tkey] = ex
        try:
            from analytics_zoo_tpu.observability.roofline import cost_of
            c = cost_of(ex)
            if c is not None:
                self._gen_cost[tkey] = c
        except Exception:  # noqa: BLE001 — telemetry only
            pass
        return src

    def warmup_generative(self, init_kv: Callable, slots: int,
                          max_kv_len: int, prompt_buckets: List[int],
                          kv_buckets: List[int]) -> "InferenceModel":
        """Pre-compile the whole decode program ladder: one prefill
        executable per prompt bucket, one step executable per kv
        bucket, each keyed (slots, bucket) through the persistent
        cache. The scratch KV pool built here is warmup-only — the
        engine allocates its own with identical shapes, so every
        request-path call lands on a warmed executable."""
        if getattr(self, "_gen_jit", None) is None:
            raise RuntimeError("load_generative() first")
        params = self._params
        kv = init_kv(int(slots), int(max_kv_len))
        for P in sorted({int(p) for p in prompt_buckets}):
            args = (params, kv, np.zeros(P, np.int32),
                    np.int32(1), np.int32(0))
            t0 = time.perf_counter()
            src = self._warm_gen("prefill", P, self._gen_jit["prefill"],
                                 args)
            ex = self._gen_aot.get(("prefill", P))
            if ex is not None:
                jax.block_until_ready(ex(*args))
            rkey = f"gen-prefill:p{P}"
            self.warmup_report[rkey] = round(time.perf_counter() - t0, 4)
            self.warmup_source[rkey] = src
        for b in sorted({int(b) for b in kv_buckets}):
            if b > max_kv_len:
                raise ValueError(f"kv bucket {b} exceeds max_kv_len "
                                 f"{max_kv_len}")
            args = (params, kv, np.zeros(slots, np.int32),
                    np.zeros(slots, np.int32))
            t0 = time.perf_counter()
            src = self._warm_gen("step", b, self._gen_step_jit(b), args)
            ex = self._gen_aot.get(("step", b))
            if ex is not None:
                jax.block_until_ready(ex(*args))
            rkey = f"gen-step:kv{b}"
            self.warmup_report[rkey] = round(time.perf_counter() - t0, 4)
            self.warmup_source[rkey] = src
        return self

    def warmup_generative_paged(self, init_kv_blocks: Callable,
                                num_blocks: int, block_len: int,
                                lanes: int, table_len: int,
                                chunk_buckets: List[int],
                                kv_buckets: List[int]) -> "InferenceModel":
        """Pre-compile the PAGED decode ladder: one chunked-prefill
        executable per (chunk bucket × context kv bucket) — the context
        window is 0 on a fresh first chunk and a kv bucket covering the
        adopted prefix plus earlier chunks otherwise — and one paged
        step executable per kv bucket, block tables in the signature.
        Same persistent-cache funnel as `warmup_generative`; the engine
        then performs 0 request-path compiles with the table in the
        loop."""
        if getattr(self, "_gen_paged_prefill_fn", None) is None:
            raise RuntimeError(
                "load_generative(..., paged_prefill_fn=, paged_step_fn=) "
                "first")
        params = self._params
        kv = init_kv_blocks(int(num_blocks), int(block_len))
        ctx_buckets = [0] + sorted({int(b) for b in kv_buckets})
        for Cb in sorted({int(c) for c in chunk_buckets}):
            for kvb in ctx_buckets:
                args = (params, kv, np.zeros(Cb, np.int32),
                        np.zeros(table_len, np.int32),
                        np.int32(0), np.int32(1))
                t0 = time.perf_counter()
                src = self._warm_gen("paged_prefill", (Cb, kvb),
                                     self._gen_paged_prefill_jit(kvb),
                                     args)
                ex = self._gen_aot.get(("paged_prefill", (Cb, kvb)))
                if ex is not None:
                    jax.block_until_ready(ex(*args))
                rkey = f"gen-paged-prefill:c{Cb}:kv{kvb}"
                self.warmup_report[rkey] = round(
                    time.perf_counter() - t0, 4)
                self.warmup_source[rkey] = src
        for b in sorted({int(b) for b in kv_buckets}):
            if b % int(block_len):
                raise ValueError(f"kv bucket {b} not a multiple of "
                                 f"block_len {block_len}")
            args = (params, kv, np.zeros(lanes, np.int32),
                    np.zeros(lanes, np.int32),
                    np.zeros((lanes, table_len), np.int32))
            t0 = time.perf_counter()
            src = self._warm_gen("paged_step", b,
                                 self._gen_paged_step_jit(b), args)
            ex = self._gen_aot.get(("paged_step", b))
            if ex is not None:
                jax.block_until_ready(ex(*args))
            rkey = f"gen-paged-step:kv{b}"
            self.warmup_report[rkey] = round(time.perf_counter() - t0, 4)
            self.warmup_source[rkey] = src
        return self

    def generative_prefill_paged(self, kv, tokens, table, pre_len,
                                 chunk_len, kv_bucket: int):
        """One prompt CHUNK through the warmed paged-prefill executable
        for its (chunk bucket, context bucket). Returns (kv, logits)."""
        tokens = np.ascontiguousarray(tokens, np.int32)
        args = (self._params, kv, tokens,
                np.ascontiguousarray(table, np.int32),
                np.int32(pre_len), np.int32(chunk_len))
        ex = self._gen_aot.get(
            ("paged_prefill", (int(tokens.shape[-1]), int(kv_bucket))))
        if ex is not None:
            return ex(*args)
        return self._gen_paged_prefill_jit(int(kv_bucket))(*args)

    def generative_step_paged(self, kv, tokens, positions, tables,
                              kv_bucket: int):
        """One decode step for every lane through the block tables.
        Returns (kv, logits[lanes, vocab])."""
        args = (self._params, kv,
                np.ascontiguousarray(tokens, np.int32),
                np.ascontiguousarray(positions, np.int32),
                np.ascontiguousarray(tables, np.int32))
        ex = self._gen_aot.get(("paged_step", int(kv_bucket)))
        if ex is not None:
            return ex(*args)
        return self._gen_paged_step_jit(int(kv_bucket))(*args)

    def generative_prefill(self, kv, tokens, length, slot):
        """One prompt through the warmed prefill executable for its
        bucket (tokens MUST already be padded to a warmed bucket).
        Returns (kv, logits)."""
        tokens = np.ascontiguousarray(tokens, np.int32)
        args = (self._params, kv, tokens, np.int32(length), np.int32(slot))
        ex = self._gen_aot.get(("prefill", int(tokens.shape[-1])))
        if ex is not None:
            return ex(*args)
        return self._gen_jit["prefill"](*args)

    def generative_step(self, kv, tokens, positions, kv_bucket: int):
        """One decode step for every slot under the static serving
        bucket. Returns (kv, logits[slots, vocab])."""
        args = (self._params, kv,
                np.ascontiguousarray(tokens, np.int32),
                np.ascontiguousarray(positions, np.int32))
        ex = self._gen_aot.get(("step", int(kv_bucket)))
        if ex is not None:
            return ex(*args)
        return self._gen_step_jit(int(kv_bucket))(*args)

    def account_generative(self, kind: str, bucket, secs: float):
        """Charge one generative call against the serving roofline with
        the cost harvested at warmup — decode is memory-bound and the
        Pallas kernel's analytic estimate is what makes the accountant
        see that (HLO cost analysis is blind inside a Mosaic call)."""
        if self._roofline is None:
            return
        cost = getattr(self, "_gen_cost", {}).get(
            (kind, self._gen_bucket_key(bucket)))
        if cost is None:
            return
        try:
            self._roofline.account("serving", cost.flops, cost.bytes,
                                   secs, n_devices=1)
        except Exception:  # noqa: BLE001 — telemetry only
            pass

    def compile_cache_size(self) -> int:
        """Number of in-process executables this model holds: AOT
        executables installed by cache-backed warmup PLUS the jit
        wrapper's own cache — which keys per (shape, committed device),
        so replicated placement counts its per-(replica, bucket)
        executables rather than reporting -1. -1 only when no counter
        is available at all (no model loaded on an old jax)."""
        n_aot = len(self._aot)
        try:
            n_jit = int(self._jit._cache_size())
        except Exception:  # noqa: BLE001 — diagnostics only
            return n_aot if n_aot else -1
        return n_aot + n_jit
