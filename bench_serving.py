"""Serving latency benchmark — p50/p99 end-to-end through the broker.

BASELINE.md target: p50 < 50 ms for the batched TPU InferenceModel behind
the Redis queue. The same workload runs through THREE broker paths and
reports each (the reference measures through Redis,
`docker/cluster-serving/perf/offline-benchmark:1-25`):

- memory: in-process MemoryBroker (stack floor: encode/batch/jit/decode)
- tcp:    TCPBrokerServer over a localhost socket
- redis:  RedisBroker speaking real RESP2 to the in-package
          MiniRedisServer over a localhost socket — the wire path a
          production Redis would serve; the headline number.

A closed-loop concurrent-client section measures SUSTAINED throughput
(what the single-in-flight p50 above cannot see): N client threads each
keep one request in flight against the pipelined engine (overlapped
decode/compute/sink, batched writeback) and against the old synchronous
loop on the same model — `serving_concurrent_rps_*` and the
`serving_pipeline_speedup` ratio. A warmup probe also reports post-
`warmup()` first-request latency vs steady-state p50 (no XLA compile on
the request path).

The model runs in this process on whatever backend jax selects; its child
processes (fleet engines, forced-host multi-device re-execs) pin themselves
to the CPU, since a chip belongs to one process at a time.

    python bench_serving.py

A multi-device section (`--devices N`) drains the same backlog through 1,
2, ..., N model replicas (one per forced-host device; re-execs itself
with `--xla_force_host_platform_device_count=N` when needed) plus one
GSPMD-sharded copy, and reports the scaling curve, per-replica batch
counts, and efficiency. NOTE the host-core ceiling: forced-host "chips"
burn real CPU cores, so an M-core box caps replica scaling at ~M× no
matter how many virtual devices exist; a real pod's chips compute
off-host and scale to the device count. Both the raw curve and the
core-normalized efficiency are reported.

    python bench_serving.py --devices 8
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np


N_REQUESTS = 200


def _setup_brokers(broker_kind: str, n_clients: int = 1):
    """One serving-side connection plus `n_clients` client connections;
    returns (serve_broker, client_brokers, server_or_None)."""
    from analytics_zoo_tpu.serving.broker import (MemoryBroker, RedisBroker,
                                                  TCPBroker, TCPBrokerServer)
    from analytics_zoo_tpu.serving.redis_server import MiniRedisServer

    if broker_kind == "memory":
        br = MemoryBroker()
        return br, [br] * n_clients, None
    if broker_kind == "tcp":
        server = TCPBrokerServer().start()
        return (TCPBroker(server.host, server.port),
                [TCPBroker(server.host, server.port)
                 for _ in range(n_clients)], server)
    if broker_kind == "redis":
        server = MiniRedisServer().start()
        return (RedisBroker(server.host, server.port),
                [RedisBroker(server.host, server.port)
                 for _ in range(n_clients)], server)
    raise ValueError(broker_kind)


def _teardown_brokers(serve_broker, client_brokers, server):
    for br in [serve_broker] + list(client_brokers):
        if hasattr(br, "close"):
            br.close()
    if server is not None:
        server.stop()


def _measure(infer, broker_kind: str, n: int = N_REQUESTS):
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.server import ClusterServing

    serve_broker, clients, server = _setup_brokers(broker_kind, 1)
    serving = ClusterServing(infer, broker=serve_broker, batch_size=32,
                             batch_timeout_ms=2).start()
    inq = InputQueue(clients[0])
    outq = OutputQueue(clients[0])

    img = np.random.rand(32, 32, 3).astype(np.float32)
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        uri = inq.enqueue(t=img)
        while True:
            res = outq.query(uri, delete=True)
            if res is not None:
                break
            time.sleep(0.0005)
        lat.append((time.perf_counter() - t0) * 1e3)
    serving.stop()
    _teardown_brokers(serve_broker, clients, server)
    lat = np.asarray(sorted(lat))
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 99)))


def _measure_concurrent(infer, broker_kind: str, n_clients: int = 8,
                        total: int = 320, pipelined: bool = True,
                        batch_size: int = 32, sample=None):
    """Closed loop, `n_clients` logical clients: a request is submitted
    the moment one completes, keeping exactly `n_clients` in flight. One
    single-threaded loop drives all of them — per-client polling threads
    would measure GIL/poll churn, not the engine. Each sweep drains
    completed results with one `hgetall` + one batched delete, then
    backfills one submit per completion. Returns (sustained records/s,
    p50 ms, p99 ms)."""
    from analytics_zoo_tpu.serving.client import RESULT_KEY, InputQueue
    from analytics_zoo_tpu.serving.server import ClusterServing

    serve_broker, (submit_br, poll_br), server = _setup_brokers(
        broker_kind, 2)
    serving = ClusterServing(infer, broker=serve_broker,
                             batch_size=batch_size,
                             batch_timeout_ms=2,
                             pipelined=pipelined).start()
    img = sample if sample is not None \
        else np.random.rand(32, 32, 3).astype(np.float32)
    inq = InputQueue(submit_br)
    inflight = {}
    lat = []
    submitted = 0

    def submit():
        nonlocal submitted
        uri = inq.enqueue(t=img)
        inflight[uri] = time.perf_counter()
        submitted += 1

    t_wall = time.perf_counter()
    for _ in range(min(n_clients, total)):
        submit()
    deadline = time.time() + 120
    while len(lat) < total and time.time() < deadline:
        allr = poll_br.hgetall(RESULT_KEY)
        done = [u for u in allr if u in inflight]
        if not done:
            time.sleep(0.001)
            continue
        now = time.perf_counter()
        poll_br.hdel_many(RESULT_KEY, done)
        for uri in done:
            lat.append((now - inflight.pop(uri)) * 1e3)
            if submitted < total:
                submit()
    t_wall = time.perf_counter() - t_wall
    serving.stop()
    _teardown_brokers(serve_broker, [submit_br, poll_br], server)
    if not lat:
        return 0.0, float("nan"), float("nan")
    arr = np.asarray(sorted(lat))
    return (len(lat) / t_wall,
            float(np.percentile(arr, 50)), float(np.percentile(arr, 99)))


def _measure_drain(infer, broker_kind: str, total: int = 480,
                   pipelined: bool = True, batch_size: int = 32,
                   sample=None):
    """Engine-limited throughput: pre-fill the stream with `total`
    records, start the engine, time until every result lands. Client
    costs are excluded (the backlog already exists), so unlike the
    closed loop this is stable run-to-run and measures the serving
    engine itself."""
    from analytics_zoo_tpu.serving.client import RESULT_KEY, InputQueue
    from analytics_zoo_tpu.serving.server import ClusterServing

    serve_broker, (submit_br, poll_br), server = _setup_brokers(
        broker_kind, 2)
    img = sample if sample is not None \
        else np.random.rand(32, 32, 3).astype(np.float32)
    inq = InputQueue(submit_br)
    for _ in range(total):
        inq.enqueue(t=img)
    serving = ClusterServing(infer, broker=serve_broker,
                             batch_size=batch_size,
                             batch_timeout_ms=2,
                             pipelined=pipelined).start()
    t0 = time.perf_counter()
    ndone = 0
    deadline = time.time() + 120
    while ndone < total and time.time() < deadline:
        allr = poll_br.hgetall(RESULT_KEY)
        if allr:
            poll_br.hdel_many(RESULT_KEY, list(allr))
            ndone += len(allr)
        else:
            time.sleep(0.001)
    dt = time.perf_counter() - t0
    serving.stop()
    _teardown_brokers(serve_broker, [submit_br, poll_br], server)
    return ndone / dt


def _measure_decode_ab(infer, total: int = 480, rounds: int = 3):
    """Decode-share A/B (ISSUE 9 satellite): the ~0.24 ms host-side gap
    between `serving_p50_ms` and wire-only p50 is decode + dispatch
    work; zero-copy decode writes each record straight into a
    preallocated bucket-shaped batch buffer (no per-record ndarray, no
    dispatch-stage np.stack). Engine-limited drain per mode, reading
    each ENGINE'S OWN stage timers (fresh per ClusterServing, so the
    two modes can't contaminate each other's percentiles). Interleaved
    rounds + per-mode MEDIAN, like the concurrent bench: a single
    drain's percentiles ride whatever the host scheduler did that
    second (first-round cold starts measured 2x on the 2-core rig)."""
    from analytics_zoo_tpu.serving.client import RESULT_KEY, InputQueue
    from analytics_zoo_tpu.serving.server import ClusterServing

    runs = {"legacy": [], "zero_copy": []}
    for _ in range(rounds):
        for label, zero_copy in (("legacy", False), ("zero_copy", True)):
            serve_broker, (submit_br, poll_br), server = _setup_brokers(
                "redis", 2)
            inq = InputQueue(submit_br)
            img = np.random.rand(32, 32, 3).astype(np.float32)
            for _ in range(total):
                inq.enqueue(t=img)
            serving = ClusterServing(infer, broker=serve_broker,
                                     batch_size=32, batch_timeout_ms=2,
                                     pipelined=True,
                                     zero_copy_decode=zero_copy).start()
            t0 = time.perf_counter()
            ndone = 0
            deadline = time.time() + 120
            while ndone < total and time.time() < deadline:
                allr = poll_br.hgetall(RESULT_KEY)
                if allr:
                    poll_br.hdel_many(RESULT_KEY, list(allr))
                    ndone += len(allr)
                else:
                    time.sleep(0.001)
            dt = time.perf_counter() - t0
            stages = {name: t.snapshot() for name, t in
                      (("decode", serving.decode_timer),
                       ("dispatch", serving.dispatch_timer))}
            serving.stop()
            _teardown_brokers(serve_broker, [submit_br, poll_br], server)
            runs[label].append((ndone / dt, stages["decode"]["p50_ms"],
                                stages["dispatch"]["p50_ms"]))
    out = {}
    for label, rows in runs.items():
        out[label] = {
            "drain_rps": round(float(np.median([r[0] for r in rows])), 1),
            "decode_p50_ms": float(np.median([r[1] for r in rows])),
            "dispatch_p50_ms": float(np.median([r[2] for r in rows])),
        }
    host = out["legacy"]["decode_p50_ms"] + out["legacy"]["dispatch_p50_ms"]
    zc = (out["zero_copy"]["decode_p50_ms"]
          + out["zero_copy"]["dispatch_p50_ms"])
    out["decode_dispatch_p50_cut_ms"] = round(host - zc, 4)
    return out


def _measure_trace_overhead(infer, total: int = 480, rounds: int = 3):
    """Trace-overhead A/B (ISSUE 17 satellite): engine-limited drain at
    head-sampling 0 / 0.01 / 1.0, fresh brokers + engine per mode per
    round so one mode's exporter thread can't ride in another's timing
    window. Interleaved rounds + per-mode MEDIAN, same estimator as the
    decode A/B — a single drain's rps rides host scheduling. The client
    stamps trace context at the matching rate (`InputQueue
    trace_sample`), so sampled drains pay the real wire cost too: the
    extra dict per record, the engine's wire/device/writeback spans,
    the hops row in each result, and the export thread. At full
    sampling the collector assembles a few finished requests from the
    published blobs — the `/trace/<id>` cost a debugging session
    actually pays."""
    from analytics_zoo_tpu.serving.client import RESULT_KEY, InputQueue
    from analytics_zoo_tpu.serving.server import ClusterServing
    from analytics_zoo_tpu.serving.trace_plane import TraceCollector

    modes = (("off", 0.0), ("1pct", 0.01), ("full", 1.0))
    runs = {label: [] for label, _ in modes}
    assembly_ms = []
    for _ in range(rounds):
        for label, rate in modes:
            serve_broker, (submit_br, poll_br), server = _setup_brokers(
                "redis", 2)
            inq = InputQueue(submit_br, trace_sample=rate)
            img = np.random.rand(32, 32, 3).astype(np.float32)
            uris = [inq.enqueue(t=img) for _ in range(total)]
            serving = ClusterServing(infer, broker=serve_broker,
                                     batch_size=32, batch_timeout_ms=2,
                                     pipelined=True, trace_sample=rate,
                                     trace_export_interval_s=0.2).start()
            t0 = time.perf_counter()
            ndone = 0
            deadline = time.time() + 120
            while ndone < total and time.time() < deadline:
                allr = poll_br.hgetall(RESULT_KEY)
                if allr:
                    poll_br.hdel_many(RESULT_KEY, list(allr))
                    ndone += len(allr)
                else:
                    time.sleep(0.001)
            dt = time.perf_counter() - t0
            serving.stop()        # flushes the exporter's final blob
            if label == "full":
                coll = TraceCollector(poll_br, "serving_stream")
                for uri in uris[:8]:
                    ta = time.perf_counter()
                    doc = coll.assemble(uri)
                    if doc.get("traceEvents"):
                        assembly_ms.append(
                            (time.perf_counter() - ta) * 1e3)
            _teardown_brokers(serve_broker, [submit_br, poll_br], server)
            runs[label].append(ndone / dt)
    out = {label: {"drain_rps": round(float(np.median(r)), 1)}
           for label, r in runs.items()}
    off = out["off"]["drain_rps"]
    out["overhead_1pct_pct"] = round(
        100.0 * (1.0 - out["1pct"]["drain_rps"] / max(off, 1e-9)), 2)
    out["overhead_full_pct"] = round(
        100.0 * (1.0 - out["full"]["drain_rps"] / max(off, 1e-9)), 2)
    if assembly_ms:
        out["assembly_p50_ms"] = round(float(np.median(assembly_ms)), 3)
    return out


def _trace_overhead_main(args) -> int:
    """--trace-overhead (ISSUE 17): the acceptance bound — 1% head
    sampling costs ≤ 2% of engine-limited drain throughput vs tracing
    off. Full (100%) sampling is reported beside it as the ceiling a
    debug session pays, plus the collector's assembly latency."""
    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    init_orca_context(cluster_mode="local")
    model = _serving_model()
    infer = InferenceModel(concurrent_num=2).load_keras(model)
    infer.warmup(np.zeros((32, 32, 3), np.float32),
                 buckets=[1, 2, 4, 8, 16, 32])
    ab = _measure_trace_overhead(infer, total=int(args.total) or 480)
    stop_orca_context()
    print(json.dumps({
        "metric": "serving_trace_overhead",
        "target_overhead_1pct_pct": 2.0,
        "trace_off_rps": ab["off"]["drain_rps"],
        "trace_1pct_rps": ab["1pct"]["drain_rps"],
        "trace_full_rps": ab["full"]["drain_rps"],
        "trace_overhead_1pct_pct": ab["overhead_1pct_pct"],
        "trace_overhead_full_pct": ab["overhead_full_pct"],
        "trace_assembly_p50_ms": ab.get("assembly_p50_ms"),
        "note": ("median of interleaved engine-limited drains per "
                 "sampling rate; negative overhead = host-scheduling "
                 "noise exceeded the tracing cost at this scale"),
    }))
    return 0


def _warmup_probe(model, replicas: int = 3):
    """Fresh InferenceModel + warmup(): is the FIRST request's latency
    within noise of steady-state (i.e. no compile on the request path)?
    Min over independent fresh replicas: a single first-request sample on
    a loaded box measures scheduler noise, while a compile on the request
    path would inflate EVERY replica's first request, so the min still
    detects it.

    The replicas share one persistent compile cache (a throwaway dir):
    the first pays the compiles and persists, the rest warm from disk —
    so the probe also reports how many buckets each restart compiled vs
    loaded (`warmup_source` counts)."""
    import shutil
    import tempfile

    from analytics_zoo_tpu.compile_cache import CompileCache
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    cache_dir = tempfile.mkdtemp(prefix="zoo-cc-probe-")
    x = np.random.rand(8, 32, 32, 3).astype(np.float32)  # exact bucket
    firsts, steadies = [], []
    sources = {"compiled": 0, "cached": 0, "jit": 0}
    try:
        cache = CompileCache(cache_dir)
        for _ in range(replicas):
            infer = InferenceModel(compile_cache=cache).load_keras(model)
            infer.warmup(np.zeros((32, 32, 3), np.float32),
                         buckets=[1, 2, 4, 8, 16, 32])
            for src in infer.warmup_source.values():
                sources[src] = sources.get(src, 0) + 1
            t0 = time.perf_counter()
            infer.predict(x)
            firsts.append((time.perf_counter() - t0) * 1e3)
            steady = []
            for _ in range(30):
                t0 = time.perf_counter()
                infer.predict(x)
                steady.append((time.perf_counter() - t0) * 1e3)
            steadies.append(float(np.percentile(np.asarray(steady), 50)))
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return min(firsts), float(np.median(steadies)), sources


# -- multi-device: replica pool + sharded placement ------------------------

def _md_model(width: int = 512, iters: int = 32):
    """Compute-heavy-per-batch forward: a fori_loop of small (width x
    width) matmuls. Small matmuls keep XLA:CPU from spreading ONE
    execution across cores, so concurrent replicas — not intra-op
    threads — are the only way to use the whole machine; that mirrors a
    TPU pod, where each replica's compute runs off-host on its own chip.
    Returns (fn, params, one_record_sample)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    W = (rng.randn(width, width).astype(np.float32) / np.sqrt(width))

    def fn(p, x):
        def body(_, c):
            return jnp.tanh(c @ p)
        return jax.lax.fori_loop(0, iters, body, x)

    return fn, W, rng.rand(width).astype(np.float32)


def multidevice_summary(n_devices: int, total: int = 256,
                        batch_size: int = 8, replica_counts=None,
                        closed_loop: bool = True) -> dict:
    """Backlog-drain scaling curve over the replica pool (requires
    `len(jax.devices()) >= n_devices`; see `--devices` for the re-exec
    wrapper). Per-replica batch counts come from the router's own
    book-keeping, so the JSON shows WHERE the work actually ran."""
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    fn, W, sample = _md_model()
    counts = sorted({c for c in (replica_counts or
                                 [1, 2, max(1, n_devices // 2), n_devices])
                     if 1 <= c <= n_devices})
    drain_rps, per_replica = {}, {}
    # every bucket the reader can form (straggler batches < batch_size
    # included) pre-compiles, or a mid-drain XLA compile pollutes the
    # scaling baseline
    def reachable(im):
        return [b for b in im.buckets if b <= batch_size] or im.buckets[:1]

    for r in counts:
        im = InferenceModel(num_replicas=r).load_fn(fn, W)
        im.warmup(sample, buckets=reachable(im))  # compile off the clock
        # best-of-2: an engine-limited drain is deterministic work, so
        # the max filters one-sided scheduler noise (the 2-core rigs
        # swing single runs 2-3x; a mean would keep the outlier). The
        # per-replica routing counts are the BEST run's delta, not the
        # sum over both — the JSON describes the run it publishes.
        best_rps, best_counts = 0.0, []
        for _ in range(2):
            before = [s["batches"] for s in im.replica_stats()]
            rps = _measure_drain(im, "memory", total=total,
                                 batch_size=batch_size, sample=sample)
            after = [s["batches"] for s in im.replica_stats()]
            if rps >= best_rps:
                best_rps = rps
                best_counts = [None if a is None else a - (b or 0)
                               for a, b in zip(after, before)]
        drain_rps[str(r)] = round(best_rps, 1)
        per_replica[str(r)] = best_counts
        im.close()

    ims = InferenceModel(placement="sharded").load_fn(fn, W)
    ims.warmup(sample, buckets=reachable(ims))
    sharded_rps = max(_measure_drain(ims, "memory", total=total,
                                     batch_size=batch_size, sample=sample)
                      for _ in range(2))

    base = drain_rps[str(counts[0])]
    best_r = max(drain_rps, key=lambda k: drain_rps[k])
    speedup = drain_rps[str(counts[-1])] / max(base, 1e-9)
    cores = os.cpu_count() or 1
    out = {
        "metric": "serving_multidevice_drain",
        "devices": n_devices,
        "host_cores": cores,
        "total_records": total,
        "batch_size": batch_size,
        "drain_rps": drain_rps,
        "drain_rps_sharded": round(sharded_rps, 1),
        "scaling_speedup": round(speedup, 2),
        "best_speedup": round(drain_rps[best_r] / max(base, 1e-9), 2),
        "best_replicas": int(best_r),
        "scaling_efficiency": round(speedup / n_devices, 3),
        # forced-host devices burn real cores: an M-core box caps replica
        # scaling at ~M x regardless of virtual device count. A real pod's
        # chips compute off-host, so there the ceiling IS the device count.
        "efficiency_vs_host_cores": round(
            speedup / min(n_devices, cores), 3),
        "per_replica_batches": per_replica,
        "note": ("forced-host devices share the host's cores: replica "
                 f"scaling here caps near {min(n_devices, cores)}x "
                 "(and oversubscribing threads past the core count can "
                 "degrade); on a real pod each chip computes off-host, "
                 "so the ceiling is the device count"),
    }
    if closed_loop:
        for label, r in (("1", 1), (str(n_devices), n_devices)):
            im = InferenceModel(num_replicas=r).load_fn(fn, W)
            im.warmup(sample, buckets=reachable(im))
            rps, p50, _p99 = _measure_concurrent(
                im, "memory", n_clients=4 * n_devices, total=total,
                batch_size=batch_size, sample=sample)
            out[f"closed_loop_rps_{label}"] = round(rps, 1)
            out[f"closed_loop_p50_ms_{label}"] = round(p50, 2)
            im.close()
    return out


def _multidevice_main(args) -> int:
    """`--devices N`: run `multidevice_summary` on an N-device platform,
    re-execing into a forced-host CPU child when this interpreter sees
    fewer devices (env must be set before jax initializes its backend —
    same pattern as `__graft_entry__._reexec_dryrun`)."""
    n = args.devices
    if len(jax.devices()) < n \
            and os.environ.get("_ZOO_MD_BENCH_CHILD") != "1":
        env = dict(os.environ)
        env["_ZOO_MD_BENCH_CHILD"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={n}").strip()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--devices", str(n), "--total", str(args.total)],
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=1800)
        return proc.returncode
    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    init_orca_context(cluster_mode="local")
    summary = multidevice_summary(n, total=args.total)
    stop_orca_context()
    print(json.dumps(summary))
    return 0


# -- chaos: fault injection against a live engine (ISSUE 5) ----------------

def _chaos_summary(n_devices: int = 4, batch_size: int = 4) -> dict:
    """Drive the fault-tolerance layer with real faults and measure what
    an operator cares about: how fast a bad replica is quarantined, how
    fast it revives, whether a broker outage loses accepted records, and
    how much throughput survives after recovery.

    Acceptance (ISSUE 5): zero accepted-record loss, quarantine
    detection under 2 s, post-recovery drain throughput within 10% of
    the no-fault baseline."""
    from analytics_zoo_tpu.common import faults
    from analytics_zoo_tpu.serving.broker import MemoryBroker
    from analytics_zoo_tpu.serving.client import RESULT_KEY, InputQueue
    from analytics_zoo_tpu.serving.inference_model import InferenceModel
    from analytics_zoo_tpu.serving.server import ClusterServing

    fn, W, sample = _md_model(width=128, iters=8)
    im = InferenceModel(num_replicas=n_devices).load_fn(fn, W)
    im.warmup(sample,
              buckets=[b for b in im.buckets if b <= batch_size]
              or im.buckets[:1])
    broker = MemoryBroker(redeliver_after_s=2.0)
    serving = ClusterServing(
        im, broker=broker, batch_size=batch_size, batch_timeout_ms=2,
        failure_threshold=3, probe_interval_s=0.1, latency_factor=6.0,
        breaker_failure_threshold=2, breaker_reset_s=0.1).start()
    inq = InputQueue(broker)

    def collect(n, deadline_s=120.0, t0=None):
        """Wait for n results; returns (got, nans, seconds)."""
        t0 = time.perf_counter() if t0 is None else t0
        got = nans = 0
        deadline = time.time() + deadline_s
        while got < n and time.time() < deadline:
            allr = broker.hgetall(RESULT_KEY)
            if allr:
                broker.hdel_many(RESULT_KEY, list(allr))
                got += len(allr)
                nans += sum(1 for v in allr.values() if v == "NaN")
            else:
                time.sleep(0.002)
        return got, nans, time.perf_counter() - t0

    from analytics_zoo_tpu.serving.broker import encode_ndarray
    encoded = encode_ndarray(np.asarray(sample))

    def drain_rps(total=400):
        # engine-limited: the record payload is pre-encoded ONCE and
        # xadd'd raw, so the submit loop costs ~µs/record and the clock
        # (from first submit to last result) measures the ENGINE, not a
        # b64-encoding client contending for the same two cores
        import uuid
        t0 = time.perf_counter()
        for _ in range(total):
            broker.xadd(serving.stream,
                        {"uri": uuid.uuid4().hex, "data": {"t": encoded}})
        got, _nans, _dt = collect(total, t0=t0)
        return got / max(time.perf_counter() - t0, 1e-9)

    def feed_until(cond, timeout_s=20.0):
        """Steady singles until cond(); returns (elapsed or None, fed)."""
        t0 = time.monotonic()
        fed = 0
        while time.monotonic() - t0 < timeout_s:
            inq.enqueue(t=sample)
            fed += 1
            if cond():
                return time.monotonic() - t0, fed
            time.sleep(0.005)
        return None, fed

    def wait_healthy(n, timeout_s=30.0):
        t0 = time.monotonic()
        while im.healthy_replicas() < n:
            if time.monotonic() - t0 > timeout_s:
                return None
            time.sleep(0.01)
        return time.monotonic() - t0

    out = {"metric": "serving_chaos_record_loss", "unit": "records",
           "replicas": n_devices, "host_cores": os.cpu_count() or 1}

    # -- no-fault baseline (best of 3: single runs on a loaded 2-core
    # host swing ±2x one-sided; the max filters scheduler noise, same
    # estimator as multidevice_summary) ------------------------------------
    drain_rps()            # discarded: thread/executable warm-up drain
    baseline = max(drain_rps() for _ in range(3))

    # -- phase 1: replica crash → quarantine → revival ---------------------
    faults.inject("replica.dispatch",
                  faults.Fault(match=lambda c: c["replica"] == 1))
    detect_s, fed = feed_until(
        lambda: im.healthy_replicas() < n_devices)
    _got, crash_nans, _ = collect(fed, deadline_s=60)
    faults.clear("replica.dispatch")
    revive_s = wait_healthy(n_devices)
    out["quarantine_detect_s"] = round(detect_s, 3) if detect_s else None
    out["quarantine_revive_s"] = round(revive_s, 3) \
        if revive_s is not None else None
    out["crash_nan_results"] = crash_nans   # pre-quarantine degradations

    # -- phase 2: slow replica → latency-outlier quarantine ----------------
    faults.inject("replica.dispatch",
                  faults.Fault(mode="stall", delay_s=0.25,
                               match=lambda c: c["replica"] == 2))
    slow_s, fed = feed_until(
        lambda: im.healthy_replicas() < n_devices, timeout_s=30.0)
    collect(fed, deadline_s=60)
    faults.clear("replica.dispatch")
    wait_healthy(n_devices)
    out["slow_quarantine_detect_s"] = round(slow_s, 3) if slow_s else None

    # -- phase 3: broker outage → buffered writebacks, zero loss -----------
    from analytics_zoo_tpu.observability import get_registry
    shed = get_registry().get("serving_sink_shed_records_total")
    shed_before = shed.value() if shed else 0.0
    n_outage = 80
    for _ in range(30):
        inq.enqueue(t=sample)
    outage = faults.Fault(match=lambda c: c["role"] in ("reader", "sink"))
    faults.inject("broker.read_group", outage)
    faults.inject("broker.hset_many", outage)
    faults.inject("broker.ack", outage)
    threading.Timer(1.0, lambda: (faults.clear("broker.read_group"),
                                  faults.clear("broker.hset_many"),
                                  faults.clear("broker.ack"))).start()
    for _ in range(n_outage - 30):
        inq.enqueue(t=sample)
        time.sleep(0.002)
    got, outage_nans, _ = collect(n_outage, deadline_s=90)
    faults.clear()
    out["value"] = n_outage - got            # record loss — must be 0
    out["target"] = 0
    out["vs_baseline"] = 1.0 if got == n_outage else 0.0
    out["broker_outage_records"] = n_outage
    out["broker_outage_nans"] = outage_nans
    out["shed_records"] = round(
        (shed.value() if shed else 0.0) - shed_before, 1)

    # -- phase 4: post-recovery throughput (same best-of-3 estimator) ------
    post = max(drain_rps() for _ in range(3))
    out["baseline_drain_rps"] = round(baseline, 1)
    out["post_recovery_drain_rps"] = round(post, 1)
    out["post_recovery_ratio"] = round(post / max(baseline, 1e-9), 3)
    out["post_recovery_target"] = ">=0.9"

    serving.stop()
    im.close()
    return out


def _chaos_main(args) -> int:
    """`--chaos`: run `_chaos_summary` on a >=4-device platform,
    re-execing into a forced-host CPU child when needed (same pattern as
    `--devices`)."""
    n = max(4, getattr(args, "devices", None) or 4)
    if len(jax.devices()) < n \
            and os.environ.get("_ZOO_CHAOS_BENCH_CHILD") != "1":
        env = dict(os.environ)
        env["_ZOO_CHAOS_BENCH_CHILD"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={n}").strip()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--chaos"],
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=1800)
        return proc.returncode
    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    init_orca_context(cluster_mode="local")
    summary = _chaos_summary(n)
    stop_orca_context()
    print(json.dumps(summary))
    return 0


# -- fleet: N engine processes behind one broker (ISSUE 10) ----------------

def _fleet_child(args) -> int:
    """One fleet engine, in its own process: build the compute-heavy
    model, warm through the SHARED compile cache (engine 1 compiles,
    the rest load — the fleet pays ~1 cold compile per bucket), report
    readiness, hold at the start gate, then join the consumer group
    under `--engine-id`, heartbeat, and drain until SIGTERM. SIGKILL
    (the chaos leg) is the point of the exercise: no cleanup runs, the
    PEL keeps this engine's unacked records, and a live peer's claim
    sweep adopts them.

    The ready-row/gate handshake (fleet:ready:<stream> /
    fleet:gate:<stream>) lets the parent prefill the WHOLE backlog
    before any engine consumes: without it the drain overlaps the
    parent's sequential xadd loop, engines run starved 1-2 record
    batches (predict p50 collapsed from 17 ms/8-rec batch to ~1.4 ms
    micro-batches when measured), and the curve benchmarks the
    prefill's contended xadd rate instead of fleet drain capacity."""
    import signal

    if args.pin_core is not None and hasattr(os, "sched_setaffinity"):
        # one core per engine (BEFORE jax sizes its threadpools): the
        # process-level analogue of forced-host devices — without it a
        # single engine's intra-op XLA threads saturate every core and
        # the fleet curve measures threadpool contention, not scaling
        try:
            os.sched_setaffinity(
                0, {args.pin_core % (os.cpu_count() or 1)})
        except OSError:
            pass

    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.compile_cache import CompileCache
    from analytics_zoo_tpu.serving.broker import connect_broker
    from analytics_zoo_tpu.serving.inference_model import InferenceModel
    from analytics_zoo_tpu.serving.server import ClusterServing

    init_orca_context(cluster_mode="local")
    # heavier per-record compute than the in-process multidevice bench:
    # the engine must be the limiter, not the pure-python MiniRedis
    # data plane (~2800 rec/s ceiling on this rig; a production Redis
    # is far above the curve). NARROW matmuls on purpose: at width 256
    # one execution stays on ONE thread (cpu/wall ~1.0 measured; 512
    # already spreads ~1.4 threads), so a single engine can't absorb
    # the whole host and fake the fleet baseline — essential where
    # sched_setaffinity isn't enforced (gVisor-style sandboxes accept
    # the call without binding). Same FLOPs/record as 512x256. The
    # forward reduces to ONE scalar per record so the writeback side
    # stays bytes-cheap too — drain scaling should measure compute,
    # not RESP serialization of 512-float rows.
    base_fn, W, sample = _md_model(width=256, iters=1024)
    rollout_version = None
    if args.rollout_dir:
        # chaos-rollout leg (ISSUE 14): the versioned weights come
        # from the published checkpoint dir, not the generator — every
        # engine starts on the newest PUBLISHED version and then
        # follows the controller's directives
        from analytics_zoo_tpu.learn.checkpoint import (
            latest_published_checkpoint, load_checkpoint)
        found = latest_published_checkpoint(args.rollout_dir)
        if found is None:
            raise SystemExit(
                f"no published checkpoint under {args.rollout_dir}")
        run_dir, rollout_version = found
        W, _, _ = load_checkpoint(run_dir, rollout_version)

    def fn(p, x):
        return base_fn(p, x).mean(axis=-1)
    cache = CompileCache(args.compile_cache_dir) \
        if args.compile_cache_dir else None
    im = InferenceModel(compile_cache=cache).load_fn(fn, W)
    batch = args.fleet_batch
    im.warmup(sample, buckets=[b for b in im.buckets if b <= batch]
              or im.buckets[:1])
    broker = connect_broker(args.broker_url)
    # construct BEFORE the gate (connections, registry wiring, replica
    # pool) so the timed drain window starts at reader-thread launch
    slo = {"latency_ms": args.slo_latency_ms, "latency_quantile": 0.99,
           "window_s": 10.0} if args.slo_latency_ms else None
    serving = ClusterServing(
        im, broker=broker, stream=args.stream,
        batch_size=batch, batch_timeout_ms=args.batch_timeout_ms,
        engine_id=args.engine_id,
        claim_min_idle_s=args.claim_min_idle,
        claim_interval_s=max(args.claim_min_idle / 4.0, 0.1),
        heartbeat_interval_s=0.25,
        # elastic knobs (ISSUE 11): the --elastic replay runs adaptive
        # deadline-aware engines against "static" pad-to-largest ones
        batch_policy=args.batch_policy,
        deadline_ms=args.deadline_ms or None,
        slo=slo, model_version=rollout_version,
        # request-plane knobs (ISSUE 16): the --request-plane scaling
        # leg runs p engines over p partition streams; default 1 keeps
        # every other leg on the legacy unsuffixed stream
        partitions=args.partitions,
        partition_lease_ttl_s=args.partition_lease_ttl)
    broker.hset(f"fleet:ready:{args.stream}", args.engine_id, "1")
    gate_deadline = time.time() + 600
    while not broker.hget(f"fleet:gate:{args.stream}", "go"):
        if time.time() > gate_deadline:
            raise SystemExit("fleet start gate never opened")
        time.sleep(0.02)
    serving.start()
    agent = None
    exec_before = im.compile_cache_size()
    if args.rollout_dir:
        from analytics_zoo_tpu.serving.rollout import EngineRolloutAgent
        agent = EngineRolloutAgent(
            serving, broker.clone(), stream=args.stream,
            poll_interval_s=0.1, drain_timeout_s=5.0,
            canary_timeout_s=10.0).start()
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    while not stop:
        time.sleep(0.05)
    if agent is not None:
        agent.stop()
    # owned set BEFORE stop(): a clean stop releases every lease, so
    # reading after would always report []
    owned_at_stop = serving.lease_table.owned() \
        if args.partitions > 1 else None
    serving.stop()
    sources = {}
    for v in im.warmup_source.values():
        sources[v] = sources.get(v, 0) + 1
    m = serving.metrics()
    stages = {k: round(v.get("p50_ms", 0.0), 2)
              for k, v in m.get("stages", {}).items()}
    stages["predict"] = round(m["predict"].get("p50_ms", 0.0), 2)
    n_batches = m.get("stages", {}).get("dispatch", {}).get("count", 0)
    report = {"engine_id": args.engine_id,
              "sources": sources,
              "records_served": serving.records_served,
              "stage_p50_ms": stages,
              "avg_read_batch": round(
                  serving.records_read / n_batches, 2)
              if n_batches else None,
              "claimed_records": m.get("claimed_records", 0)}
    if owned_at_stop is not None:
        report["partitions_owned"] = owned_at_stop
    if args.rollout_dir:
        # the 0-compiles-on-swap evidence: executable count after the
        # rollout minus before — a same-structure swap adds nothing
        report["model_version"] = serving.model_version
        report["swap"] = agent.last_swap if agent is not None else None
        report["executables_delta"] = \
            im.compile_cache_size() - exec_before
    print(json.dumps(report))
    return 0


def _fleet_spawn(k, stream, port, cache_dir, claim_min_idle, batch,
                 start_idx=0, extra_args=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = []
    for i in range(start_idx, start_idx + k):
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--fleet-child",
             "--broker-url", f"redis://127.0.0.1:{port}",
             "--stream", stream, "--engine-id", f"engine-{i}",
             "--compile-cache-dir", cache_dir,
             "--claim-min-idle", str(claim_min_idle),
             "--fleet-batch", str(batch), "--pin-core", str(i)]
            + list(extra_args),
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    return procs


def _measure_host_parallelism(seconds: float = 2.0) -> float:
    """Effective parallel speedup this host grants 2 CPU-bound
    processes RIGHT NOW (2.0 = two real cores, ~1.0 = an oversubscribed
    or one-core sandbox). Shared CI hosts swing between the two within
    minutes (measured 1.96x and 0.82x on the same rig the same day),
    and gVisor-style sandboxes accept sched_setaffinity without
    binding — so the fleet curve records the capacity that actually
    backed it instead of trusting os.cpu_count()."""
    code = ("import time,sys\n"
            "w0=time.perf_counter(); x=0\n"
            "while time.perf_counter()-w0 < %f: x+=1\n"
            "print(x)" % seconds)

    def run(k):
        procs = [subprocess.Popen([sys.executable, "-c", code],
                                  stdout=subprocess.PIPE, text=True)
                 for _ in range(k)]
        total = 0
        for p in procs:
            out, _ = p.communicate(timeout=60 + seconds)
            total += int(out)
        return total

    solo = run(1)
    duo = run(2)
    return round(duo / max(solo, 1), 2)


def _fleet_wait_ready(broker, stream, procs, n, timeout_s=300.0):
    """Wait until n engines have warmed and parked at the start gate."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        for p in procs:
            if p.poll() is not None:
                _, err = p.communicate()
                raise SystemExit(
                    f"fleet engine died during startup (rc="
                    f"{p.returncode}):\n{err[-2000:]}")
        if broker.hlen(f"fleet:ready:{stream}") >= n:
            return
        time.sleep(0.05)
    raise SystemExit(f"fleet never reached {n} ready engine(s)")


def _fleet_reports(procs, sig=None):
    """Terminate (or leave killed) children and collect their exit
    JSON; a SIGKILLed child reports nothing, by design."""
    import signal as _signal
    reports = []
    for p in procs:
        if p.poll() is None and sig is not False:
            try:
                p.send_signal(sig or _signal.SIGTERM)
            except OSError:
                pass
    for p in procs:
        try:
            out, _err = p.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _err = p.communicate()
        for line in (out or "").strip().splitlines()[::-1]:
            try:
                reports.append(json.loads(line))
                break
            except ValueError:
                continue
    return reports


def _fleet_main(args) -> int:
    """`--engines N`: the fleet scaling curve. One MiniRedis carries
    the stream; 1 then N engine PROCESSES (forced-host CPU children,
    one device each) drain the same pre-filled backlog; the chaos leg
    re-runs with a mid-drain SIGKILL of one engine and asserts zero
    accepted-record loss through the claim sweep.

    Host-core honesty (the PR 3 caveat): engine processes burn real
    cores, so an M-core box caps fleet scaling at ~M x regardless of N;
    the JSON reports host_cores and efficiency_vs_host_cores so the
    curve is legible on any rig."""
    import shutil
    import signal as _signal
    import tempfile
    import uuid

    from analytics_zoo_tpu.serving.broker import (RedisBroker,
                                                  encode_ndarray)
    from analytics_zoo_tpu.serving.redis_server import MiniRedisServer

    n = max(2, args.engines)
    total = args.total
    batch = 8
    # same (width, iters) as the child engines build — the prefilled
    # records must match the model's input width
    _fn, _W, sample = _md_model(width=256, iters=1024)
    encoded = encode_ndarray(np.asarray(sample))
    cache_dir = tempfile.mkdtemp(prefix="zoo-fleet-cc-")
    srv = MiniRedisServer().start()
    curve = {}
    reports = []
    chaos = {}
    try:
        def prefill(broker, stream, count):
            t0 = time.perf_counter()
            for _ in range(count):
                broker.xadd(stream, {"uri": uuid.uuid4().hex,
                                     "data": {"t": encoded}})
            return time.perf_counter() - t0

        def drained(broker, stream, count, deadline_s=600.0):
            # HLEN, not HGETALL: polling must not re-serialize the whole
            # result hash over RESP each check — at 20 Hz that steals a
            # measurable slice of the engines' (pinned) cores
            result_key = f"result:{stream}"
            deadline = time.time() + deadline_s
            while time.time() < deadline:
                got = broker.hlen(result_key)
                if got >= count:
                    return got
                time.sleep(0.05)
            return broker.hlen(result_key)

        # -- scaling curve: 1 engine, then N, same backlog ----------------
        host_par = {}
        for k in sorted({1, n}):
            stream = f"serving_stream_fleet{k}"
            broker = RedisBroker(srv.host, srv.port)
            # staggered start: engine 0 warms the shared cache alone
            # (the ~1-cold-compile-per-bucket contract), the rest load
            procs = _fleet_spawn(1, stream, srv.port, cache_dir, 30.0,
                                 batch)
            _fleet_wait_ready(broker, stream, procs, 1)
            if k > 1:
                procs += _fleet_spawn(k - 1, stream, srv.port,
                                      cache_dir, 30.0, batch,
                                      start_idx=1)
                _fleet_wait_ready(broker, stream, procs, k)
            # what the host can give 2 concurrent processes RIGHT
            # BEFORE this leg's drain (engines idle at the gate) — a
            # shared host's capacity swings minute to minute, so one
            # probe at bench start would misstate the leg's ceiling
            host_par[str(k)] = _measure_host_parallelism()
            # the WHOLE backlog lands before the gate opens: the timed
            # window measures fleet drain capacity, not the parent's
            # (contended) sequential xadd rate
            prefill(broker, stream, total)
            broker.hset(f"fleet:gate:{stream}", "go", "1")
            t0 = time.perf_counter()
            got = drained(broker, stream, total)
            dt = time.perf_counter() - t0
            rate = got / dt
            # best-of-2 (the multidevice precedent: single drains swing
            # 2-3x with one-sided scheduler noise on shared rigs): a
            # second backlog through the SAME live fleet; its prefill
            # overlaps consumption, but engines idle-block until it
            # starts so the backlog builds far faster than it drains
            t0 = time.perf_counter()
            prefill(broker, stream, total)
            got2 = drained(broker, stream, 2 * total) - total
            dt2 = time.perf_counter() - t0
            rate = max(rate, got2 / dt2)
            curve[str(k)] = round(rate, 1)
            reports += _fleet_reports(procs)
            broker.close()
        host_parallelism = max(host_par.values())

        # -- chaos leg: SIGKILL one of N mid-drain ------------------------
        stream = "serving_stream_fleet_chaos"
        broker = RedisBroker(srv.host, srv.port)
        claim_idle = 1.0
        procs = _fleet_spawn(1, stream, srv.port, cache_dir, claim_idle,
                             batch)
        _fleet_wait_ready(broker, stream, procs, 1)
        procs += _fleet_spawn(n - 1, stream, srv.port, cache_dir,
                              claim_idle, batch, start_idx=1)
        _fleet_wait_ready(broker, stream, procs, n)
        result_key = f"result:{stream}"
        prefill(broker, stream, total)
        broker.hset(f"fleet:gate:{stream}", "go", "1")
        deadline = time.time() + 600
        while broker.hlen(result_key) < total // 3 \
                and time.time() < deadline:
            time.sleep(0.01)
        # SIGKILL: no drain, no deregistration, unacked records strand
        # in the dead engine's PEL until a peer's claim sweep
        procs[0].send_signal(_signal.SIGKILL)
        t_kill = time.perf_counter()
        got = drained(broker, stream, total)
        t_done = time.perf_counter()
        pending_left = broker.pending_count(
            stream, "serving_group")
        chaos = {
            "engines": n,
            "killed": "engine-0",
            "kill_at_fraction": 1 / 3,
            "claim_min_idle_s": claim_idle,
            "record_loss": total - got,
            "zero_loss": got == total,
            "pending_after_drain": pending_left,
            "engine_kill_redelivery_ms": round(
                (t_done - t_kill) * 1e3, 1),
        }
        reports += _fleet_reports(procs)
        broker.close()
    finally:
        srv.stop()
        shutil.rmtree(cache_dir, ignore_errors=True)

    cores = os.cpu_count() or 1
    base = curve.get("1", 0.0)
    speedup = curve.get(str(n), 0.0) / max(base, 1e-9)
    n_buckets = len([b for b in (1, 2, 4, 8) if b <= batch])
    compiled = sum(r.get("sources", {}).get("compiled", 0)
                   for r in reports)
    survivors_claimed = sum(r.get("claimed_records", 0)
                            for r in reports)
    # the ceiling the curve was ACTUALLY measured under: nominal cores,
    # capped by what the host granted 2 concurrent processes at bench
    # time (shared CI hosts swing between ~1x and ~2x within minutes)
    ceiling = min(float(n), float(cores), host_parallelism)
    out = {
        "metric": "serving_fleet_drain",
        "engines": n,
        "total_records": total,
        "batch_size": batch,
        "host_cores": cores,
        "host_effective_parallelism": host_parallelism,
        "host_effective_parallelism_per_leg": host_par,
        "fleet_drain_rps": curve,
        "fleet_speedup": round(speedup, 2),
        "fleet_efficiency": round(speedup / n, 3),
        # engine processes burn real cores: an M-core box caps the
        # fleet at ~M x no matter how many engines run — and a shared
        # box caps it at whatever slice the host is granting right now;
        # a real pod's chips compute off-host and scale with the
        # engine count
        "efficiency_vs_host_cores": round(
            speedup / max(ceiling, 1e-9), 3),
        "note": ("engine compute is single-threaded by construction "
                 "(narrow matmuls; sched_setaffinity is advisory in "
                 "sandboxed CI), so the curve caps near "
                 f"{ceiling:g}x here: min(engines, {cores} host cores, "
                 f"measured {host_parallelism:g}x effective host "
                 "parallelism at bench time); real engines on separate "
                 "hosts scale with the engine count"),
        "fleet_zero_loss": chaos.get("zero_loss"),
        "engine_kill_redelivery_ms": chaos.get(
            "engine_kill_redelivery_ms"),
        "chaos": chaos,
        # the shared-cache contract: every engine after the first warms
        # from disk, so cold compiles per bucket stay ~1 across the
        # whole fleet (3 staggered cold starts here: one per leg)
        "cold_compiles_per_bucket": round(
            compiled / max(n_buckets, 1), 2),
        "survivor_claimed_records": survivors_claimed,
        "engine_reports": reports,
    }
    print(json.dumps(out))
    return 0


# -- request plane: ingest A/B + partition scaling (ISSUE 16) --------------

def _request_plane_main(args) -> int:
    """`--request-plane`: the million-user request-plane benches.

    Leg 1 — wire-speed ingest A/B against one MiniRedis. The wire
    floor is the measured RESP round trip (minimal HGET: request +
    nil reply). Ingest-only: the same burst enqueued per-record (one
    XADD round trip each — the PR 3 frontend pattern) vs
    `enqueue_batch` (ONE pipelined multi-XADD spanning partition
    streams). End-to-end: the burst through `predict_batch` on a
    `pipelined=False` queue (per-record XADD + per-uri HGET polls) vs
    the batched queue (multi-XADD + HMGET sweeps) vs a
    `StreamingSession`, all against the same in-process
    identity-model engine so the A/B isolates the client wire
    pattern, not model compute. The acceptance figure is frontend
    overhead per record OVER the wire floor, which the batched modes
    must cut >= 2x — the batched overhead deliberately does NOT
    subtract its own (amortized, ~rtt/n) wire share, so the ratio is
    conservative.

    Leg 2 — partition scaling: p in (1, 2, 4) partition streams with
    p engine processes each (fleet children under `--partitions p`),
    the same prefilled backlog per leg routed by the SAME crc32 hash
    the engines' lease tables partition by, drain rps per leg.
    Engine compute is single-threaded by construction (the _md_model
    contract), so the curve caps at min(p, host cores, measured host
    parallelism) — reported per the PR 3/10 honest-ceiling
    convention. A short lease ttl (1 s) keeps the fair-share
    rebalance (engines start owning nothing; the first poll grabs up
    to ceil(p/members)) well inside the first drain; best-of-2 then
    measures the balanced steady state."""
    import shutil
    import tempfile
    import uuid

    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.serving.broker import (RedisBroker,
                                                  encode_ndarray)
    from analytics_zoo_tpu.serving.client import InputQueue
    from analytics_zoo_tpu.serving.inference_model import InferenceModel
    from analytics_zoo_tpu.serving.partitions import stream_for
    from analytics_zoo_tpu.serving.redis_server import MiniRedisServer
    from analytics_zoo_tpu.serving.server import ClusterServing

    init_orca_context(cluster_mode="local")
    srv = MiniRedisServer().start()
    cache_dir = tempfile.mkdtemp(prefix="zoo-rp-cc-")
    out = {"metric": "serving_request_plane"}
    try:
        broker = RedisBroker(srv.host, srv.port)

        # wire floor: p50 of the smallest useful RESP round trip
        rtts = []
        for _ in range(300):
            t0 = time.perf_counter()
            broker.hget("wire:floor", "f")
            rtts.append((time.perf_counter() - t0) * 1e3)
        wire_rtt = _percentile(rtts, 0.5)

        # -- ingest-only A/B: per-record XADD vs one multi-XADD ----------
        n_ingest = 400
        burst = [np.full((4,), float(i), np.float32)
                 for i in range(n_ingest)]
        q_sync = InputQueue(RedisBroker(srv.host, srv.port),
                            stream="rp_ingest_sync", pipelined=False)
        t0 = time.perf_counter()
        for s in burst:
            q_sync.enqueue(t=s)
        sync_ms = (time.perf_counter() - t0) * 1e3 / n_ingest
        # partitions=4 on purpose: the fused path must hold its win
        # while fanning one burst across 4 partition streams
        q_pipe = InputQueue(RedisBroker(srv.host, srv.port),
                            stream="rp_ingest_pipe", partitions=4)
        t0 = time.perf_counter()
        q_pipe.enqueue_batch(burst)
        pipe_ms = (time.perf_counter() - t0) * 1e3 / n_ingest
        # wire-only sub-leg: the SAME prebuilt records straight at the
        # broker (no client encode), per-record XADD vs chunked
        # multi-XADD — isolates the wire pattern itself. The full
        # client legs above still pay numpy encode per record in BOTH
        # modes, so on a loopback rtt their ratio is encode-bound.
        prebuilt = [("rp_ingest_wire",
                     {"uri": f"w{i}", "data": {"t": "x" * 64}})
                    for i in range(n_ingest)]
        t0 = time.perf_counter()
        for st, rec in prebuilt:
            broker.xadd(st, rec)
        wire_sync_ms = (time.perf_counter() - t0) * 1e3 / n_ingest
        t0 = time.perf_counter()
        for i in range(0, n_ingest, 64):
            broker.xadd_many(prebuilt[i:i + 64])
        wire_pipe_ms = (time.perf_counter() - t0) * 1e3 / n_ingest
        # per-record mode pays >= 1 round trip per record BY
        # CONSTRUCTION — overhead is what it spends beyond that floor;
        # the batched mode's amortized wire share is NOT subtracted
        # (conservative against the claim)
        ingest_over_sync = max(sync_ms - wire_rtt, 0.0)
        ingest_over_pipe = max(pipe_ms, 1e-6)
        wire_over_sync = max(wire_sync_ms - wire_rtt, 0.0)
        wire_over_pipe = max(wire_pipe_ms, 1e-6)
        out["ingest"] = {
            "n": n_ingest,
            "per_record_xadd_ms": round(sync_ms, 3),
            "batched_xadd_many_ms": round(pipe_ms, 3),
            "overhead_over_wire_ms": {
                "per_record": round(ingest_over_sync, 3),
                "batched": round(ingest_over_pipe, 3)},
            "overhead_reduction": round(
                ingest_over_sync / ingest_over_pipe, 2),
            "wire_only": {
                "per_record_xadd_ms": round(wire_sync_ms, 3),
                "batched_xadd_many_ms": round(wire_pipe_ms, 3),
                "overhead_reduction": round(
                    wire_over_sync / wire_over_pipe, 2)},
        }

        # -- end-to-end A/B through an identity engine -------------------
        e2e_stream = "rp_e2e"
        ident = InferenceModel().load_fn(lambda p, x: x, params=())
        ident.warmup(np.zeros((4,), np.float32),
                     buckets=[1, 2, 4, 8, 16, 32, 64])
        serving = ClusterServing(
            ident, broker=RedisBroker(srv.host, srv.port),
            stream=e2e_stream, batch_size=64, batch_timeout_ms=2).start()
        n_e2e = 240
        e2e = {}
        for mode in ("per_record", "batched", "streaming"):
            q = InputQueue(RedisBroker(srv.host, srv.port),
                           stream=e2e_stream,
                           pipelined=(mode != "per_record"))
            t0 = time.perf_counter()
            if mode == "streaming":
                with q.stream_session(max_inflight=64) as sess:
                    for i, x in enumerate(burst[:n_e2e]):
                        sess.submit(x, uri=f"rp-stream-{i}")
                    got = sess.drain(timeout_s=300)
                assert len(got) == n_e2e
            else:
                res = q.predict_batch(burst[:n_e2e], timeout_s=600)
                assert len(res) == n_e2e
            dt = time.perf_counter() - t0
            e2e[mode] = {
                "per_record_ms": round(dt * 1e3 / n_e2e, 3),
                "rps": round(n_e2e / dt, 1)}
            q.broker.close()
        serving.stop()
        # the per-record e2e floor is TWO round trips (XADD + >= 1
        # HGET); again the batched modes' amortized wire share is not
        # subtracted, keeping the reduction ratios conservative
        e2e_over_sync = max(
            e2e["per_record"]["per_record_ms"] - 2 * wire_rtt, 0.0)
        out["e2e"] = {
            "n": n_e2e, "modes": e2e,
            "overhead_over_wire_ms": round(e2e_over_sync, 3),
            "overhead_reduction_batched": round(
                e2e_over_sync / max(e2e["batched"]["per_record_ms"],
                                    1e-6), 2),
            "overhead_reduction_streaming": round(
                e2e_over_sync / max(e2e["streaming"]["per_record_ms"],
                                    1e-6), 2),
        }

        # -- partition scaling: p engines over p partition streams -------
        total = args.total
        batch = 8
        _fn, _W, sample = _md_model(width=256, iters=1024)
        encoded = encode_ndarray(np.asarray(sample))
        curve, host_par, reports = {}, {}, []
        for p in (1, 2, 4):
            stream = f"serving_stream_rp{p}"
            pb = RedisBroker(srv.host, srv.port)
            extra = ("--partitions", str(p),
                     "--partition-lease-ttl", "1.0")
            # staggered start: engine 0 warms the shared cache alone
            procs = _fleet_spawn(1, stream, srv.port, cache_dir, 30.0,
                                 batch, extra_args=extra)
            _fleet_wait_ready(pb, stream, procs, 1)
            if p > 1:
                procs += _fleet_spawn(p - 1, stream, srv.port,
                                      cache_dir, 30.0, batch,
                                      start_idx=1, extra_args=extra)
                _fleet_wait_ready(pb, stream, procs, p)
            # this leg's ACTUAL ceiling, probed while engines idle at
            # the gate (shared hosts swing minute to minute)
            host_par[str(p)] = _measure_host_parallelism()

            def prefill(count):
                # routed by the same crc32 the engines partition by,
                # shipped as chunked multi-XADDs (the leg's producers
                # run at wire speed too)
                entries = []
                for _ in range(count):
                    uri = uuid.uuid4().hex
                    entries.append((stream_for(stream, uri, p),
                                    {"uri": uri,
                                     "data": {"t": encoded}}))
                for i in range(0, len(entries), 64):
                    pb.xadd_many(entries[i:i + 64])

            def drained(count, deadline_s=600.0):
                key = f"result:{stream}"
                deadline = time.time() + deadline_s
                while time.time() < deadline:
                    if pb.hlen(key) >= count:
                        break
                    time.sleep(0.05)
                return pb.hlen(key)

            # whole backlog lands before the gate opens (the _fleet_main
            # discipline: measure drain capacity, not the prefill)
            prefill(total)
            pb.hset(f"fleet:gate:{stream}", "go", "1")
            t0 = time.perf_counter()
            got = drained(total)
            rate = got / (time.perf_counter() - t0)
            # best-of-2: round two runs on the rebalanced, warm fleet
            t0 = time.perf_counter()
            prefill(total)
            got2 = drained(2 * total) - total
            rate = max(rate, got2 / (time.perf_counter() - t0))
            curve[str(p)] = round(rate, 1)
            reports += _fleet_reports(procs)
            pb.close()

        cores = os.cpu_count() or 1
        hp = max(host_par.values())
        speedup = curve["4"] / max(curve["1"], 1e-9)
        ceiling = min(4.0, float(cores), hp)
        owned = {r.get("engine_id"): r.get("partitions_owned")
                 for r in reports if "partitions_owned" in r}
        out.update({
            "wire_rtt_ms": round(wire_rtt, 3),
            "partitions_drain_rps": curve,
            "partition_speedup_1_to_4": round(speedup, 2),
            "host_cores": cores,
            "host_effective_parallelism": hp,
            "host_effective_parallelism_per_leg": host_par,
            "efficiency_vs_host_ceiling": round(
                speedup / max(ceiling, 1e-9), 3),
            "note": ("engine compute is single-threaded by "
                     "construction, so COMPUTE caps the curve near "
                     f"{ceiling:g}x here: min(4 partitions, {cores} "
                     f"host cores, measured {hp:g}x effective host "
                     "parallelism at bench time). A speedup ABOVE "
                     "that ceiling means the 1-partition baseline was "
                     "stream-serialization-bound, not compute-bound: "
                     "one engine on one stream idles in its own "
                     "read/writeback round trips, and partitioning "
                     "recovers that idle time by overlapping "
                     "independent streams. Real engines on separate "
                     "hosts scale with the partition count."),
            "partitions_owned_final": owned or None,
            "engine_reports": reports,
        })
    finally:
        srv.stop()
        shutil.rmtree(cache_dir, ignore_errors=True)
    print(json.dumps(out))
    return 0


# -- chaos-rollout: kill the gateway + one engine mid-rollout (ISSUE 14) ---

def _chaos_rollout_main(args) -> int:
    """`--chaos-rollout`: the zero-downtime lifecycle under fire.

    A 3-engine fleet serves published checkpoint version 1 while an
    open-loop feeder keeps records flowing. The trainer-side publishes
    version 2; the rollout controller starts converging the fleet
    engine-by-engine. Mid-rollout — at least one engine converted,
    at least one not — BOTH the gateway (controller killed without
    cleanup: its directive row stays behind, mid-campaign) and one
    unconverted engine (SIGKILL: no drain, unacked records strand in
    its PEL) die. A fresh controller then restarts, digests the mixed
    fleet from heartbeat rows alone, and must converge the survivors
    to EXACTLY version 2 with zero accepted-record loss (strict
    per-record accounting: every uri the feeder successfully XADDed
    has a result) and zero XLA compiles from the same-structure swaps
    (per-engine executable-count deltas)."""
    import shutil
    import signal as _signal
    import tempfile
    import threading
    import uuid

    from analytics_zoo_tpu.learn.checkpoint import (CheckpointManager,
                                                    write_publish_marker)
    from analytics_zoo_tpu.serving.broker import (RedisBroker,
                                                  encode_ndarray)
    from analytics_zoo_tpu.serving.fleet import FleetTracker
    from analytics_zoo_tpu.serving.redis_server import MiniRedisServer
    from analytics_zoo_tpu.serving.rollout import RolloutController

    n = 3
    batch = 8
    stream = "serving_stream_rollout"
    _fn, W, sample = _md_model(width=256, iters=1024)
    encoded = encode_ndarray(np.asarray(sample))
    model_dir = tempfile.mkdtemp(prefix="zoo-rollout-ckpt-")
    cache_dir = tempfile.mkdtemp(prefix="zoo-rollout-cc-")
    mgr = CheckpointManager(model_dir, keep=10)
    # publish in the dtype the model SERVES (numpy>=2 promotes the
    # generator's /sqrt(width) to f64; jax would canonicalize at load,
    # but the artifact should say what it means)
    W = np.asarray(W, np.float32)
    mgr.save(1, W)
    write_publish_marker(mgr.run_dir, 1)
    srv = MiniRedisServer().start()
    broker = RedisBroker(srv.host, srv.port)
    accepted = []
    feeding = threading.Event()
    feeding.set()

    def feeder():
        # open-loop, modest rate: the point is continuous traffic
        # THROUGH the rollout, not saturation — every uri appended to
        # `accepted` was acknowledged by the broker and must come back
        while feeding.is_set():
            uri = uuid.uuid4().hex
            try:
                broker.xadd(stream, {"uri": uri, "data": {"t": encoded}})
            except Exception:  # noqa: BLE001 — not accepted, not owed
                time.sleep(0.05)
                continue
            accepted.append(uri)
            time.sleep(0.01)

    procs = []
    out = {"metric": "serving_rollout_chaos", "engines": n}
    reports = []
    try:
        procs = _fleet_spawn(1, stream, srv.port, cache_dir, 1.0, batch,
                             extra_args=("--rollout-dir", model_dir))
        _fleet_wait_ready(broker, stream, procs, 1)
        procs += _fleet_spawn(n - 1, stream, srv.port, cache_dir, 1.0,
                              batch, start_idx=1,
                              extra_args=("--rollout-dir", model_dir))
        _fleet_wait_ready(broker, stream, procs, n)
        broker.hset(f"fleet:gate:{stream}", "go", "1")
        feed_thread = threading.Thread(target=feeder, daemon=True)
        feed_thread.start()
        tracker = FleetTracker(broker.clone(), stream, ttl_s=2.0,
                               poll_min_interval_s=0.05)
        controller = RolloutController(
            broker.clone(), stream, model_dir, tracker,
            poll_interval_s=0.2, engine_timeout_s=120.0).start()
        # trainer publishes version 2 (same structure: 1.01x weights)
        mgr.save(2, W * 1.01)
        write_publish_marker(mgr.run_dir, 2)
        t_publish = time.perf_counter()
        # mid-rollout point: >=1 engine on v2, >=1 still on v1
        deadline = time.time() + 300
        victim = None
        while time.time() < deadline:
            versions = tracker.versions() or {}
            on_new = [e for e, v in versions.items() if v == 2]
            on_old = [e for e, v in versions.items() if v != 2]
            if on_new and on_old:
                victim = sorted(on_old)[0]
                break
            time.sleep(0.02)
        if victim is None:
            raise SystemExit("rollout never reached a mid-point "
                             "(no mixed-version window observed)")
        # kill the GATEWAY (no clean stop: the thread is cut loose and
        # its directive row stays behind) and one UNCONVERTED engine
        controller._stop.set()
        idx = int(victim.split("-")[-1])
        procs[idx].send_signal(_signal.SIGKILL)
        t_kill = time.perf_counter()
        # gateway restarts: a FRESH controller must digest the mess
        tracker2 = FleetTracker(broker.clone(), stream, ttl_s=2.0,
                                poll_min_interval_s=0.05)
        controller2 = RolloutController(
            broker.clone(), stream, model_dir, tracker2,
            poll_interval_s=0.2, engine_timeout_s=120.0).start()
        # traffic keeps flowing a while longer, then stops
        time.sleep(2.0)
        feeding.clear()
        feed_thread.join(timeout=10)
        total = len(accepted)
        # convergence: every ALIVE engine on version 2, exactly
        deadline = time.time() + 300
        converged_at = None
        final_versions = {}
        while time.time() < deadline:
            versions = tracker2.versions() or {}
            vals = set(versions.values())
            if len(versions) == n - 1 and vals == {2}:
                converged_at = time.perf_counter()
                final_versions = dict(versions)
                break
            time.sleep(0.05)
        # drain: every accepted record answered (claim sweep owns the
        # dead engine's strays)
        result_key = f"result:{stream}"
        deadline = time.time() + 300
        while broker.hlen(result_key) < total \
                and time.time() < deadline:
            time.sleep(0.05)
        got = broker.hlen(result_key)
        res = broker.hgetall(result_key)
        missing = [u for u in accepted if u not in res]
        controller2.stop()
        status = controller2.status()
        reports = _fleet_reports([p for p in procs
                                  if p.poll() is None])
        # compiles attributable to the SWAPS themselves (the agent
        # measures across its own swap+canary window; the whole-run
        # executables_delta additionally catches unrelated bucket
        # traffic, e.g. a claim sweep forming an unwarmed batch size)
        swap_compiles = sum(
            (r.get("swap") or {}).get("swap_executables_delta") or 0
            for r in reports)
        out.update({
            "total_accepted": total,
            "records_lost": len(missing),
            "zero_loss": not missing,
            "results_written": got,
            "killed_engine": victim,
            "converged": converged_at is not None,
            "convergence_s": round(converged_at - t_publish, 2)
            if converged_at else None,
            "post_kill_convergence_s": round(converged_at - t_kill, 2)
            if converged_at else None,
            "final_versions": sorted(set(final_versions.values())),
            "swap_compiles": swap_compiles,
            "controller_state": status.get("state"),
            "engine_reports": reports,
        })
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        srv.stop()
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(model_dir, ignore_errors=True)
    print(json.dumps(out))
    return 0


# -- elastic: diurnal + spike replay, static vs autoscaled fleet -----------
# (ISSUE 11)

def _generative_main(args) -> int:
    """Continuous batching A/B (ISSUE 18): the decode engine vs a
    pad-to-max-restart baseline on the SAME executables and the SAME
    seeded Poisson arrival process with a short-skewed output-length
    mix. The baseline is the naive generative server: seat up to
    `slots` waiting prompts, decode the whole batch to its LONGEST
    max_new, only then admit the next batch — every early finisher
    holds its slot idle until the batch's straggler is done, and every
    arrival mid-batch waits for the restart. Reports tokens/sec, TTFT
    and inter-token-latency p50/p99 for both legs, the slot-utilization
    ratio (active-slot-steps over pool-width-steps), and the fresh-XLA-
    compile count on the continuous leg's request path (must be 0: the
    compile funnel is spied after warmup)."""
    import analytics_zoo_tpu.compile_cache.serialization as ccser
    from analytics_zoo_tpu.models.generative import TinyDecoder
    from analytics_zoo_tpu.serving.broker import MemoryBroker
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.decode import DecodeServing
    from analytics_zoo_tpu.serving.inference_model import (InferenceModel,
                                                           _next_bucket)

    SLOTS, MAX_KV = 8, 128
    KV_BUCKETS = [16, 32, 64, 128]
    PROMPT_BUCKETS = [8, 16]
    MAX_NEW_CAP = 48
    n = int(os.environ.get("BENCH_GEN_REQUESTS", 64))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 64,
                            size=int(rng.integers(2, 15))).astype(np.int32)
               for _ in range(n)]
    # bimodal output mix — mostly short (geometric, mean ~5) with every
    # 8th request a full-length straggler (the chat + summarization mix
    # of the Orca/vLLM evals): the regime where pad-to-max wastes the
    # most slot-steps, because each straggler pins its whole batch
    max_new = np.minimum(1 + rng.geometric(0.25, n),
                         MAX_NEW_CAP).astype(int)
    max_new[::8] = MAX_NEW_CAP
    # arrival rate sized to SATURATE the slot pool (the regime the A/B
    # is about: under light load both disciplines idle and tie)
    arrivals = np.cumsum(rng.exponential(0.002, n))

    # big enough that step COMPUTE dominates the engine's per-step
    # bookkeeping (broker intake + token-row writes); a 2-layer toy
    # makes the A/B measure engine overhead instead of scheduling
    dec = TinyDecoder(vocab=128, n_layers=4, n_heads=4, head_dim=16,
                      max_len=MAX_KV)
    im = InferenceModel(placement="replicated", num_replicas=1)
    im.load_generative(dec.prefill_fn, dec.step_fn, dec.init_params(0))
    t0 = time.perf_counter()
    im.warmup_generative(dec.init_kv, slots=SLOTS, max_kv_len=MAX_KV,
                         prompt_buckets=PROMPT_BUCKETS,
                         kv_buckets=KV_BUCKETS)
    warmup_s = time.perf_counter() - t0

    # ---- continuous leg: the decode engine over the broker rails ----
    compile_calls = []
    orig_compile = ccser.compile_lowered

    def spy(lowered):
        compile_calls.append(1)
        return orig_compile(lowered)

    ccser.compile_lowered = spy
    broker = MemoryBroker()
    srv = DecodeServing(im, dec.init_kv, broker=broker, slots=SLOTS,
                        max_kv_len=MAX_KV, kv_buckets=KV_BUCKETS,
                        prompt_buckets=PROMPT_BUCKETS,
                        max_new_default=MAX_NEW_CAP).start()
    inq = InputQueue(broker)
    outq = OutputQueue(broker)
    t0 = time.perf_counter()
    uris = []
    for i in range(n):
        dt = t0 + arrivals[i] - time.perf_counter()
        if dt > 0:
            time.sleep(dt)
        uris.append(inq.enqueue(t=prompts[i], max_new=int(max_new[i]),
                                stream=1))
    while srv.stats["finished"] < n:          # serving wall, not
        time.sleep(0.001)                     # post-hoc drain time
        if time.perf_counter() - t0 > 300:
            raise SystemExit("continuous leg stalled")
    cont_wall = time.perf_counter() - t0
    cont_ttft, cont_itl = [], []
    for u in uris:                            # post-hoc stream drain
        ms = [e["ms"] for e in outq.stream_tokens(u, timeout_s=30)
              if not e.get("done")]
        cont_ttft.append(ms[0])
        cont_itl += list(np.diff(ms))
    srv.stop()
    ccser.compile_lowered = orig_compile
    cont = {
        "tokens": srv.stats["tokens"],
        "wall_s": round(cont_wall, 4),
        "tokens_per_s": round(srv.stats["tokens"] / cont_wall, 1),
        "ttft_ms": {"p50": round(_percentile(cont_ttft, 0.5), 3),
                    "p99": round(_percentile(cont_ttft, 0.99), 3)},
        "itl_ms": {"p50": round(_percentile(cont_itl, 0.5), 3),
                   "p99": round(_percentile(cont_itl, 0.99), 3)},
        "slot_utilization": round(srv.utilization(), 4),
        "steps": srv.stats["steps"],
    }

    # ---- baseline leg: pad-to-max-restart on the same executables ----
    kv = dec.init_kv(SLOTS, MAX_KV)
    t0 = time.perf_counter()
    base_ttft, base_itl = [], []
    toks, pos, gen, last = {}, {}, {}, {}
    slot_active = slot_total = steps = tokens = 0
    arrived = finished = 0
    from collections import deque
    waiting: deque = deque()
    while finished < n:
        now = time.perf_counter() - t0
        while arrived < n and arrivals[arrived] <= now:
            waiting.append(arrived)
            arrived += 1
        if not waiting:
            time.sleep(max(0.0, t0 + arrivals[arrived]
                           - time.perf_counter()))
            continue
        batch = [waiting.popleft()
                 for _ in range(min(SLOTS, len(waiting)))]
        for s, idx in enumerate(batch):
            p = prompts[idx]
            pb = _next_bucket(len(p), PROMPT_BUCKETS)
            padded = np.zeros(pb, np.int32)
            padded[:len(p)] = p
            kv, logits = im.generative_prefill(kv, padded, len(p), s)
            toks[idx] = int(np.asarray(logits).argmax())
            tnow = time.perf_counter() - t0
            base_ttft.append((tnow - arrivals[idx]) * 1e3)
            last[idx], gen[idx], pos[idx] = tnow, 1, len(p)
            tokens += 1
        # pad-to-max: the batch decodes until its LONGEST request is
        # done; early finishers keep burning their slot
        for _ in range(max(max_new[idx] for idx in batch) - 1):
            toks_arr = np.zeros(SLOTS, np.int32)
            pos_arr = np.zeros(SLOTS, np.int32)
            for s, idx in enumerate(batch):
                toks_arr[s] = toks[idx]
                pos_arr[s] = pos[idx]
            bucket = _next_bucket(
                max(pos[idx] + 1 for idx in batch), KV_BUCKETS)
            kv, logits = im.generative_step(kv, toks_arr, pos_arr, bucket)
            nxt = np.asarray(logits).argmax(axis=-1)
            tnow = time.perf_counter() - t0
            steps += 1
            slot_total += SLOTS
            slot_active += sum(1 for idx in batch
                               if gen[idx] < max_new[idx])
            for s, idx in enumerate(batch):
                pos[idx] += 1
                if gen[idx] < max_new[idx]:
                    toks[idx] = int(nxt[s])
                    base_itl.append((tnow - last[idx]) * 1e3)
                    last[idx] = tnow
                    gen[idx] += 1
                    tokens += 1
        finished += len(batch)
    base_wall = time.perf_counter() - t0
    base_util = slot_active / slot_total if slot_total else 0.0
    base = {
        "tokens": tokens,
        "wall_s": round(base_wall, 4),
        "tokens_per_s": round(tokens / base_wall, 1),
        "ttft_ms": {"p50": round(_percentile(base_ttft, 0.5), 3),
                    "p99": round(_percentile(base_ttft, 0.99), 3)},
        "itl_ms": {"p50": round(_percentile(base_itl, 0.5), 3),
                   "p99": round(_percentile(base_itl, 0.99), 3)},
        "slot_utilization": round(base_util, 4),
        "steps": steps,
    }

    out = {
        "mode": "generative",
        "backend": jax.default_backend(),
        "n_requests": n, "slots": SLOTS, "max_kv_len": MAX_KV,
        "kv_buckets": KV_BUCKETS, "prompt_buckets": PROMPT_BUCKETS,
        "output_len_mix": {"mean": round(float(max_new.mean()), 2),
                           "max": int(max_new.max()),
                           "cap": MAX_NEW_CAP},
        "warmup_s": round(warmup_s, 3),
        "cold_compiles": len(compile_calls),
        "continuous": cont,
        "baseline_pad_to_max": base,
        "utilization_ratio": round(
            cont["slot_utilization"] / base_util, 2) if base_util else None,
        "tokens_per_s_speedup": round(
            cont["tokens_per_s"] / base["tokens_per_s"], 2),
        "ttft_p99_ratio": round(
            base["ttft_ms"]["p99"] / cont["ttft_ms"]["p99"], 2),
    }
    assert out["cold_compiles"] == 0, \
        "XLA compiled on the decode request path after warmup"
    print(json.dumps(out))
    return 0


def _generative_paged_main(args) -> int:
    """Paged-KV A/B (ISSUE 19) on a prefix-heavy Poisson mix, three
    legs over the SAME model and warmed executables:

    1. capacity — a burst of short shared-prefix prompts through the
       contiguous engine (4 stripes of max_kv_len) and the paged engine
       holding the SAME pool bytes (4*table_len blocks + scratch) but
       4x the lanes: peak concurrent sequences, target >= 2x.
    2. prefix TTFT — cold prompts with distinct 96-token prefixes vs
       prompts re-using them (the cache adopts 6 of 7 chunks copy-
       free): TTFT p50 ratio, target >= 3x.
    3. ITL under a long-prompt join — 4 live streams, then a 104-token
       prompt joins, chunked prefill ON (16-token chunks interleave
       with decode) vs OFF (one monolithic prefill): live streams'
       ITL p99 during the join vs steady state, ON target <= 2x.

    Asserts in-process: zero accepted-record loss (every uri's final
    lands with exactly max_new tokens) and 0 request-path compiles
    across ALL legs (the serialization.compile_lowered funnel is spied
    from the moment warmup ends)."""
    import analytics_zoo_tpu.compile_cache.serialization as ccser
    from analytics_zoo_tpu.models.generative import TinyDecoder
    from analytics_zoo_tpu.serving.broker import MemoryBroker
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.decode import DecodeServing
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    MAX_KV, BL = 128, 16
    KV_BUCKETS = [32, 64, 128]
    TABLE_LEN = MAX_KV // BL
    dec = TinyDecoder(vocab=128, n_layers=4, n_heads=4, head_dim=16,
                      max_len=MAX_KV)
    rng = np.random.default_rng(11)
    warmup_s = 0.0

    def new_im(paged=True):
        im = InferenceModel(placement="replicated", num_replicas=1)
        im.load_generative(
            dec.prefill_fn, dec.step_fn, dec.init_params(0),
            paged_prefill_fn=dec.paged_prefill_fn if paged else None,
            paged_step_fn=dec.paged_step_fn if paged else None)
        return im

    def paged_engine(broker, lanes, kv_blocks, prompt_buckets,
                     prefill_chunk, prefix_cache=True):
        nonlocal warmup_s
        im = new_im()
        chunk_buckets = [b for b in prompt_buckets
                         if prefill_chunk is None or b <= prefill_chunk] \
            or [prompt_buckets[0]]
        t0 = time.perf_counter()
        im.warmup_generative_paged(
            dec.init_kv_blocks, num_blocks=kv_blocks, block_len=BL,
            lanes=lanes, table_len=TABLE_LEN,
            chunk_buckets=chunk_buckets, kv_buckets=KV_BUCKETS)
        warmup_s += time.perf_counter() - t0
        return DecodeServing(
            im, dec.init_kv, broker=broker, slots=lanes,
            max_kv_len=MAX_KV, kv_buckets=KV_BUCKETS,
            prompt_buckets=prompt_buckets, max_new_default=8,
            paged=True, init_kv_blocks=dec.init_kv_blocks,
            block_len=BL, kv_blocks=kv_blocks,
            prefill_chunk=prefill_chunk, prefix_cache=prefix_cache), im

    def drain(srv, outq, uris, expect, wall_cap=300.0):
        t0 = time.perf_counter()
        peak = 0
        while srv.stats["finished"] < expect:
            peak = max(peak, len(srv._active))
            time.sleep(0.001)
            if time.perf_counter() - t0 > wall_cap:
                raise SystemExit("paged leg stalled")
        finals = outq.query_many(uris, deadline=time.monotonic() + 30)
        assert len(finals) == len(uris), \
            f"record loss: {len(uris) - len(finals)} finals missing"
        return peak, finals

    compile_calls = []
    orig_compile = ccser.compile_lowered

    def spy(lowered):
        compile_calls.append(1)
        return orig_compile(lowered)

    # ---- leg 1: capacity at fixed pool bytes --------------------------
    # 24 short prompts (16-token shared prefix + 4-token tail, 8 new),
    # all enqueued at once. Contiguous: 4 stripes of 128 = the whole
    # pool seats 4. Paged: the SAME 512 KV rows = 32 blocks seat every
    # 2-block sequence the 16 lanes can carry.
    CAP_N, STRIPES = 24, 4
    cap_prefix = rng.integers(1, 128, BL).astype(np.int32)
    cap_prompts = [np.concatenate(
        [cap_prefix, rng.integers(1, 128, 4).astype(np.int32)])
        for _ in range(CAP_N)]

    im_c = new_im(paged=False)
    t0 = time.perf_counter()
    im_c.warmup_generative(dec.init_kv, slots=STRIPES, max_kv_len=MAX_KV,
                           prompt_buckets=[32], kv_buckets=KV_BUCKETS)
    warmup_s += time.perf_counter() - t0
    ccser.compile_lowered = spy
    try:
        broker = MemoryBroker()
        srv = DecodeServing(im_c, dec.init_kv, broker=broker,
                            slots=STRIPES, max_kv_len=MAX_KV,
                            kv_buckets=KV_BUCKETS, prompt_buckets=[32],
                            max_new_default=8).start()
        inq, outq = InputQueue(broker), OutputQueue(broker)
        t0 = time.perf_counter()
        uris = [inq.enqueue(t=p, max_new=8) for p in cap_prompts]
        peak_contig, finals = drain(srv, outq, uris, CAP_N)
        contig_wall = time.perf_counter() - t0
        srv.stop()

        broker = MemoryBroker()
        srv, _ = paged_engine(broker, lanes=4 * STRIPES,
                              kv_blocks=STRIPES * TABLE_LEN + 1,
                              prompt_buckets=[16, 32], prefill_chunk=16)
        srv.start()
        inq, outq = InputQueue(broker), OutputQueue(broker)
        t0 = time.perf_counter()
        uris = [inq.enqueue(t=p, max_new=8) for p in cap_prompts]
        peak_paged, finals = drain(srv, outq, uris, CAP_N)
        paged_wall = time.perf_counter() - t0
        cap_hits = srv.stats["prefix_hit_tokens"]
        srv.stop()
        capacity = {
            "pool_kv_rows": STRIPES * MAX_KV,
            "requests": CAP_N,
            "contiguous": {"slots": STRIPES, "peak_concurrent":
                           peak_contig, "wall_s": round(contig_wall, 4)},
            "paged": {"lanes": 4 * STRIPES,
                      "kv_blocks": STRIPES * TABLE_LEN + 1,
                      "peak_concurrent": peak_paged,
                      "wall_s": round(paged_wall, 4),
                      "prefix_hit_tokens": cap_hits},
            "concurrency_ratio": round(peak_paged / peak_contig, 2),
        }

        # ---- leg 2: prefix-hit vs cold TTFT ---------------------------
        # 8 distinct 96-token prefixes, sequentially (each publishes its
        # blocks before the next arrives), then 8 re-users: a hit adopts
        # (104-1)//16 = 6 blocks and prefills ONE 16-token chunk instead
        # of seven.
        PFX_N, PFX_LEN = 8, 6 * BL
        broker = MemoryBroker()
        srv, _ = paged_engine(broker, lanes=8,
                              kv_blocks=8 * TABLE_LEN + 1,
                              prompt_buckets=[16], prefill_chunk=16)
        srv.start()
        inq, outq = InputQueue(broker), OutputQueue(broker)
        prefixes = [rng.integers(1, 128, PFX_LEN).astype(np.int32)
                    for _ in range(PFX_N)]
        ttft = {"cold": [], "hit": []}
        done = 0
        for phase in ("cold", "hit"):
            for pfx in prefixes:
                tail = rng.integers(1, 128, 8).astype(np.int32)
                u = inq.enqueue(t=np.concatenate([pfx, tail]),
                                max_new=4, stream=1)
                while srv.stats["finished"] < done + 1:
                    time.sleep(0.001)
                done += 1
                ms = [e["ms"] for e in
                      outq.stream_tokens(u, timeout_s=30)
                      if not e.get("done")]
                ttft[phase].append(ms[0])
        hit_tokens = srv.stats["prefix_hit_tokens"]
        srv.stop()
        assert hit_tokens >= PFX_N * PFX_LEN, \
            "prefix cache missed re-used prefixes"
        prefix_leg = {
            "prefix_len": PFX_LEN, "prompt_len": PFX_LEN + 8,
            "requests_per_phase": PFX_N,
            "cold_ttft_ms": {
                "p50": round(_percentile(ttft["cold"], 0.5), 3),
                "p99": round(_percentile(ttft["cold"], 0.99), 3)},
            "hit_ttft_ms": {
                "p50": round(_percentile(ttft["hit"], 0.5), 3),
                "p99": round(_percentile(ttft["hit"], 0.99), 3)},
            "prefix_hit_tokens": hit_tokens,
            "ttft_p50_ratio": round(
                _percentile(ttft["cold"], 0.5)
                / _percentile(ttft["hit"], 0.5), 2),
        }

        # ---- leg 3: ITL p99 while a near-max prompt joins -------------
        # 4 live streams decode; a 104-token prompt joins mid-flight.
        # ON: 16-token chunks interleave with decode steps. OFF: one
        # 112-bucket monolithic prefill stalls every stream for its
        # full duration.
        itl_leg = {}
        for chunk in (16, None):
            broker = MemoryBroker()
            srv, _ = paged_engine(broker, lanes=8,
                                  kv_blocks=8 * TABLE_LEN + 1,
                                  prompt_buckets=[16, 112],
                                  prefill_chunk=chunk,
                                  prefix_cache=False)
            srv.start()
            inq, outq = InputQueue(broker), OutputQueue(broker)
            enq_wall = {}
            uris = []
            for _ in range(5):
                p = rng.integers(1, 128, 12).astype(np.int32)
                u = inq.enqueue(t=p, max_new=110, stream=1)
                enq_wall[u] = time.perf_counter()
                uris.append(u)
            while srv.stats["prefills"] < 5:
                time.sleep(0.001)
            # FOUR join events pooled: a single joiner's window holds a
            # handful of ITL samples, so its p99 is the sample max —
            # noise-dominated on a 1-core host
            JOINS = 4
            joiner_uris = []
            for j in range(JOINS):
                time.sleep(0.02)              # steady-state gap
                joiner = rng.integers(1, 128, 104).astype(np.int32)
                ju = inq.enqueue(t=joiner, max_new=4, stream=1)
                enq_wall[ju] = time.perf_counter()
                joiner_uris.append(ju)
                while srv.stats["finished"] < j + 1:
                    time.sleep(0.001)
            peak, finals = drain(srv, outq, uris + joiner_uris,
                                 5 + JOINS)
            windows = []
            for ju in joiner_uris:
                j_ms = [e["ms"] for e in
                        outq.stream_tokens(ju, timeout_s=30)
                        if not e.get("done")]
                windows.append((enq_wall[ju],
                                enq_wall[ju] + j_ms[0] / 1e3))
            steady, during = [], []
            for u in uris:
                ms = [e["ms"] for e in
                      outq.stream_tokens(u, timeout_s=30)
                      if not e.get("done")]
                walls = [enq_wall[u] + m / 1e3 for m in ms]
                for prev, cur in zip(walls, walls[1:]):
                    (during if any(w0 <= cur <= w1 + 0.005
                                   for w0, w1 in windows)
                     else steady).append((cur - prev) * 1e3)
            srv.stop()
            itl_leg["chunked_on" if chunk else "chunked_off"] = {
                "join_events": JOINS,
                "prefill_chunks": srv.stats["prefill_chunks"],
                "steady_itl_ms_p99": round(_percentile(steady, 0.99), 3),
                "join_itl_ms_p99": round(_percentile(during, 0.99), 3),
                "join_over_steady_p99": round(
                    _percentile(during, 0.99)
                    / _percentile(steady, 0.99), 2),
                "join_window_ms": round(sum(
                    (w1 - w0) for w0, w1 in windows) * 1e3 / JOINS, 3),
            }
    finally:
        ccser.compile_lowered = orig_compile

    out = {
        "mode": "generative_paged",
        "backend": jax.default_backend(),
        "max_kv_len": MAX_KV, "block_len": BL,
        "kv_buckets": KV_BUCKETS,
        "warmup_s": round(warmup_s, 3),
        "cold_compiles": len(compile_calls),
        "capacity_fixed_pool_bytes": capacity,
        "prefix_cache_ttft": prefix_leg,
        "long_prompt_join_itl": itl_leg,
    }
    assert out["cold_compiles"] == 0, \
        "XLA compiled on the paged decode request path after warmup"
    print(json.dumps(out))
    return 0


def _generative_chaos_child(args) -> int:
    """One paged decode engine in its own process for the generative
    chaos leg: warm through the SHARED compile cache, park at the
    fleet start gate, then serve with the claim sweep armed. SIGKILL
    is the exercise: no cleanup runs, the PEL keeps this engine's
    unacked generative records, and the surviving peer's sweep adopts
    and RESUMES them from their durable token rows. The compile funnel
    is spied AFTER warmup, so the exit report's `cold_compiles` counts
    request-path compiles only — resume must not add any."""
    import signal

    import analytics_zoo_tpu.compile_cache.serialization as ccser
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.compile_cache import CompileCache
    from analytics_zoo_tpu.models.generative import TinyDecoder
    from analytics_zoo_tpu.serving.broker import connect_broker
    from analytics_zoo_tpu.serving.decode import DecodeServing
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    init_orca_context(cluster_mode="local")
    dec = TinyDecoder(vocab=64, n_layers=4, n_heads=4, head_dim=16,
                      max_len=64)
    cache = CompileCache(args.compile_cache_dir) \
        if args.compile_cache_dir else None
    im = InferenceModel(placement="replicated", num_replicas=1,
                        compile_cache=cache)
    im.load_generative(dec.prefill_fn, dec.step_fn, dec.init_params(0),
                       paged_prefill_fn=dec.paged_prefill_fn,
                       paged_step_fn=dec.paged_step_fn)
    im.warmup_generative_paged(
        dec.init_kv_blocks, num_blocks=33, block_len=8, lanes=4,
        table_len=8, chunk_buckets=[8, 16], kv_buckets=[16, 32, 64])

    compiles = []
    orig_compile = ccser.compile_lowered

    def spy(lowered):
        compiles.append(1)
        return orig_compile(lowered)

    ccser.compile_lowered = spy
    if args.step_stall_ms > 0:
        # stretch every decode step (the parent sizes this so the
        # SIGKILL reliably lands MID-generation instead of racing a
        # sub-second drain on fast hosts) — the ISSUE-20 stall mode on
        # the decode.step injection point, permanently armed
        from analytics_zoo_tpu.common import faults
        faults.inject("decode.step",
                      faults.Fault(mode="stall",
                                   delay_s=args.step_stall_ms / 1e3))
    broker = connect_broker(args.broker_url)
    srv = DecodeServing(
        im, dec.init_kv, broker=broker, stream=args.stream,
        slots=4, max_kv_len=64, kv_buckets=[16, 32, 64],
        prompt_buckets=[8, 16], max_new_default=40,
        # the queue bound must exceed the whole burst: every prompt
        # must be ACCEPTED (the leg asserts bitwise completion for
        # each), so overload shedding must never fire. The burst still
        # splits between the engines — records land over ~100ms while
        # both loops read every ~step, so neither can hoover the
        # stream in one XREADGROUP
        max_waiting=64,
        engine_id=args.engine_id, paged=True,
        init_kv_blocks=dec.init_kv_blocks, block_len=8, kv_blocks=33,
        claim_min_idle_s=args.claim_min_idle,
        claim_interval_s=max(args.claim_min_idle / 4.0, 0.05),
        heartbeat_interval_s=0.25)
    broker.hset(f"fleet:ready:{args.stream}", args.engine_id, "1")
    gate_deadline = time.time() + 600
    while not broker.hget(f"fleet:gate:{args.stream}", "go"):
        if time.time() > gate_deadline:
            raise SystemExit("chaos start gate never opened")
        time.sleep(0.02)
    srv.start()
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    while not stop:
        time.sleep(0.05)
    srv.stop()
    print(json.dumps({"engine_id": args.engine_id,
                      "cold_compiles": len(compiles),
                      "stats": srv.stats}))
    return 0


def _generative_chaos_main(args) -> int:
    """`--generative --chaos` (ISSUE 20): crash-safe generative
    serving. Two paged decode engines in their own processes drain a
    seeded Poisson prompt mix over one MiniRedis; one engine is
    SIGKILLed mid-generation. The survivor's claim sweep must adopt
    the dead engine's records and resume each from its durable token
    rows, so every completion lands bitwise equal to an uninterrupted
    single-engine oracle on the SAME executables (greedy decode is
    deterministic — zero token loss, zero divergence), a client that
    reconnects mid-stream sees every token index exactly once, and the
    survivor's request path stays at 0 fresh XLA compiles. A second,
    in-process pair then runs the SAME pressure mix with preemption on
    vs off: preemption must complete every sequence under KV-pool
    exhaustion where the disabled leg degrades to answered blocks-full
    truncations — and neither leg may deadlock."""
    import shutil
    import tempfile

    from analytics_zoo_tpu.compile_cache import CompileCache
    from analytics_zoo_tpu.models.generative import TinyDecoder
    from analytics_zoo_tpu.serving.broker import MemoryBroker, RedisBroker
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.decode import GROUP, DecodeServing
    from analytics_zoo_tpu.serving.inference_model import InferenceModel
    from analytics_zoo_tpu.serving.redis_server import MiniRedisServer

    LANES, MAX_KV, BL, BLOCKS = 4, 64, 8, 33
    KV_BUCKETS, PROMPT_BUCKETS = [16, 32, 64], [8, 16]
    n = int(os.environ.get("BENCH_GEN_CHAOS_REQUESTS", 48))
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, 64,
                            size=int(rng.integers(3, 9))).astype(np.int32)
               for _ in range(n)]
    max_new = np.minimum(4 + rng.geometric(0.06, n), 40).astype(int)
    # arrival rate sized to SATURATE both engines (the _generative_main
    # regime): the kill must land while a deep backlog keeps 8 lanes
    # busy, or the dead engine has nothing in flight worth recovering
    arrivals = np.cumsum(rng.exponential(0.002, n))

    cache_dir = args.compile_cache_dir or tempfile.mkdtemp(
        prefix="genchaos-cache-")
    own_cache = args.compile_cache_dir is None
    dec = TinyDecoder(vocab=64, n_layers=4, n_heads=4, head_dim=16,
                      max_len=MAX_KV)
    im = InferenceModel(placement="replicated", num_replicas=1,
                        compile_cache=CompileCache(cache_dir))
    im.load_generative(dec.prefill_fn, dec.step_fn, dec.init_params(0),
                       paged_prefill_fn=dec.paged_prefill_fn,
                       paged_step_fn=dec.paged_step_fn)
    t0 = time.perf_counter()
    # the parent warms FIRST: children then load every executable from
    # the shared cache dir instead of compiling 2x in parallel
    im.warmup_generative_paged(
        dec.init_kv_blocks, num_blocks=BLOCKS, block_len=BL, lanes=LANES,
        table_len=MAX_KV // BL, chunk_buckets=PROMPT_BUCKETS,
        kv_buckets=KV_BUCKETS)
    warmup_s = time.perf_counter() - t0

    def engine(broker, **kw):
        return DecodeServing(
            im, dec.init_kv, broker=broker, slots=LANES,
            max_kv_len=MAX_KV, kv_buckets=KV_BUCKETS,
            prompt_buckets=PROMPT_BUCKETS, max_new_default=40,
            paged=True, init_kv_blocks=dec.init_kv_blocks,
            block_len=BL, kv_blocks=BLOCKS, **kw)

    # ---- uninterrupted oracle: one engine, same executables --------------
    ref_broker = MemoryBroker()
    ref = engine(ref_broker).start()
    rin, rout = InputQueue(ref_broker), OutputQueue(ref_broker)
    ref_uris = [rin.enqueue(t=p, max_new=int(m), stream=1)
                for p, m in zip(prompts, max_new)]
    got = {}
    deadline = time.time() + 240
    while len(got) < n:
        if time.time() > deadline:
            raise SystemExit(f"oracle leg stalled: {len(got)}/{n}")
        got.update(rout.query_many([u for u in ref_uris if u not in got],
                                   delete=True))
        time.sleep(0.005)
    ref.stop()
    expected = [list(np.asarray(got[u]).reshape(-1)) for u in ref_uris]
    total_tokens = sum(len(e) for e in expected)

    # ---- the chaos fleet: 2 engines, kill one mid-generation -------------
    redis_srv = MiniRedisServer().start()
    stream = args.stream
    broker = RedisBroker("127.0.0.1", redis_srv.port)
    result_key = f"result:{stream}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--generative-child",
         "--broker-url", f"redis://127.0.0.1:{redis_srv.port}",
         "--stream", stream, "--engine-id", f"engine-{i}",
         "--compile-cache-dir", cache_dir,
         "--claim-min-idle", "0.75", "--step-stall-ms", "8"],
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(2)]
    _fleet_wait_ready(broker, stream, procs, 2)
    broker.hset(f"fleet:gate:{stream}", "go", "1")

    def finals_landed(uris):
        return sum(1 for r in broker.hmget(result_key, uris)
                   if r is not None)

    inq, outq = InputQueue(broker), OutputQueue(broker)
    t_start = time.perf_counter()
    uris = []
    for i in range(n):
        dt = t_start + arrivals[i] - time.perf_counter()
        if dt > 0:
            time.sleep(dt)
        uris.append(inq.enqueue(t=prompts[i], max_new=int(max_new[i]),
                                stream=1))

    kill_at = max(2, n // 12)
    deadline = time.time() + 240
    while finals_landed(uris) < kill_at \
            or broker.pending_count(stream, GROUP) < 6:
        if time.time() > deadline:
            raise SystemExit("chaos fleet never reached the kill point")
        if finals_landed(uris) >= n - 2:
            raise SystemExit("load finished before the kill point: "
                             "raise BENCH_GEN_CHAOS_REQUESTS")
        time.sleep(0.002)
    # kill the engine that is ACTIVELY generating (heartbeat token
    # counter grew over one beat window) — killing an idle peer would
    # leave the survivor nothing to recover
    from analytics_zoo_tpu.serving.fleet import engines_key

    def beat_tokens():
        return {eid: json.loads(v).get("tokens", 0)
                for eid, v in broker.hgetall(engines_key(stream)).items()}

    b0 = beat_tokens()
    time.sleep(0.3)
    b1 = beat_tokens()
    target_id = max(b1, key=lambda eid: b1[eid] - b0.get(eid, 0))
    target = int(target_id.rsplit("-", 1)[1])
    pending_at_kill = broker.pending_count(stream, GROUP)
    finals_at_kill = finals_landed(uris)
    assert finals_at_kill < n, "everything finished before the kill"
    t_kill = time.perf_counter()
    procs[target].kill()                          # SIGKILL: no cleanup
    procs[target].wait(timeout=30)
    while finals_landed(uris) < n:
        if time.time() > deadline:
            missing = n - finals_landed(uris)
            raise SystemExit(
                f"token loss: {missing} request(s) never completed "
                f"after the kill")
        time.sleep(0.01)
    recovery_s = time.perf_counter() - t_kill

    # ---- streaming continuity: reconnect replays only missing rows ------
    victim_i = max(i for i in range(n) if max_new[i] >= 8)
    victim = uris[victim_i]
    seen1, seen2, done_ev = [], [], None
    first_conn = outq.stream_tokens(victim, timeout_s=60.0, delete=False)
    for ev in first_conn:                         # "dropped" connection:
        if ev.get("done"):                        # close after 3 frames
            break
        seen1.append(ev)
        if len(seen1) >= 3:
            break
    first_conn.close()
    for ev in outq.stream_tokens(victim, timeout_s=60.0, delete=False,
                                 start=len(seen1)):
        if ev.get("done"):
            done_ev = ev
            break
        seen2.append(ev)
    rows = seen1 + seen2
    assert done_ev is not None and not done_ev.get("error"), done_ev
    assert [ev["i"] for ev in rows] == list(range(len(rows))), \
        "reconnect replayed or skipped a token index"
    assert [ev["t"] for ev in rows] == expected[victim_i], \
        "streamed tokens diverged from the uninterrupted oracle"

    # ---- bitwise parity for every request --------------------------------
    results = {}
    while len(results) < n:
        if time.time() > deadline:
            raise SystemExit("finals landed but would not read back")
        results.update(outq.query_many([u for u in uris
                                        if u not in results], delete=True))
        time.sleep(0.005)
    def _diverge(i, u):
        got = list(np.asarray(results[u]).reshape(-1))
        if got == expected[i]:
            return None
        d = next((j for j, (a, b) in enumerate(zip(got, expected[i]))
                  if a != b), min(len(got), len(expected[i])))
        return (i, len(got), len(expected[i]), d)

    mismatches = [m for m in (_diverge(i, u) for i, u in enumerate(uris))
                  if m is not None]
    assert not mismatches, \
        f"{len(mismatches)} completion(s) diverged from the oracle " \
        f"(idx, got_len, want_len, first_diff): {mismatches[:8]}"

    reports = _fleet_reports(procs)   # SIGTERMs the survivor; the
    assert len(reports) == 1, \
        "expected exactly the survivor's report"   # killed child is silent
    surv = reports[0]["stats"]
    assert reports[0]["cold_compiles"] == 0, \
        "survivor compiled on the resume path"
    assert surv["resumed"] + surv["duplicates"] >= 1, \
        "the kill left no records for the survivor to claim " \
        f"(pending_at_kill={pending_at_kill})"
    redis_srv.stop()

    # ---- preemption vs stall under KV-pool exhaustion --------------------
    # a SMALL pool needs its own warmup (the kv-block buffer's leading
    # dim is baked into the executables); still served from the shared
    # on-disk cache across reruns
    im2 = InferenceModel(placement="replicated", num_replicas=1,
                         compile_cache=CompileCache(cache_dir))
    im2.load_generative(dec.prefill_fn, dec.step_fn, dec.init_params(0),
                        paged_prefill_fn=dec.paged_prefill_fn,
                        paged_step_fn=dec.paged_step_fn)
    im2.warmup_generative_paged(
        dec.init_kv_blocks, num_blocks=13, block_len=BL, lanes=4,
        table_len=4, chunk_buckets=PROMPT_BUCKETS, kv_buckets=[16, 32])
    pressure_prompts = [((np.arange(8) * (i + 3)) % 63 + 1)
                        .astype(np.int32) for i in range(8)]

    def pressure_leg(preempt_max):
        # 8 seqs x 24 new tokens -> 4 blocks each at full context; 4
        # lanes x 4 = 16 demanded vs 12 usable: guaranteed mid-decode
        # exhaustion
        b = MemoryBroker()
        srv = DecodeServing(
            im2, dec.init_kv, broker=b, slots=4, max_kv_len=32,
            kv_buckets=[16, 32], prompt_buckets=PROMPT_BUCKETS,
            max_new_default=24, paged=True,
            init_kv_blocks=dec.init_kv_blocks, block_len=BL,
            kv_blocks=13, preempt_max=preempt_max).start()
        q, o = InputQueue(b), OutputQueue(b)
        t0 = time.perf_counter()
        us = [q.enqueue(t=p, max_new=24, stream=1)
              for p in pressure_prompts]
        gaps, finals = [], {}
        lock = threading.Lock()

        def consume(u):
            last = None
            for ev in o.stream_tokens(u, timeout_s=120.0):
                if ev.get("done"):
                    with lock:
                        finals[u] = ev
                    return
                now = time.perf_counter()
                if last is not None:
                    with lock:
                        gaps.append((now - last) * 1e3)
                last = now

        threads = [threading.Thread(target=consume, args=(u,),
                                    daemon=True) for u in us]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        wall = time.perf_counter() - t0
        srv.stop()
        assert len(finals) == len(us), \
            f"pressure leg (preempt_max={preempt_max}) deadlocked"
        full = sum(1 for ev in finals.values()
                   if ev.get("tokens") is not None
                   and np.asarray(ev["tokens"]).reshape(-1).size == 24)
        return {"preempt_max": preempt_max,
                "itl_ms_p99": round(_percentile(gaps, 0.99), 3),
                "full_completions": full, "requests": len(us),
                "preempted": srv.stats["preempted"],
                "aborted": srv.stats["aborted"],
                "prefix_hit_tokens": srv.stats["prefix_hit_tokens"],
                "wall_s": round(wall, 3)}

    preempt_on = pressure_leg(3)
    preempt_off = pressure_leg(0)
    assert preempt_on["aborted"] == 0 \
        and preempt_on["full_completions"] == len(pressure_prompts), \
        f"preemption failed to complete the pressure mix: {preempt_on}"
    assert preempt_on["preempted"] >= 1, \
        "the pressure mix never actually preempted"

    if own_cache:
        shutil.rmtree(cache_dir, ignore_errors=True)
    out = {
        "mode": "generative_chaos",
        "backend": jax.default_backend(),
        "requests": n, "engines": 2,
        "warmup_s": round(warmup_s, 3),
        "total_tokens": total_tokens,
        "kill": {"finals_at_kill": finals_at_kill,
                 "pending_at_kill": pending_at_kill},
        "recovery": {"all_finals_after_kill_s": round(recovery_s, 3),
                     "resumed": surv["resumed"],
                     "recovered_tokens": surv["recovered_tokens"],
                     "replayed_tokens": surv["replayed_tokens"],
                     "duplicates": surv["duplicates"],
                     "survivor_preempted": surv["preempted"]},
        "survivor_cold_compiles": reports[0]["cold_compiles"],
        "bitwise_identical": n - len(mismatches),
        "token_loss": 0,
        "streaming_reconnect": {
            "first_conn_rows": len(seen1),
            "second_conn_rows": len(seen2),
            "indices_exactly_once": True,
            "bitwise": True},
        "preemption_vs_stall": {"on": preempt_on, "off": preempt_off},
    }
    print(json.dumps(out))
    return 0


def _percentile(samples, q):
    """np.percentile, the same interpolated estimator every other
    p50/p99 in this file uses — a nearest-rank variant here would make
    the elastic replay's p99 a different statistic from the fleet and
    drain benches' in the same JSON round."""
    if not samples:
        return None
    return float(np.percentile(np.asarray(samples), q * 100))


def _elastic_light_ab(srv, cache_dir, batch, n=40):
    """Light-load p50 A/B: one engine at a trickle, adaptive
    deadline-aware dispatch vs the 'static' pad-to-largest-bucket
    strawman. Closed loop (one request in flight — there IS no queue;
    that is the point), sync predict round trips."""
    from analytics_zoo_tpu.serving.broker import RedisBroker
    from analytics_zoo_tpu.serving.client import InputQueue

    out = {}
    for policy in ("static", "adaptive"):
        stream = f"elastic_ab_{policy}"
        broker = RedisBroker(srv.host, srv.port)
        # a FAT straggler window (20 ms) for both engines: the fixed
        # policy always waits it out at light load; adaptive skips it
        # the moment the backlog reads empty
        extra = ["--batch-policy", policy, "--batch-timeout-ms", "20",
                 "--deadline-ms", "30"]
        broker.hset(f"fleet:gate:{stream}", "go", "1")
        procs = _fleet_spawn(1, stream, srv.port, cache_dir, 30.0,
                             batch, extra_args=extra)
        try:
            _fleet_wait_ready(broker, stream, procs, 1)
            q = InputQueue(RedisBroker(srv.host, srv.port), stream)
            _fn, _W, sample = _md_model(width=256, iters=1024)
            arr = np.asarray(sample)
            lats = []
            for i in range(n + 5):
                t0 = time.perf_counter()
                q.predict(arr, timeout_s=30.0)
                dt = (time.perf_counter() - t0) * 1e3
                if i >= 5:                  # settle the cost model
                    lats.append(dt)
                time.sleep(0.02)            # ~3 rps: genuinely light
            out[policy] = {
                "p50_ms": round(_percentile(lats, 0.50), 2),
                "p99_ms": round(_percentile(lats, 0.99), 2),
            }
        finally:
            _fleet_reports(procs)
            broker.close()
    imp = 1.0 - out["adaptive"]["p50_ms"] / max(
        out["static"]["p50_ms"], 1e-9)
    out["p50_improvement_pct"] = round(imp * 100, 1)
    return out


class _EngineLedger:
    """Child engines with spawn/exit timestamps — the chip-seconds
    accounting the static-vs-elastic comparison is about."""

    def __init__(self, stream, port, cache_dir, batch, extra):
        self.stream, self.port = stream, port
        self.cache_dir, self.batch, self.extra = cache_dir, batch, extra
        self.rows = []          # [proc, t_start, t_end|None]
        self.next_idx = 0

    def spawn(self):
        p = _fleet_spawn(1, self.stream, self.port, self.cache_dir,
                         5.0, self.batch, start_idx=self.next_idx,
                         extra_args=self.extra)[0]
        self.next_idx += 1
        self.rows.append([p, time.perf_counter(), None])
        return p

    def retire(self):
        import signal as _signal
        for row in reversed(self.rows):
            if row[2] is None and row[0].poll() is None:
                row[0].send_signal(_signal.SIGTERM)
                return True
        return False

    def reap(self):
        """Stamp exit times for children that have finished draining."""
        for row in self.rows:
            if row[2] is None and row[0].poll() is not None:
                row[2] = time.perf_counter()

    def chip_seconds(self, t_end, t0=None):
        """Engine-seconds in [t0, t_end]: rows spawned before t0 (the
        static fleet's pre-replay cold start, which a production static
        fleet paid long ago) are clamped to the replay window, so the
        static-vs-elastic ratio compares serving commitment, not
        process startup; an elastic MID-run spawn keeps its cold-start
        cost — that lag is part of what elasticity pays."""
        self.reap()
        return sum((row[2] if row[2] is not None else t_end)
                   - (row[1] if t0 is None else max(row[1], t0))
                   for row in self.rows)

    def live_procs(self):
        return [row[0] for row in self.rows if row[0].poll() is None]

    def all_procs(self):
        return [row[0] for row in self.rows]


def _elastic_replay(srv, cache_dir, batch, phases, mode, slo_p99_ms,
                    max_engines):
    """One diurnal+spike replay: an open-loop generator drives the
    phase schedule while a closed-loop prober samples end-to-end
    latency (~8 Hz, tagged by phase — millisecond resolution the
    drain-poll cannot give). `mode` = "static" (max_engines for the
    whole run) or "elastic" (FleetAutoscaler between 1 and
    max_engines)."""
    from analytics_zoo_tpu.serving.broker import RedisBroker, encode_ndarray
    from analytics_zoo_tpu.serving.client import InputQueue
    from analytics_zoo_tpu.serving.fleet import FleetAutoscaler, FleetTracker

    stream = f"elastic_replay_{mode}"
    # what the host grants 2 concurrent processes RIGHT before this
    # leg (the PR 10 per-leg convention): a shared rig's grant swings
    # 1.4-3.4x within minutes, and a spike sized when the host was
    # generous can be unservable by the time this leg runs — the
    # per-leg number makes any SLO miss legible as host starvation
    # vs controller failure
    leg_host_par = _measure_host_parallelism()
    broker = RedisBroker(srv.host, srv.port)
    broker.hset(f"fleet:gate:{stream}", "go", "1")   # no start gate here
    _fn, _W, sample = _md_model(width=256, iters=1024)
    encoded = encode_ndarray(np.asarray(sample))
    arr = np.asarray(sample)
    extra = ["--batch-policy", "adaptive", "--deadline-ms", "150",
             "--batch-timeout-ms", "5",
             "--slo-latency-ms", str(slo_p99_ms)]
    ledger = _EngineLedger(stream, srv.port, cache_dir, batch, extra)
    tracker = scaler = None
    if mode == "static":
        for _ in range(max_engines):
            ledger.spawn()
        _fleet_wait_ready(broker, stream, ledger.all_procs(),
                          max_engines)
    else:
        tracker = FleetTracker(RedisBroker(srv.host, srv.port), stream,
                               ttl_s=1.5)
        # thresholds in RECORDS per alive engine; aggressive up, lazy
        # down — scale-up must beat the spike, scale-down can wait out
        # the tail
        scaler = FleetAutoscaler(
            tracker, RedisBroker(srv.host, srv.port), stream,
            ledger.spawn, ledger.retire,
            min_engines=1, max_engines=max_engines,
            backlog_high=3.0 * batch, backlog_low=1.0 * batch,
            up_stable_s=0.5, down_stable_s=4.0, cooldown_s=3.0,
            # cover the child's cold start (python + jax import +
            # cache-warm ~8s here): without the grace the reconcile
            # clamp re-arms the spawn path mid-startup and every
            # scale-up double-provisions
            spawn_grace_s=45.0,
            interval_s=0.25).start()
        _fleet_wait_ready(broker, stream, ledger.all_procs(), 1)

    samples = []             # (phase, latency_ms)
    stop_probe = threading.Event()

    def prober():
        q = InputQueue(RedisBroker(srv.host, srv.port), stream)
        while not stop_probe.is_set():
            t0 = time.perf_counter()
            try:
                q.predict(arr, timeout_s=30.0)
                samples.append((current_phase[0],
                                (time.perf_counter() - t0) * 1e3))
            except Exception:  # noqa: BLE001 — a lost probe, not a fault
                samples.append((current_phase[0], 30000.0))
            stop_probe.wait(0.12)

    current_phase = ["warm"]
    # two closed-loop probers: during an overload phase one prober's
    # sampling rate collapses to 1/latency — the second keeps the
    # spike-phase sample count meaningful for a p99
    probe_threads = [threading.Thread(target=prober, daemon=True)
                     for _ in range(2)]
    for t in probe_threads:
        t.start()

    gen_broker = RedisBroker(srv.host, srv.port)
    enqueued = 0
    phase_t0 = {}
    engines_seen = {}
    t_run0 = time.perf_counter()
    for name, dur_s, rps in phases:
        current_phase[0] = name
        phase_t0[name] = time.perf_counter()
        period = 1.0 / max(rps, 1e-9)
        t_next = time.perf_counter()
        t_end = phase_t0[name] + dur_s
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if now >= t_next:
                gen_broker.xadd(stream, {"uri": f"{name}-{enqueued}",
                                         "data": {"t": encoded}})
                enqueued += 1
                t_next += period
            else:
                time.sleep(min(t_next - now, 0.005))
            ledger.reap()
        engines_seen[name] = len(ledger.live_procs())
    current_phase[0] = "drain"
    # drain: every open-loop record must land a result (zero loss).
    # hlen is the cheap progress gate, but the authoritative count
    # filters to the generator's own phase-prefixed uris: the probers
    # share this result hash (transient rows between engine HSET and
    # client HDEL, plus a timed-out probe's orphan), and counting
    # theirs could mask a genuinely lost generator record
    result_key = f"result:{stream}"
    phase_names = {name for name, _d, _r in phases}

    def generator_results():
        return sum(1 for u in broker.hgetall(result_key)
                   if u.split("-", 1)[0] in phase_names)

    deadline = time.time() + 300
    while time.time() < deadline:
        ledger.reap()
        if broker.hlen(result_key) >= enqueued \
                and generator_results() >= enqueued:
            break
        time.sleep(0.1)
    t_run_end = time.perf_counter()
    stop_probe.set()
    for t in probe_threads:
        t.join(timeout=35)
    if scaler is not None:
        scaler.stop()
    if tracker is not None:
        tracker.close()
    got = generator_results()
    chip_seconds = ledger.chip_seconds(t_run_end, t0=t_run0)
    reports = _fleet_reports(ledger.all_procs())
    broker.close()

    def phase_stats(name):
        lats = [v for p, v in samples if p == name]
        # steady-state view: the autoscaler's convergence transient
        # (detection + engine cold start) is the first part of the
        # phase; SLO attainment is judged on the settled second half
        # (full-phase numbers are reported alongside)
        k = max(1, int(len(lats) * 0.5))
        steady = lats[k:] if len(lats) > k else lats
        return {
            "n": len(lats),
            "p50_ms": round(_percentile(lats, 0.50), 1) if lats else None,
            "p99_ms": round(_percentile(lats, 0.99), 1) if lats else None,
            "steady_p99_ms": round(_percentile(steady, 0.99), 1)
            if steady else None,
            "engines_at_end": engines_seen.get(name),
        }

    compiled = sum(r.get("sources", {}).get("compiled", 0)
                   for r in reports)
    per_phase = {name: phase_stats(name) for name, _, _ in phases}
    steady = [s["steady_p99_ms"] for s in per_phase.values()
              if s["steady_p99_ms"] is not None]
    return {
        "mode": mode,
        "host_parallelism_at_leg_start": leg_host_par,
        "enqueued": enqueued,
        "results": got,
        "record_loss": enqueued - got,
        "zero_loss": got >= enqueued,
        "chip_seconds": round(chip_seconds, 1),
        "wall_seconds": round(t_run_end - t_run0, 1),
        "engines_spawned": ledger.next_idx,
        "cold_compiled_buckets": compiled,
        "phases": per_phase,
        "slo_p99_ms": slo_p99_ms,
        "slo_held_steady": bool(steady) and all(
            v <= slo_p99_ms for v in steady),
        "engine_reports": reports,
    }


def _elastic_main(args) -> int:
    """`--elastic`: the ISSUE 11 acceptance run. One MiniRedis carries
    everything; a diurnal + spike arrival trace replays twice — against
    a static fleet (max engines, whole run) and against the autoscaled
    elastic fleet — recording per-phase p50/p99, chip-seconds, record
    loss, and cold compiles; plus the light-load adaptive-vs-static-pad
    p50 A/B. Rates are set relative to a measured single-engine
    capacity probe so the spike genuinely overloads one engine on any
    rig. The JSON self-documents the host-parallelism ceiling (PR 3 /
    PR 10 convention): on a shared 2-core box the second engine only
    helps as much as the host actually grants."""
    import shutil
    import tempfile
    import uuid

    from analytics_zoo_tpu.serving.broker import RedisBroker, encode_ndarray
    from analytics_zoo_tpu.serving.redis_server import MiniRedisServer

    batch = 8
    # static baseline = the pre-elastic operating mode: provisioned for
    # peak PLUS one engine of headroom (N+1), up the whole day. The
    # spike needs 2 engines; static runs 3 for the entire replay. The
    # elastic fleet shares the same ceiling and earns its chip-seconds
    # by only using what the backlog demands.
    max_engines = 3
    slo_p99_ms = 1500.0
    cache_dir = tempfile.mkdtemp(prefix="zoo-elastic-cc-")
    srv = MiniRedisServer().start()
    try:
        # -- capacity probe: one adaptive engine drains a backlog ------
        stream = "elastic_cap"
        broker = RedisBroker(srv.host, srv.port)
        broker.hset(f"fleet:gate:{stream}", "go", "1")
        procs = _fleet_spawn(
            1, stream, srv.port, cache_dir, 30.0, batch,
            extra_args=["--batch-policy", "adaptive",
                        "--deadline-ms", "150"])
        _fleet_wait_ready(broker, stream, procs, 1)
        _fn, _W, sample = _md_model(width=256, iters=1024)
        encoded = encode_ndarray(np.asarray(sample))
        n_probe = 240
        t0 = time.perf_counter()
        for i in range(n_probe):
            broker.xadd(stream, {"uri": uuid.uuid4().hex,
                                 "data": {"t": encoded}})
        deadline = time.time() + 120
        while broker.hlen(f"result:{stream}") < n_probe \
                and time.time() < deadline:
            time.sleep(0.05)
        cap_rps = broker.hlen(f"result:{stream}") \
            / (time.perf_counter() - t0)
        _fleet_reports(procs)
        broker.close()

        # -- light-load p50 A/B ----------------------------------------
        light_ab = _elastic_light_ab(srv, cache_dir, batch)

        # host ceiling measured AFTER the probes, right before the
        # replays that the spike sizing has to survive — a probe taken
        # a minute earlier routinely misstates what the replays get
        host_par = _measure_host_parallelism()

        # -- diurnal + spike replay, static then elastic ---------------
        # the diurnal shape: most of the day is light/moderate (one
        # engine's worth), the spike is brief — exactly the regime
        # where static peak-provisioning burns chips doing nothing.
        # The spike must overload ONE engine but stay inside what the
        # scaled-out fleet can absorb on THIS host: on a real pod that
        # is engines x chip, here it is the measured host-parallelism
        # ceiling (a shared 2-core box sometimes grants only ~1.2x —
        # sizing the spike to nominal capacity would then demand the
        # impossible of any autoscaler and measure the rig, not the
        # controller). The factor is recorded in the JSON.
        # 0.7x the granted ceiling: the grant itself swings between the
        # sizing probe and the (later) elastic leg, and a spike sized
        # at the ceiling's edge turns any downswing into an unservable
        # trace — the per-leg host_parallelism_at_leg_start fields make
        # that legible when it still happens
        spike_factor = min(1.25, max(1.05, 0.7 * host_par))
        # the spike must be LONG relative to an engine cold start
        # (~8s nominal, worse when the host is starved): an autoscaler
        # can only show it absorbs a spike that outlives its own
        # scale-up lag — 30s leaves the converged fleet serving most
        # of the phase
        phases = [
            ("light", 15.0, max(3.0, 0.12 * cap_rps)),
            ("ramp", 10.0, 0.45 * cap_rps),
            ("spike", 30.0, spike_factor * cap_rps),
            ("tail", 25.0, 0.12 * cap_rps),
        ]
        static = _elastic_replay(srv, cache_dir, batch, phases,
                                 "static", slo_p99_ms, max_engines)
        elastic = _elastic_replay(srv, cache_dir, batch, phases,
                                  "elastic", slo_p99_ms, max_engines)
    finally:
        srv.stop()
        shutil.rmtree(cache_dir, ignore_errors=True)

    cores = os.cpu_count() or 1
    chip_ratio = elastic["chip_seconds"] / max(static["chip_seconds"],
                                               1e-9)
    out = {
        "metric": "serving_elastic_replay",
        "value": round(chip_ratio, 3),
        "unit": "elastic/static chip-seconds (target <= 0.6)",
        "capacity_probe_rps": round(cap_rps, 1),
        "host_cores": cores,
        "host_effective_parallelism": host_par,
        "phases_rps": {n: round(r, 1) for n, _d, r in phases},
        "spike_factor_vs_one_engine": round(spike_factor, 3),
        "slo_p99_ms": slo_p99_ms,
        "light_load_ab": light_ab,
        "static": static,
        "elastic": elastic,
        "chip_seconds_ratio": round(chip_ratio, 3),
        "elastic_slo_held": elastic["slo_held_steady"],
        "zero_loss": bool(static["zero_loss"] and elastic["zero_loss"]),
        "scale_up_cold_compiles": elastic["cold_compiled_buckets"],
        "note": ("forced-host engines burn real cores: on this "
                 f"{cores}-core rig (measured {host_par:g}x effective "
                 "parallelism at bench time) the second engine only "
                 "adds what the host grants, so spike p99 is bounded "
                 "by the host, not the autoscaler; real engines add a "
                 "whole chip each. Steady p99 excludes each phase's "
                 "first half (the scale-up convergence window)."),
    }
    print(json.dumps(out))
    return 0


# -- cold start: persistent compile cache across process restarts ----------

def _cold_start_child(args) -> int:
    """One server cold-start, timed: build the model, warm every bucket
    through the persistent compile cache, start the engine, serve one
    request end-to-end, report JSON. The parent runs this twice against
    the same cache dir — run 1 compiles and persists, run 2 loads — and
    the warmup wall-time ratio is the cache's cold-start win."""
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.compile_cache import CompileCache
    from analytics_zoo_tpu.serving.broker import MemoryBroker
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.inference_model import InferenceModel
    from analytics_zoo_tpu.serving.server import ClusterServing

    init_orca_context(cluster_mode="local")
    model = _serving_model()
    cache = CompileCache(args.compile_cache_dir)
    infer = InferenceModel(compile_cache=cache).load_keras(model)
    t0 = time.perf_counter()
    infer.warmup(np.zeros((32, 32, 3), np.float32),
                 buckets=[1, 2, 4, 8, 16, 32])
    warmup_s = time.perf_counter() - t0
    # prove the warm server actually serves: one request through the
    # full engine
    broker = MemoryBroker()
    serving = ClusterServing(infer, broker=broker, batch_size=8,
                             batch_timeout_ms=2).start()
    uri = InputQueue(broker).enqueue(
        t=np.random.rand(32, 32, 3).astype(np.float32))
    outq = OutputQueue(broker)
    deadline = time.time() + 30
    served = False
    while time.time() < deadline:
        if outq.query(uri, delete=True) is not None:
            served = True
            break
        time.sleep(0.002)
    serving.stop()
    sources = {}
    for v in infer.warmup_source.values():
        sources[v] = sources.get(v, 0) + 1
    print(json.dumps({"warmup_s": round(warmup_s, 4),
                      "served": served,
                      "sources": sources,
                      "cache": cache.stats()}))
    return 0


def _cold_start_main(args) -> int:
    """`--cold-start`: launch the serving child twice against one fresh
    cache dir — cache-cold then cache-warm — and report the warmup
    wall-time ratio (acceptance: warm <= 0.5x cold on the CI rig)."""
    import shutil
    import tempfile

    cache_dir = args.compile_cache_dir or tempfile.mkdtemp(
        prefix="zoo-cc-bench-")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    runs = []
    try:
        for label in ("cold", "warm"):
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--cold-start-child", "--compile-cache-dir", cache_dir],
                env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True, text=True, timeout=600)
            if proc.returncode != 0:
                sys.stderr.write(proc.stderr)
                raise SystemExit(
                    f"{label} cold-start child failed "
                    f"(rc={proc.returncode})")
            runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    finally:
        if args.compile_cache_dir is None:
            shutil.rmtree(cache_dir, ignore_errors=True)
    cold, warm = runs
    ratio = warm["warmup_s"] / max(cold["warmup_s"], 1e-9)
    print(json.dumps({
        "metric": "serving_cold_start_warmup_ratio",
        "value": round(ratio, 3),
        "target": "<=0.5",
        "vs_baseline": round(0.5 / max(ratio, 1e-9), 3),  # >1 beats it
        "cold_warmup_s": cold["warmup_s"],
        "warm_warmup_s": warm["warmup_s"],
        "cold_sources": cold["sources"],
        "warm_sources": warm["sources"],
        "warm_served": warm["served"],
        "cache_entries": warm["cache"]["entries"],
        "cache_bytes": warm["cache"]["bytes"],
    }))
    return 0


def _serving_model():
    from analytics_zoo_tpu.keras import Sequential
    from analytics_zoo_tpu.keras import layers as L
    model = Sequential([
        L.Convolution2D(16, 3, 3, input_shape=(32, 32, 3),
                        border_mode="same", activation="relu"),
        L.MaxPooling2D(),
        L.Convolution2D(32, 3, 3, border_mode="same", activation="relu"),
        L.GlobalAveragePooling2D(),
        L.Dense(10, activation="softmax"),
    ])
    model.ensure_built(np.zeros((1, 32, 32, 3), np.float32))
    return model


def _int8_ab_main(args) -> int:
    """--int8-ab (ISSUE 12): int8 vs bf16 vs f32 through the FULL
    serving path — InferenceModel load → per-bucket warmup (AOT/bucket
    machinery identical across precisions) → predict — over the SAME
    bucket set, interleaved rounds so host drift cannot bias one
    precision's block. Reports per-bucket and pooled p50s, the
    int8/bf16 p50 ratio (the ISSUE 12 acceptance is ≤ 0.6 on real
    chips: 2x int8 MXU rate + 4x fewer weight bytes), top-1 parity vs
    f32, and the per-dtype serving_weight_bytes price. On a CPU rig
    XLA has no VNNI-style int8 kernel (the int8 dot lowers to widening
    integer math) so the ratio documents the rig, not the design —
    the JSON self-describes this the way the fleet/scaling benches
    report host-core ceilings."""
    import jax.numpy as jnp

    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.keras import Sequential
    from analytics_zoo_tpu.keras import layers as L
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    init_orca_context(cluster_mode="local")
    width = int(os.environ.get("BENCH_INT8_WIDTH", 1024))
    model = Sequential([
        L.Dense(width, activation="relu", input_shape=(256,)),
        L.Dense(width, activation="relu"),
        L.Dense(width, activation="relu"),
        L.Dense(10, activation="softmax")])
    model.ensure_built(np.zeros((1, 256), np.float32))
    params_f32 = jax.device_get(model.params)
    params_bf16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == np.float32 else a, params_f32)

    buckets = [1, 4, 8, 16, 32]

    def load(params=None, quantize=None):
        im = InferenceModel(max_batch=max(buckets))
        if quantize is not None:
            model.params = params_f32
            im.load_keras(model, quantize=quantize)
        elif params is not None:
            im.load_fn(lambda p, x: model.apply(p, x, training=False),
                       params)
        else:
            model.params = params_f32
            im.load_keras(model)
        im.warmup(np.zeros((256,), np.float32), buckets=buckets)
        return im

    variants = {"f32": load(), "bf16": load(params=params_bf16),
                "int8": load(quantize="int8")}
    assert variants["int8"].serving_dtype == "int8"
    assert variants["bf16"].serving_dtype == "bfloat16"

    rs = np.random.RandomState(0)
    xs = {b: rs.rand(b, 256).astype(np.float32) for b in buckets}
    lat = {k: {b: [] for b in buckets} for k in variants}
    rounds, per_round = 6, 8
    for _ in range(rounds):
        for name, im in variants.items():        # interleaved A/B/C
            for b in buckets:
                for _ in range(per_round):
                    t0 = time.perf_counter()
                    im.predict(xs[b])
                    lat[name][b].append(
                        (time.perf_counter() - t0) * 1e3)

    def p50(vals):
        return float(np.percentile(np.asarray(vals), 50))

    pooled = {k: p50(sum(d.values(), [])) for k, d in lat.items()}
    per_bucket = {k: {str(b): round(p50(v), 3)
                      for b, v in d.items()} for k, d in lat.items()}
    # parity on the largest bucket (argmax agreement vs f32)
    xq = rs.rand(256, 256).astype(np.float32)
    pf = np.asarray(variants["f32"].predict(xq))
    p8 = np.asarray(variants["int8"].predict(xq))
    agreement = float((pf.argmax(-1) == p8.argmax(-1)).mean())
    weight_bytes = {k: im.weight_bytes() for k, im in variants.items()}

    ratio = pooled["int8"] / max(pooled["bf16"], 1e-9)
    print(json.dumps({
        "metric": "serving_int8_ab",
        "buckets": buckets,
        "int8_p50_ms": round(pooled["int8"], 3),
        "bf16_p50_ms": round(pooled["bf16"], 3),
        "f32_p50_ms": round(pooled["f32"], 3),
        "int8_vs_bf16_p50_ratio": round(ratio, 3),
        "target_ratio": 0.6,
        "per_bucket_p50_ms": per_bucket,
        "int8_top1_agreement_vs_f32": round(agreement, 4),
        "weight_bytes": weight_bytes,
        "weight_shrink_vs_f32": round(
            weight_bytes["f32"] / max(weight_bytes["int8"], 1), 2),
        "backend": jax.default_backend(),
        "device": getattr(jax.devices()[0], "device_kind",
                          str(jax.devices()[0])),
        "note": ("the ≤0.6 acceptance ratio is an MXU property (2x "
                 "int8 rate + 4x fewer weight bytes); XLA:CPU has no "
                 "VNNI-style int8 kernel, so on a CPU rig this ratio "
                 "documents the rig — read it on real chips, like the "
                 "host-core ceilings of the scaling benches"),
    }))
    return 0


def _registry_tail_metrics():
    """Registry-sourced tail latency + live queue depths for the JSON
    output: the process-wide `MetricsRegistry` accumulated every serving
    instance this bench ran (all broker kinds, pipelined and sync), so
    BENCH_*.json entries carry p50/p95/p99 per stage — not just
    throughput."""
    from analytics_zoo_tpu.observability import get_registry
    snap = get_registry().snapshot()
    latency = {}
    for fam in ("serving_batch_ms", "serving_stage_ms"):
        for s in snap.get(fam, {}).get("series", []):
            key = fam + "".join(f"_{v}" for _, v in
                                sorted(s["labels"].items()))
            latency[key] = {"count": s["count"],
                            "p50_ms": round(s["p50"], 3),
                            "p95_ms": round(s["p95"], 3),
                            "p99_ms": round(s["p99"], 3)}
    depths = {s["labels"]["queue"]: s["value"]
              for s in snap.get("serving_queue_depth", {}).get("series", [])}
    return latency, depths


def _registry_utilization():
    """Live roofline gauges for the bench JSON (ISSUE 6): what fraction
    of the session roofline the serving forwards actually moved —
    per-model HBM-bound fraction and cost-analysis MFU, so BENCH_r06+
    tracks utilization alongside latency with no manual math."""
    from analytics_zoo_tpu.observability import get_accountant
    s = get_accountant().snapshot("serving")
    if not s.get("seconds"):
        return None
    out = {"busy_seconds": round(s["seconds"], 4)}
    for key in ("achieved_tflops", "achieved_hbm_gbps"):
        if s.get(key) is not None:
            out[key] = round(s[key], 4)
    for key in ("mfu", "hbm_utilization"):
        if s.get(key) is not None:
            out[key + "_pct"] = round(s[key] * 100, 3)
    return out


def main():
    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=None,
                    help="multi-device mode: replica-pool/sharded drain "
                         "scaling over N (forced-host) devices")
    ap.add_argument("--total", type=int, default=256,
                    help="backlog size for the multi-device drain")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos mode: replica crash + slow replica + "
                         "broker outage against a live 4-replica engine; "
                         "reports quarantine detection/revival time, "
                         "record loss, and post-recovery throughput")
    ap.add_argument("--cold-start", action="store_true",
                    help="cold-start mode: launch a child server twice "
                         "(cache-cold, cache-warm) against one persistent "
                         "compile cache and report the warmup ratio")
    ap.add_argument("--cold-start-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--compile-cache-dir", default=None,
                    help="cache dir for --cold-start / the fleet's "
                         "shared warmup (default: throwaway temp dir)")
    ap.add_argument("--engines", type=int, default=None,
                    help="fleet mode (ISSUE 10): spawn N engine "
                         "processes behind one MiniRedis, report the "
                         "drain scaling curve, and SIGKILL one engine "
                         "mid-drain to prove zero-loss redelivery")
    ap.add_argument("--generative-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--step-stall-ms", type=float, default=0.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--fleet-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--broker-url", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--stream", default="serving_stream",
                    help=argparse.SUPPRESS)
    ap.add_argument("--engine-id", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--claim-min-idle", type=float, default=30.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--chaos-rollout", action="store_true",
                    help="zero-downtime rollout under fire: publish "
                         "v2 to a 3-engine fleet, kill the gateway + "
                         "one engine mid-rollout, restart, assert "
                         "convergence to exactly one version with "
                         "zero accepted-record loss (ISSUE 14)")
    ap.add_argument("--rollout-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--fleet-batch", type=int, default=8,
                    help=argparse.SUPPRESS)
    ap.add_argument("--pin-core", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--trace-overhead", action="store_true",
                    help="ISSUE 17: drain-throughput A/B at trace "
                         "sampling 0 / 0.01 / 1.0 + trace assembly "
                         "latency")
    ap.add_argument("--int8-ab", action="store_true",
                    help="int8-vs-bf16 A/B through the full serving "
                         "path over one bucket set (ISSUE 12): pooled "
                         "and per-bucket p50s, parity vs f32, per-dtype "
                         "weight bytes")
    ap.add_argument("--elastic", action="store_true",
                    help="diurnal+spike traffic replay: static fleet vs "
                         "autoscaled elastic fleet (adaptive batching, "
                         "tiered admission rails; ISSUE 11)")
    ap.add_argument("--batch-policy", default="adaptive",
                    help=argparse.SUPPRESS)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--batch-timeout-ms", type=int, default=2,
                    help=argparse.SUPPRESS)
    ap.add_argument("--slo-latency-ms", type=float, default=0.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--request-plane", action="store_true",
                    help="request-plane mode (ISSUE 16): wire-speed "
                         "ingest A/B (per-record XADD vs batched "
                         "multi-XADD vs streaming session, against the "
                         "measured RESP wire floor) plus the "
                         "partition-scaling drain curve at 1/2/4 "
                         "partition streams")
    ap.add_argument("--partitions", type=int, default=1,
                    help=argparse.SUPPRESS)
    ap.add_argument("--partition-lease-ttl", type=float, default=5.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--generative", action="store_true",
                    help="generative mode (ISSUE 18): continuous-"
                         "batching decode engine vs pad-to-max-restart "
                         "baseline on a seeded Poisson prompt/output "
                         "mix; tokens/sec, TTFT/ITL p99, slot-"
                         "utilization ratio, 0-compile assertion; "
                         "with --chaos (ISSUE 20): SIGKILL one of two "
                         "decode engines mid-generation — bitwise-"
                         "identical resume from durable token rows, "
                         "exactly-once streaming across a reconnect, "
                         "preemption-vs-stall under KV exhaustion")
    ap.add_argument("--paged", action="store_true",
                    help="with --generative (ISSUE 19): paged-KV legs "
                         "on a prefix-heavy Poisson mix — capacity "
                         "multiplier at fixed pool bytes, prefix-hit "
                         "vs cold TTFT, ITL p99 while a near-max "
                         "prompt joins with chunked prefill on vs off, "
                         "zero-loss + 0-compile assertions")
    args = ap.parse_args()
    if args.fleet_child:
        if not (args.broker_url and args.engine_id):
            raise SystemExit("--fleet-child needs --broker-url and "
                             "--engine-id")
        return _fleet_child(args)
    if args.generative_child:
        if not (args.broker_url and args.engine_id):
            raise SystemExit("--generative-child needs --broker-url and "
                             "--engine-id")
        return _generative_chaos_child(args)
    if args.engines:
        return _fleet_main(args)
    if args.request_plane:
        return _request_plane_main(args)
    if args.chaos_rollout:
        return _chaos_rollout_main(args)
    if args.int8_ab:
        return _int8_ab_main(args)
    if args.trace_overhead:
        return _trace_overhead_main(args)
    if args.generative and args.chaos:
        return _generative_chaos_main(args)
    if args.generative and args.paged:
        return _generative_paged_main(args)
    if args.generative:
        return _generative_main(args)
    if args.elastic:
        return _elastic_main(args)
    if args.chaos:
        return _chaos_main(args)
    if args.devices:
        return _multidevice_main(args)
    if args.cold_start_child:
        if not args.compile_cache_dir:
            raise SystemExit("--cold-start-child needs --compile-cache-dir")
        return _cold_start_child(args)
    if args.cold_start:
        return _cold_start_main(args)

    init_orca_context(cluster_mode="local")
    model = _serving_model()
    infer = InferenceModel(concurrent_num=2).load_keras(model)
    # warm every jit bucket the run will hit — warmup() (not bare
    # predicts) so the timer percentiles stay clean AND the roofline
    # layer harvests per-bucket cost analysis for the utilization JSON
    infer.warmup(np.zeros((32, 32, 3), np.float32),
                 buckets=[1, 2, 4, 8, 16, 32])

    results = {}
    for kind in ("memory", "tcp", "redis"):
        p50, p99 = _measure(infer, kind)
        results[kind] = {"p50_ms": round(p50, 2), "p99_ms": round(p99, 2)}

    # sustained concurrent throughput: pipelined engine vs the old
    # synchronous loop, same model, same redis wire path. Interleaved
    # rounds, MEDIAN per engine: single-process thread scheduling swings
    # individual runs up to 3x in both directions (2-core rigs), so a
    # best-of estimator would crown whoever got the lucky spike while
    # sequential blocks would hand one engine the warmed-up half of the
    # session
    # 32 in-flight: shallower closed loops leave the engine unsaturated
    # (the single-process harness, not the server, becomes the limiter
    # and the comparison measures harness scheduling)
    # 5 rounds: with 3, one lucky scheduling spike for either engine
    # still flips the median (observed: sync spiking 186 rps in a round
    # while its other rounds sat at 115-128)
    pipe_rounds, sync_rounds = [], []
    for _ in range(5):
        pipe_rounds.append(_measure_concurrent(infer, "redis",
                                               n_clients=32,
                                               pipelined=True))
        sync_rounds.append(_measure_concurrent(infer, "redis",
                                               n_clients=32,
                                               pipelined=False))
    pipe_rounds.sort(key=lambda r: r[0])
    rps_pipe, cp50, cp99 = pipe_rounds[len(pipe_rounds) // 2]  # median round
    rps_sync = float(np.median([r[0] for r in sync_rounds]))

    # engine-limited drain (stable): pre-filled backlog, no client costs
    drain_pipe = _measure_drain(infer, "redis", pipelined=True)
    drain_sync = _measure_drain(infer, "redis", pipelined=False)

    # decode-share A/B (ISSUE 9): legacy per-record decode vs zero-copy
    # into preallocated bucket buffers, per-stage timers per mode
    decode_ab = _measure_decode_ab(infer)

    # snapshot utilization NOW: the probe/identity models below call
    # load_fn, which resets the "serving" roofline accumulators to
    # describe THEIR program — the JSON must describe the main model's
    serving_utilization = _registry_utilization()

    # no-compile-on-request-path probe (+ cache-hit vs compile counts)
    first_ms, steady_p50, warmup_sources = _warmup_probe(model)

    # pure wire cost: identity model through the redis path, so the
    # composed TPU number (wire + device forward) never counts a model
    # forward twice
    ident = InferenceModel().load_fn(lambda p, x: x, params=())
    wire_p50, wire_p99 = _measure(ident, "redis")
    registry_latency, registry_queue_depth = _registry_tail_metrics()
    stop_orca_context()

    # headline: the Redis-wire path (what BASELINE.md names)
    p50 = results["redis"]["p50_ms"]
    print(json.dumps({
        "metric": "serving_p50_latency",
        "value": p50,
        "unit": "ms",
        "vs_baseline": round(50.0 / max(p50, 1e-6), 3),  # >1 beats target
        "broker": "redis",
        "p99_ms": results["redis"]["p99_ms"],
        "by_broker": results,
        "wire_only_p50_ms": round(wire_p50, 2),
        "wire_only_p99_ms": round(wire_p99, 2),
        "n_requests": N_REQUESTS,
        "serving_concurrent_rps_pipelined": round(rps_pipe, 1),
        "serving_concurrent_rps_sync": round(rps_sync, 1),
        "serving_pipeline_speedup": round(rps_pipe / max(rps_sync, 1e-9),
                                          2),
        "serving_concurrent_p50_ms": round(cp50, 2),
        "serving_concurrent_p99_ms": round(cp99, 2),
        "serving_drain_rps_pipelined": round(drain_pipe, 1),
        "serving_drain_rps_sync": round(drain_sync, 1),
        "serving_drain_speedup": round(drain_pipe / max(drain_sync, 1e-9),
                                       2),
        # host-side decode share: wire p50 vs end-to-end p50 is the
        # budget; the A/B shows what zero-copy decode cut out of it
        "serving_host_gap_p50_ms": round(p50 - wire_p50, 3),
        "serving_decode_ab": decode_ab,
        "serving_warm_first_request_ms": round(first_ms, 3),
        "serving_steady_p50_ms": round(steady_p50, 3),
        # what each probe restart paid: buckets compiled fresh vs
        # warmed from the shared persistent compile cache
        "serving_warmup_compiled_buckets": warmup_sources["compiled"],
        "serving_warmup_cached_buckets": warmup_sources["cached"],
        "registry_latency": registry_latency,
        "registry_queue_depth": registry_queue_depth,
        # roofline gauges (ISSUE 6): cost-analysis MFU + HBM-bound
        # fraction of the serving forwards, vs the session roofline
        "serving_utilization": serving_utilization,
    }))


if __name__ == "__main__":
    sys.exit(main())
