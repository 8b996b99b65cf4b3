"""What every runner shares: finding a cell's files by name, the
set-up clock, the compile counter, the device line, the profiler window
and the per-layer readers' dispatch. Nothing here knows a model or a
traffic mix by name."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    """Earlier output lines go to stdout too, flushed; only the LAST
    line is the result."""
    print(msg, flush=True)


def load_json(*rel: str) -> Dict:
    with open(os.path.join(BENCH_DIR, *rel)) as fh:
        return json.load(fh)


def with_rehearsal(d: Dict, rehearse: bool) -> Dict:
    """A file's `rehearsal` group holds the tiny sizes of the CPU
    rehearsal; it replaces top-level keys only when asked to."""
    out = {k: v for k, v in d.items() if k != "rehearsal"}
    if rehearse:
        out.update(d.get("rehearsal", {}))
    return out


def load_cell(workload: str, rehearse: bool, bench_file: Optional[str] = None
              ) -> Dict:
    """BENCHMARK.json's entry for `workload`, with its configuration and
    traffic files found by name, and the per-layer metrics it reports."""
    with open(bench_file or os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as fh:
        config = with_rehearsal(json.load(fh), rehearse)
    traffic = with_rehearsal(
        load_json("traffic", cell["traffic"] + ".json"), rehearse)

    def reported(metric: Dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "bench": bench, "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "per_layer": [m for m in bench["per_layer"] if reported(m)],
    }


class SetupClock:
    """Seconds since the process started, and the parts of set-up by
    name, printed on an earlier line."""

    def __init__(self, t_process_start: float):
        self.t0 = t_process_start
        self.parts: Dict[str, float] = {}
        self._last = time.perf_counter()
        self.parts["python_start_and_imports"] = self._last - self.t0

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = self.parts.get(name, 0.0) + now - self._last
        self._last = now

    def total(self) -> float:
        return time.perf_counter() - self.t0

    def report(self) -> None:
        log("setup_parts_s " + json.dumps(
            {k: round(v, 3) for k, v in self.parts.items()}))


class CompileCounter:
    """Counts XLA compile requests, fresh compiles and persistent-cache
    loads alike (every time a jit found nothing in memory), as
    `chip_smoke.CompileCounter` does."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **kwargs):
        if name == COMPILE_EVENT:
            self.n += 1


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache's key), or where
    JAX_COMPILATION_CACHE_DIR says. Set in the environment before jax
    starts, so the program's own `enable_jax_persistent_cache` takes the
    same directory and sets no other."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(ROOT, ".xla_cache"))
    os.makedirs(path, exist_ok=True)
    return path


def require_device(chips: int, rehearse: bool) -> Dict:
    """The device line of the result. Without a TPU, or with fewer chips
    than the cell asks for, the run ends with a non-zero code and prints
    no result; only the rehearsal argument lets a CPU through, and its
    line says `cpu`."""
    import jax
    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"benchmark: need {chips} TPU chip(s), found {len(devs)} "
              f"{devs[0].platform} device(s); no result "
              "(--rehearse runs the tiny CPU rehearsal)", file=sys.stderr)
        raise SystemExit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _held_bytes(stats: Dict, prefix: str = "") -> int:
    """What a chip holds: live buffers (`bytes_in_use`) plus what loaded
    programs reserve for their temporaries (`bytes_reserved`). On this
    backend the two are separate counters: after a BERT-base fit at batch
    256 `peak_bytes_in_use` read 1.46 GB and `peak_bytes_reserved` 10.0
    GB, and the largest free block confirmed that both were held at once
    (my chip run, PR 23)."""
    return int(stats.get(prefix + "bytes_in_use", 0)) \
        + int(stats.get(prefix + "bytes_reserved", 0))


def memory_peak_bytes(sampled: int = 0) -> int:
    """Peak bytes held on the fullest chip: the largest (in use +
    reserved) a `MemorySampler` saw during the window, and never less
    than either of the backend's own peak counters. 0 where the backend
    reports nothing (the CPU)."""
    import jax
    peaks = [int(sampled)]
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks += [int(stats.get("peak_bytes_in_use", 0)),
                  int(stats.get("peak_bytes_reserved", 0))]
    return max(peaks)


class MemorySampler:
    """Polls what every chip holds (`_held_bytes`) from a thread while a
    window runs; the backend keeps a peak of each counter but not of
    their sum."""

    INTERVAL_S = 0.05

    def __init__(self):
        self.max_bytes = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-memory-sampler")

    def _loop(self):
        import jax
        devices = jax.local_devices()
        while not self._stop.wait(self.INTERVAL_S):
            for d in devices:
                stats = d.memory_stats() or {}
                self.max_bytes = max(self.max_bytes, _held_bytes(stats))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=5)
        return False


def log_memory_stats() -> None:
    import jax
    log("device_memory_stats " + json.dumps(
        jax.local_devices()[0].memory_stats() or {}))


class TracedWindow:
    """`with TracedWindow(name) as tw: ...` traces the block with the
    JAX profiler and puts the benchmark's annotation `name` around it;
    afterwards `tw.reduced` is `trace_reduce.reduce_trace`'s result."""

    def __init__(self, name: str, op_patterns: Optional[Dict[str, str]]):
        self.name = name
        self.op_patterns = op_patterns
        self.reduced: Dict = {}
        # under TMPDIR: the driver gives each side its own
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1      # host spans name the idle gaps
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        from benchmark import trace_reduce
        self._ann.__exit__(*exc)
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                path = trace_reduce.find_xplane(self.dir)
                trace = trace_reduce.load_xplane(path)
                self.reduced = trace_reduce.reduce_trace(
                    trace, self.name, self.op_patterns)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return False


def reader_spec(metric: Dict) -> Dict:
    """A per-layer metric's own file, `layer_metrics/<name>.json`: which
    reader takes it and that reader's parameters. Its layer, unit and
    `moves` stand in BENCHMARK.json alone."""
    return dict(load_json("layer_metrics", metric["name"] + ".json"),
                name=metric["name"])


def op_patterns_for(per_layer: List[Dict]) -> Dict[str, str]:
    """The operation-name patterns of this cell's metrics, for the trace
    reducer: every metric whose file gives a `pattern`."""
    specs = [reader_spec(m) for m in per_layer]
    return {s["name"]: s["pattern"] for s in specs if "pattern" in s}


def read_per_layer(per_layer: List[Dict], sources: Dict) -> Dict[str, Dict]:
    """One value per per-layer metric of this cell. Each metric's file
    (`reader_spec`) names its reader, a module
    `benchmark/readers/<reader>.py` with `read(spec, sources)`. A reader
    that finds nothing to read returns None, and the metric is left out
    of the line."""
    out = {}
    for m in per_layer:
        spec = reader_spec(m)
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(spec, sources)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(ctx: Dict, *, correct: bool, attempted: int, failed: int,
                values: Dict[str, float], sources: Dict,
                traced: Optional["TracedWindow"], sampled_memory: int) -> str:
    """The last line of a run. With `--trace 0` its metrics are the
    cell's end-to-end metrics out of `values`; with `--trace 1` they are
    the cell's per-layer metrics, read by each metric's reader from
    `sources` and the reduced trace, and the device line carries the
    traced window's busy and window seconds and the breakdown."""
    device = dict(ctx["device"])
    device["memory_peak_bytes"] = memory_peak_bytes(sampled_memory)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed)}
    if traced is not None:
        reduced = traced.reduced
        line["metrics"] = read_per_layer(ctx["per_layer"], dict(
            sources, trace=reduced, device_kind=device["kind"],
            chips=ctx["chips"],
            memory_peak_bytes=device["memory_peak_bytes"]))
        device["busy_s"] = reduced.get("busy_s", 0.0)
        device["window_s"] = reduced.get("window_s", 0.0)
        line["breakdown"] = {"device_ops": reduced.get("device_ops", []),
                             "idle_gaps": reduced.get("idle_gaps", [])}
    else:
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in ctx["end_to_end"] if m["name"] in values}
    line["device"] = device
    return json.dumps(line)


def seed_key(seed: int):
    """A jax PRNG key from any whole number up to a little over 2**31
    (and beyond): folded into 32 bits."""
    import jax
    return jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))


def run_kind(kind: str) -> Callable:
    """The runner of a traffic `kind`: module `benchmark/runners/<kind>.py`
    with `run(ctx) -> result line`."""
    return importlib.import_module("benchmark.runners." + kind).run
