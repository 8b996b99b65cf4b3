"""Unified observability layer (ISSUE 2 + ISSUE 6): one metrics
registry, one tracer, one exposition path for serving AND training —
plus the deep-profiling layer that makes the stack self-measuring.

- `MetricsRegistry` / `get_registry()` — labeled Counter/Gauge/Histogram
  families; the Histogram is the log-bucketed streaming histogram from
  `serving/timer.py`, generalized.
- `render_prometheus(registry)` — Prometheus 0.0.4 text, served by the
  HTTP frontend's `GET /metrics` under `Accept: text/plain`.
- `Tracer` / `get_tracer()` — scoped spans with Chrome trace-event JSON
  export (Perfetto-viewable), threaded through the serving pipeline and
  the fit loop; a scoped span is a host event of any running
  `jax.profiler` capture too.
- `MetricsReporter` — periodic one-line digest thread (optionally
  evaluating an `SLOTracker` each report).
- `RooflineAccountant` / `cost_of` — serving's hardware utilization
  (achieved TFLOP/s, MFU, HBM GB/s vs the nameplate peaks of
  `utils/roofline.py`) derived from XLA cost analysis.
- `ProfileCapture` / `StackSampler` — bounded on-demand `jax.profiler`
  captures (`POST /profile`, `fit_keras(profile_steps=...)`) and a
  host-side stack-sampling profiler for the pipeline threads.
- `DeviceMemoryWatcher` / `leak_check` — per-device live/peak HBM
  gauges and a leak assertion for tests.
- `SLOObjectives` / `SLOTracker` — declarative latency/availability
  objectives with burn-rate gauges and the `/healthz` readiness input.
- `device_time` — device time of a profiler capture by the program's own
  `jax.named_scope`s (the compiled program's table of instruction ->
  scope joined to the capture; `python -m ...observability.device_time`).
- `programs` — `xla_program_obtain_ms{how, during}` and the span
  `fit.obtain_program`: how the process got each executable (compiled,
  or loaded from the persistent cache), listened for from import on.
"""

from analytics_zoo_tpu.observability import programs as _programs

from analytics_zoo_tpu.observability.capture import (CaptureActiveError,
                                                     ProfileCapture,
                                                     StackSampler,
                                                     load_trace_events)
from analytics_zoo_tpu.observability.memwatch import (DeviceMemoryLeak,
                                                      DeviceMemoryWatcher,
                                                      device_memory_snapshot,
                                                      leak_check)
from analytics_zoo_tpu.observability.prometheus import (CONTENT_TYPE,
                                                        render_prometheus)
from analytics_zoo_tpu.observability.registry import (Counter, Gauge,
                                                      Histogram,
                                                      LogHistogram,
                                                      MetricsRegistry,
                                                      get_registry)
from analytics_zoo_tpu.observability.reporter import MetricsReporter, digest
from analytics_zoo_tpu.observability.roofline import (ExecCost,
                                                      RooflineAccountant,
                                                      cost_of,
                                                      get_accountant)
from analytics_zoo_tpu.observability.slo import SLOObjectives, SLOTracker
from analytics_zoo_tpu.observability.tracing import (Span, Tracer,
                                                     get_tracer,
                                                     span_coverage,
                                                     span_from_dict,
                                                     span_to_dict)

_programs.install()

__all__ = [
    "CONTENT_TYPE", "CaptureActiveError", "Counter", "DeviceMemoryLeak",
    "DeviceMemoryWatcher", "ExecCost", "Gauge", "Histogram",
    "LogHistogram", "MetricsRegistry", "MetricsReporter",
    "ProfileCapture", "RooflineAccountant", "SLOObjectives", "SLOTracker",
    "Span", "StackSampler", "Tracer", "cost_of", "device_memory_snapshot",
    "digest", "get_accountant", "get_registry", "get_tracer", "leak_check",
    "load_trace_events", "render_prometheus", "span_coverage",
    "span_from_dict", "span_to_dict",
]
