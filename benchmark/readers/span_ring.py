"""Reader `span_ring`: a statistic over the `fit.epoch` spans that the
program's own tracer (`analytics_zoo_tpu.observability.get_tracer()`)
holds when a traced run ends. Per-layer metrics are read only in a
`--trace 1` run, after the traced fit call: the last `fit` root in the
ring is then the traced call, and the one before it the window's. The
window's root is held to `0.9 x window_s <= duration <= window_s`; where
it does not fit, or the program has no tracer or no such spans (the
parent of the PR that brought them), there is nothing to read.

spec: statistic:
  slowest_epoch_over_median  the window's longest `fit.epoch` over its
                             median one; every epoch's milliseconds go
                             to an earlier output line
  traced_epoch_overhead_pct  mean `fit.epoch` of the traced call over the
                             window's median one, less one, in %"""

from __future__ import annotations

import statistics

from benchmark import harness


def _epochs(spans, root):
    return [s.duration for s in spans
            if s.name == "fit.epoch" and s.trace_id == root.trace_id]


def read(spec, sources):
    try:
        from analytics_zoo_tpu.observability import get_tracer
    except ImportError:
        return None
    spans = get_tracer().spans()
    roots = [s for s in spans if s.name == "fit" and s.cat == "training"]
    window_s = sources.get("window_s")
    if len(roots) < 2 or not window_s:
        return None
    window, traced = roots[-2], roots[-1]
    if not 0.9 * window_s <= window.duration <= window_s:
        return None
    in_window, in_traced = _epochs(spans, window), _epochs(spans, traced)
    if not in_window:
        return None
    median = statistics.median(in_window)
    if spec["statistic"] == "slowest_epoch_over_median":
        harness.log(f"span_ring {window.trace_id} epoch_ms="
                    f"{[round(d * 1e3, 3) for d in in_window]}")
        return max(in_window) / median
    if spec["statistic"] == "traced_epoch_overhead_pct":
        if not in_traced:
            return None
        return 100.0 * (statistics.mean(in_traced) / median - 1.0)
    raise ValueError(f"unknown span_ring statistic {spec['statistic']!r}")
