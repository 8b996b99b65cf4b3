#!/usr/bin/env python
"""Static lint for metric names (ISSUE 2 satellite; tier-1 via
tests/test_metric_names.py).

Scans every Python source under `analytics_zoo_tpu/` (plus `scripts/`
and `bench_serving.py`) for literal registry registrations —
`<registry>.counter("name", ...)`, `.gauge(...)`, `.histogram(...)` —
and enforces the conventions the runtime registry also checks, so a
violation fails CI before it ever runs:

- names are snake_case: `[a-z][a-z0-9]*(_[a-z0-9]+)*`
- unit-suffix conventions: counters end `_total`; histograms end with a
  unit (`_ms`, `_bytes`, `_seconds`); gauges must NOT claim `_total`
- unique registration: one name maps to exactly one metric kind across
  the whole codebase (get-or-create from several sites is fine — that
  is the convergence the registry exists for — but the same name as
  both a counter and a gauge is a collision Prometheus would reject)
- docs drift (ISSUE 6 satellite): every REQUIRED family must appear in
  `docs/ProgrammingGuide/observability.md`, so a new load-bearing
  family (profiler, SLO, memory, roofline) cannot ship undocumented

Exit code 0 when clean; 1 with one line per violation otherwise.

    python scripts/check_metric_names.py [root ...]
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Tuple

NAME_RE = re.compile(r"^[a-z][a-z0-9]*(_[a-z0-9]+)*$")
# `registry.counter("x"` / `reg.gauge('y'` / `.histogram("z"` — literal
# first argument only; dynamically-built names are the runtime
# registry's job
CALL_RE = re.compile(
    r"\.\s*(counter|gauge|histogram)\s*\(\s*(?:\n\s*)?['\"]([^'\"]+)['\"]",
    re.MULTILINE)

COUNTER_SUFFIX = ("_total",)
HIST_SUFFIXES = ("_ms", "_bytes", "_seconds")

DEFAULT_ROOTS = ("analytics_zoo_tpu", "scripts", "bench_serving.py")

# Load-bearing names with their required kinds: families other code
# (dashboards, the bench JSON, docs tables) depends on existing. A
# rename or kind change here must fail CI, not silently break scrapes.
# Unit semantics ride on the suffix conventions checked above
# (`_total` counters, `_ms`/`_bytes`-suffixed histograms).
REQUIRED = {
    "compile_cache_hits_total": "counter",
    "compile_cache_misses_total": "counter",
    "compile_cache_load_errors_total": "counter",
    "compile_cache_load_ms": "histogram",
    "compile_cache_compile_ms": "histogram",
    "compile_cache_bytes": "gauge",
    "serving_records_total": "counter",
    "serving_stage_ms": "histogram",
    "training_steps_total": "counter",
    # fault-tolerance layer (ISSUE 5): the failure-matrix metrics the
    # docs table and the chaos bench read
    "serving_replica_quarantined_total": "counter",
    "serving_replica_revivals_total": "counter",
    "serving_broker_breaker_state": "gauge",
    "training_resumes_total": "counter",
    "training_step_retries_total": "counter",
    # deep-profiling layer (ISSUE 6): roofline accounting, on-demand
    # capture, device-memory telemetry, SLO health — the families the
    # bench JSON, /healthz, and the docs tables read
    "roofline_flops_total": "counter",
    "roofline_hbm_bytes_total": "counter",
    "roofline_busy_seconds_total": "counter",
    "roofline_achieved_tflops": "gauge",
    "roofline_achieved_hbm_gbps": "gauge",
    "roofline_mfu": "gauge",
    "roofline_hbm_utilization": "gauge",
    "profile_captures_total": "counter",
    "device_memory_live_bytes": "gauge",
    "device_memory_peak_bytes": "gauge",
    "slo_burn_rate": "gauge",
    "slo_met": "gauge",
    "observability_gauge_errors_total": "counter",
    # fleet scale-out (ISSUE 10): the families the fleet gateway's
    # /healthz contract, the fleet bench, and the redelivery zero-loss
    # accounting read — renaming any of these silently blinds the
    # fleet dashboard and the drain-curve JSON
    "serving_engines_alive": "gauge",
    "serving_engines_total": "counter",
    "serving_engine_heartbeats_total": "counter",
    "serving_claimed_records_total": "counter",
    # elastic serving (ISSUE 11): the adaptive-batching cost model and
    # controller telemetry, tiered admission outcomes, and autoscaler
    # state — the families the elastic bench JSON, the docs tables, and
    # any capacity dashboard read
    "serving_bucket_ms": "histogram",
    "serving_bucket_cost_ms": "gauge",
    "serving_queue_age_ms": "histogram",
    "serving_chosen_bucket_total": "counter",
    "serving_admission_total": "counter",
    "serving_backlog_depth": "gauge",
    "serving_engines_target": "gauge",
    "serving_autoscaler_decisions_total": "counter",
    # generative serving (ISSUE 18): per-token telemetry from the
    # continuous-batching decode engine — tokens throughput, the two
    # streaming SLO inputs (TTFT, inter-token latency), and the KV slot
    # occupancy gauge that drives admission
    "serving_tokens_total": "counter",
    "serving_ttft_ms": "histogram",
    "serving_itl_ms": "histogram",
    "serving_kv_slots_in_use": "gauge",
    # paged KV + prefix cache + chunked prefill (ISSUE 19): the block-
    # pool occupancy gauge that replaces the slot gauge as the paged
    # admission signal, the cache hit-rate pair, and the chunk counter
    # the ITL-protection accounting reads — renaming any of these
    # silently blinds the paged bench JSON and the docs tables
    "serving_kv_blocks_in_use": "gauge",
    "serving_prefix_cache_hits_total": "counter",
    "serving_prefix_cache_misses_total": "counter",
    "serving_prefill_chunks_total": "counter",
    # big-model frontier (ISSUE 12): quantized serving + tensor-parallel
    # placement telemetry — the families the int8 A/B bench, the docs
    # tables and any capacity dashboard read. serving_weight_bytes is
    # the honest per-dtype weight price (int8 reads ~4x under f32);
    # training_mesh_axis_size distinguishes a pure-fsdp fit from a
    # tensor-parallel one on a scrape.
    "serving_weight_bytes": "gauge",
    "training_mesh_axis_size": "gauge",
    "quantized_checkpoints_total": "counter",
    # zero-downtime rollout (ISSUE 14): the version lifecycle families
    # the /rollout endpoints, the chaos-rollout bench JSON, and the
    # fleet-convergence dashboard read — serving_model_version is how
    # a scrape watches a rollout sweep the fleet, and renaming any of
    # these silently blinds the rollback/quarantine audit trail
    "serving_model_version": "gauge",
    "serving_rollout_state": "gauge",
    "serving_rollout_transitions_total": "counter",
    "serving_rollout_rollbacks_total": "counter",
    # parallel input pipeline (ISSUE 15): the device-wait vs host-wait
    # accounting the input-pipeline bench A/B and the distributed-
    # training guide's "am I input-bound" runbook read — renaming
    # either silently blinds the input-stall verdict
    "training_input_wait_ms": "histogram",
    "training_input_bound": "gauge",
    # partitioned request plane + replicated gateway (ISSUE 16): the
    # per-partition ownership/churn families the request-plane guide's
    # runbook and the partition-scaling bench JSON read, plus the
    # gateway leader-election telemetry — renaming any of these blinds
    # the takeover audit trail a kill-the-leader drill depends on
    "serving_partitions_owned": "gauge",
    "serving_partition_lease_changes_total": "counter",
    "serving_partition_depth": "gauge",
    "gateway_role": "gauge",
    "gateway_leader_changes_total": "counter",
    # fleet observability plane (ISSUE 17): the trace-export health
    # families and the fleet-scrape staleness gauge — the guards that
    # make span loss and stale engine blobs visible on a scrape.
    # Renaming any of these blinds the trace plane's own telemetry.
    "observability_spans_dropped_total": "counter",
    "serving_trace_spans_total": "counter",
    "serving_trace_sampled_total": "counter",
    "serving_trace_dropped_total": "counter",
    "fleet_scrape_age_s": "gauge",
    # crash-safe generative serving (ISSUE 20): the recovery/preemption
    # audit trail the chaos bench JSON and the fault-tolerance docs
    # matrix read — renaming any of these silently blinds the
    # zero-token-loss accounting
    "serving_decode_resumes_total": "counter",
    "serving_preemptions_total": "counter",
    "serving_sequence_aborts_total": "counter",
    "serving_token_replays_total": "counter",
    "serving_kv_pressure_evictions_total": "counter",
}

OBSERVABILITY_DOC = os.path.join("docs", "ProgrammingGuide",
                                 "observability.md")

# Serving span-name vocabulary (ISSUE 17): the cross-process trace
# assembler keys its skew model and critical-path columns on these
# literal stage names, so a misspelled span silently falls out of
# /trace/<id>/summary. REQUEST_SPANS must carry a trace_id/trace_ids so
# the request's merged timeline can find them; LIFECYCLE_SPANS are
# engine-scoped events that legitimately have no request id.
REQUEST_SPANS = frozenset({
    "wire", "decode_q_wait", "decode", "dispatch_q_wait", "dispatch",
    "device", "sink_q_wait", "sink", "writeback", "serve_once",
    "gateway_request"})
LIFECYCLE_SPANS = frozenset({"rollout_swap"})
SERVING_SPAN_ROOT = os.path.join("analytics_zoo_tpu", "serving")
SPAN_CALL_RE = re.compile(
    r"\.\s*add_span\s*\(\s*(?:\n\s*)?['\"]([^'\"]+)['\"]", re.MULTILINE)


def iter_sources(roots) -> List[str]:
    self_path = os.path.abspath(__file__)
    out = []
    for root in roots:
        if os.path.isfile(root):
            out.append(root)
            continue
        for dirpath, _dirs, files in os.walk(root):
            out.extend(os.path.join(dirpath, f)
                       for f in files if f.endswith(".py")
                       # this linter's own docstrings hold deliberate
                       # bad examples
                       and os.path.abspath(os.path.join(dirpath, f))
                       != self_path)
    return sorted(out)


def find_registrations(path: str) -> List[Tuple[str, str, int]]:
    """(kind, name, line) for every literal registration in one file."""
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    out = []
    for m in CALL_RE.finditer(src):
        line = src.count("\n", 0, m.start()) + 1
        out.append((m.group(1), m.group(2), line))
    return out


def check(roots=DEFAULT_ROOTS) -> List[str]:
    errors: List[str] = []
    seen: Dict[str, Tuple[str, str, int]] = {}   # name -> (kind, file, ln)
    for path in iter_sources(roots):
        for kind, name, line in find_registrations(path):
            where = f"{path}:{line}"
            if not NAME_RE.match(name):
                errors.append(
                    f"{where}: {kind} {name!r} is not snake_case")
            if kind == "counter" and not name.endswith(COUNTER_SUFFIX):
                errors.append(
                    f"{where}: counter {name!r} must end with '_total'")
            if kind == "histogram" and not name.endswith(HIST_SUFFIXES):
                errors.append(
                    f"{where}: histogram {name!r} must end with a unit "
                    f"suffix ({', '.join(HIST_SUFFIXES)})")
            if kind == "gauge" and name.endswith(COUNTER_SUFFIX):
                errors.append(
                    f"{where}: gauge {name!r} must not end with '_total' "
                    "(that suffix claims a monotonic counter)")
            prev = seen.get(name)
            if prev is not None and prev[0] != kind:
                errors.append(
                    f"{where}: {name!r} registered as {kind} but already "
                    f"a {prev[0]} at {prev[1]}:{prev[2]}")
            else:
                seen.setdefault(name, (kind, path, line))
    # required-coverage pass only when linting the real tree (unit tests
    # lint synthetic snippets in tmp dirs)
    if tuple(roots) == DEFAULT_ROOTS:
        for name, kind in sorted(REQUIRED.items()):
            got = seen.get(name)
            if got is None:
                errors.append(
                    f"required metric {name!r} ({kind}) is not registered "
                    "anywhere in the codebase")
            elif got[0] != kind:
                errors.append(
                    f"required metric {name!r} must be a {kind}, found "
                    f"{got[0]} at {got[1]}:{got[2]}")
        errors.extend(check_docs())
        errors.extend(check_spans())
    return errors


def _call_window(src: str, start: int, limit: int = 4000) -> str:
    """The balanced-paren argument window of the call starting at
    `start` (bounded: lint, not a parser)."""
    i = src.index("(", start)
    depth = 0
    for j in range(i, min(len(src), i + limit)):
        ch = src[j]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return src[i:j + 1]
    return src[i:i + limit]


def check_spans(root: str = SERVING_SPAN_ROOT) -> List[str]:
    """Span-name lint (ISSUE 17): every literal `add_span("name", ...)`
    in the serving package must use the stage vocabulary, and request
    spans must propagate a trace_id/trace_ids — otherwise the span can
    never join a request's merged cross-process timeline."""
    errors: List[str] = []
    vocab = REQUEST_SPANS | LIFECYCLE_SPANS
    for path in iter_sources([root]):
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        for m in SPAN_CALL_RE.finditer(src):
            name = m.group(1)
            line = src.count("\n", 0, m.start()) + 1
            where = f"{path}:{line}"
            if name not in vocab:
                errors.append(
                    f"{where}: span {name!r} is not in the serving "
                    f"stage vocabulary ({', '.join(sorted(vocab))}) — "
                    "the trace assembler's critical-path columns key on "
                    "these names")
            elif name in REQUEST_SPANS:
                window = _call_window(src, m.start())
                if "trace_id" not in window:   # matches trace_ids too
                    errors.append(
                        f"{where}: request span {name!r} carries no "
                        "trace_id/trace_ids — it can never join a "
                        "request's merged timeline")
    return errors


def check_docs(doc_path: str = OBSERVABILITY_DOC,
               required=None) -> List[str]:
    """Docs-drift pass: every REQUIRED family must be mentioned in the
    observability guide. The match is a plain substring — a table row, a
    prose mention, or a code block all count; what cannot happen is a
    load-bearing family shipping with no documentation at all."""
    required = REQUIRED if required is None else required
    if not os.path.exists(doc_path):
        return [f"{doc_path}: observability guide missing — required "
                "metric families have nowhere to be documented"]
    with open(doc_path, encoding="utf-8") as fh:
        text = fh.read()
    return [f"{doc_path}: required metric {name!r} is not documented "
            "(docs drift — add it to the guide's tables)"
            for name in sorted(required) if name not in text]


def main(argv=None) -> int:
    roots = (argv if argv else None) or list(DEFAULT_ROOTS)
    errors = check(roots)
    for e in errors:
        print(e)
    if errors:
        print(f"{len(errors)} metric-name violation(s)")
        return 1
    n = sum(len(find_registrations(p)) for p in iter_sources(roots))
    print(f"metric names OK ({n} registrations checked)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
