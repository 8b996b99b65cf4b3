"""Roofline accounting from XLA cost analysis (ISSUE 6 tentpole, part 1).

PR 2 gave latencies and counts; this module answers *hardware
utilization* for the serving path: how many FLOPs and HBM bytes did each
executable move per second, against what the chip can do. The FLOP/byte
counts come from XLA itself — `compiled.cost_analysis()` on the
executables the serving warmup and the AOT compile cache already hold.
A fit's utilization is not estimated here: it is the benchmark's
`fit_mfu` (docs/ProgrammingGuide/observability.md "Utilization of a fit").

Two layers:

- `cost_of(stages_obj)` — harvest `{flops, bytes}` from a
  `jax.stages.Compiled` or `Lowered` (the two agree on this backend; a
  deserialized AOT executable works too). Returns None when the backend
  exposes no cost model — every caller degrades to "no roofline gauges",
  never an error.
- `RooflineAccountant` — per-`kind` accumulation of (flops, bytes,
  busy-seconds) publishing both cumulative counters and live derived
  gauges: achieved TFLOP/s, achieved HBM GB/s, MFU and HBM utilization
  against the nameplate peaks in `utils/roofline.py`. On a device that
  has none (the CPU backend) the utilization gauges are not published.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Optional, Tuple

from analytics_zoo_tpu.utils.roofline import UnknownDeviceError

log = logging.getLogger("analytics_zoo_tpu.observability")


class ExecCost:
    """FLOPs and HBM bytes one call of an executable performs, per XLA's
    own cost analysis.

    Basis contract: an ExecCost is the LOGICAL GLOBAL cost of one call
    — the model's work counted once, however many devices execute it.
    XLA reports two different bases depending on what you ask:
    `Lowered.cost_analysis()` runs on the UNPARTITIONED module (the
    logical basis), while `Compiled.cost_analysis()` on a
    GSPMD-partitioned executable runs on the per-device module — and
    per-device × span is NOT the logical cost, because work that
    replicates across a mesh axis (e.g. the optimizer update across
    the data axis of a data×fsdp mesh) is counted once per device
    (measured factors 2–8× on an 8-device mesh depending on the
    program). Classic MFU divides MODEL flops by peak, so harvesters
    use the lowered module for any multi-device program (one trace per
    signature, no compile) and executables only where the two agree
    (single-device), then pass `account(..., n_devices=span)` so the
    denominator covers the devices that did the work."""

    __slots__ = ("flops", "bytes")

    def __init__(self, flops: float, bytes_: float):
        self.flops = float(flops)
        self.bytes = float(bytes_)

    def __repr__(self):
        return f"ExecCost(flops={self.flops:g}, bytes={self.bytes:g})"


def cost_of(stages_obj, span: int = 1) -> Optional[ExecCost]:
    """Harvest per-call FLOPs / bytes-accessed from a `jax.stages`
    Compiled or Lowered object. None — never a raise —
    when the backend has no cost model or the numbers are empty: the
    roofline layer is telemetry, and telemetry must not take down the
    path it measures.

    `Lowered.cost_analysis()` is the logical (unpartitioned) basis the
    `ExecCost` contract wants, but only some backends answer it: the CPU
    does, the TPU backend of this jax (0.9.0 / libtpu 0.0.34) returns
    None and costs compiled programs only. For a single-device program
    (`span == 1`) the two bases agree, so a Lowered that gets no answer
    is compiled and asked again — a persistent-cache hit, since callers
    harvest right after the jit call that compiled the same module. A
    partitioned program (`span > 1`) has no logical cost on such a
    backend: None, with a WARNING (callers memoize per signature, so it
    is a handful per process) so the missing gauges are not a mystery.

    Caveat: XLA's HLO cost analysis counts a While-loop body ONCE, not
    times its trip count — a `lax.scan`/`fori_loop` program reports one
    iteration's cost: a model whose FORWARD hides work inside a loop will
    have its serving cost understated by the trip count."""
    if stages_obj is None:
        return None
    try:
        ca = stages_obj.cost_analysis()
        if ca is None and hasattr(stages_obj, "compile"):
            if span > 1:
                log.warning(
                    "this backend costs compiled programs only, and a "
                    "partitioned executable's cost is per device, not the "
                    "model's: no roofline gauges for this program "
                    "spanning %d devices", span)
                return None
            ca = stages_obj.compile().cost_analysis()
        if not isinstance(ca, dict):
            return None
        flops = float(ca.get("flops") or 0.0)
        bytes_ = float(ca.get("bytes accessed") or 0.0)
    except Exception as e:  # noqa: BLE001 — experimental backends throw
        log.debug("cost_analysis unavailable: %s: %s", type(e).__name__, e)
        return None
    if flops <= 0.0 and bytes_ <= 0.0:
        return None
    return ExecCost(flops, bytes_)


def _nameplate(device=None) -> Tuple[float, float]:
    """(HBM bytes/s, FLOP/s) peaks of `device` (default: device 0) from
    `utils/roofline.py`, the program's one table of peaks. Raises
    `UnknownDeviceError` for a device the table does not list (the CPU
    backend): there is no roofline to measure against."""
    from analytics_zoo_tpu.utils.roofline import peak_flops, peak_hbm
    if device is None:
        import jax
        device = jax.devices()[0]
    return peak_hbm(device), peak_flops(device)


# ---------------------------------------------------------------------------
# The accountant
# ---------------------------------------------------------------------------
class RooflineAccountant:
    """Per-kind (flops, bytes, busy-seconds) accumulation → registry.

    `account(kind, flops, bytes, seconds)` is the single entry point:
    the serving predict path calls it per materialized batch (with the
    batch's measured dispatch+materialize seconds). Counters accumulate
    forever (the Prometheus model); the derived gauges are computed from
    THIS call's window — the latest batch — so a compile-laden first
    window depresses only its own reading (cumulative-since-reset rates
    would stay diluted for the whole run). `snapshot(kind)` still
    reports the accumulation since the last `reset(kind)` — a model
    reload resets its kind so the bench-facing averages describe the
    CURRENT program.

    Never raises out of `account` — one bad division must not take down
    a dispatch path."""

    def __init__(self, registry=None):
        from analytics_zoo_tpu.observability.registry import get_registry
        self._registry = registry if registry is not None else get_registry()
        self._lock = threading.Lock()
        # kind -> [flops, bytes, seconds, devices] since last reset(kind)
        self._acc: Dict[str, list] = {}

    # registration is get-or-create and therefore safe to repeat per
    # call: it also heals after a test's registry.clear()
    def _reg(self):
        reg = self._registry
        return (
            reg.counter("roofline_flops_total",
                        "FLOPs executed, per XLA cost analysis, by kind"),
            reg.counter("roofline_hbm_bytes_total",
                        "HBM bytes accessed, per XLA cost analysis, by "
                        "kind"),
            reg.counter("roofline_busy_seconds_total",
                        "measured busy wall seconds the flops/bytes "
                        "counters were accumulated over, by kind"),
            reg.gauge("roofline_achieved_tflops",
                      "achieved TFLOP/s since the kind's last reset "
                      "(cost-analysis FLOPs / measured seconds)"),
            reg.gauge("roofline_achieved_hbm_gbps",
                      "achieved HBM GB/s since the kind's last reset"),
            reg.gauge("roofline_mfu",
                      "achieved FLOP/s over the device's nameplate peak "
                      "(cost-analysis MFU)"),
            reg.gauge("roofline_hbm_utilization",
                      "achieved HBM bytes/s over the device's nameplate "
                      "HBM bandwidth"),
        )

    def account(self, kind: str, flops: float, bytes_: float,
                seconds: float, device=None, n_devices: int = 1) -> None:
        """`flops`/`bytes_` are GLOBAL (see ExecCost); `n_devices` is
        how many devices the program spanned, scaling the MFU/HBM
        denominators to the roofline of the participating slice —
        per-chip peaks × n. The achieved_* gauges stay global
        (what the whole mesh delivered)."""
        try:
            if seconds <= 0.0 or (flops <= 0.0 and bytes_ <= 0.0):
                return
            with self._lock:
                acc = self._acc.setdefault(kind, [0.0, 0.0, 0.0, 1])
                acc[0] += flops
                acc[1] += bytes_
                acc[2] += seconds
                acc[3] = max(acc[3], max(1, int(n_devices)))
            (c_flops, c_bytes, c_secs, g_tflops, g_gbps, g_mfu,
             g_hbm) = self._reg()
            c_flops.inc(flops, kind=kind)
            c_bytes.inc(bytes_, kind=kind)
            c_secs.inc(seconds, kind=kind)
            # gauges from THIS window: the latest batch's rate
            g_tflops.set(flops / seconds / 1e12, kind=kind)
            g_gbps.set(bytes_ / seconds / 1e9, kind=kind)
            try:
                hbm_roof, flops_roof = _nameplate(device)
            except UnknownDeviceError:
                # no roofline for this device: the achieved rates above
                # stand, the utilization gauges stay unpublished
                return
            n = max(1, int(n_devices))
            if flops_roof > 0:
                g_mfu.set(flops / seconds / (flops_roof * n), kind=kind)
            if hbm_roof > 0:
                g_hbm.set(bytes_ / seconds / (hbm_roof * n), kind=kind)
        except Exception as e:  # noqa: BLE001 — telemetry must not raise
            log.debug("roofline accounting failed: %s: %s",
                      type(e).__name__, e)

    def reset(self, kind: Optional[str] = None) -> None:
        """Zero the rate accumulators (counters keep accumulating): a
        reloaded serving model starts its gauges clean."""
        with self._lock:
            if kind is None:
                self._acc.clear()
            else:
                self._acc.pop(kind, None)

    def snapshot(self, kind: str) -> Dict[str, float]:
        """The kind's accumulators since its last reset (bench JSON).
        `devices` is the largest program span accounted in the window;
        mfu/hbm_utilization divide by that many chips' roofline, like
        the live gauges."""
        with self._lock:
            f, b, s, n = self._acc.get(kind, (0.0, 0.0, 0.0, 1))
        out: Dict[str, Any] = {"flops": f, "bytes": b, "seconds": s,
                               "devices": n}
        if s > 0:
            out["achieved_tflops"] = f / s / 1e12
            out["achieved_hbm_gbps"] = b / s / 1e9
            try:
                hbm_roof, flops_roof = _nameplate()
            except UnknownDeviceError:
                pass        # no roofline: no mfu/hbm_utilization keys
            else:
                out["mfu"] = f / s / (flops_roof * n)
                out["hbm_utilization"] = b / s / (hbm_roof * n)
        return out


_default_accountant: Optional[RooflineAccountant] = None
_default_lock = threading.Lock()


def get_accountant() -> RooflineAccountant:
    """The process-wide accountant on the default registry, like
    `get_registry()`."""
    global _default_accountant
    with _default_lock:
        if _default_accountant is None:
            _default_accountant = RooflineAccountant()
        return _default_accountant
