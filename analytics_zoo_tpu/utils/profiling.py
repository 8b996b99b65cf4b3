"""Profiling & timing utilities (SURVEY §2.12/§5 tracing).

The reference has no general tracer — only `Supportive.timing` span logs
(`serving/utils/Supportive.scala`, `InferenceSupportive.timing`) and serving
`Timer` windows. The TPU build supplies both and adds what the reference
lacks: real device profiling via the jax profiler (xprof traces viewable in
TensorBoard/Perfetto) and step-level throughput/MFU accounting."""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Dict, Iterator, Optional

import jax

log = logging.getLogger("analytics_zoo_tpu.profiling")


@contextlib.contextmanager
def timing(name: str, logger: Optional[logging.Logger] = None
           ) -> Iterator[None]:
    """`Supportive.timing` span: logs `name time [s]` at INFO."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        (logger or log).info("%s time %.4fs", name,
                             time.perf_counter() - t0)


@contextlib.contextmanager
def device_trace(log_dir: str) -> Iterator[None]:
    """jax profiler trace (xprof): open in TensorBoard's profile plugin or
    Perfetto. Wrap a few training steps, not a whole run."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Per-step wall-clock + throughput accounting; the `Throughput` scalar
    the reference writes to its train summary (`Topology.scala:224`)."""

    def __init__(self, flops_per_step: Optional[float] = None,
                 peak_flops: Optional[float] = None):
        self.flops_per_step = flops_per_step
        self.peak_flops = peak_flops
        self.steps = 0
        self.total_s = 0.0
        self._t0: Optional[float] = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.total_s += time.perf_counter() - self._t0
        self.steps += 1
        return False

    @property
    def step_ms(self) -> float:
        return self.total_s / max(self.steps, 1) * 1e3

    def samples_per_sec(self, batch_size: int) -> float:
        return batch_size * self.steps / max(self.total_s, 1e-9)

    @property
    def mfu(self) -> Optional[float]:
        if not (self.flops_per_step and self.peak_flops and self.total_s):
            return None
        return (self.flops_per_step * self.steps / self.total_s
                / self.peak_flops)

    def summary(self, batch_size: Optional[int] = None) -> Dict[str, float]:
        out = {"steps": self.steps, "step_ms": round(self.step_ms, 3)}
        if batch_size:
            out["samples_per_sec"] = round(self.samples_per_sec(batch_size),
                                           1)
        if self.mfu is not None:
            out["mfu"] = round(self.mfu, 4)
        return out

    def publish(self, registry=None, batch_size: Optional[int] = None):
        """Push this timer's accounting into the metrics registry, under
        the SAME family names the trainer loop uses
        (`training_step_ms`/`training_samples_per_sec`/`training_mfu`) —
        hand-rolled loops built on StepTimer land on the unified spine
        without their own naming. Safe to call repeatedly: the step
        counter only advances by steps recorded since the last publish."""
        from analytics_zoo_tpu.observability import get_registry
        reg = registry if registry is not None else get_registry()
        published = getattr(self, "_published_steps", 0)
        if self.steps > published:
            # one observation per publish WINDOW (the average step time
            # of the steps recorded since the last publish) — repeated
            # per-step publish() calls then histogram the step-time
            # distribution instead of re-observing a running mean
            pub_total = getattr(self, "_published_total_s", 0.0)
            window_ms = ((self.total_s - pub_total)
                         / (self.steps - published) * 1e3)
            reg.histogram(
                "training_step_ms",
                "per-step wall time, averaged over each epoch's device "
                "sync").observe(window_ms)
            reg.counter("training_steps_total",
                        "optimizer steps run").inc(self.steps - published)
            self._published_steps = self.steps
            self._published_total_s = self.total_s
        if batch_size:
            reg.gauge("training_samples_per_sec",
                      "last epoch's training throughput").set(
                self.samples_per_sec(batch_size))
        if self.mfu is not None:
            reg.gauge(
                "training_mfu",
                "model FLOPs utilization vs per-chip peak (needs "
                "flops_per_step)").set(self.mfu)
        return self


def transformer_train_flops(n_params_matmul: int, tokens: int,
                            n_layers: int, seq_len: int,
                            hidden: int, batch: int) -> float:
    """Standard fwd+bwd FLOPs estimate: 6 per matmul-param per token plus
    attention score/context terms."""
    return (6.0 * n_params_matmul * tokens
            + 12.0 * n_layers * seq_len ** 2 * hidden * batch)
