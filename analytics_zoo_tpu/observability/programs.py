"""How the process got its executables: the histogram
`xla_program_obtain_ms{how, during}` and the span `fit.obtain_program`.

A jit that finds nothing in memory asks for an executable, and JAX
(0.9.0, `pxla` around `compiler.compile_or_get_cached`) brackets that
request with the event `/jax/core/compile/backend_compile_duration`: a
scalar event (the start time) as the request begins and a duration event
as it ends, both on the requesting thread, both with `fun_name`. Inside
the bracket a hit in the persistent compilation cache records the
duration `/jax/compilation_cache/cache_retrieval_time_sec` before the
bracket closes; a miss, or a process without the cache, records none and
runs the backend's compiler. So the pairing is: a bracket on whose
thread a retrieval was recorded since it opened is `how="cache_load"`,
every other `how="compile"`. The observed milliseconds are the bracket's
own (for a load: reading and deserialising; for a compile: the compiler
and writing the cache entry).

`during` is `fit` where the requesting thread's open spans of the
process-wide tracer stand under a `fit` root (`learn/trainer.py`'s
`_FitTrace`: the fit's own programs, its evaluation, the optimizer's
init), else `other` (a reference program, serving's warm-up, a step that
`step_timeout_s` runs on its watchdog's thread). Under a fit the bracket
is also the span `fit.obtain_program` (args `program`, `how`), a child of
the `fit.dispatch`, `fit.build_step` or other span that asked: seconds of
a first dispatch that are a compile or a load say so in the ring and in
a profiler capture.

`install()` registers the two listeners once a process; the package's
import does it. A program that obtains nothing (the steady state: every
measured window of the benchmark) never reaches them.
"""

from __future__ import annotations

import threading

from analytics_zoo_tpu.observability.registry import get_registry
from analytics_zoo_tpu.observability.tracing import get_tracer

OBTAIN_EVENT = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
FAMILY = "xla_program_obtain_ms"
SPAN = "fit.obtain_program"

_local = threading.local()      # .open: this thread's brackets, innermost last
_install_lock = threading.Lock()
_installed = False


def _histogram():
    # by name every time: a registry that was cleared gets the family anew
    return get_registry().histogram(
        FAMILY,
        "wall time of each request for an XLA executable that no jit had "
        "in memory: how = compile (the backend's compiler ran) or "
        "cache_load (taken from the persistent compilation cache); "
        "during = fit (asked under a fit call's spans) or other")


def _brackets() -> list:
    if not hasattr(_local, "open"):
        _local.open = []
    return _local.open


def _on_scalar(event, value, **kwargs):
    if event != OBTAIN_EVENT:
        return
    tracer = get_tracer()
    root = tracer.open_root()
    span = None
    if root is not None and root.name == "fit" and root.cat == "training":
        span = tracer.span(SPAN, cat="training",
                           args={"program": str(kwargs.get("fun_name", ""))})
        span.__enter__()
    _brackets().append({"span": span, "loaded": False})


def _on_duration(event, duration, **kwargs):
    if event == RETRIEVAL_EVENT:
        for bracket in _brackets()[-1:]:
            bracket["loaded"] = True
        return
    if event != OBTAIN_EVENT:
        return
    open_ = _brackets()
    bracket = open_.pop() if open_ else {"span": None, "loaded": False}
    how = "cache_load" if bracket["loaded"] else "compile"
    span = bracket["span"]
    if span is not None:
        span.args["how"] = how
        span.__exit__(None, None, None)
    _histogram().observe(duration * 1e3, how=how,
                         during="fit" if span is not None else "other")


def install() -> bool:
    """Register the listeners (once a process). False where jax is not
    there to be listened to."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        try:
            from jax import monitoring
        except ImportError:
            return False
        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _histogram()
        _installed = True
        return True
