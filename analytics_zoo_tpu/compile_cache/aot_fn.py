"""AOT cache wrapper for jitted trainer steps.

`build_train_step` / `build_train_run` hand back `jax.jit` callables
whose shapes are only known at the first batch. This wrapper sits in
front of one: per distinct argument signature it loads a persisted
executable (or lowers + compiles + persists once), then dispatches
every later call straight to the AOT executable — a trainer re-run
pays zero XLA compiles for shapes it has seen in any previous process.

Anything that defeats AOT serialization — an unserializable program, a
signature that fails to lower, an executable rejecting its inputs —
permanently falls back to the wrapped jit callable for that signature,
where JAX's built-in persistent compilation cache (see
`enable_jax_persistent_cache`) still amortizes the XLA compile.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, Set, Tuple

from analytics_zoo_tpu.compile_cache import serialization
from analytics_zoo_tpu.compile_cache.key import (abstract_signature,
                                                 cheap_signature, make_key)

log = logging.getLogger("analytics_zoo_tpu.compile_cache")


class AOTFunctionCache:
    """Wrap a jitted fn with per-signature AOT executable caching.

    NOT thread-safe for concurrent first-calls of the same signature
    (the training loop is single-dispatcher); steady-state calls are a
    dict hit + the executable call."""

    def __init__(self, jit_fn: Callable, cache, model_fp: str,
                 kind: str = "train", sharding: str = ""):
        self._jit = jit_fn
        self._cache = cache
        self._model_fp = model_fp
        self._kind = kind
        # mesh-axis + rule-table descriptor for GSPMD-sharded steps: the
        # argument SHAPES of a replicated and an fsdp-sharded step can
        # coincide exactly, so the disk key must carry the layout too
        self._sharding = sharding
        self._execs: Dict[Tuple, Any] = {}    # cheap sig -> executable
        self._failed: Set[Tuple] = set()
        self.sources: Dict[Tuple, str] = {}   # sig -> cached|compiled|jit

    @staticmethod
    def _cheap_sig(args) -> Tuple:
        """Steady-state dispatch key: per-leaf shape/dtype only (the
        shared `key.cheap_signature`). The full canonical
        `abstract_signature` (structure walk + per-key regex) runs ONCE
        per new shape in `_build`; paying it per training step would
        tax exactly the hot loop this cache exists to speed up. Leaf
        shapes are discriminating here because one wrapper serves one
        fixed (model, optimizer) — arg STRUCTURE can't change under it,
        only batch shapes."""
        return cheap_signature(args)

    def __call__(self, *args):
        csig = self._cheap_sig(args)
        ex = self._execs.get(csig)
        if ex is None and csig not in self._failed:
            ex = self._build(csig, args)
        if ex is None:
            return self._jit(*args)
        try:
            return ex(*args)
        except Exception as e:  # noqa: BLE001 — e.g. an input landed
            # with a sharding the persisted program wasn't built for;
            # the check fires BEFORE execution (no donation consumed),
            # so the jit retry sees intact buffers
            log.warning("AOT executable rejected a call (%s: %s); "
                        "falling back to jit for this signature",
                        type(e).__name__, e)
            self._execs.pop(csig, None)
            self._failed.add(csig)
            self.sources[csig] = "jit"
            return self._jit(*args)

    def _build(self, csig, args):
        sig = abstract_signature(args)
        key = make_key(self._kind, self._model_fp, sig, placement="train",
                       sharding=self._sharding)
        try:
            ex = self._cache.load(key)
            if ex is not None and serialization.args_treedef(ex) \
                    != serialization.live_treedef(args):
                # a naming-counter offset between processes: the stored
                # tree's keys differ from the live params/opt_state. A
                # train step RETURNS those trees, so re-treeing would
                # hand the caller stale key names — fall back to jit
                # (jax's persistent cache still amortizes the compile)
                # and leave the entry for its original tree shape.
                log.info("AOT entry tree mismatch for this signature; "
                         "using jit")
                self._failed.add(csig)
                self.sources[csig] = "jit"
                return None
            if ex is not None:
                self.sources[csig] = "cached"
            else:
                t0 = time.perf_counter()
                ex = serialization.compile_lowered(self._jit.lower(*args))
                self._cache.put(
                    key, ex,
                    compile_ms=(time.perf_counter() - t0) * 1e3)
                self.sources[csig] = "compiled"
            self._execs[csig] = ex
            return ex
        except Exception as e:  # noqa: BLE001 — AOT unavailable for
            # this shape: the jit path (+ jax's own persistent cache)
            # owns it from here
            log.info("AOT caching unavailable for signature (%s: %s); "
                     "using jit", type(e).__name__, e)
            self._failed.add(csig)
            self.sources[csig] = "jit"
            return None

    def executables(self) -> Dict[Tuple, Any]:
        """Live AOT executables by cheap signature — the roofline layer
        harvests `cost_analysis()` from these (a deserialized executable
        still answers it), so a cache-hit re-run gets utilization gauges
        without ever lowering."""
        return dict(self._execs)

    # the trainer's step-cache memo compares wrapped identity
    @property
    def wrapped(self) -> Callable:
        return self._jit
