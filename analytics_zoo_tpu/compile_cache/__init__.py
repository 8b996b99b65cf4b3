"""Persistent compilation cache — AOT executable serialization (ISSUE 4).

Every process start used to pay the full XLA compilation bill:
`InferenceModel.warmup()` compiled every (replica, bucket) executable
from scratch and the trainer re-lowered its step/run programs on every
launch — minutes of cold-start per restart on real TPUs, paid again for
every replica of a rolling deploy. This package amortizes that bill to
near-zero the way the JAX persistent-cache line of work does, but one
level higher: whole `jax.stages.Compiled` executables, serialized via
`jax.experimental.serialize_executable`, keyed by a content fingerprint
and stored on disk.

- `CompileCache` (`store.py`) — the disk store: CRC-checked entries,
  atomic write-then-rename, LRU eviction under a byte budget, and
  hit/miss/load/compile telemetry in the process-wide registry. A
  corrupt, truncated, or format-mismatched entry is a miss, an intact
  one that fails to deserialize a counted load error — never an
  exception on the load path.
- `make_key` / fingerprints (`key.py`) — the cache key anatomy: jax
  version, backend + device kind/count, model fn + params structure,
  input signature (bucket shape + dtype), placement + sharding spec.
- `pack` / `unpack` (`serialization.py`) — executable bytes plus the
  devices they were compiled for, and the re-pinning that lets ONE
  persisted entry load onto each replica's device (persist once, load
  N times).
- `AOTFunctionCache` — wraps a jitted trainer step: per input signature
  it loads/compiles-and-persists an AOT executable, falling back to the
  plain jit call (backed by JAX's built-in persistent cache, see
  `enable_jax_persistent_cache`) for anything AOT can't serialize.
"""

from analytics_zoo_tpu.compile_cache.key import (CacheKey, abstract_signature,
                                                 fingerprint, make_key,
                                                 model_fingerprint,
                                                 structure_signature)
from analytics_zoo_tpu.compile_cache.serialization import (
    compile_lowered, pack, unpack)
from analytics_zoo_tpu.compile_cache.store import (
    CompileCache, default_xla_cache_dir, enable_jax_persistent_cache,
    get_cache)
from analytics_zoo_tpu.compile_cache.aot_fn import AOTFunctionCache

__all__ = [
    "AOTFunctionCache", "CacheKey", "CompileCache", "abstract_signature",
    "compile_lowered", "default_xla_cache_dir",
    "enable_jax_persistent_cache", "fingerprint",
    "get_cache", "make_key", "model_fingerprint", "pack",
    "structure_signature", "unpack",
]
