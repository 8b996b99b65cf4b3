"""Cache-key anatomy: content fingerprints for executables.

An entry must be reusable exactly when recompiling would produce the
same program, and MUST miss when anything that feeds the compiler
changed. The key therefore folds in:

- the jax/jaxlib versions (serialized executables are not stable across
  releases) and the backend platform + device kind + device count
- a model fingerprint: the forward fn's bytecode (constants and closure
  cells included, recursively) plus the params tree structure and every
  leaf's shape/dtype — weight VALUES are runtime inputs and excluded
- the input signature: tree structure + per-leaf shape/dtype of the
  (bucket-padded) batch — so every bucket is its own entry and a dtype
  change invalidates
- the placement mode and, for sharded placement, the mesh axis layout —
  a GSPMD program for an 8-way mesh must never load into a 4-way one

The key is canonical JSON; its sha256 names the entry file. Fingerprints
are heuristic by design (two genuinely different models hashing equal is
made vanishingly unlikely by the bytecode + structure walk), and a false
MISS only costs a recompile.
"""

from __future__ import annotations

import hashlib
import json
import types
from dataclasses import dataclass, field
from typing import Any, Dict, Tuple

# Bump to invalidate every persisted executable when layout SEMANTICS
# change: v2 = ISSUE 7's ShardingRules.spec_for fsdp fallback for
# matched-but-untrimmable rules (the same table now resolves different
# placements on data×fsdp meshes, and a stale sharded executable would
# reject — or silently reshard — its inputs). v3 = the payload carries
# the compile-time device ids (`serialization.pack`).
FORMAT_VERSION = 3

_MAX_DEPTH = 5
_MAX_ITEMS = 64


def _h(parts) -> str:
    return hashlib.sha256("\x1f".join(parts).encode()).hexdigest()[:16]


def fingerprint(obj: Any, depth: int = 0) -> str:
    """Stable-across-processes content fingerprint of a python object:
    functions hash by bytecode + consts + closure cells; arrays by
    shape/dtype (values are runtime inputs); layer-bearing objects by a
    structural walk of their scalar attributes. Bounded depth/width so a
    pathological object can't stall key construction."""
    if depth > _MAX_DEPTH:
        return "deep"
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return repr(obj)
    # modules and classes identify by NAME, never by attribute walk: a
    # function closing over `import jax` would otherwise deep-walk the
    # whole package namespace (and trip over class-level `shape`/`dtype`
    # PROPERTIES masquerading as array attrs — jax.Array did exactly
    # that once the fused optimizer's update closed over the module)
    if isinstance(obj, types.ModuleType):
        return _h(["module", obj.__name__,
                   str(getattr(obj, "__version__", ""))])
    if isinstance(obj, type):
        return _h(["type", obj.__module__, obj.__qualname__])
    # bound methods: underlying function + owner structure
    owner = getattr(obj, "__self__", None)
    func = getattr(obj, "__func__", None)
    if owner is not None and func is not None:
        return _h(["method", fingerprint(func, depth + 1),
                   fingerprint(owner, depth + 1)])
    code = getattr(obj, "__code__", None)
    if code is not None:
        parts = ["fn", getattr(obj, "__qualname__", "?"),
                 hashlib.sha256(code.co_code).hexdigest()[:16],
                 repr(code.co_names)]
        for c in code.co_consts[:_MAX_ITEMS]:
            parts.append(fingerprint(c, depth + 1))
        for cell in (obj.__closure__ or ())[:_MAX_ITEMS]:
            try:
                parts.append(fingerprint(cell.cell_contents, depth + 1))
            except ValueError:      # empty cell
                parts.append("empty")
        return _h(parts)
    shape = getattr(obj, "shape", None)
    dtype = getattr(obj, "dtype", None)
    if shape is not None and dtype is not None:
        return f"arr{tuple(shape)}:{dtype}"
    if isinstance(obj, (list, tuple)):
        return _h([type(obj).__name__]
                  + [fingerprint(v, depth + 1) for v in obj[:_MAX_ITEMS]])
    if isinstance(obj, dict):
        items = sorted(obj.items(), key=lambda kv: str(kv[0]))[:_MAX_ITEMS]
        return _h(["dict"] + [f"{k}={fingerprint(v, depth + 1)}"
                              for k, v in items])
    layers = getattr(obj, "layers", None)
    if layers is not None:
        return _h([type(obj).__name__]
                  + [fingerprint(l, depth + 1) for l in layers[:_MAX_ITEMS]])
    # generic object: type + scalar attrs (hyperparameters like
    # strides and units live here) + CALLABLE attrs (a Dense stores
    # its activation as a jax function — two models differing only in
    # relu-vs-tanh must never share a key). Auto-generated `name`
    # attrs ("dense_3") are EXCLUDED: the numbering counter is
    # process-global, so the same model built after any other model
    # would fingerprint differently — identity comes from the layer
    # list order and the canonical structure signature instead.
    try:
        items = sorted(vars(obj).items())
    except TypeError:
        items = []
    parts = [type(obj).__name__]
    n = 0
    for k, v in items:
        if k == "name" or n >= _MAX_ITEMS:
            continue
        if isinstance(v, (bool, int, float, str, tuple)):
            parts.append(f"{k}={v!r}")
            n += 1
        elif callable(v):
            parts.append(f"{k}={fingerprint(v, depth + 1)}")
            n += 1
    return _h(parts)


_AUTONUM_RE = None


def structure_signature(tree: Any) -> str:
    """Canonical structure string of a pytree: container shapes, dict
    keys, and per-leaf shape/dtype — with auto-numbered layer keys
    ("dense_3") rewritten to build-order ordinals ("dense#0"). The
    layer-naming counter is process-global, so the same model built at
    a different point in a process (or under a different import order)
    carries different raw names; raw treedef strings would invalidate
    the whole cache on a mere counter offset.

    Soundness contract: two trees with EQUAL signatures flatten to
    corresponding leaf sequences under jax's dict ordering. Ordinals
    are assigned in dict-insertion (build) order, but children are
    EMITTED in sorted-raw-key order — exactly jax's flatten order. If
    a counter offset reorders the sorted sequence relative to build
    order (the "dense_9"/"dense_10" lexicographic flip), the emitted
    ordinal sequences differ, the signatures differ, and the lookup
    safely misses instead of positionally mis-mapping same-shaped
    layers."""
    global _AUTONUM_RE
    if _AUTONUM_RE is None:
        import re
        _AUTONUM_RE = re.compile(r"^(.+?)_(\d+)$")
    counters: Dict[str, int] = {}

    def canon(k) -> str:
        m = _AUTONUM_RE.match(str(k))
        base = m.group(1) if m else str(k)
        i = counters.get(base, 0)
        counters[base] = i + 1
        return f"{base}#{i}"

    def walk(t) -> str:
        if isinstance(t, dict):
            # ordinals in insertion (build) order ...
            labels = {k: canon(k) for k in t}
            # ... emission in sorted raw-key order (jax flatten order)
            return "{" + ",".join(f"{labels[k]}:{walk(t[k])}"
                                  for k in sorted(t, key=str)) + "}"
        if isinstance(t, (list, tuple)):
            return (type(t).__name__ + "["
                    + ",".join(walk(v) for v in t) + "]")
        if t is None:
            return "~"
        shape = getattr(t, "shape", None)
        dtype = getattr(t, "dtype", None)
        if shape is not None:
            return f"{tuple(shape)}:{dtype}"
        return type(t).__name__

    return walk(tree)


def model_fingerprint(fn: Any, params: Any) -> str:
    """Fingerprint of (forward fn, params STRUCTURE): what must match
    for a serialized forward executable to be the right program."""
    return _h([fingerprint(fn), structure_signature(params)])


def abstract_signature(tree: Any) -> Tuple[str, Tuple]:
    """(canonical structure str, ((shape, dtype), ...)) of a pytree of
    arrays — the per-call part of the key (and the in-process
    executable-table key)."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    return (structure_signature(tree),
            tuple((tuple(getattr(l, "shape", ())),
                   str(getattr(l, "dtype", type(l).__name__)))
                  for l in leaves))


def cheap_signature(tree: Any) -> Tuple:
    """Per-leaf (shape, dtype-name) tuple — the hot-path dispatch key
    shared by `AOTFunctionCache` and `InferenceModel`'s roofline cost
    table. Discriminating only when the tree STRUCTURE is fixed per
    consumer (one wrapper per model); pay `abstract_signature` when
    structure can vary. One implementation so the two consumers can
    never drift on dtype spelling."""
    import jax
    return tuple(
        (tuple(l.shape), l.dtype.name) if hasattr(l, "shape")
        else (type(l).__name__,)
        for l in jax.tree_util.tree_leaves(tree))


@dataclass
class CacheKey:
    """Canonical key: `fields` is the human-readable anatomy (stored in
    the entry header so `compile_cache_tool.py ls` can explain an
    entry); `digest` names the entry file."""

    fields: Dict[str, Any] = field(default_factory=dict)

    @property
    def digest(self) -> str:
        blob = json.dumps(self.fields, sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:40]


def make_key(kind: str, model_fp: str, signature, placement: str = "none",
             sharding: str = "", extra: Any = None,
             dtype: str = "") -> CacheKey:
    """Build the full cache key. `kind` separates serving forwards from
    trainer steps; `signature` is `abstract_signature(...)` of the call
    args; `sharding` describes the mesh layout for sharded placement;
    `dtype` names a non-default serving precision ("int8") so a
    quantize toggle is a guaranteed miss — empty ("", the f32 default)
    adds NO field, keeping pre-existing keys byte-identical."""
    import jax
    try:
        backend = jax.default_backend()
        dev = jax.devices(backend)[0]
        device_kind = getattr(dev, "device_kind", str(dev))
        n_devices = jax.device_count(backend)
    except Exception:  # noqa: BLE001 — key building must not crash
        backend, device_kind, n_devices = "unknown", "unknown", 0
    fields = {
        "format": FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": getattr(__import__("jaxlib"), "__version__", "?"),
        "backend": backend,
        "device_kind": device_kind,
        "n_devices": n_devices,
        "kind": kind,
        "model": model_fp,
        "signature": _sig_fields(signature),
        "placement": placement,
        "sharding": sharding,
    }
    if dtype:
        fields["dtype"] = dtype
    if extra is not None:
        fields["extra"] = fingerprint(extra)
    return CacheKey(fields)


def _sig_fields(signature):
    treedef, leaves = signature
    return {"tree": treedef,
            "leaves": [[list(shape), dtype] for shape, dtype in leaves]}
