"""Checkpointing with the reference's on-disk naming contract.

Layout follows `InternalDistriOptimizer` + `tf_optimizer.py:398-413`:
    <ckptDir>/<yyyyMMdd_HHmmss>/model.<iteration>
    <ckptDir>/<yyyyMMdd_HHmmss>/optimMethod-<name>.<iteration>
`load_checkpoint(path, version)` selects by version number like
`load_orca_checkpoint` (`orca/learn/tf/estimator.py:125`); resume restores
optimizer state so epoch continuation matches `Topology.scala:379-394`.

Format: each file is a numpy .npz of the flattened pytree plus a JSON sidecar
of the tree structure — portable, no pickle of code objects.

Durability (ISSUE 5, mirroring the compile-cache store's discipline):
writes land in a same-directory temp file and `os.replace` into place,
so a crashed writer never leaves a half-written artifact under the
final name; the structure sidecar records the npz's CRC32C and is
written LAST, acting as the commit marker. `load_pytree` verifies the
CRC, and `latest_checkpoint` skips corrupt/truncated versions, falling
back to the newest intact one — a torn disk can cost a checkpoint, not
the run.
"""

from __future__ import annotations

import datetime
import json
import logging
import os
import re
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.utils.crc import crc32c

log = logging.getLogger("analytics_zoo_tpu.checkpoint")


class CorruptCheckpointError(RuntimeError):
    """A checkpoint artifact failed its integrity check (missing
    sidecar, truncated npz, CRC mismatch)."""


# ---------------------------------------------------------------------------
# Pytree <-> flat ndarray dict
# ---------------------------------------------------------------------------
def gather_leaf(a: Any) -> np.ndarray:
    """Host copy of one checkpoint leaf, correct for sharded
    `jax.Array`s (the GSPMD fit's params/opt_state):

    - fully replicated → read ONE addressable shard; a bare np.asarray
      would be correct too but this makes the single-fetch explicit;
    - sharded but fully addressable (single-process mesh) → one
      device_get assembles every shard exactly once (np.asarray funnels
      through jax's single-gather conversion — shards are not fetched
      per-element or twice);
    - not fully addressable (multi-process) → actionable error: saving
      would silently write this host's partial view.

    Everything else (numpy, scalars) converts as before."""
    try:
        import jax
        if isinstance(a, jax.Array):
            if a.is_fully_replicated:
                return np.asarray(a.addressable_data(0))
            if not a.is_fully_addressable:
                raise NotImplementedError(
                    "checkpointing a cross-host sharded array: this "
                    "process cannot address every shard; gather to "
                    "host (e.g. multihost_utils.process_allgather) "
                    "before saving")
    except ImportError:          # jax-less tooling reading numpy trees
        pass
    return np.asarray(a)


def gather_tree(tree: Any) -> Any:
    """`gather_leaf` over a pytree — the host view a checkpoint
    stores."""
    import jax
    return jax.tree_util.tree_map(gather_leaf, tree)


def _walk(tree: Any, path: List[List[Any]], paths: List[Any],
          leaves: List[np.ndarray]) -> None:
    """Record every node: leaves carry data; empty containers carry a marker
    so parameterless layers ({} in params) survive the roundtrip (jax's
    tree_flatten silently drops them)."""
    if isinstance(tree, dict):
        if not tree:
            paths.append({"path": path, "empty": "dict"})
            return
        for k in tree:  # preserve insertion order
            _walk(tree[k], path + [["k", k]], paths, leaves)
    elif isinstance(tree, (list, tuple)):
        if not tree:
            paths.append({"path": path, "empty": "list"})
            return
        for i, v in enumerate(tree):
            _walk(v, path + [["i", i]], paths, leaves)
    else:
        paths.append({"path": path, "leaf": len(leaves)})
        leaves.append(gather_leaf(tree))


def save_pytree(path: str, tree: Any) -> None:
    """Write a pytree to `<path>` (npz + structure json), atomically:
    both files go through write-temp-then-rename, the npz first and the
    CRC-bearing sidecar last (the commit marker) — a reader can never
    observe a committed-looking checkpoint with torn bytes."""
    paths: List[Any] = []
    leaves: List[np.ndarray] = []
    _walk(tree, [], paths, leaves)
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    flat = {f"leaf_{i}": l for i, l in enumerate(leaves)}
    npz_path = path if path.endswith(".npz") else path + ".npz"
    tmp_npz = npz_path + f".tmp-{os.getpid()}"
    tmp_struct = _struct_path(path) + f".tmp-{os.getpid()}"
    try:
        with open(tmp_npz, "wb") as fh:
            np.savez(fh, **flat)
        # CRC of the INTENDED bytes, read back before the commit point:
        # a crash (or injected truncation) between here and the rename
        # yields an artifact whose CRC cannot match
        with open(tmp_npz, "rb") as fh:
            crc = crc32c(fh.read())
        nbytes = os.path.getsize(tmp_npz)
        faults.fire("checkpoint.write", path=tmp_npz)
        os.replace(tmp_npz, npz_path)
        with open(tmp_struct, "w") as fh:
            json.dump({"nodes": paths, "npz_crc32c": crc,
                       "npz_bytes": nbytes}, fh)
        os.replace(tmp_struct, _struct_path(path))
    except BaseException:
        for tmp in (tmp_npz, tmp_struct):
            try:
                os.unlink(tmp)
            except OSError:
                pass
        raise


def _struct_path(path: str) -> str:
    base = path[:-4] if path.endswith(".npz") else path
    return base + ".structure.json"


def verify_pytree(path: str) -> bool:
    """True when `<path>` is a complete, CRC-intact artifact. Legacy
    artifacts without a recorded CRC pass on existence alone."""
    npz_path = path if path.endswith(".npz") else path + ".npz"
    try:
        with open(_struct_path(path)) as fh:
            meta = json.load(fh)
        if not os.path.exists(npz_path):
            return False
        if "npz_crc32c" not in meta:
            return True
        if os.path.getsize(npz_path) != meta.get("npz_bytes"):
            return False
        with open(npz_path, "rb") as fh:
            return crc32c(fh.read()) == meta["npz_crc32c"]
    except (OSError, ValueError):
        return False


def load_pytree(path: str, verify: bool = True) -> Any:
    """Load a pytree written by save_pytree; reconstructs nested
    dicts/lists (tuples come back as lists). With `verify` (default)
    the npz's recorded CRC is checked against ONE read of the bytes
    (np.load then parses the same in-memory buffer — no second disk
    pass for multi-GB checkpoints) and a mismatch raises
    `CorruptCheckpointError` instead of feeding torn bytes to np.load."""
    import io
    npz_path = path if path.endswith(".npz") else path + ".npz"
    with open(_struct_path(path)) as fh:
        meta = json.load(fh)
    if verify and "npz_crc32c" in meta:
        with open(npz_path, "rb") as fh:
            raw = fh.read()
        if len(raw) != meta.get("npz_bytes") \
                or crc32c(raw) != meta["npz_crc32c"]:
            raise CorruptCheckpointError(
                f"checkpoint artifact {path} is corrupt or truncated")
        npz = np.load(io.BytesIO(raw))
    else:
        npz = np.load(npz_path)
    root: Any = None
    for node in meta["nodes"]:
        if "leaf" in node:
            value: Any = npz[f"leaf_{node['leaf']}"]
        else:
            value = {} if node["empty"] == "dict" else []
        root = _insert(root, node["path"], value)
    return root if root is not None else {}


def _insert(root, parts, value):
    if not parts:
        return value
    kind, key = parts[0]
    if kind == "i":
        key = int(key)
        if root is None:
            root = []
        while len(root) <= key:
            root.append(None)
        root[key] = _insert(root[key], parts[1:], value)
        return root
    if root is None:
        root = {}
    root[key] = _insert(root.get(key), parts[1:], value)
    return root


# ---------------------------------------------------------------------------
# Reference-layout training checkpoints
# ---------------------------------------------------------------------------
_STAMP_FMT = "%Y%m%d_%H%M%S"


class CheckpointManager:
    """Writes `model.<iter>` + `optimMethod-<name>.<iter>` into a timestamped
    subdir (created once per training run, `Topology.scala:1245-1252`)."""

    def __init__(self, root: str, optim_name: str = "default", keep: int = 3):
        self.root = root
        self.optim_name = optim_name
        self.keep = keep
        stamp = datetime.datetime.now().strftime(_STAMP_FMT)
        self.run_dir = os.path.join(root, stamp)
        os.makedirs(self.run_dir, exist_ok=True)
        self._saved: List[int] = []

    def save(self, iteration: int, params: Any, opt_state: Any = None,
             extra: Optional[Dict[str, Any]] = None) -> str:
        """Commit ORDER makes the checkpoint SET atomic, not just each
        artifact: optimizer state and metadata land first, the model
        artifact (whose CRC sidecar `checkpoint_intact` keys on) lands
        LAST as the commit marker. A crash anywhere before the final
        rename leaves no model.<iter>.npz, so the torn set is invisible
        to `latest_checkpoint`/resume — never a model that resumes with
        fresh optimizer state or epoch-0 metadata."""
        mpath = os.path.join(self.run_dir, f"model.{iteration}")
        if opt_state is not None:
            opath = os.path.join(self.run_dir,
                                 f"optimMethod-{self.optim_name}.{iteration}")
            save_pytree(opath, _optstate_to_tree(opt_state))
        if extra:
            tmp = mpath + f".meta.json.tmp-{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(extra, fh)
            os.replace(tmp, mpath + ".meta.json")
        save_pytree(mpath, params)
        self._saved.append(iteration)
        self._gc()
        return mpath

    def _gc(self):
        while len(self._saved) > self.keep:
            it = self._saved.pop(0)
            for pat in (f"model.{it}", f"optimMethod-{self.optim_name}.{it}"):
                # .int8.* is the quantization sidecar (ISSUE 12): it
                # lives and dies with its checkpoint version, or the
                # keep=N retention contract silently stops bounding the
                # directory
                # .published.json is the rollout marker (ISSUE 14):
                # retired with its version, or the watcher could keep
                # "seeing" a version whose artifacts are gone
                for suffix in (".npz", ".structure.json", ".meta.json",
                               ".int8.npz", ".int8.structure.json",
                               ".published.json"):
                    p = os.path.join(self.run_dir, pat + suffix)
                    if os.path.exists(p):
                        os.remove(p)


def list_checkpoints(root: str) -> List[Tuple[str, int]]:
    """Every (run_dir, version) under root, newest first (version desc,
    then run-dir stamp desc for ties across run dirs)."""
    found: List[Tuple[str, int]] = []
    if not os.path.isdir(root):
        return found
    candidates = [root] + [os.path.join(root, d)
                           for d in sorted(os.listdir(root))
                           if os.path.isdir(os.path.join(root, d))]
    for run_dir in candidates:
        if not os.path.isdir(run_dir):
            continue
        for f in os.listdir(run_dir):
            m = re.match(r"model\.(\d+)\.npz$", f)
            if m:
                found.append((run_dir, int(m.group(1))))
    return sorted(found, key=lambda rv: (rv[1], rv[0]), reverse=True)


def checkpoint_intact(run_dir: str, version: int) -> bool:
    """CRC/completeness check for one checkpoint version: the model
    artifact and (when present) its optimizer artifacts must all
    verify."""
    if not verify_pytree(os.path.join(run_dir, f"model.{version}")):
        return False
    for f in os.listdir(run_dir):
        if re.match(rf"optimMethod-.+\.{version}\.npz$", f):
            if not verify_pytree(os.path.join(run_dir, f)):
                return False
    return True


def latest_checkpoint(root: str,
                      verify: bool = True) -> Optional[Tuple[str, int]]:
    """Find (run_dir, version) of the newest INTACT model.<iter> under
    root — mirrors `find_latest_checkpoint` (`orca/learn/tf/utils.py`),
    plus the fallback discipline: a corrupt/truncated newest version is
    skipped (with a warning) in favor of the newest version that
    verifies. `verify=False` restores the raw newest-by-number scan."""
    for run_dir, version in list_checkpoints(root):
        if not verify or checkpoint_intact(run_dir, version):
            return (run_dir, version)
        log.warning(
            "checkpoint model.%d in %s is corrupt/truncated; falling "
            "back to an earlier version", version, run_dir)
    return None


def read_checkpoint_meta(run_dir: str, version: int) -> Dict[str, Any]:
    """The extra-metadata sidecar of one checkpoint ({} when absent or
    unreadable)."""
    mpath = os.path.join(run_dir, f"model.{version}.meta.json")
    try:
        with open(mpath) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return {}


def find_resume_checkpoint(root: str) -> Optional[Tuple[str, int,
                                                        Dict[str, Any]]]:
    """The checkpoint `fit_keras(auto_resume=True)` should continue
    from: the newest INTACT epoch-boundary checkpoint (mid-epoch and
    emergency saves are skipped — resuming from one would replay part
    of an epoch and break loss-identical continuation). Falls back to
    the newest intact checkpoint of any kind, with a warning, when no
    boundary checkpoint survives. Returns (run_dir, version, meta) or
    None."""
    fallback = None        # newest intact NON-boundary checkpoint
    # lazy: intactness CRC-reads whole artifacts, so verify candidates
    # newest-first only until a boundary hit instead of scanning every
    # version under every run dir up front
    for run_dir, version in list_checkpoints(root):
        if not checkpoint_intact(run_dir, version):
            continue
        meta = read_checkpoint_meta(run_dir, version)
        # legacy checkpoints predate the flag; treat them as boundaries
        if meta.get("epoch_finished", True):
            return (run_dir, version, meta)
        if fallback is None:
            fallback = (run_dir, version, meta)
    if fallback is not None:
        log.warning(
            "no epoch-boundary checkpoint under %s; resuming from "
            "mid-epoch model.%d (continuation will replay the partial "
            "epoch from its start)", root, fallback[1])
    return fallback


# ---------------------------------------------------------------------------
# Publish markers (ISSUE 14): the rollout contract between trainer and fleet
# ---------------------------------------------------------------------------
def _marker_path(run_dir: str, version: int) -> str:
    return os.path.join(run_dir, f"model.{version}.published.json")


def write_publish_marker(run_dir: str, version: int,
                         extra: Optional[Dict[str, Any]] = None) -> str:
    """Commit the PUBLISH marker for one checkpoint version — the
    rollout watcher's admission gate. Written LAST, after every
    artifact of the version (params, optimizer state, int8 sidecar) is
    durable: `latest_checkpoint` only proves the model artifact is
    intact, while a rollout must never serve a version whose sidecar
    (or opt state, for a warm A/B restart) is still mid-write. The
    marker records a CRC manifest of every artifact it vouches for, so
    `verify_publish_marker` can detect a version whose bytes changed
    (or vanished) after publication. Atomic write-then-rename like
    every other checkpoint artifact."""
    manifest: Dict[str, Dict[str, Any]] = {}
    prefix = f"model.{version}."
    optim_re = re.compile(rf"optimMethod-.+\.{version}\.")
    for f in sorted(os.listdir(run_dir)):
        if f.endswith(".published.json") or ".tmp-" in f:
            continue
        if not (f.startswith(prefix) or optim_re.match(f)):
            continue
        p = os.path.join(run_dir, f)
        with open(p, "rb") as fh:
            raw = fh.read()
        crc = crc32c(raw)
        if f.endswith(".npz"):
            # publishing asserts the WHOLE set verifies — checked in
            # THIS read pass (multi-GB checkpoints must not pay a
            # separate checkpoint_intact sweep per publish): each npz
            # must match the CRC its structure sidecar committed, so a
            # writer killed mid-write (or an injected truncation) can
            # never gain a marker
            try:
                with open(_struct_path(os.path.join(run_dir, f))) as sh:
                    meta = json.load(sh)
            except (OSError, ValueError):
                raise CorruptCheckpointError(
                    f"refusing to publish model.{version} in "
                    f"{run_dir}: {f} has no readable structure "
                    "sidecar") from None
            if "npz_crc32c" in meta and (
                    meta.get("npz_bytes") != len(raw)
                    or meta["npz_crc32c"] != crc):
                raise CorruptCheckpointError(
                    f"refusing to publish model.{version} in "
                    f"{run_dir}: {f} does not match its CRC sidecar")
        manifest[f] = {"bytes": len(raw), "crc32c": crc}
    if f"model.{version}.npz" not in manifest:
        raise FileNotFoundError(
            f"cannot publish model.{version} in {run_dir}: the model "
            "artifact is not on disk")
    marker = _marker_path(run_dir, version)
    tmp = marker + f".tmp-{os.getpid()}"
    try:
        with open(tmp, "w") as fh:
            json.dump({"version": version, "manifest": manifest,
                       "extra": extra or {}}, fh)
        os.replace(tmp, marker)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return marker


def read_publish_marker(run_dir: str,
                        version: int) -> Optional[Dict[str, Any]]:
    """The marker payload, or None when absent/unparseable (an
    unparseable marker is an UNPUBLISHED version, never an error — a
    crash mid-rename must not wedge the watcher)."""
    try:
        with open(_marker_path(run_dir, version)) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def verify_publish_marker(run_dir: str, version: int) -> bool:
    """True when the version carries a marker AND every artifact the
    marker's manifest vouches for still exists with matching
    bytes+CRC. A marked version whose artifacts were since torn (disk
    fault, partial restore) reads as unpublished."""
    marker = read_publish_marker(run_dir, version)
    if marker is None:
        return False
    for f, meta in (marker.get("manifest") or {}).items():
        p = os.path.join(run_dir, f)
        try:
            if os.path.getsize(p) != meta.get("bytes"):
                return False
            with open(p, "rb") as fh:
                if crc32c(fh.read()) != meta.get("crc32c"):
                    return False
        except OSError:
            return False
    return True


def _publish_stat_key(run_dir: str, version: int) -> Optional[tuple]:
    """Cheap cache key for a version's publish verdict: (mtime_ns,
    size) of the marker and of EVERY file its manifest vouches for —
    the marker JSON is small, so reading it per poll is cheap, and
    keying on the whole set means a verdict (True or False)
    invalidates the moment ANY artifact changes: a sidecar repaired
    in place re-verifies, a sidecar torn after the fact re-fails.
    None when the marker or any manifest file is absent (definitely
    unpublished — no verdict to cache)."""
    marker = read_publish_marker(run_dir, version)
    if marker is None:
        return None
    stats = []
    try:
        m = os.stat(_marker_path(run_dir, version))
        stats.append(("", m.st_mtime_ns, m.st_size))
        for f in sorted(marker.get("manifest") or {}):
            s = os.stat(os.path.join(run_dir, f))
            stats.append((f, s.st_mtime_ns, s.st_size))
    except OSError:
        return None
    return (run_dir, version, tuple(stats))


def published_intact(run_dir: str, version: int,
                     verify_cache: Optional[Dict] = None) -> bool:
    """The watcher's whole admission check, ONE read pass: the marker
    proves publication, and its manifest CRCs — which cover every
    artifact AND every structure sidecar, with npz↔sidecar consistency
    asserted at publish time by `write_publish_marker` — prove the set
    still holds the published bytes (a separate `checkpoint_intact`
    sweep would re-read the same multi-GB files to learn nothing new).
    With `verify_cache` (a caller-owned dict) the verdict is memoized
    per stat key, so a control loop polling every second costs stats
    plus one small JSON read per tick."""
    if verify_cache is None:
        return verify_publish_marker(run_dir, version)
    key = _publish_stat_key(run_dir, version)
    if key is None:
        return False
    verdict = verify_cache.get(key)
    if verdict is None:
        verdict = verify_publish_marker(run_dir, version)
        verify_cache[key] = verdict
    return verdict


def latest_published_checkpoint(
        root: str, skip_versions=(),
        verify_cache: Optional[Dict] = None) -> Optional[Tuple[str, int]]:
    """(run_dir, version) of the newest PUBLISHED checkpoint under
    `root` — what the rollout watcher acts on. Stricter than
    `latest_checkpoint`: a version without an intact publish marker
    (trainer still writing, crashed mid-commit, artifacts torn after
    the fact) is invisible, so a watcher polling a live training run
    can only ever observe versions whose whole artifact set is
    durable. `skip_versions` (the rollout controller's quarantine set)
    falls back to the newest published version not in it.

    `verify_cache` (a caller-owned dict) memoizes the full-CRC verdict
    per (run_dir, version, marker/model stat): verification reads and
    CRCs the WHOLE artifact set, which a control loop polling every
    second must not re-pay for a multi-GB checkpoint that hasn't
    changed — with the cache, an idle poll costs a dir listing and two
    stats. Entries for versions no longer listed are pruned."""
    skip = {int(v) for v in skip_versions}
    listed = list_checkpoints(root)
    if verify_cache is not None:
        live = {(rd, v) for rd, v in listed}
        for key in [k for k in verify_cache
                    if (k[0], k[1]) not in live]:
            verify_cache.pop(key, None)
    for run_dir, version in listed:
        if version in skip:
            continue
        if published_intact(run_dir, version, verify_cache=verify_cache):
            return (run_dir, version)
    return None


def resolve_checkpoint(path: str,
                       version: Optional[int] = None) -> Tuple[str, int]:
    """THE root-vs-run-dir resolution, shared by `load_checkpoint`,
    `InferenceModel.load_checkpoint` and the offline quantization
    script — one copy, so the sidecar probe and the param load can
    never resolve different directories. `version=None` → the newest
    INTACT checkpoint anywhere under `path`; an explicit version →
    `path` itself when it holds `model.<version>`, else the newest run
    dir under `path` that does. Raises FileNotFoundError."""
    if version is None:
        found = latest_checkpoint(path)
        if found is None:
            raise FileNotFoundError(f"No checkpoint under {path}")
        return found
    if os.path.exists(os.path.join(path, f"model.{version}.npz")):
        return path, version
    found = latest_checkpoint(path)
    if found and os.path.exists(
            os.path.join(found[0], f"model.{version}.npz")):
        return found[0], version
    raise FileNotFoundError(f"No model.{version} under {path}")


def load_checkpoint(path: str, version: Optional[int] = None,
                    optim_name: str = "default", verify: bool = True):
    """Load (params, opt_tree, meta) from a checkpoint dir. `path` may be the
    ckpt root or a run dir; `version=None` → latest. `verify=False` skips
    the CRC pass — for callers (auto-resume) that ran `checkpoint_intact`
    on this exact version moments earlier."""
    run_dir, version = resolve_checkpoint(path, version)
    params = load_pytree(os.path.join(run_dir, f"model.{version}"),
                         verify=verify)
    opt_tree = None
    opath = os.path.join(run_dir, f"optimMethod-{optim_name}.{version}")
    if os.path.exists(opath + ".npz"):
        opt_tree = load_pytree(opath, verify=verify)
    meta = {}
    mpath = os.path.join(run_dir, f"model.{version}.meta.json")
    if os.path.exists(mpath):
        with open(mpath) as fh:
            meta = json.load(fh)
    return params, opt_tree, meta


def _optstate_to_tree(opt_state: Any) -> Any:
    """Optax states are namedtuple pytrees; store leaves + paths only.
    Routed through `gather_leaf` so a GSPMD fit's sharded optimizer
    moments gather correctly (addressable shards fetched exactly
    once)."""
    return jax.tree_util.tree_map(gather_leaf, opt_state)


def remap_param_subtrees(tree: Any, param_names, remap) -> Any:
    """Rename the params-shaped subtrees of a saved optimizer state (the
    Adam moments mirror the params tree) with the SAME remap the saved
    params went through. `restore_opt_state` pours leaves by position,
    and jax orders dict leaves by sorted key: where the saving and the
    resuming model's auto-numbered layer names sort differently
    ("dense_99", "dense_100" saved; "dense_101", "dense_102" live) the
    two layers' moments would otherwise swap."""
    if isinstance(tree, dict):
        if set(tree) == param_names:
            return remap(tree)
        return {k: remap_param_subtrees(v, param_names, remap)
                for k, v in tree.items()}
    if isinstance(tree, list):
        return [remap_param_subtrees(v, param_names, remap) for v in tree]
    return tree


def restore_opt_state(template: Any, tree: Any) -> Any:
    """Pour saved leaves back into an optax state built by opt.init."""
    leaves_saved = jax.tree_util.tree_leaves(tree)
    treedef = jax.tree_util.tree_structure(template)
    leaves_tmpl = jax.tree_util.tree_leaves(template)
    if len(leaves_saved) != len(leaves_tmpl):
        raise ValueError(
            f"Optimizer state mismatch: saved {len(leaves_saved)} leaves, "
            f"template has {len(leaves_tmpl)}")
    cast = [np.asarray(s, dtype=np.asarray(t).dtype)
            for s, t in zip(leaves_saved, leaves_tmpl)]
    return jax.tree_util.tree_unflatten(treedef, cast)
