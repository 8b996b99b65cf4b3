"""AOT executable (de)serialization, with device retargeting.

`jax.experimental.serialize_executable.serialize` returns (payload
bytes, in_tree, out_tree); the pytrees pickle fine, so `pack` folds them
into one bytes blob together with the ids of the devices the executable
was compiled for, in device-assignment order. Two wrinkles this module
owns:

- **Device placement** (`unpack`): `deserialize_and_load` defaults to
  ALL backend devices, which is wrong for anything compiled on fewer
  (a one-device serving executable on a four-chip host, a sub-mesh) and
  for meshes whose device order is not id order. `unpack` therefore
  always passes `execution_devices`: the stored ids by default. A
  single-device executable can instead be re-pinned onto one
  `target_device_id` (the replicated serving pool persists ONE entry
  per bucket and loads it once per replica), which takes
  `_PinnedUnpickler`. Multi-device (GSPMD/sharded) executables never
  retarget — their device set IS the key.
- **Compile spy-ability** (`compile_lowered`): every fresh AOT compile
  in the codebase funnels through this one function, so tests can
  monkeypatch it and assert a cache-warm warmup performs ZERO compiles.
"""

from __future__ import annotations

import io
import pickle
from typing import Optional

import jax
import numpy as np
from jax._src.lib import xla_client as _xc
from jax.experimental import serialize_executable as _se


def compile_lowered(lowered):
    """`lowered.compile()` — THE fresh-compile funnel (tests spy here)."""
    return lowered.compile()


def pack(compiled) -> bytes:
    """One bytes blob from a `jax.stages.Compiled`. Raises on anything
    unserializable (callbacks, closed-over constants) — callers treat
    that as 'skip persisting', never as fatal."""
    payload, in_tree, out_tree = _se.serialize(compiled)
    device_ids = [d.id for d in
                  compiled.runtime_executable().local_devices()]
    return pickle.dumps((payload, in_tree, out_tree, device_ids),
                        protocol=4)


class _PinnedUnpickler(_se._JaxPjrtUnpickler):
    """Lands a single-device executable on ONE other device.
    `deserialize_and_load(execution_devices=[dev])` alone does not: the
    pickled args-info shardings still name the compile-time device
    (KeyError when it is not among the execution devices), and the XLA
    executable keeps its compile-time device assignment ("replica is
    assigned to device 0") unless it reloads under `CompileOptions`
    carrying a fresh one."""

    def persistent_load(self, pid):
        target = self.execution_devices[0]
        if pid[0] == "device":
            return target
        if pid[0] == "exec":
            opts = _xc.CompileOptions()
            opts.device_assignment = _xc.DeviceAssignment.create(
                np.array([[target.id]], np.int32))
            return self.backend.deserialize_executable(
                pid[1], executable_devices=self.execution_devices,
                compile_options=opts)
        return super().persistent_load(pid)


def args_treedef(compiled):
    """The pytree structure a `Compiled` expects for its inputs — the
    `((args...), {kwargs})` treedef, dict-key metadata included
    (`Compiled` rejects calls whose trees differ even when every leaf
    matches). Compare against `live_treedef(args)`."""
    return compiled.in_tree


def live_treedef(args) -> "jax.tree_util.PyTreeDef":
    """`args_treedef`-comparable structure of a positional-args call."""
    return jax.tree_util.tree_structure((tuple(args), {}))


def retree_call(compiled, stored_tree):
    """Adapter for a cache hit whose stored tree carries different
    auto-numbered layer names than the live params ("dense_3" stored,
    "dense_7" live): flatten the live args and rebuild them under the
    stored `in_tree` before calling. Sound because the canonical key
    (`structure_signature`) only matches trees whose jax flatten
    orders correspond. Serving-side only — its OUTPUTS are
    activations, so the stored names never leak back into a params
    tree the caller keeps."""

    def call(*args):
        leaves = jax.tree_util.tree_leaves((tuple(args), {}))
        new_args, new_kwargs = jax.tree_util.tree_unflatten(stored_tree,
                                                            leaves)
        return compiled(*new_args, **new_kwargs)

    return call


def unpack(data: bytes, target_device_id: Optional[int] = None):
    """Rebuild a callable `jax.stages.Compiled` from `pack` output on
    the devices it was compiled for. `target_device_id` re-pins a
    single-device executable onto that device instead (replica
    fan-out); multi-device executables refuse it."""
    payload, in_tree, out_tree, device_ids = pickle.loads(data)
    by_id = {d.id: d for d in jax.devices()}
    if target_device_id is None or [target_device_id] == device_ids:
        return _se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in device_ids])
    if len(device_ids) != 1:
        raise ValueError(
            f"cannot re-pin a {len(device_ids)}-device executable onto "
            f"device {target_device_id}")
    target = by_id[target_device_id]
    unloaded, args_info_flat, no_kwargs = _PinnedUnpickler(
        io.BytesIO(payload), target.client, [target]).load()
    return jax.stages.Compiled(unloaded.load(), [],
                               in_tree.unflatten(args_info_flat), out_tree,
                               no_kwargs=no_kwargs)
