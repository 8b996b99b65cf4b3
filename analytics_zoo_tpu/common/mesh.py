"""Device-mesh abstraction: the single communication substrate.

The reference maintains five data-parallel transports (Spark BlockManager
scatter-reduce `docs/docs/wp-bigdl.md:150-166`, Horovod-gloo, TF
MultiWorkerMirrored gRPC, torch.distributed gloo, MXNet kvstore — survey §2.5).
Here they all collapse into one object: a `jax.sharding.Mesh` whose axes map
onto the TPU interconnect. GSPMD emits `all-reduce`/`reduce-scatter`/
`all-gather`/`collective-permute` over ICI (and DCN for the outer axes), so the
"communication backend" is the XLA compiler itself.

Axis convention (outermost → innermost, i.e. DCN-most → ICI-most):
    pipeline — pipeline stages; activations `ppermute` stage-to-stage (DCN-ok).
    data     — data parallel; gradients all-reduce here.
    fsdp     — parameter/optimizer-state sharding (ZeRO-3 style all-gather).
    sequence — sequence/context parallel; ring attention `ppermute`s here.
    expert   — expert parallel. Today the name alone: the expert layer
               (`keras/moe.py`) is told which experts it holds and runs
               one chip's part without an exchange; no sharding rule or
               all-to-all rides here yet (ROADMAP M4).
    tensor   — tensor parallel; activation collectives need the fastest links.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from analytics_zoo_tpu.common.config import MeshConfig

# Outermost → innermost. Single source of truth for axis names/order.
AXIS_NAMES: Tuple[str, ...] = (
    "pipeline", "data", "fsdp", "sequence", "expert", "tensor")
# Axes over which the input batch is split.
BATCH_AXES: Tuple[str, ...] = ("data", "fsdp")


def _infer_axis_sizes(n_devices: int, cfg: MeshConfig) -> Dict[str, int]:
    sizes = {name: getattr(cfg, name) for name in AXIS_NAMES}
    for name, v in sizes.items():
        if v != -1 and v < 1:
            raise ValueError(
                f"Mesh axis {name}={v} invalid: must be >=1, or -1 to infer")
    fixed = math.prod(v for v in sizes.values() if v != -1)
    free = [k for k, v in sizes.items() if v == -1]
    if len(free) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {free}")
    if free:
        if n_devices % fixed != 0:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed axes product {fixed}")
        sizes[free[0]] = n_devices // fixed
    if math.prod(sizes.values()) != n_devices:
        raise ValueError(
            f"Mesh {sizes} does not cover {n_devices} devices")
    return sizes


class DeviceMesh:
    """A named logical mesh over the available devices.

    >>> mesh = DeviceMesh()                       # all-data-parallel
    >>> mesh = DeviceMesh(MeshConfig(data=-1, tensor=4))
    >>> with mesh: ...                            # acts as jax Mesh context
    """

    def __init__(self,
                 config: Optional[MeshConfig] = None,
                 devices: Optional[Sequence[jax.Device]] = None):
        self.config = config or MeshConfig()
        devs = list(devices) if devices is not None else jax.devices()
        self.axis_sizes = _infer_axis_sizes(len(devs), self.config)
        shape = tuple(self.axis_sizes[a] for a in AXIS_NAMES)
        # Row-major reshape keeps 'tensor' innermost so tensor-parallel
        # collectives land on directly-connected neighbours; 'pipeline'/'data'
        # outermost so their (infrequent or overlappable) transfers may span
        # DCN in multi-slice deployments.
        dev_array = np.asarray(devs).reshape(shape)
        self.mesh = Mesh(dev_array, AXIS_NAMES)

    # -- mapping helpers ---------------------------------------------------
    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def data_parallel_size(self) -> int:
        return math.prod(self.axis_sizes[a] for a in BATCH_AXES)

    def size(self, axis: str) -> int:
        return self.axis_sizes[axis]

    def sharding(self, *spec) -> NamedSharding:
        """NamedSharding for a PartitionSpec over this mesh."""
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def batch_sharding(self) -> NamedSharding:
        """Canonical input-batch sharding: batch dim split over every
        batch-like axis (data × fsdp), rest replicated."""
        return NamedSharding(self.mesh, PartitionSpec(BATCH_AXES))

    def stacked_batch_sharding(self) -> NamedSharding:
        """Sharding for a (steps, batch, ...) stack of training batches:
        leading scan dim replicated, batch dim split like batch_sharding."""
        return NamedSharding(self.mesh, PartitionSpec(None, BATCH_AXES))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    # -- context manager ---------------------------------------------------
    def __enter__(self):
        self._ctx = self.mesh.__enter__()
        return self

    def __exit__(self, *exc):
        return self.mesh.__exit__(*exc)

    def __repr__(self):
        axes = ", ".join(f"{a}={self.axis_sizes[a]}"
                         for a in AXIS_NAMES if self.axis_sizes[a] != 1)
        return f"DeviceMesh({axes or 'single-device'})"


def validate_axis_names(axes) -> None:
    """THE axis-vocabulary check for config-driven mesh construction —
    serving-config load and `mesh_from_axes` both call it, so the
    vocabulary and its error can never drift between the two sites."""
    unknown = set(axes) - set(AXIS_NAMES)
    if unknown:
        raise ValueError(
            f"unknown mesh axis name(s) {sorted(unknown)}; valid axes: "
            f"{list(AXIS_NAMES)}")


def mesh_from_axes(axes: Dict[str, int],
                   devices: Optional[Sequence[jax.Device]] = None
                   ) -> DeviceMesh:
    """DeviceMesh from a plain axis→size mapping (the serving-config /
    CLI spelling, e.g. ``{"data": 1, "fsdp": 2, "tensor": 4}``) — ONE
    validation point for config-driven mesh construction, so a typo'd
    axis name fails with the axis vocabulary instead of a dataclass
    TypeError. Sizes follow MeshConfig semantics (-1 infers one axis
    from the device count; unlisted axes default per MeshConfig)."""
    validate_axis_names(axes)
    try:
        sizes = {k: int(v) for k, v in axes.items()}
    except (TypeError, ValueError):
        raise ValueError(
            f"mesh axis sizes must be integers, got {axes!r}") from None
    return DeviceMesh(MeshConfig(**sizes), devices)


def local_mirror_mesh(n: int = 1) -> DeviceMesh:
    """Single-host mesh over the first n local devices (testing helper)."""
    return DeviceMesh(MeshConfig(data=n), jax.local_devices()[:n])
