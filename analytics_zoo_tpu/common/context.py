"""Context initialization — the `init_orca_context` / `init_nncontext` analogue.

The reference's context layer boots a SparkContext with BigDL engine config and
optionally a Ray cluster on top (`pyzoo/zoo/orca/common.py:89`,
`pyzoo/zoo/common/nncontext.py:319`, `pyzoo/zoo/ray/raycontext.py:262`). On TPU
there is no JVM and no two-level runtime: `init_orca_context` performs multi-host
rendezvous via `jax.distributed.initialize` (replacing barrier-mode master
election + redis_address handshakes), discovers the device mesh, seeds RNG, and
installs logging. `ZooContext`/`OrcaContext` keep the reference's global-flag
surface (`orca/common.py:21-86`).
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

from analytics_zoo_tpu.common.config import MeshConfig, ZooConfig
from analytics_zoo_tpu.common.mesh import DeviceMesh

log = logging.getLogger("analytics_zoo_tpu")

_GLOBAL = {"context": None, "distributed_initialized": False}


class _ContextMeta(type):
    """Class-property global flags, mirroring `ZooContextMeta`
    (`nncontext.py:269`) / `OrcaContextMeta` (`orca/common.py:21`)."""

    _log_output = False
    _pandas_read_backend = "pandas"
    _serialize_data_creator = False
    _train_data_store = "DRAM"

    @property
    def log_output(cls) -> bool:
        return _ContextMeta._log_output

    @log_output.setter
    def log_output(cls, value: bool):
        # Only toggles output capture, never the configured verbosity
        # (matches the reference, where log_output redirects executor stdout,
        # `nncontext.py:274`).
        _ContextMeta._log_output = value

    @property
    def pandas_read_backend(cls) -> str:
        return _ContextMeta._pandas_read_backend

    @pandas_read_backend.setter
    def pandas_read_backend(cls, value: str):
        value = value.lower()
        if value not in ("pandas", "spark", "arrow"):
            raise ValueError(f"Unsupported pandas_read_backend: {value}")
        _ContextMeta._pandas_read_backend = value

    @property
    def train_data_store(cls) -> str:
        return _ContextMeta._train_data_store

    @train_data_store.setter
    def train_data_store(cls, value: str):
        value = value.upper()
        if value not in ("DRAM", "DISK", "DISK_AND_DRAM"):
            raise ValueError(f"Unsupported train_data_store: {value}")
        _ContextMeta._train_data_store = value


class ZooContext(metaclass=_ContextMeta):
    pass


class OrcaContext(metaclass=_ContextMeta):
    pass


def _configure_logging(level: str):
    logging.basicConfig(
        level=getattr(logging, level.upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    log.setLevel(getattr(logging, level.upper(), logging.INFO))


class Context:
    """The live runtime context: config + device mesh (+ rendezvous state)."""

    def __init__(self, config: ZooConfig, mesh: DeviceMesh):
        self.config = config
        self.mesh = mesh
        self.rng = jax.random.PRNGKey(config.seed)

    def next_rng(self) -> jax.Array:
        self.rng, sub = jax.random.split(self.rng)
        return sub

    def __repr__(self):
        return f"Context(mesh={self.mesh}, processes={jax.process_count()})"


def init_zoo_context(config: Optional[ZooConfig] = None,
                     cluster_mode: str = "local",
                     **mesh_axes) -> Context:
    """Initialise the runtime. Equivalent of `init_nncontext`
    (`nncontext.py:319`) + `NNContext.initNNContext` (`NNContext.scala:134`).

    cluster_mode:
      "local"      — this process's devices only (like Spark local[*]).
      "multi-host" — `jax.distributed.initialize` with coordinator settings
                     from config or TPU-pod env (like yarn/k8s modes).
    """
    config = ZooConfig.from_env(config)  # copies; caller's object untouched
    _configure_logging(config.log_level)
    # Wire config fields into the global context flags (setters validate).
    ZooContext.log_output = config.log_output
    ZooContext.pandas_read_backend = config.pandas_read_backend

    if cluster_mode in ("multi-host", "yarn", "k8s", "standalone"):
        # One rendezvous replaces the reference's five (survey §5): barrier
        # election, gloo, TF_CONFIG, tcp:// master, DMLC PS env. Must run
        # before anything touches the XLA backend, so we gate on our own flag
        # rather than jax.process_count().
        coordinator = (config.coordinator_address
                       or os.environ.get("COORDINATOR_ADDRESS"))
        if not _GLOBAL["distributed_initialized"]:
            if coordinator is None and "TPU_WORKER_HOSTNAMES" not in os.environ:
                raise ValueError(
                    "cluster_mode=multi-host needs a coordinator: set "
                    "ZooConfig.coordinator_address or COORDINATOR_ADDRESS "
                    "(on TPU pods jax.distributed can also auto-discover).")
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=config.num_processes,
                process_id=config.process_id,
            )
            _GLOBAL["distributed_initialized"] = True
    elif cluster_mode != "local":
        raise ValueError(f"Unknown cluster_mode: {cluster_mode}")

    # JAX's persistent compilation cache for everything this process
    # compiles: under JAX_COMPILATION_CACHE_DIR where that is set, else
    # in the checkout (compile_cache/store.py)
    from analytics_zoo_tpu.compile_cache import enable_jax_persistent_cache
    xla_cache_dir = enable_jax_persistent_cache()

    # Fast TPU random bits for dropout et al. (rbg keys lower to the
    # hardware RngBitGenerator; threefry costs ~25% of a BERT train step on
    # v5e). TPU-only: on CPU/GPU threefry stays, keeping init draws stable.
    # The JAX_DEFAULT_PRNG_IMPL env var or a prior jax.config.update to a
    # non-threefry impl wins; to force threefry ON TPU set the env var or
    # ZooConfig.prng_impl="threefry2x32" (an explicit jax.config.update to
    # threefry is indistinguishable from the untouched default). Runs after
    # distributed init because default_backend() touches the XLA backend.
    if ("JAX_DEFAULT_PRNG_IMPL" not in os.environ
            and jax.config.jax_default_prng_impl == "threefry2x32"
            and jax.default_backend() == "tpu"):
        jax.config.update("jax_default_prng_impl", config.prng_impl)

    if mesh_axes:
        valid = set(MeshConfig.__dataclass_fields__)
        unknown = set(mesh_axes) - valid
        if unknown:
            raise TypeError(
                f"Unknown mesh axis kwarg(s) {sorted(unknown)}; "
                f"valid axes: {sorted(valid)}")
        for k, v in mesh_axes.items():
            setattr(config.mesh, k, v)
    mesh = DeviceMesh(config.mesh)
    ctx = Context(config, mesh)
    _GLOBAL["context"] = ctx
    log.info("Initialized %s on %d device(s) (%s), %d process(es); "
             "xla compile cache %s", mesh, mesh.n_devices,
             jax.devices()[0].platform, jax.process_count(), xla_cache_dir)
    return ctx


def init_orca_context(cluster_mode: str = "local",
                      cores: Optional[int] = None,
                      memory: Optional[str] = None,
                      num_nodes: int = 1,
                      init_ray_on_spark: bool = False,
                      config: Optional[ZooConfig] = None,
                      **kwargs) -> Context:
    """Drop-in analogue of `init_orca_context` (`orca/common.py:89`). The
    Spark-centric kwargs (cores/memory/num_nodes) are accepted for source
    compatibility; on TPU they are informational — the mesh is defined by the
    attached devices, not by executor sizing."""
    known_spark = {"driver_cores", "driver_memory", "num_executors",
                   "executor_cores", "executor_memory", "extra_python_lib",
                   "conf", "init_ray_on_spark"}
    mesh_axes = {}
    for k, v in kwargs.items():
        if k in MeshConfig.__dataclass_fields__:
            mesh_axes[k] = v
        elif k not in known_spark:
            raise TypeError(
                f"init_orca_context got unknown kwarg {k!r}; mesh axes are "
                f"{sorted(MeshConfig.__dataclass_fields__)}")
    if cluster_mode in ("yarn", "yarn-client", "yarn-cluster", "k8s",
                        "standalone"):
        cluster_mode = "multi-host"
    return init_zoo_context(config, cluster_mode=cluster_mode, **mesh_axes)


def get_context() -> Context:
    ctx = _GLOBAL["context"]
    if ctx is None:
        ctx = init_zoo_context()
    return ctx


def stop_orca_context() -> None:
    """Analogue of `stop_orca_context` (`orca/common.py:204`). Clears the
    global context; device runtime is managed by JAX and needs no teardown."""
    _GLOBAL["context"] = None
