"""Multi-process cluster bootstrap — the RayOnSpark-role launcher.

Reference: `RayContext` boots worker daemons across Spark executors with a
barrier-job master election (`pyzoo/zoo/ray/raycontext.py:262,210`), and
`ProcessMonitor`/`JVMGuard` reap leaked processes (`ray/process.py:90`). On
TPU, rendezvous is `jax.distributed.initialize` (one mechanism instead of
five, SURVEY §5) and pods are normally launched by the platform — so what
remains for the framework is (a) a worker entrypoint that wires coordinator
env into `init_zoo_context`, and (b) a local multi-process launcher that
simulates an N-host cluster on one machine (CPU devices), used for testing
the multi-host code path exactly like the reference tests multi-worker on
`local[N]` (SURVEY §4).

    # run fn in 2 "hosts" x 2 devices each:
    launch_local_cluster("my_module:main", num_processes=2,
                         devices_per_process=2)

Worker side (any real deployment):
    python -m analytics_zoo_tpu.common.cluster \
        --worker my_module:main --coordinator host0:29500 \
        --num-processes 8 --process-id $RANK
"""

from __future__ import annotations

import argparse
import importlib
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

__all__ = ["launch_local_cluster", "wait_all", "ProcessMonitor",
           "force_cpu_devices"]


def force_cpu_devices(n: int) -> None:
    """Force the CPU backend with `n` virtual devices and gloo
    cross-process collectives (without a collectives implementation the
    CPU backend refuses multi-process computations). Must run before the
    CPU backend initializes; the callers here sit at process start,
    before any device use."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(n))
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ProcessMonitor:
    """Tracks spawned workers; kills the whole group on exit/failure
    (`ProcessMonitor`/`JVMGuard` semantics, `ray/process.py:90`)."""

    def __init__(self, procs: Sequence[subprocess.Popen]):
        self.procs = list(procs)

    def wait(self, timeout: Optional[float] = None) -> List[int]:
        """Wait for all; on any nonzero exit, terminate the rest (fail
        fast like a barrier job). Returns exit codes."""
        deadline = None if timeout is None else time.time() + timeout
        codes: Dict[int, int] = {}
        try:
            while len(codes) < len(self.procs):
                for i, p in enumerate(self.procs):
                    if i in codes:
                        continue
                    rc = p.poll()
                    if rc is not None:
                        codes[i] = rc
                        if rc != 0:
                            self.terminate()
                            raise RuntimeError(
                                f"worker {i} exited with {rc}; cluster "
                                "terminated")
                if deadline and time.time() > deadline:
                    self.terminate()
                    raise TimeoutError("cluster wait timed out")
                time.sleep(0.05)
        except BaseException:
            self.terminate()
            raise
        return [codes[i] for i in range(len(self.procs))]

    def terminate(self):
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        t0 = time.time()
        while time.time() - t0 < 5:
            if all(p.poll() is not None for p in self.procs):
                return
            time.sleep(0.05)
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:
                    pass


def launch_local_cluster(worker: str, num_processes: int,
                         devices_per_process: int = 1,
                         worker_args: Sequence[str] = (),
                         env: Optional[Dict[str, str]] = None,
                         platform: str = "cpu") -> ProcessMonitor:
    """Spawn `num_processes` local worker processes that rendezvous via
    jax.distributed and each see `devices_per_process` CPU devices —
    an N-host pod on one machine. `worker` is "module:function"."""
    coordinator = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(num_processes):
        cmd = [sys.executable, "-m", "analytics_zoo_tpu.common.cluster",
               "--worker", worker, "--coordinator", coordinator,
               "--num-processes", str(num_processes),
               "--process-id", str(pid),
               "--devices-per-process", str(devices_per_process),
               "--platform", platform, "--", *worker_args]
        penv = dict(os.environ)
        penv.update(env or {})
        procs.append(subprocess.Popen(cmd, env=penv))
    return ProcessMonitor(procs)


def wait_all(monitor: ProcessMonitor, timeout: Optional[float] = None):
    return monitor.wait(timeout)


def _worker_main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--worker", required=True, help="module:function")
    p.add_argument("--coordinator", required=True)
    p.add_argument("--num-processes", type=int, required=True)
    p.add_argument("--process-id", type=int, required=True)
    p.add_argument("--devices-per-process", type=int, default=1)
    p.add_argument("--platform", default=None)
    p.add_argument("rest", nargs="*")
    args = p.parse_args(argv)

    if args.platform == "cpu":
        force_cpu_devices(args.devices_per_process)
    else:
        import jax  # noqa: F401

    from analytics_zoo_tpu.common.config import ZooConfig
    from analytics_zoo_tpu.common.context import init_zoo_context
    cfg = ZooConfig()
    cfg.coordinator_address = args.coordinator
    cfg.num_processes = args.num_processes
    cfg.process_id = args.process_id
    init_zoo_context(cfg, cluster_mode="multi-host")

    mod_name, _, fn_name = args.worker.partition(":")
    fn = getattr(importlib.import_module(mod_name), fn_name or "main")
    result = fn(*args.rest)
    return int(result) if isinstance(result, int) else 0


if __name__ == "__main__":
    sys.exit(_worker_main())
