"""`zoo-launch` — one-call multi-host training launcher.

Reference role: the one-call cluster bootstraps (`init_spark_on_yarn` /
`init_spark_standalone`, `pyzoo/zoo/common/nncontext.py:56,129,199`;
`scripts/standalone/start-standalone.sh`) that turn "a list of hosts"
into a running distributed job. TPU-native shape: every process runs the
SAME script; the launcher's whole job is to assign coordinator/world
env (`COORDINATOR_ADDRESS`, `ZOO_NUM_PROCESSES`, `ZOO_PROCESS_ID` —
read by `ZooConfig.from_env` inside
`init_orca_context(cluster_mode="multi-host")`) and to supervise the
process group fail-fast like `launch_local_cluster` does
(`common/cluster.py ProcessMonitor`).

    # 2 hosts, one process each (it drives all of its host's chips),
    # rendezvous on hostA:29400
    zoo-launch --hosts hostA,hostB train.py --epochs 3

    # local simulation: 2 "hosts" on this machine, 4 CPU devices each
    zoo-launch --nproc 2 --simulate-devices 4 train.py

    # TPU pod slice: hosts come from the platform env; just
    zoo-launch train.py        # (TPU_WORKER_HOSTNAMES autodetected)

One process per host: every process started on a TPU host sees every
chip of it and a chip belongs to one process at a time, so `--nproc N>1`
is accepted only together with `--simulate-devices` (CPU processes).

Remote processes start through `--ssh-cmd` (default `ssh`); anything
argv-shaped works (`--ssh-cmd "kubectl exec -i"` for GKE pods, a bash
shim in tests). Local hosts (`localhost`/`127.0.0.1`) spawn directly.
"""

from __future__ import annotations

import argparse
import os
import shlex
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from analytics_zoo_tpu.common.cluster import ProcessMonitor

_LOCAL_HOSTS = {"localhost", "127.0.0.1", "::1"}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _is_local(host: str) -> bool:
    return host.split("@")[-1] in _LOCAL_HOSTS


def detect_hosts() -> List[str]:
    """TPU pod-slice autodetect: the platform publishes the worker list
    (`TPU_WORKER_HOSTNAMES`, comma-separated). Fallback: this host."""
    names = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    hosts = [h.strip() for h in names.split(",") if h.strip()]
    return hosts or ["localhost"]


def build_commands(hosts: Sequence[str], nproc: int, coordinator: str,
                   script: str, script_args: Sequence[str],
                   python: str = sys.executable, ssh_cmd: str = "ssh",
                   extra_env: Optional[Dict[str, str]] = None,
                   simulate_devices: int = 0
                   ) -> List[Tuple[List[str], Optional[Dict[str, str]]]]:
    """One (argv, env) pair per process, ranks assigned host-major so
    rank r lives on host r // nproc (ICI-contiguous within a host).
    env is None for ssh'd commands (env rides inside the remote
    command line)."""
    world = len(hosts) * nproc
    out: List[Tuple[List[str], Optional[Dict[str, str]]]] = []
    # the launch cwd is importable on every worker (`python script.py`
    # only puts the SCRIPT's dir on sys.path) — the spark-submit
    # ships-the-project role
    pythonpath = os.pathsep.join(
        p for p in (os.getcwd(), os.environ.get("PYTHONPATH")) if p)
    # simulate mode sets the virtual device count and the CPU collectives
    # through jax.config BEFORE the script runs, so the script goes
    # through this module's --bootstrap-devices runner
    runner: List[str] = []
    if simulate_devices:
        runner = ["-m", "analytics_zoo_tpu.common.launch",
                  "--bootstrap-devices", str(simulate_devices)]
    rank = 0
    for host in hosts:
        for _ in range(nproc):
            env_vars = {
                "COORDINATOR_ADDRESS": coordinator,
                "ZOO_NUM_PROCESSES": str(world),
                "ZOO_PROCESS_ID": str(rank),
                "PYTHONPATH": pythonpath,
                **(extra_env or {}),
            }
            if _is_local(host):
                env = dict(os.environ)
                env.update(env_vars)
                out.append(([python, *runner, script, *script_args], env))
            else:
                assignments = " ".join(
                    f"{k}={shlex.quote(v)}" for k, v in env_vars.items())
                remote = (f"cd {shlex.quote(os.getcwd())} && "
                          f"env {assignments} {shlex.quote(python)} "
                          + " ".join(shlex.quote(a) for a in runner)
                          + (" " if runner else "")
                          + f"{shlex.quote(script)} "
                          + " ".join(shlex.quote(a) for a in script_args))
                # "{host}" placeholder lets exec styles that need args
                # AFTER the target work (kubectl >=1.22 requires
                # `exec POD -- cmd`): --ssh-cmd "kubectl exec -i {host} --"
                parts = shlex.split(ssh_cmd)
                if any("{host}" in p for p in parts):
                    argv = [p.replace("{host}", host) for p in parts]
                else:
                    argv = [*parts, host]
                out.append(([*argv, remote], None))
            rank += 1
    return out


def launch(hosts: Sequence[str], nproc: int, script: str,
           script_args: Sequence[str] = (),
           coordinator: Optional[str] = None, port: Optional[int] = None,
           python: str = sys.executable, ssh_cmd: str = "ssh",
           simulate_devices: int = 0,
           extra_env: Optional[Dict[str, str]] = None) -> ProcessMonitor:
    """Start the full host×nproc process group and return its monitor
    (fail-fast `.wait()`, group `.terminate()`).

    Remote coordinators default to a port DERIVED from the job identity
    (hash of script/hosts/nproc/cwd, range 29400-30399) — stable across
    re-launches of the same job, distinct for different jobs sharing a
    head host (a locally-probed free port says nothing about the remote
    head). Open that range on the head's firewall, or pass an explicit
    ``port``. Two concurrent IDENTICAL jobs still need distinct ports."""
    hosts = list(hosts)
    if nproc > 1 and not simulate_devices:
        raise ValueError(
            f"zoo-launch: --nproc {nproc} needs --simulate-devices. Every "
            "process started on a TPU host sees all of its chips, a chip "
            "belongs to one process at a time, and this launcher does not "
            "set the per-process chip bounds libtpu reads — run one "
            "process per host (it drives every chip of the host through "
            "the mesh), or simulate with CPU devices")
    if coordinator is None:
        head = hosts[0].split("@")[-1]
        if _is_local(hosts[0]):
            # loopback: probe a genuinely free local port
            head = "127.0.0.1"
            coordinator = f"{head}:{port or _free_port()}"
        else:
            # remote coordinator: a port probed by binding LOCALLY says
            # nothing about the remote host. Derive a stable per-job port
            # from (script, hosts, nproc, cwd) in 29400-30399 so two
            # DIFFERENT jobs sharing a head host don't silently rendezvous
            # into one process group; identical re-launches keep the same
            # port (the conventional-fixed-port property that matters for
            # firewalls). Callers needing two concurrent identical jobs
            # must pass distinct ports.
            if port is None:
                import hashlib
                digest = hashlib.sha1(
                    f"{script}|{','.join(hosts)}|{nproc}|{os.getcwd()}"
                    .encode()).digest()
                port = 29400 + int.from_bytes(digest[:2], "big") % 1000
            coordinator = f"{head}:{port}"
    cmds = build_commands(hosts, nproc, coordinator, script, script_args,
                          python=python, ssh_cmd=ssh_cmd,
                          extra_env=extra_env,
                          simulate_devices=simulate_devices)
    procs = [subprocess.Popen(argv, env=env) for argv, env in cmds]
    return ProcessMonitor(procs)


def _bootstrap_devices(n: int, script: str, script_args: Sequence[str]):
    """Worker-side simulate-mode entry: force the CPU backend with n
    virtual devices (`force_cpu_devices`), then run the user script as
    __main__."""
    import runpy

    from analytics_zoo_tpu.common.cluster import force_cpu_devices
    force_cpu_devices(n)
    sys.argv = [script, *script_args]
    runpy.run_path(script, run_name="__main__")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["--bootstrap-devices"]:
        _bootstrap_devices(int(argv[1]), argv[2], argv[3:])
        return 0
    p = argparse.ArgumentParser(
        prog="zoo-launch",
        description="Launch a training script across hosts "
                    "(jax.distributed rendezvous env + supervision).")
    p.add_argument("--hosts", default=None,
                   help="comma-separated host list (default: TPU pod "
                        "autodetect, else localhost)")
    p.add_argument("--nproc", type=int, default=1,
                   help="processes per host (>1 only with "
                        "--simulate-devices)")
    p.add_argument("--coordinator", default=None,
                   help="host:port rendezvous (default: first host + "
                        "free/default port)")
    p.add_argument("--port", type=int, default=None,
                   help="coordinator port when derived from --hosts")
    p.add_argument("--python", default=sys.executable)
    p.add_argument("--ssh-cmd", default="ssh",
                   help="remote-exec command (e.g. 'kubectl exec -i')")
    p.add_argument("--simulate-devices", type=int, default=0,
                   help="N>0: force JAX_PLATFORMS=cpu with N virtual "
                        "devices per process (local pod simulation)")
    p.add_argument("--timeout", type=float, default=None)
    p.add_argument("script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)

    hosts = ([h.strip() for h in args.hosts.split(",") if h.strip()]
             if args.hosts else detect_hosts())
    mon = launch(hosts, args.nproc, args.script, args.script_args,
                 coordinator=args.coordinator, port=args.port,
                 python=args.python, ssh_cmd=args.ssh_cmd,
                 simulate_devices=args.simulate_devices)
    codes = mon.wait(args.timeout)
    return max(codes) if codes else 0


if __name__ == "__main__":
    sys.exit(main())
