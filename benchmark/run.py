"""The benchmark's command.

    python3 benchmark/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

One process runs one cell on the machine it is started on: it finds the
cell in BENCHMARK.json, loads `benchmark/configs/<config>.json` and
`benchmark/traffic/<traffic>.json` by name, and hands them to the runner
of the traffic's `kind` (`benchmark/runners/<kind>.py`). The last line
of standard output is the result: one JSON object. Without a TPU it
prints no result and exits 3.

Two arguments are the builder's tools and not part of the contract:
`--rehearse` runs the cell at the tiny sizes its files give under
`rehearsal`, on the CPU, and says `cpu` in its device line (the tier-1
tests use it); `--benchmark-file` names another BENCHMARK.json, to
rehearse a cell that is not committed."""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="length of the measured window (default: "
                        "BENCHMARK.json's run_seconds)")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny CPU rehearsal; never a device number")
    p.add_argument("--benchmark-file", default=None,
                   help="another BENCHMARK.json (rehearsing a cell that "
                        "is not committed)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmark import harness

    cell = harness.load_cell(args.workload, args.rehearse,
                             args.benchmark_file)
    chips = int(cell["cell"]["chips"])
    if args.rehearse:
        # BEFORE jax starts: the CPU, with as many virtual devices as
        # the cell has chips, and no persistent cache of tiny programs
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}"
            ).strip()
        os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
    else:
        harness.enable_compile_cache()

    device = harness.require_device(chips, args.rehearse)
    clock = harness.SetupClock(T_PROCESS_START)
    seconds = float(args.seconds if args.seconds is not None
                    else cell["bench"]["run_seconds"])
    ctx = dict(cell, workload=args.workload, seed=int(args.seed),
               seconds=seconds, trace=bool(args.trace),
               rehearse=args.rehearse, chips=chips,
               device=device, clock=clock,
               compiles=harness.CompileCounter())
    harness.log(f"cell {args.workload} seed={args.seed} seconds={seconds} "
                f"trace={args.trace} device={device}")
    line = harness.run_kind(cell["traffic"]["kind"])(ctx)
    sys.stdout.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
