"""A builder's tool, not the contract's command: the two sets of runs
from which a bound is set.

    python3 benchmark/sets.py --workload <cell> [--runs 6] [--sets 2] \
        [--seconds <run_seconds>] [--out chiprun_out/sets]

Runs the benchmark's command `runs` times a set, each run of a set with
another seed and the same seeds in every set, one process after another
(this process never touches jax, so each child gets the chip). Prints for
every end-to-end metric each set's values, median and spread (distance
between the first and third quartile of `statistics.quantiles(n=4)` as a
share of the median), and the wider spread. The first run of a call
compiles or loads the cache into memory; it is run once before the sets
and reported apart, as the driver does with each side's first run."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED0 = 2147483659          # more than 32 signed bits hold


def run_once(workload, seed, seconds, trace=0):
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    lines = [ln for ln in res.stdout.splitlines() if ln.startswith("{")]
    if res.returncode != 0 or not lines:
        sys.stderr.write(res.stderr[-2000:])
        raise SystemExit(f"{workload} seed={seed}: rc={res.returncode}")
    return json.loads(lines[-1]), res.stdout


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "sets"))
    p.add_argument("--skip-first", action="store_true",
                   help="the cache is already warm: no run apart")
    p.add_argument("--traced", action="store_true",
                   help="end with one --trace 1 run")
    args = p.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        seconds = args.seconds or json.load(fh)["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, args.workload + ".jsonl"), "a")

    def record(tag, seed, line, stdout):
        log.write(json.dumps({"tag": tag, "seed": seed, "seconds": seconds,
                              "line": line}) + "\n")
        log.flush()
        with open(os.path.join(args.out, args.workload + ".stdout"), "a") as f:
            f.write(f"#### {tag} seed={seed}\n" + "\n".join(
                ln for ln in stdout.splitlines()
                if not ln.startswith("epoch_losses")) + "\n")

    if not args.skip_first:
        line, out = run_once(args.workload, SEED0 - 1, seconds)
        record("first", SEED0 - 1, line, out)
        print(f"first run (compiles or loads): setup_s="
              f"{line['metrics']['setup_s']['value']:.2f} "
              f"correct={line['correct']}", flush=True)
    sets = []
    for s in range(args.sets):
        rows = []
        for r in range(args.runs):
            line, out = run_once(args.workload, SEED0 + r, seconds)
            record(f"set{s}", SEED0 + r, line, out)
            rows.append(line)
            print(f"set{s} run{r} correct={line['correct']} failed="
                  f"{line['failed']}/{line['attempted']} " + " ".join(
                      f"{k}={v['value']:.6g}"
                      for k, v in line["metrics"].items())
                  + f" mem={line['device']['memory_peak_bytes'] / 1e9:.2f}GB",
                  flush=True)
        sets.append(rows)
    print(f"== {args.workload} seconds={seconds} runs={args.runs}")
    for name in sets[0][0]["metrics"]:
        per_set = [[row["metrics"][name]["value"] for row in rows]
                   for rows in sets]
        med = [statistics.median(v) for v in per_set]
        spr = [spread(v) if len(v) >= 2 else float("nan") for v in per_set]
        print(f"  {name}: medians={[round(m, 6) for m in med]} spreads="
              f"{[round(100 * s, 4) for s in spr]}% widest="
              f"{100 * max(spr):.4f}% -> 5x = {500 * max(spr):.3f}% "
              f"set1/set0 median={med[-1] / med[0]:.5f}", flush=True)
    if args.traced:
        line, out = run_once(args.workload, SEED0 + 100, seconds, trace=1)
        record("traced", SEED0 + 100, line, out)
        print("traced " + json.dumps(line)[:6000], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
