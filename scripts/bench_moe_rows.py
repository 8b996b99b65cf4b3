"""The expert layer's row kernels alone (`pallas/moe_rows.py`), timed on the
chip by the device trace at the expert cells' widths and held shares.

    python scripts/bench_moe_rows.py [--tree DIR] [--out FILE]    # on a TPU host

Each case draws a drop-free dispatch of N tokens, k slots each, with a
share of the slots held (the layer's `_dispatch` order: held slots first),
and times the layer's four row moves, each its own program: the dispatch
forward (`gather`), the combine forward (`combine`, weighted), the
dispatch's backward (`combine`) and the combine's backward (`gather` with
each place's weight and the weight's gradient), and, where the tree has
one, the combine's list of held rows (`plan`). It reports each kernel's
device time a call and in ns a held row, beside the least time the bytes
it must move take at the chip's HBM bandwidth (`benchmark/peaks.json`).

`--tree DIR` imports the package from another checkout (the parent
commit, unpacked): the same cases, draws and clock for both sides. The
last line of the output is one JSON object; `--out` keeps it too."""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (name, N, k, H, held share): the expert cells' widths (PERF.md section 4)
CASES = [
    ("smallthinker-25", 16384, 6, 2560, 0.25),
    ("smallthinker-40", 16384, 6, 2560, 0.40),
    ("lfm2-25", 16384, 4, 2048, 0.25),
    ("lfm2-50", 16384, 4, 2048, 0.50),
    ("kanana-12.5", 16384, 6, 2048, 0.125),
    ("kimi-3.1", 16384, 8, 2304, 0.031),
]
# calls of each program in its trace
REPS = 10
# kernel names as the trace gives them (`<name>.<n>@tpu_custom_call`)
KERNELS = ("moe_rows_gather", "moe_rows_gather_pack", "moe_rows_combine",
           "moe_rows_combine_pack")


def routing(N, k, share, seed):
    """order [N k], position [N, k], held [N, k], count [1]: each slot held
    with probability `share`, a held slot's expert one of 16, sorted held
    first by expert (stable), as `MoEFeedForward._dispatch` sorts."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    held = rng.random((N, k)) < share
    key = np.where(held, rng.integers(0, 16, (N, k)), 16).reshape(-1)
    order = np.argsort(key, kind="stable").astype(np.int32)
    position = np.argsort(order).astype(np.int32).reshape(N, k)
    return (jnp.asarray(order), jnp.asarray(position), jnp.asarray(held),
            jnp.asarray([held.sum()], jnp.int32))


def programs(moe_rows, N, k, H, share, seed):
    """{name: (jitted function, its arguments)} of one case."""
    import jax
    import jax.numpy as jnp
    order, position, held, count = routing(N, k, share, seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    x = jax.random.normal(ks[0], (N, H)).astype(bf)
    g = jax.random.normal(ks[1], (N * k, H)).astype(bf)
    w = jax.random.uniform(ks[2], (N, k), minval=0.05, maxval=1.0)
    ys = jax.random.normal(ks[3], (N * k, H)).astype(bf)
    scale = jnp.ones((N * k,), jnp.float32)
    src = order // k
    new = hasattr(moe_rows, "plan")

    def route():
        return moe_rows.plan(position, held, H, bf) if new else None

    def combine(rows, weights, r):
        if new:
            return moe_rows.combine(rows, count, r, weights)
        return moe_rows.combine(rows, count, position, held, weights)
    progs = {
        "dispatch_fwd": (jax.jit(lambda x: moe_rows.gather(x, src, count)),
                         (x,)),
        "combine_fwd": (jax.jit(lambda r, ys, w: combine(ys, w, r)),
                        (route(), ys, w)),
        "dispatch_bwd": (jax.jit(lambda r, g: combine(g, None, r)),
                         (route(), g)),
        "combine_bwd": (jax.jit(lambda x, ys: moe_rows.gather(
            x, src, count, scale=scale, dot_with=ys)), (x, ys)),
    }
    if new:
        progs["plan"] = (jax.jit(lambda p, h: moe_rows.plan(p, h, H, bf)),
                         (position, held))
    return progs, int(count[0])


def device_times(trace_dir):
    """{operation name: [durations ns]} of the device's op line."""
    sys.path.insert(0, ROOT)
    from benchmark import trace_reduce
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    _, per_dev = trace_reduce.device_op_events(trace)
    out = {}
    for events in per_dev.values():
        for name, _, dur in events:
            out.setdefault(name, []).append(dur)
    return out


def kernel_of(op):
    for k in sorted(KERNELS, key=len, reverse=True):
        if re.match(rf"^{k}\.\d+@", op) or op.startswith(k + "@"):
            return k
    return "xla"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tree", default=None,
                   help="import analytics_zoo_tpu from this checkout")
    p.add_argument("--seed", type=int, default=2147483659)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.tree) if args.tree else ROOT)
    import jax
    from analytics_zoo_tpu.pallas import moe_rows
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"no TPU: {dev.platform}"}))
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peaks = json.load(fh)
    bw = peaks["devices"][dev.device_kind]["hbm_bytes_per_s"]
    rows = []
    for name, N, k, H, share in CASES:
        progs, held = programs(moe_rows, N, k, H, share, args.seed % 2 ** 31)
        for f, a in progs.values():              # compile and warm up
            jax.block_until_ready(f(*a))
        for prog, (f, a) in progs.items():
            with tempfile.TemporaryDirectory() as d:
                with jax.profiler.trace(d):
                    for _ in range(REPS):
                        out = f(*a)
                    jax.block_until_ready(out)
                times = device_times(d)
            per = {}
            for op, durs in times.items():
                kern = kernel_of(op)
                per[kern] = per.get(kern, 0.0) + sum(durs) / REPS
            # the least bytes each kernel must move, at the HBM bandwidth
            words = H * 2                       # a bfloat16 row, as words
            floor = {
                "moe_rows_combine": held * words + N * H * 2,
                "moe_rows_gather": held * (words + H * 2)
                + (held * H * 2 if prog == "combine_bwd" else 0),
            }
            for kern, ns in sorted(per.items()):
                row = {"case": name, "program": prog, "kernel": kern,
                       "us": round(ns / 1e3, 2),
                       "ns_per_held_row": round(ns / max(held, 1), 2),
                       "held_rows": held}
                if kern in floor:
                    row["floor_us"] = round(floor[kern] / bw * 1e6, 2)
                    row["share_of_floor_pct"] = round(
                        100 * floor[kern] / bw * 1e9 / ns, 1)
                rows.append(row)
                print(json.dumps(row), flush=True)
    result = {"device": dev.device_kind, "tree": args.tree or ".",
              "rows": rows,
              "at": time.strftime("%Y-%m-%d %H:%M:%S")}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)
    print(json.dumps({"device": result["device"], "tree": result["tree"],
                      "n_rows": len(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
