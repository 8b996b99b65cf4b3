"""A benchmark cell's device time by the program's own scopes: the
builder's tool (PERF.md section 5 is made with it).

    chiprun -- python scripts/cell_device_time.py --workload <cell> [--seed n]

Builds the cell's model and data as `benchmark/runners/fit.py` does
(without the checks against the reference), warms the epoch program up
with one fit, then runs `Estimator.fit(epochs=3, profile_steps=...)`
around the second of three epochs. The fit itself writes
`device_time_by_scope.json` beside the capture
(`analytics_zoo_tpu/observability/device_time.py`); this script prints
the rows and holds them to the benchmark's own reduction of the SAME
capture (`benchmark/trace_reduce.py`): the summed operation time, and
each `*_time_share` pattern of the cell against the rows of the kernels
it names. It also says what the request cost: making the table (lower,
compile or cache load, text, parse) and reducing the capture. The last
line is one JSON object; the report stays under `--out`.

`--rehearse` runs the tiny CPU sizes: the path, never a device number."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=5)
    p.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "device_time"))
    p.add_argument("--depth", type=int, default=None)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--keep-capture", action="store_true",
                   help="leave the profiler's own files beside the report")
    p.add_argument("--hlo-text", action="store_true",
                   help="write the step program's compiled text too")
    args = p.parse_args(argv)

    from benchmark import harness, trace_reduce
    from benchmark.runners.fit import _loss, _optimizer
    cell = harness.load_cell(args.workload, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
    else:
        harness.enable_compile_cache()
    device = harness.require_device(int(cell["cell"]["chips"]),
                                    args.rehearse)

    import jax
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.learn import trainer
    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.observability import device_time

    config, traffic = cell["config"], cell["traffic"]
    family = importlib.import_module("benchmark.models." + config["family"])
    init_orca_context(cluster_mode="local", **traffic.get("mesh_axes", {}))
    model = family.build(config, traffic)
    model.params = family.init_params(model, harness.seed_key(args.seed))
    data, n = family.fit_data(config, traffic, args.seed)
    est = Estimator.from_keras(
        model, optimizer=_optimizer(config["fit"]["optimizer"]),
        loss=_loss(config["fit"]["loss"]))
    fit_kw = dict(batch_size=traffic["batch_size"],
                  seed=args.seed % (2 ** 31 - 1),
                  **traffic.get("fit_kwargs", {}))
    est.fit(data, epochs=1, **fit_kw)
    jax.block_until_ready(est.model.params)

    t0 = time.perf_counter()
    table = trainer.program_scopes(est.model)
    table_s = time.perf_counter() - t0
    steps = traffic["steps_per_epoch"]
    out = os.path.join(args.out, args.workload)
    hist = est.fit(data, epochs=3, profile_steps=(steps, 2 * steps),
                   profile_dir=out, **fit_kw)
    jax.block_until_ready(est.model.params)
    (art,) = hist["profile_artifacts"]

    t0 = time.perf_counter()
    report = device_time.reduce_capture(art, table)
    reduce_s = time.perf_counter() - t0
    rows = report["rows"]
    print(device_time.format_rows(device_time.at_depth(rows, args.depth)))

    # the benchmark's own reduction of the same capture
    patterns = harness.op_patterns_for(cell["per_layer"])
    reduced = trace_reduce.reduce_trace(
        trace_reduce.load_xplane(device_time.find_xplane(art)),
        "no annotation: the device events' extent", patterns, top=10 ** 9)
    op_time = sum(t for _, t in reduced.get("device_ops", []))
    # the rows of the Pallas kernels: a kernel's instruction is named
    # after it and its row's last part is that name
    kernel_names = {
        entry["scope"].split("/")[-1] for entries in table.values()
        for name, entry in entries.items()
        if entry["scope"].split("/")[-1] == re.sub(r"\.\d+$", "", name)}
    kernels = {}
    for name, pattern in patterns.items():
        rx = re.compile(pattern)
        mine = sum(r["share_pct"] for r in rows
                   if r["scope"].split("/")[-1] in kernel_names
                   and rx.search(r["scope"].split("/")[-1]
                                 + "@tpu_custom_call"))
        kernels[name] = {"rows_pct": mine,
                         "trace_reduce_pct":
                             100.0 * reduced["op_share"].get(name, 0.0)}
    by = {r["scope"]: r["share_pct"] for r in device_time.at_depth(
        [dict(r, direction="") for r in rows], None)}
    if args.hlo_text:
        program = est.model._train_cache[2]
        with open(os.path.join(out, "step_program.hlo.txt"), "w") as fh:
            fh.write(program.jitted.lower(
                *program.abstract_args).compile().as_text())
    if not args.keep_capture:
        shutil.rmtree(os.path.join(art, "plugins"), ignore_errors=True)
    print(json.dumps({
        "workload": args.workload, "device": device,
        "device_source": report["device_source"],
        "instructions_in_table": sum(len(t) for t in table.values()),
        "table_s": table_s, "reduce_capture_s": reduce_s,
        "rows_total_s": report["total_s"], "trace_reduce_op_s": op_time,
        "shares_sum_pct": sum(r["share_pct"] for r in rows),
        "unmatched_pct": by.get("unmatched", 0.0),
        "unscoped_pct": by.get("unscoped", 0.0),
        "mixed_pct": 100.0 * sum(r["mixed_s"] for r in rows)
        / report["total_s"] if report["total_s"] else 0.0,
        "kernels": kernels, "capture": os.path.relpath(art, ROOT)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
