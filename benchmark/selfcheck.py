"""A builder's tool, not the contract's command: shows that the
comparison which decides `correct` CAN fail.

    python3 benchmark/selfcheck.py --workload <cell> [--rehearse]

At the seeded weights and samples of the cell's reference check it
prints the errors against the plain float32 reference of (1) the system
as it runs, which must be inside the tolerance, (2) the system with
int8-quantized weights (`serving/quantization.py`), and (3) the system
against a reference with one term dropped (the family's FAULTS); (2) and
(3) must be outside it. It does so twice: for the forward (outputs) and
for the first training step (loss and gradients), where the gradient
with one leaf dropped (set to zero) and with one leaf at half its size
must fail; the reference with a term dropped is printed there too, but
need not fail: at the seeded weights NeuralCF's GMF branch adds little
to the gradient, and the forward check is what catches it. A gradient rounded to 4 bits of mantissa, as a backward
in an 8-bit float would leave it, is printed beside them: whether it
fails says how coarse a type the tolerance still tells from bfloat16.
Exit code 0 only if every line is as it must be."""

from __future__ import annotations

import argparse
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--samples", type=int, default=None,
                   help="more samples than the cell's check uses")
    args = p.parse_args(argv)
    from benchmark import harness
    cell = harness.load_cell(args.workload, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        harness.enable_compile_cache()
    import jax
    import numpy as np
    device = harness.require_device(int(cell["cell"]["chips"]),
                                    args.rehearse)
    config, traffic = cell["config"], cell["traffic"]
    from analytics_zoo_tpu import init_orca_context
    init_orca_context(cluster_mode="local", **traffic.get("mesh_axes", {}))
    family = importlib.import_module("benchmark.models." + config["family"])
    model = family.build(config, traffic)
    params = family.init_params(model, harness.seed_key(args.seed))
    chk = config["reference_check"]
    from benchmark import compare
    x = family.check_inputs(config, traffic, args.seed,
                            args.samples or chk["samples"])
    want = family.reference_outputs(params, x, config)

    def err(got):
        return compare.errors(got, want)

    got = family.system_outputs(model, params, x)
    results = {"system_as_run": (err(got), True)}
    from analytics_zoo_tpu.serving.quantization import quantize_model_params
    qparams = quantize_model_params(model, jax.device_get(params))
    results["int8_weights"] = (
        err(family.system_outputs(model, qparams, x)), False)
    for name, fault in family.FAULTS.items():
        faulty = family.reference_outputs(params, x, config, **fault)
        results[name] = (compare.errors(got, faulty), False)
    steps = step_results(family, model, config, traffic, params, args.seed)
    ok = True
    print(f"selfcheck {args.workload} device={device} samples={len(x)} "
          f"atol={chk['atol']} rms={chk['rms']} "
          f"output_rms={float(np.sqrt(np.mean(want ** 2))):.4f}")
    for name, (e, must_pass) in results.items():
        passed = compare.within(e, chk)
        ok &= passed == must_pass
        print(f"  {name}: max_abs_err={e['max_abs_err']:.4e} "
              f"rms_err={e['rms_err']:.4e} "
              f"{'passes' if passed else 'FAILS'} the comparison "
              f"({'must pass' if must_pass else 'must fail'})")
    print(f"  training step: loss_atol={chk['loss_atol']} "
          f"grad_rel={chk['grad_rel']} grad_leaf_rel={chk['grad_leaf_rel']}")
    for name, (e, must_pass) in steps.items():
        passed = compare.step_within(e, chk)
        ok &= must_pass is None or passed == must_pass
        print(f"  step {name}: loss_abs_err={e['loss_abs_err']:.4e} "
              f"grad_rel_err={e['grad_rel_err']:.4e} "
              f"grad_leaf_rel_err={e['grad_leaf_rel_err']:.4e} "
              f"{'passes' if passed else 'FAILS'} "
              f"({ {True: 'must pass', False: 'must fail'}.get(must_pass, 'either') })")
    return 0 if ok else 1


def step_results(family, model, config, traffic, params, seed):
    """{what: (step errors, must it pass)} of the training-step check."""
    import jax
    import numpy as np
    from benchmark import compare
    from benchmark.runners import fit
    n = config["reference_check"]["step_samples"]
    batch = family.step_batch(config, traffic, seed, n)
    loss, grads = fit.system_step(family, model, config, traffic, params,
                                  batch, n)
    ref = family.reference_loss_and_grads(params, batch, config)
    out = {"system_as_run": (compare.step_errors(loss, grads, *ref), True)}
    for name, fault in family.FAULTS.items():
        out[name] = (compare.step_errors(
            loss, grads, *family.reference_loss_and_grads(
                params, batch, config, **fault)), None)
    # one leaf of the system's gradient dropped, and one at half its
    # size: the leaf with the median norm among those counted alone
    leaves, tree = jax.tree_util.tree_flatten(grads)
    ref_leaves = jax.tree_util.tree_leaves(ref[1])
    norms = np.array([float(np.linalg.norm(np.asarray(g, np.float64)))
                      for g in ref_leaves])
    alone = np.flatnonzero(norms >= compare.LEAF_FLOOR
                           * np.sqrt((norms ** 2).sum()))
    victim = int(alone[np.argsort(norms[alone])[len(alone) // 2]])
    for name, scale in (("one_gradient_dropped", 0.0),
                        ("one_gradient_halved", 0.5)):
        faulty = [g * scale if i == victim else g
                  for i, g in enumerate(leaves)]
        out[name] = (compare.step_errors(
            loss, jax.tree_util.tree_unflatten(tree, faulty), *ref), False)

    def to_4_bits(g):
        m, e = np.frexp(np.asarray(g, np.float64))
        return np.ldexp(np.round(m * 16) / 16, e)
    out["gradient_rounded_to_4_bits"] = (compare.step_errors(
        loss, jax.tree_util.tree_map(to_4_bits, grads), *ref), None)
    return out


if __name__ == "__main__":
    sys.exit(main())
