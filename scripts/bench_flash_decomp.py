"""Decompose the flash-attention cost on the real chip: forward alone, and
the backward as ONE kernel (`flash_bwd_fused`) against the dq/dkv pair, at
the tiling `flash_attention` picks for each sequence length.

    python scripts/bench_flash_decomp.py [rate] [T ...] [--D 128] [--Dv 128]
                                         [--causal] [--heads 16]
                                         [--tokens 8192]

The forward line names the column chunk the kernel walks its DMA tile in
(`_fwd_chunk`); `--D 128 --causal --heads 16 --tokens 8192 0 4096` is the
seq-4096 decoder fit's call (no dropout, a mask of zeros), timed alone,
and `--D 192 --Dv 128 --causal --heads 32 --tokens 16384 0 8192` the
expert fit's (keys wider than values).
The gate (`_bwd_fused_vmem_limit`) decides from the shapes which form a
model runs and how much scoped VMEM the one kernel asks for; here both
forms are called directly (the one kernel asks for what the gate would
give it), so the table shows what the gate's choice is worth at every T
(PERF.md, PR 25 and PR 31, hold one each). By default B·T is
held at 32,768 tokens, H = 12, D = 64, bf16. All three gradients are outputs
of the timed call: consuming dq alone lets XLA dead-code-eliminate the pair's
dk/dv kernel and times half a backward (docs/ROOFLINE.md round 5). A lone
jitted kernel reads about 2 ms over its time inside a model's step (operand
copies around the custom call), the same in both forms: compare the forms
here, and take a kernel's own time from the benchmark's traced run.
"""

import argparse
import functools
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

if jax.default_backend() == "tpu":
    jax.config.update("jax_default_prng_impl", "rbg")

from analytics_zoo_tpu.pallas import flash_attention as fa


def timeit(f, *args, iters=10):
    out = jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            g = f(*args)
        jax.block_until_ready(g)
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3, out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("rate", nargs="?", type=float, default=0.1)
    ap.add_argument("lengths", nargs="*", type=int)
    ap.add_argument("--D", type=int, default=64,
                    help="head width (of the keys, where --Dv is given)")
    ap.add_argument("--Dv", type=int, default=None,
                    help="value width (default: --D)")
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--tokens", type=int, default=32768,
                    help="B x T held at this many tokens")
    ap.add_argument("--causal", action="store_true",
                    help="the causal kernels")
    args = ap.parse_args()
    rate, causal = args.rate, args.causal
    lengths = args.lengths or [512, 1024, 2048, 4096]
    H, D = args.heads, args.D
    Dv = args.Dv or D
    # off the chip the kernels run interpreted (rate 0 only: no TPU PRNG):
    # a rehearsal of the script, never a timing
    interpret = jax.default_backend() != "tpu"
    for T in lengths:
        B = max(1, args.tokens // T)
        rs = np.random.RandomState(0)
        q, k, v, dout = (jnp.asarray(rs.randn(B, H, T, d) * 0.5, jnp.bfloat16)
                         for d in (D, D, Dv, Dv))
        mask = jnp.zeros((B, 1, 1, T), jnp.float32)
        seed = jnp.full((1, 1), 7, jnp.int32)
        block = fa._auto_block(T)
        scale = 1.0 / math.sqrt(D)
        # the forward's residuals come back as outputs, so its time here
        # includes a copy of q, k and v that a model does not pay
        ms_fwd, (out, res) = timeit(jax.jit(functools.partial(
            fa._flash_fwd, rate=rate, block_q=block, block_k=block,
            interpret=interpret, causal=causal)), q, k, v, mask, seed)
        flat = tuple(x.reshape(B * H, T, -1) for x in (q, k, v))
        operands = flat + (jnp.repeat(mask[:, 0], H, axis=0), seed,
                           dout.reshape(B * H, T, Dv), res[-1],
                           out.reshape(B * H, T, Dv))
        line = (f"RESULT T {T} B {B} H {H} D {D}"
                f"{f' Dv {Dv}' if Dv != D else ''}"
                f"{' causal' if causal else ''} blocks {block}x{block} "
                f"rate {rate}: fwd {ms_fwd:.2f} ms in chunks of "
                f"{fa._fwd_chunk(block)} columns")
        grads = {}
        for name, form in (("pair", fa._bwd_pair), ("fused", fa._bwd_fused)):
            try:
                ms, grads[name] = timeit(jax.jit(functools.partial(
                    form, rate, scale, block, block, interpret, causal)),
                    operands)
                line += f", bwd {name} {ms:.2f} ms"
            except Exception as e:  # noqa: BLE001
                line += (f", bwd {name} FAILED {type(e).__name__}: "
                         f"{' '.join(str(e).split())[-120:]}")
        if len(grads) == 2:
            worst = max(
                float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32)))
                      / jnp.max(jnp.abs(b.astype(jnp.float32))))
                for a, b in zip(grads["fused"], grads["pair"]))
            line += f", fused-pair largest difference {worst:.1e} of max"
        need = fa._bwd_fused_vmem_need(block, block, T, D, q.dtype.itemsize,
                                       Dv)
        limit = fa._bwd_fused_vmem_limit(block, block, T, D,
                                         q.dtype.itemsize, Dv)
        choice = ("pair" if limit is None else "fused at the default" if
                  not limit else f"fused asking {limit / 2 ** 20:.0f} MiB")
        print(line + f"; the gate runs {choice} (reckoned need "
              f"{need / 2 ** 20:.2f} MiB)", flush=True)


if __name__ == "__main__":
    main()
