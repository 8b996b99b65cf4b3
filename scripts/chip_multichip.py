#!/usr/bin/env python
"""Four chips, one process: the checks chip_smoke.py cannot make on one.

    chiprun --chips 4 -- bash -c "python scripts/chip_multichip.py && \
        python scripts/chip_multichip.py processes"

Run by a builder, not by the driver; it prints what it saw and exits
non-zero if a device of the host was left without state or work.

  data=4     chip_smoke's fit stage under init_orca_context(data=4): params
             replicated on all four chips, each batch split four ways.
  fsdp=4     the same under fsdp=4 with sharding_rules=True: every chip
             holds about a quarter of params + optimizer state.
  replicas   InferenceModel(num_replicas=4) over a CompileCache: one entry
             per bucket, compiled for replica 0 and re-pinned onto the
             other three chips (`compile_cache/serialization.py`), every
             replica answering, answers equal to model.predict.
  processes  (own invocation, the parent stays off jax) two processes
             started together with nothing set, then four with one chip
             each through the bounds libtpu reads: what `zoo-launch
             --nproc` and the fleet's engine processes would need.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


def _bytes_in_use():
    return {d.id: d.memory_stats()["bytes_in_use"] for d in jax.devices()}


def _report_memory(label, before):
    after = _bytes_in_use()
    grown = {i: after[i] - before[i] for i in after}
    print(f"{label}: bytes_in_use growth per device "
          f"{ {i: round(g / 2**20) for i, g in grown.items()} } MiB",
          flush=True)
    return grown


def fit_stages(compiles):
    from analytics_zoo_tpu.common.context import stop_orca_context
    from analytics_zoo_tpu.observability.memwatch import tree_device_bytes

    before = _bytes_in_use()
    est = chip_smoke.stage_fit(compiles, mesh_axes=dict(data=4))
    grown = _report_memory("data=4 fit", before)
    assert min(grown.values()) > 0.9 * max(grown.values()), (
        f"replicated fit left devices uneven: {grown}")
    replicated = tree_device_bytes(est.model.params)

    stop_orca_context()
    est_fsdp = chip_smoke.stage_fit(compiles,
                                    mesh_axes=dict(data=1, fsdp=4),
                                    sharding_rules=True)
    sharded = tree_device_bytes(est_fsdp.model.params)
    print(f"params bytes per device: replicated "
          f"{ {k: round(v / 2**20) for k, v in replicated.items()} } MiB, "
          f"fsdp=4 { {k: round(v / 2**20) for k, v in sharded.items()} } MiB",
          flush=True)
    assert len(sharded) == 4
    assert max(sharded.values()) < 0.35 * max(replicated.values()), (
        "fsdp=4 did not cut per-device params to about a quarter")
    return est


def replica_stage(est, n_requests=8, waves=3):
    from analytics_zoo_tpu.compile_cache import CompileCache
    from analytics_zoo_tpu.serving import InferenceModel

    model = est.model
    rs = np.random.RandomState(1)
    batch = rs.randint(0, chip_smoke.BERT_BASE["vocab"],
                       (n_requests, 128)).astype(np.int32)
    want = np.asarray(model.predict(batch, batch_per_thread=n_requests))
    with tempfile.TemporaryDirectory() as cache_dir:
        cache = CompileCache(cache_dir)
        im = InferenceModel(num_replicas=4,
                            compile_cache=cache).load_keras(model)
        try:
            im.warmup(batch[0], buckets=[n_requests])
            print(f"replica warmup sources {im.warmup_source} "
                  f"cache {cache.stats()}", flush=True)
            assert sorted(im.warmup_source.values()) == \
                ["cached", "cached", "cached", "compiled"], im.warmup_source
            assert cache.stats()["load_errors"] == 0
            for _ in range(waves):
                # the router bounds each replica at two batches in flight
                pending = [im.predict_async(batch) for _ in range(8)]
                for p in pending:
                    np.testing.assert_allclose(np.asarray(p.result()), want,
                                               rtol=2e-2, atol=2e-3)
            devices = [rep.device.id for rep in im._replicas]
            batches = [rep.batches for rep in im._replicas]
            print(f"replica devices {devices} batches served {batches}",
                  flush=True)
            assert sorted(devices) == [d.id for d in jax.devices()]
            assert all(b > 0 for b in batches), batches
        finally:
            im.close()
    print("PASS replicas", flush=True)


PROBE = ("import jax; print('devices', [d.id for d in jax.devices()], "
         "float(jax.numpy.ones(8).sum()))")


def _probe(env):
    return subprocess.Popen([sys.executable, "-c", PROBE],
                            env=dict(os.environ, **env), text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def _reap(label, proc, timeout=120):
    """A child that hangs on a held chip is a finding, not a failure of
    this script: every wait is bounded and the child is killed."""
    try:
        out, err = proc.communicate(timeout=timeout)
        tail = (out.strip().splitlines() or err.strip().splitlines()
                or [""])[-1]
        print(f"{label}: rc={proc.returncode} {tail[:300]}", flush=True)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"{label}: no answer in {timeout}s (killed)", flush=True)


def held_chip_probe():
    """A child started while THIS process holds all four chips."""
    _reap("child of a parent that holds the chips", _probe({}), 90)


def process_stage():
    """The parent has not touched jax."""
    pair = [_probe({}) for _ in range(2)]
    for i, proc in enumerate(pair):
        _reap(f"unbound process {i} of 2 started together", proc)
    bound = [_probe({
        "TPU_VISIBLE_CHIPS": str(i), "TPU_VISIBLE_DEVICES": str(i),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{8476 + i}",
        "TPU_MESH_CONTROLLER_PORT": str(8476 + i),
    }) for i in range(4)]
    for i, proc in enumerate(bound):
        _reap(f"process bound to chip {i}", proc)


def main(argv) -> int:
    if argv[1:] == ["processes"]:
        process_stage()
        return 0
    assert jax.default_backend() == "tpu" and len(jax.devices()) == 4, \
        jax.devices()
    compiles = chip_smoke.CompileCounter()
    est = fit_stages(compiles)
    replica_stage(est)
    held_chip_probe()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
