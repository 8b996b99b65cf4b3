"""One tiny fit cell per model family through the benchmark's own
command with `--rehearse`, as a child that pins itself to the CPU before
jax starts (it can never touch a TPU): the last line has the contract's
keys and names the CPU as its device."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_cell(*args, timeout=420):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


def last_line(res):
    assert res.returncode == 0, res.stderr[-3000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["device"]["platform"] == "cpu"      # said truthfully
    assert line["device"]["count"] >= 1
    assert "memory_peak_bytes" in line["device"]
    return line


@pytest.mark.parametrize("cell,trace", [
    ("bert-base.fit-seq128", 1), ("ncf-ml20m.fit-b1m", 0),
    ("bert-base-pos2048.fit-seq2048-flash", 1)])
def test_tiny_fit_cell_prints_the_contracts_last_line(cell, trace):
    res = run_cell("--workload", cell, "--seed", str(2 ** 31 + 7),
                   "--seconds", "1", "--trace", str(trace), "--rehearse")
    line = last_line(res)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1           # whole epochs in ONE fit call
    if trace:
        assert line["metrics"]["fit_compiles_in_window"]["value"] == 0
        assert line["metrics"]["fit_input_wait_share"]["unit"] == "%"
        # a rehearsal's numbers never stand under a device metric's name
        assert "fit_device_idle_share" not in line["metrics"]
        assert "fit_mfu" not in line["metrics"]
        assert "flash_time_share" not in line["metrics"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert line["device"]["window_s"] > 0
    else:
        assert set(line["metrics"]) == {"fit_samples_per_s", "setup_s"}
        assert line["metrics"]["fit_samples_per_s"]["value"] > 0
    # the forward and the first training step were held to the reference
    for check in ("reference_check ", "step_check "):
        said = [ln for ln in res.stdout.splitlines() if ln.startswith(check)]
        assert said and said[0].endswith("ok=True"), said
    assert any(ln.startswith("epoch_losses ")
               for ln in res.stdout.splitlines())
    assert any(ln.startswith("setup_parts_s ")
               for ln in res.stdout.splitlines())


def test_without_a_tpu_and_without_rehearse_there_is_no_result():
    res = run_cell("--workload", "bert-base.fit-seq128", "--seed", "1",
                   "--seconds", "1", "--trace", "0", timeout=240)
    assert res.returncode == 3
    assert not any(ln.startswith("{") for ln in res.stdout.splitlines())
    assert "no result" in res.stderr
