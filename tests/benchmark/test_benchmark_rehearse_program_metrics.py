"""Every cell of BENCHMARK.json through the benchmark's own command with
`--rehearse --trace 1`: its line carries the five per-layer metrics that
read the program's own spans and phase counters (the three shares of
`training_fit_phase_ms` and the two statistics of the span ring), and a
four-chip cell runs on four (virtual) devices over the streamed input
path."""

import json
import os

import pytest

from test_benchmark_rehearse_fit import ROOT, last_line, run_cell

PROGRAM_METRICS = {"fit_per_call_share": "%", "fit_dispatch_share": "%",
                   "fit_loss_sync_share": "%",
                   "fit_epoch_slowest_over_median": "ratio",
                   "fit_trace_overhead": "%"}


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return [(w["name"], w["chips"]) for w in json.load(fh)["workloads"]]


@pytest.mark.parametrize("cell,chips", _cells())
def test_a_traced_rehearsal_reports_the_program_metrics(cell, chips):
    res = run_cell("--workload", cell, "--seed", str(2 ** 31 + 11),
                   "--seconds", "1", "--trace", "1", "--rehearse")
    line = last_line(res)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == chips
    got = line["metrics"]
    for name, unit in PROGRAM_METRICS.items():
        assert got[name]["unit"] == unit, name
    shares = [got[n]["value"] for n in ("fit_per_call_share",
                                        "fit_dispatch_share",
                                        "fit_loss_sync_share")]
    assert all(0 < v < 100 for v in shares) and sum(shares) <= 100.5
    assert got["fit_epoch_slowest_over_median"]["value"] >= 1.0
    assert got["fit_compiles_in_window"]["value"] == 0
    if chips > 1:
        # no device cache on a mesh: the loop waits on the prefetch queue
        assert got["fit_input_wait_share"]["value"] > 0
    else:
        assert got["fit_input_wait_share"]["value"] == 0
    said = [ln for ln in res.stdout.splitlines()
            if ln.startswith("span_ring fit-")]
    assert len(said) == 1 and "epoch_ms=[" in said[0]
