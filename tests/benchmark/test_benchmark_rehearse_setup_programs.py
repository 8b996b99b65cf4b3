"""Cells of BENCHMARK.json through the benchmark's own command with
`--rehearse --trace 1`, as test_benchmark_rehearse_program_metrics.py
runs them: the line carries the two per-layer metrics of `setup_s` that
read the program's record of how it got its executables
(`xla_program_obtain_ms`, observability/programs.py). A rehearsal has no
persistent cache, so every program of its fit calls was compiled."""

import json
import re

import pytest

from test_benchmark_rehearse_fit import last_line, run_cell


@pytest.mark.parametrize("cell", ["bert-base.fit-seq128",
                                  "ncf-ml20m.fit-b1m"])
def test_a_traced_rehearsal_reports_how_set_up_got_its_fit_programs(cell):
    res = run_cell("--workload", cell, "--seed", str(2 ** 31 + 13),
                   "--seconds", "1", "--trace", "1", "--rehearse")
    got = last_line(res)["metrics"]
    waited, compiled = (got["setup_fit_programs_obtain_s"],
                        got["setup_fit_programs_compiled"])
    assert (waited["unit"], compiled["unit"]) == ("s", "count")
    # the step check's program and the epoch program at the least
    assert compiled["value"] >= 2 and compiled["value"] % 1 == 0
    parts = json.loads(re.search(r"^setup_parts_s (.*)$", res.stdout,
                                 re.M).group(1))
    assert 0 < waited["value"] < parts["step_check"] \
        + parts["warmup_fit_compile_or_cache_load"] + parts["warmup_epoch"]
