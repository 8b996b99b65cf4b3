"""Reader `registry_at_window_start` on hand-made snapshots: what the
process did in set-up, from the registry as the window's start found
it."""

import pytest

from benchmark import harness
from benchmark.readers import registry_at_window_start as reader

OBTAIN_S = harness.reader_spec({"name": "setup_fit_programs_obtain_s"})
COMPILED = harness.reader_spec({"name": "setup_fit_programs_compiled"})


def _snap(*series):
    return {"xla_program_obtain_ms": {"kind": "histogram", "series": [
        {"labels": {"during": during, "how": how}, "count": count,
         "sum": total_ms} for during, how, count, total_ms in series]}}


def test_the_metric_files_name_the_reader_and_the_programs_family():
    for spec in (OBTAIN_S, COMPILED):
        assert spec["reader"] == "registry_at_window_start"
        assert spec["family"] == "xla_program_obtain_ms"
        assert spec["labels"]["during"] == "fit"


def test_a_fresh_checkout_compiled_and_says_how_long_it_waited():
    src = {"registry_before": _snap(("fit", "compile", 3, 90500.0),
                                    ("fit", "cache_load", 40, 2500.0),
                                    ("other", "compile", 7, 30000.0))}
    # both ways of getting a program, the fit's alone, as seconds
    assert reader.read(OBTAIN_S, src) == pytest.approx(93.0)
    assert reader.read(COMPILED, src) == 3.0


def test_a_warm_checkout_reads_zero_and_not_nothing():
    warm = {"registry_before": _snap(("fit", "cache_load", 43, 31200.0),
                                     ("other", "compile", 1, 12.0))}
    assert reader.read(COMPILED, warm) == 0.0
    assert reader.read(OBTAIN_S, warm) == pytest.approx(31.2)
    # the family is there and set-up obtained nothing at all
    assert reader.read(COMPILED, {"registry_before": _snap()}) == 0.0
    assert reader.read(OBTAIN_S, {"registry_before": _snap()}) == 0.0


def test_a_program_without_the_family_gives_nothing():
    for src in ({}, {"registry_before": None}, {"registry_before": {
            "training_fit_phase_ms": {"kind": "histogram", "series": []}}}):
        assert reader.read(OBTAIN_S, src) is None
        assert reader.read(COMPILED, src) is None


def test_an_unknown_statistic_is_an_error():
    with pytest.raises(ValueError):
        reader.read(dict(OBTAIN_S, statistic="p50"),
                    {"registry_before": _snap()})
