"""The per-layer readers on hand-made sources: each takes its number
from where its metric's file says, and returns nothing where there is
nothing to read."""

import pytest

from benchmark import harness
from benchmark.readers import harness as harness_reader
from benchmark.readers import memory_stats, registry, trace_idle, \
    trace_op_roofline, trace_op_share


def _snap(wait_sum=None):
    if wait_sum is None:
        return {}
    return {"training_input_wait_ms": {"kind": "histogram", "series": [
        {"labels": {}, "count": 3, "sum": wait_sum, "p50": 1.0}]}}


SRC = {"registry_before": _snap(50.0), "registry_after": _snap(350.0),
       "window_s": 10.0}
WAIT = {"name": "fit_input_wait_share"}


def test_registry_input_wait_share_is_the_windows_growth_over_the_window():
    spec = harness.reader_spec(WAIT)
    assert registry.read(spec, SRC) == pytest.approx(100 * 0.3 / 10.0)
    # device-resident epochs never touch the prefetch queue: zero, said so
    no_wait = dict(SRC, registry_after=_snap(), registry_before=_snap())
    assert registry.read(spec, no_wait) == 0.0
    assert registry.read(dict(spec, absent_is_zero=False), no_wait) is None


def test_an_unknown_registry_statistic_is_an_error():
    with pytest.raises(ValueError):
        registry.read(dict(harness.reader_spec(WAIT), statistic="p17"), SRC)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    assert registry.read(harness.reader_spec(WAIT), {}) is None
    assert memory_stats.read({}, {"memory_peak_bytes": 0}) is None
    assert memory_stats.read({}, {"memory_peak_bytes": 13.2e9}) == 13.2
    assert harness_reader.read({"key": "mfu_pct"}, {"harness": {}}) is None
    assert trace_op_share.read({"name": "x"}, {"trace": {}}) is None
    assert trace_idle.read({}, {"trace": {}}) is None


def test_trace_readers_take_a_device_trace_and_never_the_cpu_stand_in():
    red = {"busy_s": 1.0, "window_s": 2.0, "idle_share": 0.5,
           "op_share": {"x": 0.25}, "device_source": "device"}
    assert trace_op_share.read({"name": "x"}, {"trace": red}) == 25.0
    assert trace_idle.read({}, {"trace": red}) == 50.0
    cpu = dict(red, device_source="cpu_thunks")
    assert trace_op_share.read({"name": "x"}, {"trace": cpu}) is None
    assert trace_idle.read({}, {"trace": cpu}) is None


def test_a_kernels_roofline_share_is_its_counted_work_over_its_seconds():
    spec = harness.reader_spec({"name": "flash_attention_roofline"})
    assert spec["reader"] == "trace_op_roofline"
    assert spec["work"] == "attention"
    src = {"trace": {"device_source": "device", "busy_s": 4.0,
                     "op_seconds": {"flash_attention_roofline": 2.0}},
           "traced_work": {"attention": {"flops": 98.5e12, "bytes": 1e9}},
           "device_kind": "TPU v5 lite", "chips": 1}
    # 98.5 TFLOP need 0.5 s at 197 TFLOP/s; the kernels took 2 s
    assert trace_op_roofline.read(spec, src) == pytest.approx(25.0)
    for gone in ({"traced_work": {}},
                 {"trace": dict(src["trace"], op_seconds={})},
                 {"trace": dict(src["trace"], device_source="cpu_thunks")}):
        assert trace_op_roofline.read(spec, dict(src, **gone)) is None
    # its pattern reaches the reducer with flash_time_share's
    assert set(harness.op_patterns_for(
        [{"name": "flash_time_share"}, {"name": "flash_attention_roofline"},
         WAIT])) == {"flash_time_share", "flash_attention_roofline"}


def test_a_metrics_file_holds_its_reader_and_nothing_benchmark_json_says():
    spec = harness.reader_spec({"name": "flash_time_share"})
    assert spec["reader"] == "trace_op_share" and spec["pattern"]
    assert spec["name"] == "flash_time_share"
    assert harness.op_patterns_for(
        [{"name": "flash_time_share"}, WAIT]
    ) == {"flash_time_share": spec["pattern"]}


def test_read_per_layer_leaves_out_a_metric_with_nothing_to_read():
    per_layer = [{"name": "fit_mfu", "unit": "%"},
                 {"name": "fit_compiles_in_window", "unit": "count"}]
    out = harness.read_per_layer(
        per_layer, {"harness": {"compiles_in_window": 0}})
    assert out == {"fit_compiles_in_window": {"value": 0.0, "unit": "count"}}
