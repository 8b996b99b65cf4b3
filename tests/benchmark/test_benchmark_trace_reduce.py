"""The trace reduction on a hand-made trace (every number checkable by
eye) and on a small trace recorded on the chip."""

import os

import pytest

from benchmark import trace_reduce as tr

MS = 1e6  # ns


def _trace():
    dev0 = [["fusion.1", 100 * MS, 100 * MS], ["flash_fwd.2", 250 * MS, 50 * MS],
            ["fusion.3", 400 * MS, 100 * MS],
            # overlaps fusion.3: the union must not count it twice
            ["copy.4", 450 * MS, 20 * MS]]
    dev1 = [["fusion.1", 100 * MS, 400 * MS]]
    host = [["win", 0.0, 600 * MS], ["DevicePut", 305 * MS, 85 * MS],
            ["$loop.py:1 main", 0.0, 600 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": dev0}]},
        {"name": "/device:TPU:1",
         "lines": [{"name": "XLA Ops", "events": dev1}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
    ]}


def test_busy_idle_share_is_the_union_over_the_window_averaged_over_chips():
    red = tr.reduce_trace(_trace(), "win")
    assert red["device_source"] == "device" and red["devices"] == 2
    assert red["window_s"] == pytest.approx(0.6)
    # chip 0: 100 + 50 + 100 (copy.4 lies inside fusion.3); chip 1: 400
    assert red["busy_s"] == pytest.approx((0.25 + 0.4) / 2)
    assert red["idle_share"] == pytest.approx(1 - 0.325 / 0.6)
    assert red["idle_share_worst"] == pytest.approx(1 - 0.25 / 0.6)


def test_op_share_by_pattern_and_top_ops():
    red = tr.reduce_trace(_trace(), "win", {"flash_time_share": "flash"})
    # matched op time over all op time, both devices: 50 / (270 + 400)
    assert red["op_share"]["flash_time_share"] == pytest.approx(50 / 670)
    # matched seconds on one device, averaged over the two
    assert red["op_seconds"]["flash_time_share"] == pytest.approx(0.025)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.25)]
    assert len(red["device_ops"]) == 4


def test_gaps_are_named_by_the_host_span_inside_them():
    gaps = dict(tr.reduce_trace(_trace(), "win")["idle_gaps"])
    # chip 0's gaps: [0,100] [200,250] [300,400] [500,600]; DevicePut
    # fills 85% of the third; elsewhere only the whole-window frame
    assert gaps["win/DevicePut"] == pytest.approx(0.1)
    assert gaps["win/_loop.py_1_main"] == pytest.approx(0.25)
    bare = _trace()
    bare["planes"][2]["lines"][0]["events"].pop()   # no python frames
    gaps = dict(tr.reduce_trace(bare, "win")["idle_gaps"])
    assert gaps == {"win/DevicePut": pytest.approx(0.1),
                    "win/python": pytest.approx(0.25)}


def test_window_defaults_to_the_extent_of_device_events():
    red = tr.reduce_trace(_trace(), "no_such_annotation")
    assert red["window_s"] == pytest.approx(0.4)


def test_no_device_event_gives_zero_busy_not_a_share():
    empty = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["win", 0.0, 5 * MS]]}]}]}
    red = tr.reduce_trace(empty, "win")
    assert red["device_source"] == "none" and red["busy_s"] == 0.0
    assert "idle_share" not in red


def test_cpu_rehearsal_thunks_are_marked_as_not_a_device():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["win", 0.0, 10 * MS]]},
        {"name": "tf_XLAPjRtCpuClient/1", "events": [
            ["dot.1", 1 * MS, 2 * MS],
            ["ThreadpoolListener::StartRegion", 1 * MS, 0.0]]}]}]}
    red = tr.reduce_trace(trace, "win")
    assert red["device_source"] == "cpu_thunks"
    assert red["busy_s"] == pytest.approx(0.002)
    from benchmark.readers import trace_idle
    assert trace_idle.read({}, {"trace": red}) is None


def test_sanitize_gives_names_a_metric_may_have():
    assert tr.sanitize("PjitFunction(broadcast_in_dim)") == \
        "PjitFunction_broadcast_in_dim_"


TESTDATA = os.path.join(os.path.dirname(tr.__file__), "testdata")


def test_recorded_chip_trace_of_the_start_of_a_fit_call():
    """The first 150 ms of a traced BERT-base fit call on a TPU v5e (PR
    23, recorded with BENCH_KEEP_TRACE): the planes and line names are
    the chip's own. This is the per-call host work (optimizer init,
    placement): small device ops far apart, so the device is idle nearly
    all of the slice."""
    trace = tr.load_json(os.path.join(TESTDATA,
                                      "fit_tpu_v5e.small.json.gz"))
    red = tr.reduce_trace(trace, "in_fit_call",
                          {"init_ops": r"^(broadcast_in_dim|reshape)"})
    assert red["device_source"] == "device" and red["devices"] == 1
    assert 0.0 < red["busy_s"] < 0.01 * red["window_s"]
    assert red["idle_share"] > 0.99
    assert red["op_share"]["init_ops"] > 0.9
    assert red["device_ops"][0][0] == "broadcast_in_dim.1"
    assert all(name.startswith("in_fit_call/")
               for name, _ in red["idle_gaps"])


def test_recorded_chip_trace_of_back_to_back_serving_batches():
    """2 ms of BERT-base forwards back to back on the chip, recorded
    while this PR still had a serving cell (the window's annotation was
    `in_serve_window`): a busy device, op names taken from the HLO text
    before its `=`."""
    trace = tr.load_json(os.path.join(TESTDATA,
                                      "serve_tpu_v5e.small.json.gz"))
    red = tr.reduce_trace(trace, "in_serve_window", {"f": r"^fusion"})
    assert red["device_source"] == "device"
    assert red["idle_share"] < 0.01
    assert 0.3 < red["op_share"]["f"] < 1.0
    assert all("=" not in name and "%" not in name
               for name, _ in red["device_ops"])


def test_op_name_is_what_stands_before_the_equals_sign():
    assert tr.op_name("%fusion.12 = bf16[8,128]{1,0} fusion(%p), kind=kLoop"
                      ) == "fusion.12"
    assert tr.op_name("PjitFunction(train_run)") == "PjitFunction(train_run)"


def test_a_pallas_kernel_is_named_by_its_custom_call_target():
    hlo = ('%transpose_jvp___.3 = (bf16[24,2048,64]{2,1,0}, bf16[24,2048,64]'
           '{2,1,0}) custom-call(%bitcast.10, %copy.11), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert tr.op_name(hlo) == "transpose_jvp___.3@tpu_custom_call"
    import json
    import re
    with open(os.path.join(os.path.dirname(TESTDATA), "layer_metrics",
                           "flash_time_share.json")) as fh:
        pattern = re.compile(json.load(fh)["pattern"])
    assert pattern.search(tr.op_name(hlo))
    assert pattern.search("jvp__.1") and pattern.search("pallas_call.9")
    for other in ("fusion.4521", "multiply_add_fusion.3", "sort.2",
                  "custom-call.1@ConcatBitcast", "copy.7"):
        assert not pattern.search(other)


def test_a_while_is_busy_time_but_not_an_operation_that_took_time():
    trace = _trace()
    trace["planes"][0]["lines"][0]["events"].append(
        ["while.6", 100 * MS, 400 * MS])
    red = tr.reduce_trace(trace, "win", {"flash_time_share": "flash"})
    assert red["idle_share_worst"] == pytest.approx(1 - 0.4 / 0.6)
    assert "while.6" not in dict(red["device_ops"])
    assert red["op_share"]["flash_time_share"] == pytest.approx(50 / 670)
