"""The `span_ring` reader on a hand-made ring: it takes the last `fit`
root but one as the window's call and the last as the traced call,
returns nothing when the window's root does not fit `window_s`, and
computes its two statistics from the `fit.epoch` spans of those calls."""

import pytest

import analytics_zoo_tpu.observability as observability
from analytics_zoo_tpu.observability import Tracer
from benchmark import harness
from benchmark.readers import span_ring

SLOWEST = harness.reader_spec({"name": "fit_epoch_slowest_over_median"})
OVERHEAD = harness.reader_spec({"name": "fit_trace_overhead"})


def _ring(monkeypatch, calls):
    """A tracer holding one `fit` root for each (trace_id, [epoch
    seconds]) of `calls`, in order, each root as long as its epochs and
    a tenth of a second of per-call work."""
    tracer = Tracer()
    t = 100.0
    for trace_id, epochs in calls:
        start = t
        t += 0.1
        for i, d in enumerate(epochs):
            tracer.add_span("fit.epoch", t, t + d, trace_id=trace_id,
                            cat="training", args={"epoch": i})
            t += d
        tracer.add_span("fit", start, t, trace_id=trace_id, cat="training")
        t += 1.0
    monkeypatch.setattr(observability, "get_tracer", lambda: tracer)
    return tracer


WARM = ("fit-1", [4.0])
WINDOW = ("fit-2", [4.0, 4.1, 4.0, 6.0, 4.0, 3.9, 4.0])      # 30.1 s
TRACED = ("fit-3", [4.2, 4.4])


def test_the_last_root_but_one_is_the_windows_and_the_last_the_traced(
        monkeypatch, capsys):
    _ring(monkeypatch, [WARM, WINDOW, TRACED])
    src = {"window_s": 30.2}
    assert span_ring.read(SLOWEST, src) == pytest.approx(6.0 / 4.0)
    assert span_ring.read(OVERHEAD, src) == pytest.approx(
        100 * (4.3 / 4.0 - 1))
    # every epoch of the window on an earlier line: a stalled run says
    # which epoch it was
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("span_ring fit-2 epoch_ms=")]
    assert said and "6000.0" in said[0]


@pytest.mark.parametrize("window_s", [20.0, 40.0, 0.0, None])
def test_a_root_that_does_not_fit_the_window_reads_nothing(monkeypatch,
                                                           window_s):
    _ring(monkeypatch, [WARM, WINDOW, TRACED])
    for spec in (SLOWEST, OVERHEAD):
        assert span_ring.read(spec, {"window_s": window_s}) is None


def test_too_few_roots_or_no_epochs_read_nothing(monkeypatch):
    _ring(monkeypatch, [WINDOW])
    assert span_ring.read(SLOWEST, {"window_s": 30.2}) is None
    _ring(monkeypatch, [("fit-8", []), ("fit-9", [1.0])])
    assert span_ring.read(SLOWEST, {"window_s": 0.1}) is None
    _ring(monkeypatch, [WINDOW, ("fit-9", [])])
    assert span_ring.read(SLOWEST, {"window_s": 30.2}) == pytest.approx(1.5)
    assert span_ring.read(OVERHEAD, {"window_s": 30.2}) is None


def test_a_program_without_a_tracer_reads_nothing(monkeypatch):
    """The parent of the PR that brought the spans: the import fails, the
    metric is left out of the line, nothing raises."""
    monkeypatch.delattr(observability, "get_tracer")
    assert span_ring.read(SLOWEST, {"window_s": 30.2}) is None


def test_spans_of_other_categories_are_not_fit_roots(monkeypatch):
    tracer = _ring(monkeypatch, [WINDOW, TRACED])
    tracer.add_span("fit", 500.0, 530.0, trace_id="req-1", cat="serving")
    assert span_ring.read(SLOWEST, {"window_s": 30.2}) == pytest.approx(1.5)


def test_an_unknown_statistic_is_an_error(monkeypatch):
    _ring(monkeypatch, [WINDOW, TRACED])
    with pytest.raises(ValueError):
        span_ring.read({"statistic": "p17"}, {"window_s": 30.2})
