"""The fit call as a span tree (ISSUE 24): `fit_keras` records its phases
as scoped spans of the process-wide tracer and observes each leaf in
`training_fit_phase_ms` at the same boundaries; the spans are host events
of any profiler capture; the step and epoch programs and the flash
kernels carry names of their own on the device side."""

import logging
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from analytics_zoo_tpu.keras import Sequential
from analytics_zoo_tpu.keras import layers as L
from analytics_zoo_tpu.learn import trainer
from analytics_zoo_tpu.observability import (Tracer, get_registry,
                                             get_tracer, span_coverage)

# fit kwargs of the three paths through the loop
PATHS = {
    "device_epoch": dict(device_cache=True),
    "multi_step": dict(device_cache=False, steps_per_run=2),
    "single_step": dict(device_cache=False),
}
CALL_PHASES = ["fit.prepare", "fit.optimizer_init", "fit.build_step",
               "fit.place_data", "fit.finish"]
EPOCHS, STEPS, BATCH, IN_DIM = 3, 4, 2048, 32


def _model(width=2048):
    """Narrow inputs (a small batch for the prefetch thread to put, so
    that it does not hold the interpreter against the loop) into wide
    layers (a step long enough to time the loop against)."""
    m = Sequential()
    m.add(L.Dense(width, activation="relu", input_shape=(IN_DIM,)))
    m.add(L.Dense(width, activation="relu"))
    m.add(L.Dense(1))
    m.compile(optimizer=optax.adam(1e-3), loss="mse")
    return m


def _data(n=STEPS * BATCH):
    rs = np.random.RandomState(0)
    x = rs.randn(n, IN_DIM).astype(np.float32)
    return x, x[:, :1] * 0.5


def _phase_sums():
    fam = get_registry().snapshot().get("training_fit_phase_ms",
                                        {"series": []})
    return {(s["labels"]["phase"], s["labels"]["scope"]): s["sum"]
            for s in fam["series"]}


def _last_fit():
    """(root, the root before it, the spans of the last fit call)."""
    roots = [s for s in get_tracer().spans() if s.name == "fit"]
    return roots[-1], roots[-2], get_tracer().spans(
        trace_id=roots[-1].trace_id)


@pytest.fixture(scope="module", params=sorted(PATHS))
def traced_fit(request):
    """One fit call of each path, steps heavy enough that the loop's own
    bookkeeping is well under 1% of the call: its spans, the growth of
    the counter family, the fit call before it, and the call itself to
    make again."""
    path = request.param
    # the epoch's log line goes to the console in tens of microseconds
    # each; the test measures the loop, not the terminal
    logger = logging.getLogger("analytics_zoo_tpu.trainer")
    level = logger.level
    logger.setLevel(logging.WARNING)
    x, y = _data()
    m = _model()

    def fit(epochs):
        m.fit(x, y, nb_epoch=epochs, batch_size=BATCH, **PATHS[path])
    fit(1)                                         # compiles
    before = _phase_sums()
    fit(EPOCHS)
    after = _phase_sums()
    root, earlier, spans = _last_fit()
    grown = {k: v - before.get(k, 0.0) for k, v in after.items()}
    yield path, root, earlier, spans, grown, fit
    logger.setLevel(level)


def test_span_tree_has_the_names_and_parents_and_one_trace_id(traced_fit):
    path, root, _, spans, _, _ = traced_fit
    assert root.cat == "training" and root.parent is None
    assert re.fullmatch(r"fit-\d+", root.trace_id)
    assert root.args["path"] == path and root.args["epochs"] == EPOCHS
    assert root.args["steps_per_epoch"] == STEPS
    assert root.args["batch"] == BATCH and root.args["devices"] >= 1
    assert all(s.trace_id == root.trace_id and s.cat == "training"
               for s in spans)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    call = [p for p in CALL_PHASES
            if p != "fit.place_data" or path == "device_epoch"]
    for name in call:
        assert len(by_name[name]) == 1 and by_name[name][0].parent == "fit"
    # the call's phases follow one another in this order and do not nest
    starts = [by_name[p][0] for p in call]
    for a, b in zip(starts, starts[1:]):
        assert a.end <= b.start
    epochs = by_name["fit.epoch"]
    assert [e.args["epoch"] for e in epochs] == list(range(EPOCHS))
    assert all(e.parent == "fit" and e.args["steps"] == STEPS
               for e in epochs)
    dispatches = {"device_epoch": 1, "multi_step": 2, "single_step": STEPS}
    assert len(by_name["fit.dispatch"]) == EPOCHS * dispatches[path]
    assert sum(d.args["steps"] for d in by_name["fit.dispatch"]) \
        == EPOCHS * STEPS
    assert len(by_name["fit.loss_sync"]) == EPOCHS
    for leaf in by_name["fit.dispatch"] + by_name["fit.loss_sync"]:
        assert leaf.parent == "fit.epoch" and leaf.tid == root.tid
    if path == "device_epoch":
        assert by_name["fit.place_data"][0].args["hit"] is True
        assert "fit.input_wait" not in by_name
        assert "fit.transfer" not in by_name
    else:
        # one get a dispatch, and the one that finds the epoch's end
        assert len(by_name["fit.input_wait"]) \
            == EPOCHS * (dispatches[path] + 1)
        assert all(w.parent == "fit.epoch" for w in by_name["fit.input_wait"])
        # the prefetch thread's spans carry the call's id and no parent
        assert len(by_name["fit.transfer"]) == EPOCHS * dispatches[path]
        assert all(t.parent is None and t.tid != root.tid
                   for t in by_name["fit.transfer"])
    assert set(by_name) <= set(CALL_PHASES) | {
        "fit", "fit.epoch", "fit.dispatch", "fit.loss_sync",
        "fit.input_wait", "fit.transfer"}


def test_leaves_of_the_loops_thread_cover_the_call(traced_fit):
    """What the leaves leave uncovered is the loop's bookkeeping. On a
    machine whose cores the CPU backend's own threads fill, the loop's
    thread can also lose a few milliseconds between two spans to the
    scheduler, which is not the loop's doing: the best of three calls
    counts."""
    _, root, _, spans, _, fit = traced_fit
    covered = []
    for _ in range(3):
        leaves = [s for s in spans if s.tid == root.tid
                  and s.name not in ("fit", "fit.epoch")]
        covered.append(span_coverage(leaves, root.start, root.end))
        if covered[-1] >= 0.99:
            break
        fit(EPOCHS)
        root, _, spans = _last_fit()
    assert max(covered) >= 0.99, covered


def test_each_series_grew_by_its_spans_summed_duration(traced_fit):
    path, root, _, spans, grown, _ = traced_fit
    scope = {"dispatch": "epoch", "loss_sync": "epoch", "transfer": "worker"}
    by_phase = {}
    for s in spans:
        if s.name in ("fit", "fit.epoch", "fit.input_wait"):
            continue                       # no series of their own
        phase = s.name[len("fit."):]
        key = (phase, scope.get(phase, "call"))
        by_phase[key] = by_phase.get(key, 0.0) + s.duration * 1e3
    assert by_phase                       # every leaf has its series
    for key, ms in by_phase.items():
        assert grown[key] == pytest.approx(ms, rel=1e-6, abs=1e-4), key
    assert {k for k, v in grown.items() if v > 0} == set(by_phase)


def test_a_second_fit_call_gets_a_new_trace_id(traced_fit):
    _, root, earlier, _, _, _ = traced_fit
    assert earlier.trace_id != root.trace_id
    assert earlier.args["epochs"] == 1


def test_the_input_wait_span_is_the_histograms_observation():
    """`training_input_wait_ms` is observed by the close of each
    `fit.input_wait` span: same endpoints, same count."""
    def waits():
        fam = get_registry().snapshot().get("training_input_wait_ms")
        s = fam["series"][0] if fam and fam["series"] else {}
        return s.get("count", 0), s.get("sum", 0.0)
    x, y = _data(n=128)
    m = _model(width=32)
    n0, sum0 = waits()
    m.fit(x, y, batch_size=32, nb_epoch=2, device_cache=False)
    n1, sum1 = waits()
    root = [s for s in get_tracer().spans() if s.name == "fit"][-1]
    spans = [s for s in get_tracer().spans(trace_id=root.trace_id)
             if s.name == "fit.input_wait"]
    assert n1 - n0 == len(spans) == 2 * (4 + 1)
    assert sum1 - sum0 == pytest.approx(
        sum(s.duration for s in spans) * 1e3, rel=1e-6, abs=1e-4)


@pytest.mark.parametrize("case, path, fit_kw", [
    ("device_epoch", "device_epoch", dict(device_cache=True)),
    ("multi_step", "multi_step", dict(device_cache=False, steps_per_run=3)),
    ("single_step", "single_step", dict(device_cache=False)),
    ("sharded", "single_step", dict(device_cache=False,
                                    sharding_rules=True)),
    ("aot_cached", "single_step", dict(device_cache=False)),
])
def test_a_fit_pays_for_no_accounting(monkeypatch, tmp_path, case, path,
                                      fit_kw):
    """The benchmark answers what share of the chip a fit used; the fit
    itself estimates nothing. A whole fit walks no argument tree for a
    signature on the loop's behalf (a cost tracker did, once a dispatch,
    and lowered a sharded step a second time) and leaves no roofline
    series of kind "train" in the registry. `AOTFunctionCache` keys its
    executables with the function it imported by name, which the count
    here does not see: with `compile_cache_dir` the loop itself still
    walks nothing."""
    from analytics_zoo_tpu.compile_cache import key
    monkeypatch.delenv("ZOO_COMPILE_CACHE_DIR", raising=False)
    if case == "aot_cached":
        fit_kw = dict(fit_kw, compile_cache_dir=str(tmp_path))
    walks = []
    real = key.cheap_signature
    monkeypatch.setattr(key, "cheap_signature",
                        lambda tree: walks.append(case) or real(tree))
    x, y = _data(n=192)
    _model(width=32).fit(x, y, batch_size=32, nb_epoch=2, **fit_kw)
    root = [s for s in get_tracer().spans() if s.name == "fit"][-1]
    assert root.args["path"] == path
    assert walks == []
    train = [(name, s["labels"])
             for name, fam in get_registry().snapshot().items()
             if name.startswith("roofline_")
             for s in fam["series"] if s["labels"].get("kind") == "train"]
    assert train == []


def test_a_fused_fit_sweeps_inside_its_step_program_only():
    """`fused_optimizer=True` traces the optimizer's `fused_apply` once
    a step program: two epochs of four steps are one trace, and a sweep
    run anywhere outside the step (to time it, to cost it) would be a
    trace more."""
    from analytics_zoo_tpu.ops.optimizers import fused_adam
    opt = fused_adam(1e-3)
    traces = []

    def counted(grads, state, params):
        traces.append(1)
        return opt.fused_apply(grads, state, params)
    m = _model(width=32)
    m.compile(optimizer=opt._replace(fused_apply=counted), loss="mse")
    x, y = _data(n=128)
    h = m.fit(x, y, batch_size=32, nb_epoch=2, device_cache=False,
              fused_optimizer=True)
    assert len(traces) == 1
    assert np.isfinite(h["loss"]).all() and h["loss"][1] < h["loss"][0]


def test_a_fit_that_raises_leaves_no_span_open(monkeypatch):
    def boom(x):
        raise RuntimeError("no losses today")
    monkeypatch.setattr(trainer, "_materialize", boom)
    x, y = _data(n=128)
    with pytest.raises(RuntimeError, match="no losses today"):
        _model(width=32).fit(x, y, batch_size=32, nb_epoch=2)
    assert get_tracer()._stack() == []
    root = [s for s in get_tracer().spans() if s.name == "fit"][-1]
    names = [s.name for s in get_tracer().spans(trace_id=root.trace_id)]
    assert names.count("fit.epoch") == 1 and "fit.finish" in names
    assert names[-1] == "fit"


def test_a_profiler_capture_holds_the_fit_spans_as_host_events(tmp_path):
    from benchmark import trace_reduce
    x, y = _data(n=128)
    m = _model(width=32)
    m.fit(x, y, batch_size=32, nb_epoch=1, device_cache=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        m.fit(x, y, batch_size=32, nb_epoch=2, device_cache=True)
    finally:
        jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(str(tmp_path)))
    names = [ev[0] for ev in trace_reduce.host_events(trace)]
    assert names.count("fit") == 1 and names.count("fit.epoch") == 2
    assert names.count("fit.dispatch") == 2
    assert names.count("fit.loss_sync") == 2
    # on one clock: the epochs lie inside the root
    fit = trace_reduce.find_span(trace, "fit")
    for ev in trace_reduce.host_events(trace):
        if ev[0] == "fit.epoch":
            assert fit[0] <= ev[1] and ev[1] + ev[2] <= fit[1]


def test_the_epoch_program_names_its_parts_in_op_name():
    m = _model(width=32)
    x, y = _data(n=64)
    m.ensure_built(x[:8], jax.random.PRNGKey(0))
    run = trainer.build_device_epoch_run(
        m.apply, m.loss, m.optimizer, steps=4, batch=16, shuffle=True)
    text = run.lower(m.params, m.optimizer.init(m.params), jnp.asarray(x),
                     jnp.asarray(y), jax.random.PRNGKey(1)
                     ).as_text(debug_info=True)
    for scope in ("fit_epoch/shuffle", "fit_epoch/gather_batch",
                  "fit_step/forward_backward", "fit_step/optimizer_update"):
        assert re.search(r'loc\("[^"]*' + scope + '[/)"]', text), scope


def test_each_flash_kernel_carries_its_name(monkeypatch):
    """Cross-lowered for the TPU from here (as
    tests/test_pallas_tpu_lowering.py does): the Mosaic calls of a flash
    forward and backward are named flash_fwd and flash_bwd_fused (2048
    tokens), flash_dq and flash_dkv (65,536 tokens, where the backward is
    two kernels), in the kernel's own attribute and in the name stack
    that the compiler takes the instruction's name from."""
    from analytics_zoo_tpu.pallas.flash_attention import flash_attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()
    for T, names in ((2048, ["flash_bwd_fused", "flash_fwd"]),
                     (65536, ["flash_dkv", "flash_dq", "flash_fwd"])):
        q = jax.ShapeDtypeStruct((1, 2, T, 64), jnp.bfloat16)
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            q, q, q).lower(lowering_platforms=("tpu",)).as_text(
                debug_info=True)
        assert sorted(re.findall(r'kernel_name = "([^"]+)"', text)) == names
        for name in names:
            assert re.search(r'loc\("[^"]*' + name + r'\)*/pallas_call"',
                             text)


def test_a_span_costs_microseconds_while_no_capture_runs():
    tracer = Tracer(max_spans=1000)
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(2000):
            with tracer.span("x", cat="training"):
                pass
        best = min(best, (time.perf_counter() - t0) / 2000)
    assert best < 20e-6, f"{best * 1e6:.1f} us a span"


def test_phase_observes_the_spans_own_duration():
    reg_hist = get_registry().histogram("test_tracing_phase_ms", "test")
    tracer = Tracer()
    with tracer.phase("a.b", reg_hist, trace_id="t", cat="training",
                      args={"k": 1}, phase="b") as span:
        time.sleep(0.002)
    (s,) = tracer.spans()
    assert s.name == "a.b" and s.trace_id == "t" and s.args == {"k": 1}
    assert span.duration == s.duration >= 0.002
    series = reg_hist.snapshot()["series"]
    assert series[0]["labels"] == {"phase": "b"}
    assert series[0]["sum"] == pytest.approx(s.duration * 1e3, abs=1e-5)
