"""Device time by the program's own scopes (`observability/device_time.py`),
the step program's table made on request (`learn/trainer.program_scopes`)
and the record of how the process got its executables
(`observability/programs.py`). All on the CPU: a path, never a time."""

import json
import os

import jax
import numpy as np
import optax
import pytest

from analytics_zoo_tpu.keras import Sequential
from analytics_zoo_tpu.keras import layers as L
from analytics_zoo_tpu.learn import trainer
from analytics_zoo_tpu.learn.estimator import Estimator
from analytics_zoo_tpu.observability import (device_time, get_registry,
                                             get_tracer, programs)

DATA = os.path.join(os.path.dirname(__file__), "data")
LOOP = "jit(epoch_run)/while/body/closed_call/"
FB = "fit_step/forward_backward"
INNER = LOOP + FB + "/transpose(jvp())/while/body/closed_call/checkpoint/"

# op_names as the six cells' epoch programs carry them (recorded from
# their rehearsals under jax 0.9.0), one of each kind
OP_NAMES = [
    # plain: under a scope, never differentiated
    (LOOP + "fit_step/optimizer_update/jit(_where)/select_n",
     "fit_step/optimizer_update", "forward"),
    ("jit(epoch_run)/fit_epoch/shuffle/jit(_shuffle)/jit(_threefry_split)"
     "/build_device_epoch_run.<locals>.epoch_run/while/body/add",
     "fit_epoch/shuffle", "forward"),
    # jvp: the forward pass of a differentiated scope
    (LOOP + FB + "/jvp(bert/block/attention)/bhqd,bhkd->bhqk/dot_general",
     FB + "/bert/block/attention", "forward"),
    (LOOP + FB + "/jvp()/while/body/closed_call/moedec/moe_block/moe"
     "/experts/jit(silu)", FB + "/moedec/moe_block/moe/experts", "forward"),
    # transpose(jvp()): the backward pass
    (LOOP + FB + "/transpose(jvp(ncf/embeddings))/jit(_take)/scatter-add",
     FB + "/ncf/embeddings", "backward"),
    (INNER + "moedec/moe_block/kda/chunk_scan/while/body/closed_call"
     "/ncj,njv->ncv/add_any", FB + "/moedec/moe_block/kda/chunk_scan",
     "backward"),
    # rematerialised: the recomputation inside the backward pass
    (INNER + "rematted_computation/moedec/moe_block/moe/dispatch"
     "/jit(argsort)/iota", FB + "/moedec/moe_block/moe/dispatch",
     "recompute"),
    (LOOP + FB + "/transpose(jvp(loss/blockwise_nll))/while/body"
     "/closed_call/checkpoint/rematted_computation/jit(take_along_axis)"
     "/add", FB + "/loss/blockwise_nll", "recompute"),
    # a checkpoint restates the path so far inside its wrapper
    (LOOP + FB + "/transpose(jvp(fit_step/forward_backward))/jvp()"
     "/checkpoint/rematted_computation/m/block/ffn/tanh",
     FB + "/m/block/ffn", "recompute"),
    # the scope survived, the trainer's did not (a scan's primal loop)
    (LOOP + "looplm/pass/while/body/dynamic_slice", "looplm/pass",
     "forward"),
    # a Pallas kernel's call, and one under a vmap
    (INNER + "moedec/moe_block/mla/attention/flash_bwd_fused_causal_mla"
     "/pallas_call", FB + "/moedec/moe_block/mla/attention"
     "/flash_bwd_fused_causal_mla", "backward"),
    (LOOP + FB + "/vmap(jvp(kda/gates))/exp", FB + "/kda/gates", "forward"),
    # two instructions made one by the chip's compiler: the first's name
    (LOOP + FB + "/jvp()/while/body/closed_call/moedec/dense_block/reshape"
     ";moedec/dense_block/kda/gates/checkpoint/reshape",
     FB + "/moedec/dense_block", "forward"),
    # no scope: the loop's own work, a wrapper last, a parameter
    (LOOP + "dynamic_update_slice", "unscoped", "forward"),
    ("jit(epoch_run)/while/body/closed_call", "unscoped", "forward"),
    (LOOP + FB + "/jvp(jit(_take))", FB, "forward"),
    ("checkpoint/rematted_computation/reduce_max", "unscoped", "recompute"),
    ("p['w']", "unscoped", "forward"),
]


@pytest.mark.parametrize("op_name,scope,direction", OP_NAMES)
def test_parse_op_name(op_name, scope, direction):
    assert device_time.parse_op_name(op_name) == (scope, direction)


def _recorded_table():
    with open(os.path.join(DATA, "device_time_step.hlo.txt")) as fh:
        table = device_time.scope_table(fh.read())
    assert list(table) == ["jit_run"]
    return table


def test_scope_table_of_a_recorded_program():
    """A scan of a step with three scopes, one under a checkpoint
    (compiled text, CPU backend): the loop body's instructions stand in
    the table with the entry's, free ones (parameters, tuples) do not;
    the fusion that holds a bias gradient's sum AND the optimizer's update
    reads under its root, the update, and lists the other."""
    table = _recorded_table()["jit_run"]
    assert "while.8" in table and "dot_general.0" in table
    assert not {"arg_tuple.1", "get-tuple-element.15", "tuple.6",
                "constant.16"} & set(table)
    assert table["dot_general.0"] == {
        "scope": "fit_step/forward_backward/m/block/ffn",
        "direction": "forward", "also": []}
    assert table["dot"]["direction"] == "backward"
    assert table["multiply_subtract_fusion.1"] == {
        "scope": "fit_step/optimizer_update", "direction": "forward",
        "also": [["fit_step/forward_backward/m/emb", "backward"]]}
    # the backward of the checkpointed block holds its recomputation too
    assert table["multiply_add_fusion"] == {
        "scope": "fit_step/forward_backward/m/block/ffn",
        "direction": "backward",
        "also": [["fit_step/forward_backward/m/block/ffn", "recompute"]]}
    # the scan's own update of its stacked output, around a scoped sum
    assert table["bitcast_dynamic-update-slice_fusion"]["scope"] \
        == "fit_step/forward_backward/m/head"
    assert table["copy.7"]["scope"] == "unscoped"


TPU_TEXT = """HloModule jit_epoch_run, is_scheduled=true

%fused_computation.7 (param_0.1: bf16[256,768], param_1.2: bf16[256,3072]) -> f32[768,3072] {
  %param_0.1 = bf16[256,768]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = bf16[256,3072]{1,0:T(8,128)(2,1)} parameter(1)
  %convolution.9 = f32[768,3072]{1,0:T(8,128)} convolution(%param_0.1, %param_1.2), dim_labels=fb_io->bf, metadata={op_name="jit(epoch_run)/while/body/closed_call/fit_step/forward_backward/transpose(jvp(bert/block/ffn))/dot_general" stack_frame_id=7}
  %constant.3 = f32[] constant(0.001), metadata={op_name="jit(epoch_run)/while/body/closed_call"}
  %broadcast.5 = f32[768,3072]{1,0:T(8,128)} broadcast(%constant.3), dimensions={}
  ROOT %multiply.4 = f32[768,3072]{1,0:T(8,128)} multiply(%convolution.9, %broadcast.5), metadata={op_name="jit(epoch_run)/while/body/closed_call/fit_step/optimizer_update/mul" stack_frame_id=9}
}

%body.1 (arg.1: (s32[], bf16[256,768], bf16[256,3072])) -> (s32[], bf16[256,768], bf16[256,3072]) {
  %arg.1 = (s32[], bf16[256,768]{1,0:T(8,128)(2,1)}, bf16[256,3072]{1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.1 = bf16[256,768]{1,0:T(8,128)(2,1)} get-tuple-element(%arg.1), index=1
  %get-tuple-element.2 = bf16[256,3072]{1,0:T(8,128)(2,1)} get-tuple-element(%arg.1), index=2
  %multiply_add_fusion.705 = f32[768,3072]{1,0:T(8,128)} fusion(%get-tuple-element.1, %get-tuple-element.2), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(epoch_run)/while/body/closed_call/fit_step/optimizer_update/mul"}
  %flash_fwd.3 = (bf16[16,12,2048,64]{3,2,1,0:T(8,128)(2,1)}, f32[192,1,2048]{2,1,0:T(1,128)}) custom-call(%get-tuple-element.1), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[256,768]{1,0}}, metadata={op_name="jit(epoch_run)/while/body/closed_call/fit_step/forward_backward/jvp(bert/block/attention)/flash_fwd/pallas_call" stack_frame_id=3}
  %copy.12 = bf16[256,768]{0,1:T(8,128)(2,1)} copy(%get-tuple-element.1)
  ROOT %tuple.3 = (s32[], bf16[256,768]{1,0:T(8,128)(2,1)}, bf16[256,3072]{1,0:T(8,128)(2,1)}) tuple(%get-tuple-element.1, %get-tuple-element.1, %get-tuple-element.2)
}

%cond.1 (arg.2: (s32[], bf16[256,768], bf16[256,3072])) -> pred[] {
  %arg.2 = (s32[], bf16[256,768]{1,0:T(8,128)(2,1)}, bf16[256,3072]{1,0:T(8,128)(2,1)}) parameter(0)
  ROOT %compare.1 = pred[]{:T(512)} compare(%arg.2, %arg.2), direction=LT, metadata={op_name="jit(epoch_run)/while/cond/lt"}
}

ENTRY %main.5 (p.1: bf16[256,768]) -> bf16[256,768] {
  %p.1 = bf16[256,768]{1,0:T(8,128)(2,1)} parameter(0)
  %while.2 = (s32[], bf16[256,768]{1,0:T(8,128)(2,1)}, bf16[256,3072]{1,0:T(8,128)(2,1)}) while(%p.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(epoch_run)/while"}
  ROOT %get-tuple-element.9 = bf16[256,768]{1,0:T(8,128)(2,1)} get-tuple-element(%while.2), index=1
}
"""


def test_scope_table_reads_tiled_layouts_heroes_and_kernels():
    """The chip's text: types carry tilings (brackets inside braces), a
    product inside a fusion is a `convolution` and the fusion's hero (its
    root, the update, goes to `also`), a Pallas kernel keeps its name as
    a leaf, an instruction without metadata is unscoped."""
    table = device_time.scope_table(TPU_TEXT)["jit_epoch_run"]
    assert set(table) == {"while.2", "multiply_add_fusion.705", "flash_fwd.3",
                          "copy.12", "compare.1"}
    assert table["multiply_add_fusion.705"] == {
        "scope": "fit_step/forward_backward/bert/block/ffn",
        "direction": "backward",
        "also": [["fit_step/optimizer_update", "forward"]]}
    assert table["flash_fwd.3"]["scope"] == \
        "fit_step/forward_backward/bert/block/attention/flash_fwd"
    assert table["copy.12"] == {"scope": "unscoped", "direction": "forward",
                                "also": []}


def test_by_scope_adds_up_and_names_what_it_could_not_place():
    table = device_time.scope_table(TPU_TEXT)
    seconds = {("jit_epoch_run", "multiply_add_fusion.705"): 3.0,
               ("jit_epoch_run", "flash_fwd.3"): 1.0,
               ("", "copy.12"): 0.5,            # the capture named no module
               ("jit_eval_step", "fusion.1"): 0.5}   # another program
    rows = device_time.by_scope(seconds, table)
    assert sum(r["share_pct"] for r in rows) == pytest.approx(100.0)
    by = {(r["scope"], r["direction"]): r for r in rows}
    ffn = by["fit_step/forward_backward/bert/block/ffn", "backward"]
    assert (ffn["seconds"], ffn["mixed_s"], ffn["ops"]) == (3.0, 3.0, 1)
    assert by["unmatched", ""]["share_pct"] == pytest.approx(10.0)
    assert by["unscoped", "forward"]["seconds"] == 0.5
    at2 = device_time.by_scope(seconds, table, depth=2)
    assert [(r["scope"], r["direction"], r["seconds"]) for r in at2][:2] \
        == [("fit_step/forward_backward", "backward", 3.0),
            ("fit_step/forward_backward", "forward", 1.0)]
    assert sum(r["share_pct"] for r in at2) == pytest.approx(100.0)
    assert device_time.by_scope(seconds, {})[0]["scope"] == "unmatched"


# -- end to end on the CPU backend ------------------------------------------
class _Scoped(L.Dense):
    """A Dense layer that names its part of the program."""

    def __init__(self, units, scope, **kw):
        super().__init__(units, **kw)
        self._scope = scope

    def call(self, params, x, **kw):
        with jax.named_scope(self._scope):
            return super().call(params, x, **kw)


def _model():
    m = Sequential()
    m.add(_Scoped(16, "toy/trunk", activation="relu", input_shape=(8,)))
    m.add(_Scoped(1, "toy/head"))
    m.compile(optimizer=optax.adam(1e-2), loss="mse")
    return m


def _data(n=128):
    rs = np.random.RandomState(0)
    x = rs.randn(n, 8).astype(np.float32)
    return {"x": x, "y": (x @ rs.randn(8, 1)).astype(np.float32)}


def test_a_profiled_fit_says_where_the_device_time_went(tmp_path):
    m = _model()
    hist = Estimator.from_keras(m).fit(
        _data(), epochs=4, batch_size=32, profile_steps=(4, 8),
        profile_dir=str(tmp_path))
    (art,) = hist["profile_artifacts"]
    with open(os.path.join(art, device_time.REPORT_FILE)) as fh:
        report = json.load(fh)
    assert report["device_source"] == "cpu_thunks" and "note" in report
    assert report["table_digest"] == device_time.table_digest(
        trainer.program_scopes(m))
    rows = report["rows"]
    assert abs(sum(r["share_pct"] for r in rows) - 100.0) < 1e-6
    assert sum(r["seconds"] for r in rows) == pytest.approx(
        report["total_s"])
    seen = {(r["scope"], r["direction"]) for r in rows}
    for scope in ("toy/trunk", "toy/head"):
        for direction in ("forward", "backward"):
            assert (f"fit_step/forward_backward/{scope}", direction) in seen
    assert ("fit_step/optimizer_update", "forward") in seen
    unmatched = [r for r in rows if r["scope"] == "unmatched"]
    assert not unmatched or unmatched[0]["share_pct"] < 2.0
    # the gauges hold the same capture at three parts of the scope
    gauge = get_registry().get("training_device_time_share")
    shares = {dict(k)["scope"]: gauge.value(**dict(k))
              for k in gauge.label_keys()}
    assert shares["fit_step/forward_backward/toy"] > 0
    assert sum(gauge.value(**dict(k)) for k in gauge.label_keys()) \
        == pytest.approx(100.0)
    # the request lowered the step again and compiled nothing: the
    # executable is the one the first dispatch obtained
    asked = [s for s in get_tracer().spans(trace_id=[
        s for s in get_tracer().spans() if s.name == "fit"][-1].trace_id)
        if s.name == programs.SPAN
        and s.args["program"] == f"jit({m._train_cache[1].__name__})"]
    assert [s.parent for s in asked] == ["fit.dispatch"]
    # the command line prints the file again, at any depth
    assert device_time.main([art, "--depth", "1"]) == 0


def test_a_fit_that_asks_for_nothing_pays_for_a_tuple_of_shapes(monkeypatch):
    lowered = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(str(kw.get("fun_name")))
    monkeypatch.setattr(device_time, "scope_table", lambda text: 1 / 0)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        m = _model()
        est = Estimator.from_keras(m)
        est.fit(_data(), epochs=2, batch_size=32)
        est.fit(_data(), epochs=1, batch_size=32)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    key, step, program = m._train_cache
    assert lowered.count(f"jit({step.__name__})") == 1
    assert program.scopes is None and program.jitted is step
    leaves = jax.tree_util.tree_leaves(program.abstract_args)
    assert leaves and all(isinstance(a, jax.ShapeDtypeStruct)
                          for a in leaves)
    assert not any(isinstance(a, (jax.Array, np.ndarray))
                   for a in jax.tree_util.tree_leaves(
                       (key, program.abstract_args, program.scopes)))
    assert trainer.program_scopes(_model()) == {}     # before any fit


def _obtained():
    fam = get_registry().snapshot().get(programs.FAMILY, {"series": []})
    return {(s["labels"]["during"], s["labels"]["how"]): s["count"]
            for s in fam["series"]}


def _grown(before):
    now = _obtained()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v - before.get(k, 0)}


def test_how_a_fit_got_its_programs_is_observed_and_spanned():
    m = _model()
    est = Estimator.from_keras(m)
    data = _data()
    before = _obtained()
    est.fit(data, epochs=1, batch_size=32)
    first = _grown(before)
    # the persistent cache is off in the tests: every program is compiled
    assert set(first) == {("fit", "compile")} and first["fit", "compile"] >= 1
    spans = [s for s in get_tracer().spans()
             if s.name == programs.SPAN and s.args["program"]
             == f"jit({m._train_cache[1].__name__})"]
    assert spans[-1].parent == "fit.dispatch"
    assert spans[-1].args["how"] == "compile"
    assert spans[-1].cat == "training"
    assert spans[-1].trace_id.startswith("fit-")
    dispatch = [s for s in get_tracer().spans()
                if s.name == "fit.dispatch"
                and s.trace_id == spans[-1].trace_id][0]
    assert dispatch.start <= spans[-1].start \
        and spans[-1].end <= dispatch.end

    before = _obtained()
    est.fit(data, epochs=1, batch_size=32)      # everything is in memory
    assert _grown(before) == {}

    before = _obtained()
    jax.jit(lambda a: a * 3 + 1)(np.ones((3,), np.float32))
    assert _grown(before) == {("other", "compile"): 1}


def test_a_load_from_the_persistent_cache_is_told_from_a_compile(tmp_path):
    """The pairing of jax's events: a request that the persistent cache
    answers records a retrieval inside its bracket."""
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    from jax._src import compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()

        def f(a):
            return a * 5 - 2
        x = np.ones((7,), np.float32)
        before = _obtained()
        jax.jit(f)(x)
        assert _grown(before) == {("other", "compile"): 1}
        jax.clear_caches()
        before = _obtained()
        jax.jit(f)(x)
        assert _grown(before) == {("other", "cache_load"): 1}
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# -- the two oldest cells' models name their parts, and nothing else moves --
def _tiny_bert():
    from analytics_zoo_tpu.models.bert import BERTClassifier
    m = BERTClassifier(num_classes=2, vocab=50, hidden_size=16, n_block=2,
                       n_head=2, seq_len=8, intermediate_size=32)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 50, (4, 8)).astype(np.int32)
    return m, [ids, np.zeros_like(ids), np.ones_like(ids)], \
        ["bert/embeddings", "bert/block/attention",
         "bert/block/attention_output_norm", "bert/block/ffn",
         "bert/block/ffn_output_norm", "bert/pooler_head"]


def _tiny_ncf():
    from analytics_zoo_tpu.keras.engine import reset_name_scope
    from analytics_zoo_tpu.models.recommendation import NeuralCF
    reset_name_scope()
    m = NeuralCF(user_count=20, item_count=30, class_num=2, user_embed=4,
                 item_embed=4, hidden_layers=(8, 4), mf_embed=4).model
    rs = np.random.RandomState(0)
    x = np.stack([rs.randint(1, 20, 16), rs.randint(1, 30, 16)], 1)
    return m, x.astype(np.float32), \
        ["ncf/embeddings", "ncf/gmf", "ncf/mlp", "ncf/head"]


@pytest.mark.parametrize("make", [_tiny_bert, _tiny_ncf])
def test_the_models_scopes_are_metadata_only(make, monkeypatch):
    """The step a fit differentiates lowers to the same text with the
    scopes as without them (the parent's program, so its compile cache
    entries are this program's), and with locations on it names each."""
    import contextlib
    model, x, scopes = make()
    params = model.build(jax.random.PRNGKey(0), None) \
        if hasattr(model, "bert") else model.build(jax.random.PRNGKey(0))

    def lowered(**kw):
        def loss(p):
            return model.apply(p, x, training=True,
                               rng=jax.random.PRNGKey(1)).sum()
        return jax.jit(jax.grad(loss)).lower(params).as_text(**kw)
    named, located = lowered(), lowered(debug_info=True)
    for scope in scopes:
        assert f"jvp({scope})" in located, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert "bert/" not in lowered(debug_info=True)
    assert "ncf/" not in lowered(debug_info=True)
    assert lowered() == named
    if not hasattr(model, "bert"):
        # the layers' automatic names, which the parameters are kept
        # under, are the ones the model had before its parts had names
        assert sorted(params) == [
            "dense_1", "dense_2", "dense_3", "flatten_1", "flatten_2",
            "flatten_3", "flatten_4", "merge_1", "merge_2", "merge_3",
            "ncf_mf_item", "ncf_mf_user", "ncf_mlp_item", "ncf_mlp_user",
            "select_1", "select_2"]
        by_name = {layer.name: layer for layer in model._layers}
        assert (by_name["merge_2"].mode, by_name["merge_2"].scope) \
            == ("mul", "ncf/gmf")
        assert by_name["dense_3"].scope == "ncf/head"
