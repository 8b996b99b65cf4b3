"""BENCHMARK.json against the contract's limits, and every entry against
the files the harness finds by its name."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _cells():
    return [w["name"] for w in _bench()["workloads"]]


def test_top_level_keys_and_sizes():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    # the full check with all 24 cells must fit
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 2 * 90 + 1200 \
        <= 43200
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert b["paths"] == ["benchmark", "tests/benchmark"]
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_configs_resolve_to_their_own_files_under_paths():
    b = _bench()
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in b["workloads"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(ROOT, c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["source"] == c["source"] and len(c["source"]) <= 200
        assert cfg["reduced"] == c["reduced"] and cfg["name"] == c["name"]
        # never a width: only the position table's length is changed
        assert set(c["reduced"]) <= {"max_position_embeddings"}
        assert "deployment" in cfg and "precision" in cfg
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "models", cfg["family"] + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "reference", cfg["family"] + ".py"))


@pytest.mark.parametrize("cell", _cells())
def test_every_cell_resolves_to_files_and_reports_what_it_must(cell):
    from benchmark import harness
    b = _bench()
    w = {x["name"]: x for x in b["workloads"]}[cell]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    loaded = harness.load_cell(cell, rehearse=False)
    kind = loaded["traffic"]["kind"]
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "runners", kind + ".py"))
    e2e = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert loaded["per_layer"]
    for m in loaded["per_layer"]:
        assert m["moves"] in e2e, (m["name"], "moves", m["moves"])
    # the rehearsal sizes are data in the same files
    tiny = harness.load_cell(cell, rehearse=True)
    assert tiny["traffic"] != loaded["traffic"]
    assert tiny["config"] != loaded["config"]


def test_metrics_have_the_contracts_keys_names_and_bounds():
    b = _bench()
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    cells = set(_cells())
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in b["end_to_end"])
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    # every end-to-end metric is reported by some cell
    for m in b["end_to_end"]:
        assert m.get("workloads", cells)


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips():
    ws = _bench()["workloads"]
    assert sum(w["chips"] == 4 for w in ws) <= max(1, len(ws) // 4)


def test_each_per_layer_metric_has_a_reader_file_and_repeats_nothing():
    b = _bench()
    for m in b["per_layer"]:
        path = os.path.join(ROOT, "benchmark", "layer_metrics",
                            m["name"] + ".json")
        with open(path) as fh:
            spec = json.load(fh)
        # what BENCHMARK.json says of a metric is said there alone
        assert not set(spec) & {"name", "unit", "better", "source",
                                "layer", "moves", "workloads"}
        assert spec["what"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))


def test_the_bert_configurations_differ_in_the_position_table_alone():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as fh:
            return json.load(fh)
    base, long = load("bert-base"), load("bert-base-pos2048")
    differ = {k for k in set(base) | set(long) if base.get(k) != long.get(k)}
    assert differ == {"name", "deployment", "reduced", "rehearsal",
                      "max_position_embeddings", "reference_check"}
    assert long["max_position_embeddings"] == 2048


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_./-]+$")
    for p in _bench()["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel
