"""``BENCHMARK.json`` and the data files it names hang together."""

import importlib
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import build, layers  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|proj\w*)_size$|_dim$|_rank$|"
    r"head_size|expansion|experts_per_tok|^d_model$|^d_ff$|^n_embd$"
)


def manifest():
    return build.manifest()


def cells():
    return [w["name"] for w in manifest()["workloads"]]


def test_top_level_keys_and_limits():
    m = manifest()
    assert set(m) == KEYS
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert len(json.dumps(m)) < 64 * 1024
    assert all(os.path.isdir(os.path.join(REPO, p)) for p in m["paths"])
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert {w["chips"] for w in m["workloads"]} <= {1, 4}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_names_units_and_bounds():
    m = manifest()
    metrics = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in metrics]
    assert len(set(names)) == len(names)
    for x in metrics + m["configs"] + m["workloads"]:
        assert NAME.match(x["name"]), x["name"]
    for x in metrics:
        assert UNIT.match(x["unit"]), x["unit"]
        assert x["better"] in ("lower", "higher")
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert "setup_s" in names
    for text in [w["why"] for w in m["workloads"] + m["configs"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("cell", cells())
def test_every_file_of_a_cell_exists_and_parses(cell):
    m = manifest()
    w = {x["name"]: x for x in m["workloads"]}[cell]
    c = {x["name"]: x for x in m["configs"]}[w["config"]]
    assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
    config = build.load_json(os.path.join(REPO, c["file"]))
    assert set(c["reduced"]) == set(config["reduced"])
    assert not [k for k in c["reduced"] if WIDTH.search(k)]
    model = build.model_group(config)
    assert build.transformer_config(model, build.seq_len(config, {}))
    traffic = build.load_json(os.path.join(
        REPO, "benchmark", "traffic", f"{w['traffic']}.json"
    ))
    scenario = importlib.import_module(
        f"benchmark.scenarios.{traffic['scenario']}"
    )
    assert callable(scenario.run)
    # the preset a rehearsal swaps in has the same keys
    preset = build.load_json(os.path.join(
        REPO, "tests", "benchmark_suite", "presets",
        os.path.basename(c["file"]),
    ))
    assert set(preset) == set(config)


@pytest.mark.parametrize("cell", cells())
def test_every_cell_reports_what_its_metrics_move(cell):
    m = manifest()
    e2e = {x["name"] for x in layers.cell_entries(m, cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = layers.cell_entries(m, cell, "per_layer")
    assert per_layer
    for x in per_layer:
        assert x["moves"] in e2e, (x["name"], x["moves"])


@pytest.mark.parametrize("metric", [x["name"] for x in manifest()["per_layer"]])
def test_every_layer_metric_has_its_file_and_reader(metric):
    entry = {x["name"]: x for x in manifest()["per_layer"]}[metric]
    spec = layers.spec(metric)
    assert spec["layer"] == entry["layer"] and spec["unit"] == entry["unit"]
    assert spec["moves"] == entry["moves"]
    # which cells report a metric is said in BENCHMARK.json alone
    assert "cells" not in spec and "workloads" not in spec
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    # a reader that finds nothing to read returns nothing
    assert reader.read({}, spec.get("params", {})) is None


def test_no_layer_metric_file_is_an_orphan():
    named = {x["name"] for x in manifest()["per_layer"]}
    found = {
        f[:-len(".json")]
        for f in os.listdir(os.path.join(REPO, "benchmark", "layer_metrics"))
    }
    assert found == named


def test_layers_of_one_name_are_spelt_alike_and_listed_in_perf_md():
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for x in manifest()["per_layer"]:
        assert f"| {x['layer']} |" in perf, x["layer"]


def test_run_py_knows_no_cell():
    for name in ("run.py", "worker.py", "build.py", "layers.py"):
        with open(os.path.join(REPO, "benchmark", name)) as f:
            text = f.read()
        m = manifest()
        for x in m["workloads"] + m["configs"]:
            assert x["name"] not in text, (name, x["name"])
        for x in m["workloads"]:
            assert f'"{x["traffic"]}"' not in text
