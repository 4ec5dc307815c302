"""Where a start goes, as six metrics over readers the benchmark had:
``evidence_value`` on the attributes of the trainer's ``compile`` event and
``program_spans`` on its start-up spans, checked by hand on a small
recorded start.  (The manifest's tests hold each metric's file, reader and
``moves``; the traced rehearsals of ``test_benchmark_program_spans.py`` hold
that every cell's line names the two span metrics.)"""

import importlib
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import build, layers  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# metric -> what the recorded start reads
METRICS = {
    "compile_trace_s": 21.25,
    "compile_lower_s": 14.5,
    "compile_backend_s": 5.5,
    "compile_text_s": 1.75,
    "startup_build_s": 1.5,     # the FIRST trainer's, not the resumed one's
    "startup_init_s": 3.25,
}
PARTS = ("trace_s", "lower_s", "backend_s", "analysis_s", "text_s", "cache",
         "retrieval_s")


def recorded():
    with open(os.path.join(HERE, "recorded_compile_parts.json")) as f:
        return json.load(f)


def read(metric, evidence):
    spec = layers.spec(metric)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(evidence, spec.get("params", {}))


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_start_is_read_from_the_compile_event_and_the_startup_spans(metric):
    assert read(metric, recorded()) == pytest.approx(METRICS[metric])
    entry = {m["name"]: m for m in build.manifest()["per_layer"]}[metric]
    assert entry["moves"] == "setup_s" and entry["source"] == "program_span"
    assert entry["workloads"] == [
        w["name"] for w in build.manifest()["workloads"]
    ]


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_program_that_names_no_parts_gives_nothing(metric):
    """The parent's ``compile`` event has its seconds and not their parts;
    its start-up spans are there since PR 24, an older program's not."""
    evidence = recorded()
    parent = dict(evidence, compile={
        k: v for k, v in evidence["compile"].items() if k not in PARTS
    })
    if metric.startswith("compile_"):
        assert read(metric, parent) is None
        assert read("compile_s", parent) == 41.5
    else:
        assert read(metric, parent) == pytest.approx(METRICS[metric])
    older = dict(parent, startup_spans=evidence["startup_spans"][:2])
    assert read(metric, older) is None
    assert read(metric, dict(parent, compile=None, startup_spans=[])) is None


def test_the_parts_add_up_to_the_seconds_and_the_text_lies_beside_them():
    said = recorded()["compile"]
    in_seconds = sum(
        read(f"compile_{part}_s", recorded())
        for part in ("trace", "lower", "backend")
    ) + said["analysis_s"]
    assert in_seconds == pytest.approx(read("compile_s", recorded()))
    assert read("compile_text_s", recorded()) == said["text_s"]
