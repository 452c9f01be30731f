"""`staged_iterations_p95`: the one per-layer metric PR 28 adds is a data
file alone. It has to name a reader the benchmark already has, read a
histogram the program declares and the engine reports, carry its entry in
`BENCHMARK.json`, and read nothing (not raise) from a program without the
histogram, as the parent commit is."""

import json
import os

import pytest

from benchmarks import readers
from distributed_lms_raft_llm_tpu.utils import metrics_registry as metric

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "staged_iterations_p95"


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return _load("benchmarks", "layer_metrics", NAME + ".json")


def test_metric_file_names_a_reader_the_benchmark_has(spec):
    assert spec["reader"] in readers.READERS
    assert spec["reader"] == "metrics_histogram"
    assert spec["args"]["percentile"] in (50, 95)
    assert spec["args"]["scale"] == 1.0  # iterations, not seconds


def test_histogram_is_declared_and_reported_by_the_engine(spec):
    name = spec["args"]["histogram"]
    assert name == metric.ENGINE_STAGED_ITERATIONS
    assert metric.is_declared(name)
    assert name in metric.ENGINE_LOOP_HISTOGRAMS.values()


def test_benchmark_json_has_the_entry_well_formed(moves_a_reported_metric):
    """Found by its name, not by its place: later PRs append metrics."""
    per_layer = _load("BENCHMARK.json")["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == NAME]
    accepted = [m for m in per_layer if m is not entry]
    moves_a_reported_metric(entry)
    assert {k: v for k, v in entry.items() if k != "moves"} == {
        "name": NAME, "unit": "iterations", "better": "lower",
        "source": "program_counter",
        "layer": "paged engine (engine/paged.py)",
    }
    assert entry["layer"] in {m["layer"] for m in accepted}, \
        "an accepted layer's name, to the letter"


@pytest.mark.parametrize("window,expected", [
    ({"engine_staged_iterations": {"count": 208, "p50_s": 2.0,
                                   "p95_s": 7.0}}, 7.0),
    ({"engine_decode_lanes": {"count": 3, "p95_s": 9.0}}, None),
    ({}, None),
], ids=["change", "parent_without_the_histogram", "empty_window"])
def test_reader_reads_the_p95_or_nothing(spec, window, expected):
    ctx = {"collected": {"window": window}}
    assert readers.read(spec["reader"], spec["args"], ctx) == expected
