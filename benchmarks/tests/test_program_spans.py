"""PR 27's side of the benchmark: the engine's spans label the idle gaps,
the named programs are told apart, and the seven metric files this PR adds
name a reader that exists and have their entry in BENCHMARK.json."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import readers, trace  # noqa: E402

NEW_METRICS = {
    "queue_wait_p95_ms": ("metrics_histogram", "queue_wait"),
    "stream_chunk_gap_p95_ms": ("metrics_histogram", "stream_chunk_gap"),
    "prefill_wait_p95_ms": ("metrics_histogram", "prefill_wait"),
    "host_turn_p95_ms": ("metrics_histogram", "engine_host_turn"),
    "decode_lanes_p50": ("metrics_histogram", "engine_decode_lanes"),
    "megastep_dev_us_per_tok": ("trace_program_time", None),
    "admission_dev_us_per_tok": ("trace_program_time", None),
}

ms = 1_000_000


def hand_made_trace():
    """100 ms: a megastep, a staging burst, a megastep, with the engine's
    TraceAnnotation events on two thread lines (a TPU trace names a line
    after its thread) beside XLA's own."""
    return {
        "devices": [{"plane": "/device:TPU:0", "modules": [
            ["jit__megastep_program(111)", 10 * ms, 40 * ms],
            ["jit__stage_block_program(7)", 56 * ms, 1 * ms],
            ["jit__stage_block_program(7)", 57 * ms, 1 * ms],
            ["jit__stage_program(9)", 58 * ms, 1 * ms],
            ["jit__megastep_program(111)", 60 * ms, 30 * ms],
            ["jit__threefry_split(3)", 92 * ms, 1 * ms],
        ], "ops": []}],
        "host": [
            # the executor thread that ran the engine's turn
            ["executor-0/57", "engine.step", 2 * ms, 58 * ms],
            ["executor-0/57", "engine.reap.wait", 3 * ms, 47 * ms],
            ["executor-0/57", "engine.reap.host", 50 * ms, 2 * ms],
            ["executor-0/57", "engine.admit", 52 * ms, 5 * ms],
            ["executor-0/57", "engine.prog.stage_block", 54 * ms, 1 * ms],
            ["executor-0/57", "engine.dispatch", 57 * ms, 2 * ms],
            ["executor-0/57", "engine.prog.megastep", 58 * ms, 1 * ms],
            ["executor-0/57", "PjitFunction(_megastep_program)", 58 * ms, 1 * ms],
            # the serving loop's thread
            ["asyncio-loop/41", "queue.between_steps", 90 * ms, 9 * ms],
        ],
    }


def test_an_idle_gap_is_named_by_the_engines_innermost_span():
    got = trace.reduce(hand_made_trace())
    assert got["window_s"] == pytest.approx(0.083)     # 10 .. 93 ms
    assert got["busy_s"] == pytest.approx(0.074)
    gaps = dict(got["idle_gaps"])
    # 50-56: its midpoint lies in engine.admit, after the reap; 59-60 in
    # nothing but the turn; 90-92 and 93- between the turns.
    assert gaps == {
        "engine.admit": pytest.approx(0.006),
        "engine.step": pytest.approx(0.001),
        "queue.between_steps": pytest.approx(0.002),
    }
    # the device ended early and the host was still blocked on it: the gap
    # (40-56, named at its midpoint) carries the span's own name, "wait"
    # and all (trace.WAITS drops Python frames that only wait, by their
    # last word; a span's dotted name is one word)
    events = hand_made_trace()
    events["devices"][0]["modules"][0] = [
        "jit__megastep_program(111)", 10 * ms, 30 * ms]
    gaps = dict(trace.reduce(events)["idle_gaps"])
    assert gaps["engine.reap.wait"] == pytest.approx(0.016)


def test_the_named_programs_are_read_apart():
    reduced = trace.reduce(hand_made_trace())
    assert reduced["programs"] == {
        "jit__megastep_program": pytest.approx(0.070),
        "jit__stage_block_program": pytest.approx(0.002),
        "jit__stage_program": pytest.approx(0.001),
        "jit__threefry_split": pytest.approx(0.001),
    }

    def seconds(name):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"),
                  encoding="utf-8") as fh:
            return readers._program_seconds(
                {"trace": reduced}, json.load(fh)["args"]["programs"])

    assert seconds("megastep_dev_us_per_tok") == pytest.approx(0.070)
    assert seconds("admission_dev_us_per_tok") == pytest.approx(0.003)
    # the old pattern keeps megastep and stage, and loses the block copies
    assert seconds("engine_dev_us_per_tok") == pytest.approx(0.071)
    # a program that lacks the names (the parent of PR 27) gives the new
    # metrics nothing to read, and they do not raise
    old = {"trace": {"programs": {"jit__unknown": 0.07}, "window_s": 0.09}}
    for name in ("megastep_dev_us_per_tok", "admission_dev_us_per_tok"):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"),
                  encoding="utf-8") as fh:
            spec = json.load(fh)
        assert readers.read(spec["reader"], spec["args"],
                            dict(old, outcomes=[])) is None


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_names_a_reader_and_has_its_entry(name):
    reader, histogram = NEW_METRICS[name]
    with open(os.path.join(BENCH, "layer_metrics", name + ".json"),
              encoding="utf-8") as fh:
        spec = json.load(fh)
    assert spec["reader"] == reader and reader in readers.READERS
    assert spec["what"]
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    assert entry[0]["moves"] == "out_tok_s" and "workloads" not in entry[0]
    assert entry[0]["layer"] in {m["layer"] for m in bench["per_layer"][:9]}
    if histogram:
        from distributed_lms_raft_llm_tpu.utils import metrics_registry

        assert spec["args"]["histogram"] == histogram
        assert metrics_registry.spec(histogram).kind == "histogram"
        # a server that lacks the histogram (the parent) reads as nothing
        empty = {"collected": {"window": {histogram: {"p50_s": None,
                                                      "p95_s": None}}}}
        assert readers.read(reader, spec["args"], empty) is None
