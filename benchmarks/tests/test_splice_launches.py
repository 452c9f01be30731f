"""PR 55's side of the benchmark: `splice_launches_per_admission` is a data
file read by the reader the benchmark has, has its entry in BENCHMARK.json,
reads the program's two counters, and is left out for a program without
them (the parent of PR 55)."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.layer_readers import counter_ratio  # noqa: E402

NAME = "splice_launches_per_admission"


def spec() -> dict:
    with open(os.path.join(BENCH, "layer_metrics", NAME + ".json"),
              encoding="utf-8") as fh:
        return json.load(fh)


def ctx(then: dict, now: dict) -> dict:
    return {"marked": {"metrics": {"counters": then}},
            "collected": {"metrics": {"counters": now}}}


def test_the_entry_and_the_file_name_the_same_metric():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "launches", "better": "lower",
        "source": "program_counter",
        "layer": "paged engine (engine/paged.py)", "moves": "out_tok_s"}
    assert spec()["reader"] == "counter_ratio"
    assert {e["name"] for e in bench["end_to_end"]} >= {entry["moves"]}


def test_launches_over_admissions_from_mark_to_collection():
    args = spec()["args"]
    # 230 admissions in the window: three quarters a reader's 17 launches,
    # a quarter one.
    then = {"engine_admissions": 50, "engine_stage_block_launches": 600}
    now = {"engine_admissions": 280,
           "engine_stage_block_launches": 600 + 172 * 17 + 58}
    assert counter_ratio.read(args, ctx(then, now)) == (172 * 17 + 58) / 230
    # No hit in the window: the numerator's series may not be there yet.
    assert counter_ratio.read(args, ctx({}, {"engine_admissions": 3})) == 0.0


def test_a_program_without_the_counters_reports_nothing():
    args = spec()["args"]
    parent = {"engine_prompt_tokens_admitted": 7_000_000,
              "engine_dispatches": 90_000}
    assert counter_ratio.read(args, ctx(parent, parent)) is None
    assert counter_ratio.read(
        args, ctx({"engine_admissions": 4}, {"engine_admissions": 4})) is None
