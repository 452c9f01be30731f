"""The `dispatch_ledger` reader and the seven metrics PR 59 appended: the
fit on hand-made counters, a column left out, nothing under
`min_dispatches` or on a program without the series, and every entry and
file found by its NAME (never by its place in a list)."""

import json
import os

import pytest

from benchmarks import readers
from benchmarks.layer_readers import dispatch_ledger

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENGINE = "paged engine (engine/paged.py)"
QUEUE = ("tutoring server and queue (serving/tutoring_server.py, "
         "engine/batcher.py)")
ADDED = {
    "decode_iteration_dev_us": ("us", "lower", ENGINE, "dispatch_ledger"),
    "narrow_pass_dev_ms": ("ms", "lower", ENGINE, "dispatch_ledger"),
    "wide_pass_dev_ms": ("ms", "lower", ENGINE, "dispatch_ledger"),
    "dispatches_timed_share": ("%", "higher", ENGINE, "counter_ratio"),
    "device_dry_dispatch_share": ("%", "lower", ENGINE, "counter_ratio"),
    "decode_iteration_dev_p95_us": ("us", "lower", ENGINE,
                                    "metrics_histogram"),
    "incoming_wait_p95_ms": ("ms", "lower", QUEUE, "metrics_histogram"),
}
LISTED = {"decode_iteration_dev_p95_us": ["kimi-linear.notes-herd",
                                          "nemotron3-nano.notes-herd"],
          # Every cell but the one whose timed dispatches held 2 to 12.
          "wide_pass_dev_ms": [
              "gpt2-xl.deadline-herd", "trinity-mini.notes-herd",
              "ax-k1.notes-crowd", "nemotron3-nano.notes-herd",
              "kimi-linear.notes-herd", "lfm2-8b-a1b.notes-hall"]}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def counters(held, b=80_000, n=5_000, w=14_000, chunk=16):
    """The `engine_timed_*` counters of dispatches that held `held`
    (narrow, wide) passes each and took b + n narrow + w wide us."""
    c = dict.fromkeys(
        ("dispatches", "iterations", "device_us", "narrow_passes",
         "wide_passes", "narrow_sq", "wide_sq", "narrow_x_wide",
         "us_x_narrow", "us_x_wide"), 0)
    for narrow, wide in held:
        us = b + n * narrow + w * wide
        for key, amount in (
                ("dispatches", 1), ("iterations", chunk), ("device_us", us),
                ("narrow_passes", narrow), ("wide_passes", wide),
                ("narrow_sq", narrow * narrow), ("wide_sq", wide * wide),
                ("narrow_x_wide", narrow * wide),
                ("us_x_narrow", us * narrow), ("us_x_wide", us * wide)):
            c[key] += amount
    # The program puts a counter into /metrics at its first increment.
    return {"engine_timed_" + k: v for k, v in c.items() if v}


def ctx(now, then=None):
    return {"marked": {"metrics": {"counters": then or {}}},
            "collected": {"metrics": {"counters": now}}}


def read(of, now, then=None, **args):
    return dispatch_ledger.read({"of": of, **args}, ctx(now, then))


MIXED = [(0, 0), (1, 0), (2, 1), (0, 2), (3, 0), (1, 1), (0, 0), (4, 2)] * 4


@pytest.mark.parametrize("of,want", [
    ("iteration", 80_000 / 16), ("narrow_pass", 5.0), ("wide_pass", 14.0)])
def test_the_fit_gives_back_the_costs(of, want):
    assert read(of, counters(MIXED)) == pytest.approx(want, rel=1e-6)


def test_the_fit_is_over_the_windows_growth():
    """What stood in the counters at the mark (other costs altogether) is
    differenced away."""
    before = counters([(1, 1), (5, 0), (0, 3)] * 10, b=300_000, n=1, w=2)
    window = counters(MIXED)
    now = {k: window.get(k, 0) + before.get(k, 0)
           for k in set(window) | set(before)}
    assert read("wide_pass", now, before) == pytest.approx(14.0, rel=1e-6)
    assert read("iteration", now, before) == pytest.approx(5_000, rel=1e-6)


def test_a_column_that_did_not_grow_is_left_out():
    """A window that never ran a wide pass: no wide cost, and the other
    two from the fit without the column."""
    now = counters([(0, 0), (1, 0), (3, 0), (2, 0)] * 6)
    assert "engine_timed_wide_sq" not in now
    assert read("wide_pass", now) is None
    assert read("narrow_pass", now) == pytest.approx(5.0, rel=1e-6)
    assert read("iteration", now) == pytest.approx(5_000, rel=1e-6)
    bare = counters([(0, 0)] * 25)
    assert read("narrow_pass", bare) is None
    assert read("iteration", bare) == pytest.approx(5_000, rel=1e-6)


def test_nothing_under_min_dispatches():
    now = counters(MIXED[:19])
    assert read("iteration", now) is None
    assert read("iteration", now, min_dispatches=19) == pytest.approx(
        5_000, rel=1e-6)
    assert read("iteration", counters(MIXED[:20])) is not None


def test_nothing_where_the_equations_are_singular():
    """Every dispatch ran the same passes: b and n cannot be told apart."""
    assert read("iteration", counters([(2, 0)] * 30)) is None
    assert read("narrow_pass", counters([(2, 0)] * 30)) is None


def test_nothing_on_a_program_without_the_ledger():
    parent = {"engine_scan_iterations": 4096, "engine_prefill_passes": 200}
    for of in ("iteration", "narrow_pass", "wide_pass"):
        assert read(of, parent, {"engine_scan_iterations": 16}) is None
    # The data-file metrics on the readers that were there say nothing
    # there either, and nothing raises.
    document = {"marked": {"metrics": {"counters": {}}},
                "collected": {"metrics": {"counters": parent, "latency": {}},
                              "window": {}}}
    for name, (_, _, _, reader) in ADDED.items():
        spec = _load("benchmarks", "layer_metrics", name + ".json")
        assert readers.read(reader, spec["args"], document) is None


@pytest.mark.parametrize("name", sorted(ADDED))
def test_an_added_metric_has_its_entry_and_its_file(name):
    unit, better, layer, reader = ADDED[name]
    entries = [m for m in _load("BENCHMARK.json")["per_layer"]
               if m["name"] == name]
    want = {"name": name, "unit": unit, "better": better,
            "source": "program_span", "layer": layer, "moves": "out_tok_s"}
    if name in LISTED:
        # A window may hold no bare dispatch: listed where every run had.
        want["workloads"] = LISTED[name]
    assert entries == [want]
    spec = _load("benchmarks", "layer_metrics", name + ".json")
    assert spec["reader"] == reader and spec["what"]
    assert readers.resolve(reader)
    if reader == "dispatch_ledger":
        assert spec["args"]["of"] in ("iteration", "narrow_pass", "wide_pass")
        assert "engine_timed_dispatches" in spec["what"]


def test_the_shares_are_of_every_megastep_reaped():
    now = {"engine_timed_dispatches": 60, "engine_timed_long_dispatches": 10,
           "engine_untimed_dispatches_late": 20,
           "engine_untimed_dispatches_unanchored": 10,
           "engine_dispatches_device_dry": 5}
    for name, want in (("dispatches_timed_share", 70.0),
                       ("device_dry_dispatch_share", 5.0)):
        spec = _load("benchmarks", "layer_metrics", name + ".json")
        assert readers.read(spec["reader"], spec["args"],
                            ctx(now)) == pytest.approx(want)
