"""PR 29's seam: a configuration names its family, a metric's file names a
reader that may be a file of its own, and nothing family-blind knows a
family's words.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import families, readers  # noqa: E402

GENERIC = ("run.py", "serve.py", "check.py", "check_seeds.py", "readers.py",
           "roofline.py", "trace.py", "traffic.py", "sweep.py", "stats.py",
           "families/__init__.py", "layer_readers/__init__.py",
           "layer_readers/counter_ratio.py")
WORDS = ("gpt2", "n_embd", "n_head", "n_layer", "layer_norm_epsilon")


def load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", GENERIC)
def test_a_generic_file_knows_no_familys_words(name):
    with open(os.path.join(BENCH, name), encoding="utf-8") as fh:
        text = fh.read()
    assert [w for w in WORDS if w in text] == []


# What `benchmarks/weights.py` of the parent (8c06de4) drew for `tiny`:
# sha256 over the sorted names and the float32 bytes of each tensor.
PARENT_WEIGHTS = {
    (7, "float32"):
        "5b0aa7d5eba44eb4e6bad1c4821556a72c3f90173d44896c8d13cbd3f76a53f6",
    (7, "bfloat16"):
        "f8c30fd56dad89c7e6b8fb3d980d7af036b298c67be6cd0616ecfa99903dace8",
    (2 ** 31 + 12345, "float32"):
        "debd4bbfbe6d3fc2ebbb79ee5639e7c836d100a674a00054eb28a2e110077a82",
    (2 ** 31 + 12345, "bfloat16"):
        "b0b6bc3d320c2c4d31bea5aed31d7392da7deb74c6ffc713d683b513fc1d89dd",
}


@pytest.mark.parametrize("seed,dtype", sorted(PARENT_WEIGHTS))
def test_weights_through_the_seam_are_the_parents(seed, dtype):
    import jax.numpy as jnp
    import numpy as np

    config = load("configs", "tiny.json")
    w = families.of_config(config).weights.of_config(
        seed, config, jnp.dtype(dtype))
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k].astype(jnp.float32)).tobytes())
    assert h.hexdigest() == PARENT_WEIGHTS[seed, dtype]


@pytest.mark.parametrize("name", families.names())
def test_a_family_has_the_four_parts_and_their_names(name):
    fam = families.load(name)
    assert callable(fam.weights.of_config)
    assert callable(fam.weights.program_tree)
    assert callable(fam.reference.forward) and fam.reference.CONTROLS
    for f in ("check_sizes", "program", "readings"):
        assert callable(getattr(fam.compare, f))
    assert callable(fam.roofline.cost)


def test_the_roofline_part_alone_does_not_bring_jax():
    """The load-generating parent reads a family's roofline count and never
    imports jax."""
    import subprocess

    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmarks import families; "
            "[families.load(n, ('roofline',)) for n in families.names()]; "
            "sys.exit('jax' in sys.modules)" % REPO)
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0


def test_an_unknown_family_fails_with_the_directory_listed():
    with pytest.raises(KeyError) as e:
        families.load("transformer-of-tomorrow")
    assert all(n in str(e.value) for n in families.names())
    with pytest.raises(KeyError) as e:
        families.of_config({"name": "nameless"})
    assert "family" in str(e.value)
    assert "gpt2" in families.names()


def test_an_unknown_reader_fails_with_both_places_listed():
    with pytest.raises(KeyError) as e:
        readers.read("reader_of_tomorrow", {}, {})
    assert "counter_ratio" in str(e.value)
    assert "metrics_histogram" in str(e.value)
    assert readers.resolve("counter_ratio").__module__ == (
        "benchmarks.layer_readers.counter_ratio")
    assert readers.resolve("roofline") is readers.roofline_share


@pytest.mark.parametrize("config", sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "configs"))))
def test_a_configuration_names_its_family_and_its_limits(config):
    doc = load("configs", config + ".json")
    assert doc["family"] in families.names()
    limits = doc["check"]["limits"]
    # A distance has a limit above 0; an exact comparison (a recurrent
    # family's `idle_rows_state_change`) has the limit 0.
    assert limits and all(isinstance(v, float) and v >= 0
                          for v in limits.values())
    assert any(v > 0 for v in limits.values())
    assert all(name.endswith("_change") for name, v in limits.items()
               if v == 0)


# ------------------------------------------------------------- counter_ratio


def snapshot(counters, first_tokens):
    return {"metrics": {"counters": counters,
                        "latency": {"prefill_wait": {"count": first_tokens},
                                    "ttft": {"count": 999}}}}


LANES = {m: load("layer_metrics", f"lanes_{m}_share.json")
         for m in ("decode", "staged", "overrun", "dead")}


def test_counter_ratio_on_a_hand_made_pair_of_snapshots():
    # before the window: 1,000 lane-steps; in it 4,000 more, of which 2,100
    # tokens (100 of them requests' first tokens), 400 staged, 1,000 overrun
    ctx = {
        "marked": snapshot({"engine_lane_steps": 1000,
                            "engine_tokens_emitted": 500,
                            "engine_staged_lane_steps": 50,
                            "engine_overrun_lane_steps": 200}, 10),
        "collected": snapshot({"engine_lane_steps": 5000,
                               "engine_tokens_emitted": 2600,
                               "engine_staged_lane_steps": 450,
                               "engine_overrun_lane_steps": 1200}, 110),
    }
    got = {m: readers.read(spec["reader"], spec["args"], ctx)
           for m, spec in LANES.items()}
    assert got["decode"] == pytest.approx(100.0 * (2100 - 100) / 4000)
    assert got["staged"] == pytest.approx(10.0)
    assert got["overrun"] == pytest.approx(25.0)
    # the program puts a counter into /metrics at its first increment
    assert got["dead"] == 0.0
    assert sum(got.values()) <= 100.0
    ctx["collected"]["metrics"]["counters"]["megastep_dead_lane_tokens"] = 40
    assert readers.read("counter_ratio", LANES["dead"]["args"],
                        ctx) == pytest.approx(1.0)


@pytest.mark.parametrize("collected", [
    {"engine_tokens_emitted": 2600},                       # no denominator
    {"engine_lane_steps": 1000, "engine_tokens_emitted": 2600},  # no growth
])
def test_counter_ratio_reads_nothing_without_a_denominator(collected):
    ctx = {"marked": snapshot({"engine_lane_steps": 1000}, 0),
           "collected": snapshot(collected, 5)}
    for spec in LANES.values():
        assert readers.read("counter_ratio", spec["args"], ctx) is None


def test_the_roofline_reader_asks_the_configurations_family():
    """Steps from the family's choice of count, bytes and operations from
    its shapes, seconds from the generic table of peaks; the family's note
    is kept also where there is no device time to hold it against."""
    from benchmarks import roofline
    from benchmarks.families.gpt2 import roofline as counted
    from benchmarks.run import Outcome

    o = Outcome(0.0, 100)
    o.sent, o.token_times = 0.0, [(1.0, 16), (2.0, 16), (3.0, 16)]
    config = load("configs", "gpt2-xl.json")
    trace = {"window_s": 1.0, "busy_s": 0.9,
             "programs": {"jit__megastep_program": 0.8},
             "loops": [["%while.9 (s32[])", 90.0], ["%while.2 (s32[])", 3.0]],
             "span_counters": {"engine_scan_iterations": 74}}
    ctx = {"outcomes": [o], "trace_span": (1.0, 3.0), "trace": trace,
           "traffic_spec": {"template_tokens": 36}, "config": config,
           "device": {"kind": "TPU v5 lite"}}
    args = load("layer_metrics", "engine_roofline.json")["args"]
    share = readers.read("roofline", args, ctx)
    note = ctx["notes"]["roofline"]
    assert note["steps"] == 90 and note["steps_by_counter"] == 74
    assert note["steps_less_counter"] == 16
    want = roofline.least_seconds(counted.cost(
        config, trace, note["slot_steps"], note["mean_context"]),
        "TPU v5 lite")
    assert share == pytest.approx(100.0 * want["seconds"] / 0.8)
    assert note["seconds"] == want["seconds"] and note["bound"] == "memory"
    # no device time (a rehearsal): the note stays, the metric is left out
    ctx = dict(ctx, trace=dict(trace, programs={}), notes={},
               device={"kind": "cpu"})
    assert readers.read("roofline", args, ctx) is None
    assert ctx["notes"]["roofline"]["steps"] == 90
    # no loop counted: nothing at all
    ctx = dict(ctx, trace=dict(trace, loops=[]), notes={})
    assert readers.read("roofline", args, ctx) is None and not ctx["notes"]


@pytest.mark.parametrize("config", ["tiny", "tiny-moe"])
def test_a_program_broken_underneath_fails_the_comparison(config):
    """The comparison as a run makes it, on the engine a run builds, with
    the program's forward altered where it takes its tokens (every id
    turned by one): not correct, for every family."""
    from benchmarks import check, serve

    doc = load("configs", config + ".json")
    engine = serve.build_engine(doc, 9)
    sound = check.compare(engine.family, engine.cfg, engine.params, doc, 9)
    assert sound["ok"]
    forward = engine.family.forward

    def broken(params, cfg, ids, **kw):
        return forward(params, cfg, (ids + 1) % cfg.vocab_size, **kw)

    got = check.compare(engine.family._replace(forward=broken), engine.cfg,
                        engine.params, doc, 9)
    assert not got["ok"]
    assert got["worst"]["logits_distance"] > 5 * sound["limits"][
        "logits_distance"]
