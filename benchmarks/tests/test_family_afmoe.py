"""The `afmoe` family (Trinity-Mini; rehearsal configuration `tiny-afmoe`)
through the seam of `families/`, as new files only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

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

NEW_METRICS = ("moe_experts_reached_share", "tokens_past_window_share",
               "moe_experts_dev_us_per_tok", "moe_experts_roofline")
CELL = "trinity-mini.notes-herd"


def load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def config():
    return load("configs", "tiny-afmoe.json")


def test_the_family_comes_through_the_seam_and_weights_are_lazy(config):
    import jax.numpy as jnp

    from benchmarks import families
    from distributed_lms_raft_llm_tpu.models import registry

    fam = families.of_config(config)
    assert fam.name == "afmoe"
    assert fam.reference.CONTROLS == (
        "int8_weights", "int8_kv", "fp8_activations", "no_window")
    fam.compare.check_sizes(config, registry.resolve(
        "afmoe-tiny", jnp.bfloat16)[1])
    w = fam.weights.of_config(2 ** 31 + 5, config, jnp.float32)
    # Nothing is drawn until asked for; the same leaf twice is the same,
    # and the bfloat16 tree is the float32 draw, cast.
    a, b = w.layer(3), w.layer(3)
    assert all((a[k] == b[k]).all() for k in a)
    half = fam.weights.of_config(2 ** 31 + 5, config, jnp.bfloat16)
    tree = fam.weights.program_tree(half)
    assert len(tree["layers"]) == 5 and "mlp" in tree["layers"][0]
    moe = tree["layers"][3]["moe"]
    assert moe["wg"].dtype == jnp.bfloat16 and moe["br"].dtype == jnp.float32
    assert (moe["wg"] == a["mlp.experts.gate_proj"].astype(
        jnp.bfloat16)).all()
    # the head's quiet rows (the byte fallback's ids 128-255)
    head = w.head()
    assert float(abs(head[128:256]).max()) < 0.1 * float(abs(head[:128]).max())


def test_the_served_precision_passes_and_the_controls_read_as_recorded(config):
    """The program's bfloat16 path is inside every limit of the file. The
    window left out and fp8 activations are outside at least one. The two
    int8 controls are NOT, at this size on the CPU: bfloat16 keeps eight
    significant bits, as int8 does, and a reference that is float32 but for
    one int8 rounding reads under the served path's own distance
    (families/afmoe/README.md has the chip's readings, where the limits of
    configs/trinity-mini.json come from)."""
    from benchmarks import check, serve
    from benchmarks.families.afmoe import compare, reference

    limits = config["check"]["limits"]
    assert set(limits) == {
        "logits_distance", "logits_worst_position_distance",
        "keys_and_values_distance", "first_layer_keys_and_values_distance",
        "routing_disagreement"}
    engine = serve.build_engine(config, 5)
    assert engine.family.name == "afmoe" and engine.family.routed
    got = check.compare(engine.family, engine.cfg, engine.params, config, 5)
    assert got["ok"], got["worst"]
    for seed in (1, 2):
        seqs = check.sequences_of(config, seed)[:1]
        want = check.reference_side(config, seed, seqs)
        for name in ("fp8_activations", "no_window"):
            ctl = check.reference_side(config, seed, seqs, name)
            read = compare.readings(ctl[0], want[0])
            assert not check.verdict([read], limits)["ok"], (name, read)
    with pytest.raises(ValueError):
        check.reference_side(config, 1, seqs, "int2_everything")
    # A side that routes nothing as the reference does has no distance.
    import numpy as np
    turned = want[0][:3] + (np.roll(np.asarray(want[0][3]), 1, axis=-1),)
    read = compare.readings(turned, want[0])
    assert read["logits_distance"] == float("inf")
    assert read["routing_disagreement"] > 0.5
    assert not check.verdict([read], limits)["ok"]


def test_a_program_broken_underneath_fails_the_comparison(config):
    from benchmarks import check, serve

    engine = serve.build_engine(config, 9)
    forward = engine.family.forward

    def broken(params, cfg, ids, **kw):
        return forward(params, cfg, (ids + 1) % cfg.vocab_size, **kw)

    got = check.compare(engine.family._replace(forward=broken), engine.cfg,
                        engine.params, config, 9)
    assert not got["ok"]


def test_bytes_and_operations_by_hand(config):
    from benchmarks import roofline
    from benchmarks.families.afmoe import roofline as counted

    # 5 layers (1 dense), 32 wide, 4 heads and 2 kv heads of 16, dense MLP
    # 64, 8 experts of 16 (2 a token, 1 shared), 384 tokens, window 8.
    # Attention: q, o, gate 3 x 32 x 64; k, v 2 x 32 x 32; 4 norms of 32 and
    # 2 of 16. Dense MLP 3 x 32 x 64. An expert layer outside its routed
    # experts: router 32 x 8, bias 8, shared 3 x 32 x 16.
    assert counted.trunk_params(config) == (
        5 * (6144 + 2048 + 128 + 32) + 6144 + 4 * (256 + 8 + 1536)
        + 32 + 384 * 32)
    assert counted.expert_params(config) == 3 * 32 * 16
    # 2 picks of 8: 8 (1 - (7/8)^2) = 1.875 experts expected
    assert counted.expected_reached(config, 1.0) == pytest.approx(1.875)
    # K and V: 2 x 2 heads x 16 x 2 bytes a layer and key; the full layer
    # reads all 20 keys, the four sliding layers the window's 8
    assert counted.kv_bytes_per_slot(config, 20.0) == 128 * (20 + 4 * 8)
    assert counted.kv_bytes_per_slot(config, 5.0) == 128 * 5 * 5
    assert counted.slot_ops(config, 20.0) == (
        2.0 * (counted.trunk_params(config) + 4 * 2 * 1536)
        + 4.0 * 4 * 16 * (20 + 4 * 8))
    # 10 steps that advanced 30 slot-tokens; the program counted 70 experts
    trace = {"span_counters": {"engine_scan_iterations": 10,
                               "moe_experts_reached": 70},
             "loops": [["%while.4 (s32[])", 12.0]]}
    cost = counted.cost(config, trace, 30.0, 20.0)
    assert cost["steps"] == 10 and cost["steps_by_loop"] == 12.0
    assert cost["experts_reached"] == 70.0
    assert cost["experts_reached_per_layer_and_step"] == 1.75
    assert cost["bytes"] == (
        10 * counted.trunk_params(config) * 2 + 70 * 1536 * 2
        + 30 * counted.kv_bytes_per_slot(config, 20.0))
    assert cost["ops"] == counted.slot_ops(config, 20.0) * 30
    experts = counted.experts_cost(config, trace, 30.0, 20.0)
    assert experts["bytes"] == 70 * 1536 * 2
    assert experts["ops"] == 2.0 * 30 * 4 * 2 * 1536
    assert roofline.least_seconds(experts, "TPU v5 lite")["bound"] == "memory"
    # a program without the counter: the number expected from the lanes
    trace = {"span_counters": {"engine_scan_iterations": 10}}
    cost = counted.cost(config, trace, 30.0, 20.0)
    assert cost["experts_reached"] == pytest.approx(
        10 * 4 * counted.expected_reached(config, 3.0))
    assert counted.cost(config, {"span_counters": {}}, 30.0, 20.0) is None
    assert counted.experts_cost(config, {}, 30.0, 20.0) is None


def _ctx(config, device_ops, counters):
    from benchmarks.run import Outcome

    o = Outcome(0.0, 100)
    o.sent, o.token_times = 0.0, [(1.0, 16), (2.0, 16), (3.0, 16)]
    return {"outcomes": [o], "trace_span": (1.0, 3.0),
            "trace": {"window_s": 2.0, "busy_s": 1.8, "programs": {},
                      "loops": [], "device_ops": device_ops,
                      "span_counters": counters},
            "traffic_spec": {"template_tokens": 0}, "config": config,
            "device": {"kind": "TPU v5 lite"}}


def test_the_new_reader_on_a_hand_made_trace(config):
    """32 tokens in a span of 2 s; the grouped products took 0.4 + 0.1 s of
    it; 70 experts of 3,072 bytes were reached."""
    from benchmarks import readers, roofline

    ops = [["%fusion.3 bf16[16,32]", 1.0],
           ["%ragged-dot-none.7 bf16[32,16]", 0.4],
           ["%ragged-dot-metadata.2 s32[9]", 0.1]]
    counters = {"engine_scan_iterations": 10, "moe_experts_reached": 70}
    per_tok = load("layer_metrics", "moe_experts_dev_us_per_tok.json")
    share = load("layer_metrics", "moe_experts_roofline.json")
    assert per_tok["reader"] == share["reader"] == "trace_op_time"
    ctx = _ctx(config, ops, counters)
    assert readers.read("trace_op_time", per_tok["args"], ctx) == (
        pytest.approx(1e6 * 0.5 / 32))
    got = readers.read("trace_op_time", share["args"], ctx)
    least = 70 * 1536 * 2 / roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert got == pytest.approx(100.0 * least / 0.5)
    assert ctx["notes"]["moe_experts_roofline"]["device_s"] == 0.5
    # a trace without such an operation (GPT-2, the parent, a CPU
    # rehearsal), or no trace: nothing to read, and nothing raised
    for args in (per_tok["args"], share["args"]):
        assert readers.read("trace_op_time", args,
                            _ctx(config, ops[:1], counters)) is None
        assert readers.read("trace_op_time", args,
                            dict(ctx, trace=None)) is None
    # a family that has no such floor (GPT-2's roofline.py)
    gpt2 = load("configs", "tiny.json")
    assert readers.read("trace_op_time", share["args"],
                        _ctx(gpt2, ops, counters)) is None


def test_the_counter_metrics_read_a_share_or_nothing():
    from benchmarks import readers

    def snap(**counters):
        return {"metrics": {"counters": counters, "latency": {}}}

    reached = load("layer_metrics", "moe_experts_reached_share.json")
    past = load("layer_metrics", "tokens_past_window_share.json")
    ctx = {"marked": snap(moe_experts_reached=100, moe_expert_seats=512,
                          engine_tokens_emitted=1000),
           "collected": snap(moe_experts_reached=420, moe_expert_seats=1024,
                             engine_tokens_emitted=3000,
                             engine_tokens_past_window=190)}
    assert readers.read(reached["reader"], reached["args"], ctx) == 62.5
    assert readers.read(past["reader"], past["args"], ctx) == 9.5
    parent = {"marked": snap(), "collected": snap(engine_lane_steps=7)}
    assert readers.read(reached["reader"], reached["args"], parent) is None
    assert readers.read(past["reader"], past["args"], parent) is None


def named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, (name, found)
    return found[0]


def test_the_cell_and_its_metrics_are_found_by_name():
    # Later PRs append configurations, cells and metrics: an entry is found
    # by its name, never by its place.
    bench = load(os.pardir, "BENCHMARK.json")
    conf = named(bench["configs"], "trinity-mini")
    assert conf["file"] == "benchmarks/configs/trinity-mini.json"
    work = named(bench["workloads"], CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "trinity-mini", "notes-herd", 1)
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW_METRICS}
    for name in NEW_METRICS:
        m = named(bench["per_layer"], name)
        # Later routed families list their cells beside this one.
        assert m["moves"] == "out_tok_s" and CELL in m["workloads"]
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".json"))
    assert named(bench["per_layer"],
                 "tokens_past_window_share")["workloads"] == [CELL]
    assert named(bench["per_layer"],
                 "tokens_past_window_share")["layer"] in layers
    cell = load("workloads", CELL + ".json")
    spec = load("traffic", cell["traffic"] + ".json")
    assert (cell["config"], cell["students"]) == ("trinity-mini", 32)
    assert [(c["context_tokens"], c["share"]) for c in spec["courses"]] == [
        (152, 60), (104, 30), (2304, 10)]
    doc = load("configs", "trinity-mini.json")
    assert doc["registry_model"] == "trinity-mini-1d4e"
    assert doc["serving"]["length_buckets"] == [256, 2560]
    assert doc["check"]["width"] == 2560 + 128
    assert not any(w["name"].startswith("tiny") for w in bench["workloads"])
