"""The `nemotron_h` family (Nemotron-3-Nano; rehearsal configuration
`tiny-nemotronh`) through the seam of `families/`, as new files only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_family_nemotron_h.py -q
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

CELL = "nemotron3-nano.notes-herd"
NEW_METRICS = ("ssm_step_dev_us_per_tok", "ssm_step_roofline",
               "prefix_recomputed_for_state_share")
SHARED_METRICS = ("moe_experts_reached_share", "moe_experts_dev_us_per_tok",
                  "moe_experts_roofline", "moe_held_picks_share")
READINGS = {"logits_distance", "logits_worst_position_distance",
            "recurrent_state_distance",
            "first_layer_recurrent_state_distance",
            "keys_and_values_distance",
            "own_input_keys_and_values_distance", "routing_disagreement",
            "idle_rows_state_change"}


def load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as fh:
        return json.load(fh)


def named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


@pytest.fixture(scope="module")
def config():
    return load("configs", "tiny-nemotronh.json")


def test_the_family_comes_through_the_seam_and_weights_are_lazy(config):
    import jax.numpy as jnp

    from benchmarks import families
    from distributed_lms_raft_llm_tpu.models import registry

    fam = families.of_config(config)
    assert fam.name == "nemotron_h"
    assert fam.reference.CONTROLS == (
        "int8_weights", "bf16_state", "int8_kv", "fp8_activations",
        "conv_window_dropped", "state_dropped")
    fam.compare.check_sizes(config, registry.resolve(
        "nemotronh-tiny", jnp.bfloat16)[1])
    w = fam.weights.of_config(2 ** 31 + 5, config, jnp.float32)
    a, b = w.layer(0), w.layer(0)
    assert all((a[k] == b[k]).all() for k in a)
    tree = fam.weights.program_tree(
        fam.weights.of_config(2 ** 31 + 5, config, jnp.bfloat16))
    kinds = ["mamba" if "mamba" in lp else "moe" if "moe" in lp else "attn"
             for lp in tree["layers"]]
    assert kinds == [
        {"M": "mamba", "E": "moe", "*": "attn"}[c]
        for c in config["hybrid_override_pattern"]]
    mamba, moe = tree["layers"][0]["mamba"], tree["layers"][1]["moe"]
    # The state's own parameters and the router's bias stay float32; A in
    # [1, 16], dt in [time_step_min, time_step_max], D = 1.
    for leaf in ("a_log", "dt_bias", "d"):
        assert mamba[leaf].dtype == jnp.float32
    # ... and the bias is the calibrated one, the same for the reference.
    assert moe["br"].dtype == jnp.float32 and moe["br"].any()
    assert (moe["br"] == w.layer(1)["mixer.gate.e_score_correction_bias"]
            ).all()
    assert mamba["w_in"].dtype == jnp.bfloat16
    a_ = jnp.exp(mamba["a_log"])
    assert float(a_.min()) >= 1.0 and float(a_.max()) <= 16.0
    dt = jnp.log1p(jnp.exp(mamba["dt_bias"]))
    assert float(dt.min()) >= 0.999e-3 and float(dt.max()) <= 0.1001
    assert (mamba["d"] == 1).all()
    # The router's columns are one length; the held experts' stacks are
    # the draws, both widths padded with zeros (to whole lanes here).
    wr = w.layer(1)["mixer.gate.weight"]
    norms = jnp.linalg.norm(wr, axis=0)
    assert float(norms.max() - norms.min()) < 1e-4 * float(norms.max())
    m = int(config["moe_intermediate_size"])
    d = int(config["hidden_size"])
    assert moe["wu"].shape == (8, 128, 128) == moe["wd"].shape
    assert not moe["wu"][..., m:].any() and not moe["wu"][:, d:].any()
    assert not moe["wd"][:, m:].any() and not moe["wd"][..., d:].any()
    head = w.head()
    assert float(abs(head[128:256]).max()) < 0.1 * float(abs(head[:128]).max())


def test_the_balancing_bias_makes_the_experts_picked_alike(config):
    """Fresh tokens through the reference: with the calibrated
    `e_score_correction_bias` every expert block's 16 experts are picked
    more alike than with the bias at zero (the spread of an expert's share
    of the picks, summed over the blocks), and the held half gets half."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import families

    fam = families.of_config(config)
    seed = 2 ** 31 + 29
    w = fam.weights.of_config(seed, config, jnp.float32)

    class AtZero:
        def __getattr__(self, name):
            return getattr(w, name)

        def layer(self, i):
            return w.drawn(i)

    ids = np.random.default_rng(3).integers(0, config["vocab_size"], 384)
    check = dict(config, check=dict(config["check"], logit_positions=8))

    def spread(side):
        picks = np.asarray(fam.reference.forward(side, ids, check)[5])
        load = picks.sum(axis=1)                              # [Le, E]
        return (float((load.std(axis=1) / load.mean(axis=1)).sum()),
                float(picks[..., :8].sum() / picks.sum()))

    (balanced, held), (at_zero, _) = spread(w), spread(AtZero())
    assert balanced < 0.6 * at_zero
    assert 0.45 < held < 0.55


def test_the_served_precision_passes_and_the_controls_read_outside(config):
    """The rehearsal is `correct`, and every control that can show at this
    size on the CPU is outside at least one limit of the file: fp8
    activations, the two controls of the carry (the convolution's window,
    or the state, dropped at a chunk boundary), and int8 keys and values
    and int8 weights (both through the keys and values against the float32
    projections of a side's own input: the 8-bit rounding alone, 1.5 and
    2.0 times the served path's). The bfloat16 state is NOT, here: 8 heads
    of 8 x 16 hold no head slow enough for it to show
    (configs/nemotron3-nano.json and PERF.md section 2 have the chip's
    readings at the published widths, where all six controls are outside
    a limit)."""
    from benchmarks import check, serve
    from benchmarks.families.nemotron_h import compare

    limits = config["check"]["limits"]
    assert set(limits) == READINGS
    engine = serve.build_engine(config, 5)
    assert engine.family.name == "nemotron_h"
    assert engine.family.routed and engine.family.recurrent_state
    got = check.compare(engine.family, engine.cfg, engine.params, config, 5)
    assert got["ok"], got["worst"]
    for seed in (1, 2):
        seqs = check.sequences_of(config, seed)[:1]
        want = check.reference_side(config, seed, seqs)
        for name in ("fp8_activations", "conv_window_dropped",
                     "state_dropped", "int8_kv", "int8_weights"):
            ctl = check.reference_side(config, seed, seqs, name)
            read = compare.readings(ctl[0], want[0])
            assert set(read) == READINGS
            assert not check.verdict([read], limits)["ok"], (name, read)
        # (the products of its own input are taken again, on the host)
        same = compare.readings(want[0], want[0])
        assert all(v < 1e-6 for v in same.values()), same
    with pytest.raises(ValueError):
        check.reference_side(config, 1, seqs, "int2_everything")
    # A position is compared only if its held picks agree there and at the
    # positions the convolution's window holds before it.
    import numpy as np
    flipped = list(want[0])
    held = np.asarray(want[0][6]).copy()
    held[0, -3] = ~held[0, -3]
    flipped[6] = held
    rows = compare.readings(tuple(flipped), want[0])
    assert rows["logits_distance"] == 0.0      # what is compared agrees
    held[:] = ~held
    flipped[6] = held
    assert compare.readings(tuple(flipped), want[0])[
        "logits_distance"] == float("inf")     # nothing left to compare


@pytest.mark.parametrize("where", ["pad_positions", "idle_rows"])
def test_a_program_that_moves_the_state_where_nothing_is_live_fails(
        config, where):
    """The comparison sees a forward that lets right-pad positions into the
    recurrence (the state after the last token is another) and one that
    advances the rows of a decode step that are not live (their state is
    not what it was: any change at all is outside the limit of 0)."""
    from benchmarks import check, serve

    engine = serve.build_engine(config, 9)
    forward = engine.family.forward

    def leaky(params, cfg, ids, **kw):
        if (ids.shape[1] > 1) == (where == "pad_positions"):
            kw["live"] = kw["live"] | True
        return forward(params, cfg, ids, **kw)

    sound = check.compare(engine.family, engine.cfg, engine.params, config, 9)
    got = check.compare(engine.family._replace(forward=leaky), engine.cfg,
                        engine.params, config, 9)
    assert sound["worst"]["idle_rows_state_change"] == 0.0
    assert not got["ok"]
    if where == "pad_positions":
        assert (got["worst"]["first_layer_recurrent_state_distance"]
                > 3 * sound["worst"]["first_layer_recurrent_state_distance"])
    else:
        assert got["worst"]["idle_rows_state_change"] > 0.5


def test_bytes_and_operations_by_hand(config):
    from benchmarks import roofline
    from benchmarks.families.nemotron_h import roofline as counted

    # tiny: 32 wide, MEMEM*EME, 8 Mamba heads of 8 (inner 64), 2 groups,
    # state 16, kernel 4 (conv over 64 + 2 x 2 x 16 = 128 channels); 4
    # heads on 2 kv heads of 8; 8 of 16 experts of 16 held, 3 a token, a
    # shared expert of 32; 384 tokens.
    mamba = 32 * (64 + 128 + 8) + 64 * 32 + 128 * 5 + 3 * 8 + 64
    attn = 32 * 8 * (4 + 2 * 2) + 4 * 8 * 32
    rest = 32 * 16 + 16 + 2 * 32 * 32
    assert counted.mamba_params(config) == mamba
    assert counted.attention_params(config) == attn
    assert counted.routed_rest_params(config) == rest
    assert counted.expert_params(config) == 2 * 32 * 16
    assert counted.trunk_params(config) == (
        4 * mamba + attn + 4 * rest + 9 * 32 + 32 + 384 * 32)
    assert counted.parameters(config) == (
        counted.trunk_params(config) + 384 * 32 + 4 * 8 * 1024)
    assert counted.held_picks_per_token(config) == 1.5
    assert counted.expected_reached(config, 1.0) == pytest.approx(
        8 * (1 - (15 / 16) ** 3))
    assert counted.kv_bytes_per_token(config) == 2 * 2 * 8 * 2
    assert counted.ssm_bytes_per_slot(config) == 64 * 16 * 4
    assert counted.conv_bytes_per_slot(config) == 3 * 128 * 2
    assert counted.state_bytes_per_lane_step(config) == 2 * 4 * (4096 + 768)
    assert counted.slot_ops(config, 20.0) == (
        2.0 * (counted.trunk_params(config) + 4 * 1.5 * 1024)
        + 4.0 * 4 * 8 * 20 + 4 * 5 * 64 * 16)
    trace = {"span_counters": {"engine_scan_iterations": 10,
                               "moe_experts_reached": 70},
             "loops": [["%while.4 (s32[])", 12.0]]}
    cost = counted.cost(config, trace, 30.0, 20.0)
    assert cost["steps"] == 10 and cost["steps_by_loop"] == 12.0
    assert cost["bytes"] == (
        10 * counted.trunk_params(config) * 2 + 70 * 1024 * 2
        + 30 * (20.0 * 64 + 2 * 4 * (4096 + 768)))
    assert cost["ops"] == counted.slot_ops(config, 20.0) * 30
    # Two projections an expert, not SwiGLU's three.
    experts = counted.experts_cost(config, trace, 30.0, 20.0)
    assert experts["bytes"] == 70 * 2 * 32 * 16 * 2
    assert experts["ops"] == 2.0 * 30 * 4 * 1.5 * 1024
    # The state's floor is the LIVE lanes' (30 lane-steps), what the
    # kernel reads (10 steps x 16 slots) is a note beside it.
    step = counted.ssm_step_cost(config, trace, 30.0, 20.0)
    assert step["bytes"] == 30 * 2 * 4 * 4096
    assert step["bytes_read"] == 10 * 16 * 2 * 4 * 4096
    assert step["ops"] == 30 * 4 * 5 * 64 * 16
    assert roofline.least_seconds(step, "TPU v5 lite")["bound"] == "memory"
    trace = {"span_counters": {"engine_scan_iterations": 10}}
    assert counted.cost(config, trace, 30.0, 20.0)[
        "experts_reached"] == pytest.approx(
            10 * 4 * counted.expected_reached(config, 3.0))
    assert counted.cost(config, {"span_counters": {}}, 30.0, 20.0) is None
    assert counted.ssm_step_cost(config, {}, 30.0, 20.0) is None


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


def test_the_new_metrics_on_a_hand_made_trace(config):
    """32 tokens in a span of 2 s; the kernel `ssm_step` took 0.3 + 0.1 s of
    it. The floor is the live lanes' state whatever the kernel read."""
    from benchmarks import readers, roofline

    ops = [["%fusion.3 bf16[16,32]", 1.0],
           ["%ssm_step.1 f32[4,16,8,8,16]", 0.3],
           ["%ssm_step.2 f32[4,16,8,8,16]", 0.1]]
    counters = {"engine_scan_iterations": 10, "moe_experts_reached": 70}
    per_tok = load("layer_metrics", "ssm_step_dev_us_per_tok.json")
    share = load("layer_metrics", "ssm_step_roofline.json")
    assert per_tok["reader"] == share["reader"] == "trace_op_time"
    ctx = _ctx(config, ops, counters)
    assert readers.read("trace_op_time", per_tok["args"], ctx) == (
        pytest.approx(1e6 * 0.4 / 32))
    got = readers.read("trace_op_time", share["args"], ctx)
    notes = ctx["notes"]["ssm_step_roofline"]
    assert notes["device_s"] == pytest.approx(0.4)
    least = notes["bytes"] / roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert got == pytest.approx(100.0 * least / 0.4)
    assert notes["bytes_read"] > notes["bytes"]
    # The parent, another family's cell, a CPU rehearsal, no trace:
    # nothing to read and nothing raised.
    for args in (per_tok["args"], share["args"]):
        assert readers.read("trace_op_time", args,
                            _ctx(config, ops[:1], counters)) is None
        assert readers.read("trace_op_time", args,
                            dict(ctx, trace=None)) is None
    afmoe = load("configs", "tiny-afmoe.json")
    assert readers.read("trace_op_time", share["args"],
                        _ctx(afmoe, ops, counters)) is None


def test_the_counter_metric_reads_a_share_or_nothing():
    from benchmarks import readers

    def snap(**counters):
        return {"metrics": {"counters": counters, "latency": {}}}

    again = load("layer_metrics", "prefix_recomputed_for_state_share.json")
    ctx = {"marked": snap(engine_prefix_tokens_recomputed_for_state=10,
                          engine_prompt_tokens_admitted=1000),
           "collected": snap(engine_prefix_tokens_recomputed_for_state=70,
                             engine_prompt_tokens_admitted=3000)}
    assert readers.read(again["reader"], again["args"], ctx) == 3.0
    # A family that splices keys and values alone never counts a token
    # recomputed (the metric lists the new cell alone); a window that
    # admitted no prompt has no share.
    other = {"marked": snap(engine_prompt_tokens_admitted=1000),
             "collected": snap(engine_prompt_tokens_admitted=3000)}
    assert readers.read(again["reader"], again["args"], other) == 0.0
    idle = {"marked": snap(), "collected": snap(engine_lane_steps=7)}
    assert readers.read(again["reader"], again["args"], idle) is None


def test_the_configuration_the_cell_and_the_metrics_are_found_by_name():
    bench = load(os.pardir, "BENCHMARK.json")
    conf = named(bench["configs"], "nemotron3-nano")
    assert conf["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
        "/blob/main/config.json")
    assert conf["file"] == "benchmarks/configs/nemotron3-nano.json"
    assert conf["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                               "n_routed_experts", "vocab_size"]
    work = named(bench["workloads"], CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "nemotron3-nano", "notes-herd", 1)
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW_METRICS}
    for name in NEW_METRICS:
        m = named(bench["per_layer"], name)
        # Later recurrent families (kimi-linear) list their cells too.
        assert m["moves"] == "out_tok_s" and CELL in m["workloads"]
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".json"))
    assert named(bench["per_layer"],
                 "prefix_recomputed_for_state_share")["layer"] in layers
    for name in SHARED_METRICS:
        assert CELL in named(bench["per_layer"], name)["workloads"]
    assert not any(w["name"].startswith("tiny") for w in bench["workloads"])
    cell = load("workloads", CELL + ".json")
    spec = load("traffic", cell["traffic"] + ".json")
    assert (cell["config"], cell["students"]) == ("nemotron3-nano", 32)
    assert [(c["context_tokens"], c["share"]) for c in spec["courses"]] == [
        (152, 60), (104, 30), (2304, 10)]
    doc = load("configs", "nemotron3-nano.json")
    published = doc["published"]
    assert [published[k] for k in conf["reduced"]] == [
        52, "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", 128,
        131072]
    assert [doc[k] for k in conf["reduced"]] == [9, "MEMEM*EME", 64, 65536]
    assert doc["registry_model"] == "nemotron3-nano-9l-64of128"
    assert doc["serving"]["slots"] == 16
    assert doc["serving"]["length_buckets"] == [256, 2560]
    assert doc["check"]["width"] == 2560 + 128
    assert set(doc["check"]["limits"]) == READINGS
    assert doc["hbm_bytes_worked_out"]["weights_bfloat16"] == pytest.approx(
        6.33e9, rel=2e-3)


def test_the_catalog_entrys_numbers_stand_in_the_file_under_their_keys():
    """Every number of the catalog row's `config` is in the file under the
    same key, changed only where `reduced` says so (the check the driver
    makes before any run)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides on this machine")
    with open(catalog, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    row = [r for r in rows
           if r.get("name") == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"][0]
    doc = load("configs", "nemotron3-nano.json")
    assert doc["source"] == row["source_url"]
    reduced = set(doc["reduced"])
    for key, value in row["config"].items():
        if key in reduced:
            assert doc["published"][key] == value, key
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            assert doc[key] == value, key
        elif isinstance(value, dict):
            assert doc[key] == value, key
