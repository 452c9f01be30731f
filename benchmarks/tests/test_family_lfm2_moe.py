"""The `lfm2_moe` family (LFM2-8B-A1B; rehearsal configuration `tiny-lfm2`)
through the seam of `families/`, as new files only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_family_lfm2_moe.py -q
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

CELL = "lfm2-8b-a1b.notes-hall"
NEW_METRICS = ("shortconv_step_dev_us_per_tok",
               "moe_rows_per_reached_expert", "conv_lane_steps_share")
SHARED_METRICS = ("moe_experts_reached_share", "moe_experts_dev_us_per_tok",
                  "moe_experts_roofline", "prefix_recomputed_for_state_share")
READINGS = {"logits_distance", "logits_worst_position_distance",
            "keys_and_values_distance",
            "first_layer_own_input_keys_distance",
            "first_layer_own_input_values_distance",
            "own_input_keys_and_values_distance",
            "own_input_experts_distance", "conv_window_distance",
            "first_layer_worst_position_distance", "routing_disagreement",
            "idle_rows_state_change"}
CONTROLS = ("int8_weights", "int8_kv", "fp8_activations", "fp8_window",
            "no_router_bias", "window_zero_at_hit")


def load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as fh:
        return json.load(fh)


def named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


@pytest.fixture(scope="module")
def config():
    return load("configs", "tiny-lfm2.json")


def test_the_family_comes_through_the_seam_and_weights_are_lazy(config):
    import jax.numpy as jnp

    from benchmarks import families
    from distributed_lms_raft_llm_tpu.models import registry

    fam = families.of_config(config)
    assert fam.name == "lfm2_moe" and "lfm2_moe" in families.names()
    assert fam.reference.CONTROLS == CONTROLS
    _, cfg = registry.resolve("lfm2-tiny", jnp.bfloat16)
    fam.compare.check_sizes(config, cfg)
    with pytest.raises(ValueError, match="registry preset"):
        fam.compare.check_sizes(dict(config, rope_theta=10000), cfg)
    w = fam.weights.of_config(2 ** 31 + 5, config, jnp.float32)
    a, b = w.layer(1), w.layer(1)
    assert all((a[k] == b[k]).all() for k in a)
    other = fam.weights.of_config(2 ** 31 + 6, config, jnp.float32).layer(1)
    assert not (a["feed_forward.gate.weight"]
                == other["feed_forward.gate.weight"]).all()
    tree = fam.weights.program_tree(
        fam.weights.of_config(2 ** 31 + 5, config, jnp.bfloat16))
    kinds = ["attn" if "attn" in lp else "conv" for lp in tree["layers"]]
    assert kinds == ["conv", "attn", "conv", "conv", "conv", "attn", "conv",
                     "conv", "conv", "attn", "conv", "conv", "conv"]
    assert ["mlp" in lp for lp in tree["layers"]] == [True] + [False] * 12
    assert "lm_head" not in tree                       # the head is tied
    assert tree["layers"][1]["moe"]["br"].dtype == jnp.float32
    assert tree["embed"].dtype == jnp.bfloat16
    # The quiet rows of the tied embedding (bytes that are no text alone).
    embed = w.embed()
    assert float(jnp.abs(embed[200]).max()) < 0.05 * float(
        jnp.abs(embed[100]).max())


def test_the_router_bias_is_not_zero_and_alike_for_every_seed(config):
    """Every routed layer's `expert_bias` is the same multiset, the
    quantile midpoints of N(0, 0.03), in an order of the seed's and the
    layer's own; the router's columns are all one length."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families.lfm2_moe import weights

    seen = []
    for seed in (3, 2 ** 31 + 7):
        w = weights.of_config(seed, config, jnp.float32)
        for layer in (1, 7):
            lw = w.layer(layer, experts=False)
            bias = np.asarray(lw[weights.BIAS])
            assert bias.dtype == np.float32 and np.abs(bias).min() > 0
            seen.append(bias)
            norms = np.linalg.norm(np.asarray(lw[weights.ROUTER]), axis=0)
            np.testing.assert_allclose(norms, norms[0], rtol=1e-5)
    for bias in seen[1:]:
        np.testing.assert_allclose(np.sort(bias), np.sort(seen[0]),
                                   rtol=1e-6)
        assert not (bias == seen[0]).all()
    assert abs(float(seen[0].sum())) < 1e-6
    assert 0.02 < float(seen[0].std()) < 0.04


@pytest.fixture(scope="module")
def sides(config):
    import jax.numpy as jnp

    from benchmarks import check, families
    from distributed_lms_raft_llm_tpu.models import registry

    fam = families.of_config(config)
    family, cfg = registry.resolve("lfm2-tiny", jnp.bfloat16)
    seed = 2147483747
    seqs = check.sequences_of(config, seed)
    params = fam.weights.program_tree(
        fam.weights.of_config(seed, config, jnp.bfloat16))
    want = check.reference_side(config, seed, seqs)
    return fam, family, cfg, params, seed, seqs, want


def test_the_served_precision_passes(config, sides):
    from benchmarks import check

    fam, family, cfg, params, seed, _, _ = sides
    got = check.compare(family, cfg, params, config, seed)
    assert set(got["worst"]) == READINGS == set(config["check"]["limits"])
    assert got["ok"], got["worst"]
    assert got["worst"]["idle_rows_state_change"] == 0.0


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_reads_outside_a_limit(config, sides, control):
    """Every control is outside at least one limit, at the rehearsal's
    size too: 8-bit keys and values by the values from the side's own
    input alone (`limits_from`)."""
    from benchmarks import check

    fam, _, _, _, seed, seqs, want = sides
    ctl = check.reference_side(config, seed, seqs, control)
    read = [fam.compare.readings(c, w) for c, w in zip(ctl, want)]
    assert not check.verdict(read, config["check"]["limits"])["ok"]


def test_the_familys_own_controls_fail_the_number_made_for_them(config,
                                                                sides):
    from benchmarks import check

    fam, _, _, _, seed, seqs, want = sides
    limits = config["check"]["limits"]
    for control, number in (
            ("no_router_bias", "routing_disagreement"),
            ("int8_kv", "first_layer_own_input_values_distance"),
            ("fp8_window", "conv_window_distance"),
            ("window_zero_at_hit", "first_layer_worst_position_distance")):
        ctl = check.reference_side(config, seed, seqs, control)
        worst = check.verdict(
            [fam.compare.readings(c, w) for c, w in zip(ctl, want)],
            limits)["worst"]
        assert worst[number] > limits[number], (control, worst)
    # A window lost at the hit leaves the windows after the last token as
    # they were: it is the first layer's worst position that sees it.
    assert worst["idle_rows_state_change"] == 0.0


@pytest.mark.parametrize("where", ["pad_positions", "idle_rows"])
def test_a_program_that_moves_a_window_where_nothing_is_live_fails(
        config, sides, where):
    """A family whose forward ignores `live` at pad positions, or on idle
    decode lanes, is outside a limit: the windows after the pad tail, or
    the idle rows' bit-equality."""
    import jax.numpy as jnp

    fam, family, cfg, params, _, seqs, want = sides

    def forward(params, cfg, ids, live=None, **kw):
        if where == "pad_positions" and ids.shape[1] > 1:
            live = jnp.ones(ids.shape, bool)
        if where == "idle_rows" and ids.shape[1] == 1:
            live = jnp.ones((ids.shape[0],), bool)
        return family.forward(params, cfg, ids, live=live, **kw)

    broken = family._replace(forward=forward)
    got = fam.compare.program(broken, cfg, params, seqs[0], config["check"])
    read = fam.compare.readings(got, want[0])
    limits = config["check"]["limits"]
    if where == "idle_rows":
        assert read["idle_rows_state_change"] > 0.0
    else:
        assert any(read[k] > limits[k] for k in limits), read


def test_bytes_and_operations_by_hand(config):
    from benchmarks.families.lfm2_moe import roofline as counted

    d, m, e, k = 32, 16, 8, 2
    assert counted.conv_params(config) == d * 3 * d + d * d + 3 * d
    assert counted.attention_params(config) == (
        d * 8 * (4 + 2 * 2) + 4 * 8 * d + 2 * 8)
    assert counted.expert_params(config) == 3 * d * m
    assert counted.router_params(config) == d * e + e
    assert counted.parameters(config) == load(
        "configs", "tiny-lfm2.json")["hbm_bytes_worked_out"]["parameters"]
    assert counted.kv_bytes_per_token(config) == 3 * 2 * 2 * 8 * 2
    assert counted.window_bytes_per_slot(config) == 2 * d * 2
    trace = {"span_counters": {"engine_scan_iterations": 10,
                               "moe_experts_reached": 700}}
    experts = counted.experts_cost(config, trace, 30.0, 20.0)
    assert experts["bytes"] == 700 * 3 * d * m * 2
    assert experts["ops"] == 2.0 * 30 * 12 * k * 3 * d * m
    whole = counted.cost(config, trace, 30.0, 20.0)
    assert whole["bytes"] == (
        10 * counted.trunk_params(config) * 2 + experts["bytes"]
        + 30 * (20 * 192 + 2 * 10 * 128))
    assert whole["experts_reached_per_layer_and_step"] == 700 / 120
    assert counted.cost(config, {"span_counters": {}}, 30.0, 20.0) is None


def _ctx(config, device_ops, counters, marked=None, collected=None):
    from benchmarks.run import Outcome

    o = Outcome(0.0, 100)
    o.sent, o.token_times = 0.0, [(1.0, 16), (2.0, 16), (3.0, 16)]
    return {"outcomes": [o], "trace_span": (1.0, 3.0),
            "trace": {"window_s": 2.0, "busy_s": 1.8, "programs": {},
                      "loops": [], "device_ops": device_ops,
                      "span_counters": counters},
            "traffic_spec": {"template_tokens": 0}, "config": config,
            "device": {"kind": "TPU v5 lite"},
            "marked": {"metrics": {"counters": marked or {}}},
            "collected": {"metrics": {"counters": collected or {}}}}


def test_the_new_metrics_on_a_hand_made_trace(config):
    """32 tokens in a span of 2 s; the kernel `shortconv_step` took 0.03 +
    0.01 s of it. A trace without the kernel (the parent's, another
    family's, a CPU rehearsal) gives nothing and does not raise; the two
    counter metrics read the window's growth, and nothing where the
    program has no such counter."""
    from benchmarks import readers

    ops = [["%fusion.3 bf16[16,32]", 1.0],
           ["%shortconv_step.1 bf16[10,16,2,32]", 0.03],
           ["%shortconv_step.2 bf16[10,16,2,32]", 0.01],
           ["%ragged-dot-none.7 bf16[160,16]", 0.2]]
    counters = {"engine_scan_iterations": 10, "moe_experts_reached": 700}
    per_tok = load("layer_metrics", "shortconv_step_dev_us_per_tok.json")
    assert per_tok["reader"] == "trace_op_time"
    ctx = _ctx(config, ops, counters)
    assert readers.read("trace_op_time", per_tok["args"], ctx) == (
        pytest.approx(1e6 * (0.04 / 2.0) / 16.0))
    # The routed layer's share is counted by this family's own function.
    routed = load("layer_metrics", "moe_experts_roofline.json")
    assert readers.read(routed["reader"], routed["args"], ctx) > 0
    assert "lfm2_moe" in ctx["notes"]["moe_experts_roofline"]["counted_by"]
    bare = _ctx(config, ops[:1], counters)
    assert readers.read(per_tok["reader"], per_tok["args"], bare) is None

    rows = load("layer_metrics", "moe_rows_per_reached_expert.json")
    lanes = load("layer_metrics", "conv_lane_steps_share.json")
    assert rows["reader"] == lanes["reader"] == "counter_ratio"
    grown = _ctx(config, ops, counters,
                 marked={"moe_picks": 100, "moe_experts_reached": 40,
                         "engine_conv_lane_steps": 50,
                         "engine_attn_lane_steps": 15},
                 collected={"moe_picks": 900, "moe_experts_reached": 140,
                            "engine_conv_lane_steps": 1050,
                            "engine_attn_lane_steps": 315})
    assert readers.read(rows["reader"], rows["args"], grown) == 8.0
    assert readers.read(lanes["reader"], lanes["args"], grown) == (
        pytest.approx(100.0 * 10 / 13))
    # The parent, and a family that counts no such thing: nothing to read.
    parent = _ctx(config, ops, counters, collected={"moe_picks": 900})
    assert readers.read(rows["reader"], rows["args"], parent) is None
    assert readers.read(lanes["reader"], lanes["args"], parent) is None


def test_the_configuration_the_cell_and_the_metrics_are_found_by_name():
    bench = load(os.pardir, "BENCHMARK.json")
    conf = named(bench["configs"], "lfm2-8b-a1b")
    assert conf["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert conf["file"] == "benchmarks/configs/lfm2-8b-a1b.json"
    assert conf["reduced"] == ["num_hidden_layers", "layer_types",
                               "num_dense_layers"]
    work = named(bench["workloads"], CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "lfm2-8b-a1b", "notes-hall", 1)
    assert len(work["why"]) <= 200 and len(conf["why"]) <= 200
    for name in NEW_METRICS:
        m = named(bench["per_layer"], name)
        assert m["moves"] == "out_tok_s" and m["workloads"] == [CELL]
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".json"))
    for name in SHARED_METRICS:
        # Found by its name, not by its place: later PRs append cells.
        assert CELL in named(bench["per_layer"], name)["workloads"]
    for name in ("moe_held_picks_share", "moe_compacted_share"):
        assert CELL not in named(bench["per_layer"], name)["workloads"]
    assert not any(w["name"].startswith("tiny") for w in bench["workloads"])
    cell = load("workloads", CELL + ".json")
    spec = load("traffic", cell["traffic"] + ".json")
    assert (cell["config"], cell["students"]) == ("lfm2-8b-a1b", 128)
    assert cell["why"] == work["why"]
    assert spec["generator"] == "closed_loop"
    assert [(c["context_tokens"], c["share"]) for c in spec["courses"]] == [
        (152, 50), (104, 25), (2304, 25)]
    assert spec["question_tokens"] == {"median": 20, "sigma": 0.5, "lo": 8,
                                       "hi": 48}
    assert spec["client_deadline_s"] == 120.0
    assert 4.0 <= spec["start_spread_s"] <= 6.0
    assert spec["start_spread_s"] == int(spec["start_spread_s"])
    doc = load("configs", "lfm2-8b-a1b.json")
    assert (doc["family"], doc["registry_model"]) == (
        "lfm2_moe", "lfm2-8b-a1b-13l")
    assert doc["reduced"] == conf["reduced"]
    assert doc["layers_kept"]["first"] == 1 and doc["layers_kept"][
        "last"] == 13
    assert doc["experts_held"]["count"] == doc["experts_held"]["of"] == 32
    assert doc["deployment"]["chips_sharing_a_layer"] == 1
    serving = doc["serving"]
    assert (serving["slots"], serving["length_buckets"],
            serving["sampling"]["max_new_tokens"],
            serving["prefix_cache_blocks"]) == (64, [256, 2560], 256, 512)
    kimi = load("configs", "kimi-linear.json")["serving"]
    for key in ("chunk", "megastep", "megastep_max", "inflight",
                "prefill_chunk_tokens"):
        assert serving[key] == kimi[key], key
    for key in ("temperature", "top_k", "top_p", "repetition_penalty",
                "approx_top_k"):
        assert serving["sampling"][key] == kimi["sampling"][key], key
    assert set(doc["check"]["limits"]) == READINGS
    held = doc["hbm_bytes_worked_out"]
    assert held["weights_bfloat16"] == 2 * held["parameters"]
    assert held["serving_total"] > 4 * 1024 ** 3
    tiny = load("workloads", "tiny-lfm2.deadline-herd.json")
    assert (tiny["config"], tiny["traffic"]) == ("tiny-lfm2", "tiny-herd")


def test_the_catalog_entrys_numbers_stand_in_the_file_under_their_keys():
    """Every number of the catalog row's `config` is in the file under the
    same key, changed only where `reduced` says so (the check the driver
    makes before any run)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides on this machine")
    with open(catalog, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    row = [r for r in rows if r.get("name") == "LFM2-8B-A1B"][0]
    doc = load("configs", "lfm2-8b-a1b.json")
    assert doc["source"] == row["source_url"]
    reduced = set(doc["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types",
                       "num_dense_layers"}
    for key, value in row["config"].items():
        if key in reduced:
            assert doc["published"][key] == value, key
            assert doc[key] != value, key
        else:
            assert doc[key] == value, key
    assert doc["layer_types"] == row["config"]["layer_types"][1:14]
    assert doc["num_hidden_layers"] == 13 and doc["num_dense_layers"] == 1
