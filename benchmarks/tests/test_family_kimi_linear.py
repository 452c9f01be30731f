"""The `kimi_linear` family (Kimi-Linear-48B-A3B; rehearsal configuration
`tiny-kimilinear`) through the seam of `families/`, as new files only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_family_kimi_linear.py -q
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

CELL = "kimi-linear.notes-herd"
NEW_METRICS = ("kda_step_dev_us_per_tok", "kda_step_roofline")
SHARED_METRICS = ("moe_experts_reached_share", "moe_experts_dev_us_per_tok",
                  "moe_experts_roofline", "moe_held_picks_share",
                  "mla_decode_dev_us_per_tok", "mla_decode_roofline",
                  "prefix_recomputed_for_state_share")
READINGS = {"logits_distance", "logits_worst_position_distance",
            "recurrent_state_distance",
            "first_layer_recurrent_state_distance", "latent_cache_distance",
            "own_input_latent_cache_distance", "routing_disagreement",
            "idle_rows_state_change"}
# The controls that show at the rehearsal's size on the CPU (the docstring
# of the test that uses them names the one that does not).
SHOWN = ("fp8_activations", "conv_window_dropped", "state_dropped",
         "int8_latent", "int8_weights", "scalar_decay", "no_correction")


def load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as fh:
        return json.load(fh)


def named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


@pytest.fixture(scope="module")
def config():
    return load("configs", "tiny-kimilinear.json")


def test_the_family_comes_through_the_seam_and_weights_are_lazy(config):
    import jax.numpy as jnp

    from benchmarks import families
    from distributed_lms_raft_llm_tpu.models import registry

    fam = families.of_config(config)
    assert fam.name == "kimi_linear"
    assert fam.reference.CONTROLS == (
        "int8_weights", "bf16_state", "int8_latent", "fp8_activations",
        "conv_window_dropped", "state_dropped", "scalar_decay",
        "no_correction")
    fam.compare.check_sizes(config, registry.resolve(
        "kimilinear-tiny", jnp.bfloat16)[1])
    with pytest.raises(ValueError, match="registry preset"):
        fam.compare.check_sizes(dict(config, mla_use_nope=False),
                                registry.resolve("kimilinear-tiny",
                                                 jnp.bfloat16)[1])
    assert fam.reference.layer_kinds(config) == [
        True, True, True, False, True, True, True, False, True]
    w = fam.weights.of_config(2 ** 31 + 5, config, jnp.float32)
    a, b = w.layer(0), w.layer(0)
    assert all((a[k] == b[k]).all() for k in a)
    tree = fam.weights.program_tree(
        fam.weights.of_config(2 ** 31 + 5, config, jnp.bfloat16))
    kinds = ["kda" if "a_log" in lp["attn"] else "mla"
             for lp in tree["layers"]]
    assert kinds == ["kda" if k else "mla"
                     for k in fam.reference.layer_kinds(config)]
    assert ["mlp" in lp for lp in tree["layers"]] == [True] + [False] * 8
    mixer, moe = tree["layers"][0]["attn"], tree["layers"][1]["moe"]
    # The state's own parameters and the router's bias stay float32; A in
    # [1, 16] a head, dt in [0.001, 0.1] a head and channel.
    for leaf in ("a_log", "dt_bias"):
        assert mixer[leaf].dtype == jnp.float32
    assert mixer["a_log"].shape == (4,) and mixer["dt_bias"].shape == (32,)
    # ... and the bias is the calibrated one, the same for the reference.
    assert moe["br"].dtype == jnp.float32 and moe["br"].any()
    assert (moe["br"] == w.layer(1)[
        "block_sparse_moe.gate.e_score_correction_bias"]).all()
    assert mixer["w_qkv"].dtype == jnp.bfloat16
    assert mixer["w_qkv"].shape == (32, 96)
    assert mixer["conv_w"].shape == (4, 96)
    a_ = jnp.exp(mixer["a_log"])
    assert float(a_.min()) >= 1.0 and float(a_.max()) <= 16.0
    dt = jnp.log1p(jnp.exp(mixer["dt_bias"]))
    assert float(dt.min()) >= 0.999e-3 and float(dt.max()) <= 0.1001
    # The router's columns are one length; the held experts' stacks are
    # the draws, both widths padded with zeros (to whole lanes here).
    wr = w.layer(1)["block_sparse_moe.gate.weight"]
    norms = jnp.linalg.norm(wr, axis=0)
    assert float(norms.max() - norms.min()) < 1e-4 * float(norms.max())
    assert moe["wg"].shape == moe["wu"].shape == (8, 128, 128)
    assert moe["wd"].shape == (8, 128, 128)
    assert not moe["wu"][..., 16:].any() and not moe["wu"][:, 32:].any()
    assert not moe["wd"][:, 16:].any() and not moe["wd"][..., 32:].any()
    # kv_b_proj in the two halves the program holds apart.
    kvb = w.layer(3)["self_attn.kv_b_proj.weight"].reshape(16, 4, 16)
    mla = tree["layers"][3]["attn"]
    assert mla["wuk"].shape == mla["wuv"].shape == (16, 4, 8)
    assert (mla["wuv"] == kvb[..., 8:].astype(jnp.bfloat16)).all()
    head = w.head()
    assert float(abs(head[128:256]).max()) < 0.1 * float(abs(head[:128]).max())


def test_the_balancing_bias_makes_the_experts_picked_alike(config):
    """Fresh tokens through the reference: with the calibrated
    `e_score_correction_bias` every expert layer's 32 experts are picked
    more alike than with the bias at zero (the spread of an expert's share
    of the picks, summed over the layers), and the held quarter gets a
    quarter."""
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
        picks = np.asarray(fam.reference.forward(side, ids, check)[4])
        load = picks.sum(axis=1)                              # [Le, E]
        return (float((load.std(axis=1) / load.mean(axis=1)).sum()),
                float(picks[..., :8].sum() / picks.sum()))

    (balanced, held), (at_zero, _) = spread(w), spread(AtZero())
    assert balanced < 0.7 * at_zero
    assert 0.21 < held < 0.29


def test_the_served_precision_passes_and_the_controls_read_outside(config):
    """The rehearsal is `correct`, and every control that can show at this
    size on the CPU is outside at least one limit of the file: fp8
    activations, the two controls of the carry (the convolution's window,
    or the state, dropped at a chunk boundary), an int8 latent and int8
    weights (both through the latent against the float32 projection of a
    side's own input), and the two of this architecture's own mathematics,
    the decay made one scalar a head and the correction term left out. The
    bfloat16 state is NOT separable here: 4 heads of 8 x 8 hold no head slow
    enough for it to show (configs/kimi-linear.json and PERF.md section 2
    have the chip's readings at the published widths)."""
    from benchmarks import check, serve
    from benchmarks.families.kimi_linear import compare

    limits = config["check"]["limits"]
    assert set(limits) == READINGS
    engine = serve.build_engine(config, 5)
    fam = engine.family
    assert fam.name == "kimi_linear"
    assert fam.routed and fam.recurrent_state and fam.latent_cache
    got = check.compare(fam, engine.cfg, engine.params, config, 5)
    assert got["ok"], got["worst"]
    for seed in (1, 2):
        seqs = check.sequences_of(config, seed)[:1]
        want = check.reference_side(config, seed, seqs)
        for name in SHOWN:
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
    held = np.asarray(want[0][5]).copy()
    held[0, -3] = ~held[0, -3]
    flipped[5] = held
    rows = compare.readings(tuple(flipped), want[0])
    assert rows["logits_distance"] == 0.0      # what is compared agrees
    held[:] = ~held
    flipped[5] = held
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
    from benchmarks.families.kimi_linear import roofline as counted

    # tiny: 32 wide, 9 layers (7 KDA, 2 MLA); KDA 4 heads of 8 (inner 32),
    # kernel 4 over 3 x 32 channels; MLA 4 heads of 8 + 8, latent 16 + 8;
    # dense 64; 8 of 32 experts of 16 held, 4 a token, a shared expert of
    # 16; 384 tokens.
    kda = (4 * 32 * 32 + 3 * 32 * 4 + 2 * (32 * 8 + 8 * 32) + 4 + 32
           + 32 * 4 + 8)
    mla = 32 * 4 * 16 + 32 * 24 + 16 + 16 * 4 * 16 + 4 * 8 * 32
    rest = 32 * 32 + 32 + 3 * 32 * 16
    assert counted.kda_params(config) == kda
    assert counted.mla_params(config) == mla
    assert counted.routed_rest_params(config) == rest
    assert counted.expert_params(config) == 3 * 32 * 16
    assert counted.trunk_params(config) == (
        7 * kda + 2 * mla + 3 * 32 * 64 + 8 * rest + 18 * 32 + 32 + 384 * 32)
    assert counted.parameters(config) == (
        counted.trunk_params(config) + 384 * 32 + 8 * 8 * 1536)
    assert counted.held_picks_per_token(config) == 1.0
    assert counted.expected_reached(config, 1.0) == pytest.approx(
        8 * (1 - (31 / 32) ** 4))
    assert counted.latent_bytes_per_token(config) == 2 * 24 * 2
    assert counted.ssm_bytes_per_slot(config) == 32 * 8 * 4
    assert counted.conv_bytes_per_slot(config) == 3 * 96 * 2
    assert counted.state_bytes_per_lane_step(config) == 2 * 7 * (1024 + 576)
    trace = {"span_counters": {"engine_scan_iterations": 10,
                               "moe_experts_reached": 70},
             "loops": [["%while.4 (s32[])", 12.0]]}
    cost = counted.cost(config, trace, 30.0, 20.0)
    assert cost["steps"] == 10 and cost["steps_by_loop"] == 12.0
    assert cost["bytes"] == (
        10 * counted.trunk_params(config) * 2 + 70 * 1536 * 2
        + 30 * (20.0 * 96 + 2 * 7 * (1024 + 576)))
    assert cost["ops"] == counted.slot_ops(config, 20.0) * 30
    experts = counted.experts_cost(config, trace, 30.0, 20.0)
    assert experts["bytes"] == 70 * 1536 * 2
    assert experts["ops"] == 2.0 * 30 * 8 * 1.0 * 1536
    # The state's floor is the LIVE lanes' (30 lane-steps), what the
    # kernel reads (10 steps x 16 slots) is a note beside it.
    step = counted.kda_step_cost(config, trace, 30.0, 20.0)
    assert step["bytes"] == 30 * 2 * 7 * 1024
    assert step["bytes_read"] == 10 * 16 * 2 * 7 * 1024
    assert step["ops"] == 30 * 7 * 8 * 32 * 8
    assert roofline.least_seconds(step, "TPU v5 lite")["bound"] == "memory"
    attn = counted.mla_decode_cost(config, trace, 30.0, 20.0)
    assert attn["bytes"] == 30 * 20.0 * 96
    assert attn["width_read"] == 32 + 16
    trace = {"span_counters": {"engine_scan_iterations": 10}}
    assert counted.cost(config, trace, 30.0, 20.0)[
        "experts_reached"] == pytest.approx(
            10 * 8 * counted.expected_reached(config, 3.0))
    assert counted.cost(config, {"span_counters": {}}, 30.0, 20.0) is None
    assert counted.kda_step_cost(config, {}, 30.0, 20.0) is None
    assert counted.mla_decode_cost(config, {}, 30.0, 20.0) is None


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
    """32 tokens in a span of 2 s; the kernel `kda_step` took 0.3 + 0.1 s of
    it. The floor is the live lanes' state whatever the kernel read; a
    trace without the kernel (the parent's, another family's, a CPU
    rehearsal) gives nothing and does not raise."""
    from benchmarks import readers, roofline
    from benchmarks.families.kimi_linear import roofline as counted

    ops = [["%fusion.3 bf16[16,32]", 1.0],
           ["%kda_step.1 f32[7,16,4,8,8]", 0.3],
           ["%kda_step.2 f32[7,16,4,8,8]", 0.1],
           ["%mla_decode.7 bf16[16,4,24]", 0.2]]
    counters = {"engine_scan_iterations": 10, "moe_experts_reached": 70}
    per_tok = load("layer_metrics", "kda_step_dev_us_per_tok.json")
    share = load("layer_metrics", "kda_step_roofline.json")
    assert per_tok["reader"] == share["reader"] == "trace_op_time"
    ctx = _ctx(config, ops, counters)
    assert readers.read("trace_op_time", per_tok["args"], ctx) == (
        pytest.approx(1e6 * (0.4 / 2.0) / 16.0))
    floor = counted.kda_step_cost(config, ctx["trace"], 32.0, 1.0)
    least = roofline.least_seconds(floor, "TPU v5 lite")["seconds"]
    assert readers.read("trace_op_time", share["args"], ctx) == (
        pytest.approx(100.0 * least / 0.4))
    assert ctx["notes"]["kda_step_roofline"]["bytes_read"] == (
        10 * 16 * 2 * 7 * 1024)
    # The shared kernel's share is counted by this family's own function.
    latent = load("layer_metrics", "mla_decode_roofline.json")
    assert readers.read(latent["reader"], latent["args"], ctx) > 0
    assert "kimi_linear" in ctx["notes"]["mla_decode_roofline"]["counted_by"]
    bare = _ctx(config, ops[:1], counters)
    for metric in (per_tok, share):
        assert readers.read(metric["reader"], metric["args"], bare) is None
    other = _ctx(load("configs", "tiny-nemotronh.json"), ops, counters)
    assert readers.read(share["reader"], share["args"], other) is None


def test_the_configuration_the_cell_and_the_metrics_are_found_by_name():
    bench = load(os.pardir, "BENCHMARK.json")
    conf = named(bench["configs"], "kimi-linear")
    assert conf["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct"
        "/blob/main/config.json")
    assert conf["file"] == "benchmarks/configs/kimi-linear.json"
    assert conf["reduced"] == ["num_hidden_layers", "linear_attn_config",
                               "num_experts", "vocab_size"]
    work = named(bench["workloads"], CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "kimi-linear", "notes-herd", 1)
    for name in NEW_METRICS:
        m = named(bench["per_layer"], name)
        assert m["moves"] == "out_tok_s" and m["workloads"] == [CELL]
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".json"))
    for name in SHARED_METRICS:
        assert named(bench["per_layer"], name)["workloads"][-1] == CELL
    assert not any(w["name"].startswith("tiny") for w in bench["workloads"])
    cell = load("workloads", CELL + ".json")
    spec = load("traffic", cell["traffic"] + ".json")
    assert (cell["config"], cell["students"]) == ("kimi-linear", 32)
    assert cell["why"] == work["why"]
    assert [(c["context_tokens"], c["share"]) for c in spec["courses"]] == [
        (152, 60), (104, 30), (2304, 10)]
    doc = load("configs", "kimi-linear.json")
    published = doc["published"]
    lin = published["linear_attn_config"]
    assert [published[k] for k in ("num_hidden_layers", "num_experts",
                                   "vocab_size")] == [27, 256, 163840]
    assert len(lin["kda_layers"]) == 20 and lin["full_attn_layers"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert [doc[k] for k in ("num_hidden_layers", "num_experts",
                             "vocab_size")] == [9, 64, 40960]
    assert doc["linear_attn_config"] == dict(
        lin, kda_layers=[1, 2, 3, 5, 6, 7, 9], full_attn_layers=[4, 8])
    assert doc["experts_held"]["count"] == 64 and doc["experts_held"][
        "of"] == 256
    assert doc["deployment"]["chips_sharing_a_layer"] == 4
    assert doc["deployment"]["pipeline_stages"] == 3
    assert doc["registry_model"] == "kimi-linear-9l-64of256"
    assert doc["serving"]["slots"] == 16 and doc["serving"]["chunk"] in (8, 4)
    assert doc["serving"]["length_buckets"] == [256, 2560]
    assert doc["check"]["width"] == 2560 + 128
    assert set(doc["check"]["limits"]) == READINGS
    worked = doc["hbm_bytes_worked_out"]
    assert worked["weights_bfloat16"] == pytest.approx(8.545e9, rel=2e-3)
    assert worked["state_snapshot"] == 7 * (32 * 128 * 128 * 4
                                            + 3 * 12288 * 2)
    for key in ("source", "reduced", "published", "experts_held",
                "deployment", "assumed", "precision"):
        assert doc[key], key


def test_the_catalog_entrys_numbers_stand_in_the_file_under_their_keys():
    """Every number of the catalog row's `config` is in the file under the
    same key, changed only where `reduced` says so, and no width inside the
    one nested group that is listed (the check the driver makes before any
    run)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides on this machine")
    with open(catalog, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    row = [r for r in rows
           if r.get("name") == "Kimi-Linear-48B-A3B-Instruct"][0]
    doc = load("configs", "kimi-linear.json")
    assert doc["source"] == row["source_url"]
    reduced = set(doc["reduced"])
    for key, value in row["config"].items():
        if key in reduced:
            assert doc["published"][key] == value, key
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            assert doc[key] == value, key
        elif isinstance(value, dict):
            assert doc[key] == value, key
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert doc["linear_attn_config"][key] == row["config"][
            "linear_attn_config"][key]
