"""The `minicpm_sala` family (MiniCPM-SALA; rehearsal configuration
`tiny-sala`) through the seam of `families/`, as new files only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_family_minicpm_sala.py -q
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

CELL = "minicpm-sala.reader-herd"
NEW_METRICS = ("sparse_select_dev_us_per_tok", "sparse_decode_dev_us_per_tok",
               "sparse_decode_roofline", "lightning_step_dev_us_per_tok",
               "lightning_step_roofline", "sparse_keys_read_share")
READINGS = {"logits_distance", "logits_worst_position_distance",
            "kv_cache_distance", "pooled_keys_distance",
            "recurrent_state_distance", "first_lightning_state_distance",
            "selection_disagreement", "idle_rows_state_change"}


def load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as fh:
        return json.load(fh)


def named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


@pytest.fixture(scope="module")
def config():
    return load("configs", "tiny-sala.json")


def test_the_family_comes_through_the_seam_and_weights_are_lazy(config):
    import jax.numpy as jnp

    from benchmarks import families
    from distributed_lms_raft_llm_tpu.models import registry

    fam = families.of_config(config)
    assert fam.name == "minicpm_sala"
    assert "minicpm_sala" in families.names()
    w = fam.weights.of_config(2 ** 31 + 5, config, jnp.bfloat16)
    assert w.layers == 8 and w.dtype == jnp.bfloat16
    tree = fam.weights.program_tree(w)
    family, cfg = registry.resolve(config["registry_model"], jnp.bfloat16)
    fam.compare.check_sizes(config, cfg)
    import jax

    want = jax.eval_shape(lambda: family.init_params(jax.random.key(0), cfg))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), tree) == jax.tree.map(
        lambda x: (x.shape, x.dtype), want)
    # The same seed gives the same draws; another seed others.
    again = fam.weights.of_config(2 ** 31 + 5, config, jnp.bfloat16)
    assert (again.layer(3)["self_attn.q_proj.weight"]
            == w.layer(3)["self_attn.q_proj.weight"]).all()
    other = fam.weights.of_config(7, config, jnp.bfloat16)
    assert (other.embed() != w.embed()).any()
    # The muP placement: a stream of order 1 after scale_emb.
    emb = fam.weights.of_config(7, config, jnp.float32).embed()
    assert float(jnp.std(emb)) * config["scale_emb"] == pytest.approx(
        1.0, rel=0.05)
    assert fam.reference.CONTROLS[-2:] == ("dense_past_dense_len",
                                           "window_not_taken")


def test_the_served_precision_passes_and_the_controls_read_outside(config):
    """The comparison the cell's `correct` rests on, at the rehearsal's
    size: the bfloat16 program inside every limit of the file, and the
    controls that show at this size outside at least one (a bfloat16 state
    and int8 keys and values do not: 4 heads of 8 x 8;
    configs/minicpm-sala.json has the chip's readings)."""
    import jax.numpy as jnp

    from benchmarks import check, families, serve

    fam = families.of_config(config)
    seed = 3000000019
    engine = serve.build_engine(config, seed)
    got = check.compare(engine.family, engine.cfg, engine.params, config,
                        seed)
    assert set(got["limits"]) == READINGS == set(got["worst"])
    assert got["ok"], got["worst"]
    seqs = check.sequences_of(config, seed)
    want = check.reference_side(config, seed, seqs)
    limits = config["check"]["limits"]
    for name in ("int8_weights", "dense_past_dense_len", "window_not_taken"):
        ctl = check.reference_side(config, seed, seqs, name)
        read = [fam.compare.readings(c, w) for c, w in zip(ctl, want)]
        worst = {k: max(r[k] for r in read) for k in limits}
        assert any(worst[k] > limits[k] for k in limits), (name, worst)
    del jnp


def _ctx(config, device_ops, counters):
    from benchmarks.run import Outcome

    o = Outcome(0.0, 20)
    o.sent, o.token_times = 0.0, [(1.0, 16), (2.0, 16), (3.0, 16)]
    return {"outcomes": [o], "trace_span": (1.0, 3.0),
            "trace": {"window_s": 2.0, "busy_s": 1.8, "programs": {},
                      "loops": [], "device_ops": device_ops,
                      "span_counters": counters},
            "traffic_spec": {"template_tokens": 0}, "config": config,
            "device": {"kind": "TPU v5 lite"}}


def test_the_new_metrics_on_a_hand_made_trace(config):
    """32 tokens in a span of 2 s; `sparse_decode` took 0.3 s of it,
    `sparse_select` 0.1 s, `lightning_step` 0.2 s. The sparse layers' share
    holds BOTH kernels' time against the floor of both kernels' work; a
    trace without the kernels (the parent's, another family's, a CPU
    rehearsal) gives nothing and does not raise."""
    from benchmarks import readers, roofline
    from benchmarks.families.minicpm_sala import roofline as counted

    ops = [["%fusion.3 bf16[16,32]", 1.0],
           ["%sparse_decode.1 bf16[16,2,2,8]", 0.3],
           ["%sparse_select.4 f32[16,2,1,128]", 0.1],
           ["%sparse_append.2 bf16[2,16,2,48,8]", 0.05],
           ["%lightning_step.7 f32[6,16,4,8,8]", 0.2]]
    counters = {"engine_scan_iterations": 10, "engine_sparse_lane_steps": 40,
                "engine_sparse_keys_attended": 40 * 22.5 + 24 * 10,
                "engine_sparse_keys_in_context": 40 * 40 + 24 * 10}
    specs = {n: load("layer_metrics", n + ".json") for n in NEW_METRICS}
    ctx = _ctx(config, ops, counters)
    for name, busy in (("sparse_select_dev_us_per_tok", 0.1),
                       ("sparse_decode_dev_us_per_tok", 0.3),
                       ("lightning_step_dev_us_per_tok", 0.2)):
        assert specs[name]["reader"] == "trace_op_time"
        assert readers.read("trace_op_time", specs[name]["args"], ctx) == (
            pytest.approx(1e6 * (busy / 2.0) / 16.0))
    floor = counted.sparse_decode_cost(config, ctx["trace"], 32.0, 30.0)
    assert floor["pooled_keys_scored"] == pytest.approx(40 * 40 / 2)
    least = roofline.least_seconds(floor, "TPU v5 lite")["seconds"]
    share = specs["sparse_decode_roofline"]
    assert readers.read("trace_op_time", share["args"], ctx) == (
        pytest.approx(100.0 * least / 0.4))
    state = counted.lightning_step_cost(config, ctx["trace"], 32.0, 30.0)
    least = roofline.least_seconds(state, "TPU v5 lite")["seconds"]
    share = specs["lightning_step_roofline"]
    assert readers.read("trace_op_time", share["args"], ctx) == (
        pytest.approx(100.0 * least / 0.2))
    assert ctx["notes"]["lightning_step_roofline"]["bytes_read"] == (
        10 * 16 * 2 * 6 * 4 * 8 * 8 * 4)
    whole = load("layer_metrics", "engine_roofline.json")
    assert whole["reader"] == "roofline"
    readers.read("roofline", whole["args"], ctx)
    assert "minicpm_sala" in ctx["notes"]["roofline"]["counted_by"]
    bare = _ctx(config, ops[:1], counters)
    for name in NEW_METRICS[:5]:
        assert readers.read(specs[name]["reader"], specs[name]["args"],
                            bare) is None
    other = _ctx(load("configs", "tiny-kimilinear.json"), ops, counters)
    assert readers.read("trace_op_time",
                        specs["sparse_decode_roofline"]["args"],
                        other) is None
    # The counters' ratio, mark to collect.
    read = specs["sparse_keys_read_share"]
    assert read["reader"] == "counter_ratio"
    snap = {"marked": {"metrics": {"counters": {}}},
            "collected": {"metrics": {"counters": {
                "engine_sparse_keys_attended": 4064.0 * 3 + 500,
                "engine_sparse_keys_in_context": 32800.0 * 3 + 500}}}}
    assert readers.read("counter_ratio", read["args"], snap) == (
        pytest.approx(100 * 12692.0 / 98900.0))
    assert readers.read("counter_ratio", read["args"], {
        "marked": {"metrics": {}}, "collected": {"metrics": {}}}) is None


def test_the_configuration_the_cell_and_the_metrics_are_found_by_name():
    bench = load(os.pardir, "BENCHMARK.json")
    conf = named(bench["configs"], "minicpm-sala")
    assert conf["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    assert conf["file"] == "benchmarks/configs/minicpm-sala.json"
    assert conf["reduced"] == ["num_hidden_layers", "mixer_types"]
    work = named(bench["workloads"], CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "minicpm-sala", "reader-herd", 1)
    assert all(len(x["why"]) <= 200 for x in (conf, work))
    for name in NEW_METRICS:
        m = named(bench["per_layer"], name)
        assert m["moves"] == "out_tok_s" and m["workloads"] == [CELL]
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".json"))
    cell = load("workloads", CELL + ".json")
    spec = load("traffic", cell["traffic"] + ".json")
    assert (cell["config"], cell["students"]) == ("minicpm-sala", 96)
    assert cell["why"] == work["why"]
    assert spec["generator"] == "closed_loop"
    assert [(c["context_tokens"], c["share"]) for c in spec["courses"]] == [
        (32768, 75), (152, 25)]
    assert spec["question_tokens"] == {"median": 20, "sigma": 0.5, "lo": 8,
                                       "hi": 48}
    assert spec["client_deadline_s"] == 120.0
    doc = load("configs", "minicpm-sala.json")
    assert doc["published"]["num_hidden_layers"] == 32
    assert doc["num_hidden_layers"] == 8
    assert doc["mixer_types"] == doc["published"]["mixer_types"][17:25]
    assert (doc["layers_kept"]["first"], doc["layers_kept"]["last"]) == (
        17, 24)
    assert doc["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 2048, "topk": 64, "dense_len": 8192}
    assert doc["deployment"]["chips_sharing_a_layer"] == 1
    assert doc["registry_model"] == "minicpm-sala-8l"
    serving = doc["serving"]
    assert (serving["slots"], serving["length_buckets"]) == (
        48, [256, 33024])
    assert serving["sampling"]["max_new_tokens"] == 512
    assert serving["prefix_cache_blocks"] == 4096
    kimi = load("configs", "kimi-linear.json")["serving"]
    for key in ("chunk", "megastep", "megastep_max", "inflight",
                "prefill_chunk_tokens"):
        assert serving[key] == kimi[key], key
    assert doc["check"]["width"] == 33024 + 512
    assert set(doc["check"]["limits"]) == READINGS
    worked = doc["hbm_bytes_worked_out"]
    assert worked["weights_bfloat16"] == 5_641_090_560
    assert worked["serving_total"] > 4 * 1024 ** 3
    for key in ("source", "reduced", "published", "layers_kept",
                "deployment", "assumed", "precision"):
        assert doc[key], key


def test_the_catalog_entrys_numbers_stand_in_the_file_under_their_keys():
    """Every number of the catalog row's `config` is in the file under the
    same key, changed only where `reduced` says so (the check the driver
    makes before any run)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides on this machine")
    with open(catalog, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    row = [r for r in rows if r.get("name") == "MiniCPM-SALA"][0]
    doc = load("configs", "minicpm-sala.json")
    assert doc["source"] == row["source_url"]
    reduced = set(doc["reduced"])
    for key, value in row["config"].items():
        if key in reduced:
            assert doc["published"][key] == value, key
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            assert doc[key] == value, key
        else:
            assert doc[key] == value, key
