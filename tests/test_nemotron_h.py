"""NVIDIA's nemotron_h family (models/nemotron_h.py, models/mamba2.py,
ops/ssm.py; Nemotron-3-Nano) at `nemotronh-tiny`, on the CPU in float32.

The program's forward is held to the benchmark's plain reference
(`benchmarks/families/nemotron_h/reference.py`: the Mamba-2 recurrence token
by token, no chunked form, no cache, which imports nothing of the program)
on seeded weights: whole-sequence logits, then the served prefill in the
chunk form and the decode in the step form through the slot's state. A
recurrent state has no dead region, so what must NOT move it is pinned
bit for bit: pad positions, lanes that are not live; and through the paged
engine an idle lane, a staged lane before its flip and a slot restaged
after its tenant overran give the token streams of a fresh engine. A
request admitted from a state snapshot gives the stream of the same request
with the prefix cache off; snapshots leave with their nodes. A chip's share
of a layer's experts adds up to the uncut layer, and the engines refuse
what is not built for a recurrent state.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.nemotron_h import reference, roofline, weights
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    SamplingParams,
    TutoringEngine,
)
from distributed_lms_raft_llm_tpu.engine import paged
from distributed_lms_raft_llm_tpu.engine.prefix_cache import (
    PrefixCache,
    StateSnapshot,
)
from distributed_lms_raft_llm_tpu.models import (
    mamba2,
    moe,
    nemotron_h,
    registry,
)
from distributed_lms_raft_llm_tpu.models.common import rms_norm
from distributed_lms_raft_llm_tpu.ops import ssm as ssm_ops
from distributed_lms_raft_llm_tpu.utils import metrics_registry as metric

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 24
MAX_NEW = 8
NOTES = "a quorum of nodes agrees on each entry. "
PROMPTS = (NOTES + "why?", NOTES + "who leads?", "what is a term?")


def _load(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def config():
    config = _load("tiny-nemotronh.json")
    config["check"]["logit_positions"] = T
    return config


@pytest.fixture(scope="module")
def model():
    return registry.resolve("nemotronh-tiny", jnp.float32)


def _drawn(config, seed):
    w = weights.of_config(seed, config, jnp.float32)
    ids = np.random.default_rng(seed).integers(
        0, config["vocab_size"], T).astype(np.int32)
    return w, weights.program_tree(w), ids


def _chosen(routing, experts):
    """int32 picks [Le, T, k] -> [Le, T, E] bool."""
    out = np.zeros(routing.shape[:2] + (experts,), bool)
    np.put_along_axis(out, np.asarray(routing), True, axis=2)
    return out


def _ragged(cache, lengths):
    return cache._replace(length=jnp.asarray(lengths, jnp.int32))


# ------------------------------------------------ against the plain reference


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_forward_matches_the_reference_logits(config, model, seed):
    family, cfg = model
    w, params, ids = _drawn(config, seed)
    want = reference.forward(w, ids, config)
    with jax.default_matmul_precision("highest"):
        logits, _, aux = family.forward(params, cfg, ids[None], aux=True)
    assert (_chosen(aux["routing"][:, 0], cfg.num_experts)
            == np.asarray(want[5])).all()
    np.testing.assert_allclose(logits[0], want[0], rtol=2e-4, atol=2e-4)


def test_prefill_then_decode_through_the_state_matches_the_recurrence(
        config, model):
    """The served shapes: a right-padded bucket through the chunk form
    (its pad tail not live), then one token at a time through the row's
    state in the step form; keys, values and the state after the last
    token are the reference's."""
    family, cfg = model
    w, params, ids = _drawn(config, 5)
    want = reference.forward(w, ids, config)
    n, bucket, width = 16, 20, 32
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :n] = ids[:n]
    real = (jnp.arange(bucket) < n)[None]
    with jax.default_matmul_precision("highest"):
        cache = family.init_cache(cfg, 1, width, dtype=jnp.float32)
        pre, cache = family.forward(params, cfg, jnp.asarray(prompt),
                                    cache=_ragged(cache, [0]),
                                    kv_mask=jnp.arange(width)[None] < n,
                                    live=real)
        cache = _ragged(cache, [n])
        rows = [pre[0, :n]]
        for t in range(n, T):
            logits, cache = family.forward(
                params, cfg, jnp.asarray(ids[t:t + 1])[None],
                cache=cache,
                kv_mask=jnp.arange(width)[None] <= cache.length[:, None],
                live=jnp.ones((1,), bool))
            cache = _ragged(cache, [t + 1])
            rows.append(logits[0])
    np.testing.assert_allclose(jnp.concatenate(rows), want[0], rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(cache.k[:, 0, :, :T], want[1], atol=2e-5)
    np.testing.assert_allclose(cache.v[:, 0, :, :T], want[2], atol=2e-5)
    np.testing.assert_allclose(cache.ssm[:, 0], want[3], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(cache.conv[:, 0], want[4], atol=2e-5)


def test_a_chunked_prefill_is_a_whole_one(model):
    """Chunks of 8 through `rows` onto row 2 of a four-row cache, the last
    one padded, against one forward over the sequence: same logits, same
    state, and the other rows untouched."""
    family, cfg = model
    params = family.init_params(jax.random.key(3), cfg)
    ids = jax.random.randint(jax.random.key(4), (1, 21), 0, cfg.vocab_size)
    whole_cache = family.init_cache(cfg, 1, 32, dtype=jnp.float32)
    want, whole_cache = family.forward(params, cfg, ids, cache=whole_cache)
    cache = family.init_cache(cfg, 4, 32, dtype=jnp.float32)
    cache = cache._replace(
        ssm=cache.ssm + 3.0, conv=cache.conv + 2.0)
    cache = cache._replace(ssm=cache.ssm.at[:, 2].set(0.0),
                           conv=cache.conv.at[:, 2].set(0.0))
    padded = jnp.pad(ids, [(0, 0), (0, 3)])
    got = []
    for cur in range(0, 24, 8):
        live = (cur + jnp.arange(8) < 21)[None]
        logits, cache = family.forward(
            params, cfg, padded[:, cur:cur + 8],
            cache=_ragged(cache, [cur]), rows=jnp.asarray([2]), live=live)
        got.append(logits[0])
    np.testing.assert_allclose(jnp.concatenate(got)[:21], want[0],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(cache.ssm[:, 2], whole_cache.ssm[:, 0],
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(cache.conv[:, 2], whole_cache.conv[:, 0],
                               atol=1e-6)
    for other in (0, 1, 3):
        assert (cache.ssm[:, other] == 3.0).all()
        assert (cache.conv[:, other] == 2.0).all()


def test_pad_positions_and_dead_lanes_leave_the_state_bit_equal(model):
    family, cfg = model
    params = family.init_params(jax.random.key(1), cfg)
    ids = jax.random.randint(jax.random.key(2), (3, 8), 0, cfg.vocab_size)
    cache = family.init_cache(cfg, 3, 16, dtype=jnp.float32)
    _, cache = family.forward(params, cfg, ids, cache=_ragged(cache, [0] * 3))
    before = cache
    # A decode step in which lane 1 is not live.
    live = jnp.asarray([True, False, True])
    _, after = family.forward(params, cfg, ids[:, :1],
                              cache=_ragged(cache, [8] * 3), live=live)
    for plane in ("ssm", "conv"):
        a, b = getattr(after, plane), getattr(before, plane)
        assert (a[:, 1] == b[:, 1]).all()
        assert not (a[:, 0] == b[:, 0]).all()
    # A chunk of which nothing is live moves nothing; one whose tail is
    # padding leaves what the chunk without the tail leaves.
    _, idle = family.forward(
        params, cfg, ids[:1], cache=_ragged(cache, [8]),
        rows=jnp.asarray([2]), live=jnp.zeros((1, 8), bool))
    _, padded = family.forward(
        params, cfg, ids[:1], cache=_ragged(cache, [8]),
        rows=jnp.asarray([2]), live=(jnp.arange(8) < 5)[None])
    _, short = family.forward(
        params, cfg, ids[:1].at[:, 5:].set(0), cache=_ragged(cache, [8]),
        rows=jnp.asarray([2]), live=(jnp.arange(8) < 5)[None])
    for plane in ("ssm", "conv"):
        assert (getattr(idle, plane) == getattr(before, plane)).all()
        assert (getattr(padded, plane) == getattr(short, plane)).all()
        assert not (getattr(padded, plane)[:, 2]
                    == getattr(before, plane)[:, 2]).all()


@pytest.mark.parametrize("layer", [0, 2])
def test_the_step_kernel_computes_the_state_update(layer):
    """`ssm_step` (interpreted) against the same update in `jax.numpy`:
    one layer of the stacked plane advanced in place, the others as they
    were, a dead lane (decay 1, input 0) bit-equal."""
    lm, s, h, p, n, g = 3, 3, 8, 8, 128, 2
    keys = jax.random.split(jax.random.key(layer), 5)
    plane = jax.random.normal(keys[0], (lm, s, h, p, n), jnp.float32)
    dtx = jax.random.normal(keys[1], (s, h, p), jnp.float32)
    decay = jax.random.uniform(keys[2], (s, h), jnp.float32, 0.2, 1.0)
    dtx, decay = dtx.at[1].set(0.0), decay.at[1].set(1.0)
    b = jax.random.normal(keys[3], (s, g, n), jnp.float32)
    c = jax.random.normal(keys[4], (s, g, n), jnp.float32)
    want_plane, want_y = ssm_ops.ssm_step_reference(plane, layer, dtx, decay,
                                                    b, c)
    got_plane, got_y = ssm_ops.ssm_step(plane, layer, dtx, decay, b, c,
                                        interpret=True)
    np.testing.assert_allclose(got_plane, want_plane, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    others = [i for i in range(lm) if i != layer]
    assert (got_plane[jnp.asarray(others)]
            == plane[jnp.asarray(others)]).all()
    assert (got_plane[layer, 1] == plane[layer, 1]).all()


def test_published_checkpoint_names_load_into_the_tree(model):
    family, cfg = model
    rng = np.random.default_rng(0)
    h, p, g, n, conv_dim = mamba2.sizes(cfg)
    d, dh = cfg.hidden_size, cfg.head_dim

    def mat(*shape):
        return (0.05 * rng.standard_normal(shape)).astype(np.float32)

    sd = {"backbone.embeddings.weight": mat(cfg.vocab_size, d),
          "backbone.norm_f.weight": np.ones(d, np.float32),
          "lm_head.weight": mat(cfg.vocab_size, d)}
    for i, kind in enumerate(cfg.pattern):
        m = f"backbone.layers.{i}.mixer"
        sd[f"backbone.layers.{i}.norm.weight"] = np.ones(d, np.float32)
        if kind == "M":
            sd.update({
                m + ".in_proj.weight": mat(h * p + conv_dim + h, d),
                m + ".conv1d.weight": mat(conv_dim, 1, cfg.conv_kernel),
                m + ".conv1d.bias": mat(conv_dim),
                m + ".dt_bias": mat(h), m + ".A_log": mat(h),
                m + ".D": np.ones(h, np.float32),
                m + ".norm.weight": np.ones(h * p, np.float32),
                m + ".out_proj.weight": mat(d, h * p)})
        elif kind == "*":
            sd.update({
                m + ".q_proj.weight": mat(cfg.num_heads * dh, d),
                m + ".k_proj.weight": mat(cfg.num_kv_heads * dh, d),
                m + ".v_proj.weight": mat(cfg.num_kv_heads * dh, d),
                m + ".o_proj.weight": mat(d, cfg.num_heads * dh)})
        else:
            sd[m + ".gate.weight"] = mat(cfg.num_experts, d)
            sd[m + ".gate.e_score_correction_bias"] = mat(cfg.num_experts)
            for e in range(cfg.num_experts):
                sd[f"{m}.experts.{e}.up_proj.weight"] = mat(
                    cfg.moe_intermediate_size, d)
                sd[f"{m}.experts.{e}.down_proj.weight"] = mat(
                    d, cfg.moe_intermediate_size)
            sd[m + ".shared_experts.up_proj.weight"] = mat(
                cfg.shared_intermediate_size, d)
            sd[m + ".shared_experts.down_proj.weight"] = mat(
                d, cfg.shared_intermediate_size)
    got = family.params_from_hf(sd, cfg)
    drawn = family.init_params(jax.random.key(0), cfg)
    assert jax.tree.structure(got) == jax.tree.structure(drawn)
    assert (jax.tree.map(lambda x: x.shape, got)
            == jax.tree.map(lambda x: x.shape, drawn))
    # The convolution [C, 1, K] is held [K, C]; of the 16 experts the
    # share held is read, both of an expert's widths padded with zeros
    # (`pad_experts`: to whole lanes at this size).
    np.testing.assert_array_equal(
        got["layers"][0]["mamba"]["conv_w"][1],
        sd["backbone.layers.0.mixer.conv1d.weight"][:, 0, 1])
    wu = got["layers"][1]["moe"]["wu"]
    np.testing.assert_array_equal(
        wu[3, :d, :cfg.moe_intermediate_size],
        sd["backbone.layers.1.mixer.experts.3.up_proj.weight"].T)
    assert wu.shape == (8, 128, 128) and got["layers"][1]["moe"][
        "wd"].shape == (8, 128, 128)
    assert not wu[:, d:].any() and not wu[..., cfg.moe_intermediate_size:].any()
    # At the published widths: whole tiles of 512.
    assert [nemotron_h._whole(n) for n in (2688, 1856, 512, 32)] == [
        3072, 2048, 512, 128]
    logits, _ = family.forward(got, cfg, jnp.arange(6)[None])
    assert bool(jnp.isfinite(logits).all())


# --------------------------------------- a chip's share of a layer's experts


@pytest.mark.parametrize("shares", [2, 4, 1])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(config, shares):
    """Every chip routes over all 16 experts and computes its own experts'
    part plus the shared expert's; the routed parts of all the shares
    (the benchmark's two halves among them), with the shared expert
    counted once, are the uncut reference layer."""
    whole = dict(config, n_routed_experts=16)
    w = weights.of_config(11, whole, jnp.float32)
    lw = w.layer(1)
    x = jax.random.normal(jax.random.key(5), (T, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, picked = reference._experts(
            x, lw, eps=1e-5, k=3, norm=True, scale=2.5, first=0)
    _, cfg = registry.resolve("nemotronh-tiny", jnp.float32)
    tree = weights.program_layer(lw)
    h = rms_norm(x, tree["ln"]["scale"], 1e-5)[None]
    shared = nemotron_h.relu2(h, tree["moe"]["shared"])
    count = 16 // shares
    total, held_picks = shared, 0
    for first in range(0, 16, count):
        part = dataclasses.replace(cfg, experts_held=(first, count))
        mp = dict(tree["moe"], **{k: tree["moe"][k][first:first + count]
                                  for k in ("wu", "wd")})
        y, top_i, sizes = nemotron_h.moe_mlp(h, mp, part,
                                             jnp.ones((1, T), bool))
        assert sizes.shape == (count,)
        held_picks += int(sizes.sum())
        total = total + (y - shared)
        assert (_chosen(top_i, 16)[0] == np.asarray(picked)).all()
    assert held_picks == T * 3          # every pick landed on one share
    np.testing.assert_allclose(x + total[0], want, rtol=2e-4, atol=2e-5)


def test_idle_lanes_reach_no_expert_and_land_no_pick(model):
    family, cfg = model
    params = family.init_params(jax.random.key(1), cfg)
    ids = jnp.arange(4)[:, None] + 7

    def counts(live):
        cache = family.init_cache(cfg, 4, 8, dtype=jnp.float32)
        return family.forward(params, cfg, ids, cache=_ragged(cache, [0] * 4),
                              live=live, aux=True)[2]["counts"]

    le = cfg.count("E")
    full = dict(zip(family.counters, counts(jnp.ones((4,), bool))))
    assert full["moe_picks"] == 4 * 3 * le
    assert full["moe_expert_seats"] == 8 * le
    idle = dict(zip(family.counters, counts(jnp.zeros((4,), bool))))
    assert idle["moe_picks"] == idle["moe_picks_held"] == 0
    assert idle["moe_experts_reached"] == 0


# ------------------------------------------------- through the paged engine


def _econf(**kw):
    kw.setdefault("sampling", SamplingParams.reference_defaults(
        max_new_tokens=MAX_NEW, temperature=0.0, top_k=0, top_p=1.0))
    return EngineConfig(model="nemotronh-tiny", dtype=jnp.float32,
                        length_buckets=(32, 56), seed=4, **kw)


def _engine(prefix_cache=True, slots=4, **kw):
    return PagedEngine(_econf(**kw), slots=slots, chunk=2, megastep=2,
                       megastep_max=4, prefix_cache=prefix_cache,
                       prefix_cache_blocks=64, prefix_block_tokens=4,
                       prefill_chunk_tokens=8)


@pytest.fixture(scope="module")
def alone():
    """Every prompt's greedy answer from an engine that serves it alone,
    without a prefix cache."""
    eng = _engine(prefix_cache=False)
    out = {}
    for prompt in PROMPTS + (NOTES + "how long is a term?", "a", "bb"):
        rid = eng.submit(prompt)
        out[prompt] = eng.drain()[rid]
    return out


@pytest.fixture(scope="module")
def served():
    """One engine with a prefix cache serves the prompts three times: the
    first round prefills from zeros, the second finds the notes' keys and
    values but no state that deep and snapshots at the branch point, the
    third starts from that snapshot."""
    eng = _engine()
    rounds = []
    for _ in range(3):
        rids = [eng.submit(p) for p in PROMPTS]
        out = eng.drain()
        rounds.append(([out[r] for r in rids], eng.pop_prefix_stats(),
                       eng.pop_loop_stats()[0]))
    return eng, rounds


def test_the_bucketed_engine_serves_the_family(alone):
    assert TutoringEngine(_econf()).answer_batch(list(PROMPTS)) == [
        alone[p] for p in PROMPTS]


@pytest.mark.parametrize("round_", [0, 1, 2], ids=[
    "from_zeros", "recomputed_for_state", "from_a_snapshot"])
def test_a_request_admitted_from_a_snapshot_gives_the_cold_stream(
        served, alone, round_):
    eng, rounds = served
    answers, (hit, prompt_tokens, _, _), counts = rounds[round_]
    assert answers == [alone[p] for p in PROMPTS]
    step = 8  # lcm(prefill chunk 8, block 4)
    if round_ == 0:
        assert hit == 0 and not counts["state_snapshots_restored"]
    if round_ == 2:
        # Both notes prompts start at the branch point: a chunk's end and
        # a block boundary at or below the notes' last whole block.
        assert counts["state_snapshots_restored"] >= 2
        assert hit >= 2 * (len(NOTES) // step * step)
        assert hit % step == 0
        assert counts["prefix_tokens_recomputed_for_state"] < 2 * step
    assert set(counts) <= set(metric.ENGINE_LOOP_COUNTERS)
    for name in ("state_snapshots_taken", "state_snapshots_restored",
                 "prefix_tokens_recomputed_for_state"):
        assert metric.is_declared(metric.ENGINE_LOOP_COUNTERS[name])
    assert eng.state_snapshot_bytes == eng.prefix_cache.snapshot_bytes > 0


@pytest.mark.parametrize("round_", [0, 1, 2])
def test_a_half_of_the_experts_runs_whole_and_counts_no_bounded_pass(
        served, round_):
    """A half of the experts and more runs its products over every row at
    any size (`moe.held_rows`: the tiny family's 12, 24 and 96 picks a
    pass, the cell's 96, 192 and 768), so the family has no bounded pass
    to count and publishes the four counts it had."""
    eng, rounds = served
    counts = rounds[round_][2]
    assert eng.family.counters == (
        "moe_picks", "moe_experts_reached", "moe_expert_seats",
        "moe_picks_held")
    for name in eng.family.counters:
        assert metric.is_declared(metric.ENGINE_LOOP_COUNTERS[name])
    assert [moe.held_rows(r, 8, 16) for r in (12, 24, 96)] == [12, 24, 96]
    assert [moe.held_rows(r, 64, 128) for r in (96, 192, 768)] == [
        96, 192, 768]
    # What the products are handed: a decode row's 96 rows as they are
    # (three tiles of 32), the passes' in tiles of 32 too.
    assert [moe.tiled_rows(r, r / 128) for r in (96, 192, 768)] == [
        96, 224, 800]
    assert "moe_passes_bounded" not in counts
    assert 0 < counts["moe_picks_held"] < counts["moe_picks"]


def test_a_hit_counts_only_what_was_restored(served):
    """The tree matches the notes' keys and values in round 1 already, but
    no snapshot stands that deep: the hit is the restored boundary (0),
    and the matched tokens are counted as recomputed for the state."""
    _, rounds = served
    (_, (hit, _, _, _), counts) = rounds[1]
    matched = counts["prefix_tokens_recomputed_for_state"] + hit
    assert matched >= 2 * (len(NOTES) // 4 * 4 - 4)
    assert hit < matched
    assert counts["state_snapshots_taken"] >= 1


def test_where_a_prefill_leaves_its_snapshot(served):
    """Steps of lcm(prefill chunk 8, block 4) = 8. A prompt that shares a
    context with earlier ones snapshots at its BRANCH POINT, the last step
    at or below what the tree matched past the state it restored. A
    context's first prompt, which matched nothing, snapshots on the stride
    of 8 steps (64 tokens here, 256 at the shipped sizes): the last
    multiple below its end, so a context that does not end on the stride
    (150 tokens) still hands its second prompt all but a stride's
    remainder (128), and one shorter than a stride nothing."""
    point = served[0]._snapshot_point
    assert [point(0, 7, 40), point(0, 21, 40), point(8, 30, 40),
            point(128, 150, 170)] == [0, 16, 24, 144]
    assert point(16, 21, 40) == 0 == point(16, 16, 40)
    assert [point(0, 0, 40), point(0, 0, 64), point(0, 0, 65),
            point(0, 0, 150 + 20), point(0, 0, 129)] == [0, 0, 64, 128, 128]


def test_idle_staged_and_restaged_lanes_give_a_fresh_engines_streams(alone):
    """Two slots, five requests, the later ones submitted while the first
    decode: lanes sit idle, staged lanes wait their turn in the scan while
    the live lane decodes, every slot is handed on to a next tenant after
    its previous one ran past its cap (the device decodes on until the
    host reaps), and every stream is the one a fresh engine gives."""
    eng = _engine(prefix_cache=False, slots=2)
    prompts = [PROMPTS[2], NOTES + "how long is a term?", "a", PROMPTS[0],
               "bb"]
    rids = [eng.submit(prompts[0])]
    out = {}
    for _ in range(2):          # lane 1 idles while lane 0 decodes
        out.update(eng.step())
    rids += [eng.submit(p) for p in prompts[1:]]
    out.update(eng.drain())
    counts = eng.pop_loop_stats()[0]
    assert counts["overrun_lane_steps"] > 0
    assert counts["staged_lane_steps"] > 0
    assert [out[r] for r in rids] == [alone[p] for p in prompts]


def test_the_state_planes_are_reset_at_staging(model):
    """`_stage_program` zeroes the slot's rows whatever they held and arms
    the snapshot position; `_restore_state_program` puts a snapshot there;
    `_grow_state_program` widens keys and values and passes the planes
    without a width through."""
    family, cfg = model
    state = paged._fresh_state(family, cfg, 3, 16)
    state = state._replace(cache=state.cache._replace(
        ssm=state.cache.ssm + 1.0, conv=state.cache.conv + 1.0))
    ids = np.zeros((1, 8), np.int32)
    key = jax.random.key_data(jax.random.key(0))
    staged = paged._stage_program(state, 1, ids, 5, 0, 0, key, 8)
    assert (staged.cache.ssm[:, 1] == 0).all()
    assert (staged.cache.conv[:, 1] == 0).all()
    assert (staged.cache.ssm[:, 0] == 1).all()
    assert staged.snap_at.tolist() == [0, 8, 0]
    snap = StateSnapshot(ssm=jnp.full_like(state.cache.ssm[:, :1], 7.0),
                         conv=jnp.full_like(state.cache.conv[:, :1], 5.0))
    restored = paged._restore_state_program(staged, snap, 1)
    assert (restored.cache.ssm[:, 1] == 7).all()
    assert (restored.cache.conv[:, 1] == 5).all()
    assert (restored.cache.ssm[:, 2] == 1).all()
    grown = paged._grow_state_program(restored, 24)
    assert grown.cache.k.shape[3] == 24
    assert (grown.cache.ssm == restored.cache.ssm).all()
    assert grown.snap_ssm.shape == restored.snap_ssm.shape


def test_snapshots_leave_with_their_nodes_and_their_bytes_return_to_zero():
    pc = PrefixCache(block_tokens=2, max_blocks=4, max_snapshots=2)

    def snap(x):
        return StateSnapshot(ssm=jnp.full((1, 1, 2, 2, 4), x, jnp.float32),
                             conv=jnp.full((1, 1, 3, 8), x, jnp.float32))

    one = snap(1.0).nbytes
    a, b = [1, 2, 3, 4, 5, 6], [1, 2, 9, 9]
    pc.insert(a, lambda i: i)
    assert pc.attach_snapshot(a, 4, snap(1.0))
    assert not pc.attach_snapshot(a, 4, snap(2.0))      # one a boundary
    assert not pc.attach_snapshot([7, 7, 7, 7], 2, snap(3.0))  # no path
    assert (pc.snapshots, pc.snapshot_bytes) == (1, one)
    # A split keeps the snapshot with the block before its boundary.
    pc.insert(b, lambda i: i)
    assert pc.attach_snapshot(b, 2, snap(4.0))
    match = pc.lookup(a + [0])
    tokens, got = pc.deepest_snapshot(match, 6)
    assert tokens == 4 and float(got.ssm[0, 0, 0, 0, 0]) == 1.0
    assert pc.deepest_snapshot(match, 3)[0] == 2
    assert pc.deepest_snapshot(pc.lookup([5, 5, 5]), 2) == (0, None)
    # A third drops the least recently used (the one at b's branch).
    pc.lookup(a + [0])
    pc.deepest_snapshot(pc.lookup(a + [0]), 6)
    assert pc.attach_snapshot(a, 6, snap(5.0))
    assert pc.snapshots == 2 and pc.snapshot_bytes == 2 * one
    assert not pc.has_snapshot(b, 2) and pc.has_snapshot(a, 4)
    # Eviction to the block budget takes leaves, and their snapshots.
    pc.max_blocks = 1
    pc.evict_to_budget()
    assert pc.blocks_used == 1
    assert pc.snapshots == 0 and pc.snapshot_bytes == 0
    pc.insert(a, lambda i: i)
    pc.attach_snapshot(a, 2, snap(6.0))
    pc.clear()
    assert pc.snapshots == 0 and pc.snapshot_bytes == 0


def test_warm_up_compiles_the_snapshot_programs(alone):
    from distributed_lms_raft_llm_tpu.utils.guards import (
        compile_count_guard, expected_from_inventory)

    eng = _engine()
    eng.warmup()
    expectation = expected_from_inventory(eng)
    assert expectation.expected["_restore_state"] == len(eng.widths)
    assert expectation.expected["_export_state"] == len(eng.widths)
    assert expectation.mismatches() == {}
    with compile_count_guard(expectation):
        for _ in range(3):
            rids = [eng.submit(p) for p in PROMPTS]
            out = eng.drain()
            assert [out[r] for r in rids] == [alone[p] for p in PROMPTS]
    assert eng.pop_loop_stats()[0]["state_snapshots_restored"] >= 2


def test_scopes_are_in_the_megastep(served):
    eng, _ = served
    with eng.mesh:
        text = eng._megastep.lower(
            eng.params, eng.state, eng._step_keys(1)
        ).as_text(debug_info=True)
    for scope in ("decode", "prefill_chunk", "sample", "attn.full",
                  "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out",
                  "moe.route", "moe.experts", "moe.shared"):
        assert scope in text, scope


@pytest.mark.parametrize("engine", [PagedEngine, TutoringEngine])
@pytest.mark.parametrize("setting,why", [
    ({"ep": 2}, "requires an MoE family"),
    ({"tp": 2}, "recurrent state"),
    ({"spec_tokens": 2}, "recurrent state")])
def test_engines_refuse_what_a_recurrent_state_does_not_allow(
        engine, setting, why):
    assert registry.NEMOTRON_H_FAMILY.recurrent_state
    assert not registry.AFMOE_FAMILY.recurrent_state
    with pytest.raises(ValueError, match=why):
        engine(_econf(**setting))


def test_an_int8_cache_is_refused(model):
    family, cfg = model
    with pytest.raises(ValueError, match="kv_quant"):
        family.init_cache(dataclasses.replace(cfg, quant_kv=True), 1, 8)


def test_other_families_programs_carry_no_state_plane():
    family, cfg = registry.resolve("afmoe-tiny", jnp.float32)
    state = paged._fresh_state(family, cfg, 2, 16)
    assert state.cache.ssm is None and state.snap_ssm is None
    assert len(jax.tree.leaves(state)) == 12


# ---------------------------------------------------- the benchmark's files


def test_the_benchmark_names_the_configuration_the_cell_and_its_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = "nemotron3-nano.notes-herd"
    conf = {c["name"]: c for c in bench["configs"]}["nemotron3-nano"]
    assert conf["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                               "n_routed_experts", "vocab_size"]
    work = {w["name"]: w for w in bench["workloads"]}[cell]
    assert (work["config"], work["traffic"], work["chips"]) == (
        "nemotron3-nano", "notes-herd", 1)
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in ("ssm_step_dev_us_per_tok", "ssm_step_roofline",
                 "prefix_recomputed_for_state_share"):
        # First of its cells: a later recurrent family appends its own to
        # the snapshots' share.
        assert metrics[name]["workloads"][0] == cell
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".json"))
    for name in ("moe_experts_reached_share", "moe_experts_dev_us_per_tok",
                 "moe_experts_roofline", "moe_held_picks_share"):
        assert cell in metrics[name]["workloads"]
    config = _load("nemotron3-nano.json")
    _, cfg = registry.resolve(config["registry_model"], jnp.bfloat16)
    assert cfg.pattern == config["hybrid_override_pattern"] == "MEMEM*EME"
    assert (cfg.vocab_size, cfg.experts_held) == (65536, (0, 64))
    worked = config["hbm_bytes_worked_out"]
    assert worked["parameters"] == roofline.parameters(config) == (
        3_166_244_352)
    assert all(len(x["why"]) <= 200 for x in (conf, work))


def test_roofline_counts_by_hand():
    config = _load("nemotron3-nano.json")
    assert roofline.mamba_params(config) + 2688 == 38_744_896
    assert roofline.attention_params(config) + 2688 == 23_399_040
    assert roofline.expert_params(config) == 2 * 2688 * 1856 == 9_977_856
    assert roofline.routed_rest_params(config) + 2688 + 64 * 9_977_856 == (
        658_885_376)
    assert roofline.ssm_bytes_per_slot(config) == 64 * 64 * 128 * 4
    assert roofline.conv_bytes_per_slot(config) == 3 * 6144 * 2
    assert roofline.kv_bytes_per_token(config) == 1024
    trace = {"span_counters": {"engine_scan_iterations": 100,
                               "moe_experts_reached": 12_000}}
    experts = roofline.experts_cost(config, trace, 1500.0, 400.0)
    assert experts["bytes"] == 12_000 * 9_977_856 * 2
    assert experts["ops"] == 2.0 * 1500 * 4 * 3.0 * 9_977_856
    step = roofline.ssm_step_cost(config, trace, 1500.0, 400.0)
    assert step["bytes"] == 1500 * 2 * 4 * 2_097_152
    assert step["bytes_read"] == 100 * 16 * 2 * 4 * 2_097_152
    whole = roofline.cost(config, trace, 1500.0, 400.0)
    assert whole["bytes"] == (
        100 * roofline.trunk_params(config) * 2 + experts["bytes"]
        + 1500 * (400 * 1024 + 2 * 4 * (2_097_152 + 36_864)))
    assert roofline.cost(config, {"span_counters": {}}, 1.0, 1.0) is None
