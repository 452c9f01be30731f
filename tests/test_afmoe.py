"""Arcee's afmoe family (models/afmoe.py; Trinity-Mini) at `afmoe-tiny`, on
the CPU in float32.

The program's forward is held to the benchmark's plain reference
(`benchmarks/families/afmoe/reference.py`, which imports nothing of the
program) on seeded weights: whole-sequence logits, then prefill and decode
through the cache on a sequence three times the window, sliding and full
layers apart. The routed layer is held to a per-token loop and to the
routing's published rules; idle lanes must reach no expert; and the paged
engine must serve prompts longer than the window, through the staged
prefill and through a prefix splice, as `engine/generate` answers them.
"""

import dataclasses
import json
import os
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.afmoe import reference, weights
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    SamplingParams,
    TutoringEngine,
)
from distributed_lms_raft_llm_tpu.engine import paged
from distributed_lms_raft_llm_tpu.models import afmoe, moe, registry
from distributed_lms_raft_llm_tpu.utils import metrics_registry as metric

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = 8
T = 3 * WINDOW


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "tiny-afmoe.json")) as fh:
        config = json.load(fh)
    config["check"]["logit_positions"] = T
    return config


@pytest.fixture(scope="module")
def model():
    return registry.resolve("afmoe-tiny", jnp.float32)


def _drawn(config, seed):
    w = weights.of_config(seed, config, jnp.float32)
    ids = np.random.default_rng(seed).integers(
        0, config["vocab_size"], T).astype(np.int32)
    return w, weights.program_tree(w), ids


# ------------------------------------------------ against the plain reference


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_forward_matches_the_reference_logits(config, model, seed):
    family, cfg = model
    assert cfg.sliding_window == WINDOW and family.routed
    w, params, ids = _drawn(config, seed)
    want = reference.forward(w, ids, config)
    got, _, aux = family.forward(params, cfg, jnp.asarray(ids)[None],
                                 aux=True)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-5)
    chosen = np.zeros(want[3].shape, bool)
    np.put_along_axis(chosen, np.asarray(aux["routing"][:, 0]), True, axis=2)
    assert (chosen == np.asarray(want[3])).all()


def _through_the_cache(family, cfg, params, ids, n_prompt, bucket, width):
    """Prefill a right-padded bucket, splice into a slot of `width`, decode
    the rest a token at a time at a per-row offset: a whole-bucket call of
    `family.forward` and `_step_program`'s."""
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :n_prompt] = ids[:n_prompt]
    real = (jnp.arange(bucket) < n_prompt)[None]
    positions = jnp.minimum(jnp.arange(bucket), n_prompt - 1)[None]
    pre, cache = family.forward(
        params, cfg, jnp.asarray(prompt),
        cache=family.init_cache(cfg, 1, bucket, dtype=jnp.float32),
        positions=positions, kv_mask=real, live=real)
    pad = [(0, 0)] * 5
    pad[3] = (0, width - bucket)
    cache = cache._replace(k=jnp.pad(cache.k, pad), v=jnp.pad(cache.v, pad),
                           length=jnp.full((1,), n_prompt, jnp.int32))
    rows = [pre[0, :n_prompt]]
    for tok in ids[n_prompt:]:
        offs = cache.length
        logits, cache = family.forward(
            params, cfg, jnp.asarray([[tok]], jnp.int32), cache=cache,
            kv_mask=jnp.arange(width)[None] <= offs[:, None])
        cache = cache._replace(length=offs + 1)
        rows.append(logits[0])
    return jnp.concatenate(rows), cache


@pytest.mark.parametrize("kind", [afmoe.SLIDING, afmoe.FULL])
def test_prefill_then_decode_matches_the_reference_across_the_window(
        config, model, kind):
    """A sequence three windows long: 13 tokens prefilled in a bucket of
    16, 11 decoded through the cache at width 32. The keys and values the
    cache holds for the layers of `kind`, and the logits, against the
    reference's full forward."""
    family, cfg = model
    w, params, ids = _drawn(config, 99)
    want_logits, want_k, want_v, _ = reference.forward(w, ids, config)
    got_logits, cache = _through_the_cache(family, cfg, params, ids, 13, 16,
                                           32)
    layers = [i for i, t in enumerate(cfg.types) if t == kind]
    assert layers and len(layers) < cfg.num_layers
    for got, want in ((cache.k, want_k), (cache.v, want_v)):
        np.testing.assert_allclose(
            np.asarray(got)[layers, 0, :, :T], np.asarray(want)[layers],
            rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got_logits, want_logits, rtol=2e-4, atol=2e-5)
    # The window is what makes them agree: without it the reference differs.
    unwindowed = reference.forward(w, ids, config, control="no_window")[0]
    assert float(jnp.max(jnp.abs(unwindowed - want_logits))) > 1e-2


def test_rotary_is_in_sliding_layers_only(model):
    """A full_attention layer carries no position signal: with every layer
    full the positions handed in change nothing; with the sliding layers
    they do."""
    family, cfg = model
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 384, (1, 12)))
    straight = jnp.arange(12)[None]
    scrambled = straight[:, ::-1] * 3

    def logits(c, positions):
        params = family.init_params(jax.random.key(1), c)
        return family.forward(params, c, ids, positions=positions)[0]

    full = dataclasses.replace(cfg, layer_types=(afmoe.FULL,) * 5)
    np.testing.assert_array_equal(logits(full, straight),
                                  logits(full, scrambled))
    assert float(jnp.max(jnp.abs(logits(cfg, straight)
                                 - logits(cfg, scrambled)))) > 1e-3


def test_published_checkpoint_names_load_into_the_tree(model):
    """`params_from_hf`: the published names, linears [out, in] and one
    entry per expert, give back the tree they were written from."""
    family, cfg = model
    params = family.init_params(jax.random.key(4), cfg)
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["lnf"]["scale"],
          "lm_head.weight": params["lm_head"]}
    norms = {"ln1": "input_layernorm", "ln1p": "post_attention_layernorm",
             "ln2": "pre_mlp_layernorm", "ln2p": "post_mlp_layernorm"}
    proj = {"wg": "gate_proj", "wu": "up_proj", "wd": "down_proj"}
    for i, lp in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        for ours, theirs in norms.items():
            sd[p + theirs + ".weight"] = lp[ours]["scale"]
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wg", "gate_proj"),
                             ("wo", "o_proj")):
            sd[p + f"self_attn.{theirs}.weight"] = lp["attn"][ours].T
        sd[p + "self_attn.q_norm.weight"] = lp["attn"]["qn"]["scale"]
        sd[p + "self_attn.k_norm.weight"] = lp["attn"]["kn"]["scale"]
        if "mlp" in lp:
            for ours, theirs in proj.items():
                sd[p + f"mlp.{theirs}.weight"] = lp["mlp"][ours].T
            continue
        moe_p = lp["moe"]
        sd[p + "mlp.router.gate.weight"] = moe_p["wr"].T
        sd[p + "mlp.expert_bias"] = moe_p["br"]
        for ours, theirs in proj.items():
            sd[p + f"mlp.shared_experts.{theirs}.weight"] = (
                moe_p["shared"][ours].T)
            for e in range(cfg.num_experts):
                sd[p + f"mlp.experts.{e}.{theirs}.weight"] = moe_p[ours][e].T
    loaded = family.params_from_hf(sd, cfg)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), loaded, params)))


# ---------------------------------------------------------- the routed layer


def _experts(seed, e=8, d=32, m=16):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (0.2 * jax.random.normal(ks[0], (e, d, m)),
            0.2 * jax.random.normal(ks[1], (e, d, m)),
            0.2 * jax.random.normal(ks[2], (e, m, d)))


def test_routed_layer_matches_a_per_token_loop():
    wg, wu, wd = _experts(0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(10, 32)), jnp.float32)
    top_i = jnp.asarray(
        [rng.choice(8, 2, replace=False) for _ in range(10)], jnp.int32)
    top_w = jnp.asarray(rng.uniform(0.1, 1.0, (10, 2)), jnp.float32)
    live = jnp.asarray([True] * 7 + [False] * 3)
    y, sizes = moe.grouped_swiglu(x, top_i, top_w, live, wg, wu, wd)
    want = np.zeros((10, 32), np.float32)
    for t in range(7):
        for j in range(2):
            e = int(top_i[t, j])
            h = jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wu[e])
            want[t] += float(top_w[t, j]) * np.asarray(h @ wd[e])
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    # No capacity, no drop: every live pick is in a group, no other is.
    assert int(sizes.sum()) == 14
    assert (np.asarray(sizes)
            == np.bincount(np.asarray(top_i[:7]).ravel(), minlength=8)).all()


def test_a_tokens_output_does_not_depend_on_what_shares_its_pass():
    wg, wu, wd = _experts(1)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    # Everyone picks the same two experts: under a capacity rule the
    # sixteenth token would find no seat.
    top_i = jnp.tile(jnp.asarray([[3, 5]], jnp.int32), (16, 1))
    top_w = jnp.full((16, 2), 0.5, jnp.float32)
    among, _ = moe.grouped_swiglu(x, top_i, top_w, jnp.ones((16,), bool),
                                  wg, wu, wd)
    alone, _ = moe.grouped_swiglu(x[15:], top_i[15:], top_w[15:],
                                  jnp.ones((1,), bool), wg, wu, wd)
    np.testing.assert_allclose(among[15], alone[0], rtol=1e-5, atol=1e-6)


# The cells' own passes by their counts: (tokens, picks, held, among) ->
# the rows the grouped products are handed, over the prefix and over all
# rows (one number where the prefix is every row). A decode row, a prefill
# pass of one row's 32 positions, the pass of four rows.
_PASSES = {
    "trinity_mini_decode": (16, 8, 128, 128, {160}),
    "trinity_mini_chunk": (32, 8, 128, 128, {288}),
    "trinity_mini_wide_pass": (128, 8, 128, 128, {1056}),
    "ax_k1_decode_and_chunk": (32, 8, 12, 192, {96, 288}),
    "ax_k1_wide_pass": (128, 8, 12, 192, {160, 1056}),
    "nemotron3_nano_decode": (16, 6, 64, 128, {96}),
    "nemotron3_nano_chunk": (32, 6, 64, 128, {224}),
    "nemotron3_nano_wide_pass": (128, 6, 64, 128, {800}),
    "kimi_linear_decode": (16, 8, 64, 256, {96, 160}),
    "kimi_linear_chunk": (32, 8, 64, 256, {160, 288}),
    "kimi_linear_wide_pass": (128, 8, 64, 256, {480, 1056}),
}


@pytest.mark.parametrize("experts", ["swiglu", "relu2"])
@pytest.mark.parametrize("skewed", [False, True], ids=["fair", "skewed"])
@pytest.mark.parametrize("shape", list(_PASSES))
def test_the_products_are_handed_row_tiles_of_32_and_give_the_same_numbers(
        monkeypatch, shape, skewed, experts):
    """The grouped products are handed an odd multiple of 32 rows, the
    sorted picks and rows of no expert behind them (`moe.tiled_rows`), and
    the layer's output, its group sizes and the family's counts are, to
    the bit, those of the products over the rows as they were; a pass whose
    router sends every pick to the share overflows the prefix, takes the
    fallback over all rows and loses no pick."""
    tokens, k, held, among, handed = _PASSES[shape]
    rows = tokens * k
    ks = jax.random.split(jax.random.key(51), 5)
    x = jax.random.normal(ks[0], (tokens, 32), jnp.float32)
    wr = jax.random.normal(ks[1], (32, among), jnp.float32)
    bias = jnp.where(jnp.arange(among) < held, 4.0 if skewed else 0.0, 0.0)
    top_i, top_w = moe.route_sigmoid(x, wr, bias, k, True, 2.5)
    live = jnp.ones((tokens,), bool).at[3].set(False)
    wg, wu, wd = (0.2 * jax.random.normal(k_, sh, jnp.float32)
                  for k_, sh in zip(ks[2:], ((held, 32, 16), (held, 32, 16),
                                             (held, 16, 32))))
    first = None if held == among else 0
    if experts == "swiglu":
        call = partial(moe.grouped_swiglu, x, top_i, top_w, live, wg, wu, wd,
                       first=first, among=among)
    else:
        call = partial(moe.grouped_relu2, x, top_i, top_w, live, wu, wd,
                       first=first, among=among)
    seen = set()
    real = jax.lax.ragged_dot

    def ragged_dot(lhs, rhs, sizes):
        seen.add(lhs.shape[0])
        return real(lhs, rhs, sizes)

    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)
    y, sizes = call()
    assert seen == handed and all(n % 64 == 32 for n in seen)
    fit = moe.held_rows(rows, held, among)
    assert handed == {moe.tiled_rows(fit, rows / among),
                      moe.tiled_rows(rows, rows / among)}
    monkeypatch.setattr(moe, "tiled_rows", lambda n, group: n)
    seen.clear()
    y_was, sizes_was = call()
    assert seen == {fit, rows}
    np.testing.assert_array_equal(sizes, sizes_was)
    np.testing.assert_array_equal(y, y_was)
    # No pick is dropped, whichever branch ran; the idle lane reaches none.
    here = np.asarray((top_i < held) & live[:, None])
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(top_i)[here], minlength=held))
    if skewed:
        assert int(sizes.sum()) == (tokens - 1) * k
        assert fit == rows or int(sizes.sum()) > fit      # the fallback
    else:
        assert 0 < int(sizes.sum()) <= fit
    assert np.asarray(y).any() and not np.asarray(y[3]).any()
    counted = afmoe.layer_counts(
        afmoe.SHARE_COUNTERS, types.SimpleNamespace(num_experts=among),
        live[None], top_i[None], sizes)
    assert list(np.asarray(counted)) == [
        (tokens - 1) * k, int((np.asarray(sizes) > 0).sum()), held,
        int(sizes.sum()), int(fit < rows),
        int(fit < rows and int(sizes.sum()) <= fit)]


@pytest.mark.parametrize("norm", [True, False])
def test_sigmoid_scores_bias_in_the_choice_only(norm):
    """x = e_0 and wr's first row are the logits. The bias lifts expert 5
    into the top-2 over expert 1; its WEIGHT is its own sigmoid score."""
    logits = np.array([2.0, 1.0, 0.5, -1.0, 0.0, -2.0, 0.2, 0.1], np.float32)
    wr = np.zeros((4, 8), np.float32)
    wr[0] = logits
    bias = np.zeros((8,), np.float32)
    bias[5] = 5.0
    x = jnp.asarray([[1.0, 0.0, 0.0, 0.0]])
    top_i, top_w = moe.route_sigmoid(x, jnp.asarray(wr), jnp.asarray(bias),
                                     2, norm, 2.826)
    assert sorted(np.asarray(top_i[0]).tolist()) == [0, 5]
    s = 1.0 / (1.0 + np.exp(-logits))
    order = np.asarray(top_i[0]).tolist()
    want = s[order] / (s[[0, 5]].sum() + 1e-20 if norm else 1.0) * 2.826
    np.testing.assert_allclose(top_w[0], want, rtol=1e-6)


def test_idle_lanes_reach_no_expert(model):
    """The three integers of a forward pass: picks computed, experts
    reached, expert seats offered; a lane that is not live adds nothing
    to the first two and changes no live lane's logits."""
    family, cfg = model
    params = family.init_params(jax.random.key(0), cfg)
    toks = jnp.asarray([[5], [6], [7], [8]], jnp.int32)
    cache = family.init_cache(cfg, 4, 16, dtype=jnp.float32)
    cache = cache._replace(length=jnp.asarray([3, 0, 2, 15], jnp.int32))

    def run(live):
        return family.forward(params, cfg, toks, cache=cache,
                              live=jnp.asarray(live), aux=True)

    seats = cfg.num_experts * cfg.num_expert_layers
    k, le = cfg.num_experts_per_tok, cfg.num_expert_layers
    all_logits, _, all_aux = run([True] * 4)
    assert all_aux["counts"].tolist()[::2] == [4 * k * le, seats]
    logits, _, aux = run([True, False, True, False])
    picks, reached, offered = aux["counts"].tolist()
    assert (picks, offered) == (2 * k * le, seats)
    assert 0 < reached <= picks
    np.testing.assert_allclose(np.asarray(logits)[[0, 2]],
                               np.asarray(all_logits)[[0, 2]],
                               rtol=1e-5, atol=1e-6)
    assert run([False] * 4)[2]["counts"].tolist() == [0, 0, seats]


# ------------------------------------------------------------ the paged engine

MAX_NEW = 8
NOTES = "the notes of lecture nine on consensus say "  # 43 byte tokens
PROMPTS = [NOTES + "why?", "what is raft?", NOTES + "a log"]


def _econf():
    return EngineConfig(
        model="afmoe-tiny",
        sampling=SamplingParams.greedy(max_new_tokens=MAX_NEW),
        length_buckets=(16, 48), batch_buckets=(1, 2, 4), dtype=jnp.float32,
        param_dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def served():
    """One engine with a prefix cache serves the prompts twice: the
    first round prefills the notes in the scan (staged), the second
    splices them from the radix tree."""
    eng = PagedEngine(_econf(), slots=4, chunk=2, megastep=2, megastep_max=4,
                      prefix_cache=True, prefix_cache_blocks=64,
                      prefix_block_tokens=4, prefill_chunk_tokens=8)
    rounds = []
    for _ in range(2):
        rids = [eng.submit(p) for p in PROMPTS]
        out = eng.drain()
        rounds.append(([out[r] for r in rids], eng.pop_prefix_stats(),
                       eng.pop_loop_stats()[0]))
    return eng, rounds


@pytest.fixture(scope="module")
def expected():
    return TutoringEngine(_econf()).answer_batch(list(PROMPTS))


@pytest.mark.parametrize("round_", [0, 1], ids=["staged_prefill",
                                                "prefix_splice"])
def test_paged_engine_serves_prompts_past_the_window(served, expected,
                                                     round_):
    eng, rounds = served
    answers, (hit, prompt_tokens, _, _), _ = rounds[round_]
    assert len(eng.tokenizer.encode(PROMPTS[0])) > 5 * WINDOW
    assert answers == expected
    if round_ == 0:
        assert hit < prompt_tokens // 2
    else:  # the notes came out of the radix tree
        assert hit >= 2 * (len(NOTES) // 4) * 4


def test_a_shared_prefix_is_spliced_in_runs_of_blocks(expected):
    """The notes are 21 blocks of 2 tokens here: one `_stage_block` call
    splices a run of up to STAGE_RUN_BLOCKS (a short run padded with its
    last block and told how many tokens count; a last run too near the
    cache's end starts earlier), a lone block goes alone;
    warm-up compiled the run's program at the widths that serve a bucket
    able to share two blocks, so a warmed session compiles nothing, and
    the answers are the unspliced ones."""
    from distributed_lms_raft_llm_tpu.engine.program_inventory import (
        STAGE_RUN_BLOCKS, bucket_has_runs, stage_runs)
    from distributed_lms_raft_llm_tpu.utils.guards import (
        compile_count_guard, expected_from_inventory)

    run = STAGE_RUN_BLOCKS
    assert stage_runs(2 * run + 3, 4 * run) == [(0, run), (run, run),
                                                (2 * run, 3)]
    assert stage_runs(run + 1, 4 * run) == [(0, run), (run, 1)]
    assert stage_runs(1, 4 * run) == [(0, 1)]
    # The third run would be written past a cache of 2.5 runs: it starts
    # earlier. A cache narrower than a run takes single blocks.
    assert stage_runs(2 * run + 2, 2 * run + 8) == [
        (0, run), (run, run), (run + 8, run - 6)]
    assert stage_runs(3, run - 1) == [(0, 1), (1, 1), (2, 1)]
    assert bucket_has_runs(48, 2, 64) and not bucket_has_runs(48, 2, 24)
    assert not bucket_has_runs(4, 2, 64)
    eng = PagedEngine(_econf(), slots=4, chunk=2, megastep=2, megastep_max=4,
                      prefix_cache=True, prefix_cache_blocks=128,
                      prefix_block_tokens=2, prefill_chunk_tokens=8)
    eng.warmup()
    expectation = expected_from_inventory(eng)
    runs_at = sum(any(bucket_has_runs(t, 2, w)
                      and eng._required_width(t) <= w for t in eng.buckets)
                  for w in eng.widths)
    assert runs_at >= 1
    assert expectation.expected["_stage_block"] == len(eng.widths) + runs_at
    assert expectation.mismatches() == {}
    with compile_count_guard(expectation) as guard:
        for round_ in range(2):
            rids = [eng.submit(p) for p in PROMPTS]
            out = eng.drain()
            assert [out[r] for r in rids] == expected, round_
            hit = eng.pop_prefix_stats()[0]
            splices = sum(name == "stage_block"
                          for name, _, _ in eng._progs.pop())
    assert guard.new_compiles() == 0
    blocks = len(NOTES) // 2
    assert hit >= 2 * 2 * blocks
    # Two prompts spliced 21 blocks each in 2 calls, not in 21.
    fit = eng.state.cache.k.shape[3] // 2
    assert len(stage_runs(blocks, fit)) == 2
    assert 2 * 2 <= splices < 2 * blocks


def test_engine_counts_routing_and_tokens_past_the_window(served):
    eng, rounds = served
    counts = rounds[0][2]
    assert set(counts) <= set(metric.ENGINE_LOOP_COUNTERS)
    for name in ("moe_picks", "moe_experts_reached", "moe_expert_seats",
                 "tokens_past_window"):
        assert metric.is_declared(metric.ENGINE_LOOP_COUNTERS[name])
    cfg = eng.cfg
    k, le = cfg.num_experts_per_tok, cfg.num_expert_layers
    # Every prefilled prompt token and every decode token a client got
    # routed, and nothing else did: no idle, parked or overrun lane.
    routed = counts["prefill_tokens"] + len(PROMPTS) * (MAX_NEW - 1)
    assert counts["moe_picks"] == routed * k * le
    assert 0 < counts["moe_experts_reached"] <= counts["moe_picks"]
    assert counts["moe_expert_seats"] % (cfg.num_experts * le) == 0
    # All three prompts are longer than the window of 8: every token is.
    assert counts["tokens_past_window"] == len(PROMPTS) * MAX_NEW
    assert counts["overrun_lane_steps"] > 0


def test_spliced_prefix_tokens_reach_no_expert(served):
    """The second round's notes come out of the radix tree: the suffix
    prefilled in the scan and the decode tokens route, and a spliced
    token, which has no forward pass, reaches no expert."""
    eng, rounds = served
    _, (hit, prompt_tokens, _, _), counts = rounds[1]
    assert hit > prompt_tokens // 2
    assert counts["prompt_tokens"] == prompt_tokens
    assert counts["prefill_tokens"] == prompt_tokens - hit
    k, le = eng.cfg.num_experts_per_tok, eng.cfg.num_expert_layers
    routed = counts["prefill_tokens"] + len(PROMPTS) * (MAX_NEW - 1)
    assert counts["moe_picks"] == routed * k * le
    assert counts["moe_picks"] < rounds[0][2]["moe_picks"]


def test_scopes_are_in_the_megastep(served):
    eng, _ = served
    with eng.mesh:
        text = eng._megastep.lower(
            eng.params, eng.state, eng._step_keys(1)
        ).as_text(debug_info=True)
    for scope in ("decode", "prefill_chunk", "sample", "attn.window",
                  "attn.full", "mlp.dense", "moe.route", "moe.experts",
                  "moe.shared"):
        assert scope in text, scope


def test_gpt2_programs_carry_no_routing_output():
    """`ModelFamily.routed` is false for every other family: its step
    returns what it returned, and `_forward` calls it as before."""
    family, cfg = registry.resolve("tiny", jnp.float32)
    assert not family.routed
    state = jax.eval_shape(lambda: paged._fresh_state(family, cfg, 2, 24))
    params = jax.eval_shape(
        lambda: family.init_params(jax.random.key(0), cfg))
    out = jax.eval_shape(
        lambda p, s, r: paged._step_program(
            p, s, r, cfg=cfg, sampling=SamplingParams.greedy(),
            eos_id=0, pad_id=0, model=family, chunk=2),
        params, state, jax.random.key(0))
    assert len(out) == 3
