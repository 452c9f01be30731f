"""SK Telecom's axk1 family (models/axk1.py, models/mla.py; A.X-K1) at
`axk1-tiny`, on the CPU in float32.

The program's forward is held to the benchmark's plain reference
(`benchmarks/families/axk1/reference.py`, the published, expanded form,
which imports nothing of the program) on seeded weights: whole-sequence
logits, then the served prefill and the absorbed decode through the latent
cache. A chip's share of a layer's experts is tied to the model: the parts
all the shares give, with the shared expert counted once, add up to the
uncut layer; a share small enough for its grouped products to run over a
prefix of the sorted picks gives, to the bit, what the products over all
rows give, in the branch that takes the prefix and in the one that does
not. The cache and every prefix block hold 1,152 B a token and
layer at the published sizes; a block exported, spliced and attended gives
the logits of a fresh prefill; the paged engine serves the family through
staged admission and the prefix cache and counts the picks that land on
the share held and the passes that took the prefix; both engines refuse
`ep` and `tp` for it.
"""

import dataclasses
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.axk1 import reference, roofline, weights
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    SamplingParams,
    TutoringEngine,
)
from distributed_lms_raft_llm_tpu.engine import paged
from distributed_lms_raft_llm_tpu.models import afmoe, axk1, mla, moe, registry
from distributed_lms_raft_llm_tpu.ops import attention as attention_ops
from distributed_lms_raft_llm_tpu.utils import metrics_registry as metric

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 24
MAX_NEW = 6
NOTES = "consensus needs a quorum of nodes to agree on each log entry. "
PROMPTS = (NOTES + "why?", NOTES + "who leads?", "what is a term?")


def _load(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def config():
    config = _load("tiny-axk1.json")
    config["check"]["logit_positions"] = T
    return config


@pytest.fixture(scope="module")
def model():
    return registry.resolve("axk1-tiny", jnp.float32)


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


# ------------------------------------------------ against the plain reference


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_forward_matches_the_reference_logits(config, model, seed):
    family, cfg = model
    assert family.routed and family.latent_cache
    assert cfg.experts_held == (0, 8) and cfg.num_experts == 32
    w, params, ids = _drawn(config, seed)
    want = reference.forward(w, ids, config)
    got, _, aux = family.forward(params, cfg, jnp.asarray(ids)[None],
                                 aux=True)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-5)
    assert (_chosen(aux["routing"][:, 0], 32) == np.asarray(want[3])).all()
    assert aux["counts"].shape == (len(family.counters),) == (6,)


def _through_the_cache(family, cfg, params, ids, n_prompt, bucket, width):
    """Prefill a right-padded bucket, widen the latent cache to `width`,
    decode the rest a token at a time at a per-row offset: a whole-bucket
    call of `family.forward` and `_step_program`'s, every one of them
    through the absorbed products."""
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
    cache = cache._replace(k=jnp.pad(cache.k, pad),
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


def test_prefill_then_absorbed_decode_matches_the_expanded_reference(
        config, model):
    """13 tokens prefilled in a bucket of 16, 11 decoded through the
    latent cache at width 32: logits, and what the cache holds (c_kv
    after its norm, k_r after its rotation, one plane, no values), against
    the reference's full forward in the published form."""
    family, cfg = model
    w, params, ids = _drawn(config, 99)
    want_logits, want_c, want_r = reference.forward(w, ids, config)[:3]
    got_logits, cache = _through_the_cache(family, cfg, params, ids, 13, 16,
                                           32)
    assert cache.v is None and cache.k.shape == (5, 1, 1, 32, 16 + 8)
    held = np.asarray(cache.k)[:, 0, 0, :T]
    np.testing.assert_allclose(held[..., :16], want_c, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(held[..., 16:], want_r, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got_logits, want_logits, rtol=2e-4, atol=2e-5)
    # No term may hide in a tolerance: without the rotary term of the
    # scores the reference itself differs by far more.
    bare = reference.forward(w, ids, config, control="no_rope_term")[0]
    assert float(jnp.max(jnp.abs(bare - want_logits))) > 1e-2


def test_yarn_and_the_softmax_scale_are_the_published_ones(config):
    family, cfg = registry.resolve("ax-k1-1d4e-12of192", jnp.bfloat16)
    assert abs(mla.softmax_scale(cfg) - 0.13086) < 1e-5
    assert abs(reference.softmax_scale(_load("ax-k1.json")) - 0.13086) < 1e-5
    inv = mla.yarn_inv_freq(64, 10000.0, cfg.rope_scaling)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # Fast dimensions keep the base's frequency, slow ones a 32nd of it.
    np.testing.assert_allclose(inv[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 32, rtol=1e-6)
    assert (np.diff(inv) < 0).all()
    cos, sin = reference.yarn_tables(5, 64, 10000.0,
                                     _load("ax-k1.json")["rope_scaling"])
    np.testing.assert_allclose(np.cos(3 * inv), cos[3, :32], atol=1e-6)
    np.testing.assert_allclose(np.sin(3 * inv), sin[3, 32:], atol=1e-6)


@pytest.mark.parametrize("slots,width", [(3, 16), (2, 40)])
def test_the_decode_kernel_computes_the_absorbed_products(slots, width):
    """`latent_decode_attention` (interpreted here; the TPU's compiler has
    it in tests/test_chip_compile.py) against the same products by XLA."""
    h, c = 4, 24
    ks = jax.random.split(jax.random.key(width), 3)
    q = jax.random.normal(ks[0], (slots, h, c), jnp.float32)
    plane = jax.random.normal(ks[1], (2, slots, width, c), jnp.float32)
    lengths = jax.random.randint(ks[2], (slots,), 1, width)
    mask = (jnp.arange(width)[None] <= lengths[:, None])[:, None, None]
    got = attention_ops.latent_decode_attention(
        q, plane, 1, attention_ops.mask_to_bias(mask), 0.3, interpret=True)
    scores = jnp.einsum("bhc,bsc->bhs", q, plane[1]) * 0.3
    probs = jax.nn.softmax(jnp.where(mask[:, 0], scores, -1e30), axis=-1)
    want = jnp.einsum("bhs,bsc->bhc", probs, plane[1])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert attention_ops.latent_decode_fits(2688, 576, 2)
    assert not attention_ops.latent_decode_fits(131072, 576, 2)


def test_published_checkpoint_names_load_into_the_tree(model):
    """DeepSeek-V3's names, [out, in] linears, interleaved rotary pairs:
    loaded, the tree is `init_params`' and only the held experts are
    read."""
    family, cfg = model
    want = jax.eval_shape(lambda: family.init_params(jax.random.key(0), cfg))
    rng = np.random.default_rng(3)
    d, h, m = cfg.hidden_size, cfg.num_heads, cfg.moe_intermediate_size
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank

    def t(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    sd = {"model.embed_tokens.weight": t(cfg.vocab_size, d),
          "model.norm.weight": t(d), "lm_head.weight": t(cfg.vocab_size, d)}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        sd.update({
            p + "input_layernorm.weight": t(d),
            p + "post_attention_layernorm.weight": t(d),
            a + "q_a_proj.weight": t(qr, d),
            a + "q_a_layernorm.weight": t(qr),
            a + "q_b_proj.weight": t(h * (dn + dr), qr),
            a + "kv_a_proj_with_mqa.weight": t(kr + dr, d),
            a + "kv_a_layernorm.weight": t(kr),
            a + "kv_b_proj.weight": t(h * (dn + dv), kr),
            a + "o_proj.weight": t(d, h * dv)})
        mlps = ([(p + "mlp.", cfg.intermediate_size)]
                if i < cfg.num_dense_layers else
                [(p + "mlp.shared_experts.", m)]
                + [(f"{p}mlp.experts.{e}.", m) for e in range(8)])
        for prefix, width in mlps:
            sd.update({prefix + "gate_proj.weight": t(width, d),
                       prefix + "up_proj.weight": t(width, d),
                       prefix + "down_proj.weight": t(d, width)})
        if i >= cfg.num_dense_layers:
            sd[p + "mlp.gate.weight"] = t(cfg.num_experts, d)
    got = family.params_from_hf(sd, cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda x: x.shape, got) == jax.tree.map(
        lambda x: x.shape, want)
    # Rotary columns: interleaved pairs (0, 1), (2, 3).. become halves.
    kva = sd["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"].T
    np.testing.assert_array_equal(
        got["layers"][0]["attn"]["wkva"][:, kr:kr + dr // 2],
        kva[:, kr::2])
    logits, _ = family.forward(got, cfg, jnp.arange(6)[None])
    assert bool(jnp.isfinite(logits).all())


# --------------------------------------- a chip's share of a layer's experts


@pytest.mark.parametrize("shares", [16, 4, 1])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(config, shares):
    """The guide's share test: every chip routes over all 32 experts and
    computes its own experts' part plus the shared expert's; the routed
    parts of all the shares, with the shared expert counted once, are the
    uncut reference layer."""
    whole = dict(config, n_routed_experts=32)
    w = weights.of_config(11, whole, jnp.float32)
    lw = w.layer(2)
    x = jax.random.normal(jax.random.key(5), (T, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, picked = reference._expert_mlp(
            x, lw, eps=1e-6, k=4, norm=True, scale=2.5, first=0)
    _, cfg = registry.resolve("axk1-tiny", jnp.float32)
    tree = weights.program_layer(lw, w.sizes)
    h = afmoe.rms_norm(x, tree["ln2"]["scale"], 1e-6)[None]
    shared = afmoe.swiglu(h, tree["moe"]["shared"])
    count = 32 // shares
    total, held_picks = shared, 0
    for first in range(0, 32, count):
        part = dataclasses.replace(cfg, experts_held=(first, count))
        mp = dict(tree["moe"], **{k: tree["moe"][k][first:first + count]
                                  for k in ("wg", "wu", "wd")})
        y, top_i, sizes = afmoe.moe_mlp(h, mp, part, jnp.ones((1, T), bool))
        assert sizes.shape == (count,)
        held_picks += int(sizes.sum())
        total = total + (y - shared)
        assert (_chosen(top_i, 32)[0] == np.asarray(picked)).all()
    assert held_picks == T * 4          # every pick landed on one share
    np.testing.assert_allclose(x + total[0], want, rtol=2e-4, atol=2e-5)


def test_absent_picks_are_dropped_after_the_weights_are_normalised(model):
    """A held pick keeps the weight it has among all 4 of its token's
    picks: the share's part of a token is w_i E_i, not w_i E_i over the
    held picks' sum."""
    family, cfg = model
    mp = family.init_params(jax.random.key(2), cfg)["layers"][1]["moe"]
    h = jax.random.normal(jax.random.key(3), (1, T, 32), jnp.float32)
    top_i, top_w = afmoe.route_sigmoid(h[0], mp["wr"], None, 4, True, 2.5)
    np.testing.assert_allclose(top_w.sum(-1), 2.5, rtol=1e-5)
    y, _, sizes = afmoe.moe_mlp(h, mp, cfg, jnp.ones((1, T), bool))
    here = (top_i < 8)
    assert 0 < int(here.sum()) == int(sizes.sum()) < T * 4
    want = afmoe.swiglu(h, mp["shared"])[0]
    for tok, e, wgt in zip(*np.nonzero(here), top_w[here]):
        one = {k: mp[k][top_i[tok, e]] for k in ("wg", "wu", "wd")}
        want = want.at[tok].add(wgt * afmoe.swiglu(h[0, tok], one))
    np.testing.assert_allclose(y[0], want, rtol=2e-4, atol=2e-5)


def test_idle_lanes_reach_no_expert_and_land_no_pick(model):
    family, cfg = model
    params = family.init_params(jax.random.key(1), cfg)
    ids = jnp.arange(4)[:, None] + 7

    def counts(live):
        cache = family.init_cache(cfg, 4, 8, dtype=jnp.float32)
        cache = cache._replace(length=jnp.zeros((4,), jnp.int32))
        return family.forward(params, cfg, ids, cache=cache, live=live,
                              aux=True)[2]["counts"]

    le = cfg.num_layers - cfg.num_dense_layers
    full = dict(zip(family.counters, counts(jnp.ones((4,), bool))))
    assert full["moe_picks"] == 4 * 4 * le
    assert full["moe_expert_seats"] == 8 * le
    assert 0 < full["moe_experts_reached"] <= full["moe_picks_held"] < 64
    none = dict(zip(family.counters, counts(jnp.zeros((4,), bool))))
    assert none["moe_picks"] == none["moe_picks_held"] == 0
    assert none["moe_experts_reached"] == 0


# ------------------------ a small share's products over the held picks alone


def _small_share(skewed):
    """16 tokens x 4 picks over 32 experts of which 4 are held: 64 rows,
    of which `held_rows` keeps 48 (a fair router's 8 +- 2.6 held picks and
    twelve deviations, in whole tiles); token 3 is an idle lane. A fair
    router lands a handful of picks on the share; the skewed one sends
    every token's four picks there (the 15 live ones' 60: past the
    prefix)."""
    ks = jax.random.split(jax.random.key(43), 5)
    x = jax.random.normal(ks[0], (16, 32), jnp.float32)
    wr = jax.random.normal(ks[1], (32, 32), jnp.float32)
    if skewed:
        wr = wr.at[:, :4].set(100.0 * jnp.sign(x.sum(0))[:, None])
        x = x + 0.5 * jnp.sign(x.sum(0))[None]
    top_i, top_w = moe.route_sigmoid(x, wr, None, 4, True, 2.5)
    stacks = [0.2 * jax.random.normal(k, shape, jnp.float32) for k, shape
              in zip(ks[2:], ((4, 32, 16), (4, 32, 16), (4, 16, 32)))]
    return x, top_i, top_w, jnp.ones((16,), bool).at[3].set(False), stacks


@pytest.mark.parametrize("experts", ["swiglu", "relu2"])
@pytest.mark.parametrize("skewed", [False, True], ids=["fair", "skewed"])
def test_a_small_shares_products_over_a_prefix_equal_those_over_all_rows(
        skewed, experts):
    """The bound is on the rows, not a capacity: where the held picks fit
    the prefix the products run over it alone, where they do not over all
    rows, and either way the output and the group sizes are, to the bit,
    those of the call that was not told how many experts there are (so
    has no bound) and those of a per-pick loop to rounding."""
    x, top_i, top_w, live, (wg, wu, wd) = _small_share(skewed)
    if experts == "swiglu":
        call = partial(moe.grouped_swiglu, x, top_i, top_w, live, wg, wu, wd,
                       first=0)
        one = lambda t, e: (jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wu[e])) @ wd[e]
    else:
        call = partial(moe.grouped_relu2, x, top_i, top_w, live, wu, wd,
                       first=0)
        one = lambda t, e: jnp.square(jax.nn.relu(x[t] @ wu[e])) @ wd[e]
    fit = moe.held_rows(64, 4, 32)
    assert fit == 48
    y, sizes = call(among=32)
    y_all, sizes_all = call()
    assert ("cond" in str(jax.make_jaxpr(partial(call, among=32))())
            and "cond" not in str(jax.make_jaxpr(call)()))
    assert (int(sizes.sum()) > fit) == skewed     # which branch was taken
    np.testing.assert_array_equal(sizes, sizes_all)
    np.testing.assert_array_equal(y, y_all)
    held = np.asarray((top_i < 4) & live[:, None])
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(top_i)[held], minlength=4))
    want = np.zeros((16, 32), np.float32)
    for t, j in zip(*np.nonzero(held)):
        want[t] += float(top_w[t, j]) * np.asarray(one(t, int(top_i[t, j])))
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    assert not held[3].any() and not np.asarray(y[3]).any()  # the idle lane


@pytest.mark.parametrize("first,among,held,tokens,k", [
    (None, 32, 32, 8, 4), (0, 32, 16, 8, 4), (8, 32, 8, 8, 4),
    (0, None, 2, 8, 4), (0, 128, 64, 16, 6), (0, 128, 64, 32, 6),
    (0, 128, 64, 128, 6)],
    ids=["all_held", "a_half", "a_quarter", "router_unknown",
         "nemotron3_nano_decode", "nemotron3_nano_chunk",
         "nemotron3_nano_wide_pass"])
def test_a_share_whose_twelve_deviations_cover_every_row_has_no_cond(
        first, among, held, tokens, k):
    """The programs that must not change: a layer that holds all its
    experts, a share whose prefix is every row (a quarter of a tiny
    family's 32 rows), or a share of a half and more, which runs whole at
    any size (`nemotron3-nano`'s half of a decode row's 96, of a chunk's
    192 and of the pass of four rows' 768, of which twelve deviations
    would be 560), lowers to the text it has without `among`, with no
    conditional in it."""
    ks = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(ks[0], (tokens, 32), jnp.float32)
    top_i = jnp.tile(jnp.arange(k, dtype=jnp.int32)[None] * 8, (tokens, 1))
    top_w = jnp.full((tokens, k), 1.0 / k, jnp.float32)
    wg, wu, wd = (0.2 * jax.random.normal(k_, shape) for k_, shape in zip(
        ks[1:], ((held, 32, 16), (held, 32, 16), (held, 16, 32))))
    live = jnp.ones((tokens,), bool)
    if among is not None:
        assert moe.held_rows(tokens * k, held, among) == tokens * k

    def text(fn, *stacks, **kw):
        return jax.jit(partial(fn, first=first, **kw)).lower(
            x, top_i, top_w, live, *stacks).as_text()

    for fn, stacks in ((moe.grouped_swiglu, (wg, wu, wd)),
                       (moe.grouped_relu2, (wu, wd))):
        got = text(fn, *stacks, among=among)
        assert got == text(fn, *stacks)
        assert "stablehlo.case" not in got and "stablehlo.if" not in got
        assert "cond" not in str(jax.make_jaxpr(
            partial(fn, first=first, among=among))(
                x, top_i, top_w, live, *stacks))


# Rows a pass has -> rows its products run over, on the cells' own paths:
# a decode row, a prefill pass of one row's 32 positions, the pass of four.
@pytest.mark.parametrize("rows,held,among,fit", [
    (128, 64, 256, 96), (256, 64, 256, 160), (1024, 64, 256, 432),
    (256, 12, 192, 64), (256, 12, 192, 64), (1024, 12, 192, 160),
    (96, 64, 128, 96), (192, 64, 128, 192), (768, 64, 128, 768),
    (768, 63, 128, 560), (8, 12, 192, 8), (128, 128, 128, 128)],
    ids=["kimi_linear_decode", "kimi_linear_chunk", "kimi_linear_wide_pass",
         "ax_k1_decode", "ax_k1_chunk", "ax_k1_wide_pass",
         "nemotron3_nano_decode", "nemotron3_nano_chunk",
         "nemotron3_nano_wide_pass_a_half_runs_whole",
         "under_a_half_is_bounded", "never_above_the_rows",
         "all_held_gives_every_row"])
def test_the_prefix_is_a_fair_routers_mean_and_twelve_deviations_in_whole_tiles(
        rows, held, among, fit):
    assert moe.held_rows(rows, held, among) == fit
    p = held / among
    reach = rows * p + moe.HELD_ROWS_DEVIATIONS * (rows * p * (1 - p)) ** 0.5
    if fit == rows:
        assert 2 * held >= among or reach > rows - moe.HELD_ROWS_MULTIPLE
    else:
        assert 2 * held < among and fit % moe.HELD_ROWS_MULTIPLE == 0
        assert reach <= fit < reach + moe.HELD_ROWS_MULTIPLE


# Rows a pass's products run over, a fair router's mean rows an expert ->
# rows they are handed: the least odd multiple of 32 that holds them, so
# that the kernel's row tile, the largest power of two that divides the
# count, is 32 whatever the pass; a pass whose groups outgrow the tile is
# left as it is.
@pytest.mark.parametrize("rows,group,handed", [
    (128, 1, 160), (256, 2, 288), (1024, 8, 1056), (64, 1.33, 96),
    (192, 1.5, 224), (768, 6, 800), (432, 4, 480), (96, 0.75, 96),
    (160, 5.33, 160), (1, 0.1, 32), (32, 1, 32), (33, 1, 96), (12, 1.5, 32),
    (4096, 32, 4128), (18432, 144, 18432), (4104, 32.06, 4104)],
    ids=["trinity_mini_decode", "chunk_and_fallbacks", "wide_pass",
         "ax_k1_prefix", "nemotron3_nano_chunk", "nemotron3_nano_wide_pass",
         "kimi_linear_wide_prefix", "a_decode_row_of_96_stays",
         "a_prefix_of_160_stays", "one_row", "one_tile", "a_row_past_a_tile",
         "a_tiny_familys_pass", "a_group_of_a_tile",
         "a_reference_checks_long_pass_is_left",
         "a_group_past_a_tile_is_left"])
def test_the_products_are_handed_the_least_odd_multiple_of_32_that_holds_them(
        rows, group, handed):
    assert moe.tiled_rows(rows, group) == handed
    if group > moe.ROW_TILE:
        assert handed == rows
    else:
        assert handed >= rows and handed % 64 == moe.ROW_TILE == 32
        assert handed - rows < 64 and moe.tiled_rows(handed, group) == handed


# ------------------------------------------------ the cache, by its bytes


def test_the_cache_and_a_prefix_block_hold_1152_bytes_a_token_and_layer():
    family, cfg = registry.resolve("ax-k1-1d4e-12of192", jnp.bfloat16)
    slots = _load("ax-k1.json")["serving"]["slots"]
    state = jax.eval_shape(
        partial(paged._fresh_state, family, cfg, slots, 2688))
    planes = [x for x in (state.cache.k, state.cache.v, state.cache.ks,
                          state.cache.vs) if x is not None]
    held = sum(x.size * x.dtype.itemsize for x in planes)
    assert held == slots * 2688 * cfg.num_layers * 1152
    assert held == _load("ax-k1.json")["hbm_bytes_worked_out"][
        f"latent_cache_{slots}_slots_at_width_2688"]
    block = jax.eval_shape(
        partial(paged._export_block_program, block=16), state.cache, 0, 3)
    assert block.v is None and block.ks is None
    assert block.k.shape == (cfg.num_layers, 1, 1, 16, 576)
    assert block.k.size * block.k.dtype.itemsize == 16 * 5 * 1152
    # The expanded keys and values of the 64 heads would be 40,960 B.
    assert 2 * cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                                + cfg.v_head_dim) // 2 * 2 == 40960


def test_a_block_exported_spliced_and_attended_gives_a_fresh_prefills_logits(
        model):
    """Slot 0 prefills 20 tokens; its first block of 16 is exported,
    spliced into slot 1's pages, and slot 1 prefills the last 4 alone."""
    family, cfg = model
    params = family.init_params(jax.random.key(4), cfg)
    ids = jax.random.randint(jax.random.key(6), (1, 20), 0, cfg.vocab_size)
    state = paged._fresh_state(family, cfg, 2, 32)

    def chunk(state, toks, slot, at):
        return family.forward(
            params, cfg, toks,
            cache=state.cache._replace(length=jnp.asarray([at], jnp.int32)),
            rows=jnp.asarray([slot], jnp.int32))

    fresh, cache = chunk(state, ids, 0, 0)
    state = state._replace(cache=cache)
    block = paged._export_block_program(state.cache, 0, 0, block=16)
    assert block.v is None and block.k.shape == (5, 1, 1, 16, 24)
    state = paged._stage_block_program(state, block, 1, 0)
    np.testing.assert_array_equal(state.cache.k[:, 1, :, :16],
                                  state.cache.k[:, 0, :, :16])
    assert not np.asarray(state.cache.k[:, 1, :, 16:]).any()
    spliced, _ = chunk(state, ids[:, 16:], 1, 16)
    np.testing.assert_allclose(spliced[0], fresh[0, 16:], rtol=2e-5,
                               atol=2e-6)
    wider = paged._grow_state_program(state, 48)
    assert wider.cache.v is None and wider.cache.k.shape[3] == 48
    np.testing.assert_array_equal(wider.cache.k[:, :, :, :32], state.cache.k)


# ------------------------------------------------------- the paged engine


def _econf(**kw):
    kw.setdefault("model", "axk1-tiny")
    return EngineConfig(
        sampling=SamplingParams.greedy(max_new_tokens=MAX_NEW),
        length_buckets=(16, 48), batch_buckets=(1, 2, 4), dtype=jnp.float32,
        param_dtype=jnp.float32, **kw,
    )


def _serve(**kw):
    eng = PagedEngine(_econf(**kw), slots=4, chunk=2, megastep=2,
                      megastep_max=4, prefix_cache=True,
                      prefix_cache_blocks=64, prefix_block_tokens=4,
                      prefill_chunk_tokens=8)
    rounds = []
    for _ in range(2):
        rids = [eng.submit(p) for p in PROMPTS]
        out = eng.drain()
        rounds.append(([out[r] for r in rids], eng.pop_prefix_stats(),
                       eng.pop_loop_stats()[0]))
    return eng, rounds


@pytest.fixture(scope="module")
def served():
    """One engine with a prefix cache serves the prompts twice: the first
    round prefills the notes in the scan (staged), the second splices
    them from the radix tree."""
    return _serve()


@pytest.fixture(scope="module")
def served_one_of_32():
    """`served` by a chip that holds 1 of the 32 experts, a share small
    enough at these sizes for its products to run over a prefix of the
    rows (the decode's 4 lanes x 4 picks and the chunk's 8 tokens x 4: 16
    of 16 and 16 of 32 rows): a preset of this test's, no option of the
    program's."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setitem(registry.PRESETS, "axk1-tiny-1of32", (
            registry.AXK1_FAMILY,
            partial(axk1.AxK1Config.tiny, experts_held=(0, 1))))
        return _serve(model="axk1-tiny-1of32")


@pytest.fixture(scope="module")
def expected():
    return TutoringEngine(_econf()).answer_batch(list(PROMPTS))


@pytest.mark.parametrize("round_", [0, 1], ids=["staged_prefill",
                                                "prefix_splice"])
def test_paged_engine_serves_the_latent_cache(served, expected, round_):
    eng, rounds = served
    answers, (hit, prompt_tokens, _, _), _ = rounds[round_]
    assert answers == expected
    if round_ == 0:
        assert hit < prompt_tokens // 2
    else:  # the notes came out of the radix tree
        assert hit > prompt_tokens // 2
    width = eng.state.cache.k.shape[3]
    assert eng.state.cache.v is None
    assert eng.kv_bytes_total == eng.kv_bytes_per_chip == (
        4 * width * 5 * (16 + 8) * 4)


def test_engine_counts_the_picks_that_land_on_the_share_held(served):
    eng, rounds = served
    counts = rounds[0][2]
    assert set(counts) <= set(metric.ENGINE_LOOP_COUNTERS)
    for name in eng.family.counters:
        assert metric.is_declared(metric.ENGINE_LOOP_COUNTERS[name])
    cfg = eng.cfg
    k, le = cfg.num_experts_per_tok, cfg.num_layers - cfg.num_dense_layers
    # Every prefilled prompt token and every decode token a client got
    # routed, and nothing else did: no idle, parked or overrun lane.
    routed = counts["prefill_tokens"] + len(PROMPTS) * (MAX_NEW - 1)
    assert counts["moe_picks"] == routed * k * le
    assert 0 < counts["moe_picks_held"] < counts["moe_picks"]
    assert 0 < counts["moe_experts_reached"] <= counts["moe_picks_held"]
    assert counts["moe_expert_seats"] % (cfg.experts_held[1] * le) == 0
    assert "tokens_past_window" not in counts
    # A spliced token has no forward pass and lands no pick.
    assert rounds[1][2]["moe_picks"] < counts["moe_picks"]
    # A quarter of the experts at these sizes: twelve deviations cover
    # every row of a decode step's 16 and of a one-row pass's 32, whose
    # products run over them all; a pass of four rows (128 picks, a prefix
    # of 96) is bounded, and fits.
    wide = (counts.get("prefill_crowded_passes", 0)
            - counts.get("prefill_crowded_narrow_passes", 0))
    assert (counts["moe_passes_bounded"] == counts["moe_passes_compacted"]
            == wide * le)


def test_engine_counts_the_passes_whose_products_ran_over_the_prefix(
        served_one_of_32):
    """One expert of 32: the prefill chunk's passes (32 rows, a prefix of
    16: a fair router's 1 +- 1 held picks and twelve deviations) are
    bounded, the decode's (16 rows, all of them) are not, and under this
    fair router every bounded pass fits its prefix."""
    eng, rounds = served_one_of_32
    counts = rounds[0][2]
    assert metric.is_declared(metric.ENGINE_LOOP_COUNTERS["moe_passes_bounded"])
    le = eng.cfg.num_layers - eng.cfg.num_dense_layers
    assert eng.cfg.experts_held == (0, 1)
    assert (moe.held_rows(32, 1, 32), moe.held_rows(16, 1, 32)) == (16, 16)
    passes = counts["moe_expert_seats"] // le
    assert 0 < counts["prefill_passes"] < passes
    assert counts["moe_passes_bounded"] == counts["prefill_passes"] * le
    assert counts["moe_passes_compacted"] == counts["moe_passes_bounded"]
    assert 0 < counts["moe_picks_held"] < counts["moe_picks"] // 4


def test_scopes_are_in_the_megastep(served):
    eng, _ = served
    with eng.mesh:
        text = eng._megastep.lower(
            eng.params, eng.state, eng._step_keys(1)
        ).as_text(debug_info=True)
    for scope in ("decode", "prefill_chunk", "sample", "attn.mla",
                  "mla.absorb", "mla.scores", "mla.out", "mlp.dense",
                  "moe.route", "moe.experts", "moe.shared"):
        assert scope in text, scope


@pytest.mark.parametrize("engine", [PagedEngine, TutoringEngine])
@pytest.mark.parametrize("axis,why", [("ep", "requires an MoE family"),
                                      ("tp", "no heads axis")])
def test_engines_refuse_to_shard_the_family(engine, axis, why):
    with pytest.raises(ValueError, match=why):
        engine(_econf(**{axis: 2}))


def test_an_int8_latent_cache_is_refused(model):
    family, cfg = model
    with pytest.raises(ValueError, match="kv_quant"):
        family.init_cache(dataclasses.replace(cfg, quant_kv=True), 1, 8)


def test_a_decode_step_over_a_cache_the_kernel_cannot_hold_is_refused(model):
    """No third way to attend: a row past the kernel's VMEM is refused
    when the step is traced, on every backend, not handed to XLA's
    products on the TPU unseen. A prefill over the same cache is not a
    decode step and traces."""
    family, cfg = model
    cfg = dataclasses.replace(cfg, max_position_embeddings=32768)
    params = jax.eval_shape(lambda: family.init_params(jax.random.key(0),
                                                       cfg))
    cache = jax.eval_shape(lambda: family.init_cache(cfg, 2, 32768))
    assert not attention_ops.latent_decode_fits(
        cache.k.shape[3], cache.k.shape[4], cache.k.dtype.itemsize)

    def step(t):
        ids = jax.ShapeDtypeStruct((2, t), jnp.int32)
        return jax.eval_shape(
            lambda p, i, c: family.forward(p, cfg, i, cache=c),
            params, ids, cache)

    with pytest.raises(ValueError, match="does not fit the decode kernel"):
        step(1)
    assert step(4)[0].shape == (2, 4, cfg.vocab_size)


# ------------------------------------ the benchmark's entries, found by name


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def test_the_benchmark_names_the_configuration_the_cell_and_its_metrics():
    """By name, wherever later PRs put their own entries: the
    configuration and its cut, the cell and its traffic to the number, the
    three metrics only this cell has, and the routed layer's accepted
    metrics reading in this cell under the names Trinity's cell uses."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = "ax-k1.notes-crowd"
    conf = _named(bench["configs"], "ax-k1")
    assert conf["source"] == (
        "https://huggingface.co/skt/A.X-K1/blob/main/config.json")
    assert conf["file"] == "benchmarks/configs/ax-k1.json"
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    config = _load("ax-k1.json")
    published = config["published"]
    assert [published[k] for k in conf["reduced"]] == [61, 192, 163840]
    assert [config[k] for k in conf["reduced"]] == [5, 12, 20480]
    entry = _named(bench["workloads"], cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ax-k1", "notes-crowd", 1)
    with open(os.path.join(REPO, "benchmarks", "workloads",
                           cell + ".json")) as fh:
        assert json.load(fh)["students"] == 2 * config["serving"]["slots"]
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "notes-crowd.json")) as fh:
        traffic = json.load(fh)
    assert [(c["context_tokens"], c["share"])
            for c in traffic["courses"]] == [(152, 50), (104, 25),
                                             (2304, 25)]
    for name, layer in (("moe_held_picks_share", "routed experts"),
                        ("moe_compacted_share", "routed experts"),
                        ("mla_decode_dev_us_per_tok", "latent attention"),
                        ("mla_decode_roofline", "latent attention")):
        metric_ = _named(bench["per_layer"], name)
        # First of its cells: a later family that holds a share of its
        # experts appends its own.
        assert metric_["workloads"][0] == cell
        assert metric_["layer"].startswith(layer)
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".json"))
    for name in ("moe_experts_reached_share", "moe_experts_dev_us_per_tok",
                 "moe_experts_roofline"):
        assert cell in _named(bench["per_layer"], name)["workloads"]
    assert not [m["name"] for m in bench["per_layer"]
                if m["name"].startswith("moe_held_")
                and m["name"] != "moe_held_picks_share"]


# ------------------------------------------- the roofline's counts, by hand


def test_roofline_counts_by_hand():
    config = _load("ax-k1.json")
    worked = config["hbm_bytes_worked_out"]
    assert roofline.expert_params(config) == worked[
        "one_routed_expert_params"] == 3 * 7168 * 2048
    assert roofline.attention_params(config) + 2 * 7168 == worked[
        "attention_a_layer_params"] + 1536 + 512 + 2 * 7168
    # Everything but the held experts and the embedding's rows.
    assert roofline.trunk_params(config) == (
        worked["parameters"] - 4 * 12 * worked["one_routed_expert_params"]
        - 20480 * 7168)
    assert roofline.latent_bytes_per_token(config) == worked[
        "latent_cache_per_token"] == 5 * 1152
    assert abs(roofline.held_picks_per_token(config) - 0.5) < 1e-12
    assert abs(roofline.expected_reached(config, 64) - 11.17) < 0.01
    assert abs(roofline.expected_reached(config, 32) - 8.85) < 0.01
    trace = {"span_counters": {"engine_scan_iterations": 200,
                               "moe_experts_reached": 8800}, "loops": []}
    experts = roofline.experts_cost(config, trace, 9000.0, 800.0)
    assert experts["bytes"] == 8800 * 3 * 7168 * 2048 * 2
    assert experts["ops"] == 2.0 * 9000 * 4 * 0.5 * 3 * 7168 * 2048
    attn = roofline.mla_decode_cost(config, trace, 9000.0, 800.0)
    # The floor is the live tokens' latent; the padded rows the kernel
    # reads stand beside it and never in it.
    assert attn["bytes"] == 9000 * 800 * 5 * 1152
    assert attn["bytes_read"] == 200 * 32 * 2688 * 5 * 1152
    per_lane = 2 * 64 * (512 * 256 + 800 * (576 + 512))
    assert attn["ops"] == 9000.0 * 5 * per_lane
    whole = roofline.cost(config, trace, 9000.0, 800.0)
    assert whole["bytes"] == (200 * roofline.trunk_params(config) * 2
                              + experts["bytes"] + attn["bytes"])
    assert whole["steps"] == 200
    assert roofline.cost(config, {"span_counters": {}}, 1.0, 1.0) is None
    assert roofline.mla_decode_cost(config, {}, 1.0, 1.0) is None
