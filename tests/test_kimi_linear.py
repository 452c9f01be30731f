"""Moonshot's kimi_linear family (models/kimi_linear.py, models/kda.py,
ops/kda.py, models/mla.py; Kimi-Linear-48B-A3B) at `kimilinear-tiny`, on the
CPU in float32.

The program's forward is held to the benchmark's plain reference
(`benchmarks/families/kimi_linear/reference.py`: the delta rule's recurrence
token by token, MLA in its expanded form, no chunked form, no cache, which
imports nothing of the program) on seeded weights: whole-sequence logits,
then the served prefill in the chunk form and the decode in the step form
through the slot's state and latent. What must NOT move a matrix state is
pinned bit for bit: pad positions, lanes that are not live; and through the
paged engine an idle lane, a staged lane before its flip and a slot restaged
after its tenant overran give the token streams of a fresh engine. A request
admitted from latent blocks AND a state snapshot gives the stream of the
same request with the prefix cache off. A chip's share of a layer's experts
adds up to the uncut layer, `models/mla.py` serves both its families, and
the engines refuse what is built for neither a latent cache nor a recurrent
state.
"""

import dataclasses
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.kimi_linear import reference, roofline, weights
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    SamplingParams,
    TutoringEngine,
)
from distributed_lms_raft_llm_tpu.engine import paged
from distributed_lms_raft_llm_tpu.engine.prefix_cache import StateSnapshot
from distributed_lms_raft_llm_tpu.models import (
    afmoe,
    kda,
    kimi_linear,
    mla,
    moe,
    registry,
)
from distributed_lms_raft_llm_tpu.models.common import rms_norm
from distributed_lms_raft_llm_tpu.ops import kda as kda_ops
from distributed_lms_raft_llm_tpu.utils import metrics_registry as metric

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 24
MAX_NEW = 8
NOTES = "a quorum of nodes agrees on each entry. "
PROMPTS = (NOTES + "why?", NOTES + "who leads?", "what is a term?")
FAMILY_COUNTS = ("moe_picks", "moe_experts_reached", "moe_expert_seats",
                 "moe_picks_held", "moe_passes_bounded",
                 "moe_passes_compacted")


def _load(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def config():
    config = _load("tiny-kimilinear.json")
    config["check"]["logit_positions"] = T
    return config


@pytest.fixture(scope="module")
def model():
    return registry.resolve("kimilinear-tiny", jnp.float32)


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
            == np.asarray(want[4])).all()
    np.testing.assert_allclose(logits[0], want[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(aux["attn_in"][:, 0], want[7], rtol=2e-4,
                               atol=2e-5)


def test_prefill_then_decode_through_the_state_matches_the_recurrence(
        config, model):
    """The served shapes: a right-padded bucket through the chunk form
    (its pad tail not live), then one token at a time through the row's
    state in the step form; the latent and the state after the last token
    are the reference's."""
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
    assert cache.v is None
    np.testing.assert_allclose(cache.k[:, 0, 0, :T], want[1], atol=2e-5)
    np.testing.assert_allclose(cache.ssm[:, 0], want[2], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(cache.conv[:, 0], want[3], atol=2e-5)


@pytest.mark.parametrize("t", [5, 32, 45, 70])
def test_the_chunk_form_is_the_recurrence(t):
    """`kda._chunk_scan` against the delta rule token by token, from a
    state that is not zero, over one sub-chunk, a ragged tail and several
    sub-chunks, with decays strong enough that `exp(-G)` would overflow
    (a channel that forgets within a token: g = -100 a position)."""
    b, h, kd = 2, 3, 8
    keys = jax.random.split(jax.random.key(t), 6)
    q, k = (jax.random.normal(x, (b, t, h, kd)) for x in keys[:2])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (b, t, h, kd))
    g = -jnp.exp(jax.random.uniform(keys[3], (b, t, h, kd), minval=-6.0,
                                    maxval=4.7))
    beta = jax.random.uniform(keys[4], (b, t, h))
    state = jax.random.normal(keys[5], (b, h, kd, kd))

    def token(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.sum(s * k_t[..., None], axis=-2))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)

    with jax.default_matmul_precision("highest"):
        want_s, want_o = jax.lax.scan(
            token, state, tuple(x.swapaxes(0, 1) for x in (q, k, v, g, beta)))
        got_o, got_s = kda._chunk_scan(q, k, v, g, beta, state)
    assert float(jnp.min(jnp.cumsum(g, axis=1))) < -100  # exp(100): inf
    np.testing.assert_allclose(got_o, want_o.swapaxes(0, 1), rtol=2e-4,
                               atol=1e-4)
    np.testing.assert_allclose(got_s, want_s, rtol=2e-4, atol=1e-4)


def test_a_chunked_prefill_is_a_whole_one(model):
    """Chunks of 8 through `rows` onto row 2 of a four-row cache, the last
    one padded, against one forward over the sequence: same logits, same
    state, same latent, and the other rows untouched."""
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
    np.testing.assert_allclose(cache.k[:, 2, :, :21],
                               whole_cache.k[:, 0, :, :21], atol=1e-6)
    for other in (0, 1, 3):
        assert (cache.ssm[:, other] == 3.0).all()
        assert (cache.conv[:, other] == 2.0).all()
        assert (cache.k[:, other] == 0.0).all()


def test_pad_positions_and_dead_lanes_leave_the_state_bit_equal(model):
    family, cfg = model
    params = family.init_params(jax.random.key(1), cfg)
    ids = jax.random.randint(jax.random.key(2), (3, 8), 0, cfg.vocab_size)
    cache = family.init_cache(cfg, 3, 16, dtype=jnp.float32)
    _, cache = family.forward(params, cfg, ids, cache=_ragged(cache, [0] * 3))
    before = cache
    # A decode step in which lane 1 is not live (idle, or ended).
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


@pytest.mark.parametrize("layer,heads", [(0, 8), (2, 16), (1, 4)])
def test_the_step_kernel_computes_the_state_update(layer, heads):
    """`kda_step` (interpreted) against the same update in `jax.numpy`:
    one layer of the stacked plane advanced in place, the others as they
    were, a dead lane (decay 1, beta 0) bit-equal; one, two and a part of
    a group of `HEADS_AT_ONCE` heads."""
    lk, s, kd = 3, 3, 128
    keys = jax.random.split(jax.random.key(layer), 6)
    plane = jax.random.normal(keys[0], (lk, s, heads, kd, kd), jnp.float32)
    q, k = (jax.random.normal(x, (s, heads, kd), jnp.float32) / kd ** 0.5
            for x in keys[1:3])
    v = jax.random.normal(keys[3], (s, heads, kd), jnp.float32)
    decay = jax.random.uniform(keys[4], (s, heads, kd), jnp.float32, 0.2, 1.0)
    beta = jax.random.uniform(keys[5], (s, heads), jnp.float32)
    decay, beta = decay.at[1].set(1.0), beta.at[1].set(0.0)
    want_plane, want_o = kda_ops.kda_step_reference(plane, layer, q, k, v,
                                                    decay, beta)
    got_plane, got_o = kda_ops.kda_step(plane, layer, q, k, v, decay, beta,
                                        interpret=True)
    np.testing.assert_allclose(got_plane, want_plane, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-4, atol=1e-4)
    others = jnp.asarray([i for i in range(lk) if i != layer])
    assert (got_plane[others] == plane[others]).all()
    assert (got_plane[layer, 1] == plane[layer, 1]).all()
    assert (want_plane[layer, 1] == plane[layer, 1]).all()
    assert not (got_plane[layer, 0] == plane[layer, 0]).all()


@pytest.mark.parametrize("family_name,preset,rotates,q_lora", [
    ("axk1", "axk1-tiny", True, 24), ("kimi_linear", "kimilinear-tiny",
                                      False, None)])
def test_mla_serves_both_its_families(family_name, preset, rotates, q_lora):
    """`models/mla.py` reads the two variants off the configuration's own
    keys: A.X-K1's low-rank query with its norm and YaRN rotation, Kimi's
    one query projection and NO rotation; the absorbed products are the
    expanded attention either way."""
    _, cfg = registry.resolve(preset, jnp.float32)
    assert (mla.rotates(cfg), cfg.q_lora_rank) == (rotates, q_lora)
    keys = jax.random.split(jax.random.key(0), 7)

    def normal(key, *shape):
        return jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5

    ap = mla.init_params(keys[:6], cfg, normal, lambda *s: jnp.ones(s))
    assert ("wq" in ap, "wqa" in ap) == (q_lora is None, q_lora is not None)
    b, t = 2, 6
    h = jax.random.normal(keys[6], (b, t, cfg.hidden_size), jnp.float32)
    slots = jnp.broadcast_to(jnp.arange(t), (b, t))
    mask = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None, None]

    def run(positions):
        out, _ = mla.attention(h, ap, cfg, 0, positions, slots, mask, None,
                               jnp.zeros((), jnp.int32), None)
        return out

    with jax.default_matmul_precision("highest"):
        here, moved = run(slots), run(slots + 5)
        scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
        if not rotates:
            # The expanded form by hand: keys [c_kv Wuk | k_p], no rotation.
            dn, kr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
            q = (h @ ap["wq"]).reshape(b, t, cfg.num_heads, -1)
            kva = h @ ap["wkva"]
            c_kv = rms_norm(kva[..., :kr], ap["kvn"]["scale"],
                            cfg.rms_norm_eps)
            k_nope = jnp.einsum("btk,khn->bthn", c_kv, ap["wuk"])
            scores = (jnp.einsum("bthn,bshn->bhts", q[..., :dn], k_nope)
                      + jnp.einsum("bthr,bsr->bhts", q[..., dn:],
                                   kva[..., kr:])) * scale
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
            vals = jnp.einsum("btk,khv->bthv", c_kv, ap["wuv"])
            want = jnp.einsum("bhts,bshv->bthv", probs, vals).reshape(
                b, t, -1) @ ap["wo"]
            np.testing.assert_allclose(here, want, rtol=2e-4, atol=2e-5)
            assert mla.softmax_scale(cfg) == scale
    # A.X-K1's output depends on relative position alone (the rotation);
    # Kimi's does not see positions at all: both are shift-invariant, and
    # only the rotating one changes when positions are SCALED.
    np.testing.assert_allclose(here, moved, rtol=2e-4, atol=2e-5)
    with jax.default_matmul_precision("highest"):
        scaled = run(slots * 3)
    assert bool(jnp.allclose(here, scaled, atol=1e-6)) == (not rotates)


def test_published_checkpoint_names_load_into_the_tree(model):
    family, cfg = model
    rng = np.random.default_rng(0)
    h, kd, _, conv_dim = kda.sizes(cfg)
    d, nh = cfg.hidden_size, cfg.num_heads
    dn, dr, dv, kr = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim, cfg.kv_lora_rank)
    m_ = cfg.moe_intermediate_size

    def mat(*shape):
        return (0.05 * rng.standard_normal(shape)).astype(np.float32)

    sd = {"model.embed_tokens.weight": mat(cfg.vocab_size, d),
          "model.norm.weight": np.ones(d, np.float32),
          "lm_head.weight": mat(cfg.vocab_size, d)}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        a = p + ".self_attn"
        sd[p + ".input_layernorm.weight"] = np.ones(d, np.float32)
        sd[p + ".post_attention_layernorm.weight"] = np.ones(d, np.float32)
        if cfg.is_kda(i):
            for n in "qkv":
                sd[f"{a}.{n}_proj.weight"] = mat(h * kd, d)
                sd[f"{a}.{n}_conv1d.weight"] = mat(h * kd, 1,
                                                   cfg.kda_conv_kernel)
            sd.update({
                a + ".f_a_proj.weight": mat(kd, d),
                a + ".f_b_proj.weight": mat(h * kd, kd),
                a + ".dt_bias": mat(h * kd), a + ".A_log": mat(1, 1, h, 1),
                a + ".b_proj.weight": mat(h, d),
                a + ".g_a_proj.weight": mat(kd, d),
                a + ".g_b_proj.weight": mat(h * kd, kd),
                a + ".o_norm.weight": np.ones(kd, np.float32),
                a + ".o_proj.weight": mat(d, h * kd)})
        else:
            sd.update({
                a + ".q_proj.weight": mat(nh * (dn + dr), d),
                a + ".kv_a_proj_with_mqa.weight": mat(kr + dr, d),
                a + ".kv_a_layernorm.weight": np.ones(kr, np.float32),
                a + ".kv_b_proj.weight": mat(nh * (dn + dv), kr),
                a + ".o_proj.weight": mat(d, nh * dv)})
        if i < cfg.num_dense_layers:
            for n, shape in (("gate", (cfg.intermediate_size, d)),
                             ("up", (cfg.intermediate_size, d)),
                             ("down", (d, cfg.intermediate_size))):
                sd[f"{p}.mlp.{n}_proj.weight"] = mat(*shape)
        else:
            m = p + ".block_sparse_moe"
            sd[m + ".gate.weight"] = mat(cfg.num_experts, d)
            sd[m + ".gate.e_score_correction_bias"] = mat(cfg.num_experts)
            for e in range(cfg.num_experts):
                sd[f"{m}.experts.{e}.w1.weight"] = mat(m_, d)
                sd[f"{m}.experts.{e}.w3.weight"] = mat(m_, d)
                sd[f"{m}.experts.{e}.w2.weight"] = mat(d, m_)
            for n, shape in (("gate", (m_, d)), ("up", (m_, d)),
                             ("down", (d, m_))):
                sd[f"{m}.shared_experts.{n}_proj.weight"] = mat(*shape)
    got = family.params_from_hf(sd, cfg)
    drawn = family.init_params(jax.random.key(0), cfg)
    assert jax.tree.structure(got) == jax.tree.structure(drawn)
    assert (jax.tree.map(lambda x: x.shape, got)
            == jax.tree.map(lambda x: x.shape, drawn))
    # A convolution [C, 1, K] is held [K, C], the three side by side; of
    # the 32 experts the share held is read.
    np.testing.assert_array_equal(
        got["layers"][0]["attn"]["conv_w"][1, h * kd:2 * h * kd],
        sd["model.layers.0.self_attn.k_conv1d.weight"][:, 0, 1])
    # ... both of an expert's widths padded with zeros (`pad_experts`: to
    # whole lanes at this size, to whole tiles of 512 at the published).
    moe = got["layers"][1]["moe"]
    np.testing.assert_array_equal(
        moe["wu"][3, :d, :m_],
        sd["model.layers.1.block_sparse_moe.experts.3.w3.weight"].T)
    assert moe["wg"].shape == moe["wu"].shape == (8, 128, 128)
    assert moe["wd"].shape == (8, 128, 128)
    assert not moe["wu"][:, d:].any() and not moe["wd"][..., d:].any()
    logits, _ = family.forward(got, cfg, jnp.arange(6)[None])
    assert bool(jnp.isfinite(logits).all())


# --------------------------------------- a chip's share of a layer's experts


@pytest.mark.parametrize("shares", [4, 2, 1])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(config, shares):
    """Every chip routes over all 32 experts and computes its own experts'
    part plus the shared expert's; the routed parts of all the shares (the
    benchmark's four quarters among them), with the shared expert counted
    once, are the uncut reference layer."""
    whole = dict(config, num_experts=32)
    w = weights.of_config(11, whole, jnp.float32)
    lw = w.layer(1)
    x = jax.random.normal(jax.random.key(5), (T, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, picked = reference._experts(
            x, lw, eps=1e-5, k=4, norm=True, scale=2.446, first=0)
    _, cfg = registry.resolve("kimilinear-tiny", jnp.float32)
    tree = weights.program_layer(lw, w.sizes)
    h = rms_norm(x, tree["ln2"]["scale"], 1e-5)[None]
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


def test_idle_lanes_reach_no_expert_and_land_no_pick(model):
    family, cfg = model
    params = family.init_params(jax.random.key(1), cfg)
    ids = jnp.arange(4)[:, None] + 7

    def counts(live):
        cache = family.init_cache(cfg, 4, 8, dtype=jnp.float32)
        return family.forward(params, cfg, ids, cache=_ragged(cache, [0] * 4),
                              live=live, aux=True)[2]["counts"]

    le = cfg.num_layers - cfg.num_dense_layers
    assert family.counters == FAMILY_COUNTS
    full = dict(zip(family.counters, counts(jnp.ones((4,), bool))))
    assert full["moe_picks"] == 4 * 4 * le
    assert full["moe_expert_seats"] == 8 * le
    assert 0 < full["moe_picks_held"] < full["moe_picks"]
    idle = dict(zip(family.counters, counts(jnp.zeros((4,), bool))))
    assert idle["moe_picks"] == idle["moe_picks_held"] == 0
    assert idle["moe_experts_reached"] == 0


# ------------------ the cell's decode row over the held picks' prefix alone


@pytest.mark.parametrize("biased", [False, True], ids=["fair", "biased"])
def test_a_decode_rows_products_over_96_rows_equal_those_over_all_128(biased):
    """The cell's decode row by its counts: 16 lanes x 8 picks over 256
    experts of which 64 are held, so 128 sorted rows, of which `held_rows`
    keeps 96 (a fair router's 32 +- 4.9 held picks and twelve deviations).
    A fair router's pass takes the prefix; one whose balancing bias sends
    every pick to the share (128 held picks) takes the fallback; either
    way the output and the group sizes are, to the bit, those of the call
    that has no bound."""
    ks = jax.random.split(jax.random.key(48), 5)
    x = jax.random.normal(ks[0], (16, 32), jnp.float32)
    wr = jax.random.normal(ks[1], (32, 256), jnp.float32)
    bias = jnp.where(jnp.arange(256) < 64, 2.0 if biased else 0.0, 0.0)
    top_i, top_w = moe.route_sigmoid(x, wr, bias, 8, True, 2.446)
    wg, wu, wd = (0.2 * jax.random.normal(k, shape, jnp.float32)
                  for k, shape in zip(ks[2:], ((64, 32, 16), (64, 32, 16),
                                               (64, 16, 32))))
    call = partial(moe.grouped_swiglu, x, top_i, top_w, jnp.ones((16,), bool),
                   wg, wu, wd, first=0)
    fit = moe.held_rows(128, 64, 256)
    assert fit == 96
    y, sizes = call(among=256)
    y_all, sizes_all = call()
    bounded = str(jax.make_jaxpr(partial(call, among=256))())
    # The prefix of 96 rows is three tiles of 32 as it is; the fallback's
    # 128 rows are handed 160 (`moe.tiled_rows`), and so is the call that
    # has no bound.
    assert "cond" in bounded and "f32[96,32]" in bounded
    assert "f32[160,32]" in bounded and "f32[128,16]" not in bounded
    unbounded = str(jax.make_jaxpr(call)())
    assert "cond" not in unbounded and "f32[160,32]" in unbounded
    held = int(np.asarray(top_i < 64).sum())
    assert int(sizes.sum()) == held > 0
    assert held == 128 if biased else held <= fit  # which branch was taken
    np.testing.assert_array_equal(sizes, sizes_all)
    np.testing.assert_array_equal(y, y_all)
    assert np.asarray(y).any()


# ------------------------------------------------- through the paged engine


def _econf(**kw):
    kw.setdefault("sampling", SamplingParams.reference_defaults(
        max_new_tokens=MAX_NEW, temperature=0.0, top_k=0, top_p=1.0))
    return EngineConfig(model="kimilinear-tiny", dtype=jnp.float32,
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
    first round prefills from zeros, the second finds the notes' latent
    but no state that deep and snapshots at the branch point, the third
    splices the latent blocks AND starts from that snapshot."""
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
    "from_zeros", "recomputed_for_state", "from_blocks_and_a_snapshot"])
def test_a_prefix_hit_gives_the_cold_stream(served, alone, round_):
    eng, rounds = served
    answers, (hit, prompt_tokens, _, _), counts = rounds[round_]
    assert answers == [alone[p] for p in PROMPTS]
    step = 8  # lcm(prefill chunk 8, block 4)
    if round_ == 0:
        assert hit == 0 and not counts["state_snapshots_restored"]
    if round_ == 2:
        assert counts["state_snapshots_restored"] >= 2
        assert hit >= 2 * (len(NOTES) // step * step)
        assert hit % step == 0
        assert counts["prefix_tokens_recomputed_for_state"] < 2 * step
    assert set(counts) <= set(metric.ENGINE_LOOP_COUNTERS)
    for name in FAMILY_COUNTS:
        assert metric.is_declared(metric.ENGINE_LOOP_COUNTERS[name])
        assert counts[name] > 0 or name.startswith("moe_passes")
    # A snapshot is every KDA layer's state and window of one sequence.
    state = eng.state.cache
    one = (state.ssm[:, :1].nbytes + state.conv[:, :1].nbytes)
    assert eng.state_snapshot_bytes == eng.prefix_cache.snapshot_bytes
    assert eng.state_snapshot_bytes % one == 0 and eng.state_snapshot_bytes


@pytest.mark.parametrize("round_", [0, 1, 2])
def test_engine_counts_the_passes_whose_products_ran_over_a_prefix(
        served, round_):
    """A quarter of the experts at these sizes: twelve deviations cover
    every row of a decode step's 16 picks and of a one-row pass's 32, and
    a pass of four rows (128 picks, a prefix of 96) is bounded and, under
    this fair router, fits: the family publishes both counts."""
    eng, rounds = served
    counts = rounds[round_][2]
    assert eng.family.counters == FAMILY_COUNTS
    le = eng.cfg.num_layers - eng.cfg.num_dense_layers
    assert (moe.held_rows(16, 8, 32), moe.held_rows(32, 8, 32),
            moe.held_rows(128, 8, 32)) == (16, 32, 96)
    wide = (counts.get("prefill_crowded_passes", 0)
            - counts.get("prefill_crowded_narrow_passes", 0))
    assert (counts["moe_passes_bounded"] == counts["moe_passes_compacted"]
            == wide * le)


def test_a_snapshot_at_the_published_sizes_is_15_megabytes():
    family, cfg = registry.resolve("kimi-linear-9l-64of256", jnp.bfloat16)
    state = jax.eval_shape(lambda: paged._fresh_state(family, cfg, 16, 384))
    assert state.cache.k.shape == (2, 16, 1, 384, 576)
    assert state.cache.v is None
    assert state.cache.ssm.shape == (7, 16, 32, 128, 128)
    assert state.cache.conv.shape == (7, 16, 3, 12288)
    snap = sum(int(np.prod(x.shape[:1] + x.shape[2:])) * x.dtype.itemsize
               for x in (state.cache.ssm, state.cache.conv))
    config = _load("kimi-linear.json")
    assert snap == 15_196_160 == config["hbm_bytes_worked_out"][
        "state_snapshot"]


def test_idle_staged_and_restaged_lanes_give_a_fresh_engines_streams(alone):
    """Two slots, five requests, the later ones submitted while the first
    decode: lanes sit idle, staged lanes wait their turn in the scan while
    the live lane decodes, every slot is handed on to a next tenant after
    its previous one ran past its cap, and every stream is the one a
    fresh engine gives."""
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


def test_the_planes_of_both_kinds_are_staged_restored_and_grown(model):
    """`_stage_program` zeroes the slot's state rows; `_stage_block_program`
    splices a latent block (no `v` plane); `_restore_state_program` puts a
    snapshot there; `_grow_state_program` widens the latent and passes the
    planes without a width through; `_export_block_program` cuts the one
    plane there is."""
    family, cfg = model
    state = paged._fresh_state(family, cfg, 3, 16)
    assert state.cache.v is None and state.snap_ssm is not None
    state = state._replace(cache=state.cache._replace(
        ssm=state.cache.ssm + 1.0, conv=state.cache.conv + 1.0,
        k=state.cache.k + 2.0))
    block = paged._export_block_program(state.cache, 4, 0, block=4)
    assert block.v is None and block.k.shape == (2, 1, 1, 4, 24)
    ids = np.zeros((1, 16), np.int32)
    key = jax.random.key_data(jax.random.key(0))
    staged = paged._stage_program(state, 1, ids, 5, 0, 0, key, 8)
    assert (staged.cache.ssm[:, 1] == 0).all()
    assert (staged.cache.conv[:, 1] == 0).all()
    assert (staged.cache.ssm[:, 0] == 1).all()
    spliced = paged._stage_block_program(
        staged, block._replace(k=block.k + 1.0), 1, 8)
    assert (spliced.cache.k[:, 1, :, 8:12] == 3).all()
    assert (spliced.cache.k[:, 1, :, :8] == 2).all()
    snap = StateSnapshot(ssm=jnp.full_like(state.cache.ssm[:, :1], 7.0),
                         conv=jnp.full_like(state.cache.conv[:, :1], 5.0))
    restored = paged._restore_state_program(spliced, snap, 1)
    assert (restored.cache.ssm[:, 1] == 7).all()
    assert (restored.cache.conv[:, 1] == 5).all()
    assert (restored.cache.ssm[:, 2] == 1).all()
    grown = paged._grow_state_program(restored, 24)
    assert grown.cache.k.shape[3] == 24 and grown.cache.v is None
    assert (grown.cache.ssm == restored.cache.ssm).all()


def test_warm_up_compiles_the_programs_that_are_there(alone):
    """No program is added for this family: the inventory's, with the
    snapshot programs a recurrent family has."""
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
    for scope in ("decode", "prefill_chunk", "sample", "attn.kda",
                  "kda.proj", "kda.conv", "kda.scan", "kda.out", "attn.mla",
                  "mla.absorb", "mla.scores", "mla.out", "mlp.dense",
                  "moe.route", "moe.experts", "moe.shared"):
        assert scope in text, scope


@pytest.mark.parametrize("engine", [PagedEngine, TutoringEngine])
@pytest.mark.parametrize("setting,why", [
    ({"ep": 2}, "requires an MoE family"),
    ({"tp": 2}, "latent|recurrent state"),
    ({"spec_tokens": 2}, "recurrent state")])
def test_engines_refuse_what_neither_plane_allows(engine, setting, why):
    fam = registry.KIMI_LINEAR_FAMILY
    assert fam.routed and fam.latent_cache and fam.recurrent_state
    assert not fam.expert_parallel
    with pytest.raises(ValueError, match=why):
        engine(_econf(**setting))


def test_an_int8_cache_is_refused(model):
    family, cfg = model
    with pytest.raises(ValueError, match="kv_quant"):
        family.init_cache(dataclasses.replace(cfg, quant_kv=True), 1, 8)


def test_the_published_lists_part_the_layers():
    cfg = kimi_linear.KimiLinearConfig.kimi_linear()
    assert len(cfg.kda_layers) == 20 and len(cfg.full_attn_layers) == 7
    assert sorted(cfg.kda_layers + cfg.full_attn_layers) == list(
        range(1, 28))
    cut = kimi_linear.KimiLinearConfig.kimi_linear_9l_share()
    assert [cut.is_kda(i) for i in range(9)] == [
        True, True, True, False, True, True, True, False, True]
    assert [cut.index(i) for i in range(9)] == [0, 1, 2, 0, 3, 4, 5, 1, 6]
    assert (cut.vocab_size, cut.experts_held, cut.num_experts) == (
        40960, (0, 64), 256)


# ---------------------------------------------------- the benchmark's files


def test_the_benchmark_names_the_configuration_the_cell_and_its_metrics(
        moves_a_reported_metric):
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = "kimi-linear.notes-herd"
    conf = {c["name"]: c for c in bench["configs"]}["kimi-linear"]
    assert conf["reduced"] == ["num_hidden_layers", "linear_attn_config",
                               "num_experts", "vocab_size"]
    assert conf in bench["configs"]
    work = {w["name"]: w for w in bench["workloads"]}[cell]
    assert work in bench["workloads"]
    assert (work["config"], work["traffic"], work["chips"]) == (
        "kimi-linear", "notes-herd", 1)
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in ("kda_step_dev_us_per_tok", "kda_step_roofline"):
        assert metrics[name]["workloads"] == [cell]
        moves_a_reported_metric(metrics[name])
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".json"))
    for name in ("moe_experts_reached_share", "moe_experts_dev_us_per_tok",
                 "moe_experts_roofline", "moe_held_picks_share",
                 "mla_decode_dev_us_per_tok", "mla_decode_roofline",
                 "prefix_recomputed_for_state_share"):
        # Found by its name, not by its place: later PRs append cells.
        assert cell in metrics[name]["workloads"]
    config = _load("kimi-linear.json")
    _, cfg = registry.resolve(config["registry_model"], jnp.bfloat16)
    lin = config["linear_attn_config"]
    assert tuple(lin["kda_layers"]) == cfg.kda_layers
    assert tuple(lin["full_attn_layers"]) == cfg.full_attn_layers
    assert (cfg.vocab_size, cfg.experts_held) == (40960, (0, 64))
    worked = config["hbm_bytes_worked_out"]
    assert worked["parameters"] == roofline.parameters(config) == (
        4_272_540_512)
    assert all(len(x["why"]) <= 200 for x in (conf, work))


def test_roofline_counts_by_hand():
    config = _load("kimi-linear.json")
    assert roofline.kda_params(config) == 39_514_272
    assert roofline.mla_params(config) == 29_114_880
    assert roofline.expert_params(config) == 3 * 2304 * 1024 == 7_077_888
    assert roofline.routed_rest_params(config) + 64 * 7_077_888 == (
        460_652_800)
    assert roofline.ssm_bytes_per_slot(config) == 32 * 128 * 128 * 4
    assert roofline.conv_bytes_per_slot(config) == 3 * 12288 * 2
    assert roofline.latent_bytes_per_token(config) == 2 * 1152
    trace = {"span_counters": {"engine_scan_iterations": 100,
                               "moe_experts_reached": 12_000}}
    experts = roofline.experts_cost(config, trace, 1500.0, 400.0)
    assert experts["bytes"] == 12_000 * 7_077_888 * 2
    assert experts["ops"] == 2.0 * 1500 * 8 * 2.0 * 7_077_888
    step = roofline.kda_step_cost(config, trace, 1500.0, 400.0)
    assert step["bytes"] == 1500 * 2 * 7 * 2_097_152
    assert step["bytes_read"] == 100 * 16 * 2 * 7 * 2_097_152
    attn = roofline.mla_decode_cost(config, trace, 1500.0, 400.0)
    assert attn["bytes"] == 1500 * 400 * 2304
    assert attn["bytes_read"] == 100 * 16 * 2688 * 2304
    whole = roofline.cost(config, trace, 1500.0, 400.0)
    assert whole["bytes"] == (
        100 * roofline.trunk_params(config) * 2 + experts["bytes"]
        + 1500 * (400 * 2304 + 2 * 7 * (2_097_152 + 73_728)))
    assert roofline.cost(config, {"span_counters": {}}, 1.0, 1.0) is None
