"""Liquid AI's lfm2_moe family (models/lfm2.py, ops/shortconv.py;
LFM2-8B-A1B) at `lfm2-tiny`, on the CPU in float32.

The program's forward is held to the benchmark's plain reference
(`benchmarks/families/lfm2_moe/reference.py`: the convolution token by token
from a zero window, expanded attention, every token's experts one by one,
which imports nothing of the program) on seeded weights: whole-sequence
logits, then the served prefill in the chunk form and the decode in the step
form through the slot's windows. The conv operator, the router and the
per-head norm are done by hand on a dozen positions. A window has no dead
region, so what must NOT move it is pinned bit for bit: pad positions, lanes
that are not live. The family is the first with `KVCache.conv` and no `ssm`:
every state program of the paged engine is held with `ssm` None, and a
request admitted from a window snapshot gives the stream of the same
request served alone. The 32 experts parted into shares add up to the whole
layer. `causal_conv` took an argument for this family: `nemotron_h`'s and
`kimi_linear`'s outputs are held bit-equal to the function it was.
"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check
from benchmarks.families.lfm2_moe import compare, reference, roofline, weights
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
from distributed_lms_raft_llm_tpu.models import lfm2, mamba2, moe, registry
from distributed_lms_raft_llm_tpu.models.common import rms_norm
from distributed_lms_raft_llm_tpu.models.llama import rope
from distributed_lms_raft_llm_tpu.ops import shortconv
from distributed_lms_raft_llm_tpu.parallel import partition
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
    config = _load("tiny-lfm2.json")
    config["check"]["logit_positions"] = T
    return config


@pytest.fixture(scope="module")
def model():
    return registry.resolve("lfm2-tiny", jnp.float32)


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


def _heads(rows, heads):
    """`lfm2.fold_kv` undone: [.., T, Hkv * Dh] -> [.., Hkv, T, Dh]."""
    *lead, t, f = rows.shape
    return jnp.swapaxes(rows.reshape(*lead, t, heads, f // heads), -3, -2)


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
    np.testing.assert_allclose(aux["attn_in"][:, 0], want[5], rtol=2e-4,
                               atol=2e-5)


def test_prefill_then_decode_through_the_windows_matches_the_reference(
        config, model):
    """The served shapes: a right-padded bucket through the chunk form (its
    pad tail not live), then one token at a time through the row's windows
    in the step form; keys, values and the windows after the last token are
    the reference's."""
    family, cfg = model
    w, params, ids = _drawn(config, 5)
    want = reference.forward(w, ids, config)
    n, bucket, width = 16, 20, 32
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :n] = ids[:n]
    real = (jnp.arange(bucket) < n)[None]
    with jax.default_matmul_precision("highest"):
        cache = family.init_cache(cfg, 1, width, dtype=jnp.float32)
        logits, cache = family.forward(
            params, cfg, jnp.asarray(prompt), cache=cache, live=real)[:2]
        rows = [logits[0, :n]]
        cache = _ragged(cache, [n])
        for t in range(n, T):
            mask = jnp.arange(width)[None, :] <= cache.length[:, None]
            logits, cache = family.forward(
                params, cfg, jnp.asarray(ids[t:t + 1])[None], cache=cache,
                kv_mask=mask)[:2]
            cache = _ragged(cache, [t + 1])
            rows.append(logits[0])
    np.testing.assert_allclose(jnp.concatenate(rows), want[0], rtol=3e-4,
                               atol=3e-4)
    # A position's key heads lie side by side in one row of the plane.
    assert cache.k.shape == (3, 1, 1, width, cfg.num_kv_heads * cfg.head_dim)
    np.testing.assert_allclose(
        _heads(cache.k[:, 0, 0, :T], cfg.num_kv_heads), want[1],
        rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        _heads(cache.v[:, 0, 0, :T], cfg.num_kv_heads), want[2],
        rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(cache.conv[:, 0], want[3], rtol=2e-4,
                               atol=2e-5)
    assert cache.ssm is None


def test_a_chunked_prefill_is_a_whole_one(model):
    """Chunks of 8 through `rows=[1]` of a 3-row cache, the window carried
    across every seam, against one pass over the whole prompt: logits,
    keys, values and windows."""
    family, cfg = model
    params = family.init_params(jax.random.key(3), cfg)
    ids = jax.random.randint(jax.random.key(4), (1, 24), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        whole_cache = family.init_cache(cfg, 1, 32, dtype=jnp.float32)
        whole, whole_cache = family.forward(params, cfg, ids,
                                            cache=whole_cache)[:2]
        cache = _ragged(family.init_cache(cfg, 3, 32, dtype=jnp.float32),
                        [0, 0, 0])
        parts = []
        for start in range(0, 24, 8):
            out, new = family.forward(
                params, cfg, ids[:, start:start + 8],
                cache=cache._replace(length=jnp.asarray([start])),
                rows=jnp.asarray([1]))[:2]
            cache = new._replace(length=cache.length)
            parts.append(out)
    np.testing.assert_allclose(jnp.concatenate(parts, axis=1), whole,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(cache.conv[:, 1], whole_cache.conv[:, 0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(cache.k[:, 1, :, :24],
                               whole_cache.k[:, 0, :, :24], rtol=1e-5,
                               atol=1e-6)
    assert (cache.conv[:, 0] == 0).all() and (cache.conv[:, 2] == 0).all()


def test_pad_positions_and_dead_lanes_leave_the_windows_bit_equal(model):
    """A chunk whose tail is padding keeps the window that ends at its last
    live position; a chunk with no live position and a decode lane that is
    not live leave their row's windows as they were, bit for bit."""
    family, cfg = model
    params = family.init_params(jax.random.key(3), cfg)
    ids = jax.random.randint(jax.random.key(4), (1, 8), 0, cfg.vocab_size)
    cache = family.init_cache(cfg, 2, 16, dtype=jnp.float32)
    cache = _ragged(cache._replace(conv=cache.conv + 1.5), [0, 0])
    row = dict(rows=jnp.asarray([0]),
               cache=cache._replace(length=jnp.asarray([0])))
    live5 = (jnp.arange(8) < 5)[None]
    padded = family.forward(params, cfg, ids, live=live5, **row)[1]
    short = family.forward(params, cfg, ids[:, :5], **row)[1]
    assert (padded.conv == short.conv).all()
    assert not (padded.conv[:, 0] == cache.conv[:, 0]).all()
    assert (padded.conv[:, 1] == cache.conv[:, 1]).all()
    none = family.forward(params, cfg, ids, live=jnp.zeros((1, 8), bool),
                          **row)[1]
    assert (none.conv == cache.conv).all()
    # A decode step over both rows, row 1 alone live.
    live = jnp.asarray([False, True])
    mask = jnp.arange(16)[None, :] <= cache.length[:, None]
    stepped = family.forward(params, cfg, ids[:, :1].repeat(2, 0),
                             cache=cache, kv_mask=mask, live=live)[1]
    assert (stepped.conv[:, 0] == cache.conv[:, 0]).all()
    assert not (stepped.conv[:, 1] == cache.conv[:, 1]).all()


# ------------------------------------------------ the conv operator, by hand


def _conv_by_hand(x, cp):
    """The gated short convolution of ONE sequence x [T, D], a position at
    a time in numpy: no activation, no bias, zeros before the start."""
    d = x.shape[1]
    bcz = np.asarray(x, np.float64) @ np.asarray(cp["w_in"], np.float64)
    gate_in, gate_out, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    u = gate_in * z
    w = np.asarray(cp["conv_w"], np.float64)
    y = np.zeros_like(u)
    for t in range(len(u)):
        for j in range(3):
            back = t - 2 + j
            if back >= 0:
                y[t] += w[j] * u[back]
    return (gate_out * y) @ np.asarray(cp["w_out"], np.float64), u


@pytest.mark.parametrize("position", range(12))
def test_the_conv_operator_by_hand(model, position):
    """A dozen positions: the first two see zero inputs, both gates are
    applied, nothing is activated."""
    _, cfg = model
    cp = lfm2.init_params(jax.random.key(2), cfg)["layers"][0]["conv"]
    x = jax.random.normal(jax.random.key(9), (12, cfg.hidden_size))
    want, u = _conv_by_hand(x, cp)
    with jax.default_matmul_precision("highest"):
        got, _ = lfm2.short_conv(x[None], cp, jnp.ones((1, 12), bool))
    np.testing.assert_allclose(got[0, position], want[position], rtol=2e-4,
                               atol=2e-6)
    if position < 2:
        # Only the inputs there are enter: one tap at 0, two at 1.
        w = np.asarray(cp["conv_w"], np.float64)
        d = cfg.hidden_size
        gate_out = (np.asarray(x, np.float64)
                    @ np.asarray(cp["w_in"], np.float64))[:, d:2 * d]
        taps = sum(w[2 - j] * u[position - j] for j in range(position + 1))
        np.testing.assert_allclose(
            want[position],
            (gate_out[position] * taps) @ np.asarray(cp["w_out"],
                                                     np.float64))


def test_the_chunk_form_is_token_by_token_across_a_seam(model):
    """Twelve positions as chunks of 5 and 7 through a cache's window
    against the hand's whole sequence, and one position at a time through
    the step form."""
    _, cfg = model
    cp = lfm2.init_params(jax.random.key(2), cfg)["layers"][0]["conv"]
    x = jax.random.normal(jax.random.key(9), (1, 12, cfg.hidden_size))
    want, u = _conv_by_hand(x[0], cp)
    plane = jnp.zeros((2, 1, 2, cfg.hidden_size), jnp.float32)
    with jax.default_matmul_precision("highest"):
        a, plane_a = lfm2.short_conv(x[:, :5], cp, jnp.ones((1, 5), bool),
                                     plane, 1, jnp.asarray([0]))
        b, plane_b = lfm2.short_conv(x[:, 5:], cp, jnp.ones((1, 7), bool),
                                     plane_a, 1, jnp.asarray([0]))
        steps, stepped = [], plane
        for t in range(12):
            out, stepped = lfm2.short_conv(
                x[:, t:t + 1], cp, jnp.ones((1, 1), bool), stepped, 1)
            steps.append(out)
    np.testing.assert_allclose(jnp.concatenate([a, b], axis=1)[0], want,
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(jnp.concatenate(steps, axis=1)[0], want,
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(plane_a[1, 0], u[3:5], rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(plane_b[1, 0], u[10:], rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(stepped[1, 0], u[10:], rtol=2e-4, atol=2e-6)
    assert (plane_b[0] == 0).all() and (stepped[0] == 0).all()


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_step_kernel_computes_the_plain_form(layer, dtype):
    """`shortconv_step` (interpreted) against `shortconv_step_reference`:
    one layer of the stacked plane shifted for the live slots alone, the
    others and every other layer bit-equal to what they were."""
    slots, c = 4, 128
    keys = jax.random.split(jax.random.key(layer), 4)
    plane = jax.random.normal(keys[0], (3, slots, 2, c)).astype(dtype)
    bcz = jax.random.normal(keys[1], (slots, 3 * c)).astype(dtype)
    w = jax.random.normal(keys[2], (3, c)).astype(dtype)
    live = jnp.asarray([True, False, True, False])
    got_plane, got = shortconv.shortconv_step(plane, layer, bcz, live, w,
                                              interpret=True)
    want_plane, want = shortconv.shortconv_step_reference(
        plane, layer, bcz, live, w)
    assert (got_plane == want_plane).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-5,
                               atol=1e-6)
    dead = np.asarray(~live)
    assert (np.asarray(got_plane)[:, dead] == np.asarray(plane)[:, dead]).all()
    others = [i for i in range(3) if i != layer]
    assert (got_plane[jnp.asarray(others)]
            == plane[jnp.asarray(others)]).all()
    u = (bcz[:, :c].astype(jnp.float32)
         * bcz[:, 2 * c:].astype(jnp.float32)).astype(dtype)
    assert (got_plane[layer, 0, 1] == u[0]).all()
    assert (got_plane[layer, 0, 0] == plane[layer, 0, 1]).all()


# ------------------------------------------------ the router, by hand


def test_the_router_by_hand():
    """The bias takes part in the choice and not in the weight; the
    weights are the chosen scores over their sum + 1e-6; a tie goes to the
    lower index."""
    x = jnp.eye(4, dtype=jnp.float32)[:2]
    wr = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.5, 0.5, 0.5, 0.5],
                      [0.0] * 4, [0.0] * 4])
    bias = jnp.asarray([-10.0, 0.0, 0.0, 5.0])
    top_i, top_w = moe.route_sigmoid(x, wr, bias, 2, True, 1.0, 1e-6)
    s = 1.0 / (1.0 + np.exp(-np.asarray(wr[:2], np.float64)))
    # Token 0: scores fall 0 > 1 > 2 > 3; with the bias the choice is 3, 1.
    assert top_i[0].tolist() == [3, 1]
    np.testing.assert_allclose(
        top_w[0], [s[0, 3] / (s[0, 3] + s[0, 1] + 1e-6),
                   s[0, 1] / (s[0, 3] + s[0, 1] + 1e-6)], rtol=1e-6)
    # Token 1: every score alike; 3 by its bias, then the tie's lowest.
    assert top_i[1].tolist() == [3, 1]
    none_i, _ = moe.route_sigmoid(x, wr, None, 2, True, 1.0, 1e-6)
    assert none_i.tolist() == [[0, 1], [0, 1]]
    # The epsilon is the family's own: other families' 1e-20 is the default.
    far = moe.route_sigmoid(x, wr, bias, 2, True, 1.0)[1]
    assert float(jnp.sum(far[0])) > float(jnp.sum(top_w[0]))
    np.testing.assert_allclose(jnp.sum(top_w[0]),
                               1.0 / (1.0 + 1e-6 / (s[0, 3] + s[0, 1])),
                               rtol=1e-6)


def test_the_model_routes_with_its_bias_and_its_epsilon(config, model):
    family, cfg = model
    assert (cfg.route_eps, cfg.route_scale, cfg.route_norm) == (
        1e-6, 1.0, True)
    w, params, ids = _drawn(config, 3)
    biased = reference.forward(w, ids, config)[4]
    plain = reference.forward(w, ids, config, control="no_router_bias")[4]
    assert (np.asarray(biased) != np.asarray(plain)).any()
    lw = w.layer(1, experts=False)
    assert float(jnp.abs(lw[weights.BIAS]).min()) > 0
    np.testing.assert_allclose(np.sort(np.asarray(lw[weights.BIAS])),
                               np.sort(np.asarray(
                                   w.layer(2, experts=False)[weights.BIAS])),
                               rtol=1e-6)


# ------------------------------------------------ attention


def test_the_per_head_norm_comes_before_the_rotation(config, model):
    """One attention layer's keys as the cache holds them are
    rope(norm(k)), not norm(rope(k)) with the weight applied after, and
    the two differ (a learned weight does not commute with the rotation)."""
    family, cfg = model
    w, params, ids = _drawn(config, 9)
    ap = params["layers"][1]["attn"]          # published layer 2
    with jax.default_matmul_precision("highest"):
        cache = family.init_cache(cfg, 1, 32, dtype=jnp.float32)
        _, cache, aux = family.forward(params, cfg, ids[None], cache=cache,
                                       aux=True)
        h = aux["attn_in"][0]
        k = (h @ ap["wk"]).reshape(1, T, cfg.num_kv_heads, -1).transpose(
            0, 2, 1, 3)
        pos = jnp.arange(T)[None]
        before = rope(rms_norm(k, ap["kn"]["scale"], cfg.rms_norm_eps), pos,
                      cfg.rope_theta)
        after = rms_norm(rope(k, pos, cfg.rope_theta), ap["kn"]["scale"],
                         cfg.rms_norm_eps)
    np.testing.assert_allclose(
        _heads(cache.k[0, 0, 0, :T], cfg.num_kv_heads), before[0],
        rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(before - after))) > 1e-2
    assert cfg.rope_theta == 1e6 and cfg.head_dim * cfg.num_heads == (
        cfg.hidden_size)


@pytest.mark.parametrize("t", [1, 5])
def test_attention_over_folded_rows_is_grouped_query_attention(t):
    """`attend_folded` over rows that hold a position's key heads side by
    side against `common.attend` over heads: every query head meets its
    own key head's columns and keeps its own columns of the values."""
    from distributed_lms_raft_llm_tpu.models.common import (
        attend, causal_window_mask)

    b, h, nkv, s, dh = 2, 8, 4, 9, 8
    keys = jax.random.split(jax.random.key(t), 3)
    q = jax.random.normal(keys[0], (b, h, t, dh))
    k = jax.random.normal(keys[1], (b, nkv, s, dh))
    v = jax.random.normal(keys[2], (b, nkv, s, dh))
    mask = causal_window_mask(
        jnp.broadcast_to(jnp.arange(s - t, s)[None], (b, t)), s)
    want = attend(q.reshape(b, nkv, h // nkv * t, dh), k, v,
                  jnp.tile(mask, (1, 1, h // nkv, 1))).reshape(b, h, t, dh)
    got = lfm2.attend_folded(q, lfm2.fold_kv(k), lfm2.fold_kv(v), mask, nkv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert (_heads(lfm2.fold_kv(k), nkv) == k).all()


# ------------------------------------------------ layers by published index


@pytest.mark.parametrize("preset,layers", [
    ("lfm2-8b-a1b", 24), ("lfm2-8b-a1b-13l", 13), ("lfm2-tiny", 13)])
def test_layer_kinds_follow_the_published_index(preset, layers):
    _, cfg = registry.resolve(preset, jnp.bfloat16)
    assert cfg.num_layers == layers
    for i in range(layers):
        published = cfg.layer_offset + i
        assert cfg.is_attention(i) == (published in lfm2.PUBLISHED_ATTENTION)
        assert cfg.is_dense(i) == (published < 2)
    if layers == 13:
        assert cfg.layer_offset == 1
        assert [i for i in range(13) if cfg.is_attention(i)] == [1, 5, 9]
        assert [i for i in range(13) if cfg.is_dense(i)] == [0]
        assert (cfg.count(lfm2.CONV), cfg.count(lfm2.FULL)) == (10, 3)
        assert [cfg.index(i) for i in (0, 1, 2, 5, 12)] == [0, 0, 1, 1, 9]
    else:
        assert (cfg.count(lfm2.CONV), cfg.count(lfm2.FULL)) == (18, 6)
        assert sum(cfg.is_dense(i) for i in range(24)) == 2


def test_a_cut_past_the_published_depth_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.Lfm2MoeConfig(num_layers=13, layer_offset=12,
                           layer_types=lfm2.PUBLISHED_TYPES[1:14])
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.Lfm2MoeConfig(num_layers=3)


def test_the_cuts_tree_and_cache_have_the_cuts_shapes():
    """`lfm2-8b-a1b-13l` from shapes alone: ISSUE 57's 4,606 M parameters
    beside the experts' padding, three attention layers' keys and values
    and ten conv layers' windows, no `ssm`."""
    family, cfg = registry.resolve("lfm2-8b-a1b-13l", jnp.bfloat16)
    params = jax.eval_shape(
        lambda: family.init_params(jax.random.key(0), cfg))
    config = _load("lfm2-8b-a1b.json")
    padding = 12 * 32 * 3 * 2048 * (2048 - 1792)
    assert sum(x.size for x in jax.tree.leaves(params)) == (
        roofline.parameters(config) + padding) == 4_606_249_728 + padding
    assert "lm_head" not in params
    moe_tree = params["layers"][1]["moe"]
    assert moe_tree["wg"].shape == (32, 2048, 2048) == moe_tree["wd"].shape
    cache = jax.eval_shape(lambda: family.init_cache(cfg, 64, 2816))
    assert cache.k.shape == cache.v.shape == (3, 64, 1, 2816, 8 * 64)
    assert cache.conv.shape == (10, 64, 2, 2048) and cache.ssm is None
    assert cache.conv.dtype == jnp.bfloat16
    held = config["hbm_bytes_worked_out"]
    assert held["parameters"] == 4_606_249_728
    assert held["state_snapshot"] == 10 * 2 * 2048 * 2 == 81_920
    assert held["keys_and_values_64_slots_at_width_2816"] == (
        cache.k.size + cache.v.size) * 2


def test_published_checkpoint_names_load_into_the_tree(model):
    family, cfg = model
    params = family.init_params(jax.random.key(1), cfg)
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]),
          "model.embedding_norm.weight": np.asarray(params["lnf"]["scale"])}
    for i, lp in enumerate(params["layers"]):
        p = f"model.layers.{cfg.layer_offset + i}"
        sd[p + ".operator_norm.weight"] = np.asarray(lp["ln1"]["scale"])
        sd[p + ".ffn_norm.weight"] = np.asarray(lp["ln2"]["scale"])
        if "attn" in lp:
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "out_proj")):
                sd[f"{p}.self_attn.{theirs}.weight"] = np.asarray(
                    lp["attn"][ours]).T
            sd[p + ".self_attn.q_layernorm.weight"] = np.asarray(
                lp["attn"]["qn"]["scale"])
            sd[p + ".self_attn.k_layernorm.weight"] = np.asarray(
                lp["attn"]["kn"]["scale"])
        else:
            sd[p + ".conv.in_proj.weight"] = np.asarray(lp["conv"]["w_in"]).T
            sd[p + ".conv.out_proj.weight"] = np.asarray(
                lp["conv"]["w_out"]).T
            sd[p + ".conv.conv.weight"] = np.asarray(
                lp["conv"]["conv_w"]).T[:, None, :]
        names = (("wg", "w1"), ("wu", "w3"), ("wd", "w2"))
        if "mlp" in lp:
            for ours, theirs in names:
                sd[f"{p}.feed_forward.{theirs}.weight"] = np.asarray(
                    lp["mlp"][ours]).T
        else:
            sd[p + ".feed_forward.gate.weight"] = np.asarray(
                lp["moe"]["wr"]).T
            sd[p + ".feed_forward.expert_bias"] = np.asarray(lp["moe"]["br"])
            for e in range(cfg.num_experts):
                for ours, theirs in names:
                    sd[f"{p}.feed_forward.experts.{e}.{theirs}.weight"] = (
                        np.asarray(lp["moe"][ours][e]).T)
    loaded = family.params_from_hf(sd, cfg)
    same = jax.tree.map(lambda a, b: bool((a == b).all()), params, loaded)
    assert all(jax.tree.leaves(same))
    assert jax.tree.structure(params) == jax.tree.structure(loaded)


# --------------------------------------- a share of a layer's experts


@pytest.mark.parametrize("shares", [4, 2, 1])
def test_the_shares_of_a_layer_add_up_to_the_whole_layer(config, shares):
    """Every share routes over all 8 experts (the published layer's 32
    parted into four groups of 8 is this at the test's size) and computes
    its own experts' part through `_grouped(first=, among=)`; the parts
    add up to the reference's whole layer."""
    w = weights.of_config(11, config, jnp.float32)
    lw = w.layer(1)
    x = jax.random.normal(jax.random.key(5), (T, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        h, route_w, picked = reference._route(
            x, lw, eps=1e-5, k=2, norm=True, scale=1.0)
        want = reference._some_experts(jnp.zeros_like(x), h, route_w, lw)
    _, cfg = registry.resolve("lfm2-tiny", jnp.float32)
    tree = weights.program_layer(lw)
    hn = rms_norm(x, tree["ln2"]["scale"], 1e-5)[None]
    count = 8 // shares
    total, held_picks = 0.0, 0
    for first in range(0, 8, count):
        part = dataclasses.replace(cfg, experts_held=(first, count))
        mp = dict(tree["moe"], **{k: tree["moe"][k][first:first + count]
                                  for k in ("wg", "wu", "wd")})
        with jax.default_matmul_precision("highest"):
            y, top_i, sizes = lfm2.moe_mlp(hn, mp, part,
                                           jnp.ones((1, T), bool))
        assert sizes.shape == (count,)
        held_picks += int(sizes.sum())
        total = total + y
        assert (_chosen(top_i, 8)[0] == np.asarray(picked)).all()
    assert held_picks == T * 2          # every pick landed on one share
    np.testing.assert_allclose(total[0], want, rtol=2e-4, atol=2e-5)


def test_the_decode_pass_runs_eight_rows_an_expert_in_tiles():
    """What the grouped products are handed at the cell's sizes: 64 lanes
    x 4 picks over 32 experts held whole are 256 rows, every one run (no
    prefix, no `cond`), in tiles of 32: 288; a prefill pass of four rows
    of 32 positions 512 -> 544."""
    assert moe.held_rows(256, 32, 32) == 256
    assert moe.tiled_rows(256, 256 / 32) == 288
    assert moe.tiled_rows(512, 512 / 32) == 544
    assert moe.tiled_rows(128, 128 / 32) == 160


def test_idle_lanes_reach_no_expert_and_count_no_lane_step(model):
    family, cfg = model
    params = family.init_params(jax.random.key(3), cfg)
    ids = jax.random.randint(jax.random.key(4), (4, 1), 0, cfg.vocab_size)
    cache = _ragged(family.init_cache(cfg, 4, 16, dtype=jnp.float32),
                    [3, 0, 5, 0])
    mask = jnp.arange(16)[None, :] <= cache.length[:, None]

    def counts(live):
        aux = family.forward(params, cfg, ids, cache=cache, kv_mask=mask,
                             live=jnp.asarray(live), aux=True)[2]
        return dict(zip(family.counters, np.asarray(aux["counts"])))

    two, idle = counts([True, False, True, False]), counts([False] * 4)
    assert two["moe_picks"] == two["moe_picks_held"] == 2 * 2 * 12
    assert (two["attn_lane_steps"], two["conv_lane_steps"]) == (6, 20)
    assert two["moe_expert_seats"] == 8 * 12
    assert 0 < two["moe_experts_reached"] <= 48
    assert idle["moe_picks"] == idle["moe_experts_reached"] == 0
    assert idle["attn_lane_steps"] == idle["conv_lane_steps"] == 0


# ------------------------------------------------- through the paged engine


def _econf(**kw):
    kw.setdefault("sampling", SamplingParams.reference_defaults(
        max_new_tokens=MAX_NEW, temperature=0.0, top_k=0, top_p=1.0))
    return EngineConfig(model="lfm2-tiny", dtype=jnp.float32,
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
    first round prefills from zeros and every prompt leaves a snapshot a
    step below its end (the stride is ONE step for a state this small),
    the second and third start from those."""
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
    "from_zeros", "from_a_snapshot", "from_a_snapshot_again"])
def test_a_request_admitted_from_a_snapshot_gives_the_cold_stream(
        served, alone, round_):
    eng, rounds = served
    answers, (hit, _, _, _), counts = rounds[round_]
    assert answers == [alone[p] for p in PROMPTS]
    step = 8  # lcm(prefill chunk 8, block 4)
    if round_ == 0:
        assert hit == 0 and not counts.get("state_snapshots_restored")
        assert counts["state_snapshots_taken"] == 3
    else:
        # Every prompt starts a step below its own end: a chunk's end and
        # a block boundary.
        assert counts["state_snapshots_restored"] == 3
        assert hit >= 2 * (len(NOTES) // step * step)
        assert hit % step == 0
        assert counts["prefix_tokens_recomputed_for_state"] < step
    assert set(counts) <= set(metric.ENGINE_LOOP_COUNTERS)
    # A snapshot is the ten conv layers' windows of one sequence, no more.
    one = 10 * 2 * 32 * 4
    assert eng.state_snapshot_bytes == eng.prefix_cache.snapshot_bytes > 0
    assert eng.state_snapshot_bytes % one == 0


@pytest.mark.parametrize("round_", [0, 1, 2])
def test_the_family_counts_its_routing_and_its_lane_steps(served, round_):
    eng, rounds = served
    counts = rounds[round_][2]
    assert eng.family.counters == (
        "moe_picks", "moe_experts_reached", "moe_expert_seats",
        "moe_picks_held", "attn_lane_steps", "conv_lane_steps")
    for name in eng.family.counters:
        assert metric.is_declared(metric.ENGINE_LOOP_COUNTERS[name])
    assert metric.ENGINE_LOOP_COUNTERS["conv_lane_steps"] == (
        "engine_conv_lane_steps")
    # All the experts are held: every pick lands here.
    assert counts["moe_picks_held"] == counts["moe_picks"] > 0
    assert "moe_passes_bounded" not in counts
    # Ten conv layers to three attention layers, on every live token.
    assert counts["conv_lane_steps"] * 3 == counts["attn_lane_steps"] * 10
    assert counts["moe_picks"] == counts["attn_lane_steps"] // 3 * 2 * 12


def test_the_stride_follows_the_states_size(served):
    """A context's first prompt snapshots on a stride of as many steps as
    the tokens whose keys and values weigh what one snapshot weighs, at
    most 8: one step for this family's windows (2,560 B a sequence beside
    384 B of keys and values a token here; 82 KB beside 6 KB at the
    published widths), so a context shorter than 8 steps leaves a snapshot
    too; a prompt that branches from earlier ones snapshots at its branch
    point as before."""
    eng, _ = served
    assert eng._stride_steps == 1
    point = eng._snapshot_point
    assert [point(0, 0, n) for n in (7, 9, 44, 56)] == [0, 8, 40, 48]
    # ... and one that started from a snapshot and branches nowhere past
    # it leaves none (the short stride is for a context's first prompt:
    # applied to every prompt it left 360 a window, a step below each
    # prompt's own end; PERF.md section 6, PR 57).
    assert [point(0, 21, 40), point(8, 30, 40), point(16, 21, 40),
            point(40, 40, 56)] == [16, 24, 0, 0]
    cache = eng.state.cache
    state = cache.conv.nbytes
    token = (cache.k.nbytes + cache.v.nbytes) / cache.k.shape[3]
    assert 0 < state / token / 8 <= 1
    # The published widths: 81,920 B a snapshot, 6,144 B a token, steps of
    # 32 tokens; a state of megabytes keeps the 8 steps it had
    # (`tests/test_nemotron_h.py` pins its points).
    assert math.ceil(81_920 / 6_144 / 32) == 1
    assert min(8, math.ceil(8_536_064 / 1_024 / 32)) == 8


def test_idle_staged_and_restaged_lanes_give_a_fresh_engines_streams(alone):
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


# ---------------------- every state program with `ssm` None and `conv` alone


@pytest.fixture(scope="module")
def conv_only_state(model):
    family, cfg = model
    state = paged._fresh_state(family, cfg, 3, 16)
    return state._replace(cache=state.cache._replace(
        conv=state.cache.conv + 1.0))


def test_the_fresh_state_declares_windows_and_no_ssm(conv_only_state):
    state = conv_only_state
    assert state.cache.ssm is None and state.snap_ssm is None
    assert state.cache.conv.shape == state.snap_conv.shape == (10, 3, 2, 32)
    assert state.snap_at.tolist() == [0, 0, 0]
    assert paged._has_state(state.cache)


def test_staging_resets_a_slots_windows_with_ssm_none(conv_only_state):
    ids = np.zeros((1, 8), np.int32)
    key = jax.random.key_data(jax.random.key(0))
    staged = paged._stage_program(conv_only_state, 1, ids, 5, 0, 0, key, 8)
    assert staged.cache.ssm is None
    assert (staged.cache.conv[:, 1] == 0).all()
    assert (staged.cache.conv[:, 0] == 1).all()
    assert staged.snap_at.tolist() == [0, 8, 0]


def test_a_snapshot_is_restored_over_a_previous_tenant_with_ssm_none(
        conv_only_state):
    snap = StateSnapshot(
        ssm=None, conv=jnp.full_like(conv_only_state.cache.conv[:, :1], 5.0))
    assert snap.nbytes == 10 * 2 * 32 * 4
    restored = paged._restore_state_program(conv_only_state, snap, 1)
    assert restored.cache.ssm is None
    assert (restored.cache.conv[:, 1] == 5).all()
    assert (restored.cache.conv[:, 0] == 1).all()
    assert (restored.cache.conv[:, 2] == 1).all()


def test_a_snapshot_is_exported_with_ssm_none(conv_only_state):
    state = conv_only_state._replace(
        snap_conv=conv_only_state.snap_conv.at[:, 2].set(3.0))
    snap = paged._export_state_program(state, 2)
    assert snap.ssm is None and snap.conv.shape == (10, 1, 2, 32)
    assert (snap.conv == 3).all()


def test_growth_passes_the_windows_through_with_ssm_none(conv_only_state):
    grown = paged._grow_state_program(conv_only_state, 24)
    assert grown.cache.k.shape[3] == 24 and grown.cache.ssm is None
    assert (grown.cache.conv == conv_only_state.cache.conv).all()
    assert grown.snap_conv.shape == conv_only_state.snap_conv.shape


def test_the_prefix_tree_holds_a_snapshot_without_ssm():
    pc = PrefixCache(block_tokens=2, max_blocks=4, max_snapshots=2)
    snap = StateSnapshot(ssm=None, conv=jnp.ones((1, 1, 2, 8), jnp.float32))
    tokens = [1, 2, 3, 4]
    pc.insert(tokens, lambda i: i)
    assert pc.attach_snapshot(tokens, 4, snap)
    assert pc.snapshot_bytes == snap.nbytes == 64
    at, got = pc.deepest_snapshot(pc.lookup(tokens + [0]), 4)
    assert at == 4 and got.ssm is None
    pc.clear()
    assert pc.snapshot_bytes == 0


def test_the_snapshot_rows_are_the_prefills_windows(served):
    """A prefill that reaches its snapshot position copies the slot's
    windows into its snapshot row (`_admission_chunk`, `snap_ssm` None):
    round 0 took one a prompt, and they came back in rounds 1 and 2."""
    eng, rounds = served
    assert eng.state.snap_ssm is None and eng.state.snap_conv is not None
    assert rounds[0][2]["state_snapshots_taken"] == 3
    assert rounds[2][2]["state_snapshots_restored"] == 3


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
                  "conv.in_proj", "conv.taps", "conv.out_proj", "mlp.dense",
                  "moe.route", "moe.experts"):
        assert scope in text, scope


@pytest.mark.parametrize("engine", [PagedEngine, TutoringEngine])
@pytest.mark.parametrize("setting,why", [
    ({"ep": 2}, "requires an MoE family"),
    ({"tp": 2}, "recurrent state"),
    ({"spec_tokens": 2}, "recurrent state")])
def test_engines_refuse_what_a_recurrent_state_does_not_allow(
        engine, setting, why):
    assert registry.LFM2_FAMILY.recurrent_state
    assert registry.LFM2_FAMILY.routed
    assert not registry.LFM2_FAMILY.latent_cache
    with pytest.raises(ValueError, match=why):
        engine(_econf(**setting))


def test_an_int8_cache_is_refused(model):
    family, cfg = model
    with pytest.raises(ValueError, match="kv_quant"):
        family.init_cache(dataclasses.replace(cfg, quant_kv=True), 1, 8)


def test_the_stacks_are_replicated():
    assert partition.RULES_FOR["lfm2_moe"] is partition.LFM2_RULES
    assert [str(spec) for _, spec in partition.LFM2_RULES] == [
        str(partition.P())]


# ------------------------------ the comparison's controls, the roofline


@pytest.fixture(scope="module")
def sides(config):
    """The program's side (bfloat16, one admission's way) and the
    reference's, on two seeded sequences at the rehearsal's shape."""
    config = dict(config, check=dict(config["check"], logit_positions=24))
    family, cfg = registry.resolve("lfm2-tiny", jnp.bfloat16)
    seed = 2147483747
    seqs = check.sequences_of(config, seed)
    params = weights.program_tree(
        weights.of_config(seed, config, jnp.bfloat16))
    want = check.reference_side(config, seed, seqs)
    got = [compare.program(family, cfg, params, s, config["check"])
           for s in seqs]
    return config, seed, seqs, got, want


def test_the_programs_admission_reads_inside_the_rehearsals_limits(sides):
    config, _, _, got, want = sides
    limits = _load("tiny-lfm2.json")["check"]["limits"]
    for g, w in zip(got, want):
        read = compare.readings(g, w)
        assert set(read) == set(limits)
        assert read["idle_rows_state_change"] == 0.0
        for name, value in read.items():
            assert value <= limits[name], (name, value)


@pytest.mark.parametrize("control,number", [
    ("no_router_bias", "routing_disagreement"),
    ("window_zero_at_hit", "first_layer_worst_position_distance"),
    ("fp8_activations", "first_layer_worst_position_distance"),
    ("fp8_window", "conv_window_distance"),
    ("int8_kv", "first_layer_own_input_values_distance"),
    ("int8_weights", "first_layer_own_input_values_distance"),
    ("int8_weights", "first_layer_own_input_keys_distance"),
    ("int8_kv", "own_input_keys_and_values_distance"),
    ("int8_weights", "own_input_experts_distance"),
    ("fp8_activations", "own_input_experts_distance")])
def test_each_control_is_outside_a_limit(sides, control, number):
    config, seed, seqs, _, want = sides
    limits = _load("tiny-lfm2.json")["check"]["limits"]
    ctl = check.reference_side(config, seed, seqs, control)
    worst = max(compare.readings(c, w)[number] for c, w in zip(ctl, want))
    assert worst > limits[number], (control, number, worst)


@pytest.mark.parametrize("fault,number", [
    ("experts", "own_input_experts_distance"),
    ("keys", "own_input_keys_and_values_distance")])
def test_a_fault_of_four_percent_at_depth_is_outside_its_own_input_limit(
        sides, monkeypatch, fault, number):
    """What the own-input numbers are there for: every routed layer's
    grouped products 4% off, or every attention layer's keys and values
    written 4% off, reads outside that number's limit at every depth,
    whatever the other expert of a token does to the numbers taken against
    the reference's own stream."""
    config, seed, seqs, _, want = sides
    family, cfg = registry.resolve("lfm2-tiny", jnp.bfloat16)
    params = weights.program_tree(
        weights.of_config(seed, config, jnp.bfloat16))

    def off(fn, which):
        def wrapped(*args, **kw):
            out = fn(*args, **kw)
            if isinstance(out, tuple):
                return (which(out[0]),) + out[1:]
            return which(out)
        return wrapped

    def scaled(x):
        return (1.04 * x.astype(jnp.float32)).astype(x.dtype)

    if fault == "experts":
        monkeypatch.setattr(lfm2, "grouped_swiglu",
                            off(lfm2.grouped_swiglu, scaled))
    else:
        monkeypatch.setattr(lfm2, "fold_kv", off(lfm2.fold_kv, scaled))
    # Another function: the jitted programs are traced again.
    broken = family._replace(
        forward=lambda *a, **kw: family.forward(*a, **kw))
    read = compare.readings(
        compare.program(broken, cfg, params, seqs[0], config["check"]),
        want[0])
    limit = _load("tiny-lfm2.json")["check"]["limits"][number]
    assert read[number] > 3 * limit, read
    clean = compare.readings(
        compare.program(family, cfg, params, seqs[0], config["check"]),
        want[0])
    assert clean[number] <= limit


def test_an_unknown_control_is_refused(config):
    with pytest.raises(ValueError, match="no control"):
        reference.forward(None, [1], config, control="bf16_everything")
    assert set(reference.CONTROLS) >= {"no_router_bias",
                                       "window_zero_at_hit"}


def test_check_sizes_holds_the_preset_to_the_file(model):
    _, cfg = model
    config = _load("tiny-lfm2.json")
    compare.check_sizes(config, cfg)
    with pytest.raises(ValueError, match="registry preset"):
        compare.check_sizes(dict(config, num_experts_per_tok=3), cfg)
    with pytest.raises(ValueError, match="registry preset"):
        compare.check_sizes(config, dataclasses.replace(
            cfg, layer_offset=0))
    _, cut = registry.resolve("lfm2-8b-a1b-13l", jnp.bfloat16)
    compare.check_sizes(_load("lfm2-8b-a1b.json"), cut)


def test_an_experts_block_is_the_whole_stacks_slice(config):
    w = weights.of_config(3, config, jnp.float32)
    whole = w.layer(2)
    part = w.layer(2, experts=(3, 2))
    assert set(part) == {"feed_forward.experts.w1", "feed_forward.experts.w2",
                         "feed_forward.experts.w3"}
    for name, block in part.items():
        assert (block == whole[name][3:5]).all()
    rest = w.layer(2, experts=False)
    assert set(rest) | set(part) == set(whole)


def test_roofline_counts_by_hand():
    config = _load("lfm2-8b-a1b.json")
    assert roofline.conv_params(config) == 2048 * 6144 + 2048 * 2048 + 6144
    assert roofline.attention_params(config) == (
        2 * 2048 * 2048 + 2 * 2048 * 512 + 128)
    assert roofline.expert_params(config) == 3 * 2048 * 1792 == 11_010_048
    assert roofline.dense_params(config) == 44_040_192
    assert roofline.parameters(config) == 4_606_249_728
    assert roofline.kv_bytes_per_token(config) == 6144
    assert roofline.window_bytes_per_slot(config) == 8192
    trace = {"span_counters": {"engine_scan_iterations": 100,
                               "moe_experts_reached": 38_000}}
    experts = roofline.experts_cost(config, trace, 6000.0, 700.0)
    assert experts["bytes"] == 38_000 * 11_010_048 * 2
    assert experts["ops"] == 2.0 * 6000 * 12 * 4 * 11_010_048
    whole = roofline.cost(config, trace, 6000.0, 700.0)
    assert whole["bytes"] == (
        100 * roofline.trunk_params(config) * 2 + experts["bytes"]
        + 6000 * (700 * 6144 + 2 * 10 * 8192))
    assert roofline.cost(config, {"span_counters": {}}, 1.0, 1.0) is None
    # Without the counter: every expert of every layer, every step.
    guess = roofline.experts_cost(
        config, {"span_counters": {"engine_scan_iterations": 10}}, 640.0, 1.0)
    assert 0.999 * 10 * 12 * 32 < guess["experts_reached"] <= 10 * 12 * 32


# ------------------- `causal_conv` took an argument: its callers' arithmetic


def _causal_conv_as_it_was(xbc, window, mp, live):
    """`models/mamba2.py` `causal_conv` as the parent of PR 57 had it."""
    b, t, _ = xbc.shape
    k1 = window.shape[1]
    xbc = jnp.where(live[..., None], xbc, jnp.zeros((), xbc.dtype))
    seq = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=1)
    w = mp["conv_w"].astype(jnp.float32)
    out = sum(seq[:, j:j + t].astype(jnp.float32) * w[j]
              for j in range(k1 + 1))
    if "conv_b" in mp:
        out = out + mp["conv_b"].astype(jnp.float32)
    out = jax.nn.silu(out)
    ends = jnp.max(jnp.where(live, jnp.arange(1, t + 1), 0), axis=1)
    kept = jax.vmap(lambda s, e: jax.lax.dynamic_slice_in_dim(s, e, k1, 0))(
        seq, ends)
    return out, kept.astype(window.dtype)


@pytest.mark.parametrize("preset", ["nemotronh-tiny", "kimilinear-tiny"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_causal_convs_callers_are_bit_equal_to_the_parent(monkeypatch,
                                                          preset, dtype):
    """`nemotron_h` and `kimi_linear` call `causal_conv` without the new
    argument: their logits and state planes, a chunk through a cache and a
    decode step after it, are bit-equal to what the function as it was
    gives them."""
    from distributed_lms_raft_llm_tpu.models import kda

    family, cfg = registry.resolve(preset, dtype)
    params = family.init_params(jax.random.key(2), cfg)
    ids = jax.random.randint(jax.random.key(3), (2, 9), 0, cfg.vocab_size)
    live = jnp.asarray([[True] * 9, [True] * 6 + [False] * 3])

    def run():
        cache = family.init_cache(cfg, 2, 16, dtype=dtype)
        logits, cache = family.forward(params, cfg, ids[:, :8], cache=cache,
                                       live=live[:, :8])[:2]
        cache = _ragged(cache, [8, 6])
        mask = jnp.arange(16)[None, :] <= cache.length[:, None]
        step, cache = family.forward(params, cfg, ids[:, 8:], cache=cache,
                                     kv_mask=mask)[:2]
        return logits, step, cache.ssm, cache.conv

    now = run()
    monkeypatch.setattr(mamba2, "causal_conv", _causal_conv_as_it_was)
    monkeypatch.setattr(kda, "causal_conv", _causal_conv_as_it_was)
    for a, b in zip(now, run()):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_causal_conv_without_an_activation_is_the_three_taps():
    xbc = jax.random.normal(jax.random.key(0), (2, 6, 8))
    window = jax.random.normal(jax.random.key(1), (2, 2, 8))
    mp = {"conv_w": jax.random.normal(jax.random.key(2), (3, 8))}
    live = jnp.ones((2, 6), bool)
    plain, kept = mamba2.causal_conv(xbc, window, mp, live, act=None)
    active, kept_too = mamba2.causal_conv(xbc, window, mp, live)
    assert (jax.nn.silu(plain) == active).all() and (kept == kept_too).all()
    seq = np.concatenate([window, xbc], axis=1)
    w = np.asarray(mp["conv_w"])
    np.testing.assert_allclose(
        plain[:, 3], seq[:, 3] * w[0] + seq[:, 4] * w[1] + seq[:, 5] * w[2],
        rtol=1e-6)


# ------------------------------------------------ the benchmark's entries


def test_the_benchmark_names_the_configuration_the_cell_and_its_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = "lfm2-8b-a1b.notes-hall"
    conf = [c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b"][0]
    assert conf["reduced"] == ["num_hidden_layers", "layer_types",
                               "num_dense_layers"]
    work = [w for w in bench["workloads"] if w["name"] == cell][0]
    assert (work["config"], work["traffic"], work["chips"]) == (
        "lfm2-8b-a1b", "notes-hall", 1)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("shortconv_step_dev_us_per_tok",
                 "moe_rows_per_reached_expert", "conv_lane_steps_share"):
        assert by_name[name]["workloads"] == [cell]
    for name in ("moe_experts_reached_share", "moe_experts_dev_us_per_tok",
                 "moe_experts_roofline", "prefix_recomputed_for_state_share"):
        assert cell in by_name[name]["workloads"]  # by name, not by place
    doc = _load("lfm2-8b-a1b.json")
    assert doc["registry_model"] == "lfm2-8b-a1b-13l"
    assert doc["serving"]["slots"] == 64
    assert doc["serving"]["sampling"]["max_new_tokens"] == 256
