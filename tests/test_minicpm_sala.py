"""OpenBMB's minicpm_sala family (models/minicpm_sala.py, ops/sparse.py,
ops/lightning.py; MiniCPM-SALA) at `sala-tiny`, on the CPU in float32.

The program's forward is held to the benchmark's plain reference
(`benchmarks/families/minicpm_sala/reference.py`: the selection per query
with the attention expanded, the Lightning recurrence token by token, no
pooled plane, no cache, which imports nothing of the program) on seeded
weights: whole-sequence logits on both sides of `dense_len`, then one
admission's way through the cache (cold chunks, a hit's splice with the
pooled entries, a snapshot over a previous tenant, a padded chunk, decode
steps). The selection is pinned by hand on a row of a dozen blocks, the
pooled plane bit for bit across a splice, the Lightning chunk form against
the recurrence, and what must NOT move a state (pad positions, idle rows).
Through the paged engine a request admitted from blocks, pooled entries AND a
state snapshot gives the stream of the same request with the prefix cache
off.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.minicpm_sala import (
    compare,
    reference,
    roofline,
    weights,
)
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    SamplingParams,
    TutoringEngine,
    paged,
)
from distributed_lms_raft_llm_tpu.engine.prefix_cache import StateSnapshot
from distributed_lms_raft_llm_tpu.models import minicpm_sala as sala
from distributed_lms_raft_llm_tpu.models import registry
from distributed_lms_raft_llm_tpu.ops import lightning as lightning_ops
from distributed_lms_raft_llm_tpu.ops import sparse
from distributed_lms_raft_llm_tpu.utils import metrics_registry as metric

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 60
MAX_NEW = 8
NOTES = "a quorum of nodes agrees on each entry of the log. "
PROMPTS = (NOTES + "why?", NOTES + "who leads?", "what is a term?")


def _load(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def config():
    return _load("tiny-sala.json")


@pytest.fixture(scope="module")
def model():
    return registry.resolve("sala-tiny", jnp.float32)


def _drawn(config, seed, t=T):
    w = weights.of_config(seed, config, jnp.float32)
    ids = np.random.default_rng(seed).integers(
        0, config["vocab_size"], t).astype(np.int32)
    return w, weights.program_tree(w), ids


# ------------------------------------------------ against the plain reference


@pytest.mark.parametrize("t", [12, T], ids=["below_dense_len", "past_it"])
@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_forward_matches_the_reference_logits(config, model, seed, t):
    family, cfg = model
    compare.check_sizes(config, cfg)
    w, params, ids = _drawn(config, seed, t)
    want = reference.forward(w, ids, dict(config, check=dict(
        config["check"], logit_positions=t)))
    logits, _, aux = family.forward(params, cfg, jnp.asarray(ids)[None],
                                    aux=True)
    np.testing.assert_allclose(logits[0], want[0], atol=2e-5)
    chose = np.asarray(aux["selection"])[:, 0]
    assert chose.shape == np.asarray(want[5]).shape
    np.testing.assert_array_equal(chose, want[5])
    # Past dense_len a query reads topk blocks, fewer than lie behind it.
    if t > cfg.dense_len + cfg.topk * cfg.block_size:
        assert chose[:, :, -1].sum(-1).max() == cfg.topk < -(-t // 4)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_one_admission_through_the_cache_matches_the_reference(
        config, model, seed):
    """`compare.program`: cold chunks, the splice of keys, values and pooled
    entries, the snapshot over a previous tenant, a padded chunk and the
    decode steps, against the reference's whole forward."""
    family, cfg = model
    shape = dict(config["check"], prompt_tokens=44, decode_tokens=8,
                 bucket=48, width=64, restore_at=32)
    w, params, ids = _drawn(config, seed, 52)
    want = reference.forward(w, ids, dict(config, check=shape))
    got = compare.program(family, cfg, params, ids, shape)
    read = compare.readings(got, want)
    assert read.pop("idle_rows_state_change") == 0.0
    assert read.pop("selection_disagreement") == 0.0
    assert max(read.values()) < 1e-5, read


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_each_control_reads_outside_the_sound_path(config, control):
    """Every control moves at least one of the numbers compared by more than
    the float32 program's distance from the reference."""
    shape = dict(config["check"], logit_positions=16)
    w, _, ids = _drawn(config, 5, 52)
    cfg = dict(config, check=shape)
    want = reference.forward(w, ids, cfg)
    read = compare.readings(reference.forward(w, ids, cfg, control=control),
                            want)
    moved = {"int8_weights": "logits_distance",
             "bf16_state": "first_lightning_state_distance",
             "int8_kv": "kv_cache_distance",
             "dense_past_dense_len": "logits_distance",
             "window_not_taken": "selection_disagreement"}[control]
    assert read[moved] > 1e-4, read
    assert read["idle_rows_state_change"] == 0.0


def test_the_mup_scalars_keep_the_published_depth_at_a_cut(model):
    _, cfg = model
    assert cfg.num_layers == 8 and cfg.published_layers == 32
    assert sala.depth_scale(cfg) == pytest.approx(1.4 / 32 ** 0.5)
    whole = sala.MiniCPMSalaConfig.minicpm_sala()
    cut = sala.MiniCPMSalaConfig.minicpm_sala_8l()
    assert sala.depth_scale(whole) == sala.depth_scale(cut)
    assert cut.mixer_types == whole.mixer_types[17:25]
    assert [cut.is_sparse(i) for i in range(8)] == [
        True, False, False, False, False, True, False, False]
    # A cut layer's decay is its published layer's.
    np.testing.assert_array_equal(sala.log_decay(cut, 1),
                                  sala.log_decay(whole, 18))
    np.testing.assert_allclose(
        sala.log_decay(whole, 18),
        reference.log_decay(32, 18, 32), rtol=1e-6)
    assert whole.sparse_layers == 8 and cut.sparse_layers == 2


# ----------------------------------------------------------- the selection


def test_the_selection_by_hand_on_a_row_of_a_dozen_blocks():
    """12 blocks of 4 keys, a query at position 45 (in block 11): block 0
    and the blocks of the last 8 keys (9 = keys 38..39, 10, 11) are taken
    whatever they score; of the others the best two fill a set of six, a tie
    going to the earlier block; a block past the query is never chosen
    before one behind it."""
    scores = jnp.asarray([[0.0, 0.3, 0.9, 0.3, 0.1, 0.2, 0.3, 0.0, 0.1, 0.0,
                           0.0, 0.0]])
    pos = jnp.asarray([45])
    idx = sparse.choose(scores, pos, block=4, topk=6, init_blocks=1, window=8)
    assert sorted(np.asarray(idx)[0]) == [0, 1, 2, 9, 10, 11]
    # Three blocks tie at 0.3: the earliest wins the last seat.
    idx = sparse.choose(scores, pos, block=4, topk=7, init_blocks=1, window=8)
    assert sorted(np.asarray(idx)[0]) == [0, 1, 2, 3, 9, 10, 11]
    # A query in block 2 has three blocks behind it: the list is filled
    # with blocks past it, last.
    idx = np.asarray(sparse.choose(scores, jnp.asarray([9]), block=4, topk=6,
                                   init_blocks=1, window=8))[0]
    assert sorted(idx[:3]) == [0, 1, 2] and min(idx[3:]) > 2


def test_a_blocks_score_is_the_largest_of_the_pooled_keys_over_it():
    """The pooled key c_j lies over keys [2 j, 2 j + 4): block m (4 keys) is
    overlapped by c_{2m-1}, c_{2m} and c_{2m+1}."""
    probs = jnp.asarray([[0.1, 0.0, 0.5, 0.2, 0.0, 0.0, 0.7, 0.0, 0.0]])
    got = sparse.block_scores(probs, 2, 4)
    np.testing.assert_allclose(got, [[0.1, 0.5, 0.2, 0.7]])
    vis = np.asarray(sparse.visible(8, 2, jnp.asarray([2, 3, 8, 9])))
    # c_j is read once its last key, 2 (j + 2) - 1, is behind the query.
    assert vis.sum(-1).tolist() == [0, 1, 3, 4] and vis[1, 0]


def test_a_dense_lane_and_a_sparse_lane_share_one_step(config, model):
    """Two rows of one cache, one at position 10 (below dense_len 16: every
    block behind it is the dense answer) and one at 50: one decode step
    serves both, each as its own whole forward does."""
    family, cfg = model
    _, params, ids = _drawn(config, 9, 51)
    cache = family.init_cache(cfg, 2, 64, dtype=jnp.float32)
    lengths = [10, 50]
    for row, n in enumerate(lengths):
        _, cache = family.forward(
            params, cfg, jnp.asarray(ids[:n])[None],
            cache=cache._replace(length=jnp.zeros((1,), jnp.int32)),
            rows=jnp.asarray([row]))
    cache = cache._replace(length=jnp.asarray(lengths, jnp.int32))
    toks = jnp.asarray([[ids[10]], [ids[50]]])
    kv_mask = jnp.arange(64)[None, :] <= cache.length[:, None]
    logits, _, aux = family.forward(params, cfg, toks, cache=cache,
                                    kv_mask=kv_mask, aux=True)
    for row, n in enumerate(lengths):
        whole, _ = family.forward(params, cfg, jnp.asarray(ids[:n + 1])[None])
        np.testing.assert_allclose(logits[row, 0], whole[0, -1], atol=2e-5)
    # COUNTERS: two sparse layers; one lane past dense_len, which attends 6
    # blocks of 4 of which its own holds 3 keys; contexts of 11 and 51.
    assert aux["counts"].tolist() == [4, 2, 2 * (11 + 23), 2 * (11 + 51)]
    assert family.counters == sala.COUNTERS
    for name in sala.COUNTERS:
        assert metric.is_declared(metric.ENGINE_LOOP_COUNTERS[name])


# ------------------------------------------------------- the pooled plane


def test_the_pooled_plane_across_a_splice_is_the_cold_one(config, model):
    """A row prefilled cold in chunks of 8, and a row that took the first 32
    positions' keys, values and pooled entries from it as a hit does and
    prefilled the rest: the same plane, bit for bit, and the plane a whole
    forward computes from the keys."""
    family, cfg = model
    _, params, ids = _drawn(config, 13, 48)
    cache = family.init_cache(cfg, 3, 64, dtype=jnp.float32)
    cache = cache._replace(pool=cache.pool + 5.0)   # a previous tenant's

    def prefill(cache, row, first, last):
        for c in range(first, last, 8):
            _, cache = family.forward(
                params, cfg, jnp.asarray(ids[c:c + 8])[None],
                cache=cache._replace(length=jnp.asarray([c], jnp.int32)),
                rows=jnp.asarray([row]))
        return cache

    cache = prefill(cache, 0, 0, 32)
    s = cfg.kernel_stride
    cache = cache._replace(
        ssm=cache.ssm.at[:, 2].set(cache.ssm[:, 0]),
        k=cache.k.at[:, 2, :, :32].set(cache.k[:, 0, :, :32]),
        v=cache.v.at[:, 2, :, :32].set(cache.v[:, 0, :, :32]),
        pool=cache.pool.at[:, 2, :, :32 // s].set(cache.pool[:, 0, :, :32 // s]))
    cache = prefill(prefill(cache, 0, 32, 48), 2, 32, 48)
    entries = 48 // s
    np.testing.assert_array_equal(cache.pool[:, 2, :, :entries],
                                  cache.pool[:, 0, :, :entries])
    np.testing.assert_array_equal(
        cache.pool[0, 0, :, :entries],
        sala.pooled_keys(cache.k[0, :1, :, :48], s)[0])
    # The engine's block programs carry an entry with its positions.
    state = paged._fresh_state(family, cfg, 3, 64)
    state = state._replace(cache=cache._replace(
        length=jnp.zeros((3,), jnp.int32)))
    block = paged._export_block_program(state.cache, 16, 0, block=16,
                                        pool_stride=s)
    assert block.pool.shape == (2, 1, 2, 16 // s, cfg.head_dim)
    np.testing.assert_array_equal(block.pool[:, 0],
                                  cache.pool[:, 0, :, 8:16])
    spliced = paged._stage_block_program(state, block, 1, 16)
    np.testing.assert_array_equal(spliced.cache.pool[:, 1, :, 8:16],
                                  cache.pool[:, 0, :, 8:16])
    np.testing.assert_array_equal(spliced.cache.pool[:, 1, :, :8],
                                  cache.pool[:, 1, :, :8])
    grown = paged._grow_state_program(spliced, 128, pool_stride=s)
    assert grown.cache.k.shape[3] == 128
    assert grown.cache.pool.shape[3] == sparse.pool_len(128, s)
    assert sparse.pool_len(33536, 16) == 2176 and sparse.pool_len(768, 16) == 128


# ----------------------------------------------------------- the Lightning


@pytest.mark.parametrize("t", [1, 8, 32, 45])
def test_the_chunk_form_is_the_recurrence(t):
    """`_chunk_scan` over t positions from a state that is not zero, with
    positions that are not live, against the token-by-token recurrence."""
    rng = np.random.default_rng(t)
    b, h, kd = 2, 4, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, kd)), jnp.float32)
               for _ in range(3))
    live = jnp.asarray(rng.random((b, t)) < 0.8)
    g = jnp.where(live[..., None], -jnp.asarray(rng.random(h), jnp.float32),
                  0.0)
    k = jnp.where(live[..., None, None], k, 0.0)
    state = jnp.asarray(rng.normal(size=(b, h, kd, kd)), jnp.float32)
    o, out = sala._chunk_scan(q, k, v, g, state)
    want = []
    for i in range(t):
        state = (jnp.exp(g[:, i])[..., None, None] * state
                 + k[:, i, :, :, None] * v[:, i, :, None, :])
        want.append(jnp.sum(state * q[:, i, :, :, None], axis=-2))
    np.testing.assert_allclose(o, jnp.stack(want, axis=1), atol=2e-5)
    np.testing.assert_allclose(out, state, atol=2e-5)


@pytest.mark.parametrize("heads", [4, 16])
def test_the_step_kernel_computes_the_state_update(heads):
    """`lightning_step` (interpreted) against the same update in
    `jax.numpy`, in place in a stacked plane; a lane with decay 1 and k 0
    keeps its state."""
    rng = np.random.default_rng(heads)
    s, kd = 3, 8
    plane = jnp.asarray(rng.normal(size=(2, s, heads, kd, kd)), jnp.float32)
    q, k, v = (jnp.asarray(rng.normal(size=(s, heads, kd)), jnp.float32)
               for _ in range(3))
    decay = jnp.asarray(rng.random((s, heads)), jnp.float32).at[1].set(1.0)
    k = k.at[1].set(0.0)
    want_plane, want_o = lightning_ops.lightning_step_reference(
        plane, 1, q, k, v, decay)
    got_plane, got_o = lightning_ops.lightning_step(plane, 1, q, k, v, decay,
                                                    interpret=True)
    np.testing.assert_allclose(got_plane, want_plane, atol=1e-6)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_array_equal(got_plane[0], plane[0])
    np.testing.assert_array_equal(got_plane[1, 1], plane[1, 1])


def test_the_select_kernel_sums_the_heads_softmaxes():
    """`sparse_select` (interpreted) against the general form's scores."""
    rng = np.random.default_rng(2)
    b, hkv, g, d, n = 2, 2, 4, 8, 128
    pool = jnp.asarray(rng.normal(size=(2, b, hkv, n, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, hkv, g, d)), jnp.float32)
    pos = jnp.asarray([3, 77], jnp.int32)
    got = sparse.sparse_select(pool, 1, q, pos, scale=d ** -0.5, stride=2,
                               interpret=True)
    c = 0.5 * (pool[1, :, :, :-1] + pool[1, :, :, 1:])        # c_j
    s = jnp.einsum("bhgd,bhnd->bhgn", q, c) * d ** -0.5
    vis = sparse.visible(n, 2, pos)[:, None, None, :-1]
    want = jnp.sum(jnp.where(vis, jax.nn.softmax(
        jnp.where(vis, s, -jnp.inf), axis=-1), 0.0), axis=2)
    np.testing.assert_allclose(got[..., :-1], want, atol=1e-5)
    assert float(got[0].sum()) == pytest.approx(hkv * g, rel=1e-5)


def test_the_append_kernel_writes_a_row_and_its_groups_entry():
    """`sparse_append` (interpreted) against the general form's write."""
    rng = np.random.default_rng(3)
    la, b, hkv, w, d, s = 2, 3, 2, 64, 8, 2
    k, v = (jnp.asarray(rng.normal(size=(la, b, hkv, w, d)), jnp.float32)
            for _ in range(2))
    pool = jnp.asarray(rng.normal(size=(la, b, hkv, 128, d)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(b, hkv, 1, d)), jnp.float32)
                    for _ in range(2))
    pos = jnp.asarray([0, 37, 63], jnp.int32)
    got = sparse.sparse_append(k, v, pool, 1, k_new, v_new, pos, stride=s,
                               interpret=True)
    want = sala._write_general(k, v, pool, 1, None, pos, k_new, v_new, s)
    for a, e, was in zip(got, want, (k, v, pool)):
        np.testing.assert_allclose(a, e, atol=1e-6)
        np.testing.assert_array_equal(a[0], was[0])
    np.testing.assert_array_equal(got[0][1, 1, :, 37], k_new[1, :, 0])
    np.testing.assert_allclose(got[2][1, 1, :, 18],
                               got[0][1, 1, :, 36:38].mean(axis=1), atol=1e-6)


def test_pad_positions_and_idle_rows_leave_the_state_bit_equal(config,
                                                               model):
    family, cfg = model
    _, params, ids = _drawn(config, 17, 16)
    cache = family.init_cache(cfg, 3, 32, dtype=jnp.float32)
    cache = cache._replace(ssm=cache.ssm + 1.5,
                           length=jnp.zeros((3,), jnp.int32))
    # A chunk whose last three positions are the pad tail.
    live = jnp.arange(8) < 5
    _, padded = family.forward(
        params, cfg, jnp.asarray(ids[:8])[None],
        cache=cache._replace(length=jnp.zeros((1,), jnp.int32)),
        rows=jnp.asarray([1]), live=live[None])
    _, short = family.forward(
        params, cfg, jnp.asarray(ids[:5])[None],
        cache=cache._replace(length=jnp.zeros((1,), jnp.int32)),
        rows=jnp.asarray([1]))
    # The pad tail adds exact zeros to the sums of a longer chunk.
    np.testing.assert_allclose(padded.ssm, short.ssm, atol=2e-5)
    np.testing.assert_array_equal(padded.ssm[:, 2], cache.ssm[:, 2])
    np.testing.assert_array_equal(padded.ssm[:, 0], cache.ssm[:, 0])
    assert (np.asarray(padded.ssm[:, 1]) != np.asarray(cache.ssm[:, 1])).any()
    # A decode step in which row 1 alone is live.
    served = jnp.asarray([False, True, False])
    stepped = padded._replace(length=jnp.asarray([0, 5, 0], jnp.int32))
    kv_mask = jnp.arange(32)[None, :] <= stepped.length[:, None]
    _, after, _ = family.forward(
        params, cfg, jnp.asarray(ids[5:8])[:, None], cache=stepped,
        kv_mask=kv_mask, live=served, aux=True)
    np.testing.assert_array_equal(after.ssm[:, 0], padded.ssm[:, 0])
    np.testing.assert_array_equal(after.ssm[:, 2], padded.ssm[:, 2])
    assert (np.asarray(after.ssm[:, 1]) != np.asarray(padded.ssm[:, 1])).any()


def test_a_snapshot_is_restored_over_a_previous_tenant(model):
    """`_stage_program` zeroes the slot's state rows (no `conv` plane to
    zero), `_restore_state_program` puts a snapshot there,
    `_export_state_program` cuts one out of the snapshot rows."""
    family, cfg = model
    state = paged._fresh_state(family, cfg, 3, 32)
    assert state.cache.conv is None and state.snap_conv is None
    assert state.cache.ssm.shape == (6, 3, 4, 8, 8)
    state = state._replace(cache=state.cache._replace(
        ssm=state.cache.ssm + 1.0), snap_ssm=state.snap_ssm + 3.0)
    key = jax.random.key_data(jax.random.key(0))
    staged = paged._stage_program(state, 1, np.zeros((1, 32), np.int32), 5, 0,
                                  0, key, 8)
    assert (staged.cache.ssm[:, 1] == 0).all()
    assert (staged.cache.ssm[:, 0] == 1).all()
    snap = StateSnapshot(ssm=jnp.full_like(state.cache.ssm[:, :1], 7.0),
                         conv=None)
    restored = paged._restore_state_program(staged, snap, 1)
    assert (restored.cache.ssm[:, 1] == 7).all()
    assert (restored.cache.ssm[:, 2] == 1).all()
    out = paged._export_state_program(restored, 2)
    assert out.conv is None and (out.ssm == 3).all()
    assert out.nbytes == 6 * 4 * 8 * 8 * 4


# ------------------------------------------------- through the paged engine


def _econf(**kw):
    kw.setdefault("sampling", SamplingParams.reference_defaults(
        max_new_tokens=MAX_NEW, temperature=0.0, top_k=0, top_p=1.0))
    return EngineConfig(model="sala-tiny", dtype=jnp.float32,
                        length_buckets=(32, 56), seed=4, **kw)


def _engine(prefix_cache=True, slots=4):
    return PagedEngine(_econf(), slots=slots, chunk=2, megastep=2,
                       megastep_max=4, prefix_cache=prefix_cache,
                       prefix_cache_blocks=64, prefix_block_tokens=4,
                       prefill_chunk_tokens=8)


@pytest.fixture(scope="module")
def alone():
    """Every prompt's greedy answer from an engine that serves it alone,
    without a prefix cache."""
    eng = _engine(prefix_cache=False)
    out = {}
    for prompt in PROMPTS:
        rid = eng.submit(prompt)
        out[prompt] = eng.drain()[rid]
    return out


@pytest.fixture(scope="module")
def served():
    """One engine with a prefix cache serves the prompts three times: from
    zeros, from blocks with the state recomputed, from blocks AND a
    snapshot."""
    eng = _engine()
    rounds = []
    for _ in range(3):
        rids = [eng.submit(p) for p in PROMPTS]
        out = eng.drain()
        rounds.append(([out[r] for r in rids], eng.pop_prefix_stats(),
                       eng.pop_loop_stats()[0]))
    return eng, rounds


def test_the_bucketed_generator_is_refused():
    """`TutoringEngine` (the tests' reference generator: it serves nothing)
    left-pads its prompts and grows a cache by padding its keys: a sparse
    layer chooses blocks by a key's slot, which is its position in a
    right-padded row alone, and the pooled plane is not grown."""
    with pytest.raises(ValueError, match="pooled plane"):
        TutoringEngine(_econf()).answer_batch([PROMPTS[0]])


@pytest.mark.parametrize("round_", [0, 1, 2], ids=[
    "from_zeros", "recomputed_for_state", "from_blocks_and_a_snapshot"])
def test_a_prefix_hit_gives_the_cold_stream(served, alone, round_):
    eng, rounds = served
    answers, (hit, _, _, _), counts = rounds[round_]
    assert answers == [alone[p] for p in PROMPTS]
    if round_ == 0:
        assert hit == 0 and not counts["state_snapshots_restored"]
    if round_ == 2:
        assert counts["state_snapshots_restored"] >= 2
        assert hit >= 2 * (len(NOTES) // 8 * 8)
    assert set(counts) <= set(metric.ENGINE_LOOP_COUNTERS)
    # The notes' prompts decode past dense_len 16, "what is a term?" below.
    assert 0 < counts["sparse_lane_steps"] < counts["attn_lane_steps"]
    assert (0 < counts["sparse_keys_attended"]
            < counts["sparse_keys_in_context"])
    one = eng.state.cache.ssm[:, :1].nbytes
    assert eng.state_snapshot_bytes % one == 0 and eng.state_snapshot_bytes


def test_warm_up_compiles_the_programs_that_are_there():
    from distributed_lms_raft_llm_tpu.utils.guards import (
        compile_count_guard, expected_from_inventory)

    eng = _engine()
    eng.warmup()
    expectation = expected_from_inventory(eng)
    assert expectation.mismatches() == {}
    with compile_count_guard(expectation):
        rids = [eng.submit(p) for p in PROMPTS]
        assert len(eng.drain()) == len(rids)


def test_the_engine_bounds_its_snapshots_and_checks_the_block():
    eng = _engine(slots=2)
    assert eng.prefix_cache.max_snapshots == 4      # two a slot
    with pytest.raises(ValueError, match="pools a key every"):
        PagedEngine(_econf(), slots=2, prefix_cache=True,
                    prefix_block_tokens=3)
    _, cfg = registry.resolve("sala-tiny", jnp.float32)
    with pytest.raises(ValueError, match="whole blocks"):
        sala.init_cache(cfg, 1, 30)
    with pytest.raises(ValueError, match="kv_quant"):
        sala.init_cache(dataclasses.replace(cfg, quant_kv=True), 1, 32)


def test_a_snapshot_at_the_published_sizes_is_12_6_megabytes():
    family, cfg = registry.resolve("minicpm-sala-8l", jnp.bfloat16)
    state = jax.eval_shape(lambda: paged._fresh_state(family, cfg, 48, 768))
    assert state.cache.k.shape == (2, 48, 2, 768, 128)
    assert state.cache.pool.shape == (2, 48, 2, 128, 128)
    assert state.cache.ssm.shape == (6, 48, 32, 128, 128)
    assert state.cache.conv is None
    hbm = _load("minicpm-sala.json")["hbm_bytes_worked_out"]
    assert 6 * 32 * 128 * 128 * 4 == 12_582_912 == hbm["state_snapshot"]


# ------------------------------------------------------------ the roofline


def test_roofline_counts_by_hand():
    config = _load("minicpm-sala.json")
    hbm = config["hbm_bytes_worked_out"]
    d, ff, v = 4096, 16384, 73448
    sparse_mixer = 3 * d * 4096 + 2 * d * 256 + 2 * 128
    lightning_mixer = 5 * d * 4096 + 3 * 128
    assert roofline.sparse_mixer_params(config) == sparse_mixer == hbm[
        "sparse_mixer_params"]
    assert roofline.lightning_mixer_params(config) == lightning_mixer
    trunk = (2 * sparse_mixer + 6 * lightning_mixer + 8 * (3 * d * ff + 2 * d)
             + d + v * d)
    assert roofline.trunk_params(config) == trunk
    assert roofline.parameters(config) == trunk + v * d == hbm["parameters"]
    assert hbm["weights_bfloat16"] == 2 * hbm["parameters"] == 5_641_090_560
    # 500 steps of 40 live lanes; the program counted 36 lanes past
    # dense_len (4,064.5 keys a lane and layer on average) and 4 at 500.
    steps, lanes = 500, 40.0
    sel = steps * 36 * 2
    keys = sel * (64 * 64 - 31.5) + steps * 4 * 2 * 500
    context = steps * 36 * 2 * 33000 + steps * 4 * 2 * 500
    trace = {"span_counters": {
        "engine_scan_iterations": steps, "engine_sparse_lane_steps": sel,
        "engine_sparse_keys_attended": keys,
        "engine_sparse_keys_in_context": context}}
    got = roofline.sparse_decode_cost(config, trace, steps * lanes, 29750.0)
    scored = steps * 36 * 2 * 33000 / 16
    assert got["keys_attended"] == keys
    assert got["pooled_keys_scored"] == pytest.approx(scored)
    assert got["bytes"] == pytest.approx(keys * 1024 + scored * 512)
    assert got["ops"] == pytest.approx(2 * 32 * 128 * (2 * keys + scored))
    state = roofline.lightning_step_cost(config, trace, steps * lanes, 0.0)
    assert state["bytes"] == steps * lanes * 2 * 6 * 2_097_152
    assert state["bytes_read"] == steps * 48 * 2 * 6 * 2_097_152
    whole = roofline.cost(config, trace, steps * lanes, 29750.0)
    assert whole["bytes"] == pytest.approx(
        steps * trunk * 2 + got["bytes"] + state["bytes"])
    assert roofline.cost(config, {"span_counters": {}}, 1.0, 1.0) is None
    # Without the program's counters: every lane at the mean context.
    bare = {"span_counters": {"engine_scan_iterations": steps}}
    got = roofline.sparse_decode_cost(config, bare, steps * lanes, 500.0)
    assert got["keys_attended"] == steps * lanes * 2 * 500.0
    assert got["pooled_keys_scored"] == 0.0
