"""GPT-2's int8 K/V planes with the heads folded into the feature axis
(`models/common.py` `folds_heads`): the same int8 bytes and scales, a
position's heads side by side in one row, against today's `[L, B, H, T,
Dh]` planes and today's `attend_quant` on them.

"Today's" path is the same code with the fold switched off
(`gpt2.folds_heads` patched to say no): unfolded planes from `init_cache`,
unfolded through `forward`, the per-head products of `attend_quant`.
"""

import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    SamplingParams,
    TutoringEngine,
)
from distributed_lms_raft_llm_tpu.engine import paged
from distributed_lms_raft_llm_tpu.models import common, gpt2, registry
from distributed_lms_raft_llm_tpu.models.common import (
    KVCache,
    attend_quant,
    fold_heads,
    quantize_kv,
    unfold_heads,
)

ROWS, WIDTH = 4, 24
# benchmarks/configs/tiny.json `check.limits`: what the rehearsal holds the
# program's logits to against the float32 reference; the two paths here
# share every int8 byte and differ in the order of an f32 sum.
LIMITS = {"logits_distance": 0.057, "logits_worst_position_distance": 0.064}


def today():
    """The fold switched off for the calls made (and traced) under it."""
    return mock.patch.object(gpt2, "folds_heads", lambda *_: False)


def _tiny():
    family, cfg = registry.resolve("tiny", jnp.float32)
    cfg = dataclasses.replace(cfg, quant_kv=True)
    return family, cfg, family.init_params(jax.random.key(0), cfg)


def _filled(cache: KVCache, cfg, key) -> KVCache:
    """An unfolded cache whose every page holds something of its own."""
    k = jax.random.randint(jax.random.fold_in(key, 0), cache.k.shape,
                           -127, 128, jnp.int32).astype(jnp.int8)
    v = jax.random.randint(jax.random.fold_in(key, 1), cache.v.shape,
                           -127, 128, jnp.int32).astype(jnp.int8)
    ks = jax.random.uniform(jax.random.fold_in(key, 2), cache.ks.shape,
                            jnp.float32, 0.005, 0.02)
    vs = jax.random.uniform(jax.random.fold_in(key, 3), cache.vs.shape,
                            jnp.float32, 0.005, 0.02)
    return cache._replace(k=k, v=v, ks=ks, vs=vs)


def _fold(cache: KVCache, groups: int = 1) -> KVCache:
    return cache._replace(k=fold_heads(cache.k, groups),
                          v=fold_heads(cache.v, groups))


def _same_bytes(folded: KVCache, plain: KVCache, cfg) -> None:
    """The first layer's planes to the byte (nothing of an attention comes
    before them); below it the activations differ in the last place of an
    f32 sum, so a scale may move by an ulp and a value on a rounding edge
    by one step."""
    for name in ("k", "v"):
        got = np.asarray(unfold_heads(getattr(folded, name), cfg.num_heads,
                                      cfg.head_dim))
        want = np.asarray(getattr(plain, name))
        np.testing.assert_array_equal(got[0], want[0])
        off = got.astype(np.int32) - want
        assert np.abs(off).max() <= 1 and np.mean(off != 0) < 1e-3
        # The lanes past the last head stay zero: they are multiplied.
        np.testing.assert_array_equal(
            np.asarray(getattr(folded, name)[
                ..., cfg.num_heads // folded.k.shape[2] * cfg.head_dim:]), 0)
    for name in ("ks", "vs"):
        got, want = np.asarray(getattr(folded, name)), np.asarray(
            getattr(plain, name))
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_allclose(got, want, rtol=1e-5)


def _close(got, want) -> None:
    whole, row = check.distances(
        jnp.asarray(got).reshape(-1, got.shape[-1]),
        jnp.asarray(want, jnp.float32).reshape(-1, want.shape[-1]))
    assert float(whole) < LIMITS["logits_distance"] * 1e-3, float(whole)
    assert float(row) < LIMITS["logits_worst_position_distance"] * 1e-3


def _forward_case(case, family, cfg, params, cache):
    """(logits, cache) of one `forward` call of the named kind on `cache`
    (folded or not: the call is the same)."""
    key = jax.random.key(7)
    kw = {}
    if case == "ragged_decode":  # paged decode: T = 1, unequal lengths
        ids = jax.random.randint(key, (ROWS, 1), 0, cfg.vocab_size)
        lengths = jnp.asarray([3, 9, 0, WIDTH - 1], jnp.int32)
        kw["kv_mask"] = jnp.arange(WIDTH)[None, :] <= lengths[:, None]
    elif case == "prefill_chunk_rows":  # one staged slot of the live cache
        ids = jax.random.randint(key, (1, 6), 0, cfg.vocab_size)
        lengths = jnp.asarray([8], jnp.int32)
        kw["rows"] = jnp.asarray([2], jnp.int32)
    elif case == "speculative_window":  # T = k + 1 at ragged offsets
        ids = jax.random.randint(key, (ROWS, 3), 0, cfg.vocab_size)
        lengths = jnp.asarray([3, 9, 0, WIDTH - 3], jnp.int32)
    else:
        raise AssertionError(case)
    return family.forward(params, cfg, ids,
                          cache=cache._replace(length=lengths), **kw)


def _generate_case():
    """The scalar-length path: the bucketed engine's prefill and decode
    loop (`engine/generate.py`), greedy, both ways."""
    def run():
        eng = TutoringEngine(EngineConfig(
            model="tiny", kv_quant=True, dtype=jnp.float32,
            sampling=SamplingParams.greedy(max_new_tokens=8),
            length_buckets=(16,), batch_buckets=(2,), seed=3000000019 % 2**31,
        ))
        return eng.answer_batch(["what is raft?", "explain paging"])

    got = run()
    with today():  # each engine jits partials of its own: a fresh trace
        assert got == run()


def _block_case(family, cfg, params, plain):
    """A prefix block exported from one slot and staged into another: the
    engine's two block programs on the folded planes, then a decode step
    over the spliced slot, against the same on today's planes."""
    lengths = jnp.asarray([16, 0, 0, 0], jnp.int32)
    ids = jax.random.randint(jax.random.key(9), (ROWS, 1), 0, cfg.vocab_size)

    def through_a_block(cache):
        block = paged._export_block_program(cache, 8, 0, block=8)
        assert block.k.shape[3] == 8 and block.ks.shape == (
            cfg.num_layers, 1, cfg.num_heads, 8)
        state = paged._fresh_state(family, cfg, ROWS, WIDTH)
        state = state._replace(cache=cache._replace(length=lengths))
        state = paged._stage_block_program(state, block, 3, 8)
        spliced = state.cache._replace(
            length=jnp.asarray([16, 0, 0, 16], jnp.int32))
        return block, family.forward(params, cfg, ids, cache=spliced)

    block_f, (logits_f, cache_f) = through_a_block(_fold(plain))
    with today():
        block_p, (logits_p, cache_p) = through_a_block(plain)
    _same_bytes(KVCache(k=block_f.k, v=block_f.v, length=None,
                        ks=block_f.ks, vs=block_f.vs),
                KVCache(k=block_p.k, v=block_p.v, length=None,
                        ks=block_p.ks, vs=block_p.vs), cfg)
    # Slot 3 holds slot 0's positions 8..15 at its own 8..15.
    np.testing.assert_array_equal(np.asarray(cache_f.k[:, 3, :, 8:16]),
                                  np.asarray(_fold(plain).k[:, 0, :, 8:16]))
    return (logits_f, cache_f), (logits_p, cache_p)


@pytest.mark.parametrize("case", [
    "ragged_decode", "prefill_chunk_rows", "speculative_window",
    "scalar_generate", "prefix_block",
])
def test_folded_planes_hold_and_attend_what_todays_planes_do(case):
    if case == "scalar_generate":
        return _generate_case()
    family, cfg, params = _tiny()
    plain = _filled(family.init_cache(cfg, ROWS, WIDTH), cfg,
                    jax.random.key(1))
    assert plain.k.shape == (cfg.num_layers, ROWS, cfg.num_heads, WIDTH,
                             cfg.head_dim)
    served = family.init_cache(cfg, ROWS, WIDTH, groups=1)
    assert served.k.shape == (cfg.num_layers, ROWS, 1, WIDTH, common.LANES)
    assert served.ks.shape == plain.ks.shape
    if case == "prefix_block":
        (logits, after), (want_logits, want) = _block_case(
            family, cfg, params, plain)
    else:
        logits, after = _forward_case(case, family, cfg, params,
                                      _fold(plain))
        # Handed today's planes, `forward` folds them on its way in and
        # hands them back as they came: the benchmark's reference check
        # (benchmarks/families/gpt2/compare.py) reads them by that shape.
        logits_in, after_in = _forward_case(case, family, cfg, params, plain)
        with today():
            want_logits, want = _forward_case(case, family, cfg, params,
                                              plain)
        np.testing.assert_array_equal(np.asarray(logits_in),
                                      np.asarray(logits))
        assert after_in.k.shape == plain.k.shape
        for name in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(fold_heads(getattr(after_in, name), 1)),
                np.asarray(getattr(after, name)))
    assert after.k.shape == served.k.shape
    _same_bytes(after, want, cfg)
    _close(logits, want_logits)


@pytest.mark.parametrize("groups,t", [(1, 1), (2, 1), (3, 1), (6, 1), (1, 5),
                                      (2, 5)])
def test_attend_quant_folded_against_per_head_products(groups, t):
    """`attend_quant` alone, bfloat16 as served: a group's heads in one
    row (G = tp ways) and windows of more than one query."""
    b, h, s, dh = 3, 6, 40, 16
    keys = jax.random.split(jax.random.key(groups * 10 + t), 3)
    q = jax.random.normal(keys[0], (b, h, t, dh)).astype(jnp.bfloat16)
    k, ks = quantize_kv(jax.random.normal(keys[1], (b, h, s, dh)))
    v, vs = quantize_kv(jax.random.normal(keys[2], (b, h, s, dh)))
    frontier = jnp.asarray([5, 17, s - t])[:, None] + jnp.arange(t)[None]
    mask = (jnp.arange(s)[None, None, None, :]
            <= frontier[:, None, :, None])
    want = attend_quant(q, k, ks, v, vs, mask).astype(jnp.float32)
    got = attend_quant(q, fold_heads(k, groups), ks, fold_heads(v, groups),
                       vs, mask).astype(jnp.float32)
    assert got.shape == want.shape
    # bfloat16 results of the same f32 sums in another order.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 4e-3


@pytest.mark.parametrize("seed", [3000000019, 4330000203])
def test_paged_token_streams_equal_on_the_rehearsal_seeds(seed):
    """The paged engine end to end (staged admission, prefix blocks, the
    megastep) on `tiny` with int8 K/V, greedy: the folded planes give the
    token streams today's planes give."""
    def run():
        eng = PagedEngine(
            EngineConfig(
                model="tiny", kv_quant=True, dtype=jnp.float32,
                sampling=SamplingParams.greedy(max_new_tokens=8),
                length_buckets=(8, 16), batch_buckets=(1, 2),
                seed=seed % (2**31 - 1),
            ),
            slots=2, chunk=2, megastep=2, megastep_max=2,
            prefix_cache=True, prefix_cache_blocks=32, prefix_block_tokens=4,
            prefill_chunk_tokens=4,
        )
        prompts = ["what is raft? tell me", "what is raft? tell you",
                   "explain paging", "k"]
        rids = [eng.submit(p) for p in prompts]
        out = eng.drain()
        return eng.state.cache.k.shape, [out[r] for r in rids]

    shape, got = run()
    assert shape[2:] == (1, shape[3], common.LANES)
    with today():
        shape, want = run()
    assert shape[2] == 4 and shape[4] == 8
    assert got == want


def test_only_an_int8_plane_of_narrow_heads_is_folded():
    family, cfg = registry.resolve("tiny", jnp.float32)
    assert family.init_cache(cfg, 2, 8, groups=1).k.shape == (2, 2, 4, 8, 8)
    wide = dataclasses.replace(cfg, hidden_size=512, quant_kv=True)
    assert wide.head_dim == common.LANES
    assert family.init_cache(wide, 2, 8, groups=1).k.shape == (
        2, 2, 4, 8, 128)
    narrow = dataclasses.replace(cfg, quant_kv=True)
    assert family.init_cache(narrow, 2, 8).k.shape == (2, 2, 4, 8, 8)
    assert family.init_cache(narrow, 2, 8, groups=2).k.shape == (
        2, 2, 2, 8, 128)


@pytest.mark.parametrize("rows", [None, [2, 0]], ids=["every_row", "named_rows"])
def test_write_scales_is_the_scatter_it_replaces(rows):
    """A select over the layer's rows writes what the scatter of columns
    wrote, a slot past the width dropped and every other row untouched."""
    layers, n_rows, h, w, t = 3, 4, 5, 12, 3
    plane = jax.random.uniform(jax.random.key(0), (layers, n_rows, h, w))
    b = n_rows if rows is None else len(rows)
    scales = jax.random.uniform(jax.random.key(1), (b, h, t))
    start = jnp.asarray([0, 4, w - 1, 7][:b], jnp.int32)  # one overshoots
    slots = start[:, None] + jnp.arange(t)[None, :]
    at_rows = jnp.arange(b) if rows is None else jnp.asarray(rows)
    want = plane.at[1, at_rows[:, None], :, slots].set(
        scales.transpose(0, 2, 1), mode="drop")
    got = common.write_scales(
        plane, jnp.asarray(1), None if rows is None else at_rows, slots,
        scales)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
