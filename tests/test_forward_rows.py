"""`forward`'s `rows`: a ragged batch narrower than the cache addresses the
cache rows it names, in place — the contract the paged engine's prefill
chunk stands on (`engine/paged.py` `_admission_chunk`: one staged slot of
the live multi-slot cache, nothing sliced out or spliced back)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lms_raft_llm_tpu.models import registry
from distributed_lms_raft_llm_tpu.models.common import KVCache

ROWS, WIDTH, CHUNK = 4, 24, 6


def _filled(cache: KVCache, key) -> KVCache:
    """Every page of every row holds something of its own, so a read of
    the wrong row or a write beside the right one shows."""
    planes = {}
    for i, name in enumerate(("k", "v", "ks", "vs")):
        plane = getattr(cache, name)
        if plane is None:
            continue
        k = jax.random.fold_in(key, i)
        if plane.dtype == jnp.int8:
            planes[name] = jax.random.randint(
                k, plane.shape, -127, 128, jnp.int32).astype(jnp.int8)
        elif name in ("ks", "vs"):
            planes[name] = jax.random.uniform(
                k, plane.shape, plane.dtype, 0.005, 0.02)
        else:
            planes[name] = jax.random.normal(k, plane.shape, plane.dtype)
    return cache._replace(**planes)


def _planes(cache: KVCache) -> dict:
    return {n: np.asarray(getattr(cache, n)) for n in ("k", "v", "ks", "vs")
            if getattr(cache, n) is not None}


@pytest.mark.parametrize("preset,quant_kv", [
    ("tiny", False), ("tiny", True), ("llama-tiny", False),
    ("afmoe-tiny", False),
], ids=["gpt2", "gpt2-int8-kv", "llama", "afmoe"])
def test_a_chunk_lands_on_its_row_of_a_wider_cache(preset, quant_kv):
    family, cfg = registry.resolve(preset, jnp.float32)
    if quant_kv:
        cfg = dataclasses.replace(cfg, quant_kv=True)
    params = family.init_params(jax.random.key(0), cfg)
    cache = _filled(family.init_cache(cfg, ROWS, WIDTH), jax.random.key(1))
    before = _planes(cache)
    ids = jax.random.randint(jax.random.key(2), (1, CHUNK), 0,
                             cfg.vocab_size)
    forward = jax.jit(lambda ids, cache, **kw: family.forward(
        params, cfg, ids, cache=cache, **kw)[:2])

    # At a cursor inside the row, and at one whose tail overshoots the
    # width: the overshoot is dropped, never clamped into real pages nor
    # carried into the next row.
    for r, cur in ((2, 8), (ROWS - 1, WIDTH - CHUNK // 2), (0, 0)):
        length = jnp.asarray([cur], jnp.int32)
        positions = jnp.minimum(
            cur + jnp.arange(CHUNK, dtype=jnp.int32), WIDTH - 1)[None, :]
        logits, wide = forward(
            ids, cache._replace(length=length), positions=positions,
            rows=jnp.asarray([r], jnp.int32))
        alone = KVCache(**{n: jnp.asarray(p[:, r:r + 1])
                           for n, p in before.items()}, length=length)
        want_logits, want = forward(ids, alone, positions=positions)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(want_logits))
        after, want = _planes(wide), _planes(want)
        assert set(after) == set(before)
        for name, plane in after.items():
            np.testing.assert_array_equal(plane[:, r], want[name][:, 0])
            others = [i for i in range(ROWS) if i != r]
            np.testing.assert_array_equal(plane[:, others],
                                          before[name][:, others])
            written = np.any(plane[:, r] != before[name][:, r])
            assert written, f"{name}: the chunk wrote nothing to row {r}"

    # Left out, `rows` is row i for batch element i: today's ragged batch.
    ids = jax.random.randint(jax.random.key(3), (ROWS, 1), 0, cfg.vocab_size)
    ragged = cache._replace(length=jnp.asarray([3, 9, 0, WIDTH - 1],
                                               jnp.int32))
    logits, got = forward(ids, ragged)
    named_logits, named = forward(ids, ragged,
                                  rows=jnp.arange(ROWS, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(named_logits))
    for name, plane in _planes(got).items():
        np.testing.assert_array_equal(plane, _planes(named)[name])


@pytest.mark.parametrize("preset", ["tiny", "llama-tiny", "afmoe-tiny"])
def test_rows_without_ragged_slots_are_refused(preset):
    """A whole-batch offset writes every row: `rows` there would be read
    by the attention and ignored by the write."""
    family, cfg = registry.resolve(preset, jnp.float32)
    params = family.init_params(jax.random.key(0), cfg)
    ids = jnp.zeros((1, CHUNK), jnp.int32)
    rows = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="ragged"):
        family.forward(params, cfg, ids, rows=rows,
                       cache=family.init_cache(cfg, ROWS, WIDTH))
