"""Sampling ops vs HF transformers LogitsProcessors (golden parity)."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_lms_raft_llm_tpu.engine import sampling

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture()
def logits():
    rng = np.random.default_rng(0)
    return rng.normal(size=(3, 64)).astype(np.float32) * 3


def test_top_k_matches_hf(logits):
    ours = np.asarray(sampling.apply_top_k(jnp.asarray(logits), 10))
    proc = transformers.TopKLogitsWarper(top_k=10, filter_value=sampling.NEG_INF)
    ref = proc(None, torch.tensor(logits)).numpy()
    kept_ours = ours > sampling.NEG_INF / 2
    kept_ref = ref > sampling.NEG_INF / 2
    np.testing.assert_array_equal(kept_ours, kept_ref)
    np.testing.assert_allclose(np.where(kept_ours, ours, 0), np.where(kept_ref, ref, 0), rtol=1e-6)


def test_top_p_matches_hf(logits):
    ours = np.asarray(sampling.apply_top_p(jnp.asarray(logits), 0.9))
    proc = transformers.TopPLogitsWarper(top_p=0.9, filter_value=sampling.NEG_INF)
    ref = proc(None, torch.tensor(logits)).numpy()
    np.testing.assert_array_equal(ours > sampling.NEG_INF / 2, ref > sampling.NEG_INF / 2)


def test_repetition_penalty_matches_hf(logits):
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 64, size=(3, 12))
    seen = np.zeros((3, 64), bool)
    for b in range(3):
        seen[b, prompt[b]] = True
    ours = np.asarray(
        sampling.apply_repetition_penalty(jnp.asarray(logits), jnp.asarray(seen), 1.2)
    )
    proc = transformers.RepetitionPenaltyLogitsProcessor(penalty=1.2)
    ref = proc(torch.tensor(prompt), torch.tensor(logits)).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-5)


def test_greedy_and_temperature_paths():
    logits = jnp.asarray([[1.0, 5.0, 2.0], [4.0, 0.0, -1.0]])
    seen = jnp.zeros((2, 3), bool)
    greedy = sampling.sample_step(
        jnp.zeros(2, jnp.uint32), logits, seen, sampling.SamplingParams.greedy()
    )
    np.testing.assert_array_equal(np.asarray(greedy), [1, 0])

    import jax

    params = sampling.SamplingParams(temperature=0.7, top_k=2, top_p=0.95)
    toks = sampling.sample_step(jax.random.key(0), logits, seen, params)
    assert toks.shape == (2,)
    # top_k=2 restricts row 0 to {1, 2}, row 1 to {0, 1}.
    assert int(toks[0]) in (1, 2) and int(toks[1]) in (0, 1)


def test_seen_mask_roundtrip():
    ids = jnp.asarray([[3, 5, 3], [1, 0, 2]])
    valid = jnp.asarray([[True, True, True], [True, False, True]])
    mask = sampling.seen_mask_from_ids(ids, valid, 8)
    expect = np.zeros((2, 8), bool)
    expect[0, [3, 5]] = True
    expect[1, [1, 2]] = True  # id 0 in row 1 is padding
    np.testing.assert_array_equal(np.asarray(mask), expect)
    mask2 = sampling.update_seen(mask, jnp.asarray([7, 0]))
    assert bool(mask2[0, 7]) and bool(mask2[1, 0])


def test_approx_top_k_samples_from_plausible_set():
    """approx_top_k=True (serving opt-in, ~0.95 recall) still samples only
    high-logit tokens; exact parity is not promised, membership near the
    top is."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from distributed_lms_raft_llm_tpu.engine.sampling import (
        SamplingParams, sample_step,
    )

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 5000)).astype(np.float32))
    seen = jnp.zeros((4, 5000), bool)
    params = SamplingParams(approx_top_k=True, max_new_tokens=4)
    toks = sample_step(jax.random.key(0), logits, seen, params)
    # Every sample lands within the exact top-2k (k=50 with generous slack
    # for the approximate bins).
    _, exact_idx = jax.lax.top_k(logits, 100)
    for row in range(4):
        assert int(toks[row]) in np.asarray(exact_idx[row]), row


# --------------------------------- the exact top-k in stages (PR 56)

# The six cells' vocabularies, and one that is a multiple of no group size
# (its last group is partly padding at 64, 128 and 256 columns alike).
VOCABULARIES = [50257, 200192, 20480, 65536, 40960, 73448, 33001]
K = 50


def _rows(kind: str, rows: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng(vocab + rows)
    noise = rng.normal(size=(rows, vocab)).astype(np.float32) * 3
    if kind == "float32":
        return noise
    if kind == "bfloat16_rounded":  # ties two or three to a value
        return np.asarray(
            jnp.asarray(noise).astype(jnp.bfloat16).astype(jnp.float32))
    if kind == "integer_rounded":
        # A handful of values: every group's maximum ties with hundreds of
        # others and the k-th value with thousands (-0.0 beside 0.0).
        return np.round(noise / 3)
    assert kind == "planted"
    x = np.zeros((rows, vocab), np.float32)
    # Row 0: every logit equal; the lowest ids win.
    # Row 1: 70 equal maxima at the row's end, in the last, partly padded
    # group and the one before it; the lowest 50 of them win.
    x[1, -70:] = 1.0
    # Row 2: 30 finite logits scattered, the rest -inf like the padding:
    # the 30, then the lowest ids, never a padded column.
    x[2] = -np.inf
    x[2, rng.choice(vocab, 30, replace=False)] = rng.normal(size=30)
    # Row 3 on: k-th value tied across groups far apart.
    for r in range(3, rows):
        x[r] = noise[r]
        x[r, rng.choice(vocab, 200, replace=False)] = 9.0
    return x


@pytest.mark.parametrize("kind", [
    "float32", "bfloat16_rounded", "integer_rounded", "planted"])
@pytest.mark.parametrize("vocab", VOCABULARIES)
def test_grouped_top_k_is_lax_top_k_element_for_element(vocab, kind):
    """Values AND indices, ties included: as one jitted call over eight
    rows (the tiled view), over five (rows of their own), under `jax.vmap`
    a row at a time as the first token is sampled, and through the rule."""
    x = jnp.asarray(_rows(kind, 8, vocab))
    want_vals, want_idx = (np.asarray(a) for a in jax.lax.top_k(x, K))
    assert sampling.group_size(vocab, K) == 128
    forms = {"rule": jax.jit(lambda x: sampling.top_k(x, K))}
    for g in (64, 128, 256):
        two = partial(sampling.grouped_top_k, k=K, g=g)
        forms[f"eight rows, {g}"] = jax.jit(two)
        forms[f"five rows, {g}"] = jax.jit(lambda x, two=two: two(x[:5]))
        forms[f"a row at a time, {g}"] = jax.jit(jax.vmap(two))
    for name, form in forms.items():
        vals, idx = (np.asarray(a) for a in form(x))
        assert idx.dtype == want_idx.dtype and vals.dtype == want_vals.dtype
        np.testing.assert_array_equal(
            vals, want_vals[:len(vals)], err_msg=name)
        np.testing.assert_array_equal(
            idx, want_idx[:len(idx)], err_msg=name)


def test_the_rule_for_the_group_by_the_rows_width():
    """A row the TPU's compiler sorts whole (the CPU rehearsals'
    vocabularies, `k >= V`) takes `lax.top_k`; a long one groups of a tile
    line; the k lines it leaves, 6,400 columns, groups of 16; and every
    width reaches one stage in a few."""
    for width in (50, 64, 512, 1024, 4095):
        assert sampling.group_size(width, K) == 0
    assert [sampling.group_size(w, K) for w in (
        4096, 6400, 12799, 12800, 20480, 200192)] == [8, 16, 16, 128, 128, 128]
    for k in (1, 8, 50, 300, 5000):
        for width in (4096, 6400, 50257, 200192, 1 << 24):
            stages = 0
            while (g := sampling.group_size(width, k)):
                assert -(-width // g) >= k and k * g < width
                width, stages = k * g, stages + 1
            assert stages <= 4
    x = jnp.asarray(_rows("bfloat16_rounded", 3, 512))
    for got, want in zip(sampling.top_k(x, K), jax.lax.top_k(x, K)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    x = jnp.asarray(_rows("integer_rounded", 3, 50257))
    for k in (8, 300):
        for got, want in zip(sampling.top_k(x, k), jax.lax.top_k(x, k)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rows,vocab", [(16, 200192), (48, 73448)])
def test_sample_step_draws_the_parents_tokens(rows, vocab):
    """`sample_step` against its spelling before PR 56 (`lax.top_k` over
    the row, inlined here), one key: the same token in every row."""
    params = sampling.SamplingParams.reference_defaults()
    logits = jnp.asarray(_rows("bfloat16_rounded", rows, vocab))
    seen = jnp.asarray(
        np.random.default_rng(1).random((rows, vocab)) < 0.001)

    def parent(rng, logits, seen):
        logits = sampling.apply_repetition_penalty(
            logits, seen, params.repetition_penalty) / params.temperature
        top_vals, top_idx = jax.lax.top_k(logits, params.top_k)
        probs = jax.nn.softmax(top_vals, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        top_vals = jnp.where(
            (cum - probs) > params.top_p, sampling.NEG_INF, top_vals)
        choice = jax.random.categorical(rng, top_vals, axis=-1)
        return jnp.take_along_axis(
            top_idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)

    for seed in (0, 56):
        key = jax.random.key(seed)
        np.testing.assert_array_equal(
            np.asarray(jax.jit(sampling.sample_step, static_argnums=3)(
                key, logits, seen, params)),
            np.asarray(jax.jit(parent)(key, logits, seen)))
