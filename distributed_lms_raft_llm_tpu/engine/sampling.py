"""Jit-friendly sampling ops with HF-equivalent semantics.

The reference generates with `temperature=0.7, top_k=50, top_p=0.9,
repetition_penalty=1.2` through HF's processors (reference:
GUI_RAFT_LLM_SourceCode/tutoring_server.py:21-29). These are reimplemented
as pure static-shape JAX ops (sorts + masks, no data-dependent shapes) so
the whole sampling step fuses into the decode program on TPU. Golden parity
with HF's LogitsProcessors is tested in tests/test_sampling.py.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Static sampling configuration (hashable: safe as a jit static arg)."""

    temperature: float = 0.7
    top_k: int = 50
    top_p: float = 0.9
    repetition_penalty: float = 1.2
    max_new_tokens: int = 128
    # TPU-native approximate top-k (jax.lax.approx_max_k, ~0.95 recall of
    # the exact top-50): one partial reduction instead of a full sort.
    # Default False = bit-exact HF semantics; serving can opt in
    # (tutoring_server --approx-topk) since dropping a couple of the
    # lowest-probability nucleus candidates is statistically invisible at
    # temperature 0.7.
    approx_top_k: bool = False

    @classmethod
    def reference_defaults(cls, **kw) -> "SamplingParams":
        """The reference tutoring server's sampling configuration."""
        return cls(**kw)

    @classmethod
    def greedy(cls, **kw) -> "SamplingParams":
        kw.setdefault("temperature", 0.0)
        kw.setdefault("top_k", 0)
        kw.setdefault("top_p", 1.0)
        kw.setdefault("repetition_penalty", 1.0)
        return cls(**kw)


# The exact top-k of a long row in stages (`top_k` below), sized by what
# scripts/probe_topk.py read on the TPU v5e (PERF.md section 6, PR 56).
# A float32 array rests there in tiles of 8 rows by 128 columns, so a group
# of `_GROUP` columns of one row is one line of a tile, and `_TILE_ROWS`
# rows side by side make the view the stages read the logits through,
# [B/8, V/128, 8, 128], the logits' own bytes: the head's fusion writes the
# group maxima beside the logits and nothing as wide as the vocabulary is
# copied (tests/test_chip_compile.py reads the compiler's text). A row
# shorter than `_SORTED` the compiler sorts whole, in a few microseconds;
# from there on `lax.top_k` is a call to its `TopK`, some 70 us and 2 us a
# thousand columns of 16 rows, so the stages go on until a row is that short.
_GROUP = 128
_TILE_ROWS = 8
_SORTED = 4096


def group_size(width: int, k: int) -> int:
    """Columns a group for `top_k` over rows of `width`, 0 for one stage.

    A row the compiler sorts takes one stage. A row at least twice as long
    as the k tile lines it would leave takes groups of a line, 128 columns.
    A shorter one (those k lines are one) takes the power of two nearest
    the square root of width / k, where maxima and candidates together are
    fewest: 16 columns of 6,400 for k = 50, 400 maxima and 800 candidates."""
    if width < _SORTED:
        return 0
    if width >= 2 * k * _GROUP:
        return _GROUP
    g = 2 ** round(math.log2(width / k) / 2)
    return g if g > 1 and k * g < width else 0


def grouped_top_k(logits: jax.Array, k: int, g: int):
    """`jax.lax.top_k(logits, k)`, values and indices, ties included, with
    the selection run over `k` groups of `g` columns and not the row.

    Each of the top k lies in a group whose maximum is at least the k-th
    value and at most k groups have one, so the k groups of the largest
    maxima hold them all; `lax.top_k` breaks ties toward the lower index
    among groups as among columns, and the picked groups are laid side by
    side in vocabulary order, so equal logits win by the lower id exactly
    as in one pass over the row. The last group's `-inf` padding lies
    after every real id and is never picked before one. The candidates are
    a row again, and `top_k` takes them."""
    *lead, v = logits.shape
    x = logits.reshape(-1, v)
    b, n = x.shape[0], -(-v // g)
    r = _TILE_ROWS if b % _TILE_ROWS == 0 else 1
    x = jnp.pad(x, ((0, 0), (0, n * g - v)), constant_values=-jnp.inf)
    tiles = x.reshape(b // r, r, n, g)
    _, gid = top_k(tiles.max(-1).reshape(b, n), k)
    gid = jnp.sort(gid, axis=-1)
    # Line (row, group) of the [B/r, n, r, g] view, as a row of [B n, g].
    row = np.arange(b, dtype=np.int32)[:, None]
    lines = tiles.transpose(0, 2, 1, 3).reshape(b * n, g)
    picked = lines.at[(row // r * n * r + row % r) + gid * r].get(
        mode="promise_in_bounds"
    ).reshape(b, k * g)
    vals, pos = top_k(picked, k)
    # A candidate's id is its group's times g and its place in the group;
    # the group of each of k places is read by a mask, one fused pass
    # where a gather over [B, k] is five small operations.
    slot, place = jax.lax.div(pos, g), jax.lax.rem(pos, g)
    of = slot[..., None] == np.arange(k, dtype=np.int32)
    idx = jnp.sum(jnp.where(of, gid[:, None, :], 0), axis=-1) * g + place
    return vals.reshape(*lead, k), idx.reshape(*lead, k)


def top_k(logits: jax.Array, k: int):
    """`jax.lax.top_k` over the last axis, values and indices alike, in
    stages where the row is long (`group_size`)."""
    g = group_size(logits.shape[-1], k)
    if g == 0:
        return jax.lax.top_k(logits, k)
    return grouped_top_k(logits, k, g)


def apply_repetition_penalty(
    logits: jax.Array, seen_mask: jax.Array, penalty: float
) -> jax.Array:
    """HF semantics: seen tokens get logit/p if positive else logit*p.

    seen_mask: [B, V] bool — tokens present in the prompt or generated so far.
    """
    if penalty == 1.0:
        return logits
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen_mask, penalized, logits)


def apply_top_k(logits: jax.Array, k: int) -> jax.Array:
    """Keep the k highest logits per row; mask the rest."""
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    kth = top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, NEG_INF, logits)


def apply_top_p(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filtering, HF-style: keep the smallest prefix of the sorted
    distribution whose cumulative probability exceeds p (the crossing token
    is kept)."""
    if p >= 1.0:
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # remove token i iff cumulative prob *before* it already exceeds p.
    remove_sorted = (cum - probs) > p
    # Map the per-rank decision back to vocab order via the rank of each logit.
    ranks = jnp.argsort(jnp.argsort(logits, axis=-1)[..., ::-1], axis=-1)
    remove = jnp.take_along_axis(remove_sorted, ranks, axis=-1)
    return jnp.where(remove, NEG_INF, logits)


def sample_step(
    rng: jax.Array,
    logits: jax.Array,
    seen_mask: jax.Array,
    params: SamplingParams,
) -> jax.Array:
    """One sampling step: [B, V] float32 logits -> [B] int32 token ids.

    When top_k is active it bounds the nucleus set, so the whole
    top-p/temperature/sample pipeline runs on the k retained values — one
    exact top-k of the row (`top_k`: in stages over a long row) instead of
    three full-vocab sorts. This is the decode hot path: k is 50, the
    vocabularies served run from 20,480 to 200,192.
    """
    logits = apply_repetition_penalty(logits, seen_mask, params.repetition_penalty)
    if params.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / params.temperature
    k = params.top_k
    if 0 < k < logits.shape[-1]:
        # top_k returns values sorted descending — exactly the order HF's
        # nucleus filter cumsums in, so the two paths are equivalent.
        if params.approx_top_k:
            top_vals, top_idx = jax.lax.approx_max_k(logits, k)
        else:
            top_vals, top_idx = top_k(logits, k)
        if params.top_p < 1.0:
            probs = jax.nn.softmax(top_vals, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            top_vals = jnp.where((cum - probs) > params.top_p, NEG_INF, top_vals)
        choice = jax.random.categorical(rng, top_vals, axis=-1)
        return jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0].astype(
            jnp.int32
        )
    logits = apply_top_p(logits, params.top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def update_seen(seen_mask: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mark `tokens` [B] as seen in [B, V] mask (scatter via one-hot or)."""
    onehot = jax.nn.one_hot(tokens, seen_mask.shape[-1], dtype=seen_mask.dtype)
    return seen_mask | onehot.astype(jnp.bool_)


def seen_mask_from_ids(ids: jax.Array, valid: jax.Array, vocab_size: int) -> jax.Array:
    """[B, T] ids + [B, T] validity -> [B, V] presence mask."""
    onehot = jax.nn.one_hot(ids, vocab_size, dtype=jnp.bool_)
    return jnp.any(onehot & valid[..., None], axis=1)
