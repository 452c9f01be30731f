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

import jax
import jax.numpy as jnp

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
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
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
    `lax.top_k` over the vocab instead of three full-vocab sorts. This is
    the decode hot path: k is 50, the vocab is 50,257.
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
            top_vals, top_idx = jax.lax.top_k(logits, k)
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
