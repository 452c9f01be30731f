"""Shared speculative-decoding kernels: prompt-lookup drafting + exact verify.

Both decode engines speculate through this module — `engine.spec` (the
group-batched `decode_spec` while_loop) and `engine.paged` (the continuous-
batching chunked verify-window step) — so the exactness properties are
proven once, against one implementation (tests/test_spec.py's verifier
distribution and draft tests exercise these functions directly).

- **Drafting** is prompt-lookup (n-gram) speculation: the most recent
  earlier occurrence of the current (previous, last)-token bigram in the
  row's transcript — falling back to a unigram match — proposes the k
  tokens that followed it. Tutoring answers restate prompt phrases and
  their own earlier sentences constantly, which is exactly the regime
  where lookup drafting hits. No draft model, no extra weights, no extra
  HBM traffic.
- **Verification** walks the k drafts with rejection sampling against the
  target model's logits: draft d_i is accepted with probability p_i(d_i)
  — its probability under the FULL processed distribution (repetition
  penalty with the seen-set as of that position, temperature, top-k,
  top-p) — and the first rejection resamples from the residual
  distribution (p with the rejected token removed, renormalized), which
  for a deterministic (point-mass) draft is exactly the leftover-
  probability rule of speculative sampling [Leviathan et al. 2023; Chen
  et al. 2023]. If all k drafts survive, a bonus token samples from the
  (k+1)-th logit row. Every emitted token is therefore distributed
  identically to the non-speculative sampler — greedy (temperature=0)
  streams are bit-identical, stochastic streams are distribution-
  identical.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .sampling import NEG_INF, SamplingParams, apply_repetition_penalty, top_k


def build_drafts(
    transcript: jax.Array,
    match_valid: jax.Array,
    prev_tok: jax.Array,
    last_tok: jax.Array,
    k: int,
) -> jax.Array:
    """Prompt-lookup proposals: [B, k] continuation of the best n-gram match.

    transcript [B, W] token ids; match_valid [B, W] marks slots that may
    anchor a match (filled AND followed by at least one filled slot).
    Bigram matches (prev_tok, last_tok) outrank unigram matches
    (last_tok); ties break toward recency. Rows with no match propose
    `last_tok` repeated — a throwaway draft the verifier will almost
    surely reject, costing nothing extra (the verify forward runs at
    static width regardless).
    """
    b, w = transcript.shape
    pos = jnp.arange(w, dtype=jnp.int32)
    uni = (transcript == last_tok[:, None]) & match_valid
    prev_ids = jnp.concatenate(
        [jnp.full_like(transcript[:, :1], -1), transcript[:, :-1]], axis=1
    )
    prev_ok = jnp.concatenate(
        [jnp.zeros_like(match_valid[:, :1]), match_valid[:, :-1]], axis=1
    )
    bi = uni & prev_ok & (prev_ids == prev_tok[:, None])
    score = uni.astype(jnp.int32) + bi.astype(jnp.int32)  # 0 | 1 | 2
    best = jnp.argmax(score * w + pos[None, :], axis=1)   # [B]
    has = jnp.max(score, axis=1) > 0
    idx = best[:, None] + 1 + jnp.arange(k, dtype=jnp.int32)[None, :]
    drafts = jnp.take_along_axis(transcript, jnp.minimum(idx, w - 1), axis=1)
    return jnp.where(has[:, None], drafts, last_tok[:, None])


def build_drafts_ngram(
    transcript: jax.Array,
    match_valid: jax.Array,
    prev_tok: jax.Array,
    last_tok: jax.Array,
    k: int,
) -> jax.Array:
    """Per-row n-gram TABLE proposals: [B, k] modal continuations.

    Prompt-lookup (`build_drafts`) proposes the continuation of the most
    RECENT n-gram match — the right bet for greedy decode, where the
    model's argmax restates its most recent phrasing. At temperature>0
    the stream stops being self-copying and recency becomes a weak
    signal: the verifier accepts a draft with probability p(d), so the
    draft that maximizes acceptance is the MODAL continuation of the
    current context under the row's own empirical n-gram distribution.
    This drafter builds that table on the fly from the same transcript
    plane: every filled position i with transcript[i] == current token
    casts a vote for its continuation transcript[i+1]; bigram-context
    matches ((prev, cur) both equal) outvote any number of unigram
    matches (weight W > any unigram count); the continuation with the
    most votes wins, recency breaking ties. Each accepted proposal
    becomes the next lookup context, so the k drafts walk the table like
    a tiny per-row language model — no extra weights, no extra HBM, one
    [B, W, W] comparison per draft position (W is the transcript width,
    ~100s).

    Rows with no match propose the current token repeated — the same
    throwaway contract as `build_drafts` (the verify forward runs at
    static width regardless). Selected per engine via `[tutoring]
    draft_source = "ngram"`.
    """
    b, w = transcript.shape
    pos = jnp.arange(w, dtype=jnp.int32)
    # Continuation at anchor i is transcript[i+1]; the wrapped last
    # column is unreachable (match_valid never marks the final slot — it
    # requires k filled continuation slots after the anchor).
    nxt = jnp.concatenate([transcript[:, 1:], transcript[:, :1]], axis=1)
    prev_ids = jnp.concatenate(
        [jnp.full_like(transcript[:, :1], -1), transcript[:, :-1]], axis=1
    )
    prev_ok = jnp.concatenate(
        [jnp.zeros_like(match_valid[:, :1]), match_valid[:, :-1]], axis=1
    )
    same = (nxt[:, :, None] == nxt[:, None, :])  # continuation classes
    prev, cur = prev_tok, last_tok
    drafts = []
    for _ in range(k):
        uni = (transcript == cur[:, None]) & match_valid
        bi = uni & prev_ok & (prev_ids == prev[:, None])
        votes = (
            jnp.sum(same & uni[:, None, :], axis=-1).astype(jnp.int32)
            + jnp.sum(same & bi[:, None, :], axis=-1).astype(jnp.int32) * w
        )
        score = jnp.where(uni, votes, 0)
        # Lexicographic (score, recency) argmax without overflow: most
        # recent anchor among the max-score class.
        m = jnp.max(score, axis=1, keepdims=True)
        best = jnp.argmax(
            jnp.where((score == m) & uni, pos[None, :], -1), axis=1
        )
        has = m[:, 0] > 0
        proposed = jnp.where(
            has, jnp.take_along_axis(nxt, best[:, None], axis=1)[:, 0],
            cur,
        )
        drafts.append(proposed)
        prev, cur = cur, proposed
    return jnp.stack(drafts, axis=1)


def _processed_top(
    logits: jax.Array, seen: jax.Array, params: SamplingParams
) -> Tuple[jax.Array, jax.Array]:
    """(filtered_vals [B, K], idx [B, K]) — the processed distribution's
    support, matching sample_step's pipeline: repetition penalty, then
    temperature, then top-k, then top-p (NEG_INF outside the nucleus).
    With top_k disabled the support is the whole vocab."""
    logits = apply_repetition_penalty(logits, seen, params.repetition_penalty)
    temp = params.temperature if params.temperature > 0 else 1.0
    logits = logits / temp
    k = params.top_k
    if 0 < k < logits.shape[-1]:
        if params.approx_top_k:
            vals, idx = jax.lax.approx_max_k(logits, k)
        else:
            vals, idx = top_k(logits, k)
    else:
        vals = jnp.sort(logits, axis=-1)[..., ::-1]
        idx = jnp.argsort(logits, axis=-1)[..., ::-1]
    if params.top_p < 1.0:
        probs = jax.nn.softmax(vals, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        vals = jnp.where((cum - probs) > params.top_p, NEG_INF, vals)
    return vals, idx.astype(jnp.int32)


def verify_window(
    rng: jax.Array,
    logits: jax.Array,
    drafts: jax.Array,
    seen: jax.Array,
    active_in: jax.Array,
    sampling: SamplingParams,
    eos_id: int,
    pad_id: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Walk one verify window; returns (emitted [B,k+1], valid [B,k+1],
    seen', hit_eos [B]).

    logits[:, i] is the model's next-token distribution given the prefix
    plus drafts d_1..d_i; draft d_{i+1} is checked against logits[:, i].
    Rows enter with `active_in` (False = already done, emit nothing).
    `valid` is a contiguous prefix per row (the accept chain only ever
    breaks once), so a row's emission count is `sum(valid)` and its
    emitted tokens are the first `count` columns.

    The sampling pipeline runs ONCE, batched over all k+1 positions:
    position i's distribution only matters if drafts 1..i were all
    accepted, in which case its repetition-penalty seen-set is exactly
    `seen ∪ {d_1..d_i}` — known before any accept/reject decision. So the
    whole window pays roughly one step's sampling cost (the first
    implementation ran k+1 sequential passes and lost its speedup to
    them); the per-position walk that follows touches only [B, top_k]
    slices and scalars.
    """
    b, k1, v = logits.shape
    k = k1 - 1
    greedy = sampling.temperature <= 0.0
    logits = logits.astype(jnp.float32)

    stacks = [seen]
    for i in range(k):
        stacks.append(
            stacks[-1] | jax.nn.one_hot(drafts[:, i], v, dtype=jnp.bool_)
        )
    seen_stack = jnp.stack(stacks, axis=1)  # [B, k+1, V] hypothetical

    if greedy:
        # Deterministic fast path: top-k/top-p can't move the argmax, so
        # the processed pipeline reduces to argmax over penalty-adjusted
        # logits — no sorts at all. A rejected draft's residual argmax IS
        # the global argmax (the draft wasn't it), and so is the bonus.
        lg = apply_repetition_penalty(
            logits, seen_stack, sampling.repetition_penalty
        )
        am = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # [B, k+1]
    else:
        vals, idx = _processed_top(
            logits.reshape(b * k1, v), seen_stack.reshape(b * k1, v),
            sampling,
        )
        vals = vals.reshape(b, k1, -1)
        idx = idx.reshape(b, k1, -1)

    emitted = jnp.full((b, k1), pad_id, jnp.int32)
    valid = jnp.zeros((b, k1), jnp.bool_)
    hit_eos = jnp.zeros((b,), jnp.bool_)
    chain = active_in  # rows whose drafts have all been accepted so far

    for i in range(k1):
        rng, r_acc, r_res = jax.random.split(rng, 3)
        if greedy:
            tok = am[:, i]
            accept = (drafts[:, i] == tok) if i < k else jnp.zeros(
                (b,), jnp.bool_
            )
        elif i < k:
            d = drafts[:, i]
            at = idx[:, i] == d[:, None]  # [B, K] membership of the draft
            probs = jax.nn.softmax(vals[:, i], axis=-1)
            p_d = jnp.sum(jnp.where(at, probs, 0.0), axis=-1)
            accept = jax.random.uniform(r_acc, (b,)) < p_d
            # Residual for rejected rows: the processed distribution with
            # the draft removed, renormalized — the exact leftover rule
            # for a point-mass proposal.
            res_vals = jnp.where(at, NEG_INF, vals[:, i])
            choice = jax.random.categorical(r_res, res_vals, axis=-1)
            resample = jnp.take_along_axis(
                idx[:, i], choice[:, None], axis=-1
            )[:, 0]
            tok = jnp.where(accept, d, resample)
        else:
            # Bonus position: all k drafts survived; sample normally.
            accept = jnp.zeros((b,), jnp.bool_)
            choice = jax.random.categorical(r_res, vals[:, i], axis=-1)
            tok = jnp.take_along_axis(
                idx[:, i], choice[:, None], axis=-1
            )[:, 0]

        emit = chain  # rows still in the chain emit at window position i
        emitted = emitted.at[:, i].set(jnp.where(emit, tok, pad_id))
        valid = valid.at[:, i].set(emit)
        is_eos = emit & (tok == eos_id)
        hit_eos = hit_eos | is_eos
        # A rejection emits its resample and ends the row's window; an
        # accepted EOS also ends it (nothing follows EOS).
        chain = emit & accept & ~is_eos

    # The real (not hypothetical) seen update: tokens actually emitted.
    emit_oh = jax.nn.one_hot(emitted, v, dtype=jnp.bool_) & valid[..., None]
    seen = seen | jnp.any(emit_oh, axis=1)
    return emitted, valid, seen, hit_eos
