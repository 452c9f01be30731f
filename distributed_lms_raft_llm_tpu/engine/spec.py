"""Speculative decoding with prompt-lookup drafting (exact, jit-native).

The reference decodes strictly one token per model call (reference:
GUI_RAFT_LLM_SourceCode/tutoring_server.py:21-29 — HF `generate`'s
autoregressive loop). This module emits SEVERAL tokens per model call
while sampling from *exactly* the same distribution. The drafting and
accept/resample kernels live in `engine.draft` (shared with the paged
engine's chunked verify-window step — `engine.paged`); this module owns
the group-batched while_loop decode that `TutoringEngine` (the tests'
reference generator) swaps in for `generate.decode` when
`spec_tokens > 0`:

- **Verification** runs the target model ONCE over [last_tok, d_1..d_k]
  (k+1 positions; the KV write scatters at per-row ragged slots — see
  gpt2.forward), then `draft.verify_window` walks the k drafts with
  exact rejection sampling [Leviathan et al. 2023; Chen et al. 2023]:
  greedy (temperature=0) streams are bit-identical to the sequential
  decoder, stochastic streams are distribution-identical (tested both
  ways in tests/test_spec.py).

Per-row bookkeeping: rows accept different draft counts, so the decode
state tracks per-row generated counts `n` and the cache takes per-row
slot offsets. A row's verify window [t+n-1, t+n-1+k] always covers every
garbage slot its previous window may have left behind (the window start
advances by the number of emitted tokens ≥ 1 while the width stays k+1),
and the causal mask (key slot ≤ query slot) hides the not-yet-valid tail
within a window — so no dynamic KV-validity state is needed beyond the
static prompt padding mask.

Cost shape: the verify forward streams the same parameter and KV bytes
as ONE ordinary decode step (both are bandwidth-bound; the extra k
query positions are FLOP-cheap), but sampling runs k+1 times per step.
The win is therefore largest where per-step fixed costs dominate —
small batches, i.e. the single-student latency path — and the feature
is opt-in (`EngineConfig.spec_tokens`; `tutoring_server --spec-tokens`
selects the served engine's own verify step, `engine.paged`).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..models import registry
from ..models.common import KVCache
from ..models.registry import ModelFamily
from .draft import _processed_top, build_drafts, verify_window  # noqa: F401
from .generate import DecodeState, GenerateResult, _grow_cache
from .sampling import SamplingParams


class SpecState(NamedTuple):
    """Carry of the speculative decode loop (per-row progress)."""

    cache: KVCache
    transcript: jax.Array  # [B, t + max_new] prompt slots then generated slots
    rng: jax.Array
    out: jax.Array         # [B, max_new] emitted tokens (pad after EOS/budget)
    seen: jax.Array        # [B, V] repetition-penalty presence mask
    done: jax.Array        # [B]
    n: jax.Array           # [B] tokens generated so far (== lengths)
    real_lens: jax.Array   # [B] true prompt lengths (position base)
    kv_mask: jax.Array     # [B, cache_width] key-slot validity
    windows: jax.Array     # [] verify windows run — sum(n)/windows/B is the
    #                        mean tokens-per-window (acceptance observability)


def decode_spec(
    params,
    state: DecodeState,
    input_ids: jax.Array,
    cfg,
    sampling: SamplingParams,
    eos_id: int,
    pad_id: int,
    model: ModelFamily = registry.GPT2_FAMILY,
    spec_tokens: int = 4,
) -> Tuple[GenerateResult, SpecState]:
    """Speculative continuation of a prefilled DecodeState.

    Same contract as generate.decode (the engine swaps one for the other
    when `spec_tokens > 0`) plus the prompt `input_ids` [B, t], which
    seed the lookup transcript. The cache grows once to its high-water
    width `t + max_new + spec_tokens - 1`: the widest verify window
    starts at slot t + (max_new-1) - 1 and spans spec_tokens + 1 slots.
    """
    k = spec_tokens
    max_new = sampling.max_new_tokens
    b, t = input_ids.shape
    width = t + max_new + k - 1
    # Position-budget validation (mirrors prefill's t + max_new <= mpe
    # guard, extended by the spec window's k-1 overhang): a direct caller
    # that oversubscribes the position table gets an error here, not
    # silently-clamped (wrong) position embeddings near the end of
    # generation. The in-loop clamp below remains ONLY for idle done-rows
    # re-verifying their final window.
    if width > cfg.max_position_embeddings:
        raise ValueError(
            f"verify-window budget exceeds the position table: prompt {t} "
            f"+ max_new_tokens {max_new} + spec_tokens {k} - 1 = {width} "
            f"> max_position_embeddings {cfg.max_position_embeddings}"
        )

    prompt_valid = state.kv_mask[:, :t]
    cache = _grow_cache(state.cache, width)
    # Per-row slot offsets from here on (rows advance at different rates);
    # the loop body overwrites length each step, but the carry's type must
    # be [B] from the start.
    cache = cache._replace(
        length=jnp.broadcast_to(cache.length, (b,)).astype(jnp.int32)
    )
    kv_mask = jnp.concatenate(
        [prompt_valid, jnp.ones((b, width - t), jnp.bool_)], axis=1
    )
    # Transcript: prompt ids in slots [0, t) (left-padded like the cache),
    # generated token g at slot t + g. Pad slots never anchor a match
    # (match_valid below); out[:, 0] from prefill seeds slot t.
    transcript = jnp.concatenate(
        [input_ids, jnp.full((b, max_new), pad_id, jnp.int32)], axis=1
    )
    transcript = transcript.at[:, t].set(state.out[:, 0])

    spec = SpecState(
        cache=cache,
        transcript=transcript,
        rng=state.rng,
        out=state.out,
        seen=state.seen,
        done=state.done,
        n=state.lengths,
        real_lens=state.real_lens,
        kv_mask=kv_mask,
        windows=jnp.zeros((), jnp.int32),
    )
    w = t + max_new
    pos_w = jnp.arange(w, dtype=jnp.int32)[None, :]
    offs = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    prompt_valid_w = jnp.concatenate(
        [prompt_valid, jnp.zeros((b, max_new), jnp.bool_)], axis=1
    )

    def cond(s: SpecState):
        return ~jnp.all(s.done)

    def body(s: SpecState) -> SpecState:
        # Window base: active rows feed their last emitted token (slot
        # t+n-1). Done rows idle — clamp their base inside the budget so
        # the verify window stays in bounds; their rewrites may scramble
        # their own cache tail, which nothing ever reads (emissions are
        # masked off and per-row slots never cross rows).
        base = jnp.minimum(s.n, max_new - 1) - 1
        last = jnp.take_along_axis(
            s.transcript, (t + base)[:, None], axis=1
        )[:, 0]
        prev = jnp.take_along_axis(
            s.transcript, jnp.maximum(t + base - 1, 0)[:, None], axis=1
        )[:, 0]
        # A slot may anchor a match iff it is filled (real prompt token or
        # generated) and ALL k continuation slots behind it are filled too:
        # an anchor near the frontier would propose not-yet-generated pad
        # slots, which auto-reject and waste the window (measured: periodic
        # text sat at ~2 tokens/window because argmax preferred the most
        # recent — frontier-adjacent — anchor over the one-period-back
        # anchor whose continuation is actually known).
        filled = jnp.where(
            pos_w < t, prompt_valid_w, pos_w < (t + s.n)[:, None]
        )
        match_valid = filled & (pos_w <= (t + s.n - 1 - k)[:, None])
        drafts = build_drafts(s.transcript, match_valid, prev, last, k)

        feed = jnp.concatenate([last[:, None], drafts], axis=1)  # [B, k+1]
        positions = s.real_lens[:, None] + base[:, None] + offs
        # Clamp: done rows re-verify their final window forever (writes
        # are idempotent — same tokens, same slots); the position table
        # must not overflow while they idle.
        positions = jnp.minimum(positions, cfg.max_position_embeddings - 1)
        # Batch 1 — the latency case speculation exists for — takes the
        # scalar-offset cache path (dynamic_update_slice) instead of the
        # per-row scatter; the window start is trivially uniform.
        offs_len = t + base  # [B]
        cache_in = s.cache._replace(
            length=offs_len[0] if b == 1 else offs_len
        )
        logits, cache2 = model.forward(
            params, cfg, feed, cache=cache_in,
            positions=positions, kv_mask=s.kv_mask,
        )
        cache2 = cache2._replace(length=offs_len)  # keep the carry [B]
        rng, r_win = jax.random.split(s.rng)
        emitted, valid, seen, hit_eos = verify_window(
            r_win, logits, drafts, s.seen, ~s.done, sampling, eos_id, pad_id
        )
        # Budget clamp, then scatter: invalid window positions are routed
        # to an out-of-bounds index and dropped (mode="drop"), so only
        # genuinely emitted tokens land in out/transcript.
        slots = s.n[:, None] + offs  # [B, k+1] output indices
        valid = valid & (slots < max_new)
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        out = s.out.at[
            rows, jnp.where(valid, slots, max_new)
        ].set(emitted, mode="drop")
        tr = s.transcript.at[
            rows, jnp.where(valid, t + slots, w)
        ].set(emitted, mode="drop")
        n = s.n + jnp.sum(valid, axis=1).astype(jnp.int32)
        done = s.done | hit_eos | (n >= max_new)
        return SpecState(
            cache=cache2, transcript=tr, rng=rng, out=out, seen=seen,
            done=done, n=n, real_lens=s.real_lens, kv_mask=s.kv_mask,
            windows=s.windows + 1,
        )

    spec = jax.lax.while_loop(cond, body, spec)
    return GenerateResult(tokens=spec.out, lengths=spec.n), spec
