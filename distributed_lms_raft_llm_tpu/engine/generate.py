"""Jitted autoregressive generation: bucketed prefill + while_loop decode.

TPU-first replacement for the reference's `model.generate(...)` call
(reference: GUI_RAFT_LLM_SourceCode/tutoring_server.py:21-29): the whole
prompt batch prefills in one static-shape pass, then a `lax.while_loop`
decodes with a KV cache, sampling fused into the step — no host round-trips
per token. Early exit when every row has emitted EOS.

Generation is split into two jittable halves so the serving layer can
measure time-to-first-token for real instead of deriving it:

- `prefill` runs the prompt pass and samples the FIRST token; the engine
  blocks on that token, which is the honest TTFT boundary;
- `decode` continues from the returned `DecodeState` under a while_loop.
  The state (KV cache included) is donated by the engine's jit wrapper, so
  the handoff between the two programs reuses the cache buffers in place.

Shapes are static: prompts are left-padded to a bucket length. The KV cache
GROWS across decode segments instead of being allocated at its final size up
front: prefill builds a prompt-sized cache, and `decode` splits the token
budget into `segments` spans, padding the cache to each span's high-water
mark between the spans' while_loops. Every attention/softmax/scale op's
cost is proportional to the cache length it reads, and with a 64-token
prompt and 128 new tokens the final-size cache wastes ~1/3 of that traffic
on slots that are not valid yet; growing it in 4 segments recovers most of
the waste for a few cheap pad-copies. The last segment's
cache is exactly `bucket + max_new_tokens`, so the no-silent-overflow
precondition documented in models/gpt2.py still holds by construction.

The reference caps *total* length at 150 (`max_length`), which silently
leaves no room to answer long prompts (SURVEY.md §5 latent defect); here the
budget is `max_new_tokens` — always that much room to answer.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import registry
from ..models.common import KVCache
from ..models.registry import ModelFamily
from .sampling import SamplingParams, sample_step, seen_mask_from_ids, update_seen


class GenerateResult(NamedTuple):
    tokens: jax.Array   # [B, max_new] int32; rows padded with pad_id after EOS
    lengths: jax.Array  # [B] int32 — emitted tokens per row (including EOS)


class DecodeState(NamedTuple):
    """Carry between the prefill and decode programs (and loop iterations).

    The cache is prompt-sized coming out of `prefill`; `decode` pads it to
    each segment's high-water mark (see module docstring). `seen` stays the
    dense [B, V] presence plane: a transcript-ids + scatter-min variant was
    tried and was slower (TPU scatter serializes; the one_hot|or update
    and the fused mask read are cheap).
    """

    cache: KVCache
    tok: jax.Array        # [B] last sampled token
    rng: jax.Array
    out: jax.Array        # [B, max_new]
    seen: jax.Array       # [B, V] repetition-penalty presence mask
    done: jax.Array       # [B]
    lengths: jax.Array    # [B]
    step: jax.Array       # []
    real_lens: jax.Array  # [B] true prompt lengths (positions base)
    kv_mask: jax.Array    # [B, t + max_new] key-slot validity (full width)


def make_positions(prompt_mask: jax.Array) -> jax.Array:
    """Per-row position ids for a left-padded prompt ([B, T] bool -> int32)."""
    return jnp.maximum(jnp.cumsum(prompt_mask.astype(jnp.int32), axis=1) - 1, 0)


def prefill(
    params,
    cfg,
    input_ids: jax.Array,
    prompt_mask: jax.Array,
    rng: jax.Array,
    sampling: SamplingParams,
    eos_id: int,
    pad_id: int,
    model: ModelFamily = registry.GPT2_FAMILY,
) -> DecodeState:
    """Prompt pass + first sampled token; returns the state `decode` resumes.

    Pure and jittable: `cfg`, `sampling`, `eos_id`, `pad_id` are static.
    input_ids [B, T] int32, prompt_mask [B, T] bool (False = left padding).
    The first token is `state.out[:, 0]` — the engine blocks on it to record
    TTFT before dispatching `decode`.
    """
    b, t = input_ids.shape
    max_new = sampling.max_new_tokens
    if t + max_new > cfg.max_position_embeddings:
        raise ValueError(
            f"bucket {t} + max_new {max_new} exceeds position table "
            f"{cfg.max_position_embeddings}"
        )

    positions = make_positions(prompt_mask)
    real_lens = jnp.sum(prompt_mask.astype(jnp.int32), axis=1)  # [B]

    # Prompt-sized cache: decode pads it up per segment (module docstring).
    cache = model.init_cache(cfg, b, t, dtype=cfg.dtype, groups=1)
    # Slots 0..t-1 hold the (partly padded) prompt; decode slots are real.
    kv_mask = jnp.concatenate(
        [prompt_mask.astype(jnp.bool_), jnp.ones((b, max_new), jnp.bool_)], axis=1
    )

    logits, cache = model.forward(
        params, cfg, input_ids, cache=cache, positions=positions,
        kv_mask=kv_mask[:, :t],
    )
    last_logits = logits[:, -1]  # left-padding ⇒ every row's last slot is real

    seen = seen_mask_from_ids(input_ids, prompt_mask, cfg.vocab_size)

    rng, step_rng = jax.random.split(rng)
    first_tok = sample_step(step_rng, last_logits, seen, sampling)

    out0 = jnp.full((b, max_new), pad_id, jnp.int32)
    out0 = out0.at[:, 0].set(first_tok)
    return DecodeState(
        cache=cache,
        tok=first_tok,
        rng=rng,
        out=out0,
        seen=update_seen(seen, first_tok),
        done=first_tok == eos_id,
        lengths=jnp.ones((b,), jnp.int32),
        step=jnp.ones((), jnp.int32),
        real_lens=real_lens,
        kv_mask=kv_mask,
    )


def _grow_cache(cache: KVCache, new_len: int) -> KVCache:
    """Zero-pad the key/value slot axis up to `new_len` (no-op if there)."""
    cur = cache.k.shape[3]
    if cur >= new_len:
        return cache
    pad = [(0, 0), (0, 0), (0, 0), (0, new_len - cur), (0, 0)]
    return cache._replace(
        k=jnp.pad(cache.k, pad),
        v=None if cache.v is None else jnp.pad(cache.v, pad),
        ks=None if cache.ks is None else jnp.pad(cache.ks, pad[:-1]),
        vs=None if cache.vs is None else jnp.pad(cache.vs, pad[:-1]),
    )


def decode(
    params,
    state: DecodeState,
    cfg,
    sampling: SamplingParams,
    eos_id: int,
    pad_id: int,
    model: ModelFamily = registry.GPT2_FAMILY,
    segments: Optional[int] = None,
) -> Tuple[GenerateResult, DecodeState]:
    """Run the while_loop decode from a prefilled state to completion.

    The token budget splits into `segments` spans; each span runs its own
    while_loop against a cache padded to that span's high-water mark, so
    attention streams only the slots that can be valid yet (module
    docstring).
    A fully-EOS'd batch exits at the next span boundary: each span's cond
    starts false, so trailing spans cost one predicate each.

    segments=None picks from the (static) batch size: larger batches spend
    more of each step on KV reads, so finer segmentation pays there while
    its fixed pad/loop overheads lose at small batches (the 4-small /
    8-large split has not been re-measured on the v5e).

    Returns (result, final_state). The final state is returned so the
    engine's jit wrapper can donate the input state: the same-shaped
    outputs (out/seen/rng/flags) alias in place instead of copying. The
    cache cannot alias at any segments setting — the input is prompt-sized,
    the output [*, t + max_new] — but the copies that implies are the pads,
    already counted in the segmentation tradeoff. Callers that only want
    the tokens drop the state.
    """
    max_new = sampling.max_new_tokens
    t = state.kv_mask.shape[1] - max_new
    if segments is None:
        segments = 8 if state.out.shape[0] >= 16 else 4
    segments = max(1, min(segments, max_new))

    def seg_body(seg_end: int):
        def cond(s: DecodeState):
            return (s.step < seg_end) & ~jnp.all(s.done)

        def body(s: DecodeState) -> DecodeState:
            # Feed last token; its slot is t + step - 1, its position is
            # real_lens + step - 1 (both per the left-padded layout).
            pos = (s.real_lens + s.step - 1)[:, None]
            n_keys = s.cache.k.shape[3]
            logits, cache = model.forward(
                params, cfg, s.tok[:, None], cache=s.cache, positions=pos,
                kv_mask=s.kv_mask[:, :n_keys],
            )
            rng, step_rng = jax.random.split(s.rng)
            nxt = sample_step(step_rng, logits[:, 0], s.seen, sampling)
            nxt = jnp.where(s.done, jnp.asarray(pad_id, jnp.int32), nxt)
            out = jax.lax.dynamic_update_slice(s.out, nxt[:, None], (0, s.step))
            lengths = s.lengths + (~s.done).astype(jnp.int32)
            done = s.done | (nxt == eos_id)
            return DecodeState(
                cache=cache,
                tok=nxt,
                rng=rng,
                out=out,
                seen=update_seen(s.seen, nxt),
                done=done,
                lengths=lengths,
                step=s.step + 1,
                real_lens=s.real_lens,
                kv_mask=s.kv_mask,
            )

        return cond, body

    for i in range(segments):
        seg_end = (max_new * (i + 1)) // segments
        # Steps in [.., seg_end) feed cache slots up to t + seg_end - 2 and
        # the span's last sampled token lands at slot t + seg_end - 1 next
        # span — pad to t + seg_end so the NEXT span's first step fits too.
        state = state._replace(cache=_grow_cache(state.cache, t + seg_end))
        cond, body = seg_body(seg_end)
        state = jax.lax.while_loop(cond, body, state)

    return GenerateResult(tokens=state.out, lengths=state.lengths), state


def generate(
    params,
    cfg,
    input_ids: jax.Array,
    prompt_mask: jax.Array,
    rng: jax.Array,
    sampling: SamplingParams,
    eos_id: int,
    pad_id: int,
    model: ModelFamily = registry.GPT2_FAMILY,
) -> GenerateResult:
    """Sample continuations for a left-padded prompt batch (one program).

    Composition of `prefill` + `decode` for callers that don't need the
    TTFT split (tests, offline batch work).
    """
    state = prefill(
        params, cfg, input_ids, prompt_mask, rng, sampling, eos_id, pad_id,
        model=model,
    )
    return decode(params, state, cfg, sampling, eos_id, pad_id, model=model)[0]


def pick_bucket(length: int, buckets: Tuple[int, ...]) -> int:
    """Smallest bucket >= length (last bucket if none fit — caller truncates)."""
    for bkt in buckets:
        if length <= bkt:
            return bkt
    return buckets[-1]
