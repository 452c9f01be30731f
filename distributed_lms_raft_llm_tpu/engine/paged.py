"""Continuous batching: slot-based decode with per-slot KV lengths.

`engine.generate` runs a request group to completion — a request arriving
one step late waits a full generation (SURVEY.md §7 hard part 3). This
module generalizes the KV cache to per-slot lengths (the generalization
`models/common.py` KVCache reserves the name for): the cache holds S
independent slots; every decode step advances ALL active slots by one
token, and the host admits/evicts requests BETWEEN steps, so a new request
joins the running batch at the next step instead of queueing behind it.

Layout differences from the bucketed path (both by design):
- prompts are RIGHT-padded into their slot (slot position 0 = first prompt
  token) so per-slot raggedness is just a length integer;
- decode is a host-driven loop over a jitted program of K CHUNKED steps
  (admission needs host control between dispatches), not a device-side
  while_loop. Each dispatch advances K * `chunk` tokens for all S slots
  with one readback — see `_step_program` for what chunking amortizes.

Admission is STAGED and the decode program is one (`_megastep`); there is
no prefill program of its own between decode dispatches, so an arriving
prompt never pauses the decode train:
- `_stage`: writes a request's right-padded prompt ids into its slot's
  transcript row and arms the staged-admission plane riding in SlotState
  (staged flag, chunk cursor, true length, first-token rng). With the
  shared-prefix cache enabled (`prefix_cache=True`), admission first looks
  the prompt up in a radix tree of immutable device-resident KV block runs
  (`engine/prefix_cache.py`): on a hit `_stage_block` splices the cached
  blocks straight into the slot's pages and the cursor starts behind
  them; a flipped request's prompt blocks are published back into the
  tree (`_export_block`; a long edge as stored runs of
  `STORED_RUN_BLOCKS` blocks, `_export_run`, which a hit splices with one
  launch each), ref-count-pinned by live slots and LRU-evicted under a
  block budget.
- `_megastep`: K chunks of `_step_program` back-to-back on device (a scan
  over the chunk body), so the host pays one dispatch + one async
  readback per K*chunk tokens. The chunk body is a [S,1] last-tokens
  forward with per-row cache offsets (the models' ragged-slot scatter
  path), fused sampling, lengths/active update, scanned over `chunk`
  tokens. With `EngineConfig.spec_tokens=k` set, it generalizes to a
  [S, k+1] verify window per scan iteration (`_spec_step_program`):
  prompt-lookup drafts from the device-side transcript, one forward over
  the window, exact rejection sampling (`engine.draft`, shared with
  `engine.spec`) — rows accept different counts, so slot lengths advance
  raggedly between host dispatches and the host reaps a per-window token
  count. Per-chunk token planes and active-mask snapshots come back
  stacked (`[K, chunk, S, ...]` / `[K, S]`) for one batched host reap;
  slots that finish mid-megastep burn pad lanes until the boundary
  (counted on device — `megastep_dead_lane_tokens`) instead of forcing a
  host reap. While any slot is staged, EVERY decode iteration of the
  megastep (each row of the per-token scan inside each of its K chunks,
  not each chunk) first runs one token-budgeted prefill pass
  (`_admission_chunk`: a chunk of `prefill_chunk_tokens` positions for
  each of the oldest staged slots, as many as fill `PASS_ROWS` positions,
  in one forward pass that reads the weights once) — the Sarathi-Serve
  chunked-prefill idea, device-resident — and then advances the live
  slots by a token. A slot's final chunk samples
  the first token from the last real position's logits with the staged
  rng and the full-prompt seen mask, and flips the slot live in that same
  iteration; `flipped`/`firsts` planes come back stacked [K, chunk, S], a
  row per iteration like the tokens, so the one batched reap learns
  admission outcomes with zero extra syncs, and a request holds its lane
  staged for about as many iterations as the passes queued before its
  last (`engine_staged_iterations`; `engine_prefill_pass_slots` over
  `engine_prefill_passes` is how many slots a pass served). Prefill
  compute fills the scan's
  pipeline bubbles, and greedy outputs are bit-identical to the bucketed
  engine's (`TutoringEngine`) at any K and chunk budget
  (tests/test_fused_prefill.py).
- Staging happens at megastep boundaries; a TTFT-aware controller
  (`next_megastep_k`) grows K toward `megastep_max` when idle and, while
  admissions are waiting, caps K at the guaranteed-admission horizon
  (chunks until some live slot MUST free, `_slack_chunks`), down to one
  chunk where an answer ends within it (`engine_one_chunk_dispatches`).
  K = 1 runs through `_megastep` at rung 1.
- `_grow`: pads the live cache up to the next width when a longer prompt
  arrives.

The reference has no analogue (HF `generate`, one request at a time —
reference: GUI_RAFT_LLM_SourceCode/tutoring_server.py:21-29).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
import time
from functools import partial
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import convert, registry
from ..models import quant as quant_lib
from ..models.common import KVCache
from ..ops import attention as attention_ops
from ..ops import sparse as sparse_ops
from ..parallel import mesh as mesh_lib
from ..parallel import partition
from ..utils import tokenizer as tok_lib
from ..utils.compilation import enable_compilation_cache
from ..utils.guards import intended_transfer
from .draft import build_drafts, build_drafts_ngram, verify_window
from .engine import EngineConfig
from .generate import pick_bucket
from .prefix_cache import (
    BLOCK_TOKENS,
    KVBlock,
    Match,
    PrefixCache,
    RunBlock,
    StateSnapshot,
    plan_staged,
    splice_pieces,
)
from .program_inventory import (
    STAGE_RUN_BLOCKS,
    STORED_RUN_BLOCKS,
    bucket_has_runs,
    bucket_holds_stored_run,
    effective_megastep_max,
    megastep_ladder,
    stage_runs,
    width_holds_stored_run,
)
from .scoring import _score_program, derive_score_shapes, score_texts
from .spans import (
    KEYS,
    PROG,
    DispatchLedger,
    ProgramLog,
    Span,
    SpanSum,
    is_runtime_call,
    named_partial,
)
from .sampling import (
    SamplingParams,
    sample_step,
    seen_mask_from_ids,
    update_seen,
)

log = logging.getLogger(__name__)

# A recurrent family's state snapshots in the prefix tree
# (`PagedEngine._snapshot_point`, engine/prefix_cache.py): the tree holds one
# for every this many blocks of its budget, and a context's FIRST prompt,
# which matched nothing, snapshots on a stride of at most this many steps of
# lcm(prefill chunk, block) (256 tokens at the shipped 32 and 16): fewer
# where the state is small beside the keys and values of a stride
# (`PagedEngine._stride_steps`).
STATE_BLOCKS_A_SNAPSHOT = 16
STATE_STRIDE_STEPS = 8

# Positions an in-scan prefill pass holds (`_admission_chunk`): the rows of
# one tile of the chip's matrix unit, filled with whole chunks of
# `prefill_chunk_tokens` positions, one staged slot's each.
PASS_ROWS = 128


class SlotState(NamedTuple):
    """Device-side state of all S slots.

    A family with a recurrent state (`ModelFamily.recurrent_state`) keeps
    it in the cache beside its keys and values, `cache.ssm` [Lm, S, H, P,
    N] float32 and `cache.conv` [Lm, S, K-1, C] (either alone where the
    state is no more: `_has_state`): planes WITHOUT a positions axis,
    which a forward pass over a lane moves. They advance
    once for every real token and for nothing else: the decode forward is
    told its `_live_lanes` (an idle lane, a staged lane before its flip
    and a lane past its request's cap stand still), a prefill chunk its
    real positions, and `_stage_program` resets a slot's state for its
    next tenant (to zeros; `_restore_state_program` then puts a snapshot
    there on a prefix hit)."""

    cache: KVCache     # k/v [L, S, H, Tmax, Dh] (a family that folds its
    #                    heads: [L, S, tp, Tmax, F]); length [S] per-slot
    tok: jax.Array     # [S] last sampled token per slot
    active: jax.Array  # [S] bool
    seen: jax.Array    # [S, V] repetition-penalty presence mask
    # [S, W] per-slot token transcript mirroring the cache layout
    # (right-padded: transcript slot j = the token whose KV lives — or
    # will live — in cache slot j). Slots <= cache.length hold real
    # tokens. Feeds the prompt-lookup drafter in spec mode; carried
    # unchanged (aliased in place by donation) by the plain step. The
    # transcript doubles as the staged prompt's device-side id store:
    # `_stage_program` writes the whole right-padded prompt here and the
    # in-scan prefill chunks read their ids back out.
    transcript: jax.Array
    # Staged-admission plane (in-scan chunked prefill; all [S]): `staged`
    # marks slots whose prompt is being prefilled inside the megastep scan
    # (a chunk per decode iteration, for the oldest few by `stage_seq`),
    # `stage_cursor` the next absolute prefill position (starts at the
    # spliced shared-prefix length), `stage_len` the true prompt length
    # (it stays after the flip: a family with routed experts knows a
    # lane's request has ended from it, `_live_lanes`), `stage_seq` the
    # host's staging sequence number (FIFO service order — slot index
    # would starve an early admission whenever churn restages a lower
    # slot), and `stage_rng` the raw key data the flip samples the first
    # token with (one host split an admission, in admission order).
    staged: jax.Array       # [S] bool
    stage_cursor: jax.Array  # [S] int32
    stage_len: jax.Array     # [S] int32
    stage_seq: jax.Array     # [S] int32
    stage_rng: jax.Array     # [S, *key_data] uint32
    # A recurrent family's snapshot planes (None for every other, and
    # each None where the cache has no such plane): where
    # a staged slot's prefill reaches position `snap_at` (0: nowhere) at a
    # chunk's end, that chunk copies the slot's state into its row of
    # `snap_ssm` / `snap_conv` (shaped as the cache's `ssm` / `conv`), and
    # the host exports the row into the prefix tree when it reaps the flip
    # (`_export_state_program`): the state a later request with the same
    # first `snap_at` tokens starts from.
    snap_ssm: Optional[jax.Array] = None
    snap_conv: Optional[jax.Array] = None
    snap_at: Optional[jax.Array] = None      # [S] int32


@dataclasses.dataclass
class _Request:
    rid: int
    prompt_len: int
    tokens: List[int]
    max_new: int
    submit_time: float = 0.0
    popped_time: float = 0.0  # left the pending queue for admission
    # Set at reap time; later in-flight chunks dispatched before the finish
    # was known still carry this request in their slot snapshot and must
    # skip it (see PagedEngine.step pipelining). Until then the request
    # may already have left `_slot_req`: a slot is handed to its successor
    # as soon as this request's end is certain to lie in the dispatches in
    # flight (`_stage_admissions`), and the request lives on in their
    # snapshots, which is where `_walk` finishes it.
    finished: bool = False
    # False while the request is STAGED (prompt handed to the device,
    # prefill advancing inside the megastep scan, first token not yet
    # sampled). `tokens` still holds the prompt until the flip is
    # reaped; _live()/_slack_chunks treat staged requests as not-yet-live.
    live: bool = True
    # Scan iterations this request held a lane while staged, in the
    # dispatches reaped before its flip's (`engine_staged_iterations`).
    staged_rows: int = 0
    # A recurrent family: the prompt position its prefill snapshots its
    # state at (`SlotState.snap_at`; 0: none).
    snap_at: int = 0


def _plane_spec(name: str) -> jax.sharding.PartitionSpec:
    """The ONE semantic sharding for a named SlotState/KVBlock plane,
    resolved from the plane table (`parallel/partition.PAGED_PLANE_SPECS`).

    Replaces the all-replicated `_state_spec` contract: the KV planes
    (cache.k/v and the int8-KV scales) shard their heads axis over the
    tp mesh axis, so the slot KV working set — whose attention reads are
    a large share of the decode step — splits across chips instead of
    replicating onto every one; the genuinely-replicated host planes
    keep canonical `P()`.

    The SPELLING discipline survives from the PR-2 incident: different
    producers of the same plane (install's scatter, grow's pad, the
    step scan, reap's eager active-kill) would otherwise let GSPMD pick
    spelling-different specs for one layout — `P()` vs `P(None, None)`
    — and the pjit cache keys on the spelling, so a program silently
    compiled once per PRODUCER per width. The engine therefore respells
    every plane to its table spec at every dispatch boundary
    (`_canon_state` / `_canon_block` — zero-copy Array rewraps against
    an equivalent sharding), making each (mesh, S, k, width) program
    compile exactly once: guarded by tests/test_paged_spec.py and
    tests/test_paged_sharded.py. The `pspec-flow` lint rule checks
    every producer's resolved spec against the table, so a producer
    that disagrees with the plane table fails lint before it can key a
    second compile.
    """
    return partition.PAGED_PLANE_SPECS[name]


def _has_state(cache: KVCache) -> bool:
    """Whether the cache carries a recurrent state: an `ssm` plane, a
    `conv` plane, or both (a Lightning state has no window, a short
    convolution's whole state is its window)."""
    return cache.ssm is not None or cache.conv is not None


def _forward(model, params, cfg, ids, live, **kw):
    """`model.forward` -> (logits, cache, counts): `counts` is empty but
    for a family with routed experts (`ModelFamily.routed`), which is told
    which tokens are `live` ([B] or [B, T] bool: an idle lane or a pad
    position reaches no expert) and hands back its counts, int32
    [len(model.counters)] (picks computed, experts reached, expert seats
    offered; a family that holds a share of a layer's experts adds the
    picks that landed on it). Every other family is called exactly as
    before, so its programs do not change."""
    if not model.routed:
        return (*model.forward(params, cfg, ids, **kw), ())
    logits, cache, aux = model.forward(params, cfg, ids, live=live,
                                       aux=True, **kw)
    return logits, cache, (aux["counts"],)


def _live_lanes(s: "SlotState", sampling: SamplingParams) -> jax.Array:
    """[S] bool: lanes whose decode token a client will get. A lane is
    active on the device until the host reaps its request's end, which the
    host's budget sets (`max_new_tokens`), dispatches later: a request of
    prompt length p has had its last token once its cache holds
    p + max_new - 1 positions."""
    return s.active & (
        s.cache.length < s.stage_len + (sampling.max_new_tokens - 1))


def _export_block_program(c1: KVCache, off, slot, *, block: int,
                          pool_stride: int = 1) -> KVBlock:
    """Slice one block-aligned KV run out of a prefilled cache — a fresh
    immutable copy the radix tree owns: a block of `block` tokens, or
    (`_export_run`, `block` a stored run's tokens) a whole stored run in
    one array a plane. `slot` selects the sequence:
    admission publishes straight out of the live multi-slot state (the
    prompt region 0..prompt_len-1 is never rewritten by decode, which
    scatters at >= prompt_len). Publishing copies rather
    than aliasing: the source is transient engine state, and a tree that
    aliased it would see its buffers donated away by the next program."""
    zero = jnp.zeros((), jnp.int32)
    off = jnp.asarray(off, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)

    def cut(plane, per=1):
        # Each plane the family's `init_cache` declares, by its own shape
        # ([L, S, H, T, ...]); one it does not have (models/mla.py: a
        # latent cache is one plane) stays None. `per`: the positions one
        # entry of the plane stands for (the pooled plane's `pool_stride`:
        # a block carries the entries of its own positions).
        if plane is None:
            return None
        l, _, h, _, *rest = plane.shape
        return jax.lax.dynamic_slice(
            plane, (zero, slot, zero, off // per) + (zero,) * len(rest),
            (l, 1, h, block // per, *rest))

    return KVBlock(k=cut(c1.k), v=cut(c1.v), ks=cut(c1.ks), vs=cut(c1.vs),
                   pool=cut(c1.pool, pool_stride))


def _stage_program(state: SlotState, slot, ids, true_len, cursor0, seq,
                   rng_raw, snap_at=None) -> SlotState:
    """Arm one slot's staged admission (in-scan chunked prefill): write the
    right-padded prompt into the slot's transcript row and set the
    staged-admission plane — prefill then advances inside the megastep
    scan (`_admission_chunk`), one `prefill_chunk_tokens` chunk per
    decode iteration, until the flip samples the first token.

    `cursor0` is the already-spliced shared-prefix length (0 cold; the
    caller stages cached blocks into the slot's pages via `_stage_block`
    first). The slot's cache length is parked at width-1: the decode
    phase still computes a forward for every slot, and an inactive row
    scatters its (garbage) KV at its length position — parked above the
    prompt region, the staged pages can never be corrupted by it (the
    same clamp position a dead slot writes to). Donates the state.

    A recurrent state has no such region: the slot's `ssm` and `conv` rows
    are zeroed here, whatever its previous tenant left (its overrun rows
    may have moved it), and `snap_at` says where the prefill is to
    snapshot the state (`SlotState`). A prefix hit's snapshot is restored
    AFTER this program (`_restore_state_program`)."""
    zero = jnp.zeros((), jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    width = state.transcript.shape[1]
    transcript = jax.lax.dynamic_update_slice(
        state.transcript, ids, (slot, zero)
    )
    cache = state.cache
    # lint: disable-next=tracer-hygiene
    if _has_state(cache):
        cache = cache._replace(
            ssm=(None if cache.ssm is None
                 else cache.ssm.at[:, slot].set(0.0)),
            conv=(None if cache.conv is None
                  else cache.conv.at[:, slot].set(0.0)))
        state = state._replace(snap_at=state.snap_at.at[slot].set(
            jnp.asarray(snap_at, jnp.int32)))
    return state._replace(
        cache=cache._replace(
            length=cache.length.at[slot].set(width - 1)
        ),
        active=state.active.at[slot].set(False),
        transcript=transcript,
        staged=state.staged.at[slot].set(True),
        stage_cursor=state.stage_cursor.at[slot].set(
            jnp.asarray(cursor0, jnp.int32)
        ),
        stage_len=state.stage_len.at[slot].set(
            jnp.asarray(true_len, jnp.int32)
        ),
        stage_seq=state.stage_seq.at[slot].set(
            jnp.asarray(seq, jnp.int32)
        ),
        stage_rng=state.stage_rng.at[slot].set(rng_raw),
    )


def _stage_block_program(state: SlotState, block, slot, off,
                         tokens=None) -> SlotState:
    """Splice one immutable shared KV block, or a tuple of
    STAGE_RUN_BLOCKS consecutive ones as one run of which the first
    `tokens` tokens count (a short run comes padded with its last block;
    the tuple is concatenated here, on the device), or a STORED RUN (a
    `KVBlock` whose planes already hold `STORED_RUN_BLOCKS` blocks in one
    array each, with `tokens`: nothing is concatenated, and what lies past
    the count keeps the slot's own bytes),
    straight into a slot's pages of the LIVE multi-slot cache at token
    offset `off` (one compiled program per cache width, one more
    where a bucket can share a run and one more where a stored run
    fits). `dynamic_update_slice` CLAMPS a start that would pass the
    plane's end, so the caller hands over only what fits at `off`.
    Donates the state — a private
    accumulator between dispatches — and NEVER the block: tree blocks are
    shared structure (engine/prefix_cache.py), and donating one would free
    KV other admissions still splice from."""
    # The argument's STRUCTURE (one block, or a tuple of them with a
    # count) is fixed at trace time and keys the compiled program; no
    # traced value is read.
    # lint: disable-next=tracer-hygiene
    if not isinstance(block, KVBlock):
        block = jax.tree.map(
            lambda *planes: jnp.concatenate(planes, axis=3), *block)
    zero = jnp.zeros((), jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    off = jnp.asarray(off, jnp.int32)

    def put(plane, new, per=1):
        # `per`: the positions an entry of the plane stands for
        # (`_export_block_program`; a block's own planes say it).
        if plane is None:
            return None
        at = (zero, slot, zero, off // per) + (zero,) * (plane.ndim - 4)
        # lint: disable-next=tracer-hygiene
        if tokens is not None:
            keep = (jnp.arange(new.shape[3])
                    < jnp.asarray(tokens, jnp.int32) // per)
            keep = keep.reshape((1, 1, 1, -1) + (1,) * (plane.ndim - 4))
            new = jnp.where(
                keep, new, jax.lax.dynamic_slice(plane, at, new.shape))
        return jax.lax.dynamic_update_slice(plane, new, at)

    c = state.cache
    return state._replace(cache=c._replace(
        k=put(c.k, block.k), v=put(c.v, block.v),
        ks=put(c.ks, block.ks), vs=put(c.vs, block.vs),
        pool=(None if c.pool is None else put(
            c.pool, block.pool, block.k.shape[3] // block.pool.shape[3])),
    ))


def _restore_state_program(state: SlotState, snap: StateSnapshot,
                           slot) -> SlotState:
    """Put a prefix hit's state snapshot into a slot's rows of the state
    planes: the state a prefill from position 0 would have left at the
    snapshot's boundary, where the staged cursor starts. Donates the
    state, NEVER the snapshot (shared structure of the prefix tree, as a
    `KVBlock` is)."""
    slot = jnp.asarray(slot, jnp.int32)
    c = state.cache

    def put(plane, new):
        if plane is None:  # a state without this plane
            return None
        at = (jnp.zeros((), jnp.int32), slot) + (
            jnp.zeros((), jnp.int32),) * (plane.ndim - 2)
        return jax.lax.dynamic_update_slice(plane, new, at)

    return state._replace(cache=c._replace(
        ssm=put(c.ssm, snap.ssm), conv=put(c.conv, snap.conv)))


def _export_state_program(state: SlotState, slot) -> StateSnapshot:
    """A slot's row of the snapshot planes as a fresh immutable copy the
    prefix tree owns (`SlotState.snap_at` says which position's state it
    is). The source stays the engine's: not donated."""
    slot = jnp.asarray(slot, jnp.int32)

    def cut(plane):
        return None if plane is None else jax.lax.dynamic_slice_in_dim(
            plane, slot, 1, axis=1)

    return StateSnapshot(ssm=cut(state.snap_ssm), conv=cut(state.snap_conv))


def cfg_tmax(cfg, sampling: SamplingParams, bucket: int) -> int:
    return min(bucket + sampling.max_new_tokens, cfg.max_position_embeddings)


def _fresh_state(family, cfg, slots: int, width: int,
                 groups: int = 1) -> SlotState:
    """All-idle SlotState for `slots` slots at cache width `width`,
    unplaced: `PagedEngine._init_state` puts every plane on its table
    sharding, and tests/test_chip_compile.py lowers the step programs
    from these shapes (`jax.eval_shape`) without an engine. `groups` is
    the tp ways: a family that folds its heads into the feature axis
    (models/common.py `folds_heads`) keeps that many head groups on the
    axis the plane table shards."""
    cache = family.init_cache(cfg, slots, width, dtype=cfg.dtype,
                              groups=groups)
    cache = cache._replace(length=jnp.zeros((slots,), jnp.int32))
    # Staged-rng plane shape follows the live PRNG impl's key data
    # (threefry: [2] uint32) so wrap_key_data round-trips exactly.
    key_shape = jax.random.key_data(jax.random.key(0)).shape
    snap = {}
    if _has_state(cache):
        snap = dict(snap_ssm=(None if cache.ssm is None
                              else jnp.zeros_like(cache.ssm)),
                    snap_conv=(None if cache.conv is None
                               else jnp.zeros_like(cache.conv)),
                    snap_at=jnp.zeros((slots,), jnp.int32))
    return SlotState(
        cache=cache,
        tok=jnp.zeros((slots,), jnp.int32),
        active=jnp.zeros((slots,), bool),
        seen=jnp.zeros((slots, cfg.vocab_size), bool),
        transcript=jnp.zeros((slots, cache.k.shape[3]), jnp.int32),
        staged=jnp.zeros((slots,), bool),
        stage_cursor=jnp.zeros((slots,), jnp.int32),
        stage_len=jnp.ones((slots,), jnp.int32),
        stage_seq=jnp.zeros((slots,), jnp.int32),
        stage_rng=jnp.zeros((slots,) + key_shape, jnp.uint32),
        **snap,
    )


def _grow_state_program(state: SlotState, new_len: int, *,
                        pool_stride: int = 1) -> SlotState:
    """Zero-pad the cache's slot axis up to `new_len` (width-bucket growth:
    the live cache is only as wide as the widest ACTIVE request needs —
    see PagedEngine._grow_if_needed — and pads up when a longer prompt
    arrives). Planes without a positions axis (a recurrent family's state
    and snapshot planes) have no width and pass through as they are."""
    grow = new_len - state.cache.k.shape[3]
    pad = [(0, 0), (0, 0), (0, 0), (0, grow), (0, 0)]

    def wider(plane):
        return None if plane is None else jnp.pad(plane, pad[:plane.ndim])

    c = state.cache
    cache = c._replace(k=wider(c.k), v=wider(c.v), ks=wider(c.ks),
                       vs=wider(c.vs))
    # lint: disable-next=tracer-hygiene
    if c.pool is not None:
        # The pooled plane's own positions axis: the new width's entries.
        more = sparse_ops.pool_len(new_len, pool_stride) - c.pool.shape[3]
        cache = cache._replace(pool=jnp.pad(
            c.pool, [(0, 0), (0, 0), (0, 0), (0, more), (0, 0)]))
    return state._replace(
        cache=cache,
        transcript=jnp.pad(state.transcript, [(0, 0), (0, grow)]),
    )


def _sum_counts(*counts) -> tuple:
    """() or (sum,) of a scan iteration's routed-experts counts, each
    itself () or (int32 [len(model.counters)],): the decode's and the
    admission chunk's."""
    found = [c[0] for c in counts if c]
    return (sum(found[1:], found[0]),) if found else ()


def _over_iterations(extra: list, model, admit) -> list:
    """A scanned body's stacked extras with its counts summed over the
    iterations: `admit`'s `served` ([iterations, 3]) and, last, a routed
    family's ([iterations, 3])."""
    counts = (admit is not None) + model.routed
    return [*extra[:len(extra) - counts],
            *(jnp.sum(c, axis=0) for c in extra[len(extra) - counts:])]


def _step_program(params, state: SlotState, rng, *, cfg, sampling,
                  eos_id: int, pad_id: int, model, chunk: int = 1,
                  admit=None):
    """`chunk` decode steps for all S slots (per-row cache offsets).

    Chunking exists because the paged loop is host-driven: every dispatch
    costs a host->device->host round trip plus the host's own reap and
    admission work. One program advancing `chunk` tokens amortizes that
    (by how much on a locally attached chip is ROADMAP D7's question);
    the host reaps
    finished slots at chunk granularity (a slot finishing mid-chunk decodes
    pad tokens into its own — already dead — tail until the chunk ends).

    Returns (state, tokens [chunk, S], active_snapshot [S] int8). The
    snapshot duplicates state.active in a buffer that is NOT part of the
    donated state tuple (int8, so it can never alias the donated bool
    plane): the pipelined engine dispatches program N+1 — donating state
    N — before reading N's results, and reaping needs post-chunk active
    flags that survive that donation. A megastep (`_megastep_program`)
    scans this same body K times and stacks the per-chunk outputs along a
    leading K axis ([K, chunk, S] tokens, [K, S] snapshots) — the
    snapshot/donation invariant is per chunk, so it carries over
    unchanged; only the host reap granularity moves from one chunk to K.

    `admit` (the megastep binds `_admission_chunk` to it) runs at the
    head of EVERY scan iteration, before the decode:
    state -> (state, flipped [S], firsts [S], served [3]). A slot it flips
    live decodes its first token in that same iteration, and the two
    planes come back stacked [chunk, S] after the snapshot, `served`
    summed over the iterations after them. None (a caller that lowers the
    bare chunk) leaves body and outputs as they are.

    A family with routed experts (`ModelFamily.routed`) adds one LAST
    output, its counts summed over the iterations and `admit`'s forward
    passes (`_forward`); only `_live_lanes` route.
    """
    tmax = state.cache.k.shape[3]

    def one(s: SlotState, step_rng):
        extra = ()
        if admit is not None:
            s, *extra = admit(s)
        moe = extra[3:]  # a routed family's counts follow `admit`'s own
        # Inactive/full slots write into their current position; clamp to
        # stay in bounds — the slot is dead or about to be evicted, the
        # data ignored.
        offs = jnp.minimum(s.cache.length, tmax - 1)
        cache = s.cache._replace(length=offs)
        kv_mask = jnp.arange(tmax)[None, :] <= offs[:, None]
        with jax.named_scope("decode"):
            logits, cache, counts = _forward(
                model, params, cfg, s.tok[:, None],
                _live_lanes(s, sampling), cache=cache, kv_mask=kv_mask,
            )
        with jax.named_scope("sample"):
            nxt = sample_step(step_rng, logits[:, 0], s.seen, sampling)
        nxt = jnp.where(s.active, nxt, jnp.asarray(pad_id, jnp.int32))
        still = s.active & (nxt != eos_id)
        lengths = jnp.where(
            s.active, jnp.minimum(s.cache.length + 1, tmax), s.cache.length
        )
        seen = jnp.where(
            s.active[:, None], update_seen(s.seen, nxt), s.seen
        )
        return (
            s._replace(
                cache=cache._replace(length=lengths),
                tok=nxt,
                active=still,
                seen=seen,
            ),
            (nxt, *extra[:3], *_sum_counts(counts, moe)),
        )

    state, (toks, *extra) = jax.lax.scan(
        one, state, jax.random.split(rng, chunk)
    )
    return (state, toks, state.active.astype(jnp.int8),
            *_over_iterations(extra, model, admit))


def _spec_step_program(
    params, state: SlotState, rng, *, cfg, sampling, eos_id: int,
    pad_id: int, model, spec_tokens: int, chunk: int = 1,
    draft_fn=build_drafts, admit=None,
):
    """`chunk` speculative verify windows for all S slots.

    Each scan iteration generalizes the [S, 1] step to a [S, k+1] window:
    prompt-lookup drafts come from the device-side transcript (the paged
    layout is right-padded, so transcript slot == cache slot == position
    id), one forward writes the window's KV at per-row ragged offsets
    (models' scatter path, T = k+1), and `draft.verify_window` walks the
    drafts with exact rejection sampling. Rows accept different counts, so
    per-slot lengths advance raggedly WITHIN a dispatch; the host learns
    each window's emission count from the returned `counts` plane.

    Window invariant (same proof as engine/spec.py): a row's next window
    starts `m >= 1` slots after the previous one and spans k+1 slots, so
    it rewrites every garbage slot a rejected draft left behind before
    anything can attend to it; the causal mask hides the window's own
    not-yet-written tail. Rows that ran past the host's budget clamp
    their window base to `width - 1 - k` (the host force-finishes them at
    max_new; the clamped rewrites are garbage nothing reads) — the same
    role as the plain step's `tmax - 1` clamp, widened for the window.

    Returns (state, emitted [chunk, S, k+1], counts [chunk, S] int32,
    active_snapshot [S] int8). Per (iteration, slot), the first
    `counts[c, s]` columns of `emitted[c, s]` are that window's tokens in
    order (`verify_window`'s valid plane is a contiguous prefix); count 0
    means the slot was inactive. Like the plain step's outputs, all three
    are fresh buffers that survive the next dispatch donating the state.
    `admit` is `_step_program`'s: it runs before each window, and its
    [chunk, S] planes and `served` follow the snapshot; a routed family's
    counts come last, as there.
    """
    k = spec_tokens
    width = state.cache.k.shape[3]
    pos_w = jnp.arange(width, dtype=jnp.int32)[None, :]
    offs_k1 = jnp.arange(k + 1, dtype=jnp.int32)[None, :]

    def one(s: SlotState, step_rng):
        extra = ()
        if admit is not None:
            s, *extra = admit(s)
        moe = extra[3:]
        offs = jnp.minimum(s.cache.length, width - 1 - k)  # [S] window base
        # Drafts: the pending last token sits at transcript slot `offs`;
        # an anchor must be filled AND have k filled continuation slots
        # (a frontier-adjacent anchor would propose unwritten slots).
        prev = jnp.take_along_axis(
            s.transcript, jnp.maximum(offs - 1, 0)[:, None], axis=1
        )[:, 0]
        match_valid = pos_w <= (offs - k)[:, None]
        drafts = draft_fn(s.transcript, match_valid, prev, s.tok, k)

        # One forward over [last, d_1..d_k]: KV scatters at slots
        # offs..offs+k, queries attend causally (key slot <= query slot) —
        # history below `offs` is real, the window prefix was just
        # written, everything above is masked. Right-padding means no
        # kv_mask is needed (no interior pad holes) and positions default
        # to the slot indices.
        feed = jnp.concatenate([s.tok[:, None], drafts], axis=1)  # [S, k+1]
        with jax.named_scope("decode"):
            logits, cache, counts = _forward(
                model, params, cfg, feed, _live_lanes(s, sampling),
                cache=s.cache._replace(length=offs),
            )
        with jax.named_scope("sample"):
            emitted, valid, seen, hit_eos = verify_window(
                step_rng, logits, drafts, s.seen, s.active, sampling,
                eos_id, pad_id,
            )
        # Emitted token i lands at transcript slot offs+1+i (the slot its
        # KV will occupy once it is fed). Clamp-overrun rows route their
        # writes out of bounds and drop them.
        slots = (offs + 1)[:, None] + offs_k1  # [S, k+1]
        valid = valid & (slots < width)
        m = jnp.sum(valid, axis=1).astype(jnp.int32)  # [S] window emissions
        rows = jnp.arange(s.tok.shape[0], dtype=jnp.int32)[:, None]
        transcript = s.transcript.at[
            rows, jnp.where(valid, slots, width)
        ].set(emitted, mode="drop")
        new_tok = jnp.where(
            m > 0,
            jnp.take_along_axis(
                emitted, jnp.maximum(m - 1, 0)[:, None], axis=1
            )[:, 0],
            s.tok,
        )
        lengths = jnp.where(s.active, offs + m, s.cache.length)
        return (
            s._replace(
                cache=cache._replace(length=lengths),
                tok=new_tok,
                active=s.active & ~hit_eos,
                seen=seen,
                transcript=transcript,
            ),
            (emitted, m, *extra[:3], *_sum_counts(counts, moe)),
        )

    state, (emitted, counts, *extra) = jax.lax.scan(
        one, state, jax.random.split(rng, chunk)
    )
    return (state, emitted, counts, state.active.astype(jnp.int8),
            *_over_iterations(extra, model, admit))


def _prefill_pass(params, s: SlotState, width: int, *, cfg, sampling, model,
                  eos_id: int, pad_id: int, prefill_chunk: int):
    """One forward pass that serves the `width` oldest staged slots (by
    `stage_seq`) a chunk of `prefill_chunk` positions each, as a ragged
    batch: the weights are read once for all of them and not once a slot.

    Row i forwards the next `prefill_chunk` prompt ids of its slot's
    transcript row onto that slot's pages of the live cache, in place
    (`forward`'s `rows`: KV scatters at the slot's own cursor —
    out-of-range pad tails of a final chunk are dropped by the scatter,
    never clamped into real pages — and the attention reads that slot's
    row alone; slicing a slot's pages out and splicing them back made the
    compiler relay the whole cache four times a chunk). A row with no
    staged slot behind it (fewer staged than `width`) addresses a cache
    row past the last: a scatter drops what lies out of bounds, so it
    writes no key, value, scale or state anywhere, and none of its
    positions is `live`. When a slot's cursor covers its true length, the
    flip: sample the first token from the last real position's logits
    with the staged rng and the full-prompt seen mask, then mark the slot
    live (length=true_len, transcript gains the first token at its cache
    slot, active unless eos). The computation per real position is that
    of one whole-prompt forward (same KV values, same causal key set, pad
    tails masked) whoever shares the pass, so the flipped slot's greedy
    stream is bit-identical to the bucketed engine's.

    Returns `_admission_chunk`'s tuple."""
    n_slots = s.tok.shape[0]
    c = prefill_chunk
    # FIFO service: the staged slots with the lowest staging sequence
    # numbers (slot INDEX would let churn restage a lower slot and
    # starve an earlier admission's prefill indefinitely).
    big = jnp.iinfo(jnp.int32).max
    _, slot = jax.lax.top_k(
        -jnp.where(s.staged, s.stage_seq, big), width)
    real = s.staged[slot]  # [W]: fewer staged than rows leaves some bare
    # A cache row past the last for a bare row, each its own.
    nowhere = n_slots + jnp.arange(width, dtype=jnp.int32)
    rows = jnp.where(real, slot, nowhere)
    cur = s.stage_cursor[slot]
    tl = s.stage_len[slot]
    at = cur[:, None] + jnp.arange(c, dtype=jnp.int32)  # [W, c]
    ids = s.transcript[
        slot[:, None], jnp.minimum(at, s.transcript.shape[1] - 1)]
    # Pad-tail positions clamp to the last real position; their
    # outputs/KV are garbage nothing reads (causal frontier + the
    # decode kv_mask).
    logits, cache, counts = _forward(
        model, params, cfg, ids, (at < tl[:, None]) & real[:, None],
        cache=s.cache._replace(length=cur), rows=rows,
        positions=jnp.minimum(at, tl[:, None] - 1),
    )
    snap = {}
    # lint: disable-next=tracer-hygiene
    if s.snap_at is not None:
        # A chunk that ends at its slot's snapshot position (all of
        # it real: the position is below the prompt's end) copies the
        # state it leaves into the slot's snapshot rows: a row at a
        # time, each sliced out and put back where it lies (a gather
        # of the rows makes the compiler copy the plane's halves).
        hit = real & (cur + c == s.snap_at[slot])

        def keep(plane, new):
            if plane is None:
                return None
            for i in range(width):
                row = jax.lax.dynamic_slice_in_dim(new, slot[i], 1, 1)
                old = jax.lax.dynamic_slice_in_dim(plane, slot[i], 1, 1)
                plane = jax.lax.dynamic_update_slice_in_dim(
                    plane, jnp.where(hit[i], row, old), slot[i], 1)
            return plane

        snap = dict(snap_ssm=keep(s.snap_ssm, cache.ssm),
                    snap_conv=keep(s.snap_conv, cache.conv))
    done = real & (cur + c >= tl)
    li = jnp.clip(tl - 1 - cur, 0, c - 1)
    # A dynamic slice a row: the TPU's compiler splits a gather over
    # [W, c, V] into gathers over copies of the logits' halves.
    last = jnp.stack([
        jax.lax.dynamic_index_in_dim(logits[i], li[i], 0, keepdims=False)
        for i in range(width)])
    valid = jnp.arange(s.transcript.shape[1]) < tl[:, None]
    seen0 = seen_mask_from_ids(s.transcript[slot], valid, cfg.vocab_size)
    first = jax.vmap(lambda raw, logit, seen: sample_step(
        jax.random.wrap_key_data(raw), logit[None], seen[None],
        sampling)[0])(s.stage_rng[slot], last, seen0)
    # The flip writes at the slots that are done and nowhere else.
    flip = jnp.where(done, slot, nowhere)
    new = s._replace(
        cache=cache._replace(length=s.cache.length.at[flip].set(tl)),
        tok=s.tok.at[flip].set(first),
        active=s.active.at[flip].set(first != eos_id),
        seen=s.seen.at[flip].set(update_seen(seen0, first)),
        transcript=s.transcript.at[flip, tl].set(first),
        staged=s.staged.at[flip].set(False),
        stage_cursor=s.stage_cursor.at[rows].set(cur + c),
        **snap,
    )
    return (
        new,
        jnp.zeros((n_slots,), jnp.bool_).at[flip].set(True),
        jnp.full((n_slots,), pad_id, jnp.int32).at[flip].set(first),
        jnp.stack([jnp.ones((), jnp.int32),
                   jnp.sum(real, dtype=jnp.int32),
                   (jnp.sum(s.staged, dtype=jnp.int32) > 1).astype(jnp.int32)]),
        *counts,
    )



def _admission_chunk(params, s: SlotState, *, wide: bool, **statics):
    """The admission phase at the head of every decode iteration (the
    megastep hands it to the per-token scan body as `admit`; staging
    happens only between dispatches, so every staged slot is known at a
    megastep's entry and is served, oldest first, a chunk a pass until
    none is left): one `_prefill_pass` for the oldest staged slots, as
    wide as what is staged asks for.

    Two or more staged: the pass has `PASS_ROWS // prefill_chunk` rows (4
    at 32 positions; never more than there are slots), and the slots past
    them wait a pass. One staged: a pass of one row, because the bare rows
    of the wide one are not free (on a v5e gpt2-xl's pass of four rows
    takes 13.4 ms whatever stands behind them, and its pass of one 4.6).
    Nothing staged: a `lax.cond` skips all of it, so the steady-state
    decode iteration pays nothing for it. Not `wide` (the caller's word:
    `_megastep_program` says it for every rung but the first): the pass of
    one row whatever is staged, the oldest first.

    Returns (state, flipped [S] bool, firsts [S] int32, served int32 [3])
    — the first two hot at the slots that flipped, `served` the passes
    run, the slot-chunks they served and the passes that found two or
    more slots staged, (1, rows with a slot, 0 or 1) or zeros —
    and, for a family with routed experts, the pass's counts (`_forward`;
    a pad tail and a bare row route nowhere, and an expert counts as
    reached once a pass, not once a row)."""
    model, n_slots = statics["model"], s.tok.shape[0]
    width = max(1, min(n_slots, PASS_ROWS // statics["prefill_chunk"]))

    def idle(s: SlotState):
        return (s, jnp.zeros((n_slots,), jnp.bool_),
                jnp.full((n_slots,), statics["pad_id"], jnp.int32),
                jnp.zeros((3,), jnp.int32),
                *((jnp.zeros((len(model.counters),), jnp.int32),)
                  if model.routed else ()))

    def serve(s: SlotState, width: int, ours):
        return jax.lax.cond(
            ours, partial(_prefill_pass, params, width=width, **statics),
            idle, s)

    staged = jnp.sum(s.staged, dtype=jnp.int32)
    if width == 1 or not wide:
        return serve(s, 1, staged > 0)
    # A `cond` a width, one after the other, and not one `conditional` of
    # three branches: in that, the compiler copies a recurrent family's
    # whole state plane between the layers of a pass (`tests/
    # test_chip_compile.py` holds it to none). The count was taken before
    # either, so at most one of them runs.
    s, flipped_w, firsts_w, served_w, *counts_w = serve(
        s, width, staged > 1)
    s, flipped, firsts, served, *counts = serve(s, 1, staged == 1)
    return (s, flipped | flipped_w, jnp.where(flipped_w, firsts_w, firsts),
            served + served_w, *(a + b for a, b in zip(counts, counts_w)))


def _chunk_program(params, s: SlotState, rng, *, cfg, sampling, eos_id: int,
                   pad_id: int, model, spec_tokens: int, chunk: int,
                   prefill_chunk: int, draft_fn, wide: bool):
    """One chunk of a megastep, the body its scan repeats:
    `_step_program` / `_spec_step_program` (selected statically by
    `spec_tokens`) with `_admission_chunk` bound to its `admit`."""
    def admit(s: SlotState):
        with jax.named_scope("prefill_chunk"):
            return _admission_chunk(
                params, s, cfg=cfg, sampling=sampling, model=model,
                eos_id=eos_id, pad_id=pad_id, prefill_chunk=prefill_chunk,
                wide=wide,
            )

    body = dict(cfg=cfg, sampling=sampling, eos_id=eos_id, pad_id=pad_id,
                model=model, chunk=chunk, admit=admit)
    if spec_tokens:
        s, *outs = _spec_step_program(
            params, s, rng, spec_tokens=spec_tokens, draft_fn=draft_fn,
            **body,
        )
    else:
        s, *outs = _step_program(params, s, rng, **body)
    return s, tuple(outs)


# Jitted for its TRACE, not for a program of its own (it is only ever called
# inside `_megastep_program`, where the compiler inlines it): a jit keeps
# what it traced by its arguments' shapes and its statics, and every rung of
# the ladder scans the same chunk over the same state, so a width's rungs
# trace the three forward passes of a chunk (the decode step and the two
# prefill passes) once and not once a rung. Tracing is most of what a start
# that finds its programs in the compile cache still pays for a megastep.
_chunk = jax.jit(_chunk_program, static_argnames=(
    "cfg", "sampling", "eos_id", "pad_id", "model", "spec_tokens", "chunk",
    "prefill_chunk", "draft_fn", "wide"))


def _megastep_program(params, state: SlotState, rngs, *, cfg, sampling,
                      eos_id: int, pad_id: int, model, spec_tokens: int,
                      chunk: int, prefill_chunk: int,
                      draft_fn=build_drafts):
    """K `chunk`-token steps back-to-back on device: one dispatch, one
    readback, K*chunk decode iterations.

    `rngs` is a stacked [K] key array of sequential host splits, one a
    chunk, so chunk j of a megastep consumes the key a dispatch of chunk j
    alone would have — outputs are bit-identical at every K (the K axis
    is encoded in the rngs shape, so each K compiles its own program; the
    warmed domain is widths x the `megastep_ladder` rungs).

    The scan body is `_chunk_program`: `_step_program`/`_spec_step_program`
    (selected statically by `spec_tokens`) with `_admission_chunk` bound to
    its `admit`, which serves the oldest staged slots a prefill chunk of
    `prefill_chunk` positions each BEFORE each decode iteration, so a slot
    joins the train at a scan-iteration boundary, not a chunk or dispatch
    boundary. The pass that serves several slots at once is in the program
    of ONE chunk (K = 1) alone: that is the rung the controller dispatches
    while work waits for a slot (`engine_one_chunk_dispatches`: 88 to 100%
    of a loaded cell's dispatches), which is when prompts are staged
    together; the longer rungs are what an idle server grows into, they
    serve one staged slot a pass, and a start is spared a third forward
    pass in three of every four megastep programs it traces and loads
    (`setup_s`: +9 s of 50 with the wide pass in every rung). The
    per-chunk outputs stack along a leading K axis:

    - plain: (state, toks [K, chunk, S], active [K, S] int8, dead int32,
    - spec:  (state, emitted [K, chunk, S, k+1], counts [K, chunk, S],
              active [K, S] int8, dead int32,
    - then:   flipped [K, chunk, S] bool, firsts [K, chunk, S] int32) — per
      decode iteration (a row of the token plane), the slots whose staged
      prefill completed at its head and the first token each sampled, so
      the batched reap learns admission outcomes without an extra sync
      and starts a slot's decode walk at that row;
    - served int32 [3]: the prefill passes the dispatch ran, the
      slot-chunks they served and the passes that found two or more slots
      staged (`_admission_chunk`).
    - a family with routed experts: the above plus, LAST, its
      counts summed over the dispatch (`_forward`).

    `active[j]` is the post-chunk-j snapshot — the same fresh non-donated
    plane the single-chunk program returns, K of them — so the host's
    batched reap can walk the [K*chunk, S] token plane with the final
    snapshot and the donation/pipelining invariants of `_step_program`
    carry over unchanged.

    `dead` is the on-device early-dead account in TOKEN positions: a slot
    that finishes in chunk j cannot be reaped until the megastep boundary,
    so it burns one pad lane per remaining scan iteration — and in spec
    mode each lane is a verify window whose forward computes
    spec_tokens+1 token positions. dead = chunk * lane_tokens * sum over
    j<K-1 of |slots LIVE by chunk j but inactive after it| (live =
    active at entry, or flipped live by an admission at any row of
    chunk j or an earlier one — a flip-then-eos inside one megastep
    strands lanes too;
    lane_tokens = spec_tokens+1 when speculating, else 1) — zero at K=1
    (the host reaps every chunk), and exactly the positions a host reap
    after every chunk would have freed. Slots already dead at entry
    (empty, or reaped earlier) are capacity idle and do not count,
    and a staged slot's pre-flip iterations are admission work, never
    stranded decode.
    """
    started = state.active  # read before the scan consumes the donation

    state, outs = jax.lax.scan(
        partial(_chunk, params, cfg=cfg, sampling=sampling, eos_id=eos_id,
                pad_id=pad_id, model=model, spec_tokens=spec_tokens,
                chunk=chunk, prefill_chunk=prefill_chunk, draft_fn=draft_fn,
                wide=rngs.shape[0] == 1),
        state, rngs)
    moe = ()
    if model.routed:
        *outs, moe = outs  # [K, 3] routed-experts counts, one per chunk
        moe = (jnp.sum(moe, axis=0),)
    # [K, chunk, S] admission planes, and the passes' [K, 2] counts.
    *outs, flipped, firsts, served = outs
    active = outs[-1]  # [K, S] int8 post-chunk snapshots
    lane_tokens = chunk * ((spec_tokens + 1) if spec_tokens else 1)
    # A lane is stranded from the first chunk it is dead AFTER having
    # been live: live = active at entry, or flipped live by an
    # admission in that chunk or an earlier one (a flip-then-eos inside
    # one megastep burns real pad lanes too). Pre-flip staged iterations
    # are admission work, not stranded decode, and never count.
    live = started[None, :] | (
        jnp.cumsum(flipped.any(axis=1).astype(jnp.int32), axis=0) > 0
    )
    dead = jnp.asarray(lane_tokens, jnp.int32) * jnp.sum(
        (live[:-1] & (active[:-1] == 0)).astype(jnp.int32)
    )
    return (state, *outs, dead, flipped, firsts, jnp.sum(served, axis=0),
            *moe)


class _LaneLengths:
    """The host's copy of the device's `cache.length` and `active`, as of
    the last dispatch REAPED, for an engine whose decode attention goes by
    a lane's length (`ops/attention.py` `quant_decode_attention`): what
    `engine_attn_positions_read` is counted from, with no read-back of its
    own. The device moves both planes inside a dispatch by rules the reap's
    planes determine (`_step_program`: a flip sets a lane to its prompt's
    length, an active lane gains a position a row until its token is eos),
    and the host moves them between dispatches (`_stage_program` parks a
    lane at the width's last position, a reaped end kills its lane): the
    host's moves are queued (`ops`) and ride with the next dispatch sent
    (`sent`, in the order of `PagedEngine._inflight`), so that a reap
    replays them in the device's order."""

    def __init__(self, slots: int):
        self.length = np.zeros((slots,), np.int64)
        self.active = np.zeros((slots,), bool)
        self.ops: List[Tuple[int, int]] = []  # (slot, parked at | -1: killed)
        # A dispatch in flight: (the moves before it, its width, its width
        # if its attention went by lengths, else 0).
        self.sent: List[Tuple[List[Tuple[int, int]], int, int]] = []

    def dispatched(self, width: int, held: int) -> None:
        self.sent.append((self.ops, width, held))
        self.ops = []

    def replay(self, ops, toks, flipped, firsts, prompt_lens, eos: int,
               width: int) -> int:
        """One dispatch's rows ([rows, S] planes) after the host's `ops`:
        the positions the kernel fetched, over every lane-step."""
        for slot, parked in ops:
            self.active[slot] = False
            if parked >= 0:
                self.length[slot] = parked
        length, active, read = self.length, self.active, 0
        for row in range(toks.shape[0]):
            flip = flipped[row]
            if flip.any():
                length = np.where(flip, prompt_lens, length)
                active = np.where(flip, firsts[row] != eos, active)
            read += int(attention_ops.quant_decode_positions(
                np.minimum(length, width - 1) + 1, width).sum())
            length = np.where(active, np.minimum(length + 1, width), length)
            active = active & (toks[row] != eos)
        self.length, self.active = length, active
        return read


def rows_to_certain_end(req: Optional[_Request], tmax: int,
                        rows_in_flight: int) -> Optional[int]:
    """Scan iterations (rows) still to DISPATCH before `req`'s end is
    certain to lie inside dispatched work: its token budget left (the
    `max_new` cap, or the `tmax` clause `_walk` finishes it by) less
    `rows_in_flight`, the rows dispatched with it in its lane and not yet
    reaped. A live lane gains exactly one token a row in the plain step
    and at least one a verify window in the speculative one, so the bound
    is an upper limit, never an overshoot: eos or over-acceptance can only
    end the request sooner. 0 means the end is already in flight. None for
    no request, a finished one, or a STAGED one: its `tokens` still hold
    the prompt, and it bounds nothing until its flip is reaped.

    The one horizon of the host's scheduling: `_slack_chunks` sizes K by
    the least of it over the slots, and `_stage_admissions` hands a slot
    on where it is 0."""
    if req is None or req.finished or not req.live:
        return None
    have = len(req.tokens) + rows_in_flight
    return max(0, min(req.max_new - have, tmax - req.prompt_len - have))


def next_megastep_k(current: int, ladder: Sequence[int], pending: int,
                    slack_chunks: Optional[int] = None) -> int:
    """TTFT-aware megastep size controller (pure; one decision per
    dispatch). `ladder` is the warmed rung list (`megastep_ladder`,
    ascending, starting at 1).

    Idle pending queue: nobody is waiting on a boundary, so grow one
    rung toward `megastep_max` and amortize the host round trip further
    (the accepted tradeoff: a FUTURE arrival's worst-case admission wait
    is K*chunk device steps).

    Work waiting for a slot: shrink K — but against the admission
    OPPORTUNITY, not unconditionally. A waiting request can only be
    staged when a slot frees, and the next GUARANTEED free is
    `slack_chunks` device chunks away (the engine derives it from the
    live slots' remaining token budgets net of already-dispatched work —
    see `_slack_chunks`). Boundaries more frequent than that admit
    nobody; they only forfeit amortization. So K is capped at the
    largest rung fitting the slack: megasteps stay wide while no lane
    can free and align a boundary with the next guaranteed slot-free so
    staging starts promptly. Early finishes (eos, spec over-acceptance)
    can still strand a lane for up to the in-progress K*chunk steps —
    that exposure is the dead-lane account
    (`megastep_dead_lane_tokens`).

    The floor is one chunk: a slack of 1 (or 0, an end already due)
    gives K = 1, so the dispatch ends where the next answer ends and the
    slot is handed on there. Every row a lane decodes past its answer's
    end is thrown away (`engine_overrun_lane_steps`), where a boundary
    costs one more dispatch of a warmed program, sent while the
    dispatches in flight compute. slack_chunks=None (no live slot to
    bound: only staged requests) keeps the ladder's second rung, because
    nothing can be handed on at an earlier boundary."""
    if len(ladder) <= 1:
        return ladder[0] if ladder else 1
    if pending <= 0:
        i = ladder.index(current) if current in ladder else 0
        return ladder[min(len(ladder) - 1, i + 1)]
    cap = ladder[1] if slack_chunks is None else max(1, slack_chunks)
    return max(k for k in ladder if k <= cap)


class PagedEngine:
    """Slot-scheduled serving engine with mid-decode admission.

    Host API (single-threaded; wrap in an executor for async serving):
      submit(prompt) -> request id
      step() -> list[(rid, text)] — stage pending into free slots, send
                one decode dispatch, return requests whose end was reaped
      drain() -> dict[rid, text] — run until no work remains
    """

    def __init__(self, config: EngineConfig, devices: Optional[Sequence] = None,
                 slots: Optional[int] = None, chunk: int = 16,
                 inflight: int = 2, megastep: int = 1,
                 megastep_max: int = 0, prefix_cache: bool = False,
                 prefix_cache_blocks: int = 512,
                 prefix_block_tokens: int = BLOCK_TOKENS,
                 prefill_chunk_tokens: int = 32,
                 stored_run_blocks: int = STORED_RUN_BLOCKS):
        if prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens is the size of an in-scan prefill "
                f"chunk and must be >= 1, not {prefill_chunk_tokens}"
            )
        enable_compilation_cache()
        self.config = config
        # Tokens per dispatched step program — see _step_program. Mid-chunk
        # admissions wait at most chunk device steps (ms-scale); host
        # round-trips shrink by the same factor.
        self.chunk = max(1, chunk)
        # Dispatch programs kept in flight: at 2 the host dispatches
        # megastep N+1 before reading N's tokens, so the readback and
        # the host's reap overlap the next program's compute instead of
        # serializing every dispatch. 1 = the dispatch-sync-reap loop.
        self.inflight_limit = max(1, inflight)
        # Device-resident megastep decode: `megastep` is the controller's
        # starting K (chunks fused per dispatch), `megastep_max` its
        # ceiling (0 = follow `megastep`). The controller moves along the
        # warmed `megastep_ladder` rungs — see next_megastep_k.
        self.megastep_max = effective_megastep_max(megastep, megastep_max)
        self.megastep_ks = megastep_ladder(self.megastep_max)
        self._megastep_initial = max(
            k for k in self.megastep_ks if k <= max(1, megastep)
        )
        self.megastep_k = self._megastep_initial
        self.family, self.cfg = registry.resolve(
            config.model, config.dtype, config.param_dtype
        )
        if config.kv_quant:
            self.cfg = dataclasses.replace(self.cfg, quant_kv=True)
        # Speculative decoding: k prompt-lookup drafts verified per slot
        # per scan iteration (see _spec_step_program). 0 = the plain
        # one-token chunked step.
        self.spec = max(0, config.spec_tokens)
        if (
            self.spec
            and self.family.name == "gpt2_moe"
            and self.cfg.capacity_factor < self.cfg.num_experts
        ):
            # Capacity drops make a token's output depend on its
            # forward-pass companions, so the verify window would sample
            # from different distributions than step decode.
            raise ValueError(
                "spec_tokens with an MoE model requires capacity_factor >= "
                "num_experts (no token dropping; models/moe.py caveat)"
            )
        if config.ep > 1 and not self.family.expert_parallel:
            # Silently replicating the ep ways into dp would waste an
            # ep-factor of devices with no signal.
            raise ValueError(
                f"ep={config.ep} requires an MoE family whose expert axis "
                f"shards over ep; the {self.family.name!r} family of "
                f"{config.model!r} has none "
                f"(a routed family's grouped product takes whole expert "
                f"stacks, and the exchange between chips that each hold a "
                f"share of a layer's experts is not built)"
            )
        if config.tp > 1 and self.family.latent_cache:
            raise ValueError(
                f"tp={config.tp}: {config.model!r} caches a latent with "
                f"no heads axis (models/mla.py), and the paged KV planes "
                f"shard their heads axis over tp; a latent cache is "
                f"replicated over the chips that share a layer, each "
                f"serving its own lanes"
            )
        if self.family.recurrent_state and (self.spec or config.tp > 1):
            raise ValueError(
                f"{config.model!r} carries a recurrent state in its cache "
                f"(models/mamba2.py): spec_tokens={self.spec} needs a "
                f"verify window that rolls the state back past a rejected "
                f"draft, and tp={config.tp} the state's heads sharded "
                f"beside the mixer's projections; neither is built"
            )
        # The paged KV plane table splits the heads axis evenly across tp
        # shards (partition.PAGED_PLANE_SPECS) — reject non-divisor tp
        # ways up front with the supported ladder, before any device work.
        # GQA models shard KV heads (the plane axis); dense models' KV
        # head count is their head count.
        partition.validate_tp_heads(
            getattr(self.cfg, "num_kv_heads", None) or self.cfg.num_heads,
            config.tp, config.model,
        )
        self.mesh = mesh_lib.make_mesh(
            {"tp": config.tp, "ep": config.ep, "dp": -1}, devices=devices
        )
        self.tp = int(self.mesh.shape.get("tp", 1))
        self.ep = int(self.mesh.shape.get("ep", 1))
        self.tokenizer = tok_lib.load_gpt2_tokenizer(
            config.vocab_path, config.merges_path, config.tokenizer_json
        )
        self.slots = slots or max(config.batch_buckets)
        # Clamp the prompt bucket so bucket + max_new always fits the
        # position table (long prompts keep their tail via submit()'s
        # truncation). Without this,
        # a request reaching tmax mid-decode would have its newest KV slot
        # silently overwritten by the clamped scatter in `_step_program`.
        # Spec mode keeps its verify windows inside the table too: the
        # widest window the host still consumes from ends k-1 slots past
        # the last budgeted token.
        self._spec_extra = max(0, self.spec - 1)
        self.bucket = min(
            max(config.length_buckets),
            self.cfg.max_position_embeddings
            - config.sampling.max_new_tokens - self._spec_extra,
        )
        if self.bucket < 1:
            raise ValueError(
                f"max_new {config.sampling.max_new_tokens} "
                + (f"+ spec overhang {self._spec_extra} " if self.spec else "")
                + f"leaves no room for any prompt token in the position "
                f"table {self.cfg.max_position_embeddings}"
            )
        self.tmax = cfg_tmax(self.cfg, config.sampling, self.bucket)
        # Cache-width buckets: one admissible width per prompt bucket
        # (bucket + max_new, plus the verify window's k-1 overhang in spec
        # mode). The live cache runs at the width the widest ACTIVE request
        # needs instead of always tmax — every decode step's attention
        # streams the whole slot axis, so a cluster of short prompts pays
        # ~half the KV bytes of the worst case (the bucketed engine's
        # segmented decode, ported to the slot world).
        self.widths = sorted({
            cfg_tmax(self.cfg, config.sampling, min(b, self.bucket))
            + self._spec_extra
            for b in config.length_buckets
        })
        # The warmed prompt buckets (`_stage` compiles per admissible
        # (bucket, width) pair).
        self.buckets = sorted({
            min(b, self.bucket) for b in config.length_buckets
        })
        # Shared-prefix KV cache (engine/prefix_cache.py): a radix tree
        # of immutable device-resident block runs; admission splices the
        # longest cached prefix and prefills only the suffix.
        self.prefix_block_tokens = max(1, prefix_block_tokens)
        # Blocks in a stored run of the tree (program_inventory.py
        # STORED_RUN_BLOCKS; tests shrink it as they shrink the block). An
        # engine none of whose buckets is a run long never publishes one,
        # and its programs are the ones they were.
        self.stored_run_blocks = stored_run_blocks if any(
            bucket_holds_stored_run(
                t, self.prefix_block_tokens, stored_run_blocks)
            for t in self.buckets) else 0
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_cache:
            self.prefix_cache = PrefixCache(
                block_tokens=self.prefix_block_tokens,
                max_blocks=max(1, prefix_cache_blocks),
                run_blocks=self.stored_run_blocks,
                # A recurrent family's state snapshots: one is every
                # state-space layer's state of one sequence (8.5 MB at
                # Nemotron-3-Nano's widths, where a block of its one
                # attention layer's keys and values is 16 KB), so the tree
                # holds one for every STATE_BLOCKS_A_SNAPSHOT blocks of
                # its budget, and never more than two a slot (a
                # context's stride snapshot and its branch point for as
                # many contexts as there are slots to ask from them: with
                # MiniCPM-SALA's 4,096 blocks and 12.6 MB a snapshot, 96
                # and 1.2 GB, not 256 and 3.2).
                max_snapshots=(
                    max(1, min(prefix_cache_blocks // STATE_BLOCKS_A_SNAPSHOT,
                               2 * self.slots))
                    if self.family.recurrent_state else 0),
            )
        # In-scan chunked prefill: admissions are STAGED into SlotState
        # and prefill advances inside the megastep scan, one chunk of this
        # many positions per decode iteration. The budget is
        # clamped so a final chunk's pad-tail ids still fit the transcript
        # slice window (the slice starts at cursor <= bucket-1 and must
        # end inside the cache width = bucket + max_new + spec overhang).
        self.prefill_chunk = min(
            prefill_chunk_tokens,
            config.sampling.max_new_tokens + self._spec_extra + 1,
        )
        if self.spec and config.sampling.max_new_tokens < 2:
            # The staged slot's parked write position (width-1-k in
            # spec mode) must sit above the prompt region; max_new=1
            # would park it inside the staged pages.
            raise ValueError(
                "spec_tokens requires max_new_tokens >= 2 (staged-slot "
                "parking position)"
            )
        if config.draft_source not in ("prompt_lookup", "ngram"):
            raise ValueError(
                f"unknown draft_source {config.draft_source!r}; expected "
                "'prompt_lookup' or 'ngram'"
            )
        self._draft_fn = (
            build_drafts_ngram if config.draft_source == "ngram"
            else build_drafts
        )

        if config.checkpoint:
            sd = convert.load_safetensors(config.checkpoint)
            params = self.family.params_from_hf(sd, self.cfg)
        else:
            log.warning("no checkpoint — randomly initialized %s", config.model)
            params = self.family.init_params(jax.random.key(config.seed), self.cfg)
        if config.quant:
            if config.quant != "int8":
                raise ValueError(f"unsupported quant mode {config.quant!r}")
            params = quant_lib.quantize_params(params, self.family.name)
        rules = partition.RULES_FOR[self.family.name]
        self.params = partition.shard_tree(params, self.mesh, rules)

        # Every program is a jit of `named_partial(fn, ...)`: a FRESH
        # partial per engine (jax.jit shares one program cache across
        # wrappers of the same bare function, and the inventory guard's
        # exact counts are per engine), carrying the function's name, so a
        # device trace reads `jit__megastep_program`, not `jit__unknown`.
        statics = dict(cfg=self.cfg, sampling=config.sampling, model=self.family)
        # With the shared-prefix cache disabled the block programs warm
        # zero entries, and the inventory guard sees one stable program set.
        # A family that keeps pooled keys beside its keys
        # (`KVCache.pool`) says how many positions an entry stands for;
        # every other family's block programs are the ones they were.
        pooled = ({"pool_stride": self.cfg.pool_stride}
                  if hasattr(self.cfg, "pool_stride") else {})
        if pooled and self.prefix_block_tokens % self.cfg.pool_stride:
            raise ValueError(
                f"{config.model!r} pools a key every "
                f"{self.cfg.pool_stride} positions: a prefix block of "
                f"{self.prefix_block_tokens} tokens would cut an entry")
        self._export_block = jax.jit(named_partial(
            _export_block_program, block=self.prefix_block_tokens, **pooled,
        ))
        # A stored run leaves the slot whole, one launch (zero warmed
        # programs where no bucket is a run long).
        self._export_run = jax.jit(named_partial(
            _export_block_program,
            block=max(1, self.stored_run_blocks) * self.prefix_block_tokens,
            **pooled,
        ))
        # The live SlotState is donated on every program that replaces it,
        # so admissions and steps update the multi-slot KV cache in place
        # instead of copying it (a full cache round-trip of HBM traffic
        # otherwise). The ONE decode program: every rung of the ladder,
        # K=1 included, dispatches through it; the K axis rides in on the
        # stacked rng shape, so each warmed rung is one compiled program
        # per width.
        self._megastep = jax.jit(
            named_partial(
                _megastep_program, eos_id=self.tokenizer.eos_id,
                pad_id=self.tokenizer.pad_id, chunk=self.chunk,
                spec_tokens=self.spec, prefill_chunk=self.prefill_chunk,
                draft_fn=self._draft_fn, **statics),
            donate_argnums=(1,),
        )
        # `_stage_block` donates ONLY the state accumulator, never the
        # shared tree block.
        self._stage = jax.jit(
            named_partial(_stage_program), donate_argnums=(0,),
        )
        self._stage_block = jax.jit(
            named_partial(_stage_block_program), donate_argnums=(0,),
        )
        # A recurrent family's snapshot programs (zero warmed programs
        # for every other family and without the prefix cache): the
        # restore donates the state, never the tree's snapshot.
        self._restore_state = jax.jit(
            named_partial(_restore_state_program), donate_argnums=(0,),
        )
        self._export_state = jax.jit(named_partial(_export_state_program))
        # No statics to bind; a fresh partial all the same (see above).
        self._grow = jax.jit(
            named_partial(_grow_state_program, **pooled),
            static_argnums=(1,), donate_argnums=(0,),
        )
        # Bulk-scoring program (engine/scoring.py): the background
        # tenant's full-sequence forward. Zero warmed programs when
        # `config.scoring` is off (the stable-program-set precedent of
        # the block programs).
        self._score = jax.jit(
            named_partial(_score_program, cfg=self.cfg, model=self.family)
        )
        self.score_shapes: List[Tuple[int, int]] = (
            derive_score_shapes(
                config.length_buckets, config.batch_buckets,
                self.cfg.max_position_embeddings,
            )
            if config.scoring else []
        )
        self._rng = jax.random.key(config.seed)
        self.state = self._init_state()
        # Int8 planes and one query a lane-step: the decode attention may
        # go by the lanes' lengths (whether it does at a dispatch's width
        # is `_attn_width`'s), and the host keeps their copy.
        self._lanes = (_LaneLengths(self.slots)
                       if self.state.cache.ks is not None and not self.spec
                       else None)
        self._slot_req: List[Optional[_Request]] = [None] * self.slots
        self._pending: List[_Request] = []
        # Dispatched-but-unread megasteps, oldest first:
        # (tokens device array — [K, chunk, S] plain / [K, chunk, S, k+1]
        #  spec,
        #  counts [K, chunk, S] device array in spec mode else None,
        #  active int8 device array — [K, S] per-chunk snapshots (the
        #  reap flattens the K axis and keys dead-slot detection off the
        #  FINAL snapshot),
        #  dead-lane scalar device array,
        #  flipped / firsts [K, chunk, S] bool / int32 admission planes,
        #  slot->request snapshot at dispatch time,
        #  a routed family's counts int32 [len(counters)], else None,
        #  served int32 [3]: prefill passes, the slot-chunks they served and
        #  the passes that found two or more staged).
        # Every device entry is a fresh non-donated buffer (see
        # _step_program's snapshot note), so dispatches pipeline under
        # the donation invariants.
        self._inflight: List[
            Tuple[jax.Array, Optional[jax.Array], jax.Array,
                  jax.Array, jax.Array, jax.Array,
                  List[Optional[_Request]], Optional[jax.Array],
                  Optional[jax.Array]]
        ] = []
        self._next_rid = 0
        self.last_ttft_s: Optional[float] = None
        # Per-request time-to-first-token (submit() -> first token on host),
        # keyed by rid; the serving queue pops these into its histogram.
        self.ttfts: Dict[int, float] = {}
        # Streaming (incremental token-yield) side channel: rids the
        # serving queue watches for token-level progress. Final token
        # lists are recorded at reap ONLY for watched rids (so bench
        # harnesses that never stream accumulate nothing) and drained by
        # pop_final_tokens().
        self._stream_watch: set = set()
        self._final_tokens: Dict[int, List[int]] = {}
        # Multi-turn tutoring sessions: rid -> (session_id, pin ttl,
        # prompt token snapshot). Filled by mark_session(); consumed at
        # finish-reap by _publish_session().
        self._session_reqs: Dict[int, Tuple[str, float, List[int]]] = {}
        # Speculation observability, accumulated at reap time from the
        # device counts plane and drained by pop_spec_stats(): windows run
        # for live slots and tokens they emitted (emitted/windows is the
        # mean tokens-per-window; 1.0 = nothing accepted).
        self._spec_windows = 0
        self._spec_emitted = 0
        # Tokens finished requests generated (bench harnesses divide by
        # wall clock for tokens/sec through the serving path).
        self.total_generated_tokens = 0
        # Megastep efficiency accounting, drained by pop_dispatch_stats():
        # program dispatches the host issued (every `engine.prog.*` span,
        # counted by `_progs`), tokens emitted to requests (first
        # tokens + reaped stream tokens), and pad lanes burnt by
        # slots that finished inside a megastep (the on-device `dead`
        # account). dispatches/tokens is the host-round-trips-per-token
        # ratio the megastep exists to shrink.
        self._emitted_tokens = 0
        self._dead_lane_tokens = 0
        # Where the lanes and the waits go, each counted at the line that
        # does the work and drained by pop_loop_stats().
        self._counts: Dict[str, int] = {}
        self._obs: Dict[str, List[float]] = {}
        # Each megastep's device time on the host's clock, always on
        # (engine/spans.py `DispatchLedger`), into the same two; told of
        # every call into the runtime by `_span`.
        self._ledger = DispatchLedger(self._count, self._observe)
        # Flight-recorder observability, drained by the serving queue:
        # (program, wall-clock start, dispatch seconds) per compiled-
        # program dispatch — program names key the inventory entries and
        # the metrics registry's ENGINE_PROGRAM_HISTOGRAMS — and per-rid
        # pending-queue wait (submit -> popped for admission). Bounded so
        # a queue-less caller (bench drain loops) cannot grow them.
        self._progs = ProgramLog(self._PROG_TIMES_MAX)
        self._queue_waits: Dict[int, float] = {}
        # Shared-prefix accounting: per-rid pinned tree paths (released
        # when the request completes — eviction never frees a block a
        # live slot references), per-rid hit lengths for tracing, and
        # the cumulative hit/prompt/eviction counts pop_prefix_stats()
        # drains into the prefix_cache_* metric series.
        self._prefix_pins: Dict[int, Match] = {}
        self._prefix_hits: Dict[int, int] = {}
        self._prefix_hit_tokens = 0
        self._prefix_prompt_tokens = 0
        self._prefix_evictions = 0
        # rid -> prompt token list for STAGED requests (req.tokens is
        # replaced by the generated stream at flip-reap; the
        # publish into the radix tree still needs the prompt ids).
        self._staged_prompts: Dict[int, List[int]] = {}
        # Monotonic staging sequence (FIFO service order for the in-scan
        # prefill phase — see SlotState.stage_seq).
        self._stage_seq = 0
        self._scalars: Dict[int, jax.Array] = {}

    _PROG_TIMES_MAX = 4096

    def _shed_oldest(self, d: Dict[int, object]) -> None:
        """Bound a per-rid dict for queue-less callers (bench drain
        loops, warmup) that never pop it: past the cap, drop the oldest
        half rather than grow forever."""
        if len(d) > self._PROG_TIMES_MAX:
            for rid in list(d)[: -self._PROG_TIMES_MAX // 2]:
                d.pop(rid, None)

    def _span(self, name: str, **attrs) -> Span:
        """A host span (engine/spans.py), summed by its name in `_progs`;
        `engine.prog.*` ones are besides the timed dispatches."""
        if is_runtime_call(name):
            self._ledger.call_begins()
        return Span(name, self._span_closed, **attrs)

    def _span_closed(self, sp: Span) -> None:
        self._progs(sp)
        if is_runtime_call(sp.name):
            self._ledger.call_ended(sp.ended_s)

    def _count(self, **amounts: int) -> None:
        for name, n in amounts.items():
            self._counts[name] = self._counts.get(name, 0) + n

    def _observe(self, name: str, value: float) -> None:
        vals = self._obs.setdefault(name, [])
        vals.append(value)
        if len(vals) > self._PROG_TIMES_MAX:
            del vals[: -self._PROG_TIMES_MAX // 2]

    def pop_loop_stats(self) -> Tuple[Dict[str, int], Dict[str, List[float]],
                                      Dict[str, SpanSum]]:
        """Drain (counts, observations, spans) since the last call. The
        first two are keyed as the metrics registry's ENGINE_LOOP_COUNTERS
        and ENGINE_LOOP_HISTOGRAMS key them; the series' help strings there
        say what each counts. Observations are seconds, except
        `decode_lanes` (lanes) and `staged_iterations` (iterations).
        `spans` holds, by span name, the wall, CPU and runtime-wait seconds
        of every span this engine closed (engine/spans.py `SpanSum`): what
        the serving loop parts a turn's wall by (`spans.turn_budget`)."""
        out = (self._counts, self._obs, self._progs.pop_sums())
        self._counts, self._obs = {}, {}
        return out

    def pop_dispatch_stats(self) -> Tuple[int, int, int]:
        """Drain (host_dispatches, emitted_tokens, dead_lane_tokens)
        accumulated since the last call. dispatches/tokens is the host
        round trips paid per emitted token — the megastep's target ratio;
        dead_lane_tokens counts pad lanes already-finished slots decoded
        inside megasteps before the boundary let the host reap them. The
        serving queue turns these into the `host_dispatches_per_token`
        gauge and the `megastep_dead_lane_tokens` counter."""
        out = (self._progs.dispatches, self._emitted_tokens,
               self._dead_lane_tokens)
        self._progs.dispatches = 0
        self._emitted_tokens = self._dead_lane_tokens = 0
        return out

    def pop_prefix_stats(self) -> Optional[Tuple[int, int, int, int]]:
        """Drain (hit_tokens, prompt_tokens, evicted_blocks, blocks_used)
        accumulated since the last call; None when the shared-prefix
        cache is disabled. hit_tokens counts prompt tokens whose KV was
        spliced from the radix tree instead of re-prefilled (the USED
        prefix after bucket fitting, not the raw match) and
        prompt_tokens the total prompt tokens admitted, so
        hit/prompt is the hit rate; blocks_used is the live tree level
        the budget is enforced on. The serving queue turns these into
        `prefix_cache_hit_tokens`/`prefix_cache_evictions` counters and
        the `prefix_cache_hit_rate`/`prefix_cache_blocks_used` gauges."""
        if self.prefix_cache is None:
            return None
        out = (self._prefix_hit_tokens, self._prefix_prompt_tokens,
               self._prefix_evictions, self.prefix_cache.blocks_used)
        self._prefix_hit_tokens = self._prefix_prompt_tokens = 0
        self._prefix_evictions = 0
        return out

    def pop_prefix_hits(self) -> Dict[int, int]:
        """Drain rid -> shared-prefix tokens spliced at that request's
        admission (0 = the whole prompt prefilled). Feeds the per-request
        `engine.stage` span attributes on the trace."""
        out, self._prefix_hits = self._prefix_hits, {}
        return out

    def pop_program_times(self) -> List[Tuple[str, float, float]]:
        """Drain (program, start_unix, dispatch_s) recorded since last
        call."""
        return self._progs.pop()

    def pop_queue_waits(self) -> Dict[int, float]:
        """Drain rid -> seconds spent in the pending queue before its
        prefill was dispatched (the `queue.wait` stage of a trace)."""
        out, self._queue_waits = self._queue_waits, {}
        return out

    @property
    def kv_bytes_total(self) -> int:
        """Logical bytes of the live slot KV working set (k/v plus the
        int8-KV scale planes when quantized), at the cache's current
        width. Grows with `_grow` and shrinks on idle rebuild."""
        c = self.state.cache
        return sum(
            int(x.nbytes)
            for x in (c.k, c.v, c.ks, c.vs, c.ssm, c.conv, c.pool)
            if x is not None
        )

    @property
    def state_snapshot_bytes(self) -> Optional[int]:
        """Bytes of the state snapshots the prefix tree holds (the
        `engine_state_snapshot_bytes` gauge); None for a family without a
        recurrent state or without the prefix cache."""
        if self.prefix_cache is None or not self.family.recurrent_state:
            return None
        return self.prefix_cache.snapshot_bytes

    @property
    def kv_bytes_per_chip(self) -> int:
        """HBM the slot KV working set costs on EACH chip: the KV planes
        shard their heads axis over tp (partition.PAGED_PLANE_SPECS), so
        per-chip residency is total/tp — the number the bench record's
        `mesh` block and the `serving_kv_bytes_per_chip` gauge report,
        and the resource multi-chip paged serving exists to split."""
        return self.kv_bytes_total // max(1, self.tp)

    def _attn_width(self) -> int:
        """The live cache's width where the decode step's attention reads
        a lane's live positions alone (`quant_decode_engages`, by the
        planes' shapes), else 0."""
        cache = self.state.cache
        if self._lanes is None or not attention_ops.quant_decode_engages(
                (self.slots, cache.ks.shape[2], 1, self.cfg.head_dim),
                cache.k.shape):
            return 0
        return cache.k.shape[3]

    def _init_state(self, width: Optional[int] = None) -> SlotState:
        # Plane-table mesh shardings from birth, in the canonical
        # spelling: raw single-device arrays would key the jit caches
        # differently than the programs' own (pinned) outputs, so the
        # first stage/megastep after a rebuild would silently recompile
        # (see _plane_spec). KV planes are born tp-sharded over their
        # heads axis; host-state planes replicated.
        return self._canon_state(_fresh_state(
            self.family, self.cfg, self.slots, width or self.widths[0],
            groups=self.tp,
        ))

    # ------------------------------------------------------------ host API

    def submit(self, prompt: str) -> int:
        limit = self.bucket
        toks = self.tokenizer.encode(prompt)[-limit:] or [self.tokenizer.pad_id]
        req = _Request(
            rid=self._next_rid,
            prompt_len=len(toks),
            tokens=toks,
            max_new=self.config.sampling.max_new_tokens,
            submit_time=time.monotonic(),
        )
        self._next_rid += 1
        self._pending.append(req)
        return req.rid

    def mark_session(self, rid: int, session_id: str,
                     ttl_s: float) -> bool:
        """Tag a just-submitted request as a tutoring-session turn: at
        finish its FULL transcript (prompt + generated tokens, eos
        excluded) is published into the radix tree and session-pinned
        with `ttl_s`, so the next turn — whose prompt splices this
        transcript as its head — admits with a shared-prefix hit. Must
        be called while the request is still pending (its `tokens` field
        still holds the prompt). No-op without a prefix cache."""
        if self.prefix_cache is None:
            return False
        for req in self._pending:
            if req.rid == rid:
                self._session_reqs[rid] = (session_id, float(ttl_s),
                                           list(req.tokens))
                return True
        return False

    @property
    def backlog(self) -> int:
        """Requests submitted but not yet admitted to a decode slot (their
        prefill has not run). The serving queue counts these toward its
        admission bound."""
        return len(self._pending)

    def cancel_pending(self, rid: int) -> bool:
        """Remove a not-yet-admitted request; True if it was still pending.
        Its prefill never runs. A request already in a slot is not
        cancellable (its compute is already committed)."""
        for i, req in enumerate(self._pending):
            if req.rid == rid:
                del self._pending[i]
                self._session_reqs.pop(rid, None)
                self._stream_watch.discard(rid)
                return True
        return False

    def warmup(self) -> float:
        """Compile the serving program set so no live request pays an XLA
        compile: `_stage` at every admissible (prompt bucket, cache width)
        pair (a short prompt can join a batch running at any wider
        width), the megastep at every (cache width, ladder rung) pair,
        rung 1 included, every width-growth transition, the scoring
        domain, and — with the shared-prefix cache enabled — the block
        export and the `_stage_block` splice per width (a stored run's too,
        where one fits). Returns seconds."""
        t0 = time.monotonic()
        buckets = self.buckets
        for width in self.widths:
            self.state = self._init_state(width)
            for t in buckets:
                if self._required_width(t) > width:
                    continue  # a prompt this long can't run at this width
                ids = np.full((1, t), self.tokenizer.pad_id, np.int32)
                self._rng, rng = jax.random.split(self._rng)
                # Canon before the dispatch exactly as the live path does
                # (_stage_admissions) so warmup and live traffic key the
                # stage program identically.
                self.state = self._canon_state(self.state)
                with self.mesh:
                    # Operands as `_stage_admissions` hands them over
                    # (numpy but for the cached slot scalar): host and
                    # device operands key the program apart.
                    self.state = self._stage(
                        self.state, self._i32(0), ids, np.int32(1),
                        np.int32(0), np.int32(0),
                        jax.random.key_data(rng), *self._snap_arg(0),
                    )
            # The first dispatch consumes the post-stage state — the
            # exact live stage->megastep handoff — and lax.cond compiles
            # both admission branches regardless of the runtime staged
            # flag.
            for k in self.megastep_ks:
                rngs = self._step_keys(k)
                self.state = self._canon_state(self.state)
                with self.mesh:
                    self.state = self._megastep(
                        self.params, self.state, rngs
                    )[0]
            if self.prefix_cache is not None and any(
                t >= self.prefix_block_tokens for t in buckets
            ):
                # Shared-prefix programs per width: publish slices blocks
                # straight out of the live state, staging splices them
                # straight back in. Canon first — the live path
                # (_publish_staged/_stage_admissions) exports and splices
                # from a canonical state.
                self.state = self._canon_state(self.state)
                zero = self._i32(0)
                with self.mesh:
                    blk = self._canon_block(self._export_block(
                        self.state.cache, zero, zero))
                    self.state = self._stage_block(
                        self.state, blk, zero, zero)
                    if any(
                        bucket_has_runs(
                            t, self.prefix_block_tokens, width)
                        and self._required_width(t) <= width
                        for t in buckets
                    ):
                        self.state = self._stage_block(
                            self.state, (blk,) * STAGE_RUN_BLOCKS,
                            zero, zero, zero)
                    if width_holds_stored_run(
                            width, self.prefix_block_tokens,
                            self.stored_run_blocks):
                        # A stored run leaves a slot, enters one, and
                        # gives up a block for a cache too narrow for it
                        # (`_run_block`: one program whatever the width).
                        run = self._canon_block(self._export_run(
                            self.state.cache, zero, zero))
                        self.state = self._stage_block(
                            self.state, run, zero, zero, zero)
                        self._run_block(RunBlock(run, 0))
                    if self.family.recurrent_state:
                        # From a canonical state, as `_publish_staged`
                        # exports and `_stage_admissions` restores.
                        self.state = self._canon_state(self.state)
                        self.state = self._restore_state(
                            self.state,
                            self._canon_snapshot(self._export_state(
                                self.state, zero)), zero)
        # The last width's state goes before the growth transitions make
        # another as wide (`reset` below builds the one that serves): two
        # of MiniCPM-SALA's 33,536-wide states beside its weights are more
        # than the chip holds.
        self.state = None
        for i, wa in enumerate(self.widths):
            for wb in self.widths[i + 1:]:
                throwaway = self._init_state(wa)
                with self.mesh:
                    self._grow(throwaway, wb)
        # Scoring-tenant domain (empty unless EngineConfig.scoring): one
        # program per (batch bucket, length bucket) shape, so the first
        # bulk job a quantum dispatches pays zero live XLA compiles.
        self._warm_score()
        self.reset()  # drop the ghost stagings; compiled programs stay cached
        rid = self.submit("warmup")
        self.drain()
        self.ttfts.pop(rid, None)
        if self.prefix_cache is not None:
            # The warmup drain published the ghost "warmup" prompt into
            # the tree; live traffic must start from an empty cache and
            # zeroed hit accounting.
            self.prefix_cache.clear()
            self._prefix_hit_tokens = self._prefix_prompt_tokens = 0
            self._prefix_evictions = 0
            self._prefix_hits = {}
        # The warmup drain is not serving traffic: drop its dispatch/token
        # counts and program times (so the first drains reflect live
        # requests only) and put the controller back on its configured
        # starting rung (the idle drain grew K toward the ceiling).
        self.pop_dispatch_stats()
        self.pop_loop_stats()
        self._progs.pop()
        self.megastep_k = self._megastep_initial
        return time.monotonic() - t0

    def _warm_score(self) -> int:
        """Compile the score program over its (batch bucket x length
        bucket) domain; a no-op (empty domain) when scoring is off."""
        for nb, bucket in self.score_shapes:
            ids = np.full((nb, bucket), self.tokenizer.pad_id, np.int32)
            mask = np.ones((nb, bucket), bool)
            with self.mesh:
                self._score(self.params, jnp.asarray(ids),
                            jnp.asarray(mask))
        return len(self.score_shapes)

    @property
    def score_batch_cap(self) -> int:
        """Texts per single-dispatch score quantum (the largest batch
        bucket) — the scoring tenant's preemption granularity."""
        return max(self.config.batch_buckets)

    def score(self, texts: Sequence[str]) -> List[dict]:
        """Log-likelihood scoring through the warmed `_score` program
        (engine/scoring.py): per text logprob/tokens/ppl + a `truncated`
        flag. The background scoring tenant's quantum calls this with at
        most `score_batch_cap` texts — exactly one device dispatch, so
        interactive work preempts at quantum boundaries."""
        return score_texts(self, texts)

    @property
    def has_work(self) -> bool:
        # A departing request (its slot handed on, its end still to be
        # reaped) is in no slot; the dispatches it ends in are in flight.
        return (
            bool(self._pending)
            or bool(self._inflight)
            or any(r is not None for r in self._slot_req)
        )

    def pop_ttfts(self) -> Dict[int, float]:
        """Drain the per-request TTFT measurements recorded since last call."""
        out, self.ttfts = self.ttfts, {}
        return out

    def stream_watch(self, rid: int) -> None:
        """Mark `rid` as streamed: its final token list is retained at
        reap for pop_final_tokens(). Idempotent."""
        self._stream_watch.add(rid)

    def stream_unwatch(self, rid: int) -> None:
        self._stream_watch.discard(rid)
        self._final_tokens.pop(rid, None)

    def stream_snapshot(self, rids) -> Dict[int, List[int]]:
        """Incremental token-yield channel: for each requested rid that is
        live post-flip and not finished, a COPY of its generated-so-far
        token list with eos filtered — the same token view decode()
        renders at finish, so a streamed prefix is always a prefix of the
        final transcript. A request is looked for in its slot and, once
        its slot has been handed on (`_stage_admissions`), in the
        snapshots of the dispatches in flight, where it stays until its
        end is reaped. Called by the serving queue between steps (never
        concurrent with step())."""
        want = set(rids)
        out: Dict[int, List[int]] = {}
        if not want:
            return out
        eos = self.tokenizer.eos_id
        for req in (*self._slot_req, *self._departing()):
            if req is None or req.finished or not req.live:
                continue
            if req.rid in want:
                out[req.rid] = [t for t in req.tokens if t != eos]
        return out

    def decode_tokens(self, tokens) -> str:
        """Decode a generated-token prefix (stream offsets count these
        tokens; resume-at-offset skips len(decode(tokens[:offset]))
        chars)."""
        return self.tokenizer.decode(list(tokens))

    def pop_final_tokens(self) -> Dict[int, List[int]]:
        """Drain the final (eos-filtered) token lists of watched streamed
        requests that finished since the last call."""
        out, self._final_tokens = self._final_tokens, {}
        return out

    def pop_spec_stats(self) -> Optional[Tuple[int, int]]:
        """Drain (windows_run, tokens_emitted) accumulated at reap since the
        last call; None when speculation is off. emitted/windows is the mean
        tokens per verify window (1.0 = no draft accepted; the ceiling is
        spec_tokens + 1); emitted - windows is the count of tokens the
        windows produced beyond the guaranteed one each — the speculation
        dividend. The serving queue turns these into the
        `spec_tokens_per_window` gauge and `spec_accepted_tokens` counter.
        """
        if not self.spec:
            return None
        out = (self._spec_windows, self._spec_emitted)
        self._spec_windows = self._spec_emitted = 0
        return out

    def reset(self) -> None:
        """Discard all in-flight work and rebuild a clean device state.

        Needed after a failed step: the megastep donates the live
        SlotState, so an exception mid-step can leave `self.state`
        pointing at deleted buffers — every subsequent step would fail.
        Callers (the serving queue) fail the affected requests and reset
        the engine.
        """
        self.state = self._init_state()
        if self._lanes is not None:
            self._lanes = _LaneLengths(self.slots)
        self._slot_req = [None] * self.slots
        self._pending = []
        self._inflight = []
        self._ledger.reset()
        self.ttfts = {}
        self._stream_watch = set()
        self._final_tokens = {}
        self._session_reqs = {}
        self._progs.pop()
        self._queue_waits = {}
        self._staged_prompts = {}
        self.megastep_k = self._megastep_initial
        # The radix tree itself SURVIVES a reset: its blocks are never
        # donated, so a failed step cannot have deleted them — only the
        # per-request pins die with their requests.
        if self.prefix_cache is not None:
            for pin in self._prefix_pins.values():
                self.prefix_cache.release(pin)
        self._prefix_pins = {}
        self._prefix_hits = {}

    def _maybe_rebuild_idle(self) -> None:
        # Idle rebuild: with nothing occupied or in flight, the cache can
        # jump straight to the width the queued work needs (free — it holds
        # no live data), shrinking back after a wide request departs.
        if (
            self._pending
            and not self._inflight
            and not any(r is not None for r in self._slot_req)
        ):
            needed = max(
                self._required_width(r.prompt_len)
                for r in self._pending[: self.slots]
            )
            if needed != self.state.cache.k.shape[3]:
                self.state = self._init_state(needed)
                if self._lanes is not None:  # nothing in flight: as new
                    self._lanes = _LaneLengths(self.slots)

    def _pop_next(self) -> Tuple[_Request, int, int, np.ndarray]:
        """Take the oldest pending request: record its queue wait, pick
        its prompt bucket and required cache width, and build the
        right-padded [1, bucket] id plane `_stage` writes into the slot's
        transcript row."""
        req = self._pending.pop(0)
        req.popped_time = time.monotonic()
        wait = self._queue_waits[req.rid] = req.popped_time - req.submit_time
        self._observe("queue_wait", wait)
        self._shed_oldest(self._queue_waits)
        # Smallest length bucket that fits: a 10-token query asks for a
        # 16/32-token bucket's cache width, not the full Tmax (the decode
        # cache runs at the width the widest active request needs).
        bucket = min(
            pick_bucket(req.prompt_len, self.config.length_buckets),
            self.bucket,
        )
        w_req = self._required_width(req.prompt_len)
        ids = np.full((1, bucket), self.tokenizer.pad_id, np.int32)
        ids[0, : req.prompt_len] = req.tokens
        return req, bucket, w_req, ids

    def _grow_if_needed(self, w_req: int) -> None:
        if w_req > self.state.cache.k.shape[3]:
            # Pad the live cache up (donated, in device order after any
            # in-flight chunks — their snapshots are separate arrays and
            # unaffected).
            with self._span(PROG + "grow"):
                self.state = self._grow(self.state, w_req)

    def _stage_admissions(self) -> None:
        """Hand every admissible pending request to the device as a
        STAGED slot — prompt ids into the transcript row,
        shared-prefix blocks spliced straight into the slot's pages, the
        staged-admission plane armed — with zero blocking work. The
        prefill itself advances inside the megastep scan
        (`_admission_chunk`), one bounded chunk per decode iteration for
        the oldest staged slot, and the flip's first token comes back
        through the megastep's flipped/firsts planes at the next batched
        reap: the decode train never pauses for admission. This is the
        only place a slot becomes staged, so a megastep sees all of them
        at its entry and none appears inside it.

        A slot is admissible when it is empty, and also when its
        request's END is certain to lie inside the dispatches already in
        flight (`_end_in_flight`): the slot is HANDED ON without waiting
        for the reap of that end, two dispatches and more of a lane later.
        Everything staging does to a slot consumes `self.state`, the last
        dispatch's output, so on the device it comes after the departing
        request's last token; `_stage_program` resets the lane whole. The
        departing request lives on in the snapshots of the dispatches in
        flight and `_walk` finishes it there with all its tokens, while
        `_slot_req[slot]`, and so every later snapshot, holds the
        successor."""
        self._maybe_rebuild_idle()
        pc = self.prefix_cache
        for slot in range(self.slots):
            if not self._pending:
                break
            handed_on = self._slot_req[slot] is not None
            if handed_on and not self._end_in_flight(slot):
                continue
            req, bucket, w_req, ids = self._pop_next()
            with self._span(KEYS):
                self._rng, rng = jax.random.split(self._rng)
            cursor0 = 0
            snapshot = None
            if pc is not None:
                match = pc.lookup(req.tokens)
                cursor0 = plan_staged(
                    match.tokens, req.prompt_len, pc.block_tokens
                )
                if self.family.recurrent_state:
                    # A hit is only as long as the deepest state snapshot
                    # on the matched path; the rest of what the tree
                    # matched is prefilled again, and the prefill leaves a
                    # snapshot where the next such prompt can start.
                    matched = cursor0
                    cursor0, snapshot = pc.deepest_snapshot(match, matched)
                    req.snap_at = self._snapshot_point(
                        cursor0, matched, req.prompt_len)
                    self._count(
                        prefix_tokens_recomputed_for_state=matched - cursor0,
                        state_snapshots_restored=int(snapshot is not None))
                if cursor0:
                    pc.acquire(match)
                    self._prefix_pins[req.rid] = match
                self._staged_prompts[req.rid] = list(req.tokens)
            self._note_admission(req, cursor0)
            # Canon before the dispatches for the same reason
            # _dispatch canons: the grow/stage_block/stage programs key on
            # the warmed input shardings, whatever spelling the previous
            # program's outputs propagated (zero-copy when already
            # canonical — the steady state).
            self.state = self._canon_state(self.state)
            with self.mesh:
                self._grow_if_needed(w_req)
                if cursor0:
                    self._splice(
                        match.blocks()[: cursor0 // pc.block_tokens], slot)
                with self._span(PROG + "stage"):
                    # numpy operands ride the call's own transfer; a
                    # `jnp.asarray` each would be a program of its own.
                    self.state = self._stage(
                        self.state, self._i32(slot), ids,
                        np.int32(req.prompt_len), np.int32(cursor0),
                        np.int32(self._stage_seq),
                        jax.random.key_data(rng),
                        *self._snap_arg(req.snap_at),
                    )
                if self._lanes is not None:  # `_stage_program` parks it
                    self._lanes.ops.append(
                        (slot, self.state.cache.k.shape[3] - 1))
                if snapshot is not None:
                    with self._span(PROG + "restore_state"):
                        self.state = self._restore_state(
                            self.state, snapshot, self._i32(slot))
            self._stage_seq += 1
            req.live = False
            self._slot_req[slot] = req
            if handed_on:
                self._count(slots_handed_on=1)

    def _splice(self, blocks: list, slot: int) -> None:
        """Write a hit's blocks, the slot's first `len(blocks)`, into the
        slot's pages: a stored run (or the head of one the hit ends in:
        the program keeps the slot's bytes past the count) with one launch
        of its own arrays where the cache holds the run's whole width at
        its offset, everything else in runs of STAGE_RUN_BLOCKS blocks
        (`stage_runs`). The program would CLAMP the start of a run that
        passes the cache's end, so such a run (a short prompt in a narrow
        cache that matches the head of a long edge) reaches the slot as
        the same blocks, cut out of it."""
        blk_t = self.prefix_block_tokens
        fit = self.state.cache.k.shape[3] // blk_t

        def launch(block, first: int, *tokens) -> None:
            self._count(stage_block_launches=1)
            with self._span(PROG + "stage_block"):
                self.state = self._stage_block(
                    self.state, block, self._i32(slot),
                    self._i32(first * blk_t), *tokens)

        for first, n, run in splice_pieces(blocks):
            if run is not None and first + self.stored_run_blocks <= fit:
                launch(run, first, self._i32(n * blk_t))
                self._count(prefix_tokens_from_runs=n * blk_t)
                continue
            own = [self._run_block(b) if isinstance(b, RunBlock) else b
                   for b in blocks[first:first + n]]
            for i, m in stage_runs(n, fit, first):
                if m == 1:
                    launch(own[i], first + i)
                else:
                    launch(tuple(own[i:i + m])
                           + (own[i + m - 1],) * (STAGE_RUN_BLOCKS - m),
                           first + i, self._i32(m * blk_t))

    def _run_block(self, entry: RunBlock) -> KVBlock:
        """A stored run's block as a block of its own arrays: the run is
        handed to the block export as a cache of one slot, one warmed
        program whatever width is served."""
        run = entry.run
        with self._span(PROG + "export_block"):
            return self._canon_block(self._export_block(
                KVCache(k=run.k, v=run.v, length=None, ks=run.ks, vs=run.vs,
                        pool=run.pool),
                self._i32(entry.index * self.prefix_block_tokens),
                self._i32(0)))

    def _i32(self, n: int) -> jax.Array:
        """The device int32 scalar `n`, made once per value (slot indices
        and block offsets: a few hundred values). `jnp.asarray(n,
        jnp.int32)` is a program dispatch of its own; an admission makes
        two for every `_stage_block` call, and they held the host, and so
        the device, for as long as the splices themselves (PERF.md section
        5, PR 30)."""
        x = self._scalars.get(n)
        if x is None:
            x = self._scalars[n] = jnp.asarray(n, jnp.int32)
        return x

    def _snap_arg(self, snap_at: int) -> tuple:
        """`_stage`'s last operand for a family with a recurrent state
        (the position the prefill snapshots at), nothing for the others:
        their stage program is the one it was."""
        return (np.int32(snap_at),) if self.family.recurrent_state else ()

    def _snapshot_point(self, cursor0: int, matched: int,
                        prompt_len: int) -> int:
        """Where a staged prefill that starts at `cursor0` is to snapshot
        its state for later prompts (0: nowhere). A snapshot can stand
        only where a prefill chunk ends on a block boundary: `cursor0`
        plus whole steps of lcm(prefill chunk, block). If the tree matched
        keys and values past the state it could restore (`matched`), that
        is the BRANCH POINT of this prompt and the earlier ones: the last
        such boundary at or below it, so the next prompt of the course is
        a full hit. Else (nothing matched: a context's FIRST prompt, and
        where it will branch from the next is not known yet) the last
        multiple of STATE_STRIDE_STEPS steps below the prompt's end: the
        second prompt of a context of n tokens starts from a snapshot no
        more than a stride below n, whatever the first one's question
        was (shorter than a stride), instead of prefilling all n again,
        and leaves its own at the branch point. A chunk costs about what
        a decode row costs, so without it the notes cell's three
        second prompts cost 3.6% of a window (PERF.md section 6, PR 40,
        call F)."""
        step = math.lcm(self.prefill_chunk, self.prefix_block_tokens)
        branch = cursor0 + (matched - cursor0) // step * step
        if branch > cursor0:
            return branch
        steps = self._stride_steps
        if cursor0 and steps < STATE_STRIDE_STEPS:
            # A small state's short stride is for a context's FIRST
            # prompt: a prompt that started from a snapshot and branches
            # nowhere past it would leave one a step below its own end,
            # inside its own question, at nearly every admission, and a
            # burst of those pushes the context's own snapshot out of the
            # tree's least-recently-used few (`max_snapshots`).
            return 0
        stride = steps * step
        point = (prompt_len - 1) // stride * stride
        return point if point > cursor0 else 0

    @functools.cached_property
    def _stride_steps(self) -> int:
        """Steps between the places a context's FIRST prompt may snapshot
        at: as many as the tokens whose keys and values weigh what ONE
        snapshot weighs, in whole steps, and at most STATE_STRIDE_STEPS.
        A state of megabytes (a matrix a head: 8.5 to 15 MB a sequence
        beside 1 to 2.3 KB of keys a token) keeps the 8 steps it had; a
        state that is its convolutions' windows alone (82 KB beside 6 KB a
        token) takes one, so that a context SHORTER than 8 steps leaves a
        snapshot too: without one its next prompts each prefill it again
        until the first of them has flipped and been reaped (PERF.md
        section 6, PR 57, has what that cost, and what a stride of one
        step did while it applied to EVERY prompt: `_snapshot_point`). A
        ratio of bytes a slot, so the same at every width: worked out
        once."""
        c = self.state.cache
        state = sum(x.nbytes for x in (c.ssm, c.conv) if x is not None)
        token = sum(x.nbytes for x in (c.k, c.v, c.ks, c.vs, c.pool)
                    if x is not None) / c.k.shape[3]
        step = math.lcm(self.prefill_chunk, self.prefix_block_tokens)
        return max(1, min(STATE_STRIDE_STEPS, math.ceil(state / token / step)))

    def _required_width(self, prompt_len: int) -> int:
        bucket = min(
            pick_bucket(prompt_len, self.config.length_buckets), self.bucket
        )
        return (cfg_tmax(self.cfg, self.config.sampling, bucket)
                + self._spec_extra)

    def _note_admission(self, req: _Request, hit: int) -> None:
        """Count one admitted prompt and the shared-prefix hit it had."""
        self._count(admissions=1, prompt_tokens=req.prompt_len,
                    prefill_tokens=req.prompt_len - hit)
        if self.prefix_cache is not None:
            self._prefix_hit_tokens += hit
            self._prefix_prompt_tokens += req.prompt_len
            self._prefix_hits[req.rid] = hit
            self._shed_oldest(self._prefix_hits)

    def _insert_blocks(self, tokens: List[int], cache: KVCache,
                       slot: int) -> None:
        """Insert `tokens`' whole blocks into the radix tree: for each
        one it does not hold, an immutable copy exported from `cache` at
        `slot`, a stored run at a launch where the new blocks are a run
        long (the run lies inside the prompt, so inside the cache: no
        start is clamped). Runs under `self.mesh`, entered ONCE, like
        every other dispatch (the jit cache keys on the ambient mesh)."""
        blk_t = self.prefix_cache.block_tokens

        def export(program) -> Callable[[int], KVBlock]:
            def make(i: int) -> KVBlock:
                with self._span(PROG + "export_block"):
                    return self._canon_block(program(
                        cache, self._i32(i * blk_t), self._i32(slot),
                    ))
            return make

        self.prefix_cache.insert(
            tokens, export(self._export_block), export(self._export_run))

    def _publish_staged(self, req: _Request, slot: int) -> None:
        """Publish a flipped request's whole prompt blocks into the radix
        tree, at flip-reap time: the prompt's KV lives in the slot's
        pages of the LIVE cache, so the blocks are sliced straight out of
        `self.state` — fresh immutable copies, inserted only where the
        tree does not already hold them; safe because decode only ever
        scatters at positions >= prompt_len and the slot cannot be
        restaged before this reap returns. The block budget is enforced
        after the insert, so a publish can never evict blocks its own
        admission still references (pinned paths are never evicted
        regardless)."""
        pc = self.prefix_cache
        tokens = self._staged_prompts.pop(req.rid, None)
        if tokens is None:
            return
        blk_t = pc.block_tokens
        # Export from a canonical state: the flip-reap hands us a raw
        # megastep output, but warmup compiled `_export_block` against
        # the canonical cache shardings (zero-copy when they agree).
        self.state = self._canon_state(self.state)
        with self.mesh:
            self._insert_blocks(
                tokens[: (req.prompt_len // blk_t) * blk_t],
                self.state.cache, slot,
            )
            if req.snap_at and not pc.has_snapshot(tokens, req.snap_at):
                # The slot's snapshot rows hold the state its prefill left
                # at `snap_at`, and nobody writes them before the slot is
                # staged again, which waits for this reap.
                with self._span(PROG + "export_state"):
                    snap = self._canon_snapshot(self._export_state(
                        self.state, self._i32(slot)))
                if pc.attach_snapshot(tokens, req.snap_at, snap):
                    self._count(state_snapshots_taken=1)
        self._prefix_evictions += pc.evict_to_budget()

    def _publish_session(self, req: _Request, slot: int) -> None:
        """Finish-reap publish for a session turn: the slot's pages hold
        KV for the prompt AND every generated token that was fed back
        (all but the last sampled one), at absolute positions — so the
        same block export that publishes prompts publishes the whole
        turn transcript. The path is then session-pinned with the turn's
        TTL so the follow-up question admits against it (its prompt
        splices this transcript as its head). Same insert-then-evict
        policy as the prompt publishes."""
        entry = self._session_reqs.pop(req.rid, None)
        pc = self.prefix_cache
        if entry is None or pc is None:
            return
        session_id, ttl_s, prompt_toks = entry
        eos = self.tokenizer.eos_id
        gen: List[int] = []
        for t in req.tokens:
            if t == eos:
                break
            gen.append(t)
        full = prompt_toks + gen
        # KV exists only for FED positions: the last sampled token (and
        # any eos) never re-entered the model, so its page is unwritten.
        safe = min(len(full), req.prompt_len + len(req.tokens) - 1)
        blk_t = pc.block_tokens
        n = (safe // blk_t) * blk_t
        if n <= 0:
            return
        self.state = self._canon_state(self.state)
        with self.mesh:
            self._insert_blocks(full[:n], self.state.cache, slot)
        pc.pin_session(session_id, full[:n], ttl_s)
        self._prefix_evictions += pc.evict_to_budget()

    def release_session(self, session_id: str) -> bool:
        """Explicitly drop a session's transcript pin (session closed)."""
        if self.prefix_cache is None:
            return False
        return self.prefix_cache.release_session(session_id)

    def session_pin_stats(self) -> Optional[Tuple[int, int]]:
        """(live pinned sessions, blocks their paths hold resident) for
        the session gauges; None without a prefix cache. Expires lapsed
        pins as a side effect so the gauge never counts dead sessions."""
        pc = self.prefix_cache
        if pc is None:
            return None
        pc.expire_sessions()
        return pc.session_count, pc.session_pinned_blocks()

    def _live(self) -> bool:
        return any(
            r is not None and not r.finished and r.live
            for r in self._slot_req
        )

    def _departing(self) -> List[_Request]:
        """Requests whose slot has been handed on (`_stage_admissions`)
        and whose end is not reaped yet: in the snapshot of a dispatch in
        flight, no longer in `_slot_req`."""
        out: Dict[int, _Request] = {}
        for entry in self._inflight:
            for slot, req in enumerate(entry[6]):
                if (req is not None and not req.finished
                        and self._slot_req[slot] is not req):
                    out[req.rid] = req
        return list(out.values())

    def _any_staged(self) -> bool:
        """Any slot whose staged prefill is still advancing inside the
        scan — device work that must keep dispatching
        even when no slot is live yet."""
        return any(
            r is not None and not r.finished and not r.live
            for r in self._slot_req
        )

    def _step_keys(self, k: int) -> jax.Array:
        """Stack the next `k` sequential host splits into a [k] key
        array for a megastep, one a chunk: the key chunk j consumes does
        not depend on how the chunks were grouped into dispatches (greedy
        streams are identical by construction; stochastic streams match
        too whenever the admission interleaving matches)."""
        keys = []
        # The split, the unpacking of its result and the stack are device
        # programs of their own: whichever of a turn's calls meets the
        # runtime's full launch queue sleeps until the device finishes a
        # program, and on the chip it is most often the first of these.
        with self._span(KEYS):
            for _ in range(k):
                self._rng, r = jax.random.split(self._rng)
                keys.append(r)
            return jnp.stack(keys)

    def _rows_to_end(self, slot: int) -> Optional[int]:
        """`rows_to_certain_end` of the request in `slot`: its budget
        left, net of the rows of the in-flight dispatches whose snapshot
        holds it in this lane (host-known lengths lag the device by the
        pipeline depth; the dispatched debt is what closes the gap)."""
        req = self._slot_req[slot]
        debt = sum(
            entry[2].shape[0] * self.chunk
            for entry in self._inflight if entry[6][slot] is req
        )
        return rows_to_certain_end(req, self.tmax, debt)

    def _end_in_flight(self, slot: int) -> bool:
        """The request in `slot` is certain to have had its last token
        inside the dispatches already in flight, so the slot can be
        staged for a successor now. Never a session turn: its transcript
        is published from the slot's pages when its end is reaped
        (`_publish_session`), so its slot waits for that reap."""
        return (self._rows_to_end(slot) == 0
                and self._slot_req[slot].rid not in self._session_reqs)

    def _slack_chunks(self) -> Optional[int]:
        """Device chunks until some live slot is GUARANTEED to free — the
        K controller's admission-opportunity horizon (see
        next_megastep_k): the least `_rows_to_end` over the slots, in
        chunks of `chunk` rows, rounded up. None when no live slot bounds
        the horizon (staged requests bound nothing until their flip).
        Called after `_stage_admissions`, so a slot whose end was in
        flight while a request waited has been handed on and no longer
        counts: 0 is read only where nobody is pending, or for a session
        turn. Early eos/over-acceptance can beat the bound — that
        exposure is the dead-lane account, capped by the in-progress
        K*chunk."""
        rows = [r for r in map(self._rows_to_end, range(self.slots))
                if r is not None]
        return -(-min(rows) // self.chunk) if rows else None

    def _canon_state(self, state: SlotState) -> SlotState:
        """Respell every plane's sharding to its plane-table spec before
        a dispatch (see _plane_spec) — the KV planes to their tp heads
        sharding, the host planes to replicated. A device_put against an
        equivalent sharding is a zero-copy Array rewrap (same buffers),
        so the steady state — planes already canonical — costs the
        equality checks and nothing else; only a program that emitted a
        genuinely different layout would pay a real reshard, and the
        compile-count guards would surface it as a cache miss first."""

        def put(x, name):
            sh = jax.sharding.NamedSharding(self.mesh, _plane_spec(name))
            return x if x.sharding == sh else jax.device_put(x, sh)

        return state._replace(
            tok=put(state.tok, "tok"),
            active=put(state.active, "active"),
            seen=put(state.seen, "seen"),
            transcript=put(state.transcript, "transcript"),
            staged=put(state.staged, "staged"),
            stage_cursor=put(state.stage_cursor, "stage_cursor"),
            stage_len=put(state.stage_len, "stage_len"),
            stage_seq=put(state.stage_seq, "stage_seq"),
            stage_rng=put(state.stage_rng, "stage_rng"),
            cache=state.cache._replace(
                k=put(state.cache.k, "cache.k"),
                v=(None if state.cache.v is None
                   else put(state.cache.v, "cache.v")),
                ks=(None if state.cache.ks is None
                    else put(state.cache.ks, "cache.ks")),
                vs=(None if state.cache.vs is None
                    else put(state.cache.vs, "cache.vs")),
                length=put(state.cache.length, "cache.length"),
                ssm=(None if state.cache.ssm is None
                     else put(state.cache.ssm, "cache.ssm")),
                conv=(None if state.cache.conv is None
                      else put(state.cache.conv, "cache.conv")),
                pool=(None if state.cache.pool is None
                      else put(state.cache.pool, "cache.pool")),
            ),
            snap_ssm=(None if state.snap_ssm is None
                      else put(state.snap_ssm, "snap_ssm")),
            snap_conv=(None if state.snap_conv is None
                       else put(state.snap_conv, "snap_conv")),
            snap_at=(None if state.snap_at is None
                     else put(state.snap_at, "snap_at")),
        )

    def _canon_block(self, blk: KVBlock) -> KVBlock:
        """Respell an exported prefix block's planes to the plane-table
        KV sharding before it enters the radix tree, so every cached
        block is a per-shard device-resident run under ONE sharding: a
        later hit splices tp-sharded blocks straight into the (equally
        sharded) live pages without a gather, and every `_stage_block`
        dispatch sees one canonical block sharding (one jit-cache key).
        Zero-copy when the export already propagated the table spec —
        the steady state."""

        def put(x, name):
            sh = jax.sharding.NamedSharding(self.mesh, _plane_spec(name))
            return x if x.sharding == sh else jax.device_put(x, sh)

        return blk._replace(
            k=put(blk.k, "k"),
            v=None if blk.v is None else put(blk.v, "v"),
            ks=None if blk.ks is None else put(blk.ks, "ks"),
            vs=None if blk.vs is None else put(blk.vs, "vs"),
            pool=None if blk.pool is None else put(blk.pool, "pool"),
        )

    def _canon_snapshot(self, snap: StateSnapshot) -> StateSnapshot:
        """`_canon_block` for an exported state snapshot."""

        def put(x, name):
            sh = jax.sharding.NamedSharding(self.mesh, _plane_spec(name))
            return x if x.sharding == sh else jax.device_put(x, sh)

        return StateSnapshot(
            ssm=None if snap.ssm is None else put(snap.ssm, "ssm"),
            conv=None if snap.conv is None else put(snap.conv, "conv"))

    def step(self) -> List[Tuple[int, str]]:
        """Stage pending requests, dispatch the next megastep — K chunks
        of `chunk` tokens, K the controller's — and reap the oldest
        in-flight dispatch once the pipeline is full.

        Admission stages into empty slots and into slots whose
        request's end is certain to lie in the dispatches in flight
        (`_stage_admissions`): such a slot then carries two requests at
        once, the departing one in the in-flight snapshots until `_walk`
        reaps its end, and its successor in `_slot_req` and every
        dispatch from this one on.

        Pipelining (inflight_limit=2 default): the dispatch for program
        N+1 goes out BEFORE program N's tokens are read back, so the
        host's readback and reap overlap N+1's device compute instead of
        leaving the device idle for them. Completions therefore surface
        one step() call after their dispatch at steady state; the tail
        drains in the same call once no live slot remains (`draining`
        reaps: the dispatch ledger times none of them). Admissions join
        at dispatch boundaries, so the controller (next_megastep_k) sizes
        K against the waiting work's actual admission opportunity — the
        guaranteed-finish horizon from _slack_chunks — keeping megasteps
        wide under saturation and boundaries exact where a pending
        request can join.
        """
        with self._span("engine.step"):
            with self._span("engine.admit"):
                self._stage_admissions()
            if self._live() or self._any_staged():
                # The backlog the controller sizes K against counts a
                # request until its predecessor's end is reaped, as it
                # did when it waited in `_pending` for that reap: a slot
                # handed on changes where the successor waits, not that
                # work was waiting for a slot. (An empty backlog grows K,
                # and a dispatch of K*chunk rows sent while the ends in
                # flight are unreaped strands every lane in it.)
                backlog = len(self._pending) + len(self._departing())
                self.megastep_k = next_megastep_k(
                    self.megastep_k, self.megastep_ks, backlog,
                    self._slack_chunks(),
                )
                if backlog and self.megastep_k == 1:
                    self._count(one_chunk_dispatches=1)
                with self._span("engine.dispatch", k=self.megastep_k):
                    self._dispatch(self.megastep_k)
            done: List[Tuple[int, str]] = []
            while self._inflight:
                # _reap may finish the last live request: `busy` is read
                # anew each time round, so remaining dispatches drain
                # right here.
                busy = self._live() or self._any_staged()
                if busy and len(self._inflight) < self.inflight_limit:
                    break
                done.extend(self._reap(*self._inflight.pop(0),
                                       draining=not busy))
        return done

    def _dispatch(self, k: int) -> None:
        """Send the device the megastep at rung `k`."""
        self.state = self._canon_state(self.state)
        counts = moe = None
        rngs = self._step_keys(k)
        dry = self._ledger.device_dry()
        with self.mesh, self._span(PROG + "megastep", k=k):
            self.state, *outs = self._megastep(
                self.params, self.state, rngs
            )
        if self.family.routed:
            *outs, moe = outs
        *outs, flipped, firsts, served = outs
        if self.spec:
            toks, counts, active, dead = outs
        else:
            toks, active, dead = outs
        self._ledger.dispatched(toks.is_ready, dry)
        self._count(scan_iterations=k * self.chunk,
                    lane_steps=k * self.chunk * self.slots)
        if self._lanes is not None:
            self._lanes.dispatched(self.state.cache.k.shape[3],
                                   self._attn_width())
        self._push_inflight(toks, counts, active, dead, flipped, firsts,
                            moe, served)

    def _push_inflight(self, toks, counts, active, dead, flipped,
                       firsts, moe=None, served=None) -> None:
        """Queue one dispatched program's output buffers for a later reap.

        No blocking readback here — but START the device->host copies
        now, so the dispatch's results stream back while later programs
        compute and the reap's device_get finds them already on the
        host. The flipped/firsts planes ([K, chunk, S]) ride the
        same pipe, so learning a staged slot went live costs no extra
        sync.
        """
        for arr in (toks, counts, active, dead, flipped, firsts, moe,
                    served):
            if arr is not None:
                arr.copy_to_host_async()
        # The slot snapshot records which request each column belonged
        # to at dispatch time (a slot reused later belongs to a later
        # dispatch).
        self._inflight.append((toks, counts, active, dead, flipped,
                               firsts, list(self._slot_req), moe, served))

    def _count_moe(self, counts) -> None:
        """A routed family's counts of some forward passes, read back
        from the device (`_forward`), each into the counter of its name."""
        self._count(**{name: int(c) for name, c
                       in zip(self.family.counters, counts)})

    def _reap(self, toks_dev, counts_dev, active_dev, dead_dev,
              flipped_dev, firsts_dev, slot_snapshot,
              moe_dev=None, served_dev=None,
              draining: bool = False) -> List[Tuple[int, str]]:
        """Read one dispatch's results — a megastep's whole [K, chunk, S]
        plane in one batched pass — and finish the requests it completed.
        The same pass also learns which staged slots FLIPPED live
        mid-megastep (the flipped/firsts planes): the flip's first token
        becomes the request's stream head (TTFT recorded here — the first host moment
        the token exists), its prompt blocks publish into the radix tree
        straight from the live cache, and its decode walk starts at the
        flip's row (earlier rows are pre-flip pad filler, not content)."""
        # THE sync point of the engine loop.
        passes = crowded = 0
        with self._span("engine.reap.wait") as wait, intended_transfer():
            toks = np.asarray(toks_dev)  # [K, chunk, S(, k+1)]
            counts = None if counts_dev is None else np.asarray(counts_dev)
            active = np.asarray(active_dev)  # [K, S] per-chunk snapshots
            self._dead_lane_tokens += int(np.asarray(dead_dev))
            flipped = np.asarray(flipped_dev)  # [K, chunk, S]
            firsts = np.asarray(firsts_dev)    # [K, chunk, S]
            if moe_dev is not None:
                self._count_moe(np.asarray(moe_dev))
            if served_dev is not None:
                passes, served, crowded = np.asarray(served_dev).tolist()
                # A dispatch of more than one chunk serves one slot a
                # pass whatever is staged (`_megastep_program`).
                self._count(prefill_passes=passes,
                            prefill_pass_slots=served,
                            prefill_crowded_passes=crowded,
                            prefill_crowded_narrow_passes=(
                                crowded if toks.shape[0] > 1 else 0))
        k = toks.shape[0]
        device_us = self._ledger.reaped(k, k * toks.shape[1], passes,
                                        crowded, draining)
        self._observe("reap_wait", wait.wall_s)
        with self._span("engine.reap.host", k=k, passes=passes,
                        wide=crowded if k == 1 else 0,
                        device_us=device_us):
            if self._lanes is not None and self._lanes.sent:
                self._count_attn_positions(toks, flipped, firsts,
                                           slot_snapshot,
                                           *self._lanes.sent.pop(0))
            return self._walk(toks, counts, active, flipped, firsts,
                              slot_snapshot)

    def _count_attn_positions(self, toks, flipped, firsts, slot_snapshot,
                              ops, width: int, held: int) -> None:
        """`_LaneLengths`' turn of a reap, before `_walk` queues its kills:
        the host's moves `ops` that went before the dispatch, then its
        rows at its `width`; counted where the attention went by lengths
        (`held`: the width then, else 0)."""
        lanes = toks.shape[-1]
        read = self._lanes.replay(
            ops, toks.reshape(-1, lanes), flipped.reshape(-1, lanes),
            firsts.reshape(-1, lanes),
            np.fromiter((0 if r is None else r.prompt_len
                         for r in slot_snapshot), np.int64, lanes),
            self.tokenizer.eos_id, width)
        if held:
            self._count(attn_positions_read=read,
                        attn_positions_held=held * toks.size)

    def _walk(self, toks, counts, active, flipped, firsts,
              slot_snapshot) -> List[Tuple[int, str]]:
        """The host half of a reap: one dispatch's tokens and lanes."""
        # Flatten the K axis into one [K*chunk, S] token walk, a row per
        # scan iteration. Dead-slot detection keys off the FINAL snapshot:
        # a slot that died in chunk j padded every later lane, exactly
        # like a mid-chunk death pads the chunk tail.
        toks = toks.reshape(toks.shape[0] * toks.shape[1], *toks.shape[2:])
        if counts is not None:
            counts = counts.reshape(-1, counts.shape[-1])
        flipped = flipped.reshape(-1, flipped.shape[-1])
        firsts = firsts.reshape(-1, firsts.shape[-1])
        active = active[-1]
        done: List[Tuple[int, str]] = []
        eos, pad = self.tokenizer.eos_id, self.tokenizer.pad_id
        now = time.monotonic()
        rows = toks.shape[0]  # this dispatch's scan iterations
        decoded = staged = overrun = 0
        for slot, req in enumerate(slot_snapshot):
            if req is None or req.finished:
                # Empty at dispatch, or finished by an earlier chunk — this
                # chunk's column holds dead-slot filler (a finished one's
                # lane ran all the same: overrun).
                overrun += rows if req is not None else 0
                continue
            start_row = 0
            if not req.live:
                # Staged at dispatch time: only a flip makes this column
                # meaningful. No flip yet -> the prefill is still
                # advancing; the column is pad filler and the slot's
                # inactive flag must NOT read as a death.
                col = flipped[:, slot]
                if not col.any():
                    staged += rows
                    req.staged_rows += rows
                    continue
                # The flip's ROW is the slot's first decode iteration:
                # earlier rows are pre-flip filler.
                start_row = int(np.argmax(col))
                req.tokens = [int(firsts[start_row, slot])]
                req.live = True
                self._first_token(req, now)
                if self.prefix_cache is not None:
                    self._publish_staged(req, slot)
                staged += start_row
                self._observe("staged_iterations",
                              req.staged_rows + start_row)
            finished = False
            dead = not bool(active[slot])
            n_before = len(req.tokens)
            if counts is None:
                # Plain step: one token per scan iteration; a dead slot's
                # column holds pad filler (detected below).
                stream, filler = toks[start_row:, slot], True
            else:
                # Spec step: each scan iteration is a verify window; the
                # first counts[c, slot] columns are its tokens in order
                # (contiguous-prefix validity). Inactive windows emit
                # nothing, so there is no filler to detect. Windows run
                # while the request was live feed the acceptance stats.
                col = counts[start_row:, slot]
                live = col > 0
                self._spec_windows += int(np.sum(live))
                self._spec_emitted += int(np.sum(col))
                stream = [
                    t for c in range(col.shape[0])
                    for t in toks[start_row + c, slot, : int(col[c])]
                ]
                filler = False
            for t in stream:
                tok = int(t)
                if tok == eos:
                    # eos lands in the transcript when it's a distinct
                    # token (decode() filters it); GPT-2's pad==eos stays
                    # out, matching the reference's decoded text.
                    if tok != pad:
                        req.tokens.append(tok)
                    finished = True
                    break
                if filler and dead and tok == pad:
                    # Inactive-slot filler (the slot died at admission or
                    # in an earlier chunk, before any eos could appear in
                    # THIS chunk) — not content. Matters when pad != eos:
                    # without the device flag these pads would be appended
                    # as answer tokens. Spec streams carry no filler.
                    finished = True
                    break
                req.tokens.append(tok)
                # Final clause: force-finish a slot whose cache hit tmax
                # (only reachable if a caller bypasses the __init__ length
                # check) — past tmax the clamped scatter would corrupt its
                # newest KV slot.
                if (
                    len(req.tokens) >= req.max_new
                    or req.prompt_len + len(req.tokens) >= self.tmax
                ):
                    finished = True
                    break
            self._emitted_tokens += len(req.tokens) - n_before
            decoded += len(req.tokens) - n_before
            self._count_past_window(req, n_before)
            if finished and not dead and counts is None:
                # The host's budget cap, which the device does not know.
                overrun += rows - start_row - (len(req.tokens) - n_before)
            if dead:
                finished = True
            if finished:
                req.finished = True
                self._staged_prompts.pop(req.rid, None)
                pin = self._prefix_pins.pop(req.rid, None)
                if pin is not None and self.prefix_cache is not None:
                    # The slot no longer reads shared blocks: unpin its
                    # matched path so eviction may reclaim it.
                    self.prefix_cache.release(pin)
                if (req.rid in self._session_reqs
                        and self._slot_req[slot] is req):
                    # Session turn: publish + pin the full transcript
                    # while the slot's pages still hold its KV.
                    self._publish_session(req, slot)
                self._session_reqs.pop(req.rid, None)
                self.total_generated_tokens += len(req.tokens)
                text = self.tokenizer.decode(
                    [t for t in req.tokens if t != eos]
                )
                if req.rid in self._stream_watch:
                    self._final_tokens[req.rid] = [
                        t for t in req.tokens if t != eos
                    ]
                    self._stream_watch.discard(req.rid)
                done.append((req.rid, text))
                if self._slot_req[slot] is req:
                    self._slot_req[slot] = None
                    # Kill the slot in the LIVE state (which may already
                    # be a chunk ahead): load-bearing for the host-side
                    # max_new/tmax caps, where the device still thinks the
                    # slot is active. A slot that was handed on needs no
                    # kill, and must not get one: `_stage_program` has
                    # reset the lane, and it is the successor's now.
                    self.state = self.state._replace(
                        active=self.state.active.at[slot].set(False)
                    )
                    if self._lanes is not None:
                        self._lanes.ops.append((slot, -1))
        self._count(staged_lane_steps=staged, overrun_lane_steps=overrun)
        self._observe("decode_lanes", decoded / rows)
        return done

    def _count_past_window(self, req: _Request, n_before: int) -> None:
        """Of the tokens `req` gained since it had `n_before`, count those
        at a position at or past the model's `sliding_window` (token i of
        an answer sits at position prompt_len + i): there every window
        layer has dropped keys. Nothing for a model without a window."""
        window = getattr(self.cfg, "sliding_window", None)
        if window:
            first = max(n_before, window - req.prompt_len)
            self._count(tokens_past_window=max(0, len(req.tokens) - first))

    def _first_token(self, req: _Request, now: float) -> None:
        """A request's first token reached the host."""
        self._emitted_tokens += 1
        self._count_past_window(req, 0)
        self.ttfts[req.rid] = self.last_ttft_s = now - req.submit_time
        self._observe("prefill_wait", now - req.popped_time)

    def drain(self) -> Dict[int, str]:
        out: Dict[int, str] = {}
        while self.has_work:
            for rid, text in self.step():
                out[rid] = text
        return out
