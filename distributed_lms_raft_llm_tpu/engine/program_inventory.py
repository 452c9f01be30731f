"""Compiled-program inventory: the static manifest of every jit entry point.

The serving engine promises "no live request pays an XLA compile": warmup
compiles the whole program set, `utils/guards.compile_count_guard` polices
the promise at runtime, and the PR-2/PR-3 incidents showed how quietly it
breaks — a spelling-different sharding or a refactored handoff leaves one
(S, width) program uncovered and the first production request stalls for
tens of seconds of XLA. This module is the contract written down:

- `INVENTORY` (between the ``inventory:begin/end`` markers) is GENERATED
  by ``python scripts/gen_program_inventory.py --write`` from the static
  jit scan (`analysis/absint.scan_jit_sites` over the engine modules) and
  checked in. The `program-inventory` lint rule re-derives it on every
  run: an uninventoried `jax.jit(...)` in the engine, a stale entry, a
  drifted `donate_argnums`, or a warmup routine that no longer reaches an
  inventoried program all fail tier-1.
- `expected_counts(engine)` turns the manifest into per-program expected
  cache sizes for a live engine's static-argument domain (prompt buckets
  x cache widths, derived by `static_paged_domain` — the same math
  `PagedEngine.__init__` runs, cross-checked against the live engine so
  the mirror cannot rot silently).
- `utils/guards.expected_from_inventory(engine)` feeds those counts to
  `compile_count_guard`, which then asserts EXACT equality after warmup:
  actual > expected means warmup missed a program (the classic incident);
  actual < expected means the inventory is stale. Drift in either
  direction fails.

Coverage classes: ``warmup`` entries are the serving set plus the
bulk-scoring program (`_score`, domain ``score-pairs`` — empty
when `EngineConfig.scoring` is off, so the claim holds in both modes) —
warmup must compile their full domain, statically checked by the lint
rule and dynamically by the guard. ``on-demand`` entries (the BERT gate
encoder, `paged._chunk`) compile per live shape by design; they
are inventoried so new entry points cannot ship unclassified, but no
warmup claim is made for them (`paged._chunk` is the module-level jit the
megasteps of one engine share their traces through). `engine/engine.py` (`TutoringEngine`, the
bucketed generator the tests hold `PagedEngine`'s answers equal to) serves
nothing and is left out of the scan (`analysis/absint.REFERENCE_REL`).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

from ..utils.guards import InventoryMismatchError


@dataclasses.dataclass(frozen=True)
class ProgramEntry:
    """One jit entry point of an engine class."""

    engine: str                      # owning class ("" for module level)
    attr: str                        # bound attribute, e.g. "_step"
    target: str                      # wrapped program function
    donate_argnums: Tuple[int, ...]
    static_argnums: Tuple[int, ...]
    domain: str                      # static-argument domain, see _DOMAIN_SIZES
    coverage: str                    # "warmup" | "on-demand"


# --- inventory:begin --- (generated by scripts/gen_program_inventory.py)
INVENTORY: Tuple[ProgramEntry, ...] = (
    ProgramEntry(
        engine="", attr="_chunk", target="_chunk_program",
        donate_argnums=(), static_argnums=(),
        domain="shapes", coverage="on-demand",
    ),
    ProgramEntry(
        engine="PagedEngine", attr="_export_block", target="_export_block_program",
        donate_argnums=(), static_argnums=(),
        domain="export-widths", coverage="warmup",
    ),
    ProgramEntry(
        engine="PagedEngine", attr="_export_run", target="_export_block_program",
        donate_argnums=(), static_argnums=(),
        domain="run-export-widths", coverage="warmup",
    ),
    ProgramEntry(
        engine="PagedEngine", attr="_export_state", target="_export_state_program",
        donate_argnums=(), static_argnums=(),
        domain="state-widths", coverage="warmup",
    ),
    ProgramEntry(
        engine="PagedEngine", attr="_grow", target="_grow_state_program",
        donate_argnums=(0,), static_argnums=(1,),
        domain="width-pairs", coverage="warmup",
    ),
    ProgramEntry(
        engine="PagedEngine", attr="_megastep", target="_megastep_program",
        donate_argnums=(1,), static_argnums=(),
        domain="width-k-pairs", coverage="warmup",
    ),
    ProgramEntry(
        engine="PagedEngine", attr="_restore_state", target="_restore_state_program",
        donate_argnums=(0,), static_argnums=(),
        domain="state-widths", coverage="warmup",
    ),
    ProgramEntry(
        engine="PagedEngine", attr="_score", target="_score_program",
        donate_argnums=(), static_argnums=(),
        domain="score-pairs", coverage="warmup",
    ),
    ProgramEntry(
        engine="PagedEngine", attr="_stage", target="_stage_program",
        donate_argnums=(0,), static_argnums=(),
        domain="stage-pairs", coverage="warmup",
    ),
    ProgramEntry(
        engine="PagedEngine", attr="_stage_block", target="_stage_block_program",
        donate_argnums=(0,), static_argnums=(),
        domain="stage-widths", coverage="warmup",
    ),
    ProgramEntry(
        engine="RelevanceGate", attr="_embed", target="bert.embed",
        donate_argnums=(), static_argnums=(),
        domain="shapes", coverage="on-demand",
    ),
)
# --- inventory:end ---


def entries_for(engine_name: str) -> List[ProgramEntry]:
    return [e for e in INVENTORY if e.engine == engine_name]


def effective_megastep_max(megastep: int, megastep_max: int) -> int:
    """The controller ceiling actually in force: `megastep_max` when set
    (>0) is THE ceiling — a starting `megastep` configured above it is
    clamped down to it (the explicit cap on worst-case admission wait
    wins); 0 means follow `megastep`. Floored at 1. ONE derivation
    shared by `PagedEngine.__init__`, the bench harnesses' recorded
    knobs, and the generated inventory domain, so the records and the
    manifest cannot silently diverge from the live engine's compiled
    ladder."""
    return max(1, megastep_max) if megastep_max > 0 else max(1, megastep)


def megastep_ladder(megastep_max: int) -> List[int]:
    """The controller-reachable megastep sizes: 1 (the plain chunked
    step) plus doubling rungs up to `megastep_max`, which is always the
    top rung even when it is not a power of two (e.g. 6 -> [1, 2, 4, 6]).
    Pure math shared by `PagedEngine.__init__` (which warms one megastep
    program per (width, rung) pair) and `static_paged_domain` (which
    predicts that count for the runtime guard) — the same mirror-and-
    cross-check contract as the bucket/width derivation."""
    out = [1]
    k = 2
    while k < megastep_max:
        out.append(k)
        k *= 2
    if megastep_max > 1:
        out.append(megastep_max)
    return out


# Fused admission splices what the tree holds as blocks of their own (a short
# prompt's 6 to 15, an edge's remainder, the tail after the last stored run:
# STORED_RUN_BLOCKS below) in runs of up to this many blocks, concatenated on
# the device, one `_stage_block` dispatch a run. The runtime holds few
# programs ahead of the device, so every small program of an admission is
# launched while the device waits for it: at a dispatch a block a context
# of 2,304 tokens (144 blocks) held the device for as long as its splices
# took, and the 6 to 10 blocks of a short prompt cost as many launches
# (PERF.md sections 5 and 6, PR 30). A run is always STAGE_RUN_BLOCKS blocks
# wide (a short one is padded with its last block and told how many of its
# tokens count), so a width compiles one program for all of them.
STAGE_RUN_BLOCKS = 16

# A long edge of the prefix tree rests as STORED RUNS of this many blocks, one
# array a plane (engine/prefix_cache.py `RunBlock`): a publish cuts a run out
# of the slot with one `_export_run` launch and a hit splices it with one
# `_stage_block` launch, nothing concatenated. A launch costs the loop's
# thread about 0.4 ms on the chip's machine whatever it hands over (a stored
# run's 5 arrays, or 16 blocks' 80 at 0.5), so at a launch for every 16
# blocks a 32,768-token reader's 2,051 blocks held the thread 68 ms an
# admission and the device waited for it a sixth of the window: what the
# launch a block was at 144 blocks. By run length, the same hit
# (`scripts/probe_splice.py`, PERF.md section 6, PR 55): 64 blocks 18 ms,
# 128 12.5, 256 8.3; 128 is kept because a notes context of 144 blocks is
# then a run too, and what is left over after the last whole run (up to a
# run less a block, 16 blocks a launch) grows with the run. Who gets stored
# runs follows from the publish (how many new consecutive blocks it adds) and
# the cache width being served (`width_holds_stored_run`), never from a
# model's name.
STORED_RUN_BLOCKS = 128


def stage_runs(n_blocks: int, fit_blocks: int, start: int = 0) -> list:
    """How fused admission splices `n_blocks` consecutive blocks that rest
    on their own, the first at block `start` of a cache `fit_blocks` blocks
    wide: (first block of the stretch, blocks in the call) per
    `_stage_block` dispatch, runs of up to STAGE_RUN_BLOCKS. A run's
    program writes a whole run's width, so a last run too near the cache's
    end starts earlier and splices again some blocks the run before it
    brought; a lone block goes alone, and so does every block where the
    cache has no room for a run from `start`."""
    out, i = [], 0
    room = fit_blocks - start
    while i < n_blocks:
        n = min(STAGE_RUN_BLOCKS, n_blocks - i)
        if n == 1 or room < STAGE_RUN_BLOCKS:
            out.append((i, 1))
            i += 1
            continue
        first = min(i, room - STAGE_RUN_BLOCKS)
        out.append((first, i + n - first))
        i += n
    return out


def bucket_has_runs(bucket: int, block_tokens: int, width: int) -> bool:
    """Whether a prompt of this bucket can share two blocks or more
    (`plan_staged` leaves at least one token to recompute) in a cache wide
    enough for a run's program."""
    blk = max(1, block_tokens)
    return (bucket - 1) // blk >= 2 and STAGE_RUN_BLOCKS * blk <= width


def bucket_holds_stored_run(bucket: int, block_tokens: int,
                            run_blocks: int) -> bool:
    """Whether a prompt of this bucket can publish a stored run: its whole
    blocks are a run or more."""
    return run_blocks > 0 and bucket // max(1, block_tokens) >= run_blocks


def width_holds_stored_run(width: int, block_tokens: int,
                           run_blocks: int) -> bool:
    """Whether a cache this wide can be handed a stored run at all (at
    offset 0): `dynamic_update_slice` needs the run's whole width inside
    the plane. At a given offset the engine asks again
    (`PagedEngine._splice`: a start that would pass the end is CLAMPED by
    the program, so such a run reaches the slot as blocks)."""
    return run_blocks > 0 and run_blocks * max(1, block_tokens) <= width


def static_paged_domain(
    max_position_embeddings: int,
    max_new_tokens: int,
    length_buckets: Tuple[int, ...],
    spec_tokens: int,
    megastep_max: int = 0,
    prefix_cache: bool = False,
    prefix_block_tokens: int = 16,
    tp: int = 1,
    ep: int = 1,
    recurrent_state: bool = False,
    stored_run_blocks: int = STORED_RUN_BLOCKS,
) -> Dict[str, object]:
    """The paged engine's static-argument domain, derived from config the
    same way `PagedEngine.__init__` derives it: warmed prompt buckets,
    cache widths, and the admissible (bucket, width) stage pairs.

    Pure math over config values — `scripts/gen_program_inventory.py` runs
    it over config.py defaults and every `configs/*.toml` for the README
    table, and `expected_counts` runs it against a live engine (then
    cross-checks the result against the engine's own derivation, so this
    mirror cannot drift without failing the guard).

    `tp`/`ep` are the mesh the engine compiles its domain UNDER: the
    per-entry counts are per engine — one engine, one mesh — so the
    counts themselves don't scale with the mesh, but the domain a
    manifest row describes is keyed by it (a tp=2 engine's programs are
    different XLA programs than a tp=1 engine's, sharded over the plane
    table's specs), and `expected_counts` cross-checks the live engine's
    mesh axes against this key so a manifest generated for one mesh
    cannot silently vouch for another.

    Admission is staged: `_stage` compiles per admissible (bucket,
    width) pair, `_stage_block` splices shared-prefix blocks per width,
    block export reads the live cache (per width, where any bucket can
    hold a block), and every rung of the ladder, rung 1 included, is one
    `_megastep` program per width. Where a bucket's prompts are a stored
    run long (`stored_run_blocks`), every width a run fits exports runs
    (`_export_run`) and splices them (one more `_stage_block` program),
    and one more `_export_block` program cuts a block out of a run for a
    cache too narrow for it.
    """
    extra = max(0, spec_tokens - 1)
    bucket_cap = min(
        max(length_buckets), max_position_embeddings - max_new_tokens - extra
    )
    buckets = sorted({min(b, bucket_cap) for b in length_buckets})
    nat = {
        t: min(t + max_new_tokens, max_position_embeddings) + extra
        for t in buckets
    }
    widths = sorted(set(nat.values()))
    ladder = megastep_ladder(megastep_max)
    # Shared-prefix programs (engine/prefix_cache.py), zero when the
    # cache is off (the jit wrappers exist either way) or no bucket can
    # hold a block.
    blk = max(1, prefix_block_tokens)
    blocks = prefix_cache and any(t >= blk for t in buckets)
    # Widths a stored run fits, where some bucket's prompts are a run long
    # (else the tree never holds one): each exports runs and splices them.
    run_widths = sum(
        1 for w in widths
        if width_holds_stored_run(w, blk, stored_run_blocks)
    ) if blocks and any(
        bucket_holds_stored_run(t, blk, stored_run_blocks) for t in buckets
    ) else 0
    return {
        "mesh": {"tp": max(1, tp), "ep": max(1, ep)},
        "buckets": buckets,
        "widths": widths,
        "stage_pairs": sum(
            1 for w in widths for t in buckets if nat[t] <= w
        ),
        # One splice program per width, and one more (a run of blocks in
        # one call) per width that serves a bucket whose prompts can share
        # two blocks or more and is wide enough for a run; where the
        # tree can hold stored runs, one more per width one fits.
        "stage_block_widths": (
            len(widths) + sum(
                1 for w in widths
                if any(bucket_has_runs(t, blk, w) and nat[t] <= w
                       for t in buckets)
            ) + run_widths
            if blocks else 0
        ),
        "grow_pairs": len(widths) * (len(widths) - 1) // 2,
        # The K is encoded in the stacked rng shape, not a static argnum.
        "k_ladder": ladder,
        "megastep_pairs": len(widths) * len(ladder),
        # Besides the widths: a block cut out of a stored run (the run
        # handed over as a one-slot cache, whatever the width served).
        "export_widths": (
            len(widths) + (1 if run_widths else 0) if blocks else 0),
        "run_export_widths": run_widths,
        # A recurrent family's snapshot restore and export
        # (`ModelFamily.recurrent_state`): one program a width each,
        # beside the block programs.
        "state_widths": len(widths) if blocks and recurrent_state else 0,
    }


def static_score_domain(
    length_buckets: Tuple[int, ...],
    batch_buckets: Tuple[int, ...],
    max_position_embeddings: int,
    enabled: bool = True,
) -> Dict[str, object]:
    """The bulk-scoring program's static-argument domain: every
    (batch bucket, length bucket) device shape `score_texts` can
    dispatch, empty when `EngineConfig.scoring` is off (the wrapper
    exists either way — the _megastep precedent).

    Pure math mirroring `engine/scoring.derive_score_shapes` the same way
    `static_paged_domain` mirrors `PagedEngine.__init__`: the generator
    runs it over config defaults and shipped TOMLs, and `expected_counts`
    cross-checks it against the live engine's own derivation
    (`engine.score_shapes`) so neither side can rot silently. Length
    buckets cap at the position table.
    """
    if not enabled:
        return {"pairs": [], "score_pairs": 0}
    limit = min(max(length_buckets), max_position_embeddings)
    buckets = {min(b, limit) for b in length_buckets}
    pairs = sorted((nb, t) for nb in set(batch_buckets) for t in buckets)
    return {"pairs": pairs, "score_pairs": len(pairs)}


_DOMAIN_SIZES = {
    "stage-pairs": lambda d: d["stage_pairs"],
    "stage-widths": lambda d: d["stage_block_widths"],
    "width-pairs": lambda d: d["grow_pairs"],
    "width-k-pairs": lambda d: d["megastep_pairs"],
    "export-widths": lambda d: d["export_widths"],
    "run-export-widths": lambda d: d["run_export_widths"],
    "state-widths": lambda d: d["state_widths"],
}


def expected_counts(engine) -> Dict[str, int]:
    """Per-attribute expected program-cache sizes after `engine.warmup()`,
    for the engine's warmup-covered inventory entries.

    Raises InventoryMismatchError when the manifest's domain math and the
    live engine disagree (a stale manifest, or engine bucket/width
    derivation that moved without regenerating) — the cross-validation
    half of the static<->runtime contract.
    """
    name = type(engine).__name__
    entries = [e for e in entries_for(name) if e.coverage == "warmup"]
    if not entries:
        raise InventoryMismatchError(
            f"no warmup-covered inventory entries for {name}; "
            "expected_from_inventory only applies to engines whose warmup "
            "compiles the full program set (see engine/program_inventory.py)"
        )
    score_entries = [e for e in entries if e.domain == "score-pairs"]
    paged_entries = [e for e in entries if e.domain != "score-pairs"]
    counts: Dict[str, int] = {}
    if paged_entries:
        dom = static_paged_domain(
            engine.cfg.max_position_embeddings,
            engine.config.sampling.max_new_tokens,
            tuple(engine.config.length_buckets),
            engine.config.spec_tokens,
            megastep_max=getattr(engine, "megastep_max", 0),
            prefix_cache=getattr(engine, "prefix_cache", None) is not None,
            prefix_block_tokens=getattr(engine, "prefix_block_tokens", 16),
            tp=engine.config.tp, ep=engine.config.ep,
            recurrent_state=engine.family.recurrent_state,
            stored_run_blocks=getattr(
                engine, "stored_run_blocks", STORED_RUN_BLOCKS),
        )
        live_mesh = {
            "tp": int(engine.mesh.shape.get("tp", 1)),
            "ep": int(engine.mesh.shape.get("ep", 1)),
        }
        if live_mesh != dom["mesh"]:
            raise InventoryMismatchError(
                f"inventory mesh key disagrees with the live engine: "
                f"manifest {dom['mesh']} vs engine mesh {live_mesh} — a "
                "domain compiled under one mesh cannot vouch for another; "
                "regenerate engine/program_inventory.py "
                "(scripts/gen_program_inventory.py --write)"
            )
        if list(engine.widths) != dom["widths"]:
            raise InventoryMismatchError(
                f"inventory domain math disagrees with the live engine: "
                f"manifest widths {dom['widths']} vs engine "
                f"{list(engine.widths)}"
                " — regenerate engine/program_inventory.py "
                "(scripts/gen_program_inventory.py --write)"
            )
        if list(getattr(engine, "megastep_ks", [1])) != dom["k_ladder"]:
            raise InventoryMismatchError(
                f"inventory megastep-ladder math disagrees with the live "
                f"engine: manifest {dom['k_ladder']} vs engine "
                f"{list(getattr(engine, 'megastep_ks', [1]))} — regenerate "
                "engine/program_inventory.py "
                "(scripts/gen_program_inventory.py --write)"
            )
        for entry in paged_entries:
            if not hasattr(engine, entry.attr):
                raise InventoryMismatchError(
                    f"inventory names {name}.{entry.attr} but the engine "
                    "has no such program — stale manifest; regenerate"
                )
            size = _DOMAIN_SIZES.get(entry.domain)
            if size is None:
                raise InventoryMismatchError(
                    f"unknown inventory domain {entry.domain!r} for "
                    f"{name}.{entry.attr}"
                )
            counts[entry.attr] = size(dom)
    for entry in score_entries:
        if not hasattr(engine, entry.attr):
            raise InventoryMismatchError(
                f"inventory names {name}.{entry.attr} but the engine has "
                "no such program — stale manifest; regenerate"
            )
        sdom = static_score_domain(
            tuple(engine.config.length_buckets),
            tuple(engine.config.batch_buckets),
            engine.cfg.max_position_embeddings,
            enabled=engine.config.scoring,
        )
        if list(getattr(engine, "score_shapes", [])) != sdom["pairs"]:
            raise InventoryMismatchError(
                f"inventory score-domain math disagrees with the live "
                f"engine: manifest {sdom['pairs']} vs engine "
                f"{list(getattr(engine, 'score_shapes', []))} — regenerate "
                "engine/program_inventory.py "
                "(scripts/gen_program_inventory.py --write)"
            )
        counts[entry.attr] = len(sdom["pairs"])
    return counts
