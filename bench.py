"""Headline benchmark: GPT-2 tutoring decode throughput, TPU vs reference.

Measures the BASELINE.json north-star metric — GPT-2 (124M) tutoring
tokens/sec/chip with batched concurrent student queries (batch=8,
`max_new_tokens=128`, the reference's sampling params) — on the real TPU
through the same engine the tutoring server uses. The baseline is the
reference's serving path: HF torch-CPU `GPT2LMHeadModel.generate`, one
sequential query at a time (reference: GUI_RAFT_LLM_SourceCode/
tutoring_server.py:21-29, ThreadPoolExecutor with sequential generate).

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N,
     "ttft_p50_ms": ..., "baseline_tokens_per_sec": ...}
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from functools import lru_cache, partial

import numpy as np

BATCH = 8
PROMPT_LEN = 48
MAX_NEW = 128
ROUNDS = 10

REPO = os.path.dirname(os.path.abspath(__file__))
# Half a gigabyte of seeded artifacts: built under the temp directory, never
# inside the checkout (a chip call copies the tree as it stands on disk).
LOCAL_CKPT_DIR = os.path.join(
    tempfile.gettempdir(), "dlrl_tpu_artifacts", "gpt2-local"
)


def ensure_local_artifacts() -> dict:
    """Checkpoint + vocab for the real-weights path (built locally: the
    image has no network and no HF cache — see scripts/make_local_checkpoint
    for why this is the strongest obtainable artifact)."""
    ckpt = os.path.join(LOCAL_CKPT_DIR, "model.safetensors")
    vocab = os.path.join(LOCAL_CKPT_DIR, "vocab.json")
    merges = os.path.join(LOCAL_CKPT_DIR, "merges.txt")
    if not all(os.path.exists(p) for p in (ckpt, vocab, merges)):
        subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "make_local_checkpoint.py"),
             "--out", LOCAL_CKPT_DIR, "--bert-out", ""],
            check=True, timeout=900, cwd=REPO,
        )
    return {"checkpoint": ckpt, "vocab_path": vocab, "merges_path": merges}

# Fallback when torch isn't importable at bench time: torch-CPU GPT-2-small
# single-stream generate measured on this image (tokens/sec).
TORCH_CPU_FALLBACK_TPS = 15.0


def bench_tpu(model: str = "gpt2", tp: int = 1, quant: bool = False,
              batch: int = BATCH, spec_tokens: int = 0,
              greedy: bool = False) -> dict:
    import jax

    from distributed_lms_raft_llm_tpu.engine import (
        EngineConfig,
        SamplingParams,
        TutoringEngine,
    )

    n_chips = max(1, len(jax.devices()))
    # The local checkpoint is gpt2-small; other sizes bench random-init
    # (BASELINE configs 2-3: gpt2-medium single chip, gpt2-large tp-sharded
    # — pass --tp when more than one chip is attached).
    artifacts = ensure_local_artifacts() if model == "gpt2" else {}
    sampling = (
        SamplingParams.greedy(max_new_tokens=MAX_NEW) if greedy
        else SamplingParams.reference_defaults(max_new_tokens=MAX_NEW)
    )
    engine = TutoringEngine(
        EngineConfig(
            model=model,
            sampling=sampling,
            length_buckets=(PROMPT_LEN, 64, 128),
            batch_buckets=tuple(sorted({1, 2, 4, 8, batch})),
            tp=tp,
            # The production serving config (tutoring_server --quant int8
            # --kv-quant): weight-only int8 + int8 KV cache, near-lossless
            # (bounds in tests/test_quant.py). quant=False measures the
            # full-precision bf16 path for continuity with earlier rounds.
            quant="int8" if quant else None,
            kv_quant=quant,
            spec_tokens=spec_tokens,
            **artifacts,
        )
    )
    rng = np.random.default_rng(0)
    ids = rng.integers(0, engine.tokenizer.vocab_size,
                       (batch, PROMPT_LEN)).astype(np.int32)
    mask = np.ones((batch, PROMPT_LEN), bool)

    compile_t0 = time.monotonic()
    engine.generate_ids(ids, mask)  # compile + warm
    compile_s = time.monotonic() - compile_t0

    # Throughput under sustained load: dispatch rounds back-to-back (as a
    # loaded server pipelines batches) and sync once at the end, so the
    # host↔device round-trip latency overlaps compute instead of
    # serializing every batch.
    t0 = time.monotonic()
    results = [
        engine.generate_ids(ids, mask, measure_ttft=False, device_result=True)
        for _ in range(ROUNDS)
    ]
    results = jax.device_get(results)
    elapsed = time.monotonic() - t0
    total_tokens = sum(int(np.sum(r.lengths)) for r in results)
    tps = total_tokens / elapsed

    # TTFT, measured: the engine blocks on the first sampled token between
    # its prefill and decode programs and records the wall-clock in
    # last_ttft_s (transfer + prefill + first sample + readback).
    one_ids, one_mask = ids[:1], mask[:1]
    engine.generate_ids(one_ids, one_mask)  # compile batch-1 program
    lat = []
    for _ in range(7):
        engine.generate_ids(one_ids, one_mask)
        lat.append(engine.last_ttft_s)
    ttft_ms = sorted(lat)[len(lat) // 2] * 1000.0

    return {
        "tokens_per_sec_per_chip": tps / n_chips,
        "ttft_p50_ms": ttft_ms,
        "compile_s": compile_s,
        "batch": batch,
        "platform": jax.devices()[0].platform,
    }


def bench_paged(model: str = "gpt2", tp: int = 1, ep: int = 1,
                quant: bool = False,
                batch: int = BATCH, spec_tokens: int = 0,
                greedy: bool = False, chunk: int = 16, megastep: int = 1,
                megastep_max: int = 0, inflight: int = 2,
                max_new: int = MAX_NEW, rounds: int = ROUNDS,
                prompt_len: int = PROMPT_LEN,
                length_buckets=None, prefix_cache_blocks: int = 0,
                prefill_chunk_tokens: int = 0,
                draft_source: str = "prompt_lookup") -> dict:
    """Continuous-batching throughput/TTFT through PagedEngine directly.

    Same shape of numbers as bench_tpu so paged and paged+spec enter the
    recorded perf trajectory: sustained tokens/sec/chip with `batch` busy
    slots (rounds x batch requests churning through), then idle-engine
    batch-1 TTFT medians. Spec acceptance rides along when spec_tokens>0;
    megastep knobs and the measured host-dispatches-per-token ratio ride
    along always (the device-resident megastep's target number). The
    workload knobs (max_new/rounds/prompt_len/length_buckets) default to
    the recorded configuration; the tier-1 CPU smoke test shrinks them so
    the record path cannot rot between chip attachments.
    """
    import jax

    from distributed_lms_raft_llm_tpu.engine import (
        EngineConfig,
        PagedEngine,
        SamplingParams,
    )
    from distributed_lms_raft_llm_tpu.engine.program_inventory import (
        effective_megastep_max,
    )

    n_chips = max(1, len(jax.devices()))
    artifacts = ensure_local_artifacts() if model == "gpt2" else {}
    sampling = (
        SamplingParams.greedy(max_new_tokens=max_new) if greedy
        else SamplingParams.reference_defaults(max_new_tokens=max_new)
    )
    engine = PagedEngine(
        EngineConfig(
            model=model,
            sampling=sampling,
            length_buckets=tuple(length_buckets or (prompt_len, 64, 128)),
            batch_buckets=tuple(sorted({1, 2, 4, 8, batch})),
            tp=tp,
            ep=ep,
            quant="int8" if quant else None,
            kv_quant=quant,
            spec_tokens=spec_tokens,
            draft_source=draft_source,
            **artifacts,
        ),
        slots=batch,
        chunk=chunk,
        inflight=inflight,
        megastep=megastep,
        megastep_max=megastep_max,
        prefix_cache=prefix_cache_blocks > 0,
        prefix_cache_blocks=max(1, prefix_cache_blocks),
        prefill_chunk_tokens=prefill_chunk_tokens,
    )
    rng = np.random.default_rng(0)
    prompts = [
        engine.tokenizer.decode(
            rng.integers(0, engine.tokenizer.vocab_size, prompt_len).tolist()
        )
        for _ in range(rounds * batch)
    ]
    compile_s = engine.warmup()

    engine.pop_spec_stats()
    engine.pop_dispatch_stats()
    engine.total_generated_tokens = 0
    t0 = time.monotonic()
    for p in prompts:
        engine.submit(p)
    engine.drain()
    elapsed = time.monotonic() - t0
    tps = engine.total_generated_tokens / elapsed
    spec_stats = engine.pop_spec_stats()
    (dispatches, emitted, dead_lanes, stall_ms,
     stalled_tokens) = engine.pop_dispatch_stats()
    engine.pop_ttfts()

    # Idle-engine TTFT (same protocol as bench_tpu: median of 7 batch-1
    # runs, measured submit -> first token on host).
    lat = []
    for _ in range(7):
        rid = engine.submit(prompts[0])
        engine.drain()
        lat.append(engine.pop_ttfts()[rid])
    ttft_ms = sorted(lat)[len(lat) // 2] * 1000.0

    out = {
        "tokens_per_sec_per_chip": tps / n_chips,
        "requests_per_s": len(prompts) / elapsed,
        "ttft_p50_ms": ttft_ms,
        "compile_s": compile_s,
        # Mesh block (BENCH schema): axis sizes the engine actually built,
        # the per-chip vs total KV residency the tp sharding buys, and
        # both tok/s views — total for capacity planning, per-chip for
        # efficiency comparisons across mesh sizes.
        "mesh": {
            "tp": engine.tp,
            "ep": engine.ep,
            "dp": int(engine.mesh.shape.get("dp", 1)),
            "devices": n_chips,
            "kv_bytes_total": engine.kv_bytes_total,
            "kv_bytes_per_chip": engine.kv_bytes_per_chip,
            "tokens_per_sec_total": tps,
            "tokens_per_sec_per_chip": tps / n_chips,
        },
        "batch": batch,
        "chunk": chunk,
        "megastep": megastep,
        "megastep_max": effective_megastep_max(megastep, megastep_max),
        "inflight": inflight,
        "host_dispatches_per_token": (
            dispatches / emitted if emitted else None
        ),
        "megastep_dead_lane_tokens": dead_lanes,
        # Stall-free admission before/after: decode-train pause charged
        # to sequential admission (0 by construction when
        # prefill_chunk_tokens > 0 stages admissions into the scan).
        "prefill_chunk_tokens": prefill_chunk_tokens,
        "prefill_stall_ms": round(stall_ms, 2),
        "decode_stalled_tokens": stalled_tokens,
        "platform": jax.devices()[0].platform,
    }
    if spec_stats is not None:
        windows, spec_emitted = spec_stats
        out["spec_tokens_per_window"] = (
            spec_emitted / windows if windows else None
        )
    prefix_stats = engine.pop_prefix_stats()
    if prefix_stats is not None:
        hit, total, _evicted, _blocks = prefix_stats
        out["prefix_cache_blocks"] = prefix_cache_blocks
        out["prefix_cache_hit_rate"] = hit / total if total else None
    return out


def bench_shared_prefix(model: str = "gpt2", tp: int = 1,
                        quant: bool = False, n_requests: int = 16,
                        prefix_len: int = 96, suffix_len: int = 16,
                        max_new: int = 32, chunk: int = 16,
                        slots: int = BATCH, greedy: bool = True,
                        prefix_cache_blocks: int = 512,
                        prefix_block_tokens: int = 16,
                        length_buckets=None) -> dict:
    """The shared-prefix scenario: N requests against one common M-token
    course context, cold vs warm.

    Phase A (cold) submits `n_requests` prompts with pairwise-DISTINCT
    prefixes — every admission is a full prefill. Phase B (warm) submits
    `n_requests` prompts sharing ONE common prefix: the first seeds the
    radix tree, the rest splice its blocks and partial-prefill only
    their `suffix_len`-token tails. The record carries mean prefill
    dispatch ms and tokens/s for each phase plus the measured hit rate —
    the ISSUE acceptance number is warm prefill device time per request
    dropping >= 2x at steady-state hit rate on a same-course workload.
    """
    import jax

    from distributed_lms_raft_llm_tpu.engine import (
        EngineConfig,
        PagedEngine,
        SamplingParams,
    )

    n_chips = max(1, len(jax.devices()))
    artifacts = ensure_local_artifacts() if model == "gpt2" else {}
    total_len = prefix_len + suffix_len
    sampling = (
        SamplingParams.greedy(max_new_tokens=max_new) if greedy
        else SamplingParams.reference_defaults(max_new_tokens=max_new)
    )
    engine = PagedEngine(
        EngineConfig(
            model=model,
            sampling=sampling,
            length_buckets=tuple(
                length_buckets or sorted({suffix_len * 2, total_len})
            ),
            batch_buckets=(1, 2, 4, 8),
            tp=tp,
            quant="int8" if quant else None,
            kv_quant=quant,
            **artifacts,
        ),
        slots=slots,
        chunk=chunk,
        prefix_cache=True,
        prefix_cache_blocks=prefix_cache_blocks,
        prefix_block_tokens=prefix_block_tokens,
    )
    filler = ("the raft consensus algorithm elects a leader, replicates "
              "a log, and commits entries across the course cluster. ")

    @lru_cache(maxsize=None)
    def context_text(seed: int) -> str:
        # A natural-text course context measuring ~prefix_len tokens
        # (identical text => identical token prefix across requests —
        # what the radix tree keys on). Cached per seed: the warm phase
        # reuses one context and the host tokenizer work must not leak
        # into a benchmark of engine prefill time.
        text = f"course {seed} assignment context: " + filler
        while len(engine.tokenizer.encode(text)) < prefix_len:
            text += filler
        return engine.tokenizer.decode(
            engine.tokenizer.encode(text)[:prefix_len]
        )

    def make_prompt(prefix_seed: int, i: int) -> str:
        return context_text(prefix_seed) + f" student question {i}: why?"

    compile_s = engine.warmup()

    def run_phase(prompts):
        engine.pop_prefix_stats()
        engine.pop_program_times()
        engine.total_generated_tokens = 0
        t0 = time.monotonic()
        for p in prompts:
            engine.submit(p)
        engine.drain()
        elapsed = time.monotonic() - t0
        prefill_ms = {}
        for name, _start, wall_s in engine.pop_program_times():
            if name in ("prefill", "partial_prefill", "load_block"):
                prefill_ms.setdefault(name, []).append(wall_s * 1000.0)
        hit, total, _ev, _blocks = engine.pop_prefix_stats()
        return dict(
            tokens_per_sec_per_chip=(
                engine.total_generated_tokens / elapsed / n_chips
            ),
            prefill_dispatches={
                k: len(v) for k, v in prefill_ms.items()
            },
            prefill_ms_mean={
                k: sum(v) / len(v) for k, v in prefill_ms.items()
            },
            hit_rate=hit / total if total else 0.0,
        )

    cold = run_phase([make_prompt(1000 + i, i) for i in range(n_requests)])
    engine.prefix_cache.clear()
    warm = run_phase([make_prompt(7, i) for i in range(n_requests)])

    cold_ms = cold["prefill_ms_mean"].get("prefill")
    warm_ms = warm["prefill_ms_mean"].get("partial_prefill")
    return {
        "metric": "paged_shared_prefix_prefill_speedup",
        "value": round(cold_ms / warm_ms, 2) if cold_ms and warm_ms
        else None,
        "unit": "x cold/warm prefill dispatch ms",
        "n_requests": n_requests,
        "prefix_tokens": prefix_len,
        "suffix_tokens": suffix_len,
        "prefix_cache_blocks": prefix_cache_blocks,
        "prefill_ms_cold": round(cold_ms, 3) if cold_ms else None,
        "prefill_ms_warm": round(warm_ms, 3) if warm_ms else None,
        "tokens_per_sec_per_chip_cold": round(
            cold["tokens_per_sec_per_chip"], 2
        ),
        "tokens_per_sec_per_chip_warm": round(
            warm["tokens_per_sec_per_chip"], 2
        ),
        "prefix_cache_hit_rate": round(warm["hit_rate"], 3),
        "cold_hit_rate": round(cold["hit_rate"], 3),
        "compile_s": round(compile_s, 1),
        "platform": jax.devices()[0].platform,
    }


def bench_sweep(model: str = "gpt2", tp: int = 1, quant: bool = False,
                slots_grid=(16, 32, 64), inflight_grid=(2, 3, 4),
                megastep_grid=(1, 4, 8), spec_tokens: int = 0,
                greedy: bool = False, chunk: int = 16,
                max_new: int = MAX_NEW, rounds: int = 2,
                prompt_len: int = PROMPT_LEN, length_buckets=None,
                prefix_cache_blocks: int = 0,
                prefill_chunk_tokens: int = 0,
                draft_source: str = "prompt_lookup") -> list:
    """Round-6 grid: slots x inflight-depth x megastep rungs, one
    BENCH-schema record per point.

    Each point is an independent `bench_paged` run (fresh engine, same
    seeded workload scaled to the slot count), so a sweep answers the
    ROADMAP's open questions — slot counts beyond 16, inflight-depth,
    and megastep ladders — in one command whose output is one `jq`-able
    JSON record per point. `rounds` defaults low (2) because a sweep
    multiplies runs; raise it for tighter chip numbers. CPU-smoked in
    tests/test_bench_record.py so the grid path cannot rot between chip
    attachments."""
    records = []
    for slots in slots_grid:
        for inflight in inflight_grid:
            for mega in megastep_grid:
                out = bench_paged(
                    model=model, tp=tp, quant=quant, batch=slots,
                    spec_tokens=spec_tokens, greedy=greedy, chunk=chunk,
                    megastep=mega, megastep_max=mega, inflight=inflight,
                    max_new=max_new, rounds=rounds,
                    prompt_len=prompt_len, length_buckets=length_buckets,
                    prefix_cache_blocks=prefix_cache_blocks,
                    prefill_chunk_tokens=prefill_chunk_tokens,
                    draft_source=draft_source,
                )
                records.append({
                    "metric": (
                        f"paged_sweep_slots{slots}_inflight{inflight}"
                        f"_mega{mega}"
                    ),
                    "value": round(out["tokens_per_sec_per_chip"], 2),
                    "unit": "tokens/sec/chip",
                    "slots": slots,
                    **{k: out[k] for k in (
                        "requests_per_s", "ttft_p50_ms", "chunk",
                        "megastep", "megastep_max", "inflight",
                        "host_dispatches_per_token",
                        "megastep_dead_lane_tokens",
                        "prefill_chunk_tokens", "prefill_stall_ms",
                        "decode_stalled_tokens", "platform",
                    )},
                })
    return records


def bench_score_scenario(model: str = "gpt2", tp: int = 1,
                         quant: bool = False, slots: int = BATCH,
                         chunk: int = 16, megastep: int = 1,
                         megastep_max: int = 0, inflight: int = 2,
                         interactive: int = 24, arrival_s: float = 0.03,
                         score_texts_n: int = 128,
                         score_text_tokens: int = 48,
                         max_new: int = MAX_NEW,
                         prompt_len: int = PROMPT_LEN,
                         length_buckets=None, greedy: bool = False) -> dict:
    """The two-tenant scenario: interactive load with the background
    scoring tenant OFF then ON, through the real PagedQueue co-scheduler.

    Phase OFF drives `interactive` requests at `arrival_s` spacing and
    records interactive tokens/s + TTFT p90. Phase ON replays the same
    arrivals with a `score_texts_n`-text bulk job submitted up front:
    quanta harvest the idle lanes (arrival gaps + the post-workload
    drain). The acceptance claims the record must witness: total
    tokens/s/chip RISES with the tenant on (the harvest), interactive
    p90 TTFT HOLDS (quanta admit only while nothing interactive is
    pending — `quanta_with_pending` stays 0 and every preemption wait is
    bounded by one quantum), and the warmed score domain means ZERO live
    compiles (EngineConfig.scoring warms it; the engine is reused across
    both phases so phase ON compiles nothing).
    """
    import asyncio

    import jax

    from distributed_lms_raft_llm_tpu.engine import (
        EngineConfig,
        PagedEngine,
        PagedQueue,
        SamplingParams,
        ScoringManager,
    )
    from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

    n_chips = max(1, len(jax.devices()))
    artifacts = ensure_local_artifacts() if model == "gpt2" else {}
    sampling = (
        SamplingParams.greedy(max_new_tokens=max_new) if greedy
        else SamplingParams.reference_defaults(max_new_tokens=max_new)
    )
    engine = PagedEngine(
        EngineConfig(
            model=model,
            sampling=sampling,
            length_buckets=tuple(length_buckets or (prompt_len, 64, 128)),
            batch_buckets=tuple(sorted({1, 2, 4, 8, min(8, slots)})),
            tp=tp,
            quant="int8" if quant else None,
            kv_quant=quant,
            scoring=True,
            **artifacts,
        ),
        slots=slots, chunk=chunk, inflight=inflight,
        megastep=megastep, megastep_max=megastep_max,
    )
    compile_s = engine.warmup()
    rng = np.random.default_rng(0)
    prompts = [
        engine.tokenizer.decode(
            rng.integers(0, engine.tokenizer.vocab_size, prompt_len).tolist()
        )
        for _ in range(interactive)
    ]
    corpus = [
        engine.tokenizer.decode(
            rng.integers(0, engine.tokenizer.vocab_size,
                         score_text_tokens).tolist()
        )
        for _ in range(score_texts_n)
    ]

    async def phase(with_scoring: bool) -> dict:
        metrics = Metrics()
        scorer = (ScoringManager(engine, metrics=metrics,
                                 max_job_texts=len(corpus))
                  if with_scoring else None)
        queue = PagedQueue(engine, metrics=metrics, scorer=scorer)
        await queue.start()
        engine.total_generated_tokens = 0
        t0 = time.monotonic()
        if scorer is not None:
            scorer.submit(corpus, purpose="calibration")
        tasks = []
        for p in prompts:
            tasks.append(asyncio.ensure_future(queue.submit(p)))
            await asyncio.sleep(arrival_s)
        await asyncio.gather(*tasks)
        interactive_s = time.monotonic() - t0
        interactive_tokens = engine.total_generated_tokens
        if scorer is not None:
            # Drain the bulk backlog: pure idle-lane time from here on.
            while not scorer.done():
                await asyncio.sleep(0.01)
        elapsed = time.monotonic() - t0
        p90 = metrics.hist("ttft").percentile(90) or 0.0
        snap = metrics.snapshot()
        stats = scorer.stats() if scorer is not None else {}
        out = dict(
            interactive_s=interactive_s,
            elapsed_s=elapsed,
            interactive_tokens=interactive_tokens,
            scored_tokens=stats.get("scored_tokens", 0),
            ttft_p90_ms=p90 * 1000.0,
            quanta=stats.get("quanta", 0),
            jobs_completed=stats.get("jobs_completed", 0),
            quanta_with_pending=stats.get("quanta_with_pending", 0),
            max_quantum_wall_ms=stats.get("max_quantum_wall_ms", 0.0),
            preempt_wait_ms=snap.get("counters", {}).get(
                "score_preempt_wait_ms", 0
            ),
            max_preempt_wait_ms=queue.max_preempt_wait_s * 1000.0,
        )
        await queue.close()
        return out

    off = asyncio.run(phase(False))
    on = asyncio.run(phase(True))
    total_off = off["interactive_tokens"] / off["elapsed_s"] / n_chips
    total_on = (
        (on["interactive_tokens"] + on["scored_tokens"])
        / on["elapsed_s"] / n_chips
    )
    return {
        "metric": "paged_score_tenant_total_tokens_per_sec_per_chip",
        "value": round(total_on, 2),
        "unit": "tokens/sec/chip",
        "interactive_requests": interactive,
        "arrival_s": arrival_s,
        "score_texts": score_texts_n,
        "interactive_tokens_per_sec_per_chip_off": round(
            off["interactive_tokens"] / off["elapsed_s"] / n_chips, 2
        ),
        "interactive_tokens_per_sec_per_chip_on": round(
            on["interactive_tokens"] / on["interactive_s"] / n_chips, 2
        ),
        "total_tokens_per_sec_per_chip_off": round(total_off, 2),
        "total_tokens_per_sec_per_chip_on": round(total_on, 2),
        "ttft_p90_ms_off": round(off["ttft_p90_ms"], 2),
        "ttft_p90_ms_on": round(on["ttft_p90_ms"], 2),
        "ttft_p90_delta_ms": round(
            on["ttft_p90_ms"] - off["ttft_p90_ms"], 2
        ),
        "scoring_quanta": on["quanta"],
        "scoring_jobs_completed": on["jobs_completed"],
        "scored_tokens": on["scored_tokens"],
        # The admission-policy witnesses: quanta admitted while anything
        # interactive waited (must be 0), and the worst single wait an
        # interactive arrival paid for an in-flight quantum (bounded by
        # one quantum wall).
        "quanta_with_pending": on["quanta_with_pending"],
        "max_quantum_wall_ms": on["max_quantum_wall_ms"],
        "score_preempt_wait_ms": on["preempt_wait_ms"],
        "max_preempt_wait_ms": round(on["max_preempt_wait_ms"], 2),
        "slots": slots,
        "chunk": chunk,
        "compile_s": round(compile_s, 1),
        "platform": jax.devices()[0].platform,
    }


def bench_torch_baseline(model: str = "gpt2", budget_new_tokens: int = 32) -> float:
    """Reference path: torch-CPU GPT-2 (matching size), sequential queries."""
    arch = {
        "gpt2": dict(),
        # The reference has no MoE; its comparable is the same dense trunk
        # (gpt2-moe activates ~gpt2-small FLOPs per token).
        "gpt2-moe": dict(),
        "gpt2-medium": dict(n_embd=1024, n_layer=24, n_head=16),
        "gpt2-large": dict(n_embd=1280, n_layer=36, n_head=20),
    }[model]
    try:
        import torch
        import transformers

        cfg = transformers.GPT2Config(**arch)
        torch.manual_seed(0)
        model = transformers.GPT2LMHeadModel(cfg)
        model.eval()
        ids = torch.randint(0, 50000, (1, PROMPT_LEN))
        with torch.no_grad():
            model.generate(  # warm
                ids, max_new_tokens=4, do_sample=True, top_k=50, top_p=0.9,
                temperature=0.7, repetition_penalty=1.2,
                pad_token_id=cfg.eos_token_id,
            )
            t0 = time.monotonic()
            out = model.generate(
                ids, max_new_tokens=budget_new_tokens, do_sample=True,
                top_k=50, top_p=0.9, temperature=0.7, repetition_penalty=1.2,
                pad_token_id=cfg.eos_token_id,
            )
            elapsed = time.monotonic() - t0
        produced = out.shape[1] - PROMPT_LEN
        return produced / elapsed
    except Exception as e:  # torch missing/broken: use the recorded number
        print(f"# torch baseline unavailable ({e}); using fallback",
              file=sys.stderr)
        return TORCH_CPU_FALLBACK_TPS


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2",
                    choices=["gpt2", "gpt2-medium", "gpt2-large",
                             "gpt2-moe"],
                    help="BASELINE config to bench (default: the headline; "
                         "gpt2-moe = 8-expert top-2 small trunk, random "
                         "init)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel ways (config 4: gpt2-large tp); "
                         "with --paged the slot KV cache and prefix-cache "
                         "blocks shard their heads axis over tp too, and "
                         "the record's mesh block carries per-chip KV "
                         "bytes")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel ways (MoE models only; shards "
                         "the expert stacks — paged: requires gpt2-moe)")
    ap.add_argument("--batch", type=int, default=BATCH,
                    help="device batch (BASELINE config is 8)")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="speculative decoding draft window (engine/draft.py "
                         "kernels; exact). Measured win is on the greedy "
                         "low-batch path — pair with --greedy --batch 1, or "
                         "with --paged for the unified serving config")
    ap.add_argument("--greedy", action="store_true",
                    help="temperature-0 sampling instead of the reference "
                         "params (the speculative serving configuration)")
    ap.add_argument("--paged", action="store_true",
                    help="bench the continuous-batching PagedEngine instead "
                         "of the group-batched engine (composes with "
                         "--spec-tokens: per-slot verify windows)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="paged: tokens (spec: verify windows) per device "
                         "chunk (one step program; a megastep fuses K)")
    ap.add_argument("--megastep", type=int, default=1,
                    help="paged: starting K of the megastep controller — "
                         "chunks fused per host dispatch (1 = chunk loop)")
    ap.add_argument("--megastep-max", type=int, default=0,
                    help="paged: megastep controller ceiling (0 = follow "
                         "--megastep)")
    ap.add_argument("--inflight", type=int, default=2,
                    help="paged: dispatch pipelining depth")
    ap.add_argument("--prefix-cache-blocks", type=int, default=0,
                    help="paged: enable the radix shared-prefix KV cache "
                         "with this block budget (0 = off); the record "
                         "carries the measured hit rate")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=0,
                    help="paged: fused stall-free admission — stage "
                         "prompts and prefill this many tokens per "
                         "megastep scan iteration inside the decode "
                         "program (0 = sequential admission; the record "
                         "carries prefill_stall_ms/decode_stalled_tokens)")
    ap.add_argument("--sweep", action="store_true",
                    help="paged: run the round-6 grid (slots x inflight "
                         "x megastep rungs) and print one BENCH-schema "
                         "JSON line per point instead of the single "
                         "headline record")
    ap.add_argument("--sweep-slots", default="16,32,64",
                    help="comma-separated slot counts for --sweep")
    ap.add_argument("--sweep-inflight", default="2,3,4",
                    help="comma-separated inflight depths for --sweep")
    ap.add_argument("--sweep-megasteps", default="1,4,8",
                    help="comma-separated megastep rungs for --sweep")
    ap.add_argument("--sweep-rounds", type=int, default=2,
                    help="request rounds per sweep grid point (2 keeps a "
                         "full grid cheap; raise for tighter chip numbers)")
    ap.add_argument("--draft-source", default="prompt_lookup",
                    choices=["prompt_lookup", "ngram"],
                    help="paged+spec draft source: prompt_lookup = "
                         "most-recent n-gram continuation; ngram = per-slot "
                         "modal-continuation table (higher acceptance at "
                         "temperature>0)")
    ap.add_argument("--score-scenario", action="store_true",
                    help="paged: run the two-tenant scenario (interactive "
                         "load with the background scoring tenant off "
                         "then on) and print its BENCH record — total "
                         "tok/s/chip must rise, interactive p90 TTFT "
                         "must hold, quanta_with_pending must be 0")
    ap.add_argument("--score-texts", type=int, default=128,
                    help="bulk-job corpus size for --score-scenario")
    ap.add_argument("--score-interactive", type=int, default=24,
                    help="interactive requests per phase for "
                         "--score-scenario")
    ap.add_argument("--prefix-scenario", action="store_true",
                    help="paged: also run the shared-prefix scenario (N "
                         "requests against one common course context, "
                         "prefill ms + tokens/s cold vs warm) and embed "
                         "its record under \"shared_prefix\"")
    ap.add_argument("--config", default=None,
                    help="TOML deployment file; [tutoring] model/tp apply")
    args = ap.parse_args()
    if args.config:
        from distributed_lms_raft_llm_tpu.config import load_config

        t = load_config(args.config).tutoring
        if args.model == "gpt2" and t.model in ("gpt2", "gpt2-medium",
                                                "gpt2-large", "gpt2-moe"):
            args.model = t.model
        if args.tp == 1:
            args.tp = t.tp
        if args.ep == 1:
            args.ep = t.ep
    extra = dict(spec_tokens=args.spec_tokens, greedy=args.greedy)
    if args.score_scenario:
        record = bench_score_scenario(
            args.model, args.tp, quant=args.tp == 1, slots=args.batch,
            chunk=args.chunk, megastep=args.megastep,
            megastep_max=args.megastep_max, inflight=args.inflight,
            interactive=args.score_interactive,
            score_texts_n=args.score_texts, greedy=args.greedy,
        )
        print(json.dumps(record))
        return
    if args.sweep:
        grid = bench_sweep(
            args.model, args.tp, quant=args.tp == 1,
            slots_grid=tuple(int(s) for s in args.sweep_slots.split(",")),
            inflight_grid=tuple(
                int(s) for s in args.sweep_inflight.split(",")
            ),
            megastep_grid=tuple(
                int(s) for s in args.sweep_megasteps.split(",")
            ),
            chunk=args.chunk,
            rounds=args.sweep_rounds,
            prefix_cache_blocks=args.prefix_cache_blocks,
            prefill_chunk_tokens=args.prefill_chunk_tokens,
            draft_source=args.draft_source,
            **extra,
        )
        for record in grid:
            print(json.dumps(record))
        return
    run = bench_tpu
    if args.paged:
        run = partial(bench_paged, ep=args.ep, chunk=args.chunk,
                      megastep=args.megastep,
                      megastep_max=args.megastep_max,
                      inflight=args.inflight,
                      prefix_cache_blocks=args.prefix_cache_blocks,
                      prefill_chunk_tokens=args.prefill_chunk_tokens,
                      draft_source=args.draft_source)
    quant = (run(args.model, args.tp, quant=True, batch=args.batch, **extra)
             if args.tp == 1 else None)
    tpu = run(args.model, args.tp, batch=args.batch, **extra)
    baseline_tps = bench_torch_baseline(args.model)
    name = {"gpt2": "gpt2_small"}.get(args.model, args.model.replace("-", "_"))
    if args.tp > 1:
        name += f"_tp{args.tp}"
    if args.ep > 1:
        name += f"_ep{args.ep}"
    if args.paged:
        name += "_paged"
    if args.paged and args.megastep > 1:
        name += f"_mega{args.megastep}"
    if args.paged and args.prefill_chunk_tokens:
        name += f"_fusedadm{args.prefill_chunk_tokens}"
    if args.greedy:
        name += "_greedy"
    if args.spec_tokens:
        name += f"_spec{args.spec_tokens}"
    head = quant or tpu  # headline = the production serving config
    value = round(head["tokens_per_sec_per_chip"], 2)
    record = {
        "metric": f"{name}_tutoring_decode_tokens_per_sec_per_chip"
                  f"_batch{head['batch']}"
                  + ("_int8w_int8kv" if quant else ""),
        "value": value,
        "unit": "tokens/sec/chip",
        "vs_baseline": round(value / max(baseline_tps, 1e-9), 2),
        "ttft_p50_ms": round(head["ttft_p50_ms"], 2),
        "baseline_tokens_per_sec": round(baseline_tps, 2),
        "compile_s": round(head["compile_s"], 1),
        "platform": head["platform"],
    }
    if "requests_per_s" in head:
        record["requests_per_s"] = round(head["requests_per_s"], 2)
    if "mesh" in head:
        # Per-chip accounting for multi-chip paged serving: axis sizes,
        # the KV residency the tp sharding splits, both tok/s views.
        mesh = dict(head["mesh"])
        mesh["tokens_per_sec_total"] = round(mesh["tokens_per_sec_total"], 2)
        mesh["tokens_per_sec_per_chip"] = round(
            mesh["tokens_per_sec_per_chip"], 2
        )
        record["mesh"] = mesh
    if "megastep" in head:
        # Paged runs carry the megastep configuration and its target
        # ratio so the recorded trajectory shows host round trips per
        # token shrinking as K rises.
        record["chunk"] = head["chunk"]
        record["megastep"] = head["megastep"]
        record["megastep_max"] = head["megastep_max"]
        record["inflight"] = head["inflight"]
        if head.get("host_dispatches_per_token") is not None:
            record["host_dispatches_per_token"] = round(
                head["host_dispatches_per_token"], 4
            )
        record["megastep_dead_lane_tokens"] = (
            head["megastep_dead_lane_tokens"]
        )
        record["prefill_chunk_tokens"] = head["prefill_chunk_tokens"]
        record["prefill_stall_ms"] = head["prefill_stall_ms"]
        record["decode_stalled_tokens"] = head["decode_stalled_tokens"]
    if head.get("spec_tokens_per_window") is not None:
        record["spec_tokens_per_window"] = round(
            head["spec_tokens_per_window"], 2
        )
    if head.get("prefix_cache_hit_rate") is not None:
        record["prefix_cache_blocks"] = head["prefix_cache_blocks"]
        record["prefix_cache_hit_rate"] = round(
            head["prefix_cache_hit_rate"], 3
        )
    if args.paged and args.prefix_scenario:
        record["shared_prefix"] = bench_shared_prefix(
            args.model, args.tp, quant=args.tp == 1, chunk=args.chunk,
            prefix_cache_blocks=args.prefix_cache_blocks or 512,
        )
    if quant:
        # Full-precision numbers ride along for cross-round continuity.
        record["bf16_tokens_per_sec"] = round(
            tpu["tokens_per_sec_per_chip"], 2
        )
        record["bf16_ttft_p50_ms"] = round(tpu["ttft_p50_ms"], 2)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
