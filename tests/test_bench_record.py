"""CPU smoke of the BENCH record paths (the still-unmeasured
`--paged --spec-tokens` configurations).

Real-chip numbers come only from a chip run (PERF.md); these
seeded tiny-model runs pin the RECORD path meanwhile — both harnesses
must keep emitting BENCH-schema dicts that carry the paged+spec fields
AND the new megastep knobs (megastep/megastep_max/chunk/inflight plus the
measured host-dispatches-per-token ratio), so the recording command
cannot rot between measurement rounds.
"""

import argparse
import asyncio
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "scripts"))


def test_bench_paged_spec_record_smoke():
    """bench.py's engine-direct paged+spec measurement: one seeded tiny
    run, record carries throughput + acceptance + megastep knobs."""
    from bench import bench_paged

    out = bench_paged(
        model="tiny", batch=2, spec_tokens=2, greedy=True, chunk=2,
        megastep=2, megastep_max=4, max_new=8, rounds=1, prompt_len=8,
        length_buckets=(8, 16),
    )
    assert out["tokens_per_sec_per_chip"] > 0
    assert out["requests_per_s"] > 0
    assert out["ttft_p50_ms"] > 0
    assert out["chunk"] == 2
    assert out["megastep"] == 2
    assert out["megastep_max"] == 4
    assert out["inflight"] == 2
    assert 0.0 < out["host_dispatches_per_token"] < 2.0
    assert out["megastep_dead_lane_tokens"] >= 0
    # Spec acceptance rides along: mean emitted tokens per verify window
    # is in [1, k+1] whenever any window ran.
    assert out["spec_tokens_per_window"] is None or (
        1.0 <= out["spec_tokens_per_window"] <= 3.0
    )


def test_bench_shared_prefix_record_smoke():
    """bench.py's shared-prefix scenario: N requests against one common
    course context; the record must carry prefill ms and tokens/s cold
    vs warm plus the measured hit rate (>= 50% shared-prefix tokens at
    steady state — the ISSUE acceptance workload)."""
    from bench import bench_shared_prefix

    out = bench_shared_prefix(
        model="tiny", n_requests=6, prefix_len=24, suffix_len=8,
        max_new=8, chunk=2, slots=2, prefix_cache_blocks=64,
        prefix_block_tokens=4, length_buckets=(16, 32, 64),
    )
    assert out["metric"] == "paged_shared_prefix_prefill_speedup"
    assert out["prefill_ms_cold"] > 0
    assert out["prefill_ms_warm"] > 0
    # The headline value is the cold/warm ratio (both fields are rounded
    # independently, so compare with tolerance, not equality).
    assert out["value"] == pytest.approx(
        out["prefill_ms_cold"] / out["prefill_ms_warm"], abs=0.02
    )
    assert out["tokens_per_sec_per_chip_cold"] > 0
    assert out["tokens_per_sec_per_chip_warm"] > 0
    # The warm phase really shares >= 50% of its prompt tokens; the cold
    # phase (distinct contexts) must not.
    assert out["prefix_cache_hit_rate"] >= 0.5
    assert out["cold_hit_rate"] < 0.1


def test_bench_paged_fused_admission_record_smoke():
    """bench.py --prefill-chunk-tokens: the record carries the fused
    knob and the stall-free before/after fields (zero by construction
    with fusion on)."""
    from bench import bench_paged

    out = bench_paged(
        model="tiny", batch=2, greedy=True, chunk=2, megastep=2,
        megastep_max=2, max_new=8, rounds=1, prompt_len=8,
        length_buckets=(8, 16), prefill_chunk_tokens=4,
    )
    assert out["tokens_per_sec_per_chip"] > 0
    assert out["prefill_chunk_tokens"] == 4
    assert out["prefill_stall_ms"] == 0
    assert out["decode_stalled_tokens"] == 0


def test_bench_sweep_grid_smoke():
    """bench.py --sweep: one BENCH-schema JSON record per
    (slots, inflight, megastep) grid point, each carrying the megastep
    knobs and the admission-stall fields — the grid runner a
    chip-attached session executes verbatim."""
    from bench import bench_sweep

    grid = bench_sweep(
        model="tiny", slots_grid=(2,), inflight_grid=(1, 2),
        megastep_grid=(2,), greedy=True, chunk=2, max_new=8,
        rounds=1, prompt_len=8, length_buckets=(8, 16),
        prefill_chunk_tokens=4,
    )
    assert len(grid) == 2
    metrics = {r["metric"] for r in grid}
    assert "paged_sweep_slots2_inflight1_mega2" in metrics
    assert "paged_sweep_slots2_inflight2_mega2" in metrics
    for r in grid:
        assert r["unit"] == "tokens/sec/chip"
        assert r["value"] > 0
        assert r["slots"] == 2
        assert r["inflight"] in (1, 2)
        assert r["megastep"] == 2
        assert r["prefill_chunk_tokens"] == 4
        assert r["decode_stalled_tokens"] == 0
        assert r["host_dispatches_per_token"] > 0


def test_bench_score_scenario_record_smoke():
    """bench.py --score-scenario: the two-tenant record (interactive load
    with the background scoring tenant off/on) must witness the
    acceptance claims — quanta executed ONLY while the interactive
    pending queue was empty (quanta_with_pending == 0), the bulk job
    completed in the idle lanes, every preemption wait stayed under one
    quantum, and the interactive p90 TTFT delta is bounded."""
    from bench import bench_score_scenario

    out = bench_score_scenario(
        model="tiny", slots=2, chunk=2, interactive=6, arrival_s=0.02,
        score_texts_n=10, score_text_tokens=12, max_new=8, prompt_len=8,
        length_buckets=(8, 16), greedy=True,
    )
    assert out["metric"] == "paged_score_tenant_total_tokens_per_sec_per_chip"
    assert out["unit"] == "tokens/sec/chip"
    assert out["total_tokens_per_sec_per_chip_off"] > 0
    assert out["total_tokens_per_sec_per_chip_on"] > 0
    # The harvest: the ON phase really scored the bulk corpus...
    assert out["scored_tokens"] > 0
    assert out["scoring_jobs_completed"] == 1
    assert out["scoring_quanta"] >= 2  # ceil(10 texts / batch cap 8)
    # ...and ONLY in idle lanes: zero quanta admitted while interactive
    # work waited, and any arrival that landed mid-quantum waited at
    # most one quantum for its dispatch.
    assert out["quanta_with_pending"] == 0
    assert out["max_preempt_wait_ms"] <= out["max_quantum_wall_ms"] + 50
    # Interactive p90 TTFT holds (pinned loosely for CPU CI noise: the
    # real bound is the chip record's; a co-scheduler that blocked
    # interactive work behind the whole job would blow far past this).
    assert out["ttft_p90_ms_on"] <= out["ttft_p90_ms_off"] + 2000.0


def test_bench_paged_carries_prefix_knob_and_hit_rate():
    from bench import bench_paged

    out = bench_paged(
        model="tiny", batch=2, greedy=True, chunk=2, max_new=8,
        rounds=1, prompt_len=8, length_buckets=(8, 16),
        prefix_cache_blocks=16,
    )
    assert out["prefix_cache_blocks"] == 16
    assert out["prefix_cache_hit_rate"] is not None


def test_bench_server_paged_spec_record_smoke():
    """bench_server.py through the real gRPC stack: the one-line record
    must carry the paged+spec configuration, the megastep knobs, and the
    queue-maintained host-dispatches-per-token gauge."""
    import bench_server

    args = argparse.Namespace(
        model="tiny", clients=2, queries=1, max_new_tokens=8,
        paged=True, slots=2, chunk=2, megastep=2, megastep_max=2,
        inflight=2, quant=None, kv_quant=False, greedy=True,
        spec_tokens=2,
    )
    out = asyncio.run(bench_server.run(args))
    assert out["engine"] == "paged"
    assert out["spec_tokens"] == 2
    assert out["megastep"] == 2
    assert out["megastep_max"] == 2
    assert out["chunk"] == 2
    assert out["tokens_per_sec_per_chip"] > 0
    assert out["ttft_count"] == 2
    dpt = out["host_dispatches_per_token"]
    assert dpt is not None and 0.0 < dpt < 3.0
    # Prefix-cache fields ride along (disabled here: knob recorded False,
    # gauge absent => None, never fabricated).
    assert out["prefix_cache"] is False
    assert out["prefix_cache_hit_rate"] is None
