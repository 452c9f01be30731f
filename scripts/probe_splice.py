#!/usr/bin/env python
"""What an admission's prefix splice costs the thread that launches it, by
the length of a stored run (PERF.md section 6, PR 55, keeps the readings).

Builds a benchmark configuration's own engine (its registry model with
weights of its own drawing: no program of the model runs here), publishes
one long edge of `--blocks` blocks out of a slot of the widest cache as the
engine publishes it (`_insert_blocks`; a recurrent family's snapshot at its
end), and then admits `--admissions` prompts that hit all of it through
`_stage_admissions`, the served path: lookup, pins, the splice's launches,
`_stage`, the snapshot's restore. For every `--runs` value R (blocks in a
stored run; 0: none, every block rests on its own and goes in runs of 16
concatenated on the device, which is the tree before PR 55) one reading:
the CPU milliseconds an admission held the thread (`time.thread_time`), the
wall milliseconds until the device had written them all, the launches and
the tokens stored runs brought. One JSON line on stdout; `platform` says where it
ran, and only a line from the chip's machine is a measurement.

    chiprun -- python scripts/probe_splice.py --config minicpm-sala
    JAX_PLATFORMS=cpu python scripts/probe_splice.py --config tiny-sala \
        --platform cpu --blocks 1 --runs 0,1
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build(config: dict):
    from distributed_lms_raft_llm_tpu.engine import (
        EngineConfig,
        PagedEngine,
        SamplingParams,
    )

    s = config["serving"]
    econf = EngineConfig(
        model=config["registry_model"],
        sampling=SamplingParams.reference_defaults(**s["sampling"]),
        tp=s["tp"], quant=s["quant"], kv_quant=s["kv_quant"],
        spec_tokens=s["spec_tokens"], draft_source=s["draft_source"],
        scoring=s["scoring"], length_buckets=tuple(s["length_buckets"]),
    )
    return PagedEngine(
        econf, slots=s["slots"], chunk=s["chunk"], inflight=s["inflight"],
        megastep=s["megastep"], megastep_max=s["megastep_max"],
        prefix_cache=True, prefix_cache_blocks=s["prefix_cache_blocks"],
        prefill_chunk_tokens=s["prefill_chunk_tokens"],
    )


def set_run(engine, run_blocks: int) -> None:
    """The engine as if built with stored runs of `run_blocks` blocks: the
    tree's run length and the one program whose shape is the run's."""
    import jax

    from distributed_lms_raft_llm_tpu.engine import paged

    engine.stored_run_blocks = engine.prefix_cache.run_blocks = run_blocks
    pooled = ({"pool_stride": engine.cfg.pool_stride}
              if hasattr(engine.cfg, "pool_stride") else {})
    engine._export_run = jax.jit(paged.named_partial(
        paged._export_block_program,
        block=max(1, run_blocks) * engine.prefix_block_tokens, **pooled))


def reading(engine, run_blocks: int, blocks: int, admissions: int,
            seed: int) -> dict:
    import jax
    import numpy as np

    from distributed_lms_raft_llm_tpu.engine import paged

    set_run(engine, run_blocks)
    engine.reset()
    engine.prefix_cache.clear()
    blk = engine.prefix_block_tokens
    draw = np.random.default_rng(seed)
    shared = draw.integers(0, engine.cfg.vocab_size, blocks * blk).tolist()
    engine.state = engine._canon_state(engine._init_state(max(engine.widths)))
    t = time.perf_counter()
    with engine.mesh:
        engine._insert_blocks(shared, engine.state.cache, 0)
        if engine.family.recurrent_state:
            engine.prefix_cache.attach_snapshot(
                shared, len(shared), engine._canon_snapshot(
                    engine._export_state(engine.state, engine._i32(0))))
    jax.block_until_ready(engine.prefix_cache._root.children)
    publish_ms = 1e3 * (time.perf_counter() - t)
    # The first round pays whatever a first call of a shape pays.
    for _ in range(2):
        engine.reset()
        engine.state = engine._canon_state(
            engine._init_state(max(engine.widths)))
        jax.block_until_ready(engine.state)
        engine.pop_loop_stats()
        for _ in range(admissions):
            question = draw.integers(0, engine.cfg.vocab_size, 4)
            engine._pending.append(paged._Request(
                rid=engine._next_rid, prompt_len=len(shared) + 4,
                tokens=shared + question.tolist(),
                max_new=engine.config.sampling.max_new_tokens,
                submit_time=time.monotonic()))
            engine._next_rid += 1
        cpu, wall = time.thread_time(), time.perf_counter()
        engine._stage_admissions()
        cpu = time.thread_time() - cpu
        launched = time.perf_counter() - wall
        jax.block_until_ready(engine.state)
        wall = time.perf_counter() - wall
        counts, _, _ = engine.pop_loop_stats()
    n = counts["admissions"]
    return {
        "run_blocks": run_blocks, "admissions": n, "publish_ms": publish_ms,
        "cpu_ms_per_admission": 1e3 * cpu / n,
        "launched_ms_per_admission": 1e3 * launched / n,
        "written_ms_per_admission": 1e3 * wall / n,
        "launches_per_admission": counts["stage_block_launches"] / n,
        "tokens_from_runs_per_admission":
            counts.get("prefix_tokens_from_runs", 0) / n,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a name under benchmarks/configs/")
    ap.add_argument("--blocks", type=int, default=2051,
                    help="blocks of the shared edge (a reader's 2,051)")
    ap.add_argument("--runs", default="0,64,128,256",
                    help="blocks in a stored run, one reading each")
    ap.add_argument("--admissions", type=int, default=12)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"])
    args = ap.parse_args(argv)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           f"{args.config}.json"), encoding="utf-8") as fh:
        config = json.load(fh)

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform != args.platform:
        print(f"JAX initialised {platform!r}, the probe asked for "
              f"{args.platform!r}", file=sys.stderr)
        return 3
    engine = build(config)
    readings = [reading(engine, int(r), args.blocks,
                        min(args.admissions, engine.slots), args.seed)
                for r in args.runs.split(",")]
    print(json.dumps({
        "line": "probe_splice", "config": args.config, "platform": platform,
        "device_kind": jax.devices()[0].device_kind, "blocks": args.blocks,
        "block_tokens": engine.prefix_block_tokens,
        "width": max(engine.widths), "readings": readings,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
