"""The reference generator (`TutoringEngine`) end-to-end on the 8-device CPU
mesh: tiny model, real pipeline; and the served queue closing over a tiny
`PagedEngine`."""

import asyncio

import numpy as np
import pytest

import jax

from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    GateConfig,
    PagedEngine,
    PagedQueue,
    RelevanceGate,
    SamplingParams,
    TutoringEngine,
)


@pytest.fixture(scope="module")
def engine():
    cfg = EngineConfig(
        model="tiny",
        sampling=SamplingParams(max_new_tokens=8),
        length_buckets=(16, 32),
        batch_buckets=(1, 2, 4),
        tp=2,  # exercise tensor parallelism on the virtual mesh
        dtype=jax.numpy.float32,
    )
    return TutoringEngine(cfg)


def test_engine_mesh_uses_all_devices(engine):
    assert engine.mesh.devices.size == 8  # 2-way tp × 4-way dp


def test_answer_batch_shapes_and_determinism(engine, strict_dispatch_guard):
    # Runs under dispatch-hygiene assertion mode (conftest fixture): every
    # host sync on the serving path must be a marked intended_transfer().
    answers = engine.answer_batch(["hello world", "what is raft?"])
    assert len(answers) == 2
    assert all(isinstance(a, str) for a in answers)


def test_prompt_longer_than_bucket_keeps_tail(engine):
    long_prompt = "x" * 500  # 500 byte-tokens > largest bucket (32)
    ids, mask, bucket = engine.encode_prompts([long_prompt])
    assert bucket <= 32 - 0  # bucketed
    assert ids.shape[1] <= 32
    assert mask[0].all()  # fully real after truncation to the tail


def test_empty_prompt_is_well_formed(engine):
    answers = engine.answer_batch([""])
    assert len(answers) == 1


def test_batch_bucketing_pads_filler_rows(engine):
    ids, mask, _ = engine.encode_prompts(["a", "b", "c"])
    assert ids.shape[0] == 4  # bucketed to 4
    assert mask[3].sum() == 1  # filler row has exactly one valid slot


def test_generation_respects_max_new_tokens(engine):
    ids, mask, _ = engine.encode_prompts(["hello"])
    result = engine.generate_ids(ids, mask)
    assert result.tokens.shape[1] == 8
    assert (result.lengths <= 8).all()


def test_relevance_gate_threshold():
    gate = RelevanceGate(GateConfig(model="tiny", dtype=jax.numpy.float32))
    ok, sim = gate.check("what is a binary tree", "binary trees and traversals")
    assert -1.0 <= sim <= 1.0
    self_ok, self_sim = gate.check("same text", "same text")
    assert self_ok and self_sim == pytest.approx(1.0, abs=1e-4)


def test_answer_batch_chunks_oversized_groups():
    cfg = EngineConfig(
        model="tiny",
        sampling=SamplingParams(max_new_tokens=4),
        length_buckets=(16,),
        batch_buckets=(1, 2, 4),
        dtype=jax.numpy.float32,
    )
    eng = TutoringEngine(cfg)
    answers = eng.answer_batch([f"q{i}" for i in range(9)])  # > max bucket 4
    assert len(answers) == 9


def test_max_new_tokens_validated_against_position_table():
    with pytest.raises(ValueError, match="max_new_tokens"):
        TutoringEngine(
            EngineConfig(model="tiny", sampling=SamplingParams(max_new_tokens=128))
        )


def test_queue_close_fails_pending_submits():
    cfg = EngineConfig(
        model="tiny",
        sampling=SamplingParams(max_new_tokens=4),
        length_buckets=(16,),
        batch_buckets=(1,),
        dtype=jax.numpy.float32,
    )
    eng = PagedEngine(cfg, slots=1, chunk=2)

    async def run():
        q = PagedQueue(eng)
        await q.start()
        tasks = [asyncio.create_task(q.submit(f"q{i}")) for i in range(3)]
        await asyncio.sleep(0.05)  # let some enter flight
        await q.close()
        results = await asyncio.gather(*tasks, return_exceptions=True)
        return results

    results = asyncio.run(run())
    # Every pending submit resolved (answer or RuntimeError) — none hang.
    assert all(isinstance(r, (str, RuntimeError)) for r in results)
