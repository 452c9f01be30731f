"""PagedEngine continuous batching: parity, mid-decode admission, slot reuse.

The round-1 done-criterion for continuous batching: a request submitted
mid-decode completes without waiting for the running group (the reference
serves strictly one request at a time — reference:
GUI_RAFT_LLM_SourceCode/tutoring_server.py:21-29).
"""

import asyncio

import jax
import pytest

from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    PagedQueue,
    SamplingParams,
    TutoringEngine,
)
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

MAX_NEW = 8

PROMPTS = ["what is raft?", "hello world", "explain paging", "k"]


def make_config(**kw):
    kw.setdefault("sampling", SamplingParams.greedy(max_new_tokens=MAX_NEW))
    kw.setdefault("length_buckets", (16,))
    return EngineConfig(
        model="tiny",
        batch_buckets=(1, 2, 4),
        dtype=jax.numpy.float32,
        **kw,
    )


def test_greedy_parity_with_bucketed_engine():
    """Same params (same seed), greedy sampling: the paged engine must emit
    exactly what the bucketed engine emits, despite its different padding
    (right vs left) and per-slot ragged cache layout."""
    cfg = make_config()
    expected = TutoringEngine(cfg).answer_batch(list(PROMPTS))
    paged = PagedEngine(cfg, slots=4)
    rids = [paged.submit(p) for p in PROMPTS]
    out = paged.drain()
    assert [out[rid] for rid in rids] == expected


def test_mid_decode_admission_completes_without_waiting():
    paged = PagedEngine(make_config(), slots=2)
    paged.submit("a long question about distributed consensus and logs")
    for _ in range(3):
        paged.step()  # A is now mid-decode
    b = paged.submit("b")
    finished = {}
    steps_after_b = 0
    while paged.has_work and steps_after_b < 3 * MAX_NEW:
        steps_after_b += 1
        for rid, text in paged.step():
            finished.setdefault(rid, steps_after_b)
        if steps_after_b == 1:
            # B was admitted into a free slot immediately, joining the
            # running batch rather than queueing behind it.
            in_slots = {r.rid for r in paged._slot_req if r is not None}
            assert b in in_slots or b in finished
    assert b in finished
    # B finished within its own generation budget (+1 for the admission
    # step) — it did not wait for A's remaining decode.
    assert finished[b] <= MAX_NEW + 1


def test_pipelined_outputs_match_serialized():
    """inflight=2 (dispatch N+1 before reading N — the throughput mode)
    must produce byte-identical answers to the serialized inflight=1 loop,
    including through slot churn (4 requests over 2 slots)."""
    cfg = make_config()
    ser = PagedEngine(cfg, slots=2, inflight=1)
    rs = [ser.submit(p) for p in PROMPTS]
    out_ser = ser.drain()
    pipe = PagedEngine(cfg, slots=2, inflight=2)
    rp = [pipe.submit(p) for p in PROMPTS]
    out_pipe = pipe.drain()
    assert [out_pipe[r] for r in rp] == [out_ser[r] for r in rs]


def test_greedy_parity_with_prompt_buckets_and_churn():
    """Per-prompt buckets (short prompt -> narrow `_stage` program)
    plus slot reuse: answers still match the bucketed engine exactly."""
    cfg = make_config(length_buckets=(4, 8, 16))
    prompts = list(PROMPTS) + ["k v"]
    expected = TutoringEngine(cfg).answer_batch(prompts)
    paged = PagedEngine(cfg, slots=2)  # 5 requests churn through 2 slots
    widths = set()
    real_stage = paged._stage
    paged._stage = lambda state, slot, ids, *a: (
        widths.add(ids.shape[1]) or real_stage(state, slot, ids, *a)
    )
    rids = [paged.submit(p) for p in prompts]
    out = paged.drain()
    assert [out[r] for r in rids] == expected
    # Short prompts really were staged in narrower prompt buckets.
    assert len(widths) >= 2 and min(widths) < 16, widths


def test_cache_width_grows_and_shrinks_with_prompt_mix():
    """Width-bucketed slot cache: short prompts run at a narrow width, a
    long prompt grows the live cache mid-batch, and an idle engine shrinks
    back — all with exact greedy parity against the bucketed engine."""
    cfg = make_config(length_buckets=(4, 16))
    long_prompt = "a long question about raft elections and replicated logs"
    prompts = ["k v", long_prompt, "hi"]
    expected = TutoringEngine(cfg).answer_batch(prompts)

    paged = PagedEngine(cfg, slots=2)
    assert len(paged.widths) == 2  # (4 + 8, 16 + 8) admissible widths
    narrow, wide = paged.widths
    # Short prompt first: engine rebuilds/stays at the narrow width.
    r0 = paged.submit(prompts[0])
    paged.step()
    assert paged.state.cache.k.shape[3] == narrow
    # Long prompt arrives mid-decode: the live cache pads up.
    r1 = paged.submit(prompts[1])
    out = {}
    while paged.has_work and len(out) < 2:
        out.update(paged.step())
    assert paged.state.cache.k.shape[3] == wide
    # Idle, then a short prompt: rebuild shrinks back to narrow.
    r2 = paged.submit(prompts[2])
    while paged.has_work:
        out.update(paged.step())
    assert paged.state.cache.k.shape[3] == narrow
    assert [out[r] for r in (r0, r1, r2)] == expected


def test_slot_reuse_evict_then_readmit():
    """slots=1 forces the second request through a hand-on into the slot
    the first one held; both answers must be the bucketed engine's."""
    cfg = make_config()
    expected = TutoringEngine(cfg).answer_batch(list(PROMPTS[:2]))
    paged = PagedEngine(cfg, slots=1)
    f1 = paged.submit(PROMPTS[0])
    f2 = paged.submit(PROMPTS[1])
    both = paged.drain()
    assert [both[f1], both[f2]] == expected


def test_overflow_budget_clamped_or_rejected():
    # tiny's position table is 64. A budget of 50 clamps the prompt bucket
    # to 14 so bucket + max_new always fits (no silent KV corruption at
    # tmax); a budget leaving no prompt room at all is rejected.
    eng = PagedEngine(
        make_config(sampling=SamplingParams.greedy(max_new_tokens=50)), slots=2
    )
    assert eng.bucket == 14
    assert eng.bucket + 50 <= 64
    rid = eng.submit("a prompt much longer than fourteen byte-tokens")
    assert isinstance(eng.drain()[rid], str)
    with pytest.raises(ValueError, match="no room"):
        PagedEngine(
            make_config(sampling=SamplingParams.greedy(max_new_tokens=64)),
            slots=2,
        )


def test_paged_queue_serves_concurrent_requests():
    metrics = Metrics()
    engine = PagedEngine(make_config(), slots=2)

    async def run():
        q = PagedQueue(engine, metrics=metrics)
        await q.start()
        answers = await asyncio.gather(
            *[q.submit(f"query number {i}") for i in range(5)]
        )
        await q.close()
        return answers

    answers = asyncio.run(run())
    assert len(answers) == 5
    assert all(isinstance(a, str) for a in answers)
    # Per-request TTFT landed in the serving histogram.
    assert metrics.hist("ttft").snapshot()["count"] == 5


def test_paged_queue_recovers_after_step_failure():
    """A failed step fails its in-flight requests but must not poison the
    engine (step donates the live state) — later requests still serve."""
    engine = PagedEngine(make_config(), slots=2)
    orig_step = engine.step
    armed = {"on": True}

    def flaky_step():
        if armed["on"]:
            armed["on"] = False
            raise RuntimeError("injected device failure")
        return orig_step()

    engine.step = flaky_step

    async def run():
        q = PagedQueue(engine)
        await q.start()
        with pytest.raises(RuntimeError, match="injected"):
            await q.submit("first")
        answer = await q.submit("second")
        await q.close()
        return answer

    assert isinstance(asyncio.run(run()), str)


def test_dead_slot_pad_filler_not_appended_when_pad_differs_from_eos(
        eos_first_engine):
    """Regression (review): with a tokenizer where pad != eos, a slot that
    is inactive from admission (first sampled token is eos) must return an
    empty answer — chunk pad filler is not content."""
    paged = eos_first_engine(make_config(), "anything at all", slots=2)
    assert paged.tokenizer.eos_id != paged.tokenizer.pad_id
    rid = paged.submit("anything at all")
    out = paged.drain()
    # The request finished with no pad-filler tokens decoded as content.
    assert out[rid] == paged.tokenizer.decode([])
