"""Speculative decoding inside the paged continuous-batching engine.

The unification safety property mirrors tests/test_spec.py: speculation
changes WHEN tokens are computed, never WHICH distribution they come from.
Greedy paged+spec streams must be bit-identical to the non-spec paged
engine AND to the bucketed `engine.generate` path (any transcript, ragged
window-scatter, or seen-mask bug shows up within a few tokens); the first
token of a verify window must be distribution-identical to the plain
step's sampled token. On top of exactness: mid-decode admission still
works while another slot is mid-verify-window, the step program compiles
once per (S, k, width) configuration across a multi-request session, and
the serving queue surfaces acceptance metrics.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    PagedQueue,
    SamplingParams,
    TutoringEngine,
)
from distributed_lms_raft_llm_tpu.engine.paged import (
    SlotState,
    _spec_step_program,
    _step_program,
)
from distributed_lms_raft_llm_tpu.engine.sampling import seen_mask_from_ids
from distributed_lms_raft_llm_tpu.models import registry
from distributed_lms_raft_llm_tpu.utils.guards import compile_count_guard
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

MAX_NEW = 8

PROMPTS = ["what is raft?", "hello world", "explain paging", "k"]


def make_config(**kw):
    kw.setdefault("sampling", SamplingParams.greedy(max_new_tokens=MAX_NEW))
    kw.setdefault("length_buckets", (16,))
    kw.setdefault("spec_tokens", 3)
    return EngineConfig(
        model="tiny",
        batch_buckets=(1, 2, 4),
        dtype=jnp.float32,
        **kw,
    )


class TestGreedyBitEquality:
    @pytest.mark.parametrize("spec_tokens", [1, 3])
    def test_matches_plain_paged_and_bucketed(self, spec_tokens):
        """Same params/seed, greedy: the spec paged engine must emit exactly
        what the plain paged engine and the bucketed engine emit."""
        plain_cfg = make_config(spec_tokens=0)
        expected = TutoringEngine(plain_cfg).answer_batch(list(PROMPTS))
        plain = PagedEngine(plain_cfg, slots=4)
        pr = [plain.submit(p) for p in PROMPTS]
        out_plain = plain.drain()
        assert [out_plain[r] for r in pr] == expected

        spec = PagedEngine(make_config(spec_tokens=spec_tokens), slots=4)
        sr = [spec.submit(p) for p in PROMPTS]
        out_spec = spec.drain()
        assert [out_spec[r] for r in sr] == expected

    def test_with_repetition_penalty(self):
        # Penalty 1.2 exercises the hypothetical seen-stack inside the
        # shared verifier THROUGH the paged transcript plumbing: a token
        # accepted mid-window must penalize the rest of the window.
        sp = SamplingParams(temperature=0.0, top_k=50, top_p=1.0,
                            repetition_penalty=1.2, max_new_tokens=12)
        cfg = make_config(sampling=sp, spec_tokens=0)
        expected = TutoringEngine(cfg).answer_batch(list(PROMPTS))
        spec = PagedEngine(make_config(sampling=sp), slots=4)
        rids = [spec.submit(p) for p in PROMPTS]
        out = spec.drain()
        assert [out[r] for r in rids] == expected

    def test_with_prompt_buckets_and_slot_churn(self):
        """Per-prompt prefill buckets + slot reuse: 5 requests churn through
        2 slots, transcripts from evicted occupants must not leak into the
        next occupant's drafts (stale-anchor regression)."""
        cfg = make_config(length_buckets=(4, 8, 16), spec_tokens=0)
        prompts = list(PROMPTS) + ["k v"]
        expected = TutoringEngine(cfg).answer_batch(prompts)
        spec = PagedEngine(
            make_config(length_buckets=(4, 8, 16)), slots=2, chunk=2
        )
        rids = [spec.submit(p) for p in prompts]
        out = spec.drain()
        assert [out[r] for r in rids] == expected

    def test_with_kv_quant(self):
        cfg = make_config(spec_tokens=0, kv_quant=True)
        expected = TutoringEngine(cfg).answer_batch(list(PROMPTS[:2]))
        spec = PagedEngine(make_config(kv_quant=True), slots=2)
        rids = [spec.submit(p) for p in PROMPTS[:2]]
        out = spec.drain()
        assert [out[r] for r in rids] == expected

    def test_pipelined_outputs_match_serialized(self):
        """inflight=2 (dispatch N+1 before reading N) with ragged per-slot
        window advances must still produce byte-identical answers."""
        cfg = make_config()
        ser = PagedEngine(cfg, slots=2, inflight=1, chunk=2)
        rs = [ser.submit(p) for p in PROMPTS]
        out_ser = ser.drain()
        pipe = PagedEngine(cfg, slots=2, inflight=2, chunk=2)
        rp = [pipe.submit(p) for p in PROMPTS]
        out_pipe = pipe.drain()
        assert [out_pipe[r] for r in rp] == [out_ser[r] for r in rs]


def test_mid_verify_window_admission_completes_without_waiting():
    """A request submitted while another slot is mid-verify-window joins at
    the next chunk boundary and finishes within its own budget."""
    paged = PagedEngine(make_config(), slots=2, chunk=2)
    paged.submit("a long question about distributed consensus and logs")
    for _ in range(2):
        paged.step()  # A is now mid-decode, between verify windows
    b = paged.submit("b")
    finished = {}
    steps_after_b = 0
    while paged.has_work and steps_after_b < 3 * MAX_NEW:
        steps_after_b += 1
        for rid, _ in paged.step():
            finished.setdefault(rid, steps_after_b)
        if steps_after_b == 1:
            in_slots = {r.rid for r in paged._slot_req if r is not None}
            assert b in in_slots or b in finished
    assert b in finished
    # Each chunk=2 dispatch advances >= 2 windows of >= 1 token each, so B
    # needs at most ceil(MAX_NEW / 2) decode dispatches (+ admission +
    # pipelined-reap slack) — it did not wait for A's remaining decode.
    assert finished[b] <= MAX_NEW // 2 + 3


def test_first_window_token_matches_plain_step_distribution():
    """Distribution identity through the paged integration (mirrors
    tests/test_spec.py's verifier test, but through the transcript ->
    drafts -> ragged forward -> verify pipeline): over S identical slots,
    the FIRST token a verify window emits must be distributed exactly like
    the plain step's sampled token for the same prefix."""
    family, cfg = registry.resolve("tiny", jnp.float32)
    params = family.init_params(jax.random.key(0), cfg)
    sampling = SamplingParams(temperature=0.7, top_k=16, top_p=0.9,
                              repetition_penalty=1.2, max_new_tokens=8)
    s_slots, t0, width, k = 1500, 6, 16, 3
    rng = np.random.default_rng(0)
    row = rng.integers(1, cfg.vocab_size, t0)
    row[3:5] = row[0:2]  # a repeated bigram so the drafter finds anchors
    ids = jnp.asarray(np.tile(row, (s_slots, 1)), jnp.int32)
    pending = jnp.asarray(int(row[1]), jnp.int32)  # plausible next token

    cache = family.init_cache(cfg, s_slots, width, dtype=cfg.dtype)
    _, cache = family.forward(params, cfg, ids, cache=cache)
    cache = cache._replace(length=jnp.full((s_slots,), t0, jnp.int32))
    seen = seen_mask_from_ids(
        ids, jnp.ones((s_slots, t0), bool), cfg.vocab_size
    )
    seen = seen | jax.nn.one_hot(
        jnp.full((s_slots,), pending), cfg.vocab_size, dtype=jnp.bool_
    )
    transcript = jnp.zeros((s_slots, width), jnp.int32)
    transcript = transcript.at[:, :t0].set(ids)
    transcript = transcript.at[:, t0].set(pending)
    key_shape = jax.random.key_data(jax.random.key(0)).shape
    state = SlotState(
        cache=cache,
        tok=jnp.full((s_slots,), pending, jnp.int32),
        active=jnp.ones((s_slots,), bool),
        seen=seen,
        transcript=transcript,
        staged=jnp.zeros((s_slots,), bool),
        stage_cursor=jnp.zeros((s_slots,), jnp.int32),
        stage_len=jnp.ones((s_slots,), jnp.int32),
        stage_seq=jnp.zeros((s_slots,), jnp.int32),
        stage_rng=jnp.zeros((s_slots,) + key_shape, jnp.uint32),
    )

    statics = dict(cfg=cfg, sampling=sampling, eos_id=-1, pad_id=-1,
                   model=family, chunk=1)
    _, toks, _ = _step_program(params, state, jax.random.key(7), **statics)
    ref = np.asarray(toks)[0]  # [S] plain-step samples
    _, emitted, counts, _ = _spec_step_program(
        params, state, jax.random.key(8), spec_tokens=k, **statics
    )
    counts = np.asarray(counts)[0]
    assert (counts >= 1).all()
    got = np.asarray(emitted)[0, :, 0]  # [S] first window emission

    support = sorted(set(ref.tolist()) | set(got.tolist()))
    f_ref = np.array([(ref == s).mean() for s in support])
    f_got = np.array([(got == s).mean() for s in support])
    # 1500 trials/side: binomial std <= ~0.013 per bin; allow ~5 sigma.
    np.testing.assert_allclose(f_got, f_ref, atol=0.065)


def test_stochastic_session_plausible_and_observable():
    """A stochastic multi-request session completes, stays within budget,
    and reports acceptance stats (windows >= 1 token each, ceiling k+1)."""
    sp = SamplingParams.reference_defaults(max_new_tokens=MAX_NEW)
    eng = PagedEngine(make_config(sampling=sp), slots=2, chunk=2)
    rids = [eng.submit(f"the the the question {i}") for i in range(5)]
    out = eng.drain()
    assert all(isinstance(out[r], str) for r in rids)
    windows, emitted = eng.pop_spec_stats()
    assert windows > 0
    assert windows <= emitted <= windows * (eng.spec + 1)
    assert eng.pop_spec_stats() == (0, 0)  # drained


def test_step_program_compiles_once_per_width():
    """No silent per-step recompiles: the megastep of the spec step
    compiles exactly once per (S, k, width, rung) — S and k are fixed per
    engine, so once per width and rung — during warmup, and a live session that churns slots,
    rebuilds at both widths, and grows the cache mid-batch adds ZERO
    compilations (historically the spelling of replicated shardings
    differed between the state's producers, so warmup's compile
    did not cover the live handoffs — see paged._state_spec)."""
    eng = PagedEngine(
        make_config(length_buckets=(4, 16)), slots=2, chunk=2
    )
    assert len(eng.widths) == 2
    eng.warmup()
    programs = (eng._megastep, eng._stage, eng._grow)
    assert programs[0]._cache_size() == (
        len(eng.widths) * len(eng.megastep_ks))
    short, lng = "k v", "a long question about raft elections and logs"
    # The reusable runtime guard (utils/guards.py) generalizes this
    # assertion: zero new programs across the whole live session.
    with compile_count_guard(*programs, what="live paged session"):
        eng.submit(short)
        eng.step()       # running at the narrow width
        eng.submit(lng)  # grows the live cache mid-batch
        eng.drain()
        for prompt in (short, lng, short):  # idle rebuilds at both widths
            eng.submit(prompt)
        eng.drain()


def test_dead_slot_emits_no_filler_when_pad_differs_from_eos(
        eos_first_engine):
    """A slot inactive from admission (first sampled token is eos) emits
    zero-count windows — the spec reap must return an empty answer even
    when pad != eos (no filler misread as content)."""
    paged = eos_first_engine(make_config(), "anything at all", slots=2)
    assert paged.tokenizer.eos_id != paged.tokenizer.pad_id
    rid = paged.submit("anything at all")
    out = paged.drain()
    assert out[rid] == paged.tokenizer.decode([])


def test_paged_queue_reports_spec_metrics():
    """The default server path surfaces speculation: PagedQueue feeds the
    spec_tokens_per_window gauge and spec_accepted_tokens counter from the
    engine's reap-time stats."""
    metrics = Metrics()
    engine = PagedEngine(make_config(), slots=2, chunk=2)

    async def run():
        q = PagedQueue(engine, metrics=metrics)
        await q.start()
        answers = await asyncio.gather(
            *[q.submit(f"query number {i}") for i in range(4)]
        )
        await q.close()
        return answers

    answers = asyncio.run(run())
    assert len(answers) == 4
    snap = metrics.snapshot()
    tpw = snap["gauges"]["spec_tokens_per_window"]
    assert 1.0 <= tpw <= engine.spec + 1
    assert snap["counters"]["spec_accepted_tokens"] >= 0
    assert metrics.hist("ttft").snapshot()["count"] == 4


def test_spec_overhang_respects_position_table():
    # tiny's position table is 64. With max_new=50 and k=4 the prompt
    # bucket must shrink by the window's k-1 overhang so the widest
    # verify window stays inside the table; a budget leaving no prompt
    # room at all is rejected loudly.
    eng = PagedEngine(
        make_config(sampling=SamplingParams.greedy(max_new_tokens=50),
                    spec_tokens=4),
        slots=2,
    )
    assert eng.bucket == 64 - 50 - 3
    assert eng.widths[-1] == eng.bucket + 50 + 3 <= 64
    rid = eng.submit("a prompt much longer than eleven byte-tokens")
    assert isinstance(eng.drain()[rid], str)
    with pytest.raises(ValueError, match="no room"):
        PagedEngine(
            make_config(sampling=SamplingParams.greedy(max_new_tokens=62),
                        spec_tokens=4),
            slots=2,
        )
