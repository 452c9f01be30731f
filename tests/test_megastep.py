"""Device-resident megastep decode: K chunks per host dispatch.

The megastep changes WHEN the host talks to the device, never WHAT the
device computes: greedy streams through megasteps (any K) must be
bit-identical to the chunk-loop paged engine AND the bucketed engine, in
plain, spec, kv-quant, slot-churn, and mid-megastep-admission scenarios.
On top of exactness: the TTFT-aware K controller shrinks whenever work
waits for a slot (the p90-TTFT guard), step-program host dispatches per
emitted token drop by exactly K at steady state, the on-device dead-lane
account matches a first-principles derivation, the whole megastep domain
is warmup-covered (`expected_from_inventory` equality), and the serving
queue surfaces the new efficiency gauges.
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
    _megastep_program,
    _Request,
    _step_program,
    next_megastep_k,
    rows_to_certain_end,
)
from distributed_lms_raft_llm_tpu.engine.program_inventory import (
    effective_megastep_max,
    megastep_ladder,
)
from distributed_lms_raft_llm_tpu.models import registry
from distributed_lms_raft_llm_tpu.utils.guards import (
    compile_count_guard,
    expected_from_inventory,
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
        dtype=jnp.float32,
        **kw,
    )


# ------------------------------------------------------- controller + ladder


def test_megastep_ladder_shapes():
    assert megastep_ladder(1) == [1]
    assert megastep_ladder(0) == [1]
    assert megastep_ladder(2) == [1, 2]
    assert megastep_ladder(8) == [1, 2, 4, 8]
    assert megastep_ladder(6) == [1, 2, 4, 6]  # ceiling always a rung


def test_effective_megastep_max_explicit_ceiling_wins():
    """An explicitly configured ceiling caps the starting rung (the
    worst-case admission wait the operator bounded must hold); 0 means
    follow `megastep`."""
    assert effective_megastep_max(8, 4) == 4   # ceiling clamps the start
    assert effective_megastep_max(2, 8) == 8
    assert effective_megastep_max(4, 0) == 4   # 0 = follow megastep
    assert effective_megastep_max(0, 0) == 1
    eng = PagedEngine(make_config(), slots=2, chunk=2,
                      megastep=8, megastep_max=4)
    assert eng.megastep_ks == [1, 2, 4]
    assert eng.megastep_k == 4


def test_controller_shrinks_when_pending_queue_nonempty():
    """The TTFT guard: backlogged work caps K at the guaranteed
    admission horizon — the largest rung that fits the chunks until a
    slot MUST free — so a boundary falls where a waiting request can be
    staged."""
    ladder = [1, 2, 4, 8]
    assert next_megastep_k(8, ladder, pending=1, slack_chunks=2) == 2
    assert next_megastep_k(4, ladder, pending=1, slack_chunks=3) == 2
    assert next_megastep_k(8, ladder, pending=1, slack_chunks=5) == 4
    assert next_megastep_k(8, [1, 2, 4, 6], pending=2, slack_chunks=7) == 6
    assert next_megastep_k(1, [1], pending=5, slack_chunks=9) == 1


def test_rows_to_certain_end_arithmetic():
    """The one horizon `_slack_chunks` and the hand-on test share: budget
    left, net of the rows dispatched and not yet reaped; 0 once the end is
    in flight; nothing for a staged or a finished request."""
    def req(n_tokens, prompt_len=10, max_new=128, **kw):
        return _Request(rid=0, prompt_len=prompt_len,
                        tokens=[7] * n_tokens, max_new=max_new, **kw)

    tmax = 10 + 128
    # Budget left: the cap less the tokens the host has.
    assert rows_to_certain_end(req(1), tmax, 0) == 127
    assert rows_to_certain_end(req(100), tmax, 0) == 28
    # Dispatched debt: every row in flight brings the lane a token.
    assert rows_to_certain_end(req(33), tmax, 64) == 31
    assert rows_to_certain_end(req(63), tmax, 64) == 1
    # The end is in flight: at the cap exactly, and past it (overrun).
    assert rows_to_certain_end(req(64), tmax, 64) == 0
    assert rows_to_certain_end(req(100), tmax, 64) == 0
    # The tmax clause `_walk` finishes a request by binds first where the
    # position table is shorter than prompt + budget.
    assert rows_to_certain_end(req(1, prompt_len=40), 100, 0) == 59
    assert rows_to_certain_end(req(28, prompt_len=40), 100, 32) == 0
    # Staged (tokens still hold the prompt), finished, no request: no bound.
    assert rows_to_certain_end(req(10, live=False), tmax, 64) is None
    assert rows_to_certain_end(req(128, finished=True), tmax, 0) is None
    assert rows_to_certain_end(None, tmax, 64) is None


def test_slack_chunks_is_the_least_horizon_net_of_each_slots_debt():
    """`_slack_chunks` = the least `rows_to_certain_end` over the slots in
    chunks, rounded up; a slot's debt is the rows of the in-flight
    dispatches whose snapshot holds ITS request; staged slots are out."""
    eng = PagedEngine(make_config(), slots=4, chunk=2, megastep=2,
                      megastep_max=2)

    def req(rid, n_tokens, **kw):
        return _Request(rid=rid, prompt_len=4, tokens=[7] * n_tokens,
                        max_new=MAX_NEW, **kw)

    assert eng._slack_chunks() is None
    a, b, staged = req(0, 1), req(1, 4), req(2, 4, live=False)
    eng._slot_req = [a, b, staged, None]
    assert eng._slack_chunks() == 2            # b: 4 rows left, 2 a chunk
    assert [eng._rows_to_end(s) for s in range(4)] == [7, 4, None, None]
    # One K=2 dispatch (4 rows) in flight that held a and b, and one from
    # before a took its slot: b's end is in flight, a's debt is 4 rows.
    active = np.zeros((2, 4), np.int8)
    eng._inflight = [
        (None, None, active, None, None, None, [None, b, None, None], None),
        (None, None, active, None, None, None, [a, b, staged, None], None),
    ]
    assert [eng._rows_to_end(s) for s in range(4)] == [3, 0, None, None]
    assert eng._slack_chunks() == 0
    assert eng._end_in_flight(1) and not eng._end_in_flight(0)
    assert not eng._end_in_flight(2)
    eng._session_reqs[b.rid] = ("sess", 30.0, [1, 2, 3, 4])
    assert not eng._end_in_flight(1), "a session turn waits for its reap"
    eng._slot_req[1] = None
    assert eng._slack_chunks() == 2            # a: 3 rows left -> 2 chunks
    eng._inflight = []


def test_controller_holds_amortization_under_saturation():
    """A sustained backlog with the next guaranteed slot-free far away
    must NOT pin K at the floor: boundaries before the horizon admit
    nobody and only forfeit amortization — this is the saturation regime
    the megastep exists for, and an unconditional shrink-on-pending
    would disable it exactly there."""
    ladder = [1, 2, 4, 8]
    assert next_megastep_k(1, ladder, pending=16, slack_chunks=64) == 8
    assert next_megastep_k(8, ladder, pending=16, slack_chunks=8) == 8
    assert next_megastep_k(2, ladder, pending=1, slack_chunks=4) == 4


def test_controller_floor_is_one_chunk():
    """Under a backlog a dispatch ends where the next answer ends: a
    slack of one chunk, or of none (an end already due), gives K = 1, so
    the lane decodes no second chunk past its answer's end before the
    slot is handed on. With no horizon at all (only staged requests,
    which bound nothing until their flip) nothing can be handed on at an
    earlier boundary and K keeps the second rung. Above the floor the
    slack cap and, with nobody waiting, the growth rule answer as they
    always did, rung for rung."""
    ladder = [1, 2, 4, 8]
    for current in ladder:
        for pending in (1, 3, 16):
            assert next_megastep_k(current, ladder, pending, 1) == 1
            assert next_megastep_k(current, ladder, pending, 0) == 1
            assert next_megastep_k(current, ladder, pending, None) == 2
            # Above the floor: the largest rung that fits the slack.
            for slack, k in ((2, 2), (3, 2), (4, 4), (5, 4), (7, 4),
                             (8, 8), (9, 8), (64, 8)):
                assert next_megastep_k(current, ladder, pending, slack) == k
    assert next_megastep_k(8, [1, 2, 4, 6], 2, 1) == 1
    assert next_megastep_k(8, [1, 2, 4, 6], 2, 7) == 6
    assert next_megastep_k(6, [1, 2, 4, 6], 2, None) == 2
    # Nobody waiting: one rung up whatever the slack says, 0 and 1 too.
    for slack in (None, 0, 1, 2, 64):
        assert [next_megastep_k(k, ladder, 0, slack) for k in ladder] == [
            2, 4, 8, 8]
    # A [1] ladder (megastep disabled) still returns its only rung.
    assert next_megastep_k(1, [1], pending=5, slack_chunks=0) == 1
    assert next_megastep_k(1, [1], pending=5, slack_chunks=None) == 1


def test_controller_grows_toward_max_when_idle():
    ladder = [1, 2, 4, 8]
    assert next_megastep_k(1, ladder, pending=0) == 2
    assert next_megastep_k(4, ladder, pending=0) == 8
    assert next_megastep_k(8, ladder, pending=0) == 8  # ceiling
    assert next_megastep_k(1, [1], pending=0) == 1     # disabled


def test_engine_controller_tracks_admission_horizon(monkeypatch):
    """Through the real engine: a staged request bounds no horizon until
    its flip is reaped (the second rung meanwhile); then a backlog keeps
    K wide while no slot can free (slack = remaining budget) and steps K
    down as the dispatched debt closes on the guaranteed finish, to K = 1
    exactly where a live slot's end is within a chunk: amortization
    under saturation, and a boundary where a slot can be handed on."""
    from distributed_lms_raft_llm_tpu.engine import paged as paged_mod

    calls = []  # (pending, slack, K chosen), one per dispatch
    real = paged_mod.next_megastep_k

    def spy(current, ladder, pending, slack):
        k = real(current, ladder, pending, slack)
        calls.append((pending, slack, k))
        return k

    monkeypatch.setattr(paged_mod, "next_megastep_k", spy)
    eng = PagedEngine(
        make_config(sampling=SamplingParams.greedy(max_new_tokens=22)),
        slots=2, chunk=2, megastep=4, megastep_max=4)
    for i in range(6):
        eng.submit(f"question number {i}")
    eng.step()  # 2 staged, 4 waiting: nothing live bounds the horizon
    assert calls == [(4, None, 2)] and eng.megastep_k == 2
    eng.drain()
    backlog = [(slack, k) for pending, slack, k in calls if pending]
    wide = [slack for slack, k in backlog if k == 4]
    assert wide and min(wide) >= 4       # wide only while no slot can free
    two = [slack for slack, k in backlog if k == 2 and slack is not None]
    assert two and set(two) <= {2, 3}
    one = [slack for slack, k in backlog if k == 1]
    assert one and set(one) <= {0, 1}    # an end within a chunk, and
    assert all(k == 1 for slack, k in backlog   # nowhere else
               if slack is not None and slack <= 1)
    counts = eng.pop_loop_stats()[0]
    assert counts["one_chunk_dispatches"] == len(one)


# ------------------------------------------------------- greedy bit-equality


class TestGreedyBitEquality:
    @pytest.mark.parametrize("megastep", [1, 4])
    def test_matches_chunk_loop_and_bucketed(self, megastep):
        """Acceptance pin: megastep K in {1, 4} emits exactly what the
        chunk-loop paged engine and the bucketed engine emit."""
        cfg = make_config()
        expected = TutoringEngine(cfg).answer_batch(list(PROMPTS))
        plain = PagedEngine(cfg, slots=4, chunk=2)
        pr = [plain.submit(p) for p in PROMPTS]
        out_plain = plain.drain()
        assert [out_plain[r] for r in pr] == expected

        mega = PagedEngine(cfg, slots=4, chunk=2,
                           megastep=megastep, megastep_max=megastep)
        mr = [mega.submit(p) for p in PROMPTS]
        out_mega = mega.drain()
        assert [out_mega[r] for r in mr] == expected

    @pytest.mark.parametrize("spec_tokens", [1, 3])
    def test_spec_mode(self, spec_tokens):
        """Megastep x speculation: K fused chunks of [S, k+1] verify
        windows must still match the non-spec engines bit for bit."""
        cfg = make_config(spec_tokens=0)
        expected = TutoringEngine(cfg).answer_batch(list(PROMPTS))
        mega = PagedEngine(
            make_config(spec_tokens=spec_tokens), slots=4, chunk=2,
            megastep=4, megastep_max=4,
        )
        mr = [mega.submit(p) for p in PROMPTS]
        out = mega.drain()
        assert [out[r] for r in mr] == expected
        windows, emitted = mega.pop_spec_stats()
        assert windows > 0
        assert windows <= emitted <= windows * (spec_tokens + 1)

    def test_kv_quant(self):
        cfg = make_config(kv_quant=True)
        expected = TutoringEngine(cfg).answer_batch(list(PROMPTS[:2]))
        mega = PagedEngine(cfg, slots=2, chunk=2,
                           megastep=4, megastep_max=4)
        mr = [mega.submit(p) for p in PROMPTS[:2]]
        out = mega.drain()
        assert [out[r] for r in mr] == expected

    def test_slot_churn_and_prompt_buckets(self):
        """5 requests over 2 slots with mixed prompt buckets: admissions
        land at megastep boundaries, the controller moves along the
        ladder as the backlog drains, and every stream still matches the
        bucketed engine."""
        cfg = make_config(length_buckets=(4, 8, 16))
        prompts = list(PROMPTS) + ["k v"]
        expected = TutoringEngine(cfg).answer_batch(prompts)
        mega = PagedEngine(cfg, slots=2, chunk=2,
                           megastep=2, megastep_max=4)
        rids = [mega.submit(p) for p in prompts]
        out = mega.drain()
        assert [out[r] for r in rids] == expected

    def test_pipelined_megasteps_match_serialized(self):
        """inflight=2 (dispatch megastep N+1 before reading N) with the
        stacked [K, chunk, S] reap must produce byte-identical answers."""
        cfg = make_config()
        ser = PagedEngine(cfg, slots=2, chunk=2, inflight=1,
                          megastep=4, megastep_max=4)
        rs = [ser.submit(p) for p in PROMPTS]
        out_ser = ser.drain()
        pipe = PagedEngine(cfg, slots=2, chunk=2, inflight=2,
                           megastep=4, megastep_max=4)
        rp = [pipe.submit(p) for p in PROMPTS]
        out_pipe = pipe.drain()
        assert [out_pipe[r] for r in rp] == [out_ser[r] for r in rs]


def test_mid_megastep_admission_joins_at_next_boundary():
    """A request submitted while megasteps are in flight is admitted at
    the next dispatch boundary, and the controller's shrink keeps its
    wait bounded — it finishes within its own budget, not after A's."""
    eng = PagedEngine(make_config(), slots=2, chunk=2,
                      megastep=4, megastep_max=4)
    eng.submit("a long question about distributed consensus and logs")
    for _ in range(2):
        eng.step()  # A mid-decode; megasteps pipelined in flight
    b = eng.submit("b")
    finished = {}
    steps_after_b = 0
    while eng.has_work and steps_after_b < 3 * MAX_NEW:
        steps_after_b += 1
        for rid, _ in eng.step():
            finished.setdefault(rid, steps_after_b)
        if steps_after_b == 1:
            in_slots = {r.rid for r in eng._slot_req if r is not None}
            assert b in in_slots or b in finished
    assert b in finished
    # Each dispatch advances >= chunk tokens for B once admitted; with the
    # admission + pipelined-reap slack, B cannot have waited for A's
    # remaining decode.
    assert finished[b] <= MAX_NEW // 2 + 3


# -------------------------------------------------- dispatch amortization


def test_step_dispatches_per_token_reduced_4x_at_k4():
    """The megastep's target number: at K=4 the host pays 4x fewer
    decode dispatches per emitted token than at K=1 (the
    per-request stage dispatch is an admission constant that
    megastep does not touch; the chunk loop proper is what it removes).
    inflight=1 keeps the dispatch count exact (no pipelined overhang)."""
    max_new = 17  # 1 first token + 16 decode steps at chunk=1
    cfg = make_config(
        sampling=SamplingParams.greedy(max_new_tokens=max_new),
        length_buckets=(8,),
    )
    prompt = "a question about raft elections and paging"

    def run(megastep):
        eng = PagedEngine(cfg, slots=1, chunk=1, inflight=1,
                          megastep=megastep, megastep_max=megastep)
        eng.submit(prompt)
        eng.drain()
        dispatches, tokens, _dead = eng.pop_dispatch_stats()
        steps = sum(
            1 for name, _, _ in eng.pop_program_times()
            if name == "megastep"
        )
        return dispatches, tokens, steps

    d1, t1, steps1 = run(1)
    d4, t4, steps4 = run(4)
    assert t1 == t4 == max_new, "prompt must use its full budget (no eos)"
    assert steps1 / steps4 >= 4.0
    # Total host dispatches per token (admissions included) shrink too.
    assert d4 / t4 < d1 / t1


# ------------------------------------------------------ dead-lane account


def test_dead_lane_account_matches_first_principles():
    """A slot that dies (eos) inside a megastep burns one pad lane per
    remaining scan iteration; the device-side account must equal
    chunk * (chunks remaining after the one it died in), derived
    independently from a chunk-loop discovery run."""
    family, cfg = registry.resolve("tiny", jnp.float32)
    params = family.init_params(jax.random.key(0), cfg)
    sampling = SamplingParams.greedy(max_new_tokens=32)
    s_slots, t0, width, chunk, k_chunks = 2, 4, 40, 2, 3
    rng = np.random.default_rng(0)
    ids = jnp.asarray(
        rng.integers(1, cfg.vocab_size, (s_slots, t0)), jnp.int32
    )
    cache = family.init_cache(cfg, s_slots, width, dtype=cfg.dtype)
    _, cache = family.forward(params, cfg, ids, cache=cache)
    cache = cache._replace(length=jnp.full((s_slots,), t0, jnp.int32))
    transcript = jnp.zeros((s_slots, width), jnp.int32)
    transcript = transcript.at[:, :t0].set(ids)
    key_shape = jax.random.key_data(jax.random.key(0)).shape
    state = SlotState(
        cache=cache,
        tok=ids[:, -1],
        active=jnp.ones((s_slots,), bool),
        seen=jnp.zeros((s_slots, cfg.vocab_size), bool),
        transcript=transcript,
        staged=jnp.zeros((s_slots,), bool),
        stage_cursor=jnp.zeros((s_slots,), jnp.int32),
        stage_len=jnp.ones((s_slots,), jnp.int32),
        stage_seq=jnp.zeros((s_slots,), jnp.int32),
        stage_rng=jnp.zeros((s_slots,) + key_shape, jnp.uint32),
    )
    statics = dict(cfg=cfg, sampling=sampling, pad_id=0, model=family,
                   chunk=chunk)
    # Discovery: with an unreachable eos, greedy decode runs the full
    # k_chunks * chunk iterations; pick slot 0's token at iteration 1
    # (mid-chunk-0) as the eos for the measured run.
    _, toks, _ = _step_program(
        params, state, jax.random.key(1), eos_id=-1,
        **dict(statics, chunk=chunk * k_chunks),
    )
    toks = np.asarray(toks)  # [chunk*K, S]
    eos = int(toks[1, 0])
    # Slot 0 must die in chunk 0 and slot 1 must survive the whole
    # megastep for the expected count below to be exact.
    die_iter = int(np.argmax(toks[:, 0] == eos))
    assert die_iter < chunk
    assert eos not in toks[:, 1]
    rngs = jnp.stack([jax.random.key(1)] + [
        jax.random.key(100 + i) for i in range(k_chunks - 1)
    ])
    _, _, active, dead, _, _, _ = _megastep_program(
        params, state, rngs, eos_id=eos, spec_tokens=0, prefill_chunk=4,
        **statics
    )
    active = np.asarray(active)
    assert active[0, 0] == 0 and all(active[:, 1] == 1)
    # Slot 0 is dead after chunk 0 -> burns chunk lanes in each of the
    # K-1 remaining chunks; slot 1 never dies -> contributes nothing.
    assert int(np.asarray(dead)) == chunk * (k_chunks - 1)


def test_k1_dispatches_account_no_dead_lanes():
    """At rung 1 the host reaps every chunk, so the megastep's dead-lane
    account stays zero by construction."""
    eng = PagedEngine(make_config(), slots=2, chunk=2)
    assert eng.megastep_ks == [1]
    for p in PROMPTS[:2]:
        eng.submit(p)
    eng.drain()
    dispatches, tokens, dead = eng.pop_dispatch_stats()
    assert dead == 0 and tokens == 2 * MAX_NEW
    names = [name for name, _, _ in eng.pop_program_times()]
    assert dispatches == len(names) and set(names) == {"stage", "megastep"}
    assert eng.pop_dispatch_stats() == (0, 0, 0)


# --------------------------------------------- warmup / inventory coverage


def test_warmed_megastep_session_passes_inventory_guard():
    """compile_count_guard(expected_from_inventory(...)): warmup compiles
    the full megastep domain (widths x ladder rungs) and a live
    session that walks the controller across rungs, churns slots, and
    grows the cache adds ZERO programs."""
    eng = PagedEngine(
        make_config(length_buckets=(4, 16)), slots=2, chunk=2,
        megastep=2, megastep_max=4,
    )
    assert eng.megastep_ks == [1, 2, 4]
    eng.warmup()
    expectation = expected_from_inventory(eng)
    assert expectation.expected["_megastep"] == len(eng.widths) * 3
    assert expectation.mismatches() == {}
    with compile_count_guard(expectation) as guard:
        eng.submit("k v")
        eng.step()
        eng.submit("a longer question about raft elections and logs")
        eng.drain()
        for prompt in ("k v", "a longer question about raft", "k v"):
            eng.submit(prompt)
        eng.drain()
    assert guard.new_compiles() == 0


def test_unwarmed_megastep_engine_fails_inventory_guard():
    from distributed_lms_raft_llm_tpu.utils.guards import RecompileError

    eng = PagedEngine(
        make_config(length_buckets=(4, 16)), slots=2, chunk=2,
        megastep=4, megastep_max=4,
    )
    with pytest.raises(RecompileError):
        with compile_count_guard(expected_from_inventory(eng)):
            eng.submit("hello")
            eng.drain()


# ------------------------------------------------------- serving queue


def test_paged_queue_reports_megastep_metrics():
    """The serving path surfaces megastep efficiency: the live K gauge,
    the host-dispatches-per-token ratio, and (when megasteps strand
    finished slots) the dead-lane counter."""
    metrics = Metrics()
    engine = PagedEngine(make_config(), slots=2, chunk=2,
                         megastep=2, megastep_max=4)

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
    assert snap["gauges"]["megastep_k"] in {
        float(k) for k in engine.megastep_ks
    }
    dpt = snap["gauges"]["host_dispatches_per_token"]
    assert 0.0 < dpt < 2.0
    assert metrics.hist("ttft").snapshot()["count"] == 4
