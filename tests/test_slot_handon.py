"""A slot is handed on as soon as its answer's end is certain.

Fused admission stages the next pending request into a slot at the first
`step()` at which the slot's request is certain to have had its last token
inside the dispatches already in flight (`rows_to_certain_end` == 0), and
no longer two dispatches after that end has been reaped. The slot then
carries two requests at once: the departing one lives on in the in-flight
snapshots until `_walk` finishes it, the successor sits in `_slot_req`.
Pinned here: greedy streams stay the bucketed engine's, request by
request and to the last token; the departing request keeps streaming; the
kill of a finished slot never hits a successor; a session turn's slot
waits for its reap; speculative windows and eos endings fall under the
same test; and the lane account still sums.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    SamplingParams,
    TutoringEngine,
)
from distributed_lms_raft_llm_tpu.engine import paged as paged_mod
from distributed_lms_raft_llm_tpu.utils import metrics_registry

PROMPTS = ["what is raft?", "hello world", "explain paging", "k",
           "why a log?", "who leads?", "a b c d", "terms and votes"]


def make_config(max_new, **kw):
    return EngineConfig(
        model="tiny",
        sampling=SamplingParams.greedy(max_new_tokens=max_new),
        length_buckets=(16,),
        batch_buckets=(1, 2, 4),
        dtype=jnp.float32,
        **kw,
    )


def fused_engine(max_new, spec_tokens=0, **kw):
    """The shipped shape at tiny size: three dispatches in flight, K = 2
    (4 rows a dispatch), fused admission, two slots."""
    return PagedEngine(make_config(max_new, spec_tokens=spec_tokens),
                       slots=2, chunk=2, inflight=3, megastep=2,
                       megastep_max=2, prefill_chunk_tokens=4, **kw)


_BUCKETED = {}


def bucketed_tokens(max_new, prompts, memo=True):
    """Every prompt's token list (eos filtered, as `pop_final_tokens`
    gives them) from the bucketed engine, which shares nothing of
    admission, the scan or the hand-on; memoized per budget."""
    key = (max_new, tuple(prompts))
    if memo and key in _BUCKETED:
        return _BUCKETED[key]
    eng = TutoringEngine(make_config(max_new))
    cap = max(eng.config.batch_buckets)
    out = []
    for i in range(0, len(prompts), cap):
        group = prompts[i:i + cap]
        ids, mask, _ = eng.encode_prompts(group)
        res = eng.generate_ids(ids, mask, real_rows=len(group))
        toks, lengths = np.asarray(res.tokens), np.asarray(res.lengths)
        out += [[t for t in toks[j, :lengths[j]].tolist()
                 if t != eng.tokenizer.eos_id] for j in range(len(group))]
    if memo:
        _BUCKETED[key] = out
    return out


def departing(eng):
    """slot -> request whose slot has been handed on and whose end is not
    reaped yet: in an in-flight snapshot, no longer in `_slot_req`."""
    out = {}
    for entry in eng._inflight:
        for slot, req in enumerate(entry[6]):
            if (req is not None and not req.finished
                    and eng._slot_req[slot] is not req):
                out[slot] = req
    return out


def rows_in_flight(eng, slot, req):
    return sum(entry[2].shape[0] * eng.chunk for entry in eng._inflight
               if entry[6][slot] is req)


def run(eng, prompts, each_step=None):
    """Submit, step to the end, and return (rids, token lists, counts)."""
    rids = [eng.submit(p) for p in prompts]
    for rid in rids:
        eng.stream_watch(rid)
    texts = {}
    while eng.has_work:
        for rid, text in eng.step():
            texts[rid] = text
        if each_step is not None:
            each_step(eng)
    finals = eng.pop_final_tokens()
    assert [texts[r] for r in rids] == [
        eng.decode_tokens(finals[r]) for r in rids]
    counts = eng.pop_loop_stats()[0]
    return rids, [finals[r] for r in rids], counts


# ------------------------------------------------ (a) greedy bit-equality


@pytest.mark.parametrize("max_new", [4, 8, 16, 24])
def test_greedy_streams_equal_the_bucketed_engines(max_new):
    """More requests than slots, inflight 3, K = 2: every request's tokens
    are the bucketed engine's and each has exactly its budget. At a
    budget of 4, one dispatch's rows, an answer that flips early ends
    inside its first dispatch and is never live on the host in time, and
    one that flips late is handed on at its first reap: the same test
    decides, not a second path."""
    expected = bucketed_tokens(max_new, PROMPTS)
    assert all(len(t) == max_new for t in expected), "no eos at tiny size"
    _, got, counts = run(fused_engine(max_new), PROMPTS)
    assert got == expected
    handed = counts.get("slots_handed_on", 0)
    assert handed <= len(PROMPTS) - 2  # the last answers have no successor
    assert handed > 0 or max_new == 4


# ------------------------------- (b) two requests in one slot, and the kill


def test_successor_is_staged_before_the_reap_and_outlives_it():
    """The successor takes the slot while its predecessor's end is
    unreaped (`engine_slots_handed_on` counts it); the predecessor then
    finishes with all its tokens, and the kill of a finished slot does not
    fire on a lane that is the successor's by then."""
    max_new = 16
    eng = fused_engine(max_new)
    seen = {}  # departing rid -> (slot, successor, tokens when handed on)
    outlived = []

    def watch(eng):
        now = departing(eng)
        for slot, req in now.items():
            succ = eng._slot_req[slot]
            assert succ is not None and not succ.finished
            seen.setdefault(req.rid, (slot, succ, len(req.tokens)))
        for rid, (slot, succ, _) in seen.items():
            if rid in outlived or any(r.rid == rid for r in now.values()):
                continue
            # The reap that finished the predecessor has just run: the
            # successor still holds the lane on the device, prefilling or
            # decoding (unless it has had its own whole answer since).
            outlived.append(rid)
            if eng._slot_req[slot] is succ and not succ.finished:
                state = eng.state
                assert bool(state.staged[slot]) or bool(state.active[slot])

    _, got, counts = run(eng, PROMPTS, each_step=watch)
    assert got == bucketed_tokens(max_new, PROMPTS)
    assert counts["slots_handed_on"] == len(seen) > 0
    assert sorted(outlived) == sorted(seen)
    # Handed on with tokens still to come: the end really was unreaped.
    assert all(n < max_new for _, _, n in seen.values())
    assert metrics_registry.ENGINE_LOOP_COUNTERS["slots_handed_on"] == (
        "engine_slots_handed_on")
    assert metrics_registry.is_declared("engine_slots_handed_on")


def test_a_handed_on_finish_launches_no_kill():
    """`_stage_program` has reset a handed-on lane, so its predecessor's
    finish replaces no plane of the live state: one small launch fewer a
    request."""
    eng = fused_engine(16)
    killed = []
    real = eng._walk

    def walk(*a):
        before = eng.state.active
        before_slots = list(eng._slot_req)
        done = real(*a)
        snapshot = a[-1]
        for slot, req in enumerate(snapshot):
            if req is not None and any(r == req.rid for r, _ in done):
                killed.append((before_slots[slot] is req,
                               eng.state.active is not before))
        return done

    eng._walk = walk
    run(eng, PROMPTS)
    assert (True, True) in killed      # in its own slot: killed as before
    handed_on = [k for own, k in killed if not own]
    assert handed_on and not any(handed_on)


def test_the_k_controller_still_sees_the_backlog_a_hand_on_took(monkeypatch):
    """A successor staged early no longer waits in `_pending`, but work
    WAS waiting for that slot: until its predecessor's end is reaped the
    controller counts it, as it did when the successor waited for that
    reap, so an emptied queue does not grow K over ends still in flight."""
    seen = []  # (backlog the controller was given, pending, departing)
    real = paged_mod.next_megastep_k

    def spy(current, ladder, pending, slack):
        seen.append((pending, len(eng._pending), len(eng._departing())))
        return real(current, ladder, pending, slack)

    monkeypatch.setattr(paged_mod, "next_megastep_k", spy)
    eng = PagedEngine(make_config(16), slots=2, chunk=2, inflight=3,
                      megastep=2, megastep_max=8, prefill_chunk_tokens=4)
    ks = []
    rids = [eng.submit(p) for p in PROMPTS[:4]]
    while eng.has_work:
        eng.step()
        if eng._departing():
            ks.append(eng.megastep_k)
    assert rids and all(b == p + d for b, p, d in seen)
    # The queue was empty with ends still in flight, and K did not grow.
    assert any(p == 0 and d > 0 for _, p, d in seen)
    assert ks and max(ks) <= 2


# ------------------------------------------------------ (c) session turns


def test_a_session_turn_keeps_its_slot_until_its_reap():
    """A session request publishes its transcript from the slot's pages
    when its end is reaped, so its slot is never handed on; the other
    slot's requests are, in the same run."""
    max_new = 16
    eng = fused_engine(max_new, prefix_cache=True, prefix_cache_blocks=64,
                       prefix_block_tokens=4)
    rids = [eng.submit(p) for p in PROMPTS[:6]]
    session = rids[0]
    assert eng.mark_session(session, "sess", 30.0)
    gone = []
    while eng.has_work:
        eng.step()
        gone += [r.rid for r in departing(eng).values()]
    assert gone and session not in gone
    counts = eng.pop_loop_stats()[0]
    assert counts["slots_handed_on"] == len(set(gone))
    assert eng.session_pin_stats()[0] == 1, "transcript published and pinned"
    assert not eng._session_reqs


# ----------------------------------------------------- (d) stream channel


def test_stream_snapshot_follows_a_departing_request_to_its_end():
    """A watched request whose slot has been handed on is still found, in
    the in-flight snapshots, with its tokens so far: its last chunks do
    not wait for the final one."""
    max_new = 16
    eng = fused_engine(max_new)
    in_slot = {}  # rid -> tokens streamed while the request held its slot
    gone = {}     # rid -> tokens streamed once its slot was handed on

    def watch(eng):
        for req in eng._slot_req:
            if req is not None and req.live and not req.finished:
                in_slot[req.rid] = len(eng.stream_snapshot([req.rid])[req.rid])
        for req in departing(eng).values():
            snap = eng.stream_snapshot([req.rid])
            assert snap == {req.rid: list(req.tokens)}
            gone[req.rid] = len(snap[req.rid])

    run(eng, PROMPTS, each_step=watch)
    assert gone
    # The reap of the step that handed the slot on brought more tokens,
    # and the snapshot shows them though the request is in no slot.
    assert all(in_slot[rid] < n < max_new for rid, n in gone.items())


# ----------------------------------- (e) speculative windows, eos endings


def assert_no_early_hand_on(eng):
    """Whatever has left its slot unfinished has its end in flight: its
    tokens on the host and the rows dispatched for it reach its budget
    (a verify window gives at least one token, so in speculative mode the
    sum can only grow at a reap)."""
    for slot, req in departing(eng).items():
        assert req.live
        assert (len(req.tokens) + rows_in_flight(eng, slot, req)
                >= req.max_new)


@pytest.mark.parametrize("spec_tokens", [1, 3])
def test_speculative_windows_fall_under_the_same_test(spec_tokens):
    max_new = 16
    eng = fused_engine(max_new, spec_tokens=spec_tokens)
    _, got, counts = run(eng, PROMPTS, each_step=assert_no_early_hand_on)
    assert got == bucketed_tokens(max_new, PROMPTS)
    assert counts.get("slots_handed_on", 0) > 0


def test_an_eos_ending_comes_sooner_and_changes_nothing(monkeypatch):
    """With a token of the greedy streams declared eos, answers end early
    and at different lengths: a request is handed on only by its budget,
    may then end by eos inside the work in flight, and every stream still
    equals the bucketed engine's."""
    max_new = 16
    plain = bucketed_tokens(max_new, PROMPTS)
    eos = plain[0][6]
    load = paged_mod.tok_lib.load_gpt2_tokenizer

    def with_eos(*a, **kw):
        tok = load(*a, **kw)
        tok.eos_id = eos
        return tok

    monkeypatch.setattr(paged_mod.tok_lib, "load_gpt2_tokenizer", with_eos)
    expected = bucketed_tokens(max_new, PROMPTS, memo=False)
    assert any(len(t) < max_new for t in expected), "some answer ends by eos"
    assert any(len(t) == max_new for t in expected), "and some by budget"
    eng = fused_engine(max_new)
    _, got, counts = run(eng, PROMPTS, each_step=assert_no_early_hand_on)
    assert got == expected
    assert counts.get("slots_handed_on", 0) > 0


# ----------------------------------------------------- (f) the lane account


def test_lane_counters_sum_and_the_overrun_shrinks(monkeypatch):
    """decode + staged + overrun + dead lane-steps fit the lanes' budget
    over a run with hand-ons, and against the same engine with the slots
    waiting for their reaps the answers need fewer scan iterations and
    leave fewer overrun lane-steps."""
    max_new, slots, n = 16, 2, len(PROMPTS)

    def account(eng):
        _, got, c = run(eng, PROMPTS)
        _, emitted, dead = eng.pop_dispatch_stats()
        assert emitted == n * max_new
        decode = emitted - n  # first tokens are the prefill's
        parts = (decode + dead + c["staged_lane_steps"]
                 + c["overrun_lane_steps"])
        assert c["lane_steps"] == c["scan_iterations"] * slots
        assert 0 < parts <= c["lane_steps"]
        return got, c

    got, with_hand_on = account(fused_engine(max_new))
    monkeypatch.setattr(PagedEngine, "_end_in_flight",
                        lambda self, slot: False)
    waited, waiting = account(fused_engine(max_new))
    assert got == waited
    assert "slots_handed_on" not in waiting
    assert with_hand_on["slots_handed_on"] >= n - slots - 1
    assert with_hand_on["overrun_lane_steps"] < waiting["overrun_lane_steps"]
    assert with_hand_on["scan_iterations"] < waiting["scan_iterations"]
    # A handed-on request overruns the rest of ONE dispatch at most.
    rows = 2 * 2
    handed = with_hand_on["slots_handed_on"]
    assert with_hand_on["overrun_lane_steps"] <= (
        handed * (rows - 1) + (n - handed) * 3 * rows)


# ------------------------------------- (g) a dispatch ends where an answer ends


def second_rung_floor(real):
    """`next_megastep_k` as it was before the floor fell to one chunk:
    under a backlog never below the ladder's second rung."""
    def floored(current, ladder, pending, slack):
        k = real(current, ladder, pending, slack)
        return max(k, ladder[1]) if pending > 0 and len(ladder) > 1 else k
    return floored


@pytest.mark.parametrize("chunk", [2, 4])
def test_under_a_backlog_an_answer_overruns_less_than_a_chunk(
        monkeypatch, chunk):
    """Answers of a whole multiple of 2 x chunk rows and more requests
    than slots: while work waits, the controller ends a dispatch in the
    chunk in which the nearest answer ends (K = 1,
    `engine_one_chunk_dispatches`), so a request decodes less than one
    chunk's rows past its last token; floored at the second rung, as the
    controller was, it decodes up to two. The tokens are the same."""
    max_new, n = 4 * chunk, len(PROMPTS)

    def account(eng):
        rids = [eng.submit(p) for p in PROMPTS]
        for rid in rids:
            eng.stream_watch(rid)
        ended = 0
        while eng._pending:  # the standing backlog
            ended += len(eng.step())
        standing = eng.pop_loop_stats()[0]
        while eng.has_work:
            eng.step()
        finals = eng.pop_final_tokens()
        return [finals[r] for r in rids], standing, ended

    def engine():
        return PagedEngine(make_config(max_new), slots=2, chunk=chunk,
                           inflight=2, megastep=2, megastep_max=4,
                           prefill_chunk_tokens=4)

    got, counts, ended = account(engine())
    assert got == bucketed_tokens(max_new, PROMPTS)
    assert ended >= n - 4
    assert counts["one_chunk_dispatches"] > 0
    assert counts["overrun_lane_steps"] <= ended * (chunk - 1)
    assert metrics_registry.ENGINE_LOOP_COUNTERS["one_chunk_dispatches"] == (
        "engine_one_chunk_dispatches")
    assert metrics_registry.is_declared("engine_one_chunk_dispatches")

    monkeypatch.setattr(paged_mod, "next_megastep_k",
                        second_rung_floor(paged_mod.next_megastep_k))
    floored, old, old_ended = account(engine())
    assert floored == got
    assert "one_chunk_dispatches" not in old
    assert old["overrun_lane_steps"] > old_ended * (chunk - 1)
    assert old["scan_iterations"] > counts["scan_iterations"]
