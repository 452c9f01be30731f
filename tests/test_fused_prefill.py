"""Stall-free admission: chunked prefill fused into the megastep scan.

The fusion changes WHERE prefill compute runs (inside the decode scan,
one bounded chunk per iteration) and WHEN a slot joins the train (at a
scan-iteration flip instead of a dispatch-boundary install) — never WHAT
the device computes. Greedy outputs through fused staged admission must
be bit-identical to the sequential prefill-then-decode engine at every
ladder rung and any chunk budget, across plain/spec/kv-quant/
prefix-cache-hit/slot-churn configs. On top of exactness: warmup covers
the fused program domain with exact inventory equality (a live session
walking admissions mid-megastep adds zero programs), the decode train
records ZERO stalled tokens under fused admission while the sequential
path records them (the PR's before/after number), and the K controller
holds K >= 2 under a non-empty pending queue. The per-slot n-gram-table
drafter (`draft_source = "ngram"`) rides along: acceptance pinned above
prompt-lookup's on a temperature-0.8 workload.
"""

import asyncio

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
from distributed_lms_raft_llm_tpu.engine.prefix_cache import plan_staged
from distributed_lms_raft_llm_tpu.utils.guards import (
    compile_count_guard,
    expected_from_inventory,
)
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

MAX_NEW = 8

PROMPTS = ["what is raft?", "hello world", "explain paging", "k"]

SHARED = "the raft consensus algorithm elects a leader and replicates a log"


def make_config(**kw):
    kw.setdefault("sampling", SamplingParams.greedy(max_new_tokens=MAX_NEW))
    kw.setdefault("length_buckets", (16,))
    return EngineConfig(
        model="tiny",
        batch_buckets=(1, 2, 4),
        dtype=jnp.float32,
        **kw,
    )


_EXPECTED_CACHE = {}


def expected_answers(cfg, prompts):
    """Bucketed-engine reference stream, memoized per (config, prompts):
    several tests pin against the same reference, and a TutoringEngine
    build is the expensive part of each."""
    key = (repr(cfg), tuple(prompts))
    if key not in _EXPECTED_CACHE:
        _EXPECTED_CACHE[key] = TutoringEngine(cfg).answer_batch(
            list(prompts)
        )
    return _EXPECTED_CACHE[key]


# ------------------------------------------------------- greedy bit-equality


class TestGreedyBitEquality:
    @pytest.mark.parametrize("megastep", [1, 4])
    def test_matches_sequential_at_every_rung(self, megastep):
        """Acceptance pin: fused admission at the ladder floor AND a
        wide rung — rung 1 included, where the fused engine still
        dispatches through the megastep program — emits exactly what the
        sequential prefill-then-decode paged engine and the bucketed
        engine emit (rung 2 rides in the churn/prefix tests below)."""
        cfg = make_config()
        expected = expected_answers(cfg, PROMPTS)
        # (sequential-paged == bucketed at these rungs is test_megastep's
        # pin; here the fused engine closes the triangle.)
        fused = PagedEngine(cfg, slots=4, chunk=2, megastep=megastep,
                            megastep_max=megastep, prefill_chunk_tokens=4)
        fr = [fused.submit(p) for p in PROMPTS]
        out_fused = fused.drain()
        assert [out_fused[r] for r in fr] == expected

    @pytest.mark.parametrize("prefill_chunk", [1, 3])
    def test_any_chunk_budget(self, prefill_chunk):
        """The chunk budget moves how many scan iterations a prompt's
        prefill spans (one position at a time at 1; multi-chunk with a
        final-chunk pad overshoot at 3) — never the emitted stream.
        (The whole-prompt-in-one-chunk shape is the rung tests' budget
        of 4 over shorter prompts.)"""
        cfg = make_config()
        expected = expected_answers(cfg, PROMPTS)
        eng = PagedEngine(cfg, slots=4, chunk=2, megastep=2,
                          megastep_max=4,
                          prefill_chunk_tokens=prefill_chunk)
        rs = [eng.submit(p) for p in PROMPTS]
        out = eng.drain()
        assert [out[r] for r in rs] == expected

    @pytest.mark.parametrize("spec_tokens", [1, 3])
    def test_spec_mode(self, spec_tokens):
        """Fused admission x speculation: staged slots flip into verify
        windows (drafts from the transcript the stage seeded) and still
        match the non-spec engines bit for bit."""
        expected = expected_answers(make_config(), PROMPTS)
        eng = PagedEngine(
            make_config(spec_tokens=spec_tokens), slots=4, chunk=2,
            megastep=4, megastep_max=4, prefill_chunk_tokens=3,
        )
        rs = [eng.submit(p) for p in PROMPTS]
        out = eng.drain()
        assert [out[r] for r in rs] == expected
        windows, emitted = eng.pop_spec_stats()
        assert windows > 0
        assert windows <= emitted <= windows * (spec_tokens + 1)

    def test_kv_quant(self):
        cfg = make_config(kv_quant=True)
        expected = TutoringEngine(cfg).answer_batch(list(PROMPTS[:2]))
        eng = PagedEngine(cfg, slots=2, chunk=2, megastep=4,
                          megastep_max=4, prefill_chunk_tokens=4)
        rs = [eng.submit(p) for p in PROMPTS[:2]]
        out = eng.drain()
        assert [out[r] for r in rs] == expected

    def test_slot_churn_and_prompt_buckets(self):
        """5 requests over 2 slots with mixed prompt buckets: stagings
        land as slots free, prefills of different lengths interleave
        with live decode inside the same megasteps, and every stream
        still matches the bucketed engine."""
        cfg = make_config(length_buckets=(4, 8, 16))
        prompts = list(PROMPTS) + ["k v"]
        expected = TutoringEngine(cfg).answer_batch(prompts)
        eng = PagedEngine(cfg, slots=2, chunk=2, megastep=2,
                          megastep_max=4, prefill_chunk_tokens=3)
        rs = [eng.submit(p) for p in prompts]
        out = eng.drain()
        assert [out[r] for r in rs] == expected

    def test_prefix_cache_hit(self):
        """Fused staged admission composes with the radix cache: a hit
        splices blocks straight into the slot's pages (`_stage_block`)
        and only the uncached suffix is chunked — warm output
        bit-identical to cold, both bit-identical to the bucketed
        engine."""
        cfg = make_config(length_buckets=(8, 16, 32))
        q1, q2 = SHARED + " why?", SHARED + " how?"
        expected = TutoringEngine(cfg).answer_batch([q1, q2])
        eng = PagedEngine(cfg, slots=2, chunk=2, megastep=2,
                          megastep_max=4, prefill_chunk_tokens=4,
                          prefix_cache=True, prefix_cache_blocks=64,
                          prefix_block_tokens=4)
        r1 = eng.submit(q1)
        o1 = eng.drain()
        r2 = eng.submit(q2)
        o2 = eng.drain()
        assert [o1[r1], o2[r2]] == expected
        hit, _total, _ev, _blocks = eng.pop_prefix_stats()
        assert hit > 0, "the second request must splice cached blocks"
        # The staged planner keeps hits block-aligned (no suffix-bucket
        # fitting to give blocks back).
        assert hit % 4 == 0

    @pytest.mark.parametrize("kv_quant", [False, True],
                             ids=["plain", "kv_quant"])
    def test_prefix_cache_hit_spliced_as_a_run(self, kv_quant):
        """A hit goes into the slot's pages a run of blocks a
        `_stage_block` call: a short run is padded to STAGE_RUN_BLOCKS
        with its last block, and only the hit's tokens are written (every
        plane, the int8 cache's scale planes too). Warm output equals
        cold and the bucketed engine's."""
        from distributed_lms_raft_llm_tpu.engine.program_inventory import (
            STAGE_RUN_BLOCKS)
        cfg = make_config(length_buckets=(8, 16, 32), kv_quant=kv_quant)
        q1, q2 = SHARED + " why?", SHARED + " how?"
        expected = TutoringEngine(cfg).answer_batch([q1, q2])
        eng = PagedEngine(cfg, slots=2, chunk=2, megastep=2,
                          megastep_max=4, prefill_chunk_tokens=4,
                          prefix_cache=True, prefix_cache_blocks=64,
                          prefix_block_tokens=1)
        assert STAGE_RUN_BLOCKS <= eng.widths[-1]
        r1 = eng.submit(q1)
        o1 = eng.drain()
        eng._progs.pop()
        r2 = eng.submit(q2)
        o2 = eng.drain()
        assert [o1[r1], o2[r2]] == expected
        hit = eng.pop_prefix_stats()[0]
        splices = sum(name == "stage_block"
                      for name, _, _ in eng._progs.pop())
        assert hit % STAGE_RUN_BLOCKS >= 2     # the last run is a short one
        assert splices == -(-hit // STAGE_RUN_BLOCKS)

    def test_pipelined_matches_serialized(self):
        """inflight=2 with staged admission: flips are learned one reap
        late, snapshots carry staged requests across dispatches, and the
        answers stay byte-identical to the serialized engine."""
        cfg = make_config()
        ser = PagedEngine(cfg, slots=2, chunk=2, inflight=1, megastep=4,
                          megastep_max=4, prefill_chunk_tokens=4)
        rs = [ser.submit(p) for p in PROMPTS]
        out_ser = ser.drain()
        pipe = PagedEngine(cfg, slots=2, chunk=2, inflight=2, megastep=4,
                           megastep_max=4, prefill_chunk_tokens=4)
        rp = [pipe.submit(p) for p in PROMPTS]
        out_pipe = pipe.drain()
        assert [out_pipe[r] for r in rp] == [out_ser[r] for r in rs]


# --------------------------------------- a chunk at every scan iteration


STAGED_TOGETHER = ["what is raft?", "hello world", "explain paging",
                   "k v w x"]


@pytest.mark.parametrize("spec_tokens", [0, 2], ids=["plain", "spec"])
def test_staged_slots_are_served_every_iteration_in_stage_order(spec_tokens):
    """Four slots staged at one dispatch boundary, each needing several
    prefill chunks: ONE megastep serves them a chunk per scan iteration
    (not per `chunk` of iterations), oldest staging first, so slot n
    flips within the first sum(chunks of slots 0..n) rows of the
    `flipped` plane — and every stream is still the sequential path's.
    """
    budget = 4
    eng = PagedEngine(
        make_config(spec_tokens=spec_tokens), slots=4, chunk=2, inflight=2,
        megastep=8, megastep_max=8, prefill_chunk_tokens=budget,
    )
    rs = [eng.submit(p) for p in STAGED_TOGETHER]
    eng.step()  # stage all four, dispatch one K=8 megastep, reap nothing
    (_, _, _, _, flipped, firsts, snapshot, _), = eng._inflight
    assert [r.rid for r in snapshot] == rs, "staged in slot = submit order"
    chunks = [-(-r.prompt_len // budget) for r in snapshot]
    assert min(chunks) > 1 and sum(chunks) <= 8 * 2
    flipped = np.asarray(flipped)
    assert flipped.shape == (8, 2, 4) == np.asarray(firsts).shape
    rows = flipped.reshape(-1, 4)
    served = 0
    for slot, need in enumerate(chunks):
        assert rows[:, slot].sum() == 1, "one flip per staged slot"
        served += need
        # FIFO, one chunk an iteration: its last chunk is row served-1.
        assert int(np.argmax(rows[:, slot])) == served - 1
    assert rows[served:].sum() == 0
    out = eng.drain()
    seq = PagedEngine(make_config(), slots=4, chunk=2, megastep=8,
                      megastep_max=8)
    sr = [seq.submit(p) for p in STAGED_TOGETHER]
    expected = seq.drain()
    assert [out[r] for r in rs] == [expected[r] for r in sr]
    _, observations = eng.pop_loop_stats()
    assert observations["staged_iterations"] == [
        float(sum(chunks[: i + 1]) - 1) for i in range(4)
    ]


# ------------------------------------------------- stall-free acceptance


def _churn(engine):
    """A mid-decode arrival: A is admitted and decoding when B and C
    arrive, so their admissions happen under a LIVE train — the exact
    scenario sequential admission pays a full prefill stall for and
    staged admission absorbs into the scan."""
    engine.submit("a long question about distributed consensus and logs")
    for _ in range(2):
        engine.step()  # A live, mid-decode
    engine.submit("b second question")
    engine.submit("c third question")
    engine.drain()
    return engine.pop_dispatch_stats()


def test_sequential_admission_stalls_fused_does_not():
    """THE before/after number: a request arriving mid-decode pauses the
    sequential engine's live decode train for its prefill (stalled
    tokens + stall wall accrue); the fused engine records ZERO decode
    stall for the identical workload, and its K controller never drops
    to the chunk loop while requests wait."""
    cfg = make_config()
    _, _, _, stall_ms, stalled = _churn(
        PagedEngine(cfg, slots=2, chunk=2, megastep=2, megastep_max=2)
    )
    assert stalled > 0, "sequential admission under churn must stall decode"
    assert stall_ms > 0

    _, _, _, stall_ms, stalled = _churn(
        PagedEngine(cfg, slots=2, chunk=2, megastep=2, megastep_max=2,
                    prefill_chunk_tokens=4)
    )
    assert stalled == 0, "fused staged admission must never pause decode"
    assert stall_ms == 0

    # Saturation: K stays wide (>= 2) the whole time a backlog waits.
    fused = PagedEngine(cfg, slots=2, chunk=2, megastep=4,
                        megastep_max=4, prefill_chunk_tokens=4)
    ks = []
    for i in range(8):
        fused.submit(f"question number {i}")
    while fused.has_work:
        fused.step()
        if fused._pending:
            ks.append(fused.megastep_k)
    _, _, _, stall_ms, stalled = fused.pop_dispatch_stats()
    assert stalled == 0 and stall_ms == 0
    assert ks and min(ks) >= 2, "K must stay wide while admissions drain"


# --------------------------------------------- warmup / inventory coverage


def test_warmed_fused_session_passes_inventory_guard():
    """compile_count_guard(expected_from_inventory(...)): warmup compiles
    the fused domain — stage pairs, megasteps at EVERY rung including 1,
    zero sequential admission programs — and a live session walking
    admissions mid-megastep, churning slots, and growing the cache adds
    ZERO programs."""
    eng = PagedEngine(
        make_config(length_buckets=(4, 16)), slots=2, chunk=2,
        megastep=2, megastep_max=4, prefill_chunk_tokens=3,
    )
    eng.warmup()
    expectation = expected_from_inventory(eng)
    dom_widths = len(eng.widths)
    assert expectation.expected["_megastep"] == dom_widths * 3  # rungs 1,2,4
    assert expectation.expected["_step"] == 0
    assert expectation.expected["_prefill"] == 0
    assert expectation.expected["_install"] == 0
    assert expectation.expected["_stage"] > 0
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


def test_warmed_fused_prefix_session_passes_inventory_guard():
    """Fused + shared-prefix: block export moves to the live cache and
    `_stage_block` splices per width; hits, misses, publishes, and
    evictions mid-session add zero programs."""
    eng = PagedEngine(
        make_config(length_buckets=(8, 16, 32)), slots=2, chunk=2,
        megastep=2, megastep_max=4, prefill_chunk_tokens=4,
        prefix_cache=True, prefix_cache_blocks=64, prefix_block_tokens=4,
    )
    eng.warmup()
    expectation = expected_from_inventory(eng)
    assert expectation.expected["_stage_block"] == len(eng.widths)
    assert expectation.expected["_export_block"] == len(eng.widths)
    assert expectation.expected["_load_block"] == 0
    assert expectation.expected["_partial_prefill"] == 0
    assert expectation.mismatches() == {}
    with compile_count_guard(expectation) as guard:
        eng.submit(SHARED + " why?")
        eng.drain()
        for q in (SHARED + " how?", "short q", SHARED + " when?"):
            eng.submit(q)
        eng.drain()
    assert guard.new_compiles() == 0
    hit, total, _ev, _blocks = eng.pop_prefix_stats()
    assert hit > 0


def test_unwarmed_fused_engine_fails_inventory_guard():
    from distributed_lms_raft_llm_tpu.utils.guards import RecompileError

    eng = PagedEngine(make_config(), slots=2, chunk=2,
                      prefill_chunk_tokens=4)
    with pytest.raises(RecompileError):
        with compile_count_guard(expected_from_inventory(eng)):
            eng.submit("hello")
            eng.drain()


# ------------------------------------------------------- serving queue


class _StallingStubEngine:
    """Paged-protocol stub whose dispatch stats report a known admission
    stall: pins the PagedQueue emission path deterministically (driving
    a real engine into a mid-decode arrival from the queue is a timing
    race on CPU)."""

    def __init__(self):
        self._work = []
        self._rid = 0

    def submit(self, prompt):
        self._rid += 1
        self._work.append((self._rid, prompt))
        return self._rid

    @property
    def has_work(self):
        return bool(self._work)

    backlog = 0

    def step(self):
        done, self._work = self._work[:1], self._work[1:]
        return [(rid, f"answer to {p}") for rid, p in done]

    def pop_ttfts(self):
        return {}

    def pop_dispatch_stats(self):
        return (3, 10, 0, 12.5, 4)


def test_paged_queue_reports_stall_metrics():
    """The serving path surfaces the admission-stall series from
    `pop_dispatch_stats()`: prefill_stall_ms and decode_stalled_tokens
    counters when the engine reports a blocking admission, and neither
    (zero) from a fused engine's real run."""

    async def run(q, n):
        await q.start()
        answers = await asyncio.gather(
            *[q.submit(f"query number {i}") for i in range(n)]
        )
        await q.close()
        return answers

    metrics = Metrics()
    answers = asyncio.run(run(PagedQueue(_StallingStubEngine(),
                                         metrics=metrics), 2))
    assert len(answers) == 2
    snap = metrics.snapshot()
    assert snap["counters"].get("decode_stalled_tokens", 0) > 0
    assert snap["counters"].get("prefill_stall_ms", 0) > 0

    fused_metrics = Metrics()
    fused = PagedEngine(make_config(), slots=2, chunk=2,
                        prefill_chunk_tokens=4)
    answers = asyncio.run(run(PagedQueue(fused, metrics=fused_metrics), 6))
    assert len(answers) == 6
    snap = fused_metrics.snapshot()
    assert snap["counters"].get("decode_stalled_tokens", 0) == 0
    assert snap["counters"].get("prefill_stall_ms", 0) == 0
    assert fused_metrics.hist("ttft").snapshot()["count"] == 6


# ------------------------------------------------- staged planning + knobs


def test_plan_staged_block_alignment():
    assert plan_staged(16, 20, 4) == 16
    assert plan_staged(16, 16, 4) == 12   # >= 1 recomputed token
    assert plan_staged(15, 20, 4) == 12   # block-aligned down
    assert plan_staged(3, 20, 4) == 0     # under one block: cold
    assert plan_staged(0, 20, 4) == 0


def test_draft_source_validation():
    with pytest.raises(ValueError, match="draft_source"):
        PagedEngine(make_config(draft_source="nope"), slots=2)
    with pytest.raises(ValueError, match="paged-engine"):
        TutoringEngine(make_config(spec_tokens=2, draft_source="ngram"))


def test_fused_spec_requires_decode_headroom():
    with pytest.raises(ValueError, match="max_new_tokens >= 2"):
        PagedEngine(
            make_config(
                spec_tokens=2,
                sampling=SamplingParams.greedy(max_new_tokens=1),
            ),
            slots=2, prefill_chunk_tokens=4,
        )


# ------------------------------------------------- n-gram table drafter


def test_ngram_drafter_beats_prompt_lookup_at_temperature():
    """Satellite pin: at temperature 0.8, the per-slot n-gram TABLE
    drafter (modal continuation of the current context) accepts more
    tokens per verify window than prompt-lookup (most recent
    continuation) on a repetitive tutoring-style workload — the regime
    prompt-lookup was built for greedy streams and loses at temp>0."""
    # Workload shape matters: the separation lives in MODEL-SAMPLED
    # history (where the most recent continuation is a random draw but
    # the modal one tracks the distribution), so short prompts + long
    # generations; top_k=2 keeps the random-weight tiny model's
    # processed support peaked enough that drafts CAN be accepted (the
    # full 50k-vocab distribution of an untrained model is near-uniform
    # — acceptance ~0 for every drafter, no signal). Everything is
    # seeded: same submission order, same rng split sequence per
    # drafter, deterministic on CPU.
    sampling = SamplingParams(temperature=0.8, top_k=2, top_p=1.0,
                              repetition_penalty=1.0, max_new_tokens=56)
    base = dict(
        sampling=sampling, length_buckets=(64,), spec_tokens=3,
        batch_buckets=(1, 2, 4, 8), model="tiny", dtype=jnp.float32,
    )
    prompts = [f"q{i} the cat" for i in range(8)]

    def acceptance(source):
        eng = PagedEngine(
            EngineConfig(draft_source=source, **base),
            slots=4, chunk=2, prefill_chunk_tokens=8,
        )
        for p in prompts:
            eng.submit(p)
        eng.drain()
        windows, emitted = eng.pop_spec_stats()
        assert windows > 100, "need a real window population"
        return emitted / windows

    lookup = acceptance("prompt_lookup")
    ngram = acceptance("ngram")
    assert ngram > lookup, (
        f"ngram acceptance {ngram:.3f} must beat prompt_lookup "
        f"{lookup:.3f} at temperature 0.8"
    )
