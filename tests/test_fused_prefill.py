"""Staged admission: chunked prefill inside the megastep scan.

Prefill compute runs inside the decode scan, one bounded chunk per
iteration, and a slot joins the train at a scan-iteration flip. Greedy
outputs through staged admission must be bit-identical to the bucketed
engine's (`TutoringEngine`, which shares nothing of admission or the
scan) at every ladder rung and any chunk budget, across plain/spec/
kv-quant/prefix-cache-hit/slot-churn configs, several prompt buckets and
cache widths. On top of exactness: warmup covers the program domain with
exact inventory equality (a live session walking admissions mid-megastep
adds zero programs), and a `prefill_chunk_tokens` below 1 is refused.
The per-slot n-gram-table drafter (`draft_source = "ngram"`) rides along:
acceptance pinned above prompt-lookup's on a temperature-0.8 workload.
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
    def test_matches_bucketed_at_every_rung(self, megastep):
        """Acceptance pin: staged admission at the ladder floor AND a
        wide rung — rung 1 included, which dispatches through the
        megastep program too — emits exactly what the bucketed
        engine emits (rung 2 rides in the churn/prefix tests below)."""
        cfg = make_config()
        expected = expected_answers(cfg, PROMPTS)
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


# ------------------------ several buckets, several widths, slots reused


LONG = "a long question about raft elections and replicated logs"
MIXED = ["k v", "what now", LONG, "hello world", LONG + "?", "k",
         "ok then?"]


@pytest.mark.parametrize("spec_tokens", [0, 2], ids=["plain", "spec"])
@pytest.mark.parametrize("budget", [3, 4, 9],
                         ids=["below", "at", "above"])
def test_buckets_widths_and_churn_at_a_budget(budget, spec_tokens):
    """Seven requests of three prompt buckets over two slots: `_stage`
    runs at every bucket, the live cache grows twice as longer prompts
    join a short one mid-decode and is rebuilt narrow when idle, slots
    are reused, and the chunk budget sits below, at and above the smallest
    bucket (4) — a whole short prompt in one chunk with a pad tail, a long
    one over many. Every stream is the bucketed engine's."""
    cfg = make_config(length_buckets=(4, 8, 16))
    expected = expected_answers(cfg, MIXED)
    eng = PagedEngine(
        make_config(length_buckets=(4, 8, 16), spec_tokens=spec_tokens),
        slots=2, chunk=2, megastep=2, megastep_max=4,
        prefill_chunk_tokens=budget)
    assert eng.prefill_chunk == budget and len(eng.widths) == 3
    staged, widths = set(), []
    real_stage = eng._stage

    def stage(state, slot, ids, *a):
        staged.add(ids.shape[1])
        return real_stage(state, slot, ids, *a)

    eng._stage = stage
    out = {}
    rids = [eng.submit(MIXED[0])]
    out.update(eng.step())
    widths.append(eng.state.cache.k.shape[3])
    rids += [eng.submit(p) for p in MIXED[1:5]]
    while eng.has_work:
        out.update(eng.step())
        widths.append(eng.state.cache.k.shape[3])
    rids += [eng.submit(p) for p in MIXED[5:]]  # idle: rebuilt narrow
    out.update(eng.step())
    widths.append(eng.state.cache.k.shape[3])
    out.update(eng.drain())
    assert [out[r] for r in rids] == expected
    assert staged == {4, 8, 16}
    # Narrow for the first, grown for the second, grown again for the
    # long one, and rebuilt at the middle width for the last two.
    assert sorted(set(widths)) == eng.widths
    assert widths == sorted(widths[:-1]) + [eng.widths[1]]


EDGE_SHORT = "the raft log"                      # 12 tokens: bucket 16
EDGE_LONG = "the raft log elects a leader"       # 28 tokens: bucket 32


@pytest.mark.parametrize("order", ["short_then_long", "long_then_short"])
def test_prefix_hit_across_a_bucket_edge(order):
    """A hit between prompts of different buckets. Short then long: three
    blocks published out of a 24-wide cache are spliced into a slot of a
    cache grown to 40 while the first request still decodes, and the
    suffix, which starts inside the short bucket and ends past it, is
    chunked in the scan. Long then short: the whole short prompt is in
    the tree, and `plan_staged` gives back its last block so that one
    token is left to compute. Answers are the bucketed engine's."""
    cfg = make_config(length_buckets=(8, 16, 32))
    first, second = ((EDGE_SHORT, EDGE_LONG) if order == "short_then_long"
                     else (EDGE_LONG, EDGE_SHORT))
    expected = expected_answers(cfg, [first, second])
    eng = PagedEngine(cfg, slots=2, chunk=2, megastep=2, megastep_max=2,
                      prefill_chunk_tokens=4, prefix_cache=True,
                      prefix_cache_blocks=64, prefix_block_tokens=4)
    out = {}
    r1 = eng.submit(first)
    while not eng.prefix_cache.blocks_used:
        out.update(eng.step())  # until the flip's reap has published
    assert eng.has_work and r1 not in out, "the first one still decodes"
    width_before = eng.state.cache.k.shape[3]
    r2 = eng.submit(second)
    out.update(eng.step())
    hit = eng.pop_prefix_hits()[r2]
    out.update(eng.drain())
    assert [out[r1], out[r2]] == expected
    if order == "short_then_long":
        assert hit == plan_staged(12, 28, 4) == 12
        assert eng.state.cache.k.shape[3] > width_before
    else:
        assert hit == plan_staged(12, 12, 4) == 8
        assert eng.state.cache.k.shape[3] == width_before


# --------------------------------------- a chunk at every scan iteration


STAGED_TOGETHER = ["what is raft?", "hello world", "explain paging",
                   "k v w x"]


@pytest.mark.parametrize("k,chunk", [(1, 16), (8, 2)],
                         ids=["one-chunk", "eight-chunks"])
@pytest.mark.parametrize("spec_tokens", [0, 2], ids=["plain", "spec"])
def test_staged_slots_are_served_every_iteration_in_stage_order(
        spec_tokens, k, chunk):
    """Four slots staged at one dispatch boundary, each needing several
    prefill chunks: ONE megastep of 16 rows serves them a pass per scan
    iteration (not per `chunk` of iterations). In the program of one chunk,
    the rung dispatched while work waits, a pass serves all four a chunk
    each (128 // 4 rows of chunks, four slots), so slot n flips at the row
    of its own last chunk, whoever else is staged; in a longer rung a pass
    serves the oldest staged slot alone, so slot n flips within the first
    sum(chunks of slots 0..n) rows. Either way every stream is still the
    bucketed engine's, and the device's counts of passes, of the
    slot-chunks they served and of the passes that found two or more
    staged (which in the longer rung served one all the same) are the hand
    count.
    """
    budget = 4
    eng = PagedEngine(
        make_config(spec_tokens=spec_tokens), slots=4, chunk=chunk,
        inflight=2, megastep=k, megastep_max=k, prefill_chunk_tokens=budget,
    )
    rs = [eng.submit(p) for p in STAGED_TOGETHER]
    eng.step()  # stage all four, dispatch one megastep, reap nothing
    (_, _, _, _, flipped, firsts, snapshot, _, served), = eng._inflight
    assert [r.rid for r in snapshot] == rs, "staged in slot = submit order"
    chunks = [-(-r.prompt_len // budget) for r in snapshot]
    assert min(chunks) > 1 and len(set(chunks)) > 1 and sum(chunks) <= 16
    # The row of each slot's flip, and the passes that ran.
    if k == 1:
        last, passes = [need - 1 for need in chunks], max(chunks)
        crowded = sorted(chunks)[-2]  # until the last but one has flipped
    else:
        last = [sum(chunks[: i + 1]) - 1 for i in range(4)]
        passes = sum(chunks)
        crowded = passes - chunks[-1]  # until the last is alone
    flipped = np.asarray(flipped)
    assert flipped.shape == (k, chunk, 4) == np.asarray(firsts).shape
    rows = flipped.reshape(-1, 4)
    for slot, row in enumerate(last):
        assert rows[:, slot].sum() == 1, "one flip per staged slot"
        assert int(np.argmax(rows[:, slot])) == row
    assert rows[passes:].sum() == 0
    assert np.asarray(served).tolist() == [passes, sum(chunks), crowded]
    out = eng.drain()
    assert [out[r] for r in rs] == expected_answers(
        make_config(), STAGED_TOGETHER)
    counts, observations, _ = eng.pop_loop_stats()
    assert observations["staged_iterations"] == [float(row) for row in last]
    assert counts["prefill_passes"] == passes
    assert counts["prefill_pass_slots"] == sum(chunks)
    assert counts["prefill_crowded_passes"] == crowded
    assert counts["prefill_crowded_narrow_passes"] == (0 if k == 1
                                                       else crowded)


# --------------------------------------------- warmup / inventory coverage


# ------------------------------------------------- stored runs, every family

# Forty tokens (ten blocks of 4) of notes and questions of eight: a prompt
# fills twelve blocks, four stored runs of three. The second prompt's
# question splits the last run (and is where a recurrent family's snapshot
# comes to stand: `_snapshot_point`), so the third splices three whole runs
# and the head of a fourth, across the split's two nodes.
NOTES = "a quorum of nodes agrees on each entry. "
RUN_PROMPTS = [NOTES + "why so? ", NOTES + "and how?", NOTES + "say more"]
RUN_FAMILIES = {
    "plain": dict(model="tiny", length_buckets=(16, 48)),
    "int8_kv": dict(model="tiny", kv_quant=True, length_buckets=(16, 48)),
    "latent_cache": dict(model="axk1-tiny", param_dtype=jnp.float32,
                         length_buckets=(16, 48)),
    "recurrent": dict(model="sala-tiny", seed=4, length_buckets=(32, 56)),
}


def run_engine(family, run_blocks, prefix_cache=True):
    return PagedEngine(
        EngineConfig(sampling=SamplingParams.greedy(max_new_tokens=MAX_NEW),
                     batch_buckets=(1, 2, 4), dtype=jnp.float32,
                     **RUN_FAMILIES[family]),
        slots=2, chunk=2, megastep=2, megastep_max=4, prefill_chunk_tokens=8,
        prefix_cache=prefix_cache, prefix_cache_blocks=64,
        prefix_block_tokens=4, stored_run_blocks=run_blocks)


@pytest.mark.parametrize("family", sorted(RUN_FAMILIES))
def test_a_hit_over_stored_runs_is_the_cold_answer_and_the_blocks_bytes(
        family, stage_last_prompt):
    """Whole runs plus the head of one: token for token what an engine
    without a tree generates, and the slot's planes (whichever the family
    has: values, int8 scales, pooled keys) bit-equal to what the splice of
    blocks resting on their own leaves."""
    assert len(NOTES) == 40
    _, _, cold = stage_last_prompt(
        run_engine(family, 0, prefix_cache=False), RUN_PROMPTS)
    planes, counts, answers = stage_last_prompt(
        run_engine(family, 3), RUN_PROMPTS)
    want, blocks, same = stage_last_prompt(run_engine(family, 0), RUN_PROMPTS)
    assert answers == same == cold
    assert counts["prefix_tokens_from_runs"] == 40
    assert "prefix_tokens_from_runs" not in blocks
    assert counts["stage_block_launches"] == 4
    # Ten blocks on their own: one by one, but for the one cache here that
    # is STAGE_RUN_BLOCKS blocks wide (64 tokens), which takes them as a run.
    assert blocks["stage_block_launches"] == (1 if family == "recurrent"
                                              else 10)
    assert planes.keys() == want.keys()
    for name in planes:
        assert np.array_equal(planes[name], want[name]), name


def test_a_reader_of_2051_blocks_is_spliced_in_a_few_launches(monkeypatch):
    """The launches of a 2,051-block hit by `engine_stage_block_launches`:
    sixteen stored runs of STORED_RUN_BLOCKS and what is left over in runs
    of STAGE_RUN_BLOCKS, where the tree before PR 55 took 129."""
    from functools import partial

    from distributed_lms_raft_llm_tpu.engine import paged
    from distributed_lms_raft_llm_tpu.engine.program_inventory import (
        STAGE_RUN_BLOCKS, STORED_RUN_BLOCKS)
    from distributed_lms_raft_llm_tpu.models import gpt2, registry

    monkeypatch.setitem(registry.PRESETS, "tiny-reader", (
        registry.PRESETS["tiny"][0],
        partial(gpt2.GPT2Config.tiny, max_position_embeddings=2200)))
    blocks, r = 2051, STORED_RUN_BLOCKS
    bound = -(-blocks // r) + r // STAGE_RUN_BLOCKS + 2
    launches = {}
    for run_blocks in (r, 0):
        eng = PagedEngine(
            EngineConfig(model="tiny-reader", batch_buckets=(1,),
                         dtype=jnp.float32, length_buckets=(2100,),
                         sampling=SamplingParams.greedy(
                             max_new_tokens=MAX_NEW)),
            slots=2, chunk=2, prefix_cache=True, prefix_cache_blocks=4096,
            prefix_block_tokens=1, stored_run_blocks=run_blocks)
        reader = np.random.default_rng(5).integers(
            0, eng.cfg.vocab_size, blocks).tolist()
        with eng.mesh:
            eng._insert_blocks(reader, eng._canon_state(eng.state).cache, 0)
        assert eng.prefix_cache.blocks_used == blocks
        eng._pending.append(paged._Request(
            rid=0, prompt_len=blocks + 9, tokens=reader + list(range(9)),
            max_new=MAX_NEW))
        eng._stage_admissions()
        counts = eng.pop_loop_stats()[0]
        assert counts["admissions"] == 1
        assert eng._prefix_hit_tokens == blocks
        launches[run_blocks] = counts["stage_block_launches"]
        assert counts.get("prefix_tokens_from_runs", 0) == (
            blocks // r * r if run_blocks else 0)
    assert launches[r] == blocks // r + 1 <= bound
    assert launches[0] == -(-blocks // STAGE_RUN_BLOCKS) > bound


def test_warmed_fused_session_passes_inventory_guard():
    """compile_count_guard(expected_from_inventory(...)): warmup compiles
    the domain — stage pairs, megasteps at EVERY rung including 1
    — and a live session walking
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
    assert expectation.expected["_stage"] == 3  # (4,12) (4,24) (16,24)
    assert set(expectation.expected) == {
        "_megastep", "_stage", "_stage_block", "_export_block", "_grow",
        "_score", "_restore_state", "_export_state", "_export_run"}
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


def test_warmed_session_over_stored_runs_passes_inventory_guard():
    """A run of 6 blocks fits the 56-wide cache and not the 16-wide one:
    after warm-up the inventory's counts are the programs' cache sizes, and
    a session that publishes a long edge as runs, splits one, splices them
    whole and in part, and hands a narrow cache a block cut out of a run
    compiles nothing."""
    eng = PagedEngine(
        make_config(length_buckets=(8, 48)), slots=2, chunk=2,
        megastep=2, megastep_max=4, prefill_chunk_tokens=4,
        prefix_cache=True, prefix_cache_blocks=64, prefix_block_tokens=4,
        stored_run_blocks=6,
    )
    eng.warmup()
    assert list(eng.widths) == [16, 56]
    expectation = expected_from_inventory(eng)
    assert expectation.expected["_stage_block"] == 2 + 1
    assert expectation.expected["_export_block"] == 2 + 1
    assert expectation.expected["_export_run"] == 1
    assert expectation.mismatches() == {}
    with compile_count_guard(expectation) as guard:
        for q in (NOTES + "why so? ", NOTES + "and how?", NOTES[:7],
                  NOTES + "say more"):
            eng.submit(q)
            eng.drain()
        # Both at once: the short prompt joins the wide cache and is handed
        # the head of the run itself.
        eng.submit(NOTES + "and then")
        eng.submit(NOTES[:7])
        eng.drain()
    assert guard.new_compiles() == 0
    counts = eng.pop_loop_stats()[0]
    assert counts["admissions"] == 6
    assert counts["prefix_tokens_from_runs"] == 40 + 40 + 40 + 4
    # "and then" shares a block of its question with "and how?".
    assert eng.pop_prefix_stats()[0] == 40 + 4 + 40 + 44 + 4


@pytest.mark.parametrize("spec_tokens", [0, 2], ids=["plain", "spec"])
@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["no_tree", "tree"])
def test_warmup_compiles_exactly_the_staged_domain(prefix_cache, spec_tokens):
    """Three buckets and three widths, the tree on and off, plain and
    speculative: after warm-up every program's cache holds exactly the
    count worked out here from the buckets and widths alone, the
    inventory says the same, and a session that stages at every bucket,
    grows the cache and hits the tree compiles nothing."""
    eng = PagedEngine(
        make_config(length_buckets=(4, 8, 16), spec_tokens=spec_tokens),
        slots=2, chunk=2, megastep=2, megastep_max=4,
        prefill_chunk_tokens=4, prefix_cache=prefix_cache,
        prefix_cache_blocks=64, prefix_block_tokens=4,
    )
    eng.warmup()
    assert eng.buckets == [4, 8, 16] and len(eng.widths) == 3
    blocks = len(eng.widths) if prefix_cache else 0
    want = {
        # A bucket is staged at its own width and at every wider one.
        "_stage": 3 + 2 + 1,
        "_megastep": len(eng.widths) * len(eng.megastep_ks),
        "_grow": 3,              # 12->16, 12->24, 16->24
        "_export_block": blocks,
        "_stage_block": blocks,  # no cache here is a run of blocks wide
        "_score": 0,
        # Snapshot programs of a family with a recurrent state alone.
        "_restore_state": 0,
        "_export_state": 0,
        # A stored run's export: no bucket here is a run long.
        "_export_run": 0,
    }
    assert {a: getattr(eng, a)._cache_size() for a in want} == want
    expectation = expected_from_inventory(eng)
    assert expectation.expected == want
    with compile_count_guard(expectation) as guard:
        for prompt in ("k v", LONG, "hello world", LONG, "what now"):
            eng.submit(prompt)
        eng.drain()
    assert guard.new_compiles() == 0
    if prefix_cache:
        assert eng.pop_prefix_stats()[0] > 0


def test_unwarmed_fused_engine_fails_inventory_guard():
    from distributed_lms_raft_llm_tpu.utils.guards import RecompileError

    eng = PagedEngine(make_config(), slots=2, chunk=2,
                      prefill_chunk_tokens=4)
    with pytest.raises(RecompileError):
        with compile_count_guard(expected_from_inventory(eng)):
            eng.submit("hello")
            eng.drain()


# ------------------------------------------------- staged planning + knobs


def test_plan_staged_block_alignment():
    assert plan_staged(16, 20, 4) == 16
    assert plan_staged(16, 16, 4) == 12   # >= 1 recomputed token
    assert plan_staged(15, 20, 4) == 12   # block-aligned down
    assert plan_staged(3, 20, 4) == 0     # under one block: cold
    assert plan_staged(0, 20, 4) == 0


@pytest.mark.parametrize("tokens", [0, -4])
def test_a_chunk_budget_below_one_is_refused(tokens):
    """`prefill_chunk_tokens` is a size: there is no admission path that
    0 would select."""
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        PagedEngine(make_config(), slots=2, prefill_chunk_tokens=tokens)


def test_tutoring_config_refuses_a_chunk_budget_below_one(tmp_path):
    from distributed_lms_raft_llm_tpu.config import (
        TutoringConfig, load_config)

    assert TutoringConfig().prefill_chunk_tokens == 32
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        TutoringConfig(prefill_chunk_tokens=0)
    toml = tmp_path / "zero.toml"
    toml.write_text("[tutoring]\nprefill_chunk_tokens = 0\n")
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        load_config(str(toml))


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
