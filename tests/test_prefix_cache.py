"""Radix shared-prefix KV cache: prefill each course context once.

The cache changes WHERE prompt KV comes from, never WHAT the device
computes: a cache-hit generation must equal the cold-prefill generation
token for token, and both the bucketed engine's, across every engine
configuration (plain, speculative, kv-quant, megastep, megastep+spec). On top of exactness: the radix
tree's structure (longest-prefix lookup, insert-with-split, LRU
eviction) is pinned at the unit level, eviction under pressure never
frees a block a live slot references (ref-count pin), slot churn with
interleaved hits and misses stays correct, a hit whose suffix crosses a
prompt-bucket edge splices into a wider cache,
the serving queue surfaces the new hit-rate/eviction/blocks gauges, and
the sim workload's same-course concentration knob produces the
deterministic shared prefixes the cache targets.
"""

import asyncio

import jax.numpy as jnp
import pytest

from distributed_lms_raft_llm_tpu.config import SimConfig
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    PagedQueue,
    SamplingParams,
    TutoringEngine,
)
from distributed_lms_raft_llm_tpu.engine.prefix_cache import (
    PrefixCache,
    RunBlock,
    splice_pieces,
)
from distributed_lms_raft_llm_tpu.sim import workload as wl
from distributed_lms_raft_llm_tpu.sim.slo import evaluate_slos
from distributed_lms_raft_llm_tpu.utils.guards import (
    compile_count_guard,
    expected_from_inventory,
)
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

MAX_NEW = 8
BLOCK = 4

# A shared course context long enough to span several 4-token blocks
# (byte-fallback tokenizer on the tiny model: ~1 token per character),
# with distinct per-student suffixes — the same-course workload shape.
CTX = "the raft leader election protocol works by "
PROMPTS = [
    CTX + "choosing a leader",
    CTX + "replicating a log",
    "what is paging?",
    CTX + "electing nodes",
]


def make_config(**kw):
    kw.setdefault("sampling", SamplingParams.greedy(max_new_tokens=MAX_NEW))
    kw.setdefault("length_buckets", (16, 32))
    return EngineConfig(
        model="tiny",
        batch_buckets=(1, 2, 4),
        dtype=jnp.float32,
        **kw,
    )


def bucketed(cfg, prompts):
    """The reference: the bucketed engine's answers (no slots, no staged
    admission, no tree). Speculation is exact, so its plain decode
    stands for a speculative config too."""
    import dataclasses

    return TutoringEngine(
        dataclasses.replace(cfg, spec_tokens=0)).answer_batch(list(prompts))


def make_engine(cfg=None, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 2)
    kw.setdefault("prefix_cache", True)
    kw.setdefault("prefix_cache_blocks", 64)
    kw.setdefault("prefix_block_tokens", BLOCK)
    return PagedEngine(cfg if cfg is not None else make_config(), **kw)


# --------------------------------------------------------- radix tree unit


def ints(n, start=0):
    return list(range(start, start + n))


def test_tree_lookup_insert_and_partial_hit():
    pc = PrefixCache(block_tokens=4, max_blocks=64)
    toks = ints(17)  # 4 full blocks + 1 spare token
    added = pc.insert(toks[:16], lambda i: f"blk{i}")
    assert added == 4 and pc.blocks_used == 4
    # Full-prompt lookup is usable-capped at len-1: 16 cached tokens but
    # only 3 blocks (12 tokens) are usable for an identical 16-token
    # prompt (the last position's logits must be recomputed).
    m = pc.lookup(toks[:16])
    assert m.tokens == 12
    # A longer prompt sharing the prefix uses all 4 blocks.
    m = pc.lookup(toks + ints(8, 100))
    assert m.tokens == 16
    assert m.blocks() == ["blk0", "blk1", "blk2", "blk3"]
    # Divergence mid-path: only the shared whole blocks match.
    m = pc.lookup(ints(8) + ints(12, 500))
    assert m.tokens == 8
    assert m.blocks() == ["blk0", "blk1"]
    # No hit at all.
    assert pc.lookup(ints(12, 900)).tokens == 0


def test_tree_insert_splits_and_dedups():
    pc = PrefixCache(block_tokens=2, max_blocks=64)
    pc.insert(ints(8), lambda i: ("a", i))
    # Shares 2 blocks then diverges: the shared edge must split, the new
    # tail gets fresh blocks, and NOTHING already cached is re-made.
    made = []

    def mk(i):
        made.append(i)
        return ("b", i)

    added = pc.insert(ints(4) + ints(6, 50), mk)
    assert added == 3 and made == [2, 3, 4]
    assert pc.blocks_used == 7
    # Both branches still fully resolvable after the split.
    assert pc.lookup(ints(8) + [99]).tokens == 8
    assert pc.lookup(ints(4) + ints(6, 50) + [99]).tokens == 10
    # Re-inserting an exact existing prefix adds nothing.
    assert pc.insert(ints(8), mk) == 0


def test_tree_lru_eviction_and_refcount_pin():
    pc = PrefixCache(block_tokens=2, max_blocks=4)
    pc.insert(ints(4), lambda i: ("a", i))         # 2 blocks
    pc.insert(ints(4, 100), lambda i: ("b", i))    # 2 blocks
    pin = pc.lookup(ints(4) + [9])                 # touch + pin branch a
    pc.acquire(pin)
    # Pressure: a third branch overruns the budget. The LRU unpinned
    # leaf (branch b) must go; the pinned branch a must survive even
    # though it is older than c.
    pc.insert(ints(4, 200), lambda i: ("c", i))
    freed = pc.evict_to_budget()
    assert freed == 2 and pc.blocks_used == 4
    assert pc.lookup(ints(4) + [9]).tokens == 4        # a survived
    assert pc.lookup(ints(4, 100) + [9]).tokens == 0   # b evicted
    # Everything pinned => budget overruns rather than freeing live
    # blocks.
    pin_c = pc.lookup(ints(4, 200) + [9])
    pc.acquire(pin_c)
    pc.insert(ints(4, 300), lambda i: ("d", i))
    pin_d = pc.lookup(ints(4, 300) + [9])
    pc.acquire(pin_d)
    assert pc.evict_to_budget() == 0 and pc.blocks_used == 6
    # Releasing makes the LRU leaf evictable again.
    pc.release(pin)
    assert pc.evict_to_budget() == 2 and pc.blocks_used == 4
    assert pc.evicted_blocks == 4  # cumulative


def test_tree_split_keeps_pin_on_deep_node():
    """A pinned node that later splits keeps its refcount on the deep
    (tail) node; the new upper node is protected structurally by having
    a child — no split may strand a pinned path evictable."""
    pc = PrefixCache(block_tokens=2, max_blocks=2)
    pc.insert(ints(8), lambda i: ("a", i))
    pin = pc.lookup(ints(8) + [9])
    pc.acquire(pin)
    pc.insert(ints(4) + ints(4, 50), lambda i: ("b", i))  # forces split
    # Budget 2 is far exceeded (6 blocks), but branch a's tail is pinned
    # and interior nodes have children: only branch b may go.
    pc.evict_to_budget()
    assert pc.lookup(ints(8) + [9]).tokens == 8


# ------------------------------------------- stored runs: what a node holds


def run_tree(run_blocks=4, **kw):
    """A tree of 2-token blocks whose runs and blocks say what they are:
    a run is ("run", name, first block), a block ("blk", name, block)."""
    pc = PrefixCache(block_tokens=2, run_blocks=run_blocks, **kw)

    def publish(tokens, name):
        return pc.insert(tokens, lambda i: ("blk", name, i),
                         lambda i: ("run", name, i))

    return pc, publish


def held(pc):
    """Blocks the tree's nodes hold, counted node by node."""
    return sum(len(n.blocks) for n in pc._iter_nodes())


def test_a_long_edge_rests_as_runs_and_its_remainder_as_blocks():
    pc, publish = run_tree()
    assert publish(ints(22), "a") == 11 and pc.blocks_used == 11
    blocks = pc.lookup(ints(22) + [999]).blocks()
    assert [b.index for b in blocks[:8]] == [0, 1, 2, 3] * 2
    assert all(b.run == ("run", "a", 0) for b in blocks[:4])
    assert all(b.run == ("run", "a", 4) for b in blocks[4:8])
    assert blocks[8:] == [("blk", "a", i) for i in (8, 9, 10)]
    # A splice takes them as two runs and one stretch of blocks.
    assert splice_pieces(blocks) == [
        (0, 4, ("run", "a", 0)), (4, 4, ("run", "a", 4)), (8, 3, None)]
    # An edge shorter than a run rests as blocks, as it did.
    assert publish(ints(6, 500), "s") == 3
    assert pc.lookup(ints(6, 500) + [9]).blocks() == [
        ("blk", "s", i) for i in range(3)]
    # And a tree told of no run length never asks for one.
    off, publish_off = run_tree(run_blocks=0)
    publish_off(ints(22), "a")
    assert not any(isinstance(b, RunBlock)
                   for b in off.lookup(ints(22) + [9]).blocks())


def test_runs_count_from_the_edges_own_start():
    """Case 1: a second course shares the first block alone, so its edge
    starts at block 1: its runs are blocks 1-4 and 5-8, not 4-7."""
    pc, publish = run_tree()
    publish(ints(18), "a")
    assert publish(ints(2) + ints(18, 100), "b") == 9
    pieces = splice_pieces(pc.lookup(ints(2) + ints(18, 100) + [9]).blocks())
    assert pieces == [(0, 1, ("run", "a", 0)), (1, 4, ("run", "b", 1)),
                      (5, 4, ("run", "b", 5)), (9, 1, None)]
    assert pc.blocks_used == held(pc) == 18


def test_a_split_inside_a_run_leaves_both_nodes_the_one_array():
    """Case 2: a new question diverges inside a run. Tree arrays are
    immutable: the head and the tail keep their entries of the SAME run,
    nothing is cut, and a later hit over both is one piece again."""
    pc, publish = run_tree()
    publish(ints(16), "a")
    publish(ints(12) + ints(4, 70), "q")       # diverges at block 6 of 8
    match = pc.lookup(ints(16) + [9])
    assert [len(n.blocks) for n in match.nodes] == [6, 2]
    top, tail = match.nodes
    assert top.blocks[4].run is tail.blocks[0].run
    assert [b.index for b in top.blocks[4:] + tail.blocks] == [0, 1, 2, 3]
    assert splice_pieces(match.blocks()) == [
        (0, 4, ("run", "a", 0)), (4, 4, ("run", "a", 4))]
    # The other branch reads the head of the shared run and its own blocks.
    assert splice_pieces(pc.lookup(ints(12) + ints(4, 70) + [9]).blocks()) \
        == [(0, 4, ("run", "a", 0)), (4, 2, ("run", "a", 4)), (6, 2, None)]
    assert pc.blocks_used == held(pc) == 10


def test_a_hit_that_ends_inside_a_run_is_the_runs_head():
    """Case 3: `plan_staged` or a snapshot cuts a hit inside a run; the
    piece says how many of the run's blocks count."""
    pc, publish = run_tree()
    publish(ints(16), "a")
    blocks = pc.lookup(ints(16) + [9]).blocks()
    assert splice_pieces(blocks[:7]) == [
        (0, 4, ("run", "a", 0)), (4, 3, ("run", "a", 4))]
    assert splice_pieces(blocks[:1]) == [(0, 1, ("run", "a", 0))]
    assert splice_pieces([]) == []
    # Entries that do not start their run are never taken for one.
    assert splice_pieces(blocks[2:6]) == [(0, 2, None),
                                          (2, 2, ("run", "a", 4))]


def test_evicting_beside_a_shared_run_frees_nothing_the_other_reads():
    """Case 5: the tail of a split run is evicted; the head's entries
    still point at the run, and `blocks_used` is what the nodes hold."""
    pc, publish = run_tree(max_blocks=8)
    publish(ints(16), "a")
    publish(ints(12) + ints(4, 70), "q")
    assert pc.blocks_used == held(pc) == 10
    pc.lookup(ints(12) + ints(4, 70) + [9])    # the question is the newer
    assert pc.evict_to_budget() == 2           # a's tail: blocks 6 and 7
    assert pc.blocks_used == held(pc) == 8
    match = pc.lookup(ints(16) + [9])
    assert match.tokens == 12
    assert splice_pieces(match.blocks()) == [
        (0, 4, ("run", "a", 0)), (4, 2, ("run", "a", 4))]
    # The next over-budget leaf to go is the question's, then the shared
    # head: every count is a node's entries.
    pc.max_blocks = 1
    assert pc.evict_to_budget() == 8 and pc.blocks_used == held(pc) == 0


# ----------------------------- stored runs through the engine: the slot's planes

# 40 tokens (ten blocks of 4) of one course and of another, and questions of
# 8, so that a prompt fills the 48 bucket's twelve blocks.
RUN_A = "the raft leader election works by votes "
RUN_B = "a paged cache keeps every block in place"
assert len(RUN_A) == len(RUN_B) == 40
HEAD = "abcd"

# What is served first, then the admission whose splice is looked at; blocks
# in a stored run; the tokens that admission's hit holds, and how many of them
# stored runs brought.
RUN_SCENARIOS = {
    # Case 1: both courses share the first block, so the second course's
    # edge starts at block 1 and its runs are blocks 1-3, 4-6, 7-9.
    "edge_starts_inside_a_span": (
        [HEAD + RUN_A[:36] + "why so? ", HEAD + RUN_B[:36] + "and how?",
         HEAD + RUN_B[:36] + "say more"], 3, 40, 40),
    # Case 2: the second prompt diverges at block 8, inside the run 6-8;
    # the third reads that run across the split's two nodes.
    "split_inside_a_run": (
        [RUN_A + "why so? ", RUN_A[:34] + "with terms and", RUN_A + "say more"],
        3, 40, 40),
    # Case 3: seven blocks match, the last the head of the run 6-8.
    "hit_ends_inside_a_run": (
        [RUN_A + "why so? ", RUN_A[:30] + "something else now"], 3, 28, 28),
    # Case 4: the 8 bucket's cache is 16 tokens wide and a run 20: the
    # program would clamp its start, so the block is cut out of the run.
    "cache_too_narrow_for_a_run": (
        [RUN_A + "why so? ", RUN_A[:7]], 5, 4, 0),
}


def run_engine(run_blocks):
    return make_engine(make_config(length_buckets=(8, 48)),
                       stored_run_blocks=run_blocks)


@pytest.mark.parametrize("scenario", sorted(RUN_SCENARIOS))
def test_a_stored_runs_splice_leaves_the_planes_the_blocks_did(
        scenario, stage_last_prompt):
    import numpy as np

    prompts, run_blocks, hit, from_runs = RUN_SCENARIOS[scenario]
    eng, off = run_engine(run_blocks), run_engine(0)
    planes, counts, answers = stage_last_prompt(eng, prompts)
    assert answers == bucketed(make_config(length_buckets=(8, 48)), prompts)
    assert eng.stored_run_blocks == run_blocks
    assert eng._prefix_hit_tokens >= hit
    assert counts.get("prefix_tokens_from_runs", 0) == from_runs
    # The same traffic with every block resting on its own: the parent's
    # splice. Same bytes in every plane, fewer launches.
    want, blocks, same = stage_last_prompt(off, prompts)
    assert off.stored_run_blocks == 0 and same == answers
    assert "prefix_tokens_from_runs" not in blocks
    assert planes.keys() == want.keys()
    for name in planes:
        assert np.array_equal(planes[name], want[name]), name
    assert counts["admissions"] == blocks["admissions"] == 1
    if from_runs:
        assert counts["stage_block_launches"] <= -(-hit // (4 * run_blocks)) + 1
    assert eng.prefix_cache.blocks_used == off.prefix_cache.blocks_used == sum(
        len(n.blocks) for n in eng.prefix_cache._iter_nodes())


# ------------------------------------------------------- greedy bit-equality


class TestCacheHitBitEquality:
    def _assert_two_passes_match(self, eng, prompts, expected):
        """Pass 1 seeds the tree (later same-course requests already
        hit); pass 2 is fully warm. Both must equal the bucketed
        engine."""
        for pass_no in (1, 2):
            rids = [eng.submit(p) for p in prompts]
            out = eng.drain()
            assert [out[r] for r in rids] == expected, f"pass {pass_no}"
        hit, total, _ev, _blocks = eng.pop_prefix_stats()
        assert 0 < hit < total

    def test_plain_matches_cold_and_bucketed(self):
        cfg = make_config()
        expected = bucketed(cfg, PROMPTS)
        cold = make_engine(cfg, prefix_cache=False)
        rids = [cold.submit(p) for p in PROMPTS]
        out = cold.drain()
        assert [out[r] for r in rids] == expected
        self._assert_two_passes_match(make_engine(cfg), PROMPTS, expected)

    @pytest.mark.parametrize("spec_tokens", [2])
    def test_spec_mode(self, spec_tokens):
        cfg = make_config(spec_tokens=spec_tokens)
        expected = bucketed(cfg, PROMPTS)
        self._assert_two_passes_match(make_engine(cfg), PROMPTS, expected)

    def test_kv_quant(self):
        cfg = make_config(kv_quant=True)
        expected = bucketed(cfg, PROMPTS)
        self._assert_two_passes_match(make_engine(cfg), PROMPTS, expected)

    def test_megastep(self):
        cfg = make_config()
        expected = bucketed(cfg, PROMPTS)
        eng = make_engine(cfg, megastep=4, megastep_max=4)
        self._assert_two_passes_match(eng, PROMPTS, expected)

    def test_megastep_with_spec(self):
        cfg = make_config(spec_tokens=2)
        expected = bucketed(cfg, PROMPTS)
        eng = make_engine(cfg, megastep=4, megastep_max=4)
        self._assert_two_passes_match(eng, PROMPTS, expected)


def test_slot_churn_interleaved_hits_and_misses():
    """More requests than slots, hits and misses interleaved: every
    stream must match the bucketed engine while the tree is being
    built, hit, split, and re-hit under churn."""
    cfg = make_config()
    prompts = [
        CTX + "choosing a leader",
        "completely unrelated question",
        CTX + "replicating a log entry",
        "another cold miss here",
        CTX + "choosing a leader",          # exact repeat: deep hit
        CTX + "counting votes",
        "what is paging?",
        CTX + "replicating a log entry",    # repeat again
    ]
    eng = make_engine(cfg)
    re_ = [eng.submit(p) for p in prompts]
    out = eng.drain()
    assert [out[a] for a in re_] == bucketed(cfg, prompts)
    hits = eng.pop_prefix_hits()
    assert len(hits) == len(prompts)
    assert any(v > 0 for v in hits.values())
    assert any(v == 0 for v in hits.values())


def test_eviction_under_pressure_keeps_live_pins_and_stays_exact():
    """A tiny block budget under heavy distinct-prefix churn: evictions
    happen, pinned (in-flight) paths are never freed, and outputs still
    equal the bucketed engine's."""
    cfg = make_config()
    # Budget = ONE prompt's blocks: every distinct publish overruns and
    # evicts; adjacent repeats hit (and pin) before churn can evict them.
    prompts = [f"unique course context number {i} question" for i in range(3)]
    prompts += [PROMPTS[0], PROMPTS[0]]
    prompts += [f"more cold churn number {i} ok" for i in range(3)]
    prompts += [PROMPTS[1], PROMPTS[1]]
    eng = make_engine(cfg, prefix_cache_blocks=8)
    re_ = [eng.submit(p) for p in prompts]
    # Step (not drain) so we can observe live pins mid-flight.
    saw_pin = False
    out = {}
    while eng.has_work:
        for rid, text in eng.step():
            out[rid] = text
        for pin in eng._prefix_pins.values():
            saw_pin = True
            # The pinned path's deepest node must still be reachable in
            # the tree (eviction never freed a live slot's blocks).
            assert pin.nodes[-1].refs > 0
    assert [out[a] for a in re_] == bucketed(cfg, prompts)
    assert saw_pin
    assert not eng._prefix_pins  # all released at completion
    hit, total, evicted, blocks_used = eng.pop_prefix_stats()
    assert evicted > 0
    assert hit > 0


def test_reset_releases_pins_but_keeps_tree():
    eng = make_engine()
    eng.submit(PROMPTS[0])
    while not eng.prefix_cache.blocks_used:
        eng.step()  # staged, then flipped: the flip's reap publishes
    assert eng.has_work, "reset with the request still in its slot"
    blocks_before = eng.prefix_cache.blocks_used
    assert blocks_before > 0
    eng.reset()
    assert not eng._prefix_pins
    assert all(
        n.refs == 0 for n in eng.prefix_cache._iter_nodes()
    )
    # Tree blocks were never donated: the cache survives an engine reset.
    assert eng.prefix_cache.blocks_used == blocks_before
    rid = eng.submit(PROMPTS[0])
    out = eng.drain()
    assert out[rid]
    assert eng.pop_prefix_stats()[0] > 0  # re-hit after reset


# ------------------------------------------------- compile-once acceptance


def test_disabled_prefix_cache_expects_zero_programs():
    """With the cache off, the block export/splice wrappers exist but
    their expected (and actual) program counts are zero — the manifest
    stays exact in both modes."""
    eng = PagedEngine(make_config(length_buckets=(8,)), slots=2, chunk=2)
    eng.warmup()
    expectation = expected_from_inventory(eng)
    assert expectation.expected["_export_block"] == 0
    assert expectation.expected["_stage_block"] == 0
    assert expectation.mismatches() == {}


# ------------------------------------------------------------ serving queue


def test_paged_queue_reports_prefix_metrics():
    metrics = Metrics()
    engine = make_engine()

    async def run():
        q = PagedQueue(engine, metrics=metrics)
        await q.start()
        answers = await asyncio.gather(
            *[q.submit(p) for p in PROMPTS],
            *[q.submit(p) for p in PROMPTS],
        )
        await q.close()
        return answers

    answers = asyncio.run(run())
    assert len(answers) == 2 * len(PROMPTS)
    snap = metrics.snapshot()
    assert snap["counters"]["prefix_cache_hit_tokens"] > 0
    assert 0.0 < snap["gauges"]["prefix_cache_hit_rate"] < 1.0
    assert snap["gauges"]["prefix_cache_blocks_used"] > 0


# ------------------------------------------------------------- sim workload


def sim_cfg(**kw):
    kw.setdefault("seed", 7)
    kw.setdefault("students", 12)
    kw.setdefault("courses", 3)
    kw.setdefault("duration_s", 5.0)
    kw.setdefault("base_rate", 20.0)
    return SimConfig(**kw)


def test_concentration_zero_keeps_legacy_assignment_and_bare_prompts():
    gen = wl.WorkloadGenerator(sim_cfg(course_concentration=0.0))
    ops = gen.ops()
    asks = [o for o in ops if o.kind == wl.ASK_LLM_ON_TOPIC]
    assert asks
    assert all(o.payload["query"] in wl.ON_TOPIC_QUERIES for o in asks)
    # Legacy hash spread: with 12 students over 3 courses, more than one
    # course sees traffic.
    assert len({o.course for o in ops}) > 1


def test_concentration_shares_course_prefixes_deterministically():
    cfg = sim_cfg(course_concentration=0.6)
    gen = wl.WorkloadGenerator(cfg)
    ops = gen.ops()
    asks = [o for o in ops if o.kind == wl.ASK_LLM_ON_TOPIC]
    assert asks
    for o in asks:
        prefix = gen.course_context(o.course)
        assert o.payload["query"].startswith(prefix)
        assert o.payload["query"][len(prefix):] in wl.ON_TOPIC_QUERIES
    # Off-topic asks stay bare so the relevance gate still discriminates.
    for o in ops:
        if o.kind == wl.ASK_LLM_OFF_TOPIC:
            assert o.payload["query"] in wl.OFF_TOPIC_QUERIES
    # Deterministic: same seed, same trace (prefixes included).
    assert wl.trace_digest(ops) == wl.trace_digest(
        wl.WorkloadGenerator(cfg).ops()
    )


def test_concentration_skews_and_saturates():
    base = sim_cfg(course_concentration=0.0)
    skew = sim_cfg(course_concentration=0.9)
    full = sim_cfg(course_concentration=1.0)
    students = [f"student{i:03d}" for i in range(64)]

    def share0(cfg):
        gen = wl.WorkloadGenerator(cfg)
        return sum(
            1 for s in students if gen.course_of(s) == "course0"
        ) / len(students)

    assert share0(full) == 1.0
    assert share0(skew) > share0(base)


def test_slo_verdict_carries_prefix_hit_rate():
    report = evaluate_slos(
        sim_cfg(), node_metrics={}, node_health={}, sim_metrics={},
        ledger_report={"losses": [], "ryw_violations": [],
                       "acked_writes": 0},
        tutoring_metrics={"gauges": {"prefix_cache_hit_rate": 0.42}},
    )
    assert report.prefix_cache_hit_rate == 0.42
    assert report.to_dict()["prefix_cache_hit_rate"] == 0.42
    # Absent engine => carried as None, never fabricated.
    report = evaluate_slos(
        sim_cfg(), node_metrics={}, node_health={}, sim_metrics={},
        ledger_report={"losses": [], "ryw_violations": [],
                       "acked_writes": 0},
        tutoring_metrics={},
    )
    assert report.prefix_cache_hit_rate is None
