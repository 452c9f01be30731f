"""The background bulk-scoring tenant (engine/scoring.py + the queue
co-scheduler in engine/batcher.py).

Claims pinned here:

- score numerics are pad/batch-invariant: per-text logprobs are equal
  batched-vs-singleton across batch AND length buckets, equal to a
  log-softmax by hand, and unchanged by chunking an oversized group;
- `score()` reports truncation per item (and the manager counts it in
  `score_truncated_texts`) instead of silently scoring prefixes;
- the score program is a first-class inventoried program: a warmed
  scoring-enabled session runs a bulk job with ZERO live compiles and
  `expected_from_inventory` exact equality holds;
- the co-scheduler admits quanta only while nothing interactive is
  pending, and an interactive request arriving mid-quantum waits at most
  ONE quantum before its prefill dispatches — measured and recorded as
  `score_preempt_wait_ms`;
- the fleet router's background route places bulk jobs OFF the hot
  affinity nodes.
"""

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    PagedQueue,
    SamplingParams,
    ScoringManager,
)
from distributed_lms_raft_llm_tpu.engine.scoring import score_admin_get
from distributed_lms_raft_llm_tpu.utils.guards import (
    compile_count_guard,
    expected_from_inventory,
)
from distributed_lms_raft_llm_tpu.sim.cluster import EchoEngine
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics


def tiny_paged(**kw):
    kw.setdefault("model", "tiny")
    kw.setdefault("sampling", SamplingParams.greedy(max_new_tokens=4))
    kw.setdefault("length_buckets", (4, 16))
    kw.setdefault("batch_buckets", (1, 2))
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("scoring", True)
    return PagedEngine(EngineConfig(**kw), slots=2, chunk=2)


def tiny_scorer(**kw):
    """A served engine for `score` alone, in float32 weights: nothing is
    warmed or generated."""
    kw.setdefault("length_buckets", (16, 32))
    kw.setdefault("param_dtype", jnp.float32)
    kw.setdefault("scoring", False)
    return tiny_paged(**kw)


# ---------------------------------------------------------- numerics


class TestScoreNumerics:
    def test_batched_equals_singleton_across_buckets(self):
        """Pad invariance: a text's logprob must not depend on which
        (batch, length) bucket its companions forced it into."""
        eng = tiny_scorer()
        texts = [
            "a",                                     # 16-bucket, short
            "the raft consensus algorithm elects a leader and "
            "replicates a log across the cluster members",  # 32-bucket
            "a quorum is a majority of the members",
            "logs",
        ]
        batched = eng.score(texts)  # mixed lengths -> widest bucket
        for text, got in zip(texts, batched):
            [alone] = eng.score([text])  # smallest admissible buckets
            assert alone["tokens"] == got["tokens"]
            np.testing.assert_allclose(got["logprob"], alone["logprob"],
                                       rtol=1e-4, atol=1e-4)

    def test_matches_manual_log_softmax(self):
        eng = tiny_scorer()
        text = "raft elects a leader"  # fits the 32-token bucket
        [res] = eng.score([text])
        toks = eng.tokenizer.encode(text)
        logits, _ = eng.family.forward(
            eng.params, eng.cfg, jnp.asarray([toks], jnp.int32)
        )
        logp = jax.nn.log_softmax(
            jnp.asarray(logits[0], jnp.float32), axis=-1
        )
        want = float(sum(
            logp[i, toks[i + 1]] for i in range(len(toks) - 1)
        ))
        assert res["tokens"] == len(toks) - 1
        np.testing.assert_allclose(res["logprob"], want, rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(
            res["ppl"], float(np.exp(-want / (len(toks) - 1))), rtol=1e-4
        )

    def test_moe_scores(self):
        eng = tiny_scorer(model="moe-tiny")
        [res] = eng.score(["hello experts"])
        assert res["tokens"] >= 1 and np.isfinite(res["ppl"])

    def test_oversized_group_chunks(self):
        # More texts than the largest batch bucket run as several device
        # batches, order preserved.
        eng = tiny_scorer()
        texts = [f"text number {i}" for i in range(5)]  # cap is 2
        res = eng.score(texts)
        assert len(res) == 5
        # Chunking must not change any individual score.
        [alone] = eng.score([texts[3]])
        np.testing.assert_allclose(res[3]["logprob"], alone["logprob"],
                                   rtol=1e-4, atol=1e-4)

    def test_truncated_flag_marks_prefix_scores(self):
        eng = tiny_scorer(length_buckets=(8,))
        long_text = " ".join(["raft"] * 30)
        short_text = "raft"  # under the 8-token bucket in any tokenizer
        res = eng.score([short_text, long_text])
        assert res[0]["truncated"] is False
        assert res[1]["truncated"] is True
        # The truncated score really is the prefix's score.
        limit_toks = eng.tokenizer.encode(long_text)[:8]
        [prefix] = eng.score([eng.tokenizer.decode(limit_toks)])
        assert prefix["tokens"] == res[1]["tokens"]
        np.testing.assert_allclose(res[1]["logprob"], prefix["logprob"],
                                   rtol=1e-4, atol=1e-4)


# ------------------------------------------------- inventory / compiles


class TestScoreInventory:
    def test_warmed_paged_scoring_session_zero_live_compiles(self):
        """The acceptance path: scoring enabled, warmup covers the score
        domain, `expected_from_inventory` exact equality holds, and a
        live session interleaving generation and a bulk score job adds
        ZERO programs."""
        eng = tiny_paged()
        eng.warmup()
        expectation = expected_from_inventory(eng)
        assert expectation.mismatches() == {}
        assert expectation.expected["_score"] == len(eng.score_shapes) > 0
        with compile_count_guard(expectation) as guard:
            eng.submit("what is raft?")
            eng.drain()
            eng.score(["the leader replicates logs", "a quorum votes",
                       "terms increase monotonically"])  # > one quantum
        assert guard.new_compiles() == 0

    def test_paged_without_scoring_expects_zero_score_programs(self):
        eng = tiny_paged(scoring=False)
        eng.warmup()
        expectation = expected_from_inventory(eng)
        assert expectation.expected["_score"] == 0
        assert expectation.mismatches() == {}


# ------------------------------------------------------ the job manager


class SlowScoreEngine(EchoEngine):
    """The sim's double with a scoring quantum of its own: a wall the
    co-scheduler timing tests choose, a quantum that can be made to
    fail, and a truncation flag to count."""

    score_batch_cap = 2

    def __init__(self, quantum_s: float = 0.0, fail_at: int = -1):
        super().__init__(delay_s=0.0, answer="ans:{}".format)
        self.quantum_s = quantum_s
        self.fail_at = fail_at
        self.calls = 0

    def score(self, texts):
        self.calls += 1
        if self.calls == self.fail_at:
            raise RuntimeError("injected score failure")
        if self.quantum_s:
            time.sleep(self.quantum_s)
        return [
            {"logprob": -2.0 * max(1, len(t.split())),
             "tokens": max(1, len(t.split())), "ppl": 7.389,
             "truncated": t.startswith("LONG")}
            for t in texts
        ]


class TestScoringManager:
    def test_jobs_chunk_resume_and_complete(self):
        metrics = Metrics()
        mgr = ScoringManager(SlowScoreEngine(), metrics=metrics)
        job = mgr.submit(["a b", "c", "d e f", "g", "LONG x"],
                         purpose="grading", job_id="j1")
        assert job["status"] == "queued" and job["texts"] == 5
        # Idempotent: a retried POST returns the same job, no re-queue.
        again = mgr.submit(["ignored"], job_id="j1")
        assert again["job_id"] == "j1" and again["texts"] == 5
        quanta = 0
        while mgr.has_work:
            assert mgr.run_quantum()
            quanta += 1
        assert quanta == 3  # ceil(5 / cap 2)
        detail = mgr.job("j1")
        assert detail["status"] == "done"
        assert len(detail["results"]) == 5
        assert detail["truncated_texts"] == 1
        snap = metrics.snapshot()["counters"]
        assert snap["scoring_quanta"] == 3
        assert snap["scoring_jobs_completed"] == 1
        assert snap["score_truncated_texts"] == 1
        assert snap["scoring_scored_tokens"] == detail["scored_tokens"] > 0
        assert not mgr.run_quantum()  # drained

    def test_job_failure_fails_the_job_not_the_tenant(self):
        metrics = Metrics()
        mgr = ScoringManager(SlowScoreEngine(fail_at=1), metrics=metrics)
        mgr.submit(["a", "b"], job_id="bad")
        mgr.submit(["c"], job_id="good")
        assert mgr.run_quantum()      # fails the first job internally
        assert mgr.job("bad")["status"] == "failed"
        while mgr.has_work:
            mgr.run_quantum()
        assert mgr.job("good")["status"] == "done"
        snap = metrics.snapshot()["counters"]
        assert snap["scoring_jobs_failed"] == 1
        assert snap["scoring_jobs_completed"] == 1

    def test_admission_caps_and_validation(self):
        mgr = ScoringManager(SlowScoreEngine(), max_job_texts=3)
        with pytest.raises(ValueError, match="admission cap"):
            mgr.submit(["x"] * 4)
        with pytest.raises(ValueError, match="non-empty"):
            mgr.submit(["", "  "])

    def test_admin_get_surface(self):
        mgr = ScoringManager(SlowScoreEngine())
        mgr.submit(["a"], job_id="jj")
        doc = score_admin_get("/admin/score", mgr)
        assert doc["ok"] and doc["jobs"][0]["job_id"] == "jj"
        assert doc["stats"]["backlog_texts"] == 1
        got = score_admin_get("/admin/score/jj", mgr)
        assert got["status"] == "queued" and got["results"] is None
        with pytest.raises(KeyError):
            score_admin_get("/admin/score/nope", mgr)
        with pytest.raises(KeyError):
            score_admin_get("/admin/score", None)  # tenant disabled


# ------------------------------------------------- queue co-scheduling


class TestCoScheduling:
    def test_preemption_wait_bounded_by_one_quantum(self):
        """Satellite pin: an interactive request arriving mid-quantum
        dispatches after at most ONE quantum, and the wait is recorded in
        score_preempt_wait_ms."""
        async def run():
            metrics = Metrics()
            eng = SlowScoreEngine(quantum_s=0.4)
            scorer = ScoringManager(eng, metrics=metrics)
            q = PagedQueue(eng, metrics=metrics, scorer=scorer)
            await q.start()
            scorer.submit(["t one", "t two", "t three", "t four"])
            await asyncio.sleep(0.1)  # first quantum is in flight
            t0 = time.monotonic()
            answer = await q.submit("hello")
            wait_s = time.monotonic() - t0
            while not scorer.done():
                await asyncio.sleep(0.01)
            await q.close()
            return answer, wait_s, metrics.snapshot(), scorer, q

        answer, wait_s, snap, scorer, q = asyncio.run(run())
        assert answer == "ans:hello"
        # Arrived ~0.1 s into a 0.4 s quantum: served after that quantum
        # finishes, never after the whole job.
        assert wait_s < 0.4 + 0.35, f"waited {wait_s:.3f}s"
        assert snap["counters"]["score_preempt_wait_ms"] >= 1
        assert q.max_preempt_wait_s <= scorer.max_quantum_wall_s + 0.05
        # The policy witness: no quantum was ever admitted while
        # interactive work waited.
        assert scorer.stats()["quanta_with_pending"] == 0
        assert scorer.stats()["jobs_completed"] == 1

    def test_paged_queue_harvests_idle_lanes_real_engine(self):
        """End-to-end through the real paged engine: interactive answers
        resolve, the bulk job completes in the idle gaps, zero quanta
        run while anything interactive is pending, and the whole session
        compiles nothing live."""
        eng = tiny_paged()
        eng.warmup()
        expectation = expected_from_inventory(eng)

        async def run():
            metrics = Metrics()
            scorer = ScoringManager(eng, metrics=metrics)
            q = PagedQueue(eng, metrics=metrics, scorer=scorer)
            await q.start()
            scorer.submit([f"course text number {i} about raft logs"
                           for i in range(5)], purpose="relevance")
            answers = await asyncio.gather(
                q.submit("what is a term?"),
                q.submit("who votes?"),
            )
            while not scorer.done():
                await asyncio.sleep(0.01)
            await q.close()
            return answers, scorer, metrics.snapshot()

        with compile_count_guard(expectation) as guard:
            answers, scorer, snap = asyncio.run(run())
        assert guard.new_compiles() == 0
        assert all(isinstance(a, str) for a in answers)
        stats = scorer.stats()
        assert stats["jobs_completed"] == 1
        assert stats["quanta"] == 3  # ceil(5 / batch cap 2)
        assert stats["quanta_with_pending"] == 0
        assert snap["counters"]["scoring_scored_tokens"] > 0

    def test_scorer_wake_starts_idle_server(self):
        """A job submitted to an IDLE queue starts scoring without any
        interactive traffic to kick the runner."""
        async def run():
            metrics = Metrics()
            eng = SlowScoreEngine()
            scorer = ScoringManager(eng, metrics=metrics)
            q = PagedQueue(eng, metrics=metrics, scorer=scorer)
            await q.start()
            await asyncio.sleep(0.05)  # runner parked on the idle wait
            scorer.submit(["a", "b", "c"])
            for _ in range(200):
                if scorer.done():
                    break
                await asyncio.sleep(0.01)
            await q.close()
            return scorer.stats()

        stats = asyncio.run(run())
        assert stats["jobs_completed"] == 1


# --------------------------------------------------- background routing


def test_background_route_avoids_hot_nodes():
    """Bulk jobs place OFF the hot affinity nodes: deepest-queue and
    most-routed nodes sort last."""
    from distributed_lms_raft_llm_tpu.lms.tutoring_pool import TutoringPool

    pool = TutoringPool(
        ["127.0.0.1:1", "127.0.0.1:2", "127.0.0.1:3"],
        health_addresses=["127.0.0.1:11", "127.0.0.1:12", "127.0.0.1:13"],
    )
    hot, warm, cold = pool.nodes
    hot.routes = 50
    hot.queued, hot.queued_at = 9, pool._clock()
    warm.routes = 10
    order = pool.plan_background()
    assert [n.index for n in order] == [cold.index, warm.index, hot.index]
    # A draining node is not a background candidate either.
    cold.draining = True
    order = pool.plan_background()
    assert [n.index for n in order] == [warm.index, hot.index]
