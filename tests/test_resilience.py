"""Unit tests for the resilience layer (utils/resilience.py, utils/faults.py)
and its batcher integration: deadline arithmetic, breaker state machine,
jittered backoff, seeded fault injection, bounded admission, and
expired-before-prefill shedding.
"""

import asyncio
import random
import time

import pytest

from distributed_lms_raft_llm_tpu.engine.batcher import PagedQueue
from distributed_lms_raft_llm_tpu.sim.cluster import EchoEngine
from distributed_lms_raft_llm_tpu.utils.faults import (
    FaultInjected,
    FaultInjector,
    FaultyTransport,
)
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics
from distributed_lms_raft_llm_tpu.utils.resilience import (
    DEADLINE_METADATA_KEY,
    CircuitBreaker,
    Deadline,
    DeadlineExpired,
    Overloaded,
    jittered_backoff,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------------ Deadline


def test_deadline_remaining_and_expiry():
    clock = FakeClock()
    d = Deadline.after(5.0, clock=clock)
    assert d.remaining() == pytest.approx(5.0)
    assert not d.expired
    clock.advance(4.0)
    assert d.remaining() == pytest.approx(1.0)
    clock.advance(2.0)
    assert d.expired
    assert d.remaining() == 0.0  # never negative
    with pytest.raises(DeadlineExpired):
        d.raise_if_expired()


def test_deadline_timeout_cap():
    clock = FakeClock()
    d = Deadline.after(10.0, clock=clock)
    assert d.timeout(cap=3.0) == pytest.approx(3.0)
    assert d.timeout() == pytest.approx(10.0)
    clock.advance(9.0)
    assert d.timeout(cap=3.0) == pytest.approx(1.0)


def test_deadline_metadata_roundtrip():
    clock = FakeClock()
    d = Deadline.after(2.5, clock=clock)
    md = d.to_metadata()
    assert md == [(DEADLINE_METADATA_KEY, "2500")]
    d2 = Deadline.from_metadata(md, clock=clock)
    assert d2.remaining() == pytest.approx(2.5, abs=0.01)
    # Malformed / absent headers decode to None, not an error.
    assert Deadline.from_metadata([(DEADLINE_METADATA_KEY, "bogus")]) is None
    assert Deadline.from_metadata([("other", "1")]) is None
    assert Deadline.from_metadata(None) is None


def test_deadline_from_grpc_context_prefers_tighter_budget():
    clock = FakeClock()

    class Ctx:
        def time_remaining(self):
            return 9.0

        def invocation_metadata(self):
            return [(DEADLINE_METADATA_KEY, "3000")]

    d = Deadline.from_grpc_context(Ctx(), clock=clock)
    assert d.remaining() == pytest.approx(3.0, abs=0.01)

    class NoBudget:
        def time_remaining(self):
            return None

        def invocation_metadata(self):
            return []

    assert Deadline.from_grpc_context(NoBudget(), clock=clock) is None


# ------------------------------------------------------------------- backoff


def test_jittered_backoff_bounds_and_growth():
    rng = random.Random(7)
    for attempt in range(8):
        for _ in range(50):
            d = jittered_backoff(attempt, base_s=0.1, cap_s=1.0, rng=rng)
            assert 0.0 <= d <= min(1.0, 0.1 * 2.0 ** attempt) + 1e-9
    # Deterministic under a fixed seed.
    a = [jittered_backoff(i, rng=random.Random(3)) for i in range(4)]
    b = [jittered_backoff(i, rng=random.Random(3)) for i in range(4)]
    assert a == b


# ------------------------------------------------------------------- breaker


def test_breaker_state_machine():
    clock = FakeClock()
    changes = []
    br = CircuitBreaker(
        failure_threshold=3, recovery_s=5.0, clock=clock,
        on_state_change=lambda old, new: changes.append((old, new)),
    )
    assert br.state == CircuitBreaker.CLOSED
    assert br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == CircuitBreaker.CLOSED  # below threshold
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    assert not br.allow()  # open: reject in O(1)
    clock.advance(5.1)
    assert br.state == CircuitBreaker.HALF_OPEN
    assert br.allow()       # the probe slot
    assert not br.allow()   # only one probe at a time (half_open_max=1)
    br.record_failure()     # probe failed: re-open, recovery clock restarts
    assert br.state == CircuitBreaker.OPEN
    clock.advance(5.1)
    assert br.allow()
    br.record_success()     # probe succeeded: closed again
    assert br.state == CircuitBreaker.CLOSED
    assert br.allow()
    assert ("closed", "open") in changes and ("open", "half_open") in changes
    snap = br.snapshot()
    assert snap["opened"] == 2 and snap["state"] == "closed"


def test_breaker_heals_leaked_half_open_probe():
    """A caller that takes the probe slot and dies before recording must
    not wedge the breaker half-open with no capacity forever."""
    clock = FakeClock()
    br = CircuitBreaker(failure_threshold=1, recovery_s=5.0, clock=clock)
    br.record_failure()
    clock.advance(5.1)
    assert br.allow()          # probe taken...
    assert not br.allow()      # ...and never recorded (caller died)
    clock.advance(5.1)         # another recovery window re-arms the probe
    assert br.allow()
    br.record_success()
    assert br.state == CircuitBreaker.CLOSED


def test_breaker_success_resets_consecutive_failures():
    br = CircuitBreaker(failure_threshold=2, clock=FakeClock())
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == CircuitBreaker.CLOSED  # never 2 consecutive


# ------------------------------------------------------------- fault injector


def test_fault_injector_deterministic_and_targeted():
    a = FaultInjector(seed=42)
    b = FaultInjector(seed=42)
    a.configure("raft:1", drop=0.5)
    b.configure("raft:1", drop=0.5)
    plans_a = [a.plan("raft:1").drop for _ in range(64)]
    plans_b = [b.plan("raft:1").drop for _ in range(64)]
    assert plans_a == plans_b          # same seed, same faults
    assert any(plans_a) and not all(plans_a)
    # Unconfigured targets never fault (and don't consume RNG state).
    assert not a.plan("raft:2").any
    # Wildcard fallback applies to any target without its own spec.
    a.configure("*", drop=1.0)
    assert a.plan("raft:9").drop
    a.clear("*")
    assert not a.plan("raft:9").any
    with pytest.raises(ValueError):
        a.configure("raft:1", nonsense=1.0)


def test_fault_injector_snapshot_and_reset():
    inj = FaultInjector(seed=0)
    inj.configure("tutoring", error=1.0)
    snap = inj.snapshot()
    assert snap["targets"]["tutoring"]["error"] == 1.0
    inj.clear()
    assert inj.snapshot()["targets"] == {}
    assert not inj.active


class _FakeInner:
    """Transport double: counts sends, returns a canned response."""

    def __init__(self):
        self.sent = []
        self.addresses = {1: "a", 2: "b"}

    async def send(self, peer, message):
        self.sent.append((peer, message))
        return ("resp", peer)

    async def close(self):
        self.closed = True


def test_faulty_transport_drop_error_duplicate():
    async def run():
        inner = _FakeInner()
        inj = FaultInjector(seed=1)
        t = FaultyTransport(inner, inj)
        # No spec: passthrough.
        assert await t.send(1, "m") == ("resp", 1)
        # 100% drop: raises BEFORE delivery.
        inj.configure("raft:1", drop=1.0)
        with pytest.raises(FaultInjected):
            await t.send(1, "m2")
        assert len(inner.sent) == 1  # m2 never delivered
        # 100% error: delivered, then the response is lost.
        inj.configure("raft:1", error=1.0)
        with pytest.raises(FaultInjected):
            await t.send(1, "m3")
        assert inner.sent[-1] == (1, "m3")
        # 100% duplicate: delivered twice.
        inj.configure("raft:1", duplicate=1.0)
        await t.send(1, "m4")
        assert [m for _, m in inner.sent].count("m4") == 2
        # addresses proxies to the wrapped transport (RaftNode syncs it).
        assert t.addresses is inner.addresses

    asyncio.run(run())


# ----------------------------------------------------- bounded queue admission


def FakePagedEngine(step_delay_s=0.02):
    """The sim's double with ONE slot, so the pending/slot split of the
    real engine shows: submit() backlogs, step() admits one request a
    call (`prefilled` lists the prompts whose prefill ran) and answers it
    in that same step."""
    return EchoEngine(step_delay_s, slots=1, answer="ans:{}".format)


def test_paged_queue_sheds_expired_before_admission():
    async def run():
        engine = FakePagedEngine()
        metrics = Metrics()
        q = PagedQueue(engine, metrics=metrics)
        await q.start()
        try:
            with pytest.raises(DeadlineExpired):
                await q.submit("x", deadline=Deadline.after(0.0))
            assert await q.submit("y") == "ans:y"
        finally:
            await q.close()
        assert engine.prefilled == ["y"]  # "x" never reached the engine
        assert metrics.snapshot()["counters"]["shed_expired"] == 1

    asyncio.run(run())


def test_paged_queue_sheds_engine_backlogged_expired_before_prefill():
    """A request that expires while waiting in the ENGINE's pending list
    (no free slot) is cancelled before its prefill dispatches."""
    async def run():
        engine = FakePagedEngine(step_delay_s=0.15)
        metrics = Metrics()
        q = PagedQueue(engine, metrics=metrics)
        await q.start()
        try:
            t1 = asyncio.ensure_future(q.submit("slow"))
            await asyncio.sleep(0.05)  # "slow" admitted to the only slot
            t2 = asyncio.ensure_future(
                q.submit("doomed", deadline=Deadline.after(0.02))
            )
            assert await t1 == "ans:slow"
            with pytest.raises(DeadlineExpired):
                await t2
        finally:
            await q.close()
        assert engine.prefilled == ["slow"]  # "doomed" never prefilled
        assert metrics.snapshot()["counters"]["shed_expired"] == 1

    asyncio.run(run())


def test_paged_queue_counts_engine_backlog_toward_bound():
    """Backpressure accounts for the engine's pre-slot pending list, not
    just the (eagerly drained) incoming queue."""
    async def run():
        engine = FakePagedEngine(step_delay_s=0.2)
        metrics = Metrics()
        q = PagedQueue(engine, metrics=metrics, max_queue=1)
        await q.start()
        try:
            t1 = asyncio.ensure_future(q.submit("a"))  # takes the slot
            await asyncio.sleep(0.05)
            t2 = asyncio.ensure_future(q.submit("b"))  # engine backlog = 1
            await asyncio.sleep(0.05)
            with pytest.raises(Overloaded):
                await q.submit("c")
            assert await t1 == "ans:a"
            assert await t2 == "ans:b"
        finally:
            await q.close()
        assert metrics.snapshot()["counters"]["shed_overload"] == 1
        assert "c" not in engine.prefilled

    asyncio.run(run())
