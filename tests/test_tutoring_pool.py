"""Tutoring fleet router (lms/tutoring_pool.py).

Ring properties first — deterministic placement, the minimal-remap bound
on membership change (only the departed/arrived node's keys move),
warm-up weighting — then spill ordering, budget-aware hedging with
loser cancellation, per-node chaos targets, single-node back-compat,
and the drain -> eject -> rejoin lifecycle over real gRPC + the real
healthz/drain admin plane.
"""

import asyncio
import time

import grpc
import pytest

from distributed_lms_raft_llm_tpu.engine import PagedQueue
from distributed_lms_raft_llm_tpu.lms.tutoring_pool import (
    TutoringPool,
    TutoringUnavailable,
    affinity_key,
)
from distributed_lms_raft_llm_tpu.proto import rpc
from distributed_lms_raft_llm_tpu.serving.tutoring_server import (
    TutoringService,
    make_tutoring_admin,
    make_tutoring_health,
)
from distributed_lms_raft_llm_tpu.sim.cluster import EchoEngine
from distributed_lms_raft_llm_tpu.utils.faults import FaultInjector
from distributed_lms_raft_llm_tpu.utils.healthz import HealthServer
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics
from distributed_lms_raft_llm_tpu.utils.resilience import (
    CircuitBreaker,
    Deadline,
)

ADDRS = ["10.0.0.1:50054", "10.0.0.2:50054", "10.0.0.3:50054"]
KEYS = [
    affinity_key(f"course{i % 40} assignment context: question {i}")
    for i in range(400)
]


def _pool(addresses, **kw):
    kw.setdefault("metrics", Metrics())
    return TutoringPool(addresses, **kw)


def _owners(pool):
    return {k: pool.rendezvous_order(k)[0].address for k in KEYS}


# ----------------------------------------------------------- ring maths


def test_placement_is_deterministic():
    """Same membership + same key => same node, across pool instances
    (the ring is pure hash, no per-process seed)."""
    assert _owners(_pool(ADDRS)) == _owners(_pool(ADDRS))


def test_remove_moves_only_the_departed_nodes_keys():
    """Rendezvous property: scores are per-(node, key), so removing a
    node reassigns exactly its own keys (~1/N) — the survivors' prefix
    caches keep every key they had."""
    before = _owners(_pool(ADDRS))
    after = _owners(_pool(ADDRS[:2]))
    moved = [k for k in KEYS if before[k] != after[k]]
    owned_by_removed = [k for k in KEYS if before[k] == ADDRS[2]]
    assert set(moved) == set(owned_by_removed)
    # The departed share is ~1/3 of the keys, not a reshuffle.
    assert 0.15 * len(KEYS) < len(moved) < 0.55 * len(KEYS)


def test_add_steals_at_most_a_fair_share():
    """Adding a node moves only the keys the NEW node wins (~1/(N+1));
    every moved key lands on it."""
    before = _owners(_pool(ADDRS))
    grown = _pool(ADDRS + ["10.0.0.4:50054"])
    after = _owners(grown)
    moved = [k for k in KEYS if before[k] != after[k]]
    assert moved, "a new node must take some share"
    assert all(after[k] == "10.0.0.4:50054" for k in moved)
    assert len(moved) < 0.45 * len(KEYS)  # expected ~1/4


def test_warmup_weight_shrinks_then_restores_the_key_share():
    """A warming node takes a reduced key share (its prefix cache is
    cold); once the ramp ends its placement is bit-identical to the
    steady state."""
    steady = _pool(ADDRS)
    warming = _pool(ADDRS, warmup_weight=0.25, warmup_s=60.0)
    node = warming.nodes[2]
    node.warming_until = warming._clock() + 60.0
    share_steady = sum(
        1 for k in KEYS if _owners(steady)[k] == node.address
    )
    share_warm = sum(
        1 for k, a in _owners(warming).items() if a == node.address
    )
    assert share_warm < 0.6 * share_steady
    node.warming_until = 0.0  # ramp over
    assert _owners(warming) == _owners(steady)


def test_affinity_key_normalizes_prompt_heads():
    assert affinity_key("  What   is\nRaft? ") == "what is raft?"
    long = "course0 assignment context: " + "x" * 200
    assert len(affinity_key(long)) == 64
    # Same course context prefix => same key, regardless of the tail.
    assert affinity_key(long + " A") == affinity_key(long + " B")


# -------------------------------------------------------- spill ordering


def test_queue_depth_spills_to_second_choice():
    pool = _pool(ADDRS, queue_spill_depth=8)
    key = KEYS[0]
    order = pool.rendezvous_order(key)
    now = pool._clock()
    order[0].queued, order[0].queued_at = 50, now
    order[1].queued, order[1].queued_at = 0, now
    routed, reason, affinity = pool.plan_route(key)
    assert reason == "spill:queue"
    assert routed[0] is order[1]
    assert affinity is order[0], "affinity reports the ring winner"
    # Both deep: no point spilling — stay on affinity.
    order[1].queued = 50
    _, reason, _ = pool.plan_route(key)
    assert reason == "affinity"
    # Stale reading: a depth observed longer than queue_ttl_s ago is
    # treated as drained — a node spilled around receives no trailers,
    # so a non-expiring burst reading would lock out its key share
    # (and its prefix-cache affinity) forever.
    order[0].queued_at = now - pool.queue_ttl_s - 1.0
    order[1].queued = 0
    _, reason, _ = pool.plan_route(key)
    assert reason == "affinity"


def test_budget_spills_when_affinity_ewma_exceeds_remaining():
    pool = _pool(ADDRS)
    key = KEYS[1]
    order = pool.rendezvous_order(key)
    order[0].ewma_s = 5.0
    order[1].ewma_s = 0.02
    routed, reason, affinity = pool.plan_route(key, Deadline.after(1.0))
    assert reason == "spill:budget"
    assert routed[0] is order[1]
    assert affinity is order[0]
    # Plenty of budget: affinity keeps the send.
    _, reason, _ = pool.plan_route(key, Deadline.after(30.0))
    assert reason == "affinity"


def test_hedging_is_budget_aware():
    pool = _pool(ADDRS, hedge_after_s=0.2, deadline_floor_s=0.25)
    assert pool._can_hedge(None)
    assert pool._can_hedge(Deadline.after(10.0))
    assert not pool._can_hedge(Deadline.after(0.3))
    assert not _pool(ADDRS, hedge_after_s=0.0)._can_hedge(None)


def test_empty_and_ejected_pools_raise_typed_unavailable():
    async def run():
        with pytest.raises(TutoringUnavailable) as none_exc:
            await _pool([]).forward("q", "tok")
        assert none_exc.value.kind == "none"
        pool = _pool(ADDRS)
        for node in pool.nodes:
            node.ejected = True
        with pytest.raises(TutoringUnavailable) as ej_exc:
            await pool.forward("q", "tok")
        assert ej_exc.value.kind == "ejected"

    asyncio.run(run())


# ------------------------------------------------------- real-gRPC fleet


async def _start_tutoring(node_id, delay_s=0.002, with_health=False):
    metrics = Metrics()
    queue = PagedQueue(EchoEngine(delay_s), metrics=metrics)
    await queue.start()
    server = grpc.aio.server()
    service = TutoringService(queue, metrics, node_id=node_id)
    rpc.add_TutoringServicer_to_server(service, server)
    port = server.add_insecure_port("127.0.0.1:0")
    await server.start()
    rec = {
        "server": server, "queue": queue, "metrics": metrics,
        "service": service, "address": f"127.0.0.1:{port}",
        "health": None, "health_address": None, "node_id": node_id,
    }
    if with_health:
        health = HealthServer(
            metrics,
            health=make_tutoring_health(service, queue, "EchoEngine", 64),
            admin=make_tutoring_admin(service),
        )
        hport = await health.start()
        rec["health"] = health
        rec["health_address"] = f"127.0.0.1:{hport}"
    return rec


async def _stop_tutoring(rec):
    if rec["health"] is not None:
        await rec["health"].stop()
    await rec["server"].stop(None)
    await rec["queue"].close()


def _query_with_affinity(pool, want_address):
    """A query string the ring places on `want_address` first."""
    for i in range(200):
        q = f"probe question variant {i}?"
        if pool.rendezvous_order(affinity_key(q))[0].address == \
                want_address:
            return q
    raise AssertionError("no key found for node")


def test_forward_routes_by_affinity_and_reports_served_by():
    async def run():
        nodes = [await _start_tutoring("tutA"),
                 await _start_tutoring("tutB")]
        metrics = Metrics()
        pool = TutoringPool([n["address"] for n in nodes],
                            metrics=metrics, hedge_after_s=1.0)
        ids = {n["address"]: n["node_id"] for n in nodes}
        try:
            for i in range(6):
                q = f"what is consensus, variant {i}?"
                expected = pool.rendezvous_order(affinity_key(q))[0]
                answer, served = await pool.forward(q, "tok")
                assert answer.success and "Echo tutor" in answer.response
                # x-served-by trailing metadata names the fleet member
                # the ring predicted.
                assert served == ids[expected.address]
            snap = metrics.snapshot()["counters"]
            assert snap.get("tutoring_spills", 0) == 0
            assert snap.get("tutoring_hedges", 0) == 0
        finally:
            await pool.close()
            for n in nodes:
                await _stop_tutoring(n)

    asyncio.run(run())


def test_hedge_fires_wins_and_cancels_the_slow_primary():
    """Brownout the affinity node (injected per-node delay): the hedge
    to the second choice must win well before the primary's delay, the
    loser is cancelled (the forward returns fast), and the counters
    record one hedge + one win + one spill (served off-affinity)."""
    async def run():
        nodes = [await _start_tutoring("tutA"),
                 await _start_tutoring("tutB")]
        metrics = Metrics()
        injector = FaultInjector()
        pool = TutoringPool([n["address"] for n in nodes],
                            metrics=metrics, fault_injector=injector,
                            hedge_after_s=0.05)
        ids = {n["address"]: n["node_id"] for n in nodes}
        try:
            slow = pool.nodes[0]
            q = _query_with_affinity(pool, slow.address)
            injector.configure(slow.fault_target(), delay_s=0.8)
            t0 = time.monotonic()
            answer, served = await pool.forward(
                q, "tok", deadline=Deadline.after(5.0)
            )
            elapsed = time.monotonic() - t0
            assert answer.success
            other = next(n for n in pool.nodes if n is not slow)
            assert served == ids[other.address]
            assert elapsed < 0.6, (
                f"loser not cancelled: forward took {elapsed:.2f}s"
            )
            snap = metrics.snapshot()["counters"]
            assert snap.get("tutoring_hedges", 0) == 1
            assert snap.get("tutoring_hedge_wins", 0) == 1
            assert snap.get("tutoring_spills", 0) == 1
        finally:
            await pool.close()
            for n in nodes:
                await _stop_tutoring(n)

    asyncio.run(run())


def test_blackout_of_one_node_spills_and_recovers():
    async def run():
        nodes = [await _start_tutoring("tutA"),
                 await _start_tutoring("tutB")]
        metrics = Metrics()
        injector = FaultInjector()
        pool = TutoringPool([n["address"] for n in nodes],
                            metrics=metrics, fault_injector=injector,
                            hedge_after_s=0.0,
                            breaker_failure_threshold=2,
                            breaker_recovery_s=0.1)
        try:
            dead = pool.nodes[0]
            q = _query_with_affinity(pool, dead.address)
            injector.configure(dead.fault_target(), drop=1.0)
            answer, _served = await pool.forward(q, "tok")
            assert answer.success, "the spill must serve the answer"
            snap = metrics.snapshot()["counters"]
            assert snap.get("tutoring_spills", 0) >= 1
            assert snap.get("tutoring_failures", 0) >= 1
            # Fault cleared: affinity routing resumes (give the breaker
            # its half-open window).
            injector.clear(dead.fault_target())
            await asyncio.sleep(0.15)
            answer, served = await pool.forward(q, "tok")
            assert answer.success and served == "tutA"
        finally:
            await pool.close()
            for n in nodes:
                await _stop_tutoring(n)

    asyncio.run(run())


def test_single_node_breaker_backcompat_and_legacy_fault_target():
    """A bare one-address fleet behaves like the pre-fleet forward: the
    injected legacy target "tutoring" still faults it (hierarchical
    spec fallback), consecutive failures open the injected breaker, and
    an open circuit raises kind="breaker" without dialing."""
    async def run():
        node = await _start_tutoring("solo")
        metrics = Metrics()
        injector = FaultInjector()
        breaker = CircuitBreaker(failure_threshold=2, recovery_s=30.0)
        pool = TutoringPool([node["address"]], metrics=metrics,
                            fault_injector=injector, breakers=[breaker],
                            hedge_after_s=0.0)
        try:
            injector.configure("tutoring", drop=1.0)
            for _ in range(2):
                with pytest.raises(TutoringUnavailable) as exc:
                    await pool.forward("q?", "tok")
                assert exc.value.kind == "rpc"
            assert breaker.state == CircuitBreaker.OPEN
            before = node["metrics"].snapshot()["counters"].get(
                "llm_requests", 0
            )
            with pytest.raises(TutoringUnavailable) as exc:
                await pool.forward("q?", "tok")
            assert exc.value.kind == "breaker"
            after = node["metrics"].snapshot()["counters"].get(
                "llm_requests", 0
            )
            assert after == before, "open circuit must not dial"
        finally:
            await pool.close()
            await _stop_tutoring(node)

    asyncio.run(run())


def test_duplicate_fault_delivers_twice_on_the_faulted_node():
    async def run():
        node = await _start_tutoring("solo")
        metrics = Metrics()
        injector = FaultInjector()
        pool = TutoringPool([node["address"]], metrics=metrics,
                            fault_injector=injector, hedge_after_s=0.0)
        try:
            injector.configure("tutoring:0", duplicate=1.0)
            answer, _ = await pool.forward("q?", "tok")
            assert answer.success
            assert node["metrics"].snapshot()["counters"][
                "llm_requests"
            ] == 2
            assert metrics.snapshot()["counters"][
                "tutoring_duplicates"
            ] == 1
        finally:
            await pool.close()
            await _stop_tutoring(node)

    asyncio.run(run())


def test_drain_ejects_rejoins_with_warmup_and_restores_affinity():
    """The elastic-membership lifecycle over the real admin plane: a
    draining node is ejected by the health poller (traffic keeps
    flowing via the second choice, with a draining refusal never
    counted as a breaker failure), the drain's end re-admits it with a
    warm-up ramp, and once the ramp ends the ring places its old keys
    back on it."""
    async def run():
        nodes = [await _start_tutoring("tutA", with_health=True),
                 await _start_tutoring("tutB", with_health=True)]
        metrics = Metrics()
        pool = TutoringPool(
            [n["address"] for n in nodes],
            metrics=metrics,
            health_addresses=[n["health_address"] for n in nodes],
            hedge_after_s=0.0, warmup_s=0.2, health_poll_s=0.03,
        )
        pool.start()

        async def wait_for(pred, what, timeout=5.0):
            end = time.monotonic() + timeout
            while time.monotonic() < end:
                if pred():
                    return
                await asyncio.sleep(0.02)
            raise AssertionError(f"timed out waiting for {what}")

        try:
            victim = pool.nodes[0]
            q = _query_with_affinity(pool, victim.address)
            nodes[0]["service"].set_draining(True)
            await wait_for(lambda: not victim.routable(),
                           "poller to eject the draining node")
            answer, served = await pool.forward(q, "tok")
            assert answer.success and served == "tutB"
            assert victim.breaker.state == CircuitBreaker.CLOSED, (
                "draining must never count as a breaker failure"
            )
            nodes[0]["service"].set_draining(False)
            await wait_for(lambda: victim.routable(),
                           "poller to re-admit the node")
            assert victim.warming(time.monotonic()), (
                "rejoin must start a warm-up ramp"
            )
            await wait_for(
                lambda: not victim.warming(time.monotonic()),
                "warm-up to finish",
            )
            order = pool.rendezvous_order(affinity_key(q))
            assert order[0] is victim, "affinity must be restored"
            answer, served = await pool.forward(q, "tok")
            assert answer.success and served == "tutA"
            counters = metrics.snapshot()["counters"]
            assert counters["tutoring_node_ejections"] == 1
            assert counters["tutoring_node_rejoins"] == 1
        finally:
            await pool.close()
            for n in nodes:
                await _stop_tutoring(n)

    asyncio.run(run())
