"""End-to-end resilience over real gRPC: deadline propagation
client → LMS → tutoring → batcher, circuit-broken degraded answers
(instructor queue), and a seeded chaos soak with `FaultInjector` on the
live Raft transport — the acceptance scenarios of the resilience layer.
"""

import asyncio
import threading
import time

import grpc
import pytest

import jax

from distributed_lms_raft_llm_tpu.client import LMSClient
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    PagedQueue,
    SamplingParams,
)
from distributed_lms_raft_llm_tpu.lms.node import LMSNode
from distributed_lms_raft_llm_tpu.lms.service import (
    FileTransferServicer,
    LMSServicer,
)
from distributed_lms_raft_llm_tpu.proto import lms_pb2, rpc
from distributed_lms_raft_llm_tpu.raft import RaftConfig
from distributed_lms_raft_llm_tpu.raft.grpc_transport import RaftServicer
from distributed_lms_raft_llm_tpu.serving import tutoring_server as ts
from distributed_lms_raft_llm_tpu.sim.cluster import EchoEngine
from distributed_lms_raft_llm_tpu.utils import pdf
from distributed_lms_raft_llm_tpu.utils.faults import FaultInjector
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics
from distributed_lms_raft_llm_tpu.utils.resilience import (
    DEADLINE_METADATA_KEY,
    REQUEST_ID_METADATA_KEY,
    CircuitBreaker,
)

FAST = RaftConfig(
    election_timeout_min=0.11, election_timeout_max=0.22,
    heartbeat_interval=0.05,
)


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    """1-node LMS + tiny tutoring node, breaker + injector installed."""
    tmp = tmp_path_factory.mktemp("resilience")
    loop = asyncio.new_event_loop()
    started = threading.Event()
    state = {}

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            engine = PagedEngine(
                EngineConfig(
                    model="tiny",
                    sampling=SamplingParams(max_new_tokens=6),
                    length_buckets=(32,),
                    batch_buckets=(1, 2, 4),
                    dtype=jax.numpy.float32,
                ),
                slots=4, chunk=2,
            )
            tut_metrics = Metrics()
            queue = PagedQueue(engine, metrics=tut_metrics, max_queue=8)
            await queue.start()
            tut_server = grpc.aio.server()
            rpc.add_TutoringServicer_to_server(
                ts.TutoringService(queue, tut_metrics), tut_server
            )
            tut_port = tut_server.add_insecure_port("127.0.0.1:0")
            await tut_server.start()

            injector = FaultInjector(seed=1234)
            metrics = Metrics()
            breaker = CircuitBreaker(failure_threshold=2, recovery_s=0.5)

            server = grpc.aio.server(
                options=[("grpc.max_receive_message_length", 50 * 1024 * 1024)]
            )
            port = server.add_insecure_port("127.0.0.1:0")
            addresses = {1: f"127.0.0.1:{port}"}
            node = LMSNode(1, addresses, str(tmp / "node1"), raft_config=FAST,
                           fault_injector=injector)
            servicer = LMSServicer(
                node.node, node.state, node.blobs,
                tutoring_address=f"127.0.0.1:{tut_port}",
                metrics=metrics,
                tutoring_breaker=breaker,
                fault_injector=injector,
                tutoring_timeout_s=30.0,
                deadline_floor_s=0.25,
            )
            rpc.add_LMSServicer_to_server(servicer, server)
            rpc.add_RaftServiceServicer_to_server(
                RaftServicer(node.node, addresses, kv=node.state.data["kv"]),
                server,
            )
            rpc.add_FileTransferServiceServicer_to_server(
                FileTransferServicer(node.blobs), server
            )
            await server.start()
            await node.start()
            state.update(
                node=node, server=server, queue=queue, servicer=servicer,
                tut_server=tut_server, tut_metrics=tut_metrics,
                metrics=metrics, breaker=breaker, injector=injector,
                address=addresses[1], tut_address=f"127.0.0.1:{tut_port}",
                loop=loop,
            )
            started.set()

        loop.run_until_complete(boot())
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(60)
    yield state

    async def teardown():
        await state["node"].stop()
        await state["server"].stop(None)
        await state["queue"].close()
        await state["tut_server"].stop(None)

    asyncio.run_coroutine_threadsafe(teardown(), loop).result(30)
    loop.call_soon_threadsafe(loop.stop)


@pytest.fixture(scope="module")
def student(stack):
    c = LMSClient([stack["address"]], discovery_backoff_s=0.2,
                  backoff_base_s=0.02, backoff_max_s=0.2, seed=5)
    assert c.register("ana", "pw", "student").success
    assert c.login("ana", "pw")
    assert c.upload_assignment("hw.pdf", pdf.make_pdf("B-tree homework"))
    yield c
    c.close()


def test_ask_llm_works_with_no_faults(stack, student):
    resp = student.ask_llm("How does a B-tree split?")
    assert resp.success
    assert "instructor" not in resp.response.lower()


def test_ask_llm_degrades_within_deadline_when_tutoring_faulted(stack, student):
    """The acceptance scenario: tutoring at 100% injected failure — ask_llm
    returns a degraded instructor-queue answer within the client budget
    instead of hanging or erroring."""
    stack["injector"].configure("tutoring", drop=1.0)
    try:
        t0 = time.monotonic()
        resp = student.ask_llm("What is an LSM tree?", budget_s=10.0)
        elapsed = time.monotonic() - t0
    finally:
        stack["injector"].clear("tutoring")
    assert elapsed < 10.0, "must answer within the client deadline"
    assert resp.success
    assert "instructor" in resp.response.lower()
    # The query really landed on the replicated instructor queue.
    queries = [q["query"] for q in stack["node"].state.unanswered_queries()]
    assert "What is an LSM tree?" in queries
    # One failure (threshold 2): breaker still closed, service recovers.
    resp = student.ask_llm("What is an LSM tree, again?")
    assert resp.success and "instructor" not in resp.response.lower()


def test_breaker_opens_after_consecutive_failures_then_recovers(stack, student):
    breaker = stack["breaker"]
    stack["injector"].configure("tutoring", drop=1.0)
    try:
        for _ in range(2):  # threshold=2 consecutive failures
            assert student.ask_llm("q?").success
        assert breaker.state == CircuitBreaker.OPEN
        rejections_before = (
            stack["metrics"].snapshot()["counters"]
            .get("tutoring_breaker_rejections", 0)
        )
        # Open circuit: degraded in O(1), no dial, no timeout stacking.
        t0 = time.monotonic()
        resp = student.ask_llm("q while open?")
        assert time.monotonic() - t0 < 2.0
        assert resp.success and "instructor" in resp.response.lower()
        counters = stack["metrics"].snapshot()["counters"]
        assert counters["tutoring_breaker_rejections"] == rejections_before + 1
    finally:
        stack["injector"].clear("tutoring")
    time.sleep(0.6)  # recovery_s=0.5: open -> half-open
    resp = student.ask_llm("probe?")  # half-open probe succeeds, closes
    assert resp.success and "instructor" not in resp.response.lower()
    assert breaker.state == CircuitBreaker.CLOSED


def test_budget_below_floor_degrades_without_forwarding(stack, student):
    """Deadline propagation client → LMS: a budget under the floor makes
    the LMS degrade immediately rather than start a forward it cannot
    finish in time. The floor is temporarily raised to 2 s so the check
    (budget 1.5 < floor 2) is deterministic while the wall-clock margin
    for the degrade round trip stays generous on slow CI."""
    servicer = stack["servicer"]
    before = stack["tut_metrics"].snapshot()["counters"]["llm_requests"]
    old_floor = servicer._deadline_floor_s
    servicer._deadline_floor_s = 2.0
    try:
        resp = student.ask_llm("tiny budget?", budget_s=1.5)
    finally:
        servicer._deadline_floor_s = old_floor
    assert resp.success and "instructor" in resp.response.lower()
    counters = stack["metrics"].snapshot()["counters"]
    assert counters.get("tutoring_budget_exhausted", 0) >= 1
    after = stack["tut_metrics"].snapshot()["counters"]["llm_requests"]
    assert after == before  # never dialed tutoring


def test_tutoring_honors_deadline_metadata_over_wire(stack):
    """Deadline propagation LMS → tutoring: an already-expired budget
    header aborts with DEADLINE_EXCEEDED before any generation."""
    with grpc.insecure_channel(stack["tut_address"]) as channel:
        stub = rpc.TutoringStub(channel)
        with pytest.raises(grpc.RpcError) as err:
            stub.GetLLMAnswer(
                lms_pb2.QueryRequest(token="t", query="late question"),
                timeout=5,
                metadata=[(DEADLINE_METADATA_KEY, "0")],
            )
    assert err.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
    assert stack["tut_metrics"].snapshot()["counters"]["shed_expired"] >= 1


def test_tutoring_overload_returns_resource_exhausted(stack):
    """Bounded admission over the wire: saturate the queue bound and the
    surplus RPC is refused with RESOURCE_EXHAUSTED (not queued forever)."""
    queue = stack["queue"]
    loop = stack["loop"]

    # Block the engine worker with a synthetic slow step, then fill the
    # bounded queue from the cluster loop so the backlog really
    # accumulates.
    real_engine = queue.engine

    async def saturate():
        queue.engine = EchoEngine(delay_s=1.0, slots=queue.max_queue,
                                  answer=lambda prompt: "plugged")
        # Stage 1: one request the runner takes alone into the (plugged)
        # engine; stage 2: exactly max_queue more fill the bound while the
        # engine is busy.
        futs = [asyncio.ensure_future(queue.submit("fill first"))]
        await asyncio.sleep(0.1)
        futs += [asyncio.ensure_future(queue.submit(f"fill {i}"))
                 for i in range(queue.max_queue)]
        await asyncio.sleep(0.05)
        assert queue.waiting >= queue.max_queue
        return futs

    futs = asyncio.run_coroutine_threadsafe(saturate(), loop).result(10)
    try:
        with grpc.insecure_channel(stack["tut_address"]) as channel:
            stub = rpc.TutoringStub(channel)
            with pytest.raises(grpc.RpcError) as err:
                stub.GetLLMAnswer(
                    lms_pb2.QueryRequest(token="t", query="one too many"),
                    timeout=5,
                )
        assert err.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
        assert (stack["tut_metrics"].snapshot()["counters"]
                .get("shed_overload", 0) >= 1)
    finally:
        async def drain():
            await asyncio.gather(*futs, return_exceptions=True)
            queue.engine = real_engine

        asyncio.run_coroutine_threadsafe(drain(), loop).result(30)


def test_degraded_fallback_dedupes_client_retries(stack, student):
    """ROADMAP item (a): ONE logical ask_llm, retried, queues ONE
    instructor entry. The client threads a single x-request-id across its
    retries; the degraded fallback keys the replicated AskQuery on it, so
    the applier's idempotency ledger drops the retry's duplicate."""
    stack["injector"].configure("tutoring", drop=1.0)
    query = "idempotent degraded question (one entry expected)"
    try:
        with grpc.insecure_channel(stack["address"]) as channel:
            stub = rpc.LMSStub(channel)
            # Two wire attempts of the SAME logical request (what the
            # client's retry loop sends after a lost response).
            for _ in range(2):
                resp = stub.GetLLMAnswer(
                    lms_pb2.QueryRequest(token=student.token, query=query),
                    timeout=10,
                    metadata=[(REQUEST_ID_METADATA_KEY, "logical-req-1")],
                )
                assert resp.success
                assert "instructor" in resp.response.lower()
    finally:
        stack["injector"].clear("tutoring")
        # The induced failures may have opened the breaker; close it so
        # later tests start from the healthy state.
        stack["breaker"].record_success()
    queued = [q for q in stack["node"].state.unanswered_queries()
              if q["query"] == query]
    assert len(queued) == 1, (
        f"expected one instructor entry for one logical request, got "
        f"{len(queued)}"
    )


def test_degraded_fallback_without_request_id_still_queues(stack, student):
    """Clients that send no x-request-id keep the old per-attempt ids (no
    dedupe, but never dropped either) — pins the fallback's default."""
    stack["injector"].configure("tutoring", drop=1.0)
    query = "degraded question without idempotency key"
    try:
        with grpc.insecure_channel(stack["address"]) as channel:
            stub = rpc.LMSStub(channel)
            resp = stub.GetLLMAnswer(
                lms_pb2.QueryRequest(token=student.token, query=query),
                timeout=10,
            )
            assert resp.success and "instructor" in resp.response.lower()
    finally:
        stack["injector"].clear("tutoring")
        stack["breaker"].record_success()  # close again for later tests
    queued = [q for q in stack["node"].state.unanswered_queries()
              if q["query"] == query]
    assert len(queued) == 1


def test_duplicate_fault_delivers_tutoring_query_twice(stack, student):
    """ROADMAP item (b): the "duplicate" fault is now real on the tutoring
    hop — the forward is delivered twice (idempotent: same success, extra
    compute only), it counts as injected, and the tutoring node really
    sees both deliveries."""
    before = (stack["tut_metrics"].snapshot()["counters"]
              .get("llm_requests", 0))
    injected_before = stack["injector"].snapshot()["injected_total"]
    stack["injector"].configure("tutoring", duplicate=1.0)
    try:
        resp = student.ask_llm("duplicated question?")
    finally:
        stack["injector"].clear("tutoring")
    assert resp.success
    assert "instructor" not in resp.response.lower()  # not degraded
    after = stack["tut_metrics"].snapshot()["counters"]["llm_requests"]
    assert after == before + 2, "tutoring must see both deliveries"
    assert stack["injector"].snapshot()["injected_total"] > injected_before
    assert (stack["metrics"].snapshot()["counters"]
            .get("tutoring_duplicates", 0) >= 1)


# ----------------------------------------------------------- chaos over gRPC


@pytest.mark.slow
def test_chaos_soak_over_real_grpc(tmp_path):
    """Seeded chaos on the LIVE Raft gRPC transport: drops, delays, and
    duplicates on every node's egress while clients keep mutating. After
    healing, all replicas converge — the MemNetwork chaos guarantees,
    now over real sockets."""
    loop = asyncio.new_event_loop()
    started = threading.Event()
    state = {}

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            ids = [1, 2, 3]
            injectors = {i: FaultInjector(seed=100 + i) for i in ids}
            servers, addresses = {}, {}
            for i in ids:
                servers[i] = grpc.aio.server()
                port = servers[i].add_insecure_port("127.0.0.1:0")
                addresses[i] = f"127.0.0.1:{port}"
            nodes = {}
            for i in ids:
                node = LMSNode(i, addresses, str(tmp_path / f"node{i}"),
                               raft_config=FAST,
                               fault_injector=injectors[i])
                servicer = LMSServicer(node.node, node.state, node.blobs)
                rpc.add_LMSServicer_to_server(servicer, servers[i])
                rpc.add_RaftServiceServicer_to_server(
                    RaftServicer(node.node, addresses,
                                 kv=node.state.data["kv"]),
                    servers[i],
                )
                rpc.add_FileTransferServiceServicer_to_server(
                    FileTransferServicer(node.blobs), servers[i]
                )
                await servers[i].start()
                await node.start()
                nodes[i] = node
            state.update(nodes=nodes, servers=servers, addresses=addresses,
                         injectors=injectors)
            started.set()

        loop.run_until_complete(boot())
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(60)
    try:
        client = LMSClient(list(state["addresses"].values()),
                           discovery_backoff_s=0.2, backoff_base_s=0.05,
                           backoff_max_s=0.5, rpc_retries=8,
                           request_timeout_s=30.0, seed=9)
        # Let a leader emerge cleanly, then unleash the chaos.
        client.discover_leader()
        for inj in state["injectors"].values():
            inj.configure("*", drop=0.15, delay_s=0.002,
                          delay_jitter_s=0.01, duplicate=0.1)
        users = [f"user{i}" for i in range(4)]
        for u in users:
            assert client.register(u, "pw", "student").success
        assert client.login(users[0], "pw")
        assert client.ask_instructor("chaos question?")
        client.logout()
        # Heal and verify convergence across all replicas.
        for inj in state["injectors"].values():
            inj.clear()
        faulted = sum(
            inj.snapshot()["injected_total"]
            for inj in state["injectors"].values()
        )
        assert faulted > 0, "the soak must actually have injected faults"
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            datas = [n.state.data for n in state["nodes"].values()]
            if all(set(d["users"]) == set(users) for d in datas) and all(
                d["queries"].get(users[0]) for d in datas
            ):
                break
            time.sleep(0.25)
        for n in state["nodes"].values():
            assert set(n.state.data["users"]) == set(users)
            assert n.state.data["queries"][users[0]][0]["query"] == (
                "chaos question?"
            )
        client.close()
    finally:
        async def teardown():
            for n in state["nodes"].values():
                await n.stop()
            for s in state["servers"].values():
                await s.stop(None)

        asyncio.run_coroutine_threadsafe(teardown(), loop).result(30)
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=10)
