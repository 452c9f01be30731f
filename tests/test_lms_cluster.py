"""Full-system integration: 3-node LMS cluster + TPU tutoring node + gate,
driven through the sync client library over real gRPC — the end-to-end
journey the reference validated manually (SURVEY.md §4)."""

import asyncio
import threading

import pytest

import jax

from distributed_lms_raft_llm_tpu.client import LMSClient
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    GateConfig,
    PagedEngine,
    PagedQueue,
    RelevanceGate,
    SamplingParams,
)
from distributed_lms_raft_llm_tpu.lms.node import LMSNode
from distributed_lms_raft_llm_tpu.lms.service import (
    FileTransferServicer,
    LMSServicer,
)
from distributed_lms_raft_llm_tpu.proto import rpc
from distributed_lms_raft_llm_tpu.raft import RaftConfig
from distributed_lms_raft_llm_tpu.raft.grpc_transport import RaftServicer
from distributed_lms_raft_llm_tpu.serving import tutoring_server as ts
from distributed_lms_raft_llm_tpu.utils import pdf
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

import grpc

FAST = RaftConfig(
    election_timeout_min=0.11, election_timeout_max=0.22, heartbeat_interval=0.05
)


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """3 LMS nodes + tutoring server on a private event-loop thread."""
    tmp = tmp_path_factory.mktemp("cluster")
    loop = asyncio.new_event_loop()
    started = threading.Event()
    state = {}

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            # Tutoring node (tiny model).
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
            queue = PagedQueue(engine)
            await queue.start()
            tut_server = grpc.aio.server()
            rpc.add_TutoringServicer_to_server(
                ts.TutoringService(queue, Metrics()), tut_server
            )
            tut_port = tut_server.add_insecure_port("127.0.0.1:0")
            await tut_server.start()

            gate = RelevanceGate(
                GateConfig(model="tiny", dtype=jax.numpy.float32, threshold=0.0)
            )

            ids = [1, 2, 3]
            servers, addresses = {}, {}
            for i in ids:
                servers[i] = grpc.aio.server(
                    options=[("grpc.max_receive_message_length", 50 * 1024 * 1024)]
                )
                port = servers[i].add_insecure_port("127.0.0.1:0")
                addresses[i] = f"127.0.0.1:{port}"
            lms_nodes = {}
            for i in ids:
                node = LMSNode(
                    i, addresses, str(tmp / f"node{i}"), raft_config=FAST
                )
                servicer = LMSServicer(
                    node.node, node.state, node.blobs, gate=gate,
                    tutoring_address=f"127.0.0.1:{tut_port}",
                )
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
                lms_nodes[i] = node
            state.update(
                servers=servers, nodes=lms_nodes, addresses=addresses,
                tut_server=tut_server, queue=queue, tmp=tmp, loop=loop,
            )
            started.set()

        loop.run_until_complete(boot())
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(60)
    yield state

    async def teardown():
        for node in state["nodes"].values():
            if not node.node._stopped:
                await node.stop()
        for s in state["servers"].values():
            await s.stop(None)
        await state["queue"].close()
        await state["tut_server"].stop(None)

    asyncio.run_coroutine_threadsafe(teardown(), loop).result(30)
    loop.call_soon_threadsafe(loop.stop)


@pytest.fixture(scope="module")
def client(cluster):
    c = LMSClient(list(cluster["addresses"].values()),
                  discovery_backoff_s=0.2)
    yield c
    c.close()


def test_full_student_instructor_journey(client):
    # -- registration / login ------------------------------------------------
    assert client.register("ana", "pw1", "student").success
    assert client.register("prof", "pw2", "instructor").success
    assert not client.register("ana", "zzz", "student").success  # duplicate
    assert client.login("prof", "pw2") and client.role == "instructor"

    # -- instructor posts course material ------------------------------------
    material = pdf.make_pdf("Lecture 4: B-trees, LSM trees, and storage engines")
    assert client.upload_course_material("lecture4.pdf", material)
    client.logout()

    # -- student journey -----------------------------------------------------
    assert client.login("ana", "pw1") and client.role == "student"
    mats = client.course_materials()
    assert [m.filename for m in mats] == ["lecture4.pdf"]
    assert mats[0].file == material  # bytes round-trip through the blob store

    hw = pdf.make_pdf("Homework: implement a B-tree with insert and split")
    assert client.upload_assignment("hw1.pdf", hw)
    assert "No grade" in client.my_grade()

    # LLM path: gate (threshold 0 in fixture) + tutoring node
    resp = client.ask_llm("How does a B-tree split work?")
    assert resp.success

    assert client.ask_instructor("When is hw1 due?")
    client.logout()

    # -- instructor grades + responds ----------------------------------------
    assert client.login("prof", "pw2")
    subs = client.student_assignments()
    assert [(e.id, e.filename) for e in subs] == [("ana", "hw1.pdf")]
    assert subs[0].file == hw
    assert client.grade("ana", "A").success
    queries = client.unanswered_queries()
    assert [(q.id, q.data) for q in queries] == [("ana", "When is hw1 due?")]
    assert client.respond_to_query("ana", "Friday midnight.")
    client.logout()

    # -- student sees results ------------------------------------------------
    assert client.login("ana", "pw1")
    assert client.my_grade() == "Your grade: A"
    responses = client.instructor_responses()
    assert len(responses) == 1
    assert "Friday midnight." in responses[0].data
    client.logout()


def test_unauthorized_paths(client):
    assert client.login("ana", "pw1")
    # Student cannot grade or list assignments.
    assert not client.grade("ana", "F").success
    assert client.student_assignments() == []
    client.logout()
    # Bogus token fails cleanly.
    client.token = "forged-token"
    assert client.my_grade() in ("Invalid session",)
    client.token = None


def test_state_replicated_to_all_nodes(cluster, client):
    """After the journey, every node's state machine has converged."""
    import time

    time.sleep(0.5)  # let followers apply the tail
    datas = [n.state.data for n in cluster["nodes"].values()]
    for d in datas:
        assert set(d["users"]) == {"ana", "prof"}
        assert [a["grade"] for a in d["assignments"]["ana"]] == ["A"]
        assert d["queries"]["ana"][0]["answered"]


def test_uploaded_files_replicated_to_followers(cluster, client):
    import time

    time.sleep(0.5)
    present = [
        n.blobs.exists("materials/lecture4.pdf")
        and n.blobs.exists("assignments/ana/hw1.pdf")
        for n in cluster["nodes"].values()
    ]
    assert all(present), present


def test_replica_state_digests_converge(cluster, client):
    """PR 18: every replica folds LMSState.digest() into a per-applied-
    index digest chain; at quiescence all three replicas of the group
    must sit at the same applied index with the SAME digest — the
    runtime half of the state-machine-determinism rule.

    (Runs before the failover test below, which stops the leader.)"""
    import time

    deadline = time.monotonic() + 10.0
    nodes = list(cluster["nodes"].values())
    while time.monotonic() < deadline:
        applied = {n._last_applied_index for n in nodes}
        digests = {n.state_digest for n in nodes}
        if len(applied) == 1 and len(digests) == 1:
            break
        time.sleep(0.1)
    assert len(applied) == 1, f"applied indexes diverged: {applied}"
    assert len(digests) == 1, (
        "replicas diverged at the same applied index — "
        f"nondeterministic apply: {digests}"
    )
    (digest,) = digests
    assert len(digest) == 16 and int(digest, 16) >= 0
    # The chain is a pure fold of (index, state): recomputing on each
    # node reproduces the live value, and raw state digests agree too.
    for n in nodes:
        assert n._fold_digest(n._last_applied_index) == digest
    assert len({n.state.digest() for n in nodes}) == 1


def test_digest_chain_survives_restart_and_snapshot_install(tmp_path):
    """PR 18: the digest is a pure function of (applied index, state) —
    NOT an in-memory running hash — so a node restarted from its own
    WAL+snapshot, and a wiped node rejoining via InstallSnapshot, both
    land back on the exact chain value their peers report."""
    from distributed_lms_raft_llm_tpu.lms.node import LMSNode as _LMSNode
    from distributed_lms_raft_llm_tpu.raft.messages import encode_command

    async def run():
        ids = [1, 2, 3]
        servers, addresses, ports = {}, {}, {}
        for i in ids:
            servers[i] = grpc.aio.server()
            ports[i] = servers[i].add_insecure_port("127.0.0.1:0")
            addresses[i] = f"127.0.0.1:{ports[i]}"
        nodes = {}

        async def boot(i, dirname):
            node = _LMSNode(i, addresses, str(tmp_path / dirname),
                            raft_config=FAST, snapshot_every=5)
            rpc.add_RaftServiceServicer_to_server(
                RaftServicer(node.node, addresses), servers[i]
            )
            await servers[i].start()
            await node.start()
            nodes[i] = node

        async def reboot_server(i):
            servers[i] = grpc.aio.server()
            bound = servers[i].add_insecure_port(f"127.0.0.1:{ports[i]}")
            assert bound == ports[i], "could not rebind node port"

        async def converged_digest(expect_members=3):
            """Wait for one (applied, digest) across all live nodes."""
            for _ in range(500):
                live = list(nodes.values())
                applied = {n._last_applied_index for n in live}
                digests = {n.state_digest for n in live}
                if (len(live) == expect_members and len(applied) == 1
                        and len(digests) == 1):
                    return applied.pop(), digests.pop()
                await asyncio.sleep(0.02)
            raise AssertionError(
                f"no digest convergence: applied={applied} digests={digests}"
            )

        for i in ids:
            await boot(i, f"node{i}")
        try:
            leader = None
            for _ in range(300):
                leaders = [n for n in nodes.values() if n.node.is_leader]
                if leaders:
                    leader = leaders[0]
                    break
                await asyncio.sleep(0.02)
            assert leader is not None

            async def register(k):
                await leader.node.propose(encode_command(
                    "Register",
                    {"username": f"user{k}", "password_hash": "h",
                     "salt": "", "role": "student"},
                ))

            # Past the snapshot cadence (5) so restarts replay from a
            # snapshot + WAL suffix, not a fresh log.
            for k in range(12):
                await register(k)
            applied0, digest0 = await converged_digest()

            # -- restart a follower from its own data dir ------------------
            victim = next(i for i in ids if not nodes[i].node.is_leader)
            await nodes[victim].stop()
            await servers[victim].stop(None)
            del nodes[victim]
            await reboot_server(victim)
            await boot(victim, f"node{victim}")  # SAME dir: snapshot+WAL
            applied1, digest1 = await converged_digest()
            assert applied1 == applied0 and digest1 == digest0, (
                "restart-from-snapshot left the digest chain"
            )

            # -- wipe a follower; rejoin via InstallSnapshot ---------------
            victim2 = next(
                i for i in ids
                if i != victim and not nodes[i].node.is_leader
            )
            await nodes[victim2].stop()
            await servers[victim2].stop(None)
            del nodes[victim2]
            for k in range(12, 15):  # commits while it is down
                await register(k)
            await reboot_server(victim2)
            await boot(victim2, f"node{victim2}-wiped")  # EMPTY dir
            applied2, digest2 = await converged_digest()
            assert applied2 > applied0
            assert digest2 != digest0  # state moved on; chain did too
            # The rejoiner really came through snapshot install.
            assert nodes[victim2].node.core.snapshot_index >= 5
            assert len(nodes[victim2].state.data["users"]) == 15
        finally:
            for n in nodes.values():
                await n.stop()
            for s in servers.values():
                await s.stop(None)

    asyncio.run(run())


def test_sessions_survive_failover(cluster, client):
    """The D7 fix: a login taken before leader failure works after it."""

    async def stop_leader():
        for node in cluster["nodes"].values():
            if node.node.is_leader:
                await node.stop()
                return node.node_id
        return None

    assert client.login("ana", "pw1")
    token_before = client.token
    # Stop the current leader from the cluster's own loop.
    fut = asyncio.run_coroutine_threadsafe(stop_leader(), cluster["loop"])
    stopped = fut.result(10)
    assert stopped is not None
    client.discover_leader(force=True)
    # Old token still valid on the new leader (sessions are replicated).
    assert client.my_grade() == "Your grade: A"
    assert client.token == token_before
