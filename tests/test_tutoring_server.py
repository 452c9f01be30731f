"""End-to-end tutoring server test: tiny engine behind real gRPC."""

import asyncio
import threading

import grpc
import pytest

import jax

from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    SamplingParams,
)
from distributed_lms_raft_llm_tpu.proto import lms_pb2, rpc
from distributed_lms_raft_llm_tpu.serving import tutoring_server


@pytest.fixture(scope="module")
def server_addr():
    """Run the aio server on a private event loop thread."""
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
    loop = asyncio.new_event_loop()
    started = threading.Event()
    state = {}

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            server = grpc.aio.server()
            from distributed_lms_raft_llm_tpu.engine import PagedQueue
            from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

            metrics = Metrics()
            queue = PagedQueue(engine, metrics=metrics)
            await queue.start()
            rpc.add_TutoringServicer_to_server(
                tutoring_server.TutoringService(queue, metrics), server
            )
            port = server.add_insecure_port("127.0.0.1:0")
            await server.start()
            state["port"] = port
            state["server"] = server
            state["metrics"] = metrics
            state["queue"] = queue
            started.set()

        loop.run_until_complete(boot())
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(timeout=60)
    yield f"127.0.0.1:{state['port']}", state

    async def teardown():
        await state["server"].stop(None)
        await state["queue"].close()

    asyncio.run_coroutine_threadsafe(teardown(), loop).result(30)
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=10)


def test_get_llm_answer_over_wire(server_addr):
    addr, state = server_addr
    with grpc.insecure_channel(addr) as channel:
        stub = rpc.TutoringStub(channel)
        resp = stub.GetLLMAnswer(
            lms_pb2.QueryRequest(token="t", query="What is a mutex?"), timeout=120
        )
    assert resp.success
    assert isinstance(resp.response, str)
    snap = state["metrics"].snapshot()
    assert snap["counters"]["llm_requests"] == 1
    assert snap["latency"]["ttft"]["count"] == 1


def test_concurrent_queries_batched(server_addr):
    addr, state = server_addr
    with grpc.insecure_channel(addr) as channel:
        stub = rpc.TutoringStub(channel)
        futures = [
            stub.GetLLMAnswer.future(
                lms_pb2.QueryRequest(token="t", query=f"question {i}"), timeout=120
            )
            for i in range(4)
        ]
        responses = [f.result() for f in futures]
    assert all(r.success for r in responses)


def test_empty_query_rejected(server_addr):
    addr, _ = server_addr
    with grpc.insecure_channel(addr) as channel:
        stub = rpc.TutoringStub(channel)
        resp = stub.GetLLMAnswer(
            lms_pb2.QueryRequest(token="t", query="   "), timeout=30
        )
    assert not resp.success
