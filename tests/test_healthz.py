"""HTTP health/metrics endpoint (utils/healthz.py)."""

import asyncio
import json
import os

from distributed_lms_raft_llm_tpu.utils.healthz import HealthServer
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics


async def _get(port: int, path: str):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, body = raw.partition(b"\r\n\r\n")
    status = int(head.split()[1])
    return status, json.loads(body)


def test_healthz_and_metrics_roundtrip():
    async def run():
        metrics = Metrics()
        metrics.inc("llm_requests", 3)
        metrics.hist("ttft").observe(0.123)
        hs = HealthServer(
            metrics, health=lambda: {"ok": True, "role": "leader"}
        )
        port = await hs.start()
        try:
            status, body = await _get(port, "/healthz")
            assert status == 200 and body["ok"] and body["role"] == "leader"
            status, body = await _get(port, "/metrics")
            assert status == 200
            assert body["counters"]["llm_requests"] == 3
            assert body["latency"]["ttft"]["count"] == 1
            status, body = await _get(port, "/nope")
            assert status == 404
        finally:
            await hs.stop()

    asyncio.run(run())


def test_tutoring_server_exposes_endpoint():
    """serve_async wires the endpoint; /metrics reflects served requests."""
    import grpc

    from distributed_lms_raft_llm_tpu.engine import (
        EngineConfig, PagedEngine, SamplingParams,
    )
    from distributed_lms_raft_llm_tpu.proto import lms_pb2, rpc
    from distributed_lms_raft_llm_tpu.serving import tutoring_server

    async def run():
        engine = PagedEngine(
            EngineConfig(
                model="tiny",
                sampling=SamplingParams.reference_defaults(max_new_tokens=8),
                length_buckets=(16,), batch_buckets=(1, 2),
            ),
            slots=2,
        )
        server = await tutoring_server.serve_async(0, engine, metrics_port=0)
        # serve_async binds the gRPC port before returning; for port 0 grab
        # the real one from the server object is not exposed — dial health.
        hport = server._health.port
        status, body = await _get(hport, "/healthz")
        assert status == 200 and body["ok"]
        assert body["engine"] == "PagedEngine"
        # The node says what it computes on (a JAX-free launcher reads
        # this instead of asking JAX itself) and where it caches compiles.
        assert body["device"] == {"platform": "cpu", "kind": "cpu",
                                  "count": 8}
        assert body["device_memory"] == {}  # the CPU reports none
        assert body["compile_cache"]["dir"] == os.environ[
            "JAX_COMPILATION_CACHE_DIR"]
        assert (0 <= body["compile_cache"]["hits"]
                <= body["compile_cache"]["requests"])
        status, body = await _get(hport, "/metrics")
        assert status == 200 and "counters" in body
        await server.stop(None)
        await server._health.stop()
        await server._queue.close()

    asyncio.run(run())


async def _post(port: int, path: str, payload: dict):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps(payload).encode()
    writer.write(
        f"POST {path} HTTP/1.1\r\nHost: x\r\n"
        f"Content-Length: {len(body)}\r\n\r\n".encode() + body
    )
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, resp = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), json.loads(resp)


def test_admin_endpoint_roundtrip_and_errors():
    """POST /admin/* dispatches to the admin hook with the parsed JSON
    body; unknown paths 404, ValueErrors 400, other failures 500."""
    calls = []

    async def admin(path, body):
        if path != "/admin/membership":
            raise KeyError(path)
        if body.get("op") not in ("add", "remove"):
            raise ValueError("op must be 'add' or 'remove'")
        if body.get("boom"):
            raise RuntimeError("kaput")
        calls.append(body)
        return {"ok": True, "index": 7}

    async def run():
        hs = HealthServer(Metrics(), admin=admin)
        port = await hs.start()
        try:
            status, body = await _post(
                port, "/admin/membership",
                {"op": "add", "id": 6, "address": "127.0.0.1:9"},
            )
            assert status == 200 and body == {"ok": True, "index": 7}
            assert calls and calls[0]["id"] == 6
            status, body = await _post(port, "/admin/nope", {})
            assert status == 404
            status, body = await _post(port, "/admin/membership", {"op": "x"})
            assert status == 400 and "op must be" in body["error"]
            status, body = await _post(
                port, "/admin/membership", {"op": "add", "boom": True}
            )
            assert status == 500
            # GET to an admin path stays 404 when no read-only handler
            # is configured (mutations remain POST-only either way).
            status, _ = await _get(port, "/admin/membership")
            assert status == 404
        finally:
            await hs.stop()

    asyncio.run(run())


def test_admin_get_routes_read_only_introspection():
    """GET /admin/* dispatches to `admin_get` (read-only plane, e.g.
    GET /admin/faults); unknown paths 404, ValueErrors 400; POST still
    routes to the mutating handler."""
    posts = []

    async def admin(path, body):
        posts.append((path, body))
        return {"posted": True}

    async def admin_get(path):
        if path == "/admin/faults":
            return {"ok": True, "faults": {"targets": {}}}
        if path == "/admin/teapot":
            raise ValueError("short and stout")
        raise KeyError(path)

    async def run():
        hs = HealthServer(Metrics(), admin=admin, admin_get=admin_get)
        port = await hs.start()
        try:
            status, body = await _get(port, "/admin/faults")
            assert status == 200 and body["ok"] and "faults" in body
            status, body = await _get(port, "/admin/teapot")
            assert status == 400 and "stout" in body["error"]
            status, _ = await _get(port, "/admin/nope")
            assert status == 404
            # POST keeps hitting the mutating handler, not admin_get.
            status, body = await _post(port, "/admin/faults", {"x": 1})
            assert status == 200 and body == {"posted": True}
            assert posts == [("/admin/faults", {"x": 1})]
        finally:
            await hs.stop()

    asyncio.run(run())
