"""TPU tutoring server: `Tutoring.GetLLMAnswer` on the JAX engine.

Drop-in replacement for the reference's PyTorch inference node (reference:
GUI_RAFT_LLM_SourceCode/tutoring_server.py:33-49 — port 50054, 10-thread
sync gRPC, one sequential `model.generate` per RPC). This server keeps the
wire contract byte-identical and changes everything behind it:

- `grpc.aio` front-end; concurrent RPCs join the running device batch
  through `engine.PagedQueue` instead of queueing on a thread pool;
- the model is loaded/sharded once at startup and pre-compiled (`warmup`)
  so the first student query doesn't pay the XLA compile;
- per-query latency lands in a first-class histogram (p50 TTFT is the
  BASELINE metric) and is logged periodically.

Run: python -m distributed_lms_raft_llm_tpu.serving.tutoring_server \
        [--port 50054] [--model gpt2] [--checkpoint model.safetensors ...]
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import importlib.metadata
import json
import logging
import time
from typing import Dict, Optional, Tuple

import grpc

from ..engine import (
    EngineConfig,
    PagedEngine,
    PagedQueue,
    SamplingParams,
    ScoringManager,
)
from ..engine.scoring import score_admin_get
from ..parallel.mesh import (
    device_info,
    device_memory,
    initialize_multihost,
)
from ..proto import lms_pb2, rpc
from ..utils import auth
from ..utils.compilation import cache_stats
from ..utils.guards import make_serving_watchdog
from ..utils.metrics import Metrics
from ..utils.resilience import (
    Deadline,
    DeadlineExpired,
    Overloaded,
    QUEUE_DEPTH_METADATA_KEY,
    SERVED_BY_METADATA_KEY,
)
from ..utils.timeline import TimelineSampler, timeline_admin_get
from ..utils.tracing import get_tracer, trace_admin_get, traced_grpc_handler

log = logging.getLogger("tutoring_server")

# Same role as the reference's prompt template (tutoring_server.py:15-19):
# frame the raw student query for an instruction-free base LM.
PROMPT_TEMPLATE = (
    "You are an intelligent assistant. Answer the following question clearly "
    "and concisely.\nQuestion: {query}\nAnswer:"
)

# Follow-up turns of a tutoring session append to the running transcript
# (turn N's prompt + answer) instead of re-framing from scratch, so the
# session's token prefix is byte-stable across turns and the radix prefix
# cache can splice turn N's KV blocks under turn N+1's prompt.
FOLLOWUP_TEMPLATE = "\nQuestion: {query}\nAnswer:"


class TutoringService(rpc.TutoringServicer):
    def __init__(self, queue: PagedQueue, metrics: Metrics,
                 auth_key: Optional[str] = None,
                 node_id: Optional[str] = None,
                 session_ttl_s: float = 600.0,
                 session_max: int = 256):
        self.queue = queue
        self.metrics = metrics
        self.auth_key = auth_key
        # Fleet identity: rides every answer's trailing metadata
        # (x-served-by) so the router, waterfalls, and the ledger can
        # attribute answers to fleet members.
        self.node_id = node_id
        self.draining = False  # guarded-by: event-loop
        # Multi-turn tutoring sessions ([sessions] in the TOML): this
        # node's running transcripts, session_id -> (transcript text,
        # expiry). The transcript is the byte-exact prompt+answer of every
        # turn served HERE, so turn N+1's prompt extends it verbatim and
        # the radix prefix cache splices turn N's KV blocks. Node-local by
        # design — the affinity router keeps a session sticky to one node;
        # a session that lands elsewhere (failover) restarts its
        # transcript there and only loses cache warmth, never correctness.
        self.session_ttl_s = float(session_ttl_s)
        self.session_max = int(session_max)
        self._sessions: Dict[str, Tuple[str, float]] = {}  # event-loop only

    def set_draining(self, draining: bool) -> None:
        """POST /admin/drain: stop admitting new queries while in-flight
        work finishes. The fleet router observes `draining` on /healthz
        (or the UNAVAILABLE refusal) and ejects this node from its ring;
        un-draining re-admits it with a warm-up weight."""
        self.draining = bool(draining)
        self.metrics.set_gauge("tutoring_draining",
                               1.0 if self.draining else 0.0)
        log.info("tutoring node %s %s", self.node_id or "(unnamed)",
                 "draining: admission stopped" if self.draining
                 else "drain ended: admitting again")

    def _session_transcript(self, session_id: str) -> str:
        """Live transcript for `session_id` ('' = fresh/expired session)."""
        entry = self._sessions.get(session_id)
        if entry is None:
            return ""
        text, expiry = entry
        if time.monotonic() >= expiry:
            self._drop_session(session_id)
            return ""
        return text

    def _session_update(self, session_id: str, transcript: str) -> None:
        """Record the turn's prompt+answer; refresh the TTL; enforce the
        per-node cap (oldest-expiry sessions out first — their prefix
        pins are released so the blocks fall back to plain LRU)."""
        self._sessions[session_id] = (
            transcript, time.monotonic() + self.session_ttl_s
        )
        while self.session_max and len(self._sessions) > self.session_max:
            oldest = min(self._sessions, key=lambda s: self._sessions[s][1])
            self._drop_session(oldest)
        self.metrics.set_gauge("session_active", float(len(self._sessions)))

    def _drop_session(self, session_id: str) -> None:
        self._sessions.pop(session_id, None)
        release = getattr(self.queue.engine, "release_session", None)
        if release is not None:
            release(session_id)
        self.metrics.set_gauge("session_active", float(len(self._sessions)))

    @traced_grpc_handler("tutoring.GetLLMAnswer")
    async def GetLLMAnswer(self, request, context):
        self.metrics.inc("llm_requests")
        # Trailing metadata is buffered until the RPC completes, so it
        # can be set up front: who served this answer + live queue depth
        # (a passive load signal for the router between health polls).
        # Guarded: direct servicer-level tests call with context=None.
        if context is not None:
            trailer = [(QUEUE_DEPTH_METADATA_KEY,
                        str(self.queue.waiting))]
            if self.node_id:
                trailer.append((SERVED_BY_METADATA_KEY, self.node_id))
            context.set_trailing_metadata(tuple(trailer))
        if self.draining:
            self.metrics.inc("tutoring_drain_rejections")
            if context is not None:
                await context.abort(
                    grpc.StatusCode.UNAVAILABLE,
                    "draining: this tutoring node is not admitting new "
                    "work",
                )
            return lms_pb2.QueryResponse(
                success=False,
                response="draining: this tutoring node is not admitting "
                "new work",
            )
        if self.auth_key and not auth.verify_query(
            self.auth_key, request.query, request.token
        ):
            # Only the LMS leader holds the key: direct dials can't bypass
            # the session check and BERT gate (reference defect: token was
            # never read, tutoring_server.py:33-37).
            self.metrics.inc("llm_unauthorized")
            return lms_pb2.QueryResponse(
                success=False, response="Unauthorized: query the LMS, not "
                "the tutoring node."
            )
        if not request.query.strip():
            return lms_pb2.QueryResponse(success=False, response="Empty query.")
        # The caller's remaining budget rides in on the gRPC deadline (and/or
        # the explicit metadata header); thread it into the batcher so a
        # request that expires while queued is shed before its prefill.
        deadline = Deadline.from_grpc_context(context)
        if deadline is not None and deadline.expired:
            self.metrics.inc("shed_expired")
            await context.abort(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                "deadline already expired on arrival",
            )
        prompt = PROMPT_TEMPLATE.format(query=request.query)
        try:
            # Full-answer latency for this RPC; the "ttft" histogram is fed
            # by the batcher from the engine's measured first-token time.
            with self.metrics.time("answer_latency"):
                # The handler's trace fragment rides into the batcher as an
                # explicit span handle: queue internals run on other tasks
                # (and the engine in an executor thread), where contextvars
                # from this handler are not in scope.
                answer = await self.queue.submit(
                    prompt, deadline=deadline, span=get_tracer().current()
                )
        except Overloaded as e:
            # The wire's backpressure signal: clients back off and retry,
            # the LMS breaker counts it toward opening.
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, str(e))
        except DeadlineExpired as e:
            await context.abort(grpc.StatusCode.DEADLINE_EXCEEDED, str(e))
        except Exception:
            log.exception("generation failed")
            self.metrics.inc("llm_failures")
            return lms_pb2.QueryResponse(
                success=False, response="The tutoring model is unavailable."
            )
        return lms_pb2.QueryResponse(success=True, response=answer.strip())

    @traced_grpc_handler("tutoring.StreamLLMAnswer")
    async def StreamLLMAnswer(self, request, context):
        """Server-streaming tutoring answer (resumable-stream contract).

        Chunk offsets count tokens and are monotone and gap-free;
        `request.resume_offset = K` regenerates deterministically and
        delivers only tokens >= K (the failover path: the pool resumes a
        broken stream at the client's delivered offset instead of
        restarting it). The final chunk carries the sha256 hexdigest of
        the full *stripped* answer — byte-identical to what the unary
        GetLLMAnswer would return — so resumed clients verify their
        spliced transcript against it.

        `request.session_id` makes the turn conversational: the prompt
        extends this node's running transcript (turn N's prompt+answer),
        and on completion the transcript is re-published so the radix
        prefix cache serves turn N+1's shared prefix from cached KV.
        """
        self.metrics.inc("llm_requests")
        if context is not None:
            trailer = [(QUEUE_DEPTH_METADATA_KEY,
                        str(self.queue.waiting))]
            if self.node_id:
                trailer.append((SERVED_BY_METADATA_KEY, self.node_id))
            context.set_trailing_metadata(tuple(trailer))
        if self.draining:
            self.metrics.inc("tutoring_drain_rejections")
            if context is not None:
                await context.abort(
                    grpc.StatusCode.UNAVAILABLE,
                    "draining: this tutoring node is not admitting new "
                    "work",
                )
            yield lms_pb2.StreamChunk(
                success=False, final=True,
                text="draining: this tutoring node is not admitting new "
                "work",
            )
            return
        if self.auth_key and not auth.verify_query(
            self.auth_key, request.query, request.token
        ):
            self.metrics.inc("llm_unauthorized")
            yield lms_pb2.StreamChunk(
                success=False, final=True,
                text="Unauthorized: query the LMS, not the tutoring node.",
            )
            return
        if not request.query.strip():
            yield lms_pb2.StreamChunk(success=False, final=True,
                                      text="Empty query.")
            return
        deadline = Deadline.from_grpc_context(context)
        if deadline is not None and deadline.expired:
            self.metrics.inc("shed_expired")
            await context.abort(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                "deadline already expired on arrival",
            )
        # Session turns extend the running transcript verbatim (byte-
        # stable prefix => radix cache splices turn N's KV); fresh
        # streams frame the query exactly like the unary path so
        # stream-vs-unary answers are bit-identical.
        session_id = request.session_id
        transcript = self._session_transcript(session_id) if session_id \
            else ""
        if transcript:
            prompt = transcript + FOLLOWUP_TEMPLATE.format(
                query=request.query)
        else:
            prompt = PROMPT_TEMPLATE.format(query=request.query)
        session = (session_id, self.session_ttl_s) if session_id else None
        sent_any = False
        try:
            with self.metrics.time("answer_latency"):
                async for delta in self.queue.submit_stream(
                    prompt, deadline=deadline,
                    span=get_tracer().current(),
                    resume_offset=request.resume_offset,
                    session=session,
                ):
                    self.metrics.inc("stream_chunks")
                    if delta.final:
                        full = delta.full_text
                        if session_id:
                            self._session_update(session_id, prompt + full)
                        yield lms_pb2.StreamChunk(
                            success=True, text=delta.text,
                            offset=delta.offset, count=delta.count,
                            final=True,
                            digest=hashlib.sha256(
                                full.strip().encode()).hexdigest(),
                        )
                    else:
                        yield lms_pb2.StreamChunk(
                            success=True, text=delta.text,
                            offset=delta.offset, count=delta.count,
                        )
                    sent_any = True
        except Overloaded as e:
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, str(e))
        except DeadlineExpired as e:
            await context.abort(grpc.StatusCode.DEADLINE_EXCEEDED, str(e))
        except asyncio.CancelledError:
            raise
        except Exception:
            log.exception("streamed generation failed")
            self.metrics.inc("llm_failures")
            if not sent_any:
                # No byte delivered yet: fail softly like the unary path.
                yield lms_pb2.StreamChunk(
                    success=False, final=True,
                    text="The tutoring model is unavailable.",
                )
            elif context is not None:
                # Mid-stream: delivered text can't be retracted — surface
                # a hard error so the pool resumes at the client's offset.
                await context.abort(grpc.StatusCode.INTERNAL,
                                    "stream broken mid-answer")


async def _report_metrics(metrics: Metrics, period_s: float) -> None:
    while True:
        await asyncio.sleep(period_s)
        log.info("metrics %s", json.dumps(metrics.snapshot()))


def make_tutoring_admin(service: TutoringService, scorer=None):
    """POST handler for the tutoring node's admin plane. Module-level
    (like lms_server.make_admin) so the in-process semester-sim fleet
    serves the EXACT operator surface the production entrypoint serves.

    POST /admin/drain {"drain": true|false} — stop/resume admission.
    Draining finishes in-flight work; the fleet router ejects the node
    while it drains and re-admits it (warm-up weighted) when it ends.

    POST /admin/score {"texts": [...], "purpose": "grading"|...,
    "job_id"?} — queue one bulk job on the background scoring tenant
    (engine/scoring.py; idempotent on job_id). Quanta run only while the
    interactive queue is empty; progress and results are read back via
    GET /admin/score[/<job-id>]. 404 when the tenant is disabled."""

    async def admin(path: str, body: dict) -> dict:
        if path == "/admin/drain":
            service.set_draining(bool(body.get("drain", True)))
            return {"ok": True, "draining": service.draining,
                    "node_id": service.node_id}
        if path == "/admin/score":
            if scorer is None:
                raise KeyError(path)  # scoring tenant disabled: 404
            texts = body.get("texts")
            if not isinstance(texts, list):
                raise ValueError("score job needs 'texts': [str, ...]")
            job = scorer.submit(
                texts, purpose=str(body.get("purpose", "adhoc")),
                job_id=(str(body["job_id"]) if body.get("job_id")
                        else None),
            )
            return {"ok": True, "node_id": service.node_id, **job}
        raise KeyError(path)

    return admin


def make_tutoring_health(service: TutoringService, queue,
                         engine_name: str, max_queue: int, scorer=None):
    """/healthz provider: admission pressure + fleet lifecycle state
    (the router's health poller reads `draining`/`queued`/`node_id`),
    plus the device this node computes on and its compile-cache account
    (a JAX-free launcher reads both here)."""
    device = device_info()

    def health() -> dict:
        doc = {
            "ok": True,
            "engine": engine_name,
            "node_id": service.node_id,
            "device": device,
            "device_memory": device_memory(),
            "compile_cache": cache_stats(),
            # Admission pressure at a glance (details in /metrics:
            # shed_overload / shed_expired). `queued` is what the
            # bound is enforced against: it includes the engine's
            # pre-slot backlog.
            "queue_depth_limit": max_queue,
            "queued": queue.waiting,
            # Drain lifecycle: true while this node refuses new work and
            # finishes what it holds; the router ejects it meanwhile.
            "draining": service.draining,
            # Live multi-turn tutoring sessions held on this node (stream
            # path; transcripts + prefix-cache pins expire on [sessions]
            # ttl_s).
            "sessions": len(service._sessions),
        }
        if scorer is not None:
            # Background-tenant surface: backlog/quanta/completed at a
            # glance (the LMS router's background route reads `queued`
            # above for placement; scoring detail is informational).
            doc["scoring"] = scorer.stats()
        return doc

    return health


async def serve_async(
    port: int,
    engine,
    *,
    max_batch: int = 8,
    max_queue: int = 0,
    metrics: Optional[Metrics] = None,
    metrics_period_s: float = 60.0,
    auth_key: Optional[str] = None,
    metrics_port: Optional[int] = None,
    telemetry: bool = True,
    telemetry_interval_s: float = 1.0,
    telemetry_ring: int = 600,
    node_id: Optional[str] = None,
    scoring: bool = False,
    scoring_max_job_texts: int = 4096,
    scoring_jobs_retained: int = 32,
    session_ttl_s: float = 600.0,
    session_max: int = 256,
) -> grpc.aio.Server:
    """Start (and return) the aio server; caller awaits termination.

    `engine` is served through a `PagedQueue` (continuous batching:
    requests join the running batch mid-decode) whatever it is: a
    `PagedEngine`, or any object with the queue's `ENGINE_CONTRACT`; one
    without it is refused here with a `TypeError`. `max_batch` is unused
    (kept for the callers that pass it; ROADMAP D25). `max_queue` bounds
    waiting requests (0 = unbounded): beyond it new
    RPCs are refused with RESOURCE_EXHAUSTED instead of queueing forever.
    `scoring` attaches the background bulk-scoring tenant
    (engine/scoring.ScoringManager + POST/GET /admin/score): quanta run
    only while the interactive queue is empty and yield at
    single-dispatch boundaries.
    """
    metrics = metrics or Metrics()
    scorer = None
    if scoring:
        scorer = ScoringManager(
            engine, metrics=metrics,
            max_job_texts=scoring_max_job_texts,
            jobs_retained=scoring_jobs_retained,
        )
    queue = PagedQueue(engine, metrics=metrics, max_queue=max_queue,
                       scorer=scorer)
    await queue.start()
    server = grpc.aio.server(
        options=[
            ("grpc.max_send_message_length", 50 * 1024 * 1024),
            ("grpc.max_receive_message_length", 50 * 1024 * 1024),
        ]
    )
    service = TutoringService(queue, metrics, auth_key=auth_key,
                              node_id=node_id,
                              session_ttl_s=session_ttl_s,
                              session_max=session_max)
    rpc.add_TutoringServicer_to_server(service, server)
    server._port = server.add_insecure_port(f"[::]:{port}")
    await server.start()
    # Keep strong references (asyncio tasks are weakly held by the loop) and
    # expose them for shutdown: callers should cancel _metrics_task /
    # _watchdog_task and await
    # _queue.close() after stop().
    server._metrics_task = asyncio.get_running_loop().create_task(
        _report_metrics(metrics, metrics_period_s)
    )
    # Heartbeat watchdog on the serving loop: an engine call that
    # accidentally blocks the loop (instead of running in the executor)
    # shows up as serving_tick_lag/serving_tick_stalls in /metrics.
    server._watchdog_task = asyncio.get_running_loop().create_task(
        make_serving_watchdog(metrics).run()
    )
    server._queue = queue
    server._health = None
    # Node-local telemetry timeline (serving tok/s, queue depth, TTFT
    # percentiles over time), served at GET /admin/timeline; the cluster
    # aggregator (scripts/telemetry.py) merges it with the LMS nodes'.
    server._telemetry_sampler = None
    if telemetry:
        server._telemetry_sampler = TimelineSampler(
            metrics, interval_s=telemetry_interval_s,
            max_points=telemetry_ring,
        ).start()
        # The sampler is a thread, not a loop task: it outlives the
        # event loop unless stopped. Piggyback on server.stop() so every
        # existing caller (tests included) tears it down without a new
        # contract item.
        _grpc_stop = server.stop

        async def _stop_with_sampler(grace):
            if server._telemetry_sampler is not None:
                server._telemetry_sampler.stop()
            return await _grpc_stop(grace)

        server.stop = _stop_with_sampler
    if metrics_port is not None:
        from ..utils.healthz import HealthServer

        sampler = server._telemetry_sampler

        async def admin_get(path: str) -> dict:
            # GET /admin/trace[/id]: this node's flight-recorder fragments
            # (engine spans live HERE; trace_report merges them with the
            # LMS nodes' fragments into one waterfall).
            # GET /admin/timeline: the telemetry ring.
            # GET /admin/score[/<job-id>]: the scoring tenant's job list
            # / one job's progress+results (404 when disabled).
            if path == "/admin/timeline":
                return timeline_admin_get(
                    path, sampler.timeline if sampler is not None else None
                )
            if path.startswith("/admin/score"):
                return score_admin_get(path, scorer)
            return trace_admin_get(path)

        server._health = HealthServer(
            metrics,
            health=make_tutoring_health(service, queue,
                                        type(engine).__name__, max_queue,
                                        scorer=scorer),
            admin=make_tutoring_admin(service, scorer=scorer),
            admin_get=admin_get,
            port=metrics_port,
        )
        bound = await server._health.start()
        log.info("health/metrics endpoint on http://127.0.0.1:%d", bound)
    log.info("tutoring server listening on %d", server._port)
    return server


def _dist_version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", default=None,
                        help="TOML deployment file (config.py [tutoring] + "
                             "[sampling]); explicit flags override it")
    parser.add_argument("--port", type=int, default=50054)
    parser.add_argument("--model", default="gpt2")
    parser.add_argument("--checkpoint", default=None,
                        help="HF-layout .safetensors weights")
    parser.add_argument("--vocab", default=None, help="GPT-2 vocab.json")
    parser.add_argument("--merges", default=None, help="GPT-2 merges.txt")
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel ways (MoE presets "
                             "gpt2-moe/moe-tiny; experts shard over the "
                             "ep mesh axis)")
    parser.add_argument(
        "--quant", default=None, choices=["int8"],
        help="weight-only int8 serving (halves the parameter bytes the "
        "decode loop streams; near-lossless, see tests/test_quant.py)",
    )
    parser.add_argument(
        "--kv-quant", action="store_true",
        help="int8 KV cache with per-slot scales",
    )
    parser.add_argument(
        "--approx-topk", action="store_true",
        help="approximate top-k sampling (~0.95 recall, +12%% decode "
        "throughput); default is bit-exact HF semantics",
    )
    parser.add_argument(
        "--spec-tokens", type=int, default=0,
        help="speculative decoding: verify this many prompt-lookup draft "
        "tokens per step (engine/draft.py kernels; exact — the output "
        "distribution is unchanged): per-slot verify windows; acceptance "
        "visible as the spec_tokens_per_window gauge and "
        "spec_accepted_tokens counter in /metrics. Best when per-step "
        "fixed costs dominate; 0 = off",
    )
    parser.add_argument("--max-new-tokens", type=int, default=128)
    parser.add_argument("--max-batch", type=int, default=8,
                        help="decode slots where --slots is absent")
    parser.add_argument(
        "--queue-depth", type=int, default=64,
        help="bounded admission: waiting requests beyond this are refused "
        "with RESOURCE_EXHAUSTED (0 = unbounded)",
    )
    parser.add_argument("--slots", type=int, default=None,
                        help="decode slots (default: --max-batch)")
    parser.add_argument("--chunk", type=int, default=16,
                        help="tokens per device chunk "
                        "(verify windows when --spec-tokens is set); "
                        "admission joins at dispatch boundaries")
    parser.add_argument("--megastep", type=int, default=1,
                        help="megastep: starting K of the "
                        "TTFT-aware controller — K chunks run "
                        "back-to-back on device per host dispatch "
                        "(1 = the plain chunk loop)")
    parser.add_argument("--megastep-max", type=int, default=0,
                        help="megastep controller ceiling: K grows toward "
                        "this while the pending queue is empty; under "
                        "load K is capped at the next guaranteed "
                        "slot-free horizon, holding admission latency "
                        "(worst-case wait is K*chunk device steps); "
                        "0 = follow --megastep")
    parser.add_argument("--inflight", type=int, default=2,
                        help="dispatch pipelining depth: "
                        "programs dispatched before the oldest is read "
                        "back (1 = serialized)")
    parser.add_argument("--prefix-cache", action="store_true",
                        help="radix shared-prefix KV cache: "
                        "prompts sharing a course/assignment context "
                        "prefill it once; later requests splice the "
                        "cached blocks and prefill only their suffix "
                        "(hit rate in /metrics prefix_cache_hit_rate)")
    parser.add_argument("--prefix-cache-blocks", type=int, default=512,
                        help="shared-prefix cache block budget (16 "
                        "tokens/block; LRU eviction, blocks referenced "
                        "by live slots are never freed)")
    parser.add_argument("--prefill-chunk-tokens", type=int, default=32,
                        help="admission: arriving prompts "
                        "are staged into the decode state and prefilled "
                        "this many tokens (>= 1) per decode iteration "
                        "INSIDE the megastep program, so admission never "
                        "pauses the decode train (admission latency is "
                        "bounded by scan iterations, not prompt length)")
    parser.add_argument("--draft-source", default="prompt_lookup",
                        choices=["prompt_lookup", "ngram"],
                        help="speculative draft source (with "
                        "--spec-tokens): prompt_lookup = most-recent "
                        "n-gram continuation; ngram = per-slot "
                        "modal-continuation table (higher acceptance "
                        "at temperature>0)")
    parser.add_argument("--scoring", action="store_true",
                        help="background bulk-scoring tenant "
                        "(engine/scoring.py): warmup-cover the score "
                        "program domain and co-schedule preemptible "
                        "score quanta into idle lanes — POST/GET "
                        "/admin/score on the metrics plane; quanta run "
                        "only while the interactive queue is empty "
                        "([scoring] in the TOML)")
    parser.add_argument("--scoring-max-job-texts", type=int, default=4096,
                        help="admission cap per bulk score job (texts)")
    parser.add_argument("--scoring-jobs-retained", type=int, default=32,
                        help="finished score jobs kept for "
                        "GET /admin/score")
    parser.add_argument("--node-id", default=None,
                        help="fleet member identity: rides every "
                        "answer's x-served-by response trailer and "
                        "/healthz so the LMS routing tier, waterfalls, "
                        "and the ledger can attribute answers (default: "
                        "tut-<port>)")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="HTTP /healthz + /metrics endpoint (0 = "
                             "ephemeral); omit to disable. Also serves "
                             "POST /admin/drain (stop admission, finish "
                             "in-flight work; the fleet router ejects "
                             "this node until the drain ends)")
    parser.add_argument("--no-telemetry", action="store_true",
                        help="disable the node-local telemetry timeline "
                             "(sampler thread + GET /admin/timeline)")
    parser.add_argument("--telemetry-interval", type=float, default=1.0,
                        help="telemetry timeline sample interval in "
                             "seconds")
    parser.add_argument("--telemetry-ring", type=int, default=600,
                        help="telemetry timeline ring length (samples "
                             "retained)")
    parser.add_argument("--no-warmup", action="store_true")
    parser.add_argument(
        "--strict-dispatch", action="store_true",
        help="assertion mode for dispatch hygiene (utils/guards.py): any "
        "device->host readback outside a `with intended_transfer():` "
        "block raises instead of silently stalling the hot path (TPU/GPU "
        "backends; CPU readbacks are zero-copy and exempt)",
    )
    parser.add_argument(
        "--auth-key-file", default=None,
        help="file holding the LMS↔tutoring shared secret; when set, only "
        "queries HMAC-signed by the LMS leader are answered",
    )
    parser.add_argument(
        "--jax-platform", default="default", choices=["cpu", "default"],
        help="'cpu' for CPU-only runs (tests/dev); 'default' means the "
        "TPU: the server refuses to start on anything else",
    )
    args = parser.parse_args(argv)
    args.telemetry = not args.no_telemetry
    if args.config:
        from ..config import apply_file_defaults, load_config

        cfg = load_config(args.config)
        t, s = cfg.tutoring, cfg.sampling
        apply_file_defaults(args, parser, {
            "port": t.port, "model": t.model, "checkpoint": t.checkpoint,
            "vocab": t.vocab, "merges": t.merges, "tp": t.tp,
            "ep": t.ep,
            "quant": t.quant, "max_new_tokens": s.max_new_tokens,
            "max_batch": t.max_batch,
            "queue_depth": cfg.resilience.queue_depth,
            "slots": t.slots, "chunk": t.chunk,
            "megastep": t.megastep, "megastep_max": t.megastep_max,
            "inflight": t.inflight,
            "prefix_cache": t.prefix_cache,
            "prefix_cache_blocks": t.prefix_cache_blocks,
            "prefill_chunk_tokens": t.prefill_chunk_tokens,
            "draft_source": t.draft_source,
            "auth_key_file": t.auth_key_file,
            # store_true flags merge the same way: presence in argv is what
            # marks them explicit, so the file fills only absent ones.
            "kv_quant": t.kv_quant,
            "approx_topk": s.approx_top_k,
            "spec_tokens": t.spec_tokens,
            "scoring": cfg.scoring.enabled,
            "scoring_max_job_texts": cfg.scoring.max_job_texts,
            "scoring_jobs_retained": cfg.scoring.jobs_retained,
            "telemetry_interval": cfg.telemetry.sample_interval_s,
            "telemetry_ring": cfg.telemetry.ring_points,
        }, argv=argv)
        args.session_ttl_s = cfg.sessions.ttl_s
        args.session_max = cfg.sessions.max_sessions
        if not args.no_telemetry:
            args.telemetry = cfg.telemetry.enabled
        args.sampling_overrides = dict(
            temperature=s.temperature, top_k=s.top_k, top_p=s.top_p,
            repetition_penalty=s.repetition_penalty,
        )
        # Rebuild the process tracer from [tracing] (ring size, exemplar
        # pins, kill switch) before any request can open a span.
        from ..utils.tracing import configure_from

        configure_from(cfg.tracing)
    else:
        args.sampling_overrides = {}
        args.session_ttl_s = 600.0
        args.session_max = 256
    if args.jax_platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    # Multi-host: joins the JAX cluster when JAX_COORDINATOR_ADDRESS (or
    # Cloud TPU metadata) is present, making jax.devices() global so the
    # tp/dp mesh spans hosts; no-op for the common single-host run.
    if initialize_multihost():
        log.info("joined multi-host JAX cluster")

    # Say what this node computes on, and never serve from a CPU that
    # nobody asked for: without --jax-platform cpu the device is the TPU.
    device = device_info()
    log.info(
        "device: platform=%s device_kind=%s count=%d jax=%s jaxlib=%s "
        "libtpu=%s", device["platform"], device["kind"], device["count"],
        *(_dist_version(d) for d in ("jax", "jaxlib", "libtpu")),
    )
    if device["platform"] != "tpu" and args.jax_platform != "cpu":
        raise SystemExit(
            f"no TPU: JAX initialized platform {device['platform']!r} "
            "(pass --jax-platform cpu for a CPU-only run)"
        )

    if args.strict_dispatch:
        # Before engine construction so warmup runs under the same guard:
        # a sync the warmup path tolerates must not hide in the live path.
        from ..utils.guards import enable_strict_dispatch

        enable_strict_dispatch()

    sampling = SamplingParams.reference_defaults(
        max_new_tokens=args.max_new_tokens, approx_top_k=args.approx_topk,
        **args.sampling_overrides,
    )
    config = EngineConfig(
        model=args.model,
        checkpoint=args.checkpoint,
        vocab_path=args.vocab,
        merges_path=args.merges,
        sampling=sampling,
        tp=args.tp,
        ep=args.ep,
        quant=args.quant,
        kv_quant=args.kv_quant,
        spec_tokens=args.spec_tokens,
        draft_source=args.draft_source,
        # Scoring-tenant warmup coverage: with --scoring, warmup compiles
        # the score program's (batch bucket x length bucket) domain so
        # the first bulk job pays zero live XLA compiles.
        scoring=args.scoring,
    )
    # --max-batch is the decode slot count unless --slots names it (with
    # megastep enabled, raising slots amortizes the per-dispatch host
    # overhead over more lanes — cluster.toml ships 16).
    # spec_tokens rides in on the EngineConfig: the engine verifies
    # per-slot draft windows (chunk then counts verify WINDOWS per chunk,
    # up to spec_tokens+1 tokens each).
    engine = PagedEngine(config, slots=args.slots or args.max_batch,
                         chunk=args.chunk, inflight=args.inflight,
                         megastep=args.megastep,
                         megastep_max=args.megastep_max,
                         prefix_cache=args.prefix_cache,
                         prefix_cache_blocks=args.prefix_cache_blocks,
                         prefill_chunk_tokens=args.prefill_chunk_tokens)
    if not args.no_warmup:
        log.info("warmup compile took %.1fs", engine.warmup())

    auth_key = None
    if args.auth_key_file:
        with open(args.auth_key_file) as fh:
            auth_key = fh.read().strip()

    async def run():
        server = await serve_async(
            args.port, engine, max_queue=args.queue_depth,
            auth_key=auth_key,
            metrics_port=args.metrics_port,
            telemetry=args.telemetry,
            telemetry_interval_s=args.telemetry_interval,
            telemetry_ring=args.telemetry_ring,
            node_id=args.node_id or f"tut-{args.port}",
            scoring=args.scoring,
            scoring_max_job_texts=args.scoring_max_job_texts,
            scoring_jobs_retained=args.scoring_jobs_retained,
            session_ttl_s=args.session_ttl_s,
            session_max=args.session_max,
        )
        await server.wait_for_termination()

    asyncio.run(run())


if __name__ == "__main__":
    main()
