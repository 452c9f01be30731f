"""The serving queue: continuous batching in front of the engine.

The wire contract is unary (`Tutoring.GetLLMAnswer`, one query per RPC —
reference: GUI_RAFT_LLM_SourceCode/lms.proto:123-125), so batching must
happen *inside* the server without changing the RPC (SURVEY.md §7 hard part
3). `PagedQueue` drives the engine step by step and hands it what arrived
between two dispatches, so concurrent student queries share the running
device batch and a late one joins it at the next dispatch boundary.

The reference handles concurrency with a 10-thread pool and sequential
model.generate calls (tutoring_server.py:40) — throughput 1/latency. Here
throughput scales with the slots until the chip saturates.

What the queue asks of an engine is `ENGINE_CONTRACT` below; everything
else it reaches through `getattr` and does without where it is absent
(`engine.paged.PagedEngine` has all of it, and so has the simulator's
JAX-free double, `sim.cluster.EchoEngine`).

Overload behavior: admission is bounded — `max_queue` waiting
requests, beyond which `submit()` raises `Overloaded` (the server maps it
to RESOURCE_EXHAUSTED, the wire's backpressure signal) instead of growing
an unbounded backlog whose tail nobody is still waiting for. Requests may
carry a `Deadline`; one that expires while queued is dropped *before* its
prefill is dispatched (counter `shed_expired`), so a saturated chip only
computes answers that can still be delivered.

Two-tenant scheduling: with a `ScoringManager`
(engine/scoring.py) attached, the runner co-schedules background bulk
scoring into idle lanes — Orca-style iteration-level scheduling decides
*per dispatch* what runs. A scoring quantum (one batch-bucket forward) is
admitted ONLY while the interactive pending queue is empty and the engine
holds no in-flight work, and the runner re-checks interactive arrivals at
every quantum boundary, so an interactive request waits behind at most
one in-flight quantum (the wait lands in `score_preempt_wait_ms`).
Interactive traffic never queues behind bulk work; bulk work drains the
idle gap between the serving load and the chip's saturation ceiling.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import time
from collections import deque
from typing import Any, AsyncIterator, Deque, Dict, List, Optional, Tuple

from ..utils import metrics_registry as metric
from ..utils.resilience import Deadline, DeadlineExpired, Overloaded
from ..utils.tracing import FLAG_DEADLINE, NULL_SPAN, get_tracer
from .spans import (
    BETWEEN_STEPS,
    NO_SPANS,
    REAP_WAIT,
    Span,
    SpanSum,
    turn_budget,
)

log = logging.getLogger(__name__)

# Queue items: (prompt, deadline-or-None, result future, request span,
# its open queue.wait child, monotonic enqueue time). Spans are NULL_SPAN
# when the request entered through an untraced edge, so the scheduling
# code never branches on tracing; the enqueue time feeds the scoring
# tenant's preemption-wait account (score_preempt_wait_ms).
_Item = Tuple[str, Optional[Deadline], asyncio.Future, Any, Any, float]

# The names every engine handed to `PagedQueue` must have; the queue
# refuses anything else when it is built, not at the first request.
ENGINE_CONTRACT = ("submit", "step", "has_work", "pop_ttfts", "reset",
                   "decode_tokens")

# ---------------------------------------------------------------- streaming
#
# `submit_stream()` is an async iterator of StreamDelta feeding the
# StreamLLMAnswer wire path. The resumable-stream contract:
#
# - offsets count TOKENS; within one logical stream they are monotone and
#   gap-free (delta i+1 starts exactly where delta i ended);
# - `resume_offset=K` asks for a stream whose first delta starts at token
#   K: the engine regenerates deterministically and the text of tokens
#   [0, K) is skipped, so a client that already holds K tokens' text can
#   splice the tail without duplication;
# - the final delta carries `full_text` — the COMPLETE answer from token 0
#   — so the wire layer can digest it (the client verifies its spliced
#   transcript against the digest; any resume divergence is caught there).
#
# Live token progress comes off the engine's incremental channel
# (`stream_snapshot`); an engine without one streams a single final delta.


@dataclasses.dataclass(frozen=True)
class StreamDelta:
    """One increment of a streamed answer: the decoded text of tokens
    [offset, offset + count). `full_text` is set on the final delta only
    (the complete answer from token 0, digest source)."""

    offset: int
    count: int
    text: str
    final: bool
    full_text: str = ""


@dataclasses.dataclass
class _StreamState:
    """Per-stream emission state the queue's runner advances between
    engine steps. `abs_text` is the decoded text through `sent_tokens`
    ABSOLUTE tokens (None until the resume skip is resolved); deltas are
    emitted only at decode-prefix-stable boundaries — a snapshot whose
    decode does not extend the already-emitted text verbatim is held
    back until more tokens stabilize it."""

    q: "asyncio.Queue[StreamDelta]"
    skip: int = 0
    rid: Optional[int] = None
    sent_tokens: int = 0
    abs_text: Optional[str] = None
    last_push: Optional[float] = None  # monotonic time of the last delta


def _observe_program_times(metrics, entries) -> None:
    """Feed engine-reported (program, start_unix, wall_s) dispatch times
    into the per-program histogram series. Unknown program names are
    skipped (an engine may report more detail than the registry names)."""
    if metrics is None:
        return
    for pname, _start, wall_s in entries:
        if pname in metric.ENGINE_PROGRAM_HISTOGRAMS:
            metrics.hist(
                metric.ENGINE_PROGRAM_HISTOGRAMS[pname]
            ).observe(wall_s)


async def _run_score_quantum(owner) -> None:
    """Dispatch ONE background-scoring quantum off-loop and record its
    window. Called only while the interactive
    pending queue is empty and the engine is idle — the admission policy
    the scoring tenant promises. The engine's `score` program time is
    drained into the `engine_prog_score` histogram here (there is no
    request batch to attribute it to)."""
    scorer = owner._scorer
    loop = asyncio.get_running_loop()
    t0 = time.monotonic()
    with get_tracer().span("scoring.quantum",
                           job=scorer.current_job_id() or "") as sp:
        did = await loop.run_in_executor(
            None, scorer.run_quantum, owner.waiting
        )
        sp.set_attr("did_work", bool(did))
    # The quantum window: interactive arrivals inside it waited for the
    # boundary; _note_preempt charges them to score_preempt_wait_ms.
    owner._last_quantum = (t0, time.monotonic())
    pop = getattr(owner.engine, "pop_program_times", None)
    if pop is not None:
        _observe_program_times(owner.metrics, pop())


async def _next_item(owner, incoming: asyncio.Queue) -> Optional[_Item]:
    """The two-tenant idle wait: interactive work first, always; a
    scoring quantum only when none is pending; block on BOTH arrival
    sources otherwise. Returns an interactive item, or None after a
    scoring round (the caller loops — arrivals are re-checked at every
    quantum boundary, so nothing waits behind more than one quantum)."""
    if not incoming.empty():
        return incoming.get_nowait()
    scorer = owner._scorer
    if scorer is None:
        return await incoming.get()
    if scorer.has_work:
        await _run_score_quantum(owner)
        return None
    getter = asyncio.ensure_future(incoming.get())
    waker = asyncio.ensure_future(scorer.wake_event().wait())
    try:
        await asyncio.wait({getter, waker},
                           return_when=asyncio.FIRST_COMPLETED)
    finally:
        # An un-popped item survives getter cancellation (asyncio.Queue
        # re-wakes the next getter); the wake flag is level-triggered.
        for t in (getter, waker):
            if not t.done():
                t.cancel()
        await asyncio.gather(getter, waker, return_exceptions=True)
    if getter.done() and not getter.cancelled() and (
        getter.exception() is None
    ):
        # Already-done asyncio.Task: result() is immediate.
        return getter.result()  # lint: disable=no-blocking-in-async
    scorer.clear_wake()
    return None


@dataclasses.dataclass
class _ReqTrace:
    """Per-request trace state a paged request carries from admission to
    completion. Continuous batching has no per-request device batch, so
    the engine span is synthesized at completion (admission -> last
    token) and per-program dispatch times are attributed as SHARED
    aggregates: every program dispatched while the request was in
    flight (diff of `prog_snapshot` against the queue's accumulator)."""

    span: Any                 # the request's trace span (or NULL_SPAN)
    qspan: Any                # its open queue.wait child
    submitted_mono: float
    submitted_unix: float
    queued_s: float           # filled once the engine reports the wait
    prog_snapshot: Dict[str, Tuple[float, float]]
    # Shared-prefix cache hit at admission (prompt tokens spliced from
    # the radix tree; None until the engine reports it, stays None on
    # engines without the prefix contract). Attributed to the request's
    # engine.stage span (the admission program) as prefix_hit_tokens.
    prefix_hit: Optional[int] = None


class PagedQueue:
    """Continuous-batching front-end over `engine.paged.PagedEngine` (or
    any object with `ENGINE_CONTRACT`).

    The worker drives the engine step by step — new submissions are
    drained into the engine *between* dispatches, so a request arriving
    mid-decode joins the running batch at the next dispatch boundary (one
    chunk away, or up to K chunks when the engine is running megasteps;
    the engine's K controller aligns megastep boundaries with the next
    guaranteed slot-free while anything waits, so a waiting request joins
    no later than the chunk loop would have admitted it) rather than
    queueing behind the whole group (the reference serves strictly one at
    a time — reference: GUI_RAFT_LLM_SourceCode/tutoring_server.py:21-29).
    """

    def __init__(self, engine, metrics=None, max_queue: int = 0,
                 scorer=None):
        missing = [n for n in ENGINE_CONTRACT if not hasattr(engine, n)]
        if missing:
            raise TypeError(
                f"PagedQueue needs an engine with {ENGINE_CONTRACT}; "
                f"{type(engine).__name__} lacks {missing}"
            )
        self.engine = engine
        self.metrics = metrics
        self.max_queue = max_queue  # bound on not-yet-admitted requests
        # Background scoring tenant (engine/scoring.ScoringManager or
        # None): quanta run only while nothing interactive is pending
        # AND the engine holds no in-flight decode work (the outer loop
        # only reaches the idle wait once has_work is False).
        self._scorer = scorer
        self._last_quantum: Optional[Tuple[float, float]] = None  # guarded-by: event-loop
        self.max_preempt_wait_s = 0.0                # guarded-by: event-loop
        # Loop-confined: everything below is touched only from coroutines
        # on the serving loop. The engine's step() runs in an executor
        # thread, but it never sees these containers — admissions and
        # reaps happen on the runner coroutine between steps.
        self._incoming: asyncio.Queue[_Item] = asyncio.Queue()  # guarded-by: event-loop
        self._futures: Dict[int, asyncio.Future] = {}  # guarded-by: event-loop
        # Streaming registry: future -> stream state while the request
        # waits for admission (no rid yet), re-keyed to rid -> state at
        # _admit. Session turns ride the same handoff (future ->
        # (session_id, pin ttl), applied to the engine at _admit).
        self._stream_reg: Dict[asyncio.Future, _StreamState] = {}  # guarded-by: event-loop
        self._streams: Dict[int, _StreamState] = {}  # guarded-by: event-loop
        self._session_reg: Dict[asyncio.Future, Tuple[str, float]] = {}  # guarded-by: event-loop
        # rid -> deadline for requests sitting in the ENGINE's pending list
        # (handed over by _admit but no slot yet — prefill hasn't run).
        self._pending_deadlines: Dict[int, Deadline] = {}  # guarded-by: event-loop
        self._spans: Dict[int, _ReqTrace] = {}       # guarded-by: event-loop
        # Cumulative per-program (count, wall_s) since queue start; each
        # request snapshots it at submit and diffs at completion.
        self._prog_cum: Dict[str, List[float]] = {}  # guarded-by: event-loop
        # Recent (monotonic time, emitted tokens) reaps feeding the
        # serving_tokens_per_s utilization gauge — a sliding few-second
        # window, not a run ratio, so the gauge tracks the CURRENT load
        # the capacity model bins against.
        self._tok_window: Deque[Tuple[float, int]] = deque()  # guarded-by: event-loop
        self._tok_window_s = 5.0
        self._runner: Optional[asyncio.Task] = None  # guarded-by: event-loop
        self._closed = False                         # guarded-by: event-loop

    @property
    def waiting(self) -> int:
        """Requests admitted nowhere yet: queued here plus backlogged in
        the engine (the runner drains _incoming eagerly, so the engine's
        pre-slot pending list is where the real backlog accumulates).
        The `max_queue` bound is enforced against this; healthz reports
        it."""
        return self._incoming.qsize() + getattr(self.engine, "backlog", 0)

    def _inc(self, name: str) -> None:
        if self.metrics is not None:
            self.metrics.inc(name)

    async def start(self) -> None:
        if self._runner is None:
            self._runner = asyncio.create_task(self._run())

    async def close(self) -> None:
        self._closed = True
        if self._runner is not None:
            self._runner.cancel()
            try:
                await self._runner
            except asyncio.CancelledError:
                pass
            self._runner = None
        while not self._incoming.empty():
            _, _, fut, _, qspan, _ = self._incoming.get_nowait()
            qspan.end()
            if not fut.done():
                fut.set_exception(RuntimeError("paged queue closed"))
        for fut in self._futures.values():
            if not fut.done():
                fut.set_exception(RuntimeError("paged queue closed"))
        for fut in self._stream_reg:
            if not fut.done():
                fut.set_exception(RuntimeError("paged queue closed"))
        for entry in self._spans.values():
            entry.qspan.end()
        self._futures.clear()
        self._pending_deadlines.clear()
        self._spans.clear()
        self._stream_reg.clear()
        self._streams.clear()
        self._session_reg.clear()

    async def submit(self, prompt: str,
                     deadline: Optional[Deadline] = None,
                     span: Any = None) -> str:
        if self._closed:
            raise RuntimeError("paged queue is closed")
        if deadline is not None and deadline.expired:
            self._inc("shed_expired")
            raise DeadlineExpired("expired before enqueue")
        if self.max_queue and self.waiting >= self.max_queue:
            self._inc("shed_overload")
            raise Overloaded(
                f"paged admission queue full ({self.waiting} waiting)"
            )
        span = span if span is not None else NULL_SPAN
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        await self._incoming.put(
            (prompt, deadline, fut, span, span.child("queue.wait"),
             time.monotonic())
        )
        return await fut

    async def submit_stream(
        self, prompt: str,
        deadline: Optional[Deadline] = None,
        span: Any = None,
        resume_offset: int = 0,
        session: Optional[Tuple[str, float]] = None,
    ) -> AsyncIterator[StreamDelta]:
        """Incremental token-yield stream: deltas are emitted as the
        engine's continuous-batching steps produce tokens (see the
        module streaming contract for offset/resume semantics).
        `session=(session_id, ttl_s)` marks the request as a tutoring
        session turn: its transcript is published into the radix cache
        and session-pinned at finish."""
        if self._closed:
            raise RuntimeError("paged queue is closed")
        if deadline is not None and deadline.expired:
            self._inc("shed_expired")
            raise DeadlineExpired("expired before enqueue")
        if self.max_queue and self.waiting >= self.max_queue:
            self._inc("shed_overload")
            raise Overloaded(
                f"paged admission queue full ({self.waiting} waiting)"
            )
        span = span if span is not None else NULL_SPAN
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        st = _StreamState(q=asyncio.Queue(),
                          skip=max(0, int(resume_offset)))
        self._stream_reg[fut] = st
        if session is not None:
            self._session_reg[fut] = session
        await self._incoming.put(
            (prompt, deadline, fut, span, span.child("queue.wait"),
             time.monotonic())
        )
        try:
            while True:
                getter = asyncio.ensure_future(st.q.get())
                await asyncio.wait({getter, fut},
                                   return_when=asyncio.FIRST_COMPLETED)
                if getter.done() and not getter.cancelled():
                    # Already-done future: result() is immediate.
                    delta = getter.result()  # lint: disable=no-blocking-in-async
                    yield delta
                    if delta.final:
                        return
                    continue
                getter.cancel()
                await asyncio.gather(getter, return_exceptions=True)
                # The result future resolved first: propagate its failure,
                # or drain deltas the runner pushed in the same iteration.
                exc = fut.exception()
                if exc is not None:
                    raise exc
                while not st.q.empty():
                    delta = st.q.get_nowait()
                    yield delta
                    if delta.final:
                        return
                # Defensive: the engine resolved the answer without the
                # stream channel reporting a final (shouldn't happen on
                # the paged engine) — degrade to one final delta.
                # fut resolved first (FIRST_COMPLETED, getter not done),
                # so result() is immediate.
                text = fut.result()  # lint: disable=no-blocking-in-async
                sent = st.abs_text or ""
                yield StreamDelta(
                    offset=st.sent_tokens, count=0,
                    text=text[len(sent):] if text.startswith(sent) else "",
                    final=True, full_text=text,
                )
                return
        finally:
            self._stream_reg.pop(fut, None)
            self._session_reg.pop(fut, None)
            if st.rid is not None:
                self._streams.pop(st.rid, None)
                unwatch = getattr(self.engine, "stream_unwatch", None)
                if unwatch is not None:
                    unwatch(st.rid)
            if fut.done() and not fut.cancelled():
                fut.exception()  # consumed above; mark retrieved

    def _note_preempt(self, t_enq: float) -> None:
        """Charge an interactive arrival that landed inside the last
        scoring quantum's window the wait it paid for the boundary."""
        if self._last_quantum is None:
            return
        q0, q1 = self._last_quantum
        if q0 <= t_enq < q1:
            wait_s = q1 - t_enq
            self.max_preempt_wait_s = max(self.max_preempt_wait_s, wait_s)
            if self.metrics is not None:
                self.metrics.inc("score_preempt_wait_ms",
                                 max(1, int(wait_s * 1000.0)))

    def _admit(self, prompt: str, deadline: Optional[Deadline],
               fut: asyncio.Future, span: Any, qspan: Any,
               t_enq: float) -> None:
        self._note_preempt(t_enq)
        # Shed before prefill: a queue-expired request never enters the
        # engine (its prefill chunk is the expensive step).
        if deadline is not None and deadline.expired:
            self._inc("shed_expired")
            qspan.end()
            span.flag(FLAG_DEADLINE)
            self._stream_reg.pop(fut, None)
            self._session_reg.pop(fut, None)
            if not fut.done():
                fut.set_exception(
                    DeadlineExpired("expired while queued; prefill skipped")
                )
            return
        # The tokenizer's pass, under its own name in a trace; then the
        # one wait before `queue_wait`'s clock starts (engine.submit
        # stamps the request's submit_time): the stay in `_incoming`
        # plus that pass.
        with Span("queue.submit"):
            rid = self.engine.submit(prompt)
        if self.metrics is not None:
            self.metrics.hist("incoming_wait").observe(
                time.monotonic() - t_enq)
        self._futures[rid] = fut
        self._spans[rid] = _ReqTrace(span, qspan, time.monotonic(),
                                     time.time(), 0.0,
                                     self._prog_snapshot())
        if deadline is not None:
            self._pending_deadlines[rid] = deadline
        st = self._stream_reg.pop(fut, None)
        if st is not None:
            st.rid = rid
            self._streams[rid] = st
            watch = getattr(self.engine, "stream_watch", None)
            if watch is not None:
                watch(rid)
        session = self._session_reg.pop(fut, None)
        if session is not None:
            mark = getattr(self.engine, "mark_session", None)
            if mark is not None:
                mark(rid, session[0], session[1])

    def _prog_snapshot(self) -> Dict[str, Tuple[float, float]]:
        return {k: (v[0], v[1]) for k, v in self._prog_cum.items()}

    def _drain_incoming(self) -> None:
        while not self._incoming.empty():
            item = self._incoming.get_nowait()
            self._admit(*item)

    def _shed_expired_pending(self) -> None:
        """Requests that expired while backlogged in the engine's pending
        list are cancelled BEFORE the next step admits them to a slot —
        their prefill never dispatches. Once a request holds a slot its
        deadline stops mattering (the compute is already committed)."""
        if not self._pending_deadlines:
            return
        cancel = getattr(self.engine, "cancel_pending", None)
        for rid, dl in list(self._pending_deadlines.items()):
            if not dl.expired:
                continue
            if cancel is not None and cancel(rid):
                self._pending_deadlines.pop(rid, None)
                fut = self._futures.pop(rid, None)
                self._inc("shed_expired")
                entry = self._spans.pop(rid, None)
                if entry is not None:
                    entry.span.flag(FLAG_DEADLINE)
                    entry.qspan.end()
                if fut is not None and not fut.done():
                    fut.set_exception(DeadlineExpired(
                        "expired while backlogged; prefill skipped"
                    ))
            else:
                # Already in a slot (or the engine can't cancel): stop
                # tracking, the answer will resolve normally.
                self._pending_deadlines.pop(rid, None)

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            # Idle: block until a request arrives (or, with the scoring
            # tenant attached, run one background quantum per round and
            # re-check arrivals at its boundary), then admit the request
            # plus any companions that queued behind it. Scoring only
            # ever runs HERE — the engine holds no in-flight interactive
            # work at the idle wait, so a quantum never competes with a
            # live decode train.
            with Span("queue.idle"):
                item = await _next_item(self, self._incoming)
            if item is None:
                continue  # a scoring quantum ran; arrivals re-checked
            self._admit(*item)
            self._drain_incoming()
            self._shed_expired_pending()
            while self.engine.has_work:
                t_turn = time.monotonic()
                try:
                    # step() blocks on device compute; run off-loop so new
                    # submissions keep landing in _incoming meanwhile.
                    done = await loop.run_in_executor(None, self.engine.step)
                except asyncio.CancelledError:
                    raise
                except Exception as e:
                    log.exception("paged step failed")
                    for f in self._futures.values():
                        if not f.done():
                            f.set_exception(e)
                    for entry in self._spans.values():
                        entry.span.set_status("error")
                        entry.qspan.end()
                    self._futures.clear()
                    self._pending_deadlines.clear()
                    self._spans.clear()
                    # Stream consumers observe the failure through their
                    # result future; drop the emission states (reset()
                    # below clears the engine-side watch set).
                    self._streams.clear()
                    # A failed step may have donated the live state away;
                    # rebuild it or every later request fails too.
                    self.engine.reset()
                    break
                with Span(BETWEEN_STEPS) as between:
                    spans = self._between_steps(done)
                if self.metrics is not None:
                    self._count_turn(time.monotonic() - t_turn, spans,
                                     between)

    def _count_turn(self, wall_s: float, spans: Dict[str, SpanSum],
                    between: Span) -> None:
        """One turn of the loop into the metrics: its wall parted into
        host work, device wait and (what is left) stall, as counters of
        microseconds, its host work as one `engine_host_work` observation,
        and `engine_host_turn`, which is the wall less the wall of its
        `engine.reap.wait` spans."""
        reap_wait_s = spans.get(REAP_WAIT, NO_SPANS).wall_s
        self.metrics.hist("engine_host_turn").observe(
            max(0.0, wall_s - reap_wait_s)
        )
        budget = turn_budget(wall_s, spans, between)
        for key, n in budget.items():
            self.metrics.inc(metric.ENGINE_LOOP_COUNTERS[key], n)
        self.metrics.hist(metric.ENGINE_LOOP_HISTOGRAMS["host_work"]).observe(
            budget["loop_host_work_us"] / 1e6
        )

    def _between_steps(
        self, done: List[Tuple[int, str]]
    ) -> Dict[str, SpanSum]:
        """Everything the loop does from one step()'s return to the next
        call: drain the engine's stats into the metrics, push stream
        chunks, resolve finished requests, and hand the engine what
        arrived meanwhile (a request that expired while backlogged is
        shed before the next step can admit it). Returns the step's
        spans, summed by name (`pop_loop_stats`)."""
        spans = self._reap_observability()
        ttfts = self.engine.pop_ttfts()
        if self.metrics is not None:
            for ttft in ttfts.values():
                self.metrics.hist("ttft").observe(ttft)
            self._export_engine_stats()
        # Stream emission BEFORE future resolution: a consumer woken by
        # its future always finds the final delta (and any last
        # partials) already queued.
        self._emit_stream_progress(done)
        for rid, text in done:
            self._pending_deadlines.pop(rid, None)
            self._finish_span(rid)
            f = self._futures.pop(rid, None)
            if f is not None and not f.done():
                f.set_result(text)
        self._drain_incoming()
        self._shed_expired_pending()
        return spans

    def _export_engine_stats(self) -> None:
        """The engine's gauges and drained counts, once per turn."""
        # Megastep efficiency: the controller's live K, pad lanes burnt
        # by mid-megastep finishes, and the run's host-dispatches-per-
        # token ratio (the number the megastep exists to shrink).
        mk = getattr(self.engine, "megastep_k", None)
        if mk is not None:
            self.metrics.set_gauge("megastep_k", float(mk))
        self.metrics.set_gauge("serving_queue_depth", float(self.waiting))
        # Multi-chip paged serving: the mesh's tp ways and the per-chip
        # KV residency the heads-axis sharding buys (tracks cache
        # growth/idle shrink live).
        kvb = getattr(self.engine, "kv_bytes_per_chip", None)
        if kvb is not None:
            self.metrics.set_gauge(
                "serving_tp", float(getattr(self.engine, "tp", 1))
            )
            self.metrics.set_gauge("serving_kv_bytes_per_chip", float(kvb))
        pop_ds = getattr(self.engine, "pop_dispatch_stats", None)
        if pop_ds is not None:
            dispatches, tokens, dead = pop_ds()
            if dead:
                self.metrics.inc("megastep_dead_lane_tokens", dead)
            n_disp = self.metrics.inc("engine_dispatches", dispatches)
            n_tok = self.metrics.inc("engine_tokens_emitted", tokens)
            if n_tok:
                self.metrics.set_gauge("host_dispatches_per_token",
                                       n_disp / n_tok)
            now = time.monotonic()
            self._tok_window.append((now, tokens))
            cutoff = now - self._tok_window_s
            while self._tok_window[0][0] < cutoff:
                self._tok_window.popleft()
            span = now - self._tok_window[0][0]
            if span > 0.2:
                self.metrics.set_gauge(
                    "serving_tokens_per_s",
                    sum(n for _, n in self._tok_window) / span,
                )
        prefix = getattr(self.engine, "pop_prefix_stats", lambda: None)()
        if prefix is not None:
            # Shared-prefix cache effectiveness: tokens whose KV came
            # from the radix tree, the eviction pressure, the live block
            # level, and the run's cumulative hit rate (over the prompt
            # tokens admitted, which `_reap_observability` has counted).
            hit, _total, evicted, blocks_used = prefix
            n_hit = self.metrics.inc("prefix_cache_hit_tokens", hit)
            if evicted:
                self.metrics.inc("prefix_cache_evictions", evicted)
            self.metrics.set_gauge("prefix_cache_blocks_used",
                                   float(blocks_used))
            n_prompt = self.metrics.inc("engine_prompt_tokens_admitted", 0)
            if n_prompt:
                self.metrics.set_gauge("prefix_cache_hit_rate",
                                       n_hit / n_prompt)
        snap_bytes = getattr(self.engine, "state_snapshot_bytes", None)
        if snap_bytes is not None:
            self.metrics.set_gauge("engine_state_snapshot_bytes",
                                   float(snap_bytes))
        sess = getattr(self.engine, "session_pin_stats", lambda: None)()
        if sess is not None:
            # Session residency: blocks held by live transcript pins
            # (TTL-expired pins are dropped inside the stats call).
            _n_sessions, pinned = sess
            self.metrics.set_gauge("session_pinned_blocks", float(pinned))
        spec = getattr(self.engine, "pop_spec_stats", lambda: None)()
        if spec is not None:
            windows, emitted = spec
            if windows:
                # Speculation effectiveness on the default serving path:
                # mean emitted tokens per verify window (gauge; 1.0 =
                # nothing accepted) and the cumulative tokens speculation
                # produced beyond the guaranteed one per window.
                self.metrics.set_gauge(
                    "spec_tokens_per_window", emitted / windows
                )
                self.metrics.inc("spec_accepted_tokens", emitted - windows)

    def _emit_stream_progress(
        self, done: List[Tuple[int, str]]
    ) -> None:
        """Advance every registered stream after an engine step: finals
        for requests that completed this step (their token lists drained
        from the engine's watch channel), then partial deltas for the
        still-live ones from the incremental snapshot."""
        if not self._streams:
            return
        finals: Dict[int, List[int]] = {}
        popf = getattr(self.engine, "pop_final_tokens", None)
        if popf is not None:
            finals = popf()
        done_map = dict(done)
        for rid in [r for r in self._streams if r in done_map]:
            st = self._streams.pop(rid)
            self._push_final(st, finals.get(rid), done_map[rid])
        live = list(self._streams)
        if not live:
            return
        snap = getattr(self.engine, "stream_snapshot", None)
        if snap is None:
            return
        for rid, toks in snap(live).items():
            self._push_partial(self._streams[rid], toks)

    def _push_partial(self, st: _StreamState, toks: List[int]) -> None:
        n = len(toks)
        if st.abs_text is None:
            # Resume skip unresolved: wait until the regeneration reaches
            # the resume offset, then anchor the emitted-text position at
            # the skipped prefix's decoded length.
            if n < st.skip:
                return
            st.sent_tokens = st.skip
            st.abs_text = (self.engine.decode_tokens(toks[:st.skip])
                           if st.skip else "")
        if n <= st.sent_tokens:
            return
        full = self.engine.decode_tokens(toks)
        if not full.startswith(st.abs_text):
            # Decode not prefix-stable at this token boundary (byte-level
            # merges can transiently rewrite the tail): hold back — the
            # already-delivered text must never be retracted.
            return
        self._push(st, StreamDelta(
            offset=st.sent_tokens, count=n - st.sent_tokens,
            text=full[len(st.abs_text):], final=False,
        ))
        st.sent_tokens = n
        st.abs_text = full

    def _push(self, st: _StreamState, delta: StreamDelta) -> None:
        """Queue one delta for the stream's consumer; from the second on,
        observe the gap since the one before (`stream_chunk_gap`)."""
        now = time.monotonic()
        if st.last_push is not None and self.metrics is not None:
            self.metrics.hist("stream_chunk_gap").observe(now - st.last_push)
        st.last_push = now
        st.q.put_nowait(delta)

    def _push_final(self, st: _StreamState,
                    toks: Optional[List[int]], text: str) -> None:
        n = len(toks) if toks is not None else max(st.sent_tokens, st.skip)
        if st.abs_text is None:
            eff = min(st.skip, n)
            st.sent_tokens = eff
            st.abs_text = (self.engine.decode_tokens(toks[:eff])
                           if (toks and eff) else "")
        # Best-effort slice when the final decode diverged from a held-
        # back partial (the digest check downstream catches corruption).
        self._push(st, StreamDelta(
            offset=st.sent_tokens, count=max(0, n - st.sent_tokens),
            text=text[len(st.abs_text):], final=True, full_text=text,
        ))

    def _reap_observability(self) -> Dict[str, SpanSum]:
        """Between steps: drain the engine's measured queue waits (closing
        the matching `queue.wait` spans with the true submit->prefill
        interval), per-program dispatch times (feeding the
        `engine_prog_*` histogram series and the shared-attribution
        accumulator the completion-time engine spans diff against) and
        loop counts and observations (`metric.ENGINE_LOOP_*`). Returns
        the last step's spans summed by name (none from an engine that
        keeps no loop stats)."""
        spans: Dict[str, SpanSum] = {}
        pop_loop = getattr(self.engine, "pop_loop_stats", None)
        if pop_loop is not None:
            counts, observations, spans = pop_loop()
            if self.metrics is not None:
                for key, n in counts.items():
                    self.metrics.inc(metric.ENGINE_LOOP_COUNTERS[key], n)
                for key, values in observations.items():
                    hist = self.metrics.hist(
                        metric.ENGINE_LOOP_HISTOGRAMS[key]
                    )
                    for v in values:
                        hist.observe(v)
        pop_waits = getattr(self.engine, "pop_queue_waits", None)
        if pop_waits is not None:
            for rid, wait_s in pop_waits().items():
                entry = self._spans.get(rid)
                if entry is None:
                    continue
                entry.qspan.end(duration_s=wait_s)
                entry.queued_s = wait_s
        pop_progs = getattr(self.engine, "pop_program_times", None)
        if pop_progs is not None:
            entries = pop_progs()
            _observe_program_times(self.metrics, entries)
            for pname, _start, wall_s in entries:
                cum = self._prog_cum.setdefault(pname, [0.0, 0.0])
                cum[0] += 1.0
                cum[1] += wall_s
        pop_hits = getattr(self.engine, "pop_prefix_hits", None)
        if pop_hits is not None:
            # Per-request shared-prefix hit length, reported once at the
            # request's admission; attached to its prefill span at
            # completion.
            for rid, hit in pop_hits().items():
                entry = self._spans.get(rid)
                if entry is not None:
                    entry.prefix_hit = hit
        return spans

    def _finish_span(self, rid: int) -> None:
        """Synthesize the request's `engine.decode` span: admission (end
        of queue wait) -> last token. Continuous batching shares every
        dispatched program across the whole running batch, so per-program
        attribution is the AGGREGATE of dispatches that ran while this
        request was in flight (`shared: true` on the children), clamped
        into the parent so the waterfall still nests."""
        entry = self._spans.pop(rid, None)
        if entry is None:
            return
        # Idempotent: a no-op when the reap already closed the wait span.
        entry.qspan.end()
        queued_s = entry.queued_s
        t_unix = entry.submitted_unix
        total_s = max(0.0,
                      time.monotonic() - entry.submitted_mono - queued_s)
        espan = entry.span.child_timed("engine.decode", t_unix + queued_s,
                                       total_s)
        for pname, cum in sorted(self._prog_cum.items()):
            before = entry.prog_snapshot.get(pname, (0.0, 0.0))
            n = int(cum[0] - before[0])
            wall_s = cum[1] - before[1]
            if n <= 0:
                continue
            attrs: Dict[str, Any] = dict(shared=True, dispatches=n)
            if entry.prefix_hit is not None and pname == "stage":
                # The request's own admission fact (not a shared
                # aggregate): prompt tokens spliced from the
                # shared-prefix cache instead of re-prefilled.
                attrs["prefix_hit_tokens"] = entry.prefix_hit
            espan.child_timed(
                f"engine.{pname}", t_unix + queued_s,
                min(wall_s, total_s), **attrs,
            )
