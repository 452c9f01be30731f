"""Flight-recorder tracer (utils/tracing.py): span trees, the bounded
ring with anomaly/slowest pinning, cross-process propagation, and the
overhead budget the PR's acceptance criteria put on it.

These are the unit tests; tests/test_trace_e2e.py drives the same tracer
through the real 3-node sim cluster.
"""

import asyncio
import threading
import time

import pytest

from distributed_lms_raft_llm_tpu.utils.tracing import (
    FLAG_DEADLINE,
    FLAG_DEGRADED,
    NULL_SPAN,
    TRACE_METADATA_KEY,
    Tracer,
    assemble_forest,
    get_tracer,
    parse_trace_context,
    set_tracer,
    trace_admin_get,
    trace_metadata,
)


@pytest.fixture()
def tracer():
    """A private tracer installed as the process global (so the module
    adapters — trace_metadata, trace_admin_get — see it), restored after
    the test."""
    prev = get_tracer()
    t = set_tracer(Tracer(ring_size=8, exemplars_per_route=2,
                          flagged_max=4))
    yield t
    set_tracer(prev)


class FakeContext:
    """gRPC server context stand-in: just invocation_metadata()."""

    def __init__(self, md):
        self._md = md

    def invocation_metadata(self):
        return self._md


# ------------------------------------------------------------- span trees


def test_span_tree_nesting_and_durations(tracer):
    with tracer.trace("client.op", trace_id="rid-1") as root:
        with tracer.span("stage.a") as a:
            time.sleep(0.01)
            with tracer.span("stage.a.inner"):
                pass
        with tracer.span("stage.b", key="v"):
            pass
    tree = tracer.tree("rid-1")
    assert tree is not None and tree["route"] == "client.op"
    (r,) = tree["spans"]
    assert r["name"] == "client.op"
    assert [c["name"] for c in r["children"]] == ["stage.a", "stage.b"]
    assert r["children"][0]["children"][0]["name"] == "stage.a.inner"
    assert r["children"][1]["attrs"] == {"key": "v"}
    # Durations nest: every child fits inside its parent.
    assert r["duration_s"] >= r["children"][0]["duration_s"] >= 0.01
    assert r["children"][0]["duration_s"] >= (
        r["children"][0]["children"][0]["duration_s"]
    )


def test_span_outside_trace_is_noop(tracer):
    with tracer.span("orphan") as sp:
        assert sp is NULL_SPAN
    assert tracer.records() == []


def test_disabled_tracer_records_nothing():
    prev = get_tracer()
    t = set_tracer(Tracer(enabled=False))
    try:
        with t.trace("client.op", trace_id="x") as sp:
            assert sp is NULL_SPAN
            assert trace_metadata() is None
        assert t.tree("x") is None
    finally:
        set_tracer(prev)


def test_exception_flags_and_errors_span(tracer):
    with pytest.raises(ValueError):
        with tracer.trace("client.op", trace_id="boom"):
            with tracer.span("stage"):
                raise ValueError("x")
    tree = tracer.tree("boom")
    assert "error" in tree["flags"]
    assert tree["spans"][0]["children"][0]["status"] == "error"
    # Anomalous -> pinned past eviction.
    for i in range(64):
        with tracer.trace("client.op", trace_id=f"filler-{i}"):
            pass
    assert tracer.tree("boom") is not None


def test_manual_child_and_timed_child(tracer):
    with tracer.trace("route", trace_id="t") as root:
        q = root.child("queue.wait")
        q.end(duration_s=1.25)
        q.end(duration_s=99.0)  # idempotent: first measurement wins
        root.child_timed("engine.prefill", start_unix=123.0,
                         duration_s=0.5, shared=True)
    (r,) = tracer.tree("t")["spans"]
    by_name = {c["name"]: c for c in r["children"]}
    assert by_name["queue.wait"]["duration_s"] == 1.25
    assert by_name["engine.prefill"]["start_s"] == 123.0
    assert by_name["engine.prefill"]["attrs"]["shared"] is True


def test_contextvar_isolation_across_tasks(tracer):
    """Two concurrent asyncio tasks each see their own current span."""

    async def one(i):
        with tracer.trace(f"route", trace_id=f"task-{i}"):
            with tracer.span(f"inner-{i}"):
                await asyncio.sleep(0.01)

    async def main():
        await asyncio.gather(one(0), one(1))

    asyncio.run(main())
    for i in range(2):
        (r,) = tracer.tree(f"task-{i}")["spans"]
        assert [c["name"] for c in r["children"]] == [f"inner-{i}"]


# ------------------------------------------------------- flight recorder


def test_ring_evicts_oldest_unpinned(tracer):
    for i in range(20):
        with tracer.trace("bulk", trace_id=f"r-{i}"):
            pass
    # ring_size=8 plus at most 2 slowest-per-route exemplar pins: the
    # oldest unpinned traces are gone, the newest survive.
    pinned = {s["trace_id"] for s in tracer.summaries()["exemplars"]}
    assert tracer.tree("r-19") is not None
    retained = {f"r-{i}" for i in range(20)
                if tracer.tree(f"r-{i}") is not None}
    assert len(retained) <= 8 + 2
    # The ring bounds the UNPINNED traces: a pin taken late (durations of
    # empty traces are jitter) leaves its place to an older unpinned one.
    unpinned = [f"r-{i}" for i in range(20) if f"r-{i}" not in pinned]
    assert retained - pinned <= set(unpinned[-8:]), (
        "anything retained beyond the newest unpinned traces must be pinned")


def test_slowest_per_route_pinned_past_eviction(tracer):
    clock = [0.0]
    t = Tracer(ring_size=4, exemplars_per_route=1, flagged_max=4,
               clock=lambda: clock[0], wall=time.time)
    with t.trace("ask", trace_id="slowpoke"):
        clock[0] += 10.0
    for i in range(50):
        with t.trace("ask", trace_id=f"fast-{i}"):
            clock[0] += 0.001
    tree = t.tree("slowpoke")
    assert tree is not None, "slowest exemplar must never be evicted"
    summary = t.summaries()
    assert any(s["trace_id"] == "slowpoke" and "slowest" in s["pinned"]
               for s in summary["exemplars"])


def test_flagged_pins_bounded_fifo(tracer):
    for i in range(10):
        with tracer.trace("ask", trace_id=f"bad-{i}") as sp:
            sp.flag(FLAG_DEGRADED)
    pinned = [s["trace_id"] for s in tracer.summaries()["exemplars"]
              if "flagged" in s["pinned"]]
    # flagged_max=4: only the newest 4 stay pinned.
    assert len(pinned) == 4
    assert set(pinned) == {f"bad-{i}" for i in range(6, 10)}


def test_span_cap_truncates_not_grows(tracer):
    t = Tracer(ring_size=4, max_spans_per_trace=10)
    with t.trace("big", trace_id="big"):
        pass
    for _ in range(30):
        with t.continue_trace("frag", "big", None):
            pass
    tree = t.tree("big")
    assert "truncated" in tree["flags"]
    total = len(tree["spans"])
    assert total <= 10


def test_span_cap_keeps_first_n_of_oversized_fragment():
    """A single fragment larger than the whole budget is trimmed
    (keep-first-N), not dropped: the runaway request is exactly the trace
    the flight recorder exists to keep."""
    t = Tracer(ring_size=4, max_spans_per_trace=5)
    with t.trace("big", trace_id="big"):
        for _ in range(20):
            with t.span("child"):
                pass
    tree = t.tree("big")
    assert "truncated" in tree["flags"]

    def count(spans):
        return sum(1 + count(s.get("children", [])) for s in spans)

    n = count(tree["spans"])
    assert 1 <= n <= 5, f"expected a trimmed non-empty tree, got {n} spans"


def test_route_rename_leaves_one_exemplar_heap():
    """When the outermost client fragment lands after a handler fragment
    and renames the record's route, the old route's exemplar heap must
    drop its entry: a stale entry would block that route's future
    exemplars forever and let displacement there strip the pin the new
    route still relies on."""
    clock, wall = [0.0], [100.0]
    t = Tracer(ring_size=4, exemplars_per_route=1, flagged_max=4,
               clock=lambda: clock[0], wall=lambda: wall[0])
    # Handler fragment records first (route lms.GetLLMAnswer, 10 s) ...
    with t.continue_trace("lms.GetLLMAnswer", "t1", None):
        clock[0] += 10.0
    # ... then the outer client fragment (earlier wall start) renames it.
    wall[0] = 90.0
    with t.trace("client.ask_llm", trace_id="t1"):
        clock[0] += 0.1
    # A fresh, much faster handler-routed trace must still become the
    # lms.GetLLMAnswer exemplar (a stale 10 s heap entry would block it).
    wall[0] = 200.0
    with t.continue_trace("lms.GetLLMAnswer", "t2", None):
        clock[0] += 1.0
    pins = {s["trace_id"]: s["pinned"]
            for s in t.summaries()["exemplars"]}
    assert "slowest" in pins.get("t2", []), (
        "stale heap entry for the renamed trace blocked the new exemplar"
    )
    assert "slowest" in pins.get("t1", []), (
        "renamed trace must stay pinned under its new route"
    )


def test_pins_do_not_starve_the_ring():
    """`ring_size` bounds the unpinned ring only: a burst of flagged
    anomalies must not evict every subsequent normal trace."""
    t = Tracer(ring_size=2, exemplars_per_route=0, flagged_max=8)
    for i in range(8):
        with t.trace("ask", trace_id=f"bad-{i}") as sp:
            sp.flag(FLAG_DEGRADED)
    for i in range(2):
        with t.trace("quiet-route", trace_id=f"ok-{i}"):
            pass
    for i in range(2):
        assert t.tree(f"ok-{i}") is not None, (
            "normal traces evicted by pinned anomalies"
        )


# ----------------------------------------------------------- propagation


def test_parse_trace_context_malformed():
    assert parse_trace_context(None) is None
    assert parse_trace_context("") is None
    assert parse_trace_context("no-slash") is None
    assert parse_trace_context("/x") is None
    assert parse_trace_context("x/") is None
    assert parse_trace_context("tid/sid") == ("tid", "sid")


def test_trace_metadata_appends_header(tracer):
    assert trace_metadata() is None
    assert trace_metadata([("x-base", "1")]) == [("x-base", "1")]
    with tracer.trace("op", trace_id="tid-1") as sp:
        md = trace_metadata([("x-base", "1")])
        assert md[0] == ("x-base", "1")
        key, value = md[1]
        assert key == TRACE_METADATA_KEY
        assert value == f"tid-1/{sp.span_id}"


def test_continue_from_grpc_context_variants(tracer):
    # 1. Full trace context: remote-parented fragment of the same trace.
    with tracer.continue_from_grpc_context(
        FakeContext([(TRACE_METADATA_KEY, "tid-x/span-y")]), "server.h"
    ):
        pass
    (frag,) = tracer.tree("tid-x")["spans"]
    assert frag["parent_id"] == "span-y"
    # 2. Request id only: fresh trace under the client's logged id.
    with tracer.continue_from_grpc_context(
        FakeContext([("x-request-id", "rid-z")]), "server.h"
    ):
        pass
    assert tracer.tree("rid-z") is not None
    # 3. Nothing: fresh random trace, never an error.
    with tracer.continue_from_grpc_context(FakeContext([]), "server.h"):
        pass
    # 4. A context whose metadata call explodes degrades the same way.
    class Broken:
        def invocation_metadata(self):
            raise RuntimeError("no metadata")
    with tracer.continue_from_grpc_context(Broken(), "server.h"):
        pass


def test_assemble_forest_grafts_remote_fragments():
    client = {"name": "client.ask", "span_id": "c1", "start_s": 1.0,
              "duration_s": 2.0,
              "children": [{"name": "attempt", "span_id": "c2",
                            "start_s": 1.1, "duration_s": 1.8}]}
    server = {"name": "lms.handler", "span_id": "s1", "parent_id": "c2",
              "start_s": 1.2, "duration_s": 1.5}
    orphan = {"name": "other.handler", "span_id": "o1",
              "parent_id": "nowhere", "start_s": 0.5, "duration_s": 0.1}
    forest = assemble_forest([server, client, orphan])
    assert [f["name"] for f in forest] == ["other.handler", "client.ask"]
    grafted = forest[1]["children"][0]["children"]
    assert grafted[0]["name"] == "lms.handler"


# ---------------------------------------------------------- admin plane


def test_trace_admin_get_endpoints(tracer):
    with tracer.trace("op", trace_id="seen") as sp:
        sp.flag(FLAG_DEADLINE)
    listing = trace_admin_get("/admin/trace")
    assert listing["ok"] and any(
        s["trace_id"] == "seen" for s in listing["exemplars"]
    )
    tree = trace_admin_get("/admin/trace/seen")
    assert tree["trace"]["spans"][0]["name"] == "op"
    with pytest.raises(KeyError):
        trace_admin_get("/admin/trace/never-seen")
    with pytest.raises(KeyError):
        trace_admin_get("/admin/nope")


def test_thread_safety_under_concurrent_recording(tracer):
    """Fragments recorded from many threads into one trace id must not
    corrupt the store (the sim's client threads + server loop do this)."""
    errs = []

    def worker(i):
        try:
            for j in range(50):
                with tracer.continue_trace("frag", f"shared-{j % 4}",
                                           None):
                    pass
        except Exception as e:  # pragma: no cover - the assertion
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert tracer.summaries() is not None


# -------------------------------------------------------------- overhead


def test_tracing_overhead_budget():
    """Acceptance bound: tracing must stay within 5% of the seeded sim's
    ask p95 at the default ring size. A traced ask creates ~15 spans and
    the sim's p95 bound is seconds-scale, so the budget per span is
    generous (5% of even a 100 ms ask across 15 spans is >300 us each);
    this pins the per-span cost two orders of magnitude under that, on
    the DEFAULT ring configuration, including ring-eviction churn."""
    t = Tracer()  # default knobs — the configuration the bound is about
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        with t.trace("bench.route", trace_id=f"b-{i}"):
            with t.span("stage.a"):
                pass
            with t.span("stage.b"):
                pass
    per_span_s = (time.perf_counter() - t0) / (n * 3)
    assert per_span_s < 200e-6, (
        f"span overhead {per_span_s * 1e6:.1f} us; at ~15 spans per ask "
        "this would threaten the 5% ask-p95 budget"
    )
