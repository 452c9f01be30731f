"""Flight-recorder tracing through the REAL 3-node sim cluster.

The PR's acceptance scenario: one GetLLMAnswer's span tree — fetched over
the live admin plane, not from internal handles — must show the whole
journey (client ask, LMS handler, Raft commit, relevance gate, tutoring
forward, batcher queue wait, engine program) with durations that nest
inside the measured end-to-end latency; the degraded path must keep
trace continuity down to the instructor-queue write under one request
id; and `scripts/trace_report.py` must render both from `/admin/trace`.
"""

import sys
import time
from pathlib import Path

import pytest

from distributed_lms_raft_llm_tpu.client import LMSClient
from distributed_lms_raft_llm_tpu.config import SimConfig
from distributed_lms_raft_llm_tpu.sim.cluster import SimCluster
from distributed_lms_raft_llm_tpu.sim.workload import ASSIGNMENT_TEXT
from distributed_lms_raft_llm_tpu.utils import pdf
from distributed_lms_raft_llm_tpu.utils.tracing import get_tracer

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "scripts"))

import trace_report  # noqa: E402  (scripts/ CLI under test)


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    get_tracer().reset()
    c = SimCluster(str(tmp_path_factory.mktemp("trace-e2e")), SimConfig())
    c.start()
    try:
        assert c.wait_leader(timeout=20.0) is not None
        yield c
    finally:
        c.stop()


@pytest.fixture(scope="module")
def student(cluster):
    client = LMSClient(
        cluster.client_servers(),
        discovery_rounds=8, discovery_backoff_s=0.2,
        rpc_retries=6, rpc_timeout=5.0,
        request_timeout_s=20.0, llm_timeout_s=15.0,
        backoff_base_s=0.02, backoff_max_s=0.3, seed=11,
    )
    try:
        assert client.register("tracee", "pw", "student") is not None
        assert client.login("tracee", "pw")
        # ask_llm needs a submitted assignment (the gate scores the query
        # against its text).
        assert client.upload_assignment(
            "tracee_hw.pdf", pdf.make_pdf(ASSIGNMENT_TEXT)
        )
        yield client
    finally:
        client.close()


def _flatten(span, depth=0, out=None):
    out = out if out is not None else []
    out.append((depth, span))
    for child in span.get("children", ()):
        _flatten(child, depth + 1, out)
    return out


def _spans_by_name(tree):
    rows = []
    for root in tree["spans"]:
        rows.extend(_flatten(root))
    by_name = {}
    for _, span in rows:
        by_name.setdefault(span["name"], []).append(span)
    return by_name


def _assert_nesting(span, skew_s=0.05):
    """Every child's interval sits inside its parent's (small skew
    allowance: remote fragments align by wall clock, and the engine's
    timed children are measured on another thread)."""
    t0, d = span["start_s"], span["duration_s"]
    for child in span.get("children", ()):
        assert child["start_s"] >= t0 - skew_s, (span["name"],
                                                 child["name"])
        assert (child["start_s"] + child["duration_s"]
                <= t0 + d + skew_s), (span["name"], child["name"])
        _assert_nesting(child, skew_s)


@pytest.fixture(scope="module")
def traced_ask(cluster, student):
    """One successful on-topic ask under a known request id, plus its
    measured end-to-end latency and its span tree fetched over HTTP."""
    rid = "trace-e2e-ask-1"
    t0 = time.monotonic()
    resp = student.ask_llm(
        "Explain Raft leader election and log replication.",
        budget_s=15.0, request_id=rid,
    )
    wall_s = time.monotonic() - t0
    assert resp.success and "Echo tutor" in resp.response
    doc = cluster.admin_get(cluster.node_ids()[0], f"/admin/trace/{rid}")
    assert doc["ok"]
    return rid, doc["trace"], wall_s


def test_ask_span_tree_covers_the_full_path(traced_ask):
    """THE acceptance criterion: client -> handler -> raft commit ->
    gate -> tutoring forward -> queue wait -> engine program, one tree,
    one request id."""
    rid, tree, _ = traced_ask
    assert tree["trace_id"] == rid
    by_name = _spans_by_name(tree)
    for required in (
        "client.ask_llm",          # the client's whole logical op
        "lms.GetLLMAnswer",        # LMS servicer handler fragment
        "raft.commit",             # the read fence's no-op barrier commit
        "gate.check",              # relevance gate (KeywordGate in sim)
        "tutoring.forward",        # the HMAC'd LMS -> tutoring hop
        "tutoring.GetLLMAnswer",   # tutoring servicer handler fragment
        "queue.wait",              # queue admission -> a slot
        "engine.decode",           # the slot -> the request's last token
        "engine.megastep",         # the engine program (EchoEngine keeps
                                   # the real pop_program_times contract)
    ):
        assert required in by_name, (
            f"span {required!r} missing; tree has {sorted(by_name)}"
        )
    # One tree, not orphan fragments: the client span is the single root
    # and every other span hangs beneath it.
    assert len(tree["spans"]) == 1
    assert tree["spans"][0]["name"] == "client.ask_llm"
    # The gate verdict rides the span.
    assert by_name["gate.check"][0]["attrs"]["passed"] is True


def test_ask_span_durations_nest_within_e2e_latency(traced_ask):
    _, tree, wall_s = traced_ask
    (root,) = tree["spans"]
    assert root["duration_s"] <= wall_s + 0.05, (
        "client span must not exceed the latency the caller measured"
    )
    _assert_nesting(root)
    # The stages the waterfall attributes must be real time, not zeros.
    by_name = _spans_by_name(tree)
    assert by_name["engine.megastep"][0]["duration_s"] > 0
    assert by_name["tutoring.forward"][0]["duration_s"] > 0


def test_trace_listing_pins_the_ask_exemplar(cluster, traced_ask):
    rid, _, _ = traced_ask
    listing = cluster.admin_get(cluster.node_ids()[0], "/admin/trace")
    assert listing["ok"]
    everything = listing["exemplars"] + listing["recent"]
    assert any(s["trace_id"] == rid for s in everything)
    # The first ask is by definition among the slowest-N for its route.
    assert any(s["trace_id"] == rid and "slowest" in s["pinned"]
               for s in listing["exemplars"])


def test_degraded_ask_keeps_trace_continuity(cluster, student):
    """Satellite: a breaker-open/blackout ask still reaches the
    instructor-queue write under ONE request id — the flight recorder
    pins it, and the tree shows handler -> degraded.queue ->
    raft.commit."""
    rid = "trace-e2e-degraded-1"
    for nid in cluster.node_ids():
        cluster.admin_post(nid, "/admin/faults",
                           {"target": "tutoring", "drop": 1.0})
    try:
        resp = student.ask_llm(
            "Explain Raft commitment and safety under partitions.",
            budget_s=15.0, request_id=rid,
        )
        assert resp.success and "forwarded to an instructor" in resp.response
    finally:
        for nid in cluster.node_ids():
            cluster.admin_post(nid, "/admin/faults", {"reset": True})
    doc = cluster.admin_get(cluster.node_ids()[0], f"/admin/trace/{rid}")
    tree = doc["trace"]
    assert tree["trace_id"] == rid
    assert "degraded" in tree["flags"]
    by_name = _spans_by_name(tree)
    assert "lms.GetLLMAnswer" in by_name
    assert "degraded.queue" in by_name
    # The instructor-queue write is a replicated command: its raft.commit
    # span must sit UNDER the degraded.queue span of this same trace.
    queue_span = by_name["degraded.queue"][0]
    assert any(c["name"] == "raft.commit"
               for c in queue_span.get("children", ())), (
        "the degraded path's instructor-queue write lost its raft.commit"
    )
    # Anomalies are never sampled away: the trace is pinned.
    listing = cluster.admin_get(cluster.node_ids()[0], "/admin/trace")
    assert any(s["trace_id"] == rid and "flagged" in s["pinned"]
               for s in listing["exemplars"])


# ------------------------------------------------- trace_report.py smoke


def test_trace_report_listing_smoke(cluster, traced_ask, capsys):
    """Satellite: the waterfall CLI reads /admin/trace from a live
    cluster."""
    url = f"http://127.0.0.1:{cluster.health_port(cluster.node_ids()[0])}"
    assert trace_report.main(["--endpoint", url]) == 0
    out = capsys.readouterr().out
    assert "exemplars" in out and "client.ask_llm" in out


def test_trace_report_waterfall_smoke(cluster, traced_ask, capsys):
    rid, _, _ = traced_ask
    urls = []
    for nid in cluster.node_ids():
        urls += ["--endpoint",
                 f"http://127.0.0.1:{cluster.health_port(nid)}"]
    assert trace_report.main(urls + [rid]) == 0
    out = capsys.readouterr().out
    assert f"trace {rid}" in out
    for stage in ("client.ask_llm", "lms.GetLLMAnswer", "raft.commit",
                  "gate.check", "tutoring.forward", "queue.wait",
                  "engine.decode", "engine.megastep"):
        assert stage in out, f"waterfall lost stage {stage}"


def test_trace_report_unknown_trace_fails(cluster, capsys):
    url = f"http://127.0.0.1:{cluster.health_port(cluster.node_ids()[0])}"
    assert trace_report.main(["--endpoint", url, "never-existed"]) == 2


# ------------------------------------------- the real engine's prefix hit


@pytest.fixture(scope="module")
def paged_cluster(tmp_path_factory):
    """A second cluster whose tutoring node serves the real paged engine
    at tiny size, prefix cache on (the echo stand-in splices nothing)."""
    c = SimCluster(str(tmp_path_factory.mktemp("trace-e2e-paged")),
                   SimConfig(tutoring_engine="tiny-paged"))
    c.start()
    try:
        assert c.wait_leader(timeout=20.0) is not None
        yield c
    finally:
        c.stop()


def test_a_prefix_hit_rides_the_requests_stage_span(paged_cluster):
    """The same question twice: the second admission splices its prompt's
    blocks from the radix tree, and its trace says how many tokens, on
    the admission program that exists (`engine.stage`; the `prefill` and
    `partial_prefill` programs the attribute used to ride are gone)."""
    client = LMSClient(
        paged_cluster.client_servers(),
        discovery_rounds=8, discovery_backoff_s=0.2,
        rpc_retries=6, rpc_timeout=5.0,
        request_timeout_s=20.0, llm_timeout_s=15.0,
        backoff_base_s=0.02, backoff_max_s=0.3, seed=12,
    )
    try:
        assert client.register("prefixee", "pw", "student") is not None
        assert client.login("prefixee", "pw")
        assert client.upload_assignment(
            "prefixee_hw.pdf", pdf.make_pdf(ASSIGNMENT_TEXT))
        hits = []
        for n in (1, 2):
            rid = f"trace-e2e-prefix-{n}"
            resp = client.ask_llm(
                "Explain Raft leader election and log replication.",
                budget_s=15.0, request_id=rid)
            assert resp.success
            doc = paged_cluster.admin_get(paged_cluster.node_ids()[0],
                                          f"/admin/trace/{rid}")
            assert doc["ok"]
            by_name = _spans_by_name(doc["trace"])
            (stage,) = by_name["engine.stage"]
            assert stage["attrs"]["shared"] is True
            hits.append(stage["attrs"]["prefix_hit_tokens"])
            assert "prefix_hit_tokens" not in by_name[
                "engine.megastep"][0]["attrs"]
    finally:
        client.close()
    assert hits[0] == 0 and hits[1] > 0
