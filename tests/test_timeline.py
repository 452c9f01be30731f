"""Telemetry timeline (utils/timeline.py + utils/scrape.py), Prometheus
exposition, the continuous SLO burn-rate engine, the capacity-model
fitter, and the shared quantile helper.

Tier-1 guards here are deliberately cheap (the ~870 s budget is tight):
the sampler-overhead bound runs ~0.6 s of wall clock, everything else is
synthetic-time unit work. The end-to-end continuous-SLO acceptance run
rides the existing module-scoped semester-sim fixture in
tests/test_semester_sim.py instead of booting a second cluster.
"""

import asyncio
import importlib.util
import json
import re
import time
from pathlib import Path

import pytest

from distributed_lms_raft_llm_tpu.config import SimConfig, TelemetryConfig
from distributed_lms_raft_llm_tpu.sim.slo import (
    ContinuousSloEngine,
    evaluate_slos,
    stage_breakdown,
)
from distributed_lms_raft_llm_tpu.utils import metrics_registry
from distributed_lms_raft_llm_tpu.utils.healthz import HealthServer
from distributed_lms_raft_llm_tpu.utils.metrics import (
    LatencyHistogram,
    Metrics,
    percentile_of_sorted,
)
from distributed_lms_raft_llm_tpu.utils.scrape import ClusterScraper
from distributed_lms_raft_llm_tpu.utils.timeline import (
    Timeline,
    TimelineSampler,
    render_prometheus,
    snap_counter,
    snap_gauge,
    snap_hist,
    timeline_admin_get,
)

REPO = Path(__file__).resolve().parent.parent


def _load_script(name: str):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "scripts" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------- quantile helper


def test_percentile_of_sorted_small_n_agrees_everywhere():
    """Satellite: ONE index formula. p50 of two samples is the FIRST
    sample (the old snapshot() formula returned the max), and the
    histogram's percentile(), snapshot(), and stage_breakdown all agree
    with the helper at small n."""
    assert percentile_of_sorted([1.0, 2.0], 50) == 1.0
    assert percentile_of_sorted([1.0, 2.0], 95) == 2.0
    assert percentile_of_sorted([3.0], 99) == 3.0
    with pytest.raises(ValueError):
        percentile_of_sorted([], 50)

    h = LatencyHistogram()
    h.observe(2.0)
    h.observe(1.0)
    snap = h.snapshot()
    assert snap["p50_s"] == 1.0 == h.percentile(50)
    assert snap["p95_s"] == 2.0 == h.percentile(95)

    stages = stage_breakdown([{
        "spans": [
            {"name": "s", "duration_s": 1.0, "children": []},
            {"name": "s", "duration_s": 2.0, "children": []},
        ]
    }])
    assert stages["s"]["p50_s"] == 1.0
    assert stages["s"]["count"] == 2


def test_percentile_matches_nearest_rank_at_scale():
    vals = sorted(float(i) for i in range(1, 101))
    assert percentile_of_sorted(vals, 95) == 95.0
    assert percentile_of_sorted(vals, 50) == 50.0
    assert percentile_of_sorted(vals, 99) == 99.0


def test_window_percentile_is_sliding_window():
    """The recent ring answers windowed quantiles a cumulative reservoir
    can't: an old spike ages out."""
    h = LatencyHistogram()
    h._recent.append((time.monotonic() - 100.0, 9.0))  # aged-out spike
    h.observe(0.1)
    h.observe(0.2)
    assert h.window_percentile(10.0, 95) == 0.2  # spike outside window
    assert h.percentile(95) == 9.0 or h.percentile(95) == 0.2
    assert h.window_percentile(10.0, 95, now=time.monotonic() + 1000) is None


# ------------------------------------------------------------- timeline


def _snap(counters=None, gauges=None, hists=None):
    out = {"counters": counters or {}}
    if gauges:
        out["gauges"] = gauges
    if hists:
        out["latency"] = hists
    return out


def test_timeline_window_queries_and_reset():
    tl = Timeline()
    t = 1000.0
    tl.append(_snap({"reqs": 10}), t=t)           # baseline
    tl.append(_snap({"reqs": 30}), t=t + 1)       # +20
    tl.append(_snap({"reqs": 40},
                    gauges={"depth": 3.0},
                    hists={"lat": {"count": 2, "p95_s": 0.5,
                                   "mean_s": 0.3}}), t=t + 2)  # +10
    # Counter reset (restart): 40 -> 5 contributes 5, never -35.
    tl.append(_snap({"reqs": 5}), t=t + 3)
    assert tl.counter_delta("reqs", 1.5, now=t + 3) == 15
    rate = tl.counter_rate("reqs", 2.5, now=t + 3)
    assert rate is not None and rate > 0
    assert tl.counter_rate("reqs", 10.0, now=t + 500) is None
    assert tl.gauge_last("depth") == 3.0
    assert tl.hist_p95("lat", 10.0, now=t + 3) == 0.5
    assert tl.gauge_percentile("depth", 10.0, 95, now=t + 3) == 3.0
    # dcount: histogram observations attributed to the sample interval.
    point = tl.points()[2]
    assert point.hists["lat"]["dcount"] == 2.0

    tl.record_event("boom", "it happened", t=t + 2, level="fast")
    assert tl.events()[0]["kind"] == "boom"

    # Export -> rehydrate round trip preserves windowed rates.
    doc = tl.to_dict()
    back = Timeline.from_dict(doc)
    assert len(back.points()) == len(tl.points())
    assert back.events()[0]["detail"] == "it happened"
    r0 = tl.points()[1].rates()["reqs"]
    assert back.points()[1].rates()["reqs"] == pytest.approx(r0, rel=0.01)


def test_timeline_first_sample_seeds_baselines_only():
    """A timeline started against an already-warm process must not read
    the boot-era totals as a rate spike in its first window (the
    two-samples-for-a-rate rule)."""
    tl = Timeline()
    t = 1000.0
    tl.append(_snap({"reqs": 100000},
                    hists={"lat": {"count": 5000, "p95_s": 0.1}}), t=t)
    assert tl.counter_delta("reqs", 60.0, now=t) == 0
    assert tl.points()[0].hists["lat"]["dcount"] == 0.0
    tl.append(_snap({"reqs": 100003},
                    hists={"lat": {"count": 5002, "p95_s": 0.1}}), t=t + 1)
    assert tl.counter_delta("reqs", 60.0, now=t + 1) == 3
    assert tl.hist_rate("lat", 60.0, now=t + 1) == pytest.approx(2.0)


def test_snapshot_readers():
    snap = _snap({"a": 2}, gauges={"g": 1.5},
                 hists={"h": {"count": 1, "p95_s": 0.2}})
    assert snap_counter(snap, "a") == 2
    assert snap_counter(snap, "zzz") == 0
    assert snap_gauge(snap, "g") == 1.5
    assert snap_hist(snap, "h")["p95_s"] == 0.2
    assert snap_hist(snap, "zzz") == {}


def test_timeline_sampler_overhead_bound():
    """The watcher must stay ~free: ~25 samples of a realistically sized
    Metrics cost well under 100 ms of sampling work, and the wall budget
    of this whole test is ~1 s."""
    t0 = time.monotonic()
    m = Metrics()
    for i in range(20):
        m.inc(f"c{i}", i)
        m.set_gauge(f"g{i}", float(i))
    for i in range(8):
        h = m.hist(f"h{i}")
        for j in range(50):
            h.observe(0.001 * j)
    sampler = TimelineSampler(m, interval_s=0.02, max_points=64).start()
    time.sleep(0.55)
    sampler.stop()
    assert sampler.samples >= 10
    assert len(sampler.timeline.points()) == min(sampler.samples, 64)
    per_sample = sampler.overhead_s / sampler.samples
    assert per_sample < 0.005, (
        f"sampling cost {per_sample * 1e3:.2f} ms/sample — the telemetry "
        "plane is supposed to be invisible next to what it watches"
    )
    assert time.monotonic() - t0 < 5.0, "wall budget: keep this test cheap"


def test_sampler_rejects_bad_interval():
    with pytest.raises(ValueError):
        TimelineSampler(Metrics(), interval_s=0.0)


# --------------------------------------------------- prometheus round trip


_SAMPLE_RE = re.compile(
    r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{([^}]*)\})?\s+(-?[0-9.eE+]+)$"
)


def parse_prometheus(text: str):
    """Minimal text-exposition parser: families {name: kind}, helps
    {name: help}, samples {(name, labels): value}. Raises on any line
    that is neither a comment nor a well-formed sample."""
    kinds, helps, samples = {}, {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name, help_text = line[len("# HELP "):].split(" ", 1)
            helps[name] = help_text
        elif line.startswith("# TYPE "):
            name, kind = line[len("# TYPE "):].split(" ", 1)
            kinds[name] = kind
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"unparseable exposition line: {line!r}"
            samples[(m.group(1), m.group(2) or "")] = float(m.group(3))
    return kinds, helps, samples


def _declared_metrics():
    m = Metrics()
    m.inc("llm_requests", 7)
    m.set_gauge("storage_recovering", 1.0)
    h = m.hist("ttft")
    for v in (0.1, 0.2, 0.3, 0.4):
        h.observe(v)
    m.inc("scratch_adhoc_series")  # undeclared: TYPE yes, HELP no
    return m


def test_render_prometheus_round_trip():
    m = _declared_metrics()
    snap = m.snapshot()
    kinds, helps, samples = parse_prometheus(render_prometheus(snap))
    assert kinds["llm_requests"] == "counter"
    assert samples[("llm_requests", "")] == 7
    assert kinds["storage_recovering"] == "gauge"
    assert samples[("storage_recovering", "")] == 1.0
    # Histograms expose as Prometheus summaries: quantile samples +
    # _count/_sum, values matching the JSON snapshot exactly.
    assert kinds["ttft"] == "summary"
    assert samples[("ttft", 'quantile="0.95"')] == snap["latency"]["ttft"][
        "p95_s"
    ]
    assert samples[("ttft_count", "")] == 4
    assert samples[("ttft_sum", "")] == pytest.approx(1.0)
    # Name/help come from the registry declarations (single source).
    assert helps["llm_requests"] == metrics_registry.spec(
        "llm_requests"
    ).help
    # Undeclared ad-hoc series still export, but carry no HELP — only
    # registry-declared series are documented (and only they pass lint).
    assert kinds["scratch_adhoc_series"] == "counter"
    assert "scratch_adhoc_series" not in helps


def test_metrics_prom_endpoint_and_admin_timeline():
    """GET /metrics.prom serves text-plain exposition that parses, and
    GET /admin/timeline serves the sampler's ring; both on the same
    HealthServer the servers already run."""
    m = _declared_metrics()
    tl = Timeline()
    tl.append(m.snapshot(), t=time.time())

    async def admin_get(path):
        return timeline_admin_get(path, tl)

    async def run():
        hs = HealthServer(m, admin_get=admin_get)
        port = await hs.start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            writer.write(b"GET /metrics.prom HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            raw = await reader.read()
            writer.close()
            await writer.wait_closed()
            head, _, body = raw.partition(b"\r\n\r\n")
            assert b" 200 " in head.splitlines()[0]
            assert b"text/plain" in head
            kinds, _, samples = parse_prometheus(body.decode())
            assert samples[("llm_requests", "")] == 7
            assert kinds["ttft"] == "summary"

            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           port)
            writer.write(b"GET /admin/timeline HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            raw = await reader.read()
            writer.close()
            await writer.wait_closed()
            head, _, body = raw.partition(b"\r\n\r\n")
            doc = json.loads(body)
            assert doc["ok"] and len(doc["timeline"]["points"]) == 1
            point = doc["timeline"]["points"][0]
            assert point["hists"]["ttft"]["p95_s"] == pytest.approx(0.4)
        finally:
            await hs.stop()

    asyncio.run(run())


# ------------------------------------------------------ cluster scraper


def test_cluster_scraper_merges_deltas_and_survives_restarts():
    node_a = {"counters": {"llm_requests": 100, "tutoring_degraded": 0}}
    node_b = {"counters": {"llm_requests": 50},
              "gauges": {"serving_queue_depth": 2.0},
              "latency": {"llm_ttft": {"count": 3, "p95_s": 0.9}}}
    snaps = {"a": node_a, "b": node_b}
    down = set()

    def src(name):
        return lambda: None if name in down else snaps[name]

    scraper = ClusterScraper(sources={"a": src("a"), "b": src("b")})
    t = 2000.0
    # First sight seeds baselines: boot-era counts are NOT a rate spike.
    scraper.poll(now=t)
    assert scraper.cluster.counter_delta("llm_requests", 60.0, now=t) == 0

    node_a["counters"]["llm_requests"] = 110      # +10
    node_b["counters"]["llm_requests"] = 55       # +5
    scraper.poll(now=t + 1)
    assert scraper.cluster.counter_delta("llm_requests", 1.5,
                                         now=t + 1) == 15

    # b restarts: unreachable one round, then counters wiped.
    down.add("b")
    node_a["counters"]["llm_requests"] = 120      # +10
    scraper.poll(now=t + 2)
    down.clear()
    node_b["counters"]["llm_requests"] = 4        # reset; contributes 4
    scraper.poll(now=t + 3)
    assert scraper.unreachable["b"] == 1
    assert scraper.cluster.counter_delta("llm_requests", 1.5,
                                         now=t + 3) == 14
    # Gauges merge worst-of; histograms merge worst-p95.
    assert scraper.cluster.gauge_last("serving_queue_depth") == 2.0
    assert scraper.cluster.hist_p95("llm_ttft", 60.0, now=t + 3) == 0.9
    export = scraper.export()
    assert export["node_count"] == 2
    assert set(export["nodes"]) == {"a", "b"}


def test_cluster_scraper_hist_count_stays_monotonic_across_worst_flips():
    """The merged block carries the worst node's percentiles but a
    cluster-cumulative count: when the slowest node flips between polls,
    dcount must reflect real new observations, not the count jump
    between two different nodes' reservoirs."""
    a = {"counters": {}, "latency": {"lat": {"count": 1000, "p95_s": 0.1}}}
    b = {"counters": {}, "latency": {"lat": {"count": 10, "p95_s": 0.9}}}
    scraper = ClusterScraper(sources={"a": lambda: a, "b": lambda: b})
    t = 4000.0
    scraper.poll(now=t)  # baseline (worst = b)
    # 2 new observations on a, 1 on b; worst flips to a.
    a["latency"]["lat"] = {"count": 1002, "p95_s": 2.0}
    b["latency"]["lat"] = {"count": 11, "p95_s": 0.9}
    scraper.poll(now=t + 1)
    # worst flips back to b; 1 more observation on each.
    a["latency"]["lat"] = {"count": 1003, "p95_s": 0.1}
    b["latency"]["lat"] = {"count": 12, "p95_s": 3.0}
    scraper.poll(now=t + 2)
    points = scraper.cluster.points()
    assert points[1].hists["lat"]["dcount"] == 3.0
    assert points[2].hists["lat"]["dcount"] == 2.0
    assert scraper.cluster.hist_rate("lat", 1.5, now=t + 2) == \
        pytest.approx(5.0 / 2.0)  # 5 real observations over 2 s of span
    # Percentile merge is still worst-of.
    assert points[1].hists["lat"]["p95_s"] == 2.0
    assert points[2].hists["lat"]["p95_s"] == 3.0


# ------------------------------------------- continuous burn-rate engine


def _engine(cfg=None, **kw):
    cfg = cfg or SimConfig(duration_s=16.0)
    cluster = Timeline()
    sim_metrics = Metrics()
    harness_metrics = Metrics()
    kw.setdefault("fast_window_s", 1.0)
    kw.setdefault("slow_window_s", 4.0)
    eng = ContinuousSloEngine(cfg, cluster, sim_metrics,
                              metrics=harness_metrics, **kw)
    return eng, cluster, sim_metrics, harness_metrics


def test_burn_engine_raises_and_clears_on_degraded_burst():
    """The multi-window state machine: a healthy phase stays silent, a
    full blackout raises the fast alert after `sustain` consecutive
    over-threshold windows, recovery clears it; fault classification
    separates expected alerts from false alarms."""
    eng, cluster, sim_metrics, harness_metrics = _engine()
    sim_metrics.hist("sim_ask_latency").observe(0.05)
    base = 3000.0
    req = deg = 0

    def tick(i, dreq, ddeg):
        nonlocal req, deg
        req += dreq
        deg += ddeg
        t = base + i * 0.25
        cluster.append(
            {"counters": {"llm_requests": req, "tutoring_degraded": deg,
                          "gate_reject": 0, "raft_tick_stalls": 0}}, t=t
        )
        eng.evaluate(at_s=i * 0.25, now=t)

    tick(0, 0, 0)                      # baseline
    for i in range(1, 9):              # healthy: traffic, no degrades
        tick(i, 2, 0)
    assert not eng.alerts
    for i in range(9, 17):             # blackout: everything degrades
        tick(i, 2, 2)
    fast = [a for a in eng.alerts if a.window == "fast"]
    assert fast, "a full blackout must raise the fast-window alert"
    assert fast[0].peak_burn >= 1.5
    assert fast[0].raised_at_s >= 0.25 * 10, "sustain: never on one sample"
    for i in range(17, 34):            # recovery: healthy again
        tick(i, 2, 0)
    assert fast[0].cleared_at_s is not None, "recovery must clear it"
    assert harness_metrics.snapshot()["counters"]["sim_burn_alerts"] >= 1
    events = [e["kind"] for e in cluster.events()]
    assert "slo_alert_raised" in events and "slo_alert_cleared" in events
    # Every SLO was evaluated in at least one window.
    assert all(eng.windows_evaluated[s] >= 1
               for s in ("answer_p95", "degraded_rate", "tick_stalls"))

    # Fault classification drives the verdict check both ways.
    blackout_window = (0.25 * 9, 0.25 * 17)
    eng.finish([blackout_window])
    assert all(a.during_fault for a in eng.alerts)
    ledger = {"losses": [], "acked_writes": 1, "ryw_violations": []}
    report = evaluate_slos(eng.cfg, {}, {}, sim_metrics.snapshot(), ledger,
                           continuous=eng.report())
    by_name = {c.name: c for c in report.checks}
    assert by_name["no_false_alarms"].ok
    assert by_name["burn_windows_evaluated"].ok

    eng.finish([])                     # no faults planned -> false alarm
    assert eng.false_alarms()
    report = evaluate_slos(eng.cfg, {}, {}, sim_metrics.snapshot(), ledger,
                           continuous=eng.report())
    assert not report.ok
    assert not {c.name: c for c in report.checks}["no_false_alarms"].ok


def test_burn_engine_quiet_window_holds_no_evidence():
    """No traffic in the window => no evaluation (None), never a spurious
    0-burn clear or raise."""
    eng, cluster, _, _ = _engine()
    assert eng._burn("degraded_rate", 1.0, now=5000.0) is None
    cluster.append({"counters": {"llm_requests": 0,
                                 "tutoring_degraded": 0}}, t=5000.0)
    cluster.append({"counters": {"llm_requests": 0,
                                 "tutoring_degraded": 0}}, t=5000.5)
    assert eng._burn("degraded_rate", 1.0, now=5000.5) == 0.0


def test_telemetry_config_validation():
    with pytest.raises(ValueError):
        TelemetryConfig(sample_interval_s=0.0)
    with pytest.raises(ValueError):
        TelemetryConfig(fast_window_s=60.0, slow_window_s=30.0)
    with pytest.raises(ValueError):
        SimConfig(telemetry_sample_s=0.0)


# ------------------------------------------------------- capacity model


def _capacity_export(saturate=True, tokens=True):
    points = []
    for i in range(1, 31):
        req = float(i)
        p95 = 0.2 if (not saturate or req <= 20) else 9.0
        gauges = {"serving_queue_depth": 0.0 if req <= 20 else req - 20}
        if tokens:
            gauges["serving_tokens_per_s"] = req * 128.0
        points.append({
            "t": 100.0 + i, "dt": 1.0,
            "rates": {"llm_requests": req},
            "gauges": gauges,
            "hists": {"answer_latency": {"count": i, "p95_s": p95}},
        })
    return {
        "node_count": 3,
        "cluster": {"points": [], "events": []},
        "nodes": {"tutoring": {"points": points, "events": []}},
    }


def test_fit_capacity_finds_the_slo_knee():
    telemetry = _load_script("telemetry")
    model = telemetry.fit_capacity(
        _capacity_export(), slo_p95_s=6.0, ceiling_tokens_per_s=61500.0
    )
    assert model["metric"] == "capacity_req_s_per_node_at_slo"
    assert model["source"] == "tutoring"
    assert model["slo_saturated"] is True
    # The knee is at 20 req/s; bin granularity may shave the top bin.
    assert 15.0 <= model["value"] <= 22.0
    assert model["p95_at_capacity_s"] <= 6.0
    util = model["utilization"]
    assert util is not None
    assert util["tokens_per_req"] == pytest.approx(128.0, rel=0.05)
    assert util["token_limited_req_s"] == pytest.approx(61500.0 / 128.0,
                                                        rel=0.05)
    assert model["queue_depth_p95"] > 0


def test_fit_capacity_unsaturated_is_a_lower_bound():
    telemetry = _load_script("telemetry")
    model = telemetry.fit_capacity(
        _capacity_export(saturate=False, tokens=False),
        slo_p95_s=6.0, ceiling_tokens_per_s=61500.0,
    )
    assert model["slo_saturated"] is False
    assert model["value"] == pytest.approx(30.0, rel=0.05)
    assert model["utilization"] is None


def test_capacity_cli_over_bench_record(tmp_path, capsys):
    """The acceptance path: a (synthetic) BENCH record with an embedded
    timeline -> `telemetry.py --capacity` -> one capacity-model JSON
    line with req/s-per-node-at-SLO."""
    telemetry = _load_script("telemetry")
    record = {
        "metric": "semester_sim_ask_p95_s",
        "timeline": _capacity_export(),
        "slos": {"stage_p95s": {"engine.decode": {"count": 5,
                                                 "p95_s": 0.012}}},
    }
    path = tmp_path / "record.json"
    path.write_text(json.dumps(record))
    rc = telemetry.main(["--capacity", str(path), "--slo-p95", "6.0"])
    assert rc == 0
    model = json.loads(capsys.readouterr().out.strip())
    assert model["metric"] == "capacity_req_s_per_node_at_slo"
    assert model["value"] > 0
    assert model["unit"] == "req/s/node"
    assert model["service_time_p95_s"] == pytest.approx(0.012)


# ------------------------------------------------------ trace_report diff


def test_trace_report_stage_diff(tmp_path, capsys):
    """Satellite: --diff renders a side-by-side per-stage p95 diff from
    two exports (BENCH record shape and bare mapping shape)."""
    trace_report = _load_script("trace_report")
    a = {"slos": {"stage_p95s": {
        "queue.wait": {"count": 10, "p50_s": 0.01, "p95_s": 0.05,
                       "max_s": 0.06},
        "engine.decode": {"count": 10, "p50_s": 0.02, "p95_s": 0.04,
                         "max_s": 0.05},
        "gate.check": {"count": 10, "p50_s": 0.001, "p95_s": 0.002,
                       "max_s": 0.01},
    }}}
    b = {
        "queue.wait": {"count": 12, "p50_s": 0.01, "p95_s": 0.40,
                       "max_s": 0.50},
        "engine.decode": {"count": 12, "p50_s": 0.02, "p95_s": 0.04,
                         "max_s": 0.05},
        "raft.commit": {"count": 12, "p50_s": 0.003, "p95_s": 0.004,
                        "max_s": 0.01},
    }
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    rc = trace_report.main(["--diff", str(pa), str(pb)])
    assert rc == 0
    out = capsys.readouterr().out
    lines = out.splitlines()
    # Worst regression first; one-sided stages stay visible.
    assert "queue.wait" in lines[1]
    assert "+350.0ms" in lines[1] or "+" in lines[1]
    assert any("raft.commit" in ln and "new" in ln for ln in lines)
    assert any("gate.check" in ln and "gone" in ln for ln in lines)
    # Saved-trace shape: breakdown computed from spans.
    trace_doc = {"trace": {"spans": [
        {"name": "client.ask", "duration_s": 1.0,
         "children": [{"name": "queue.wait", "duration_s": 0.3,
                       "children": []}]},
    ]}}
    pt = tmp_path / "t.json"
    pt.write_text(json.dumps(trace_doc))
    stages = trace_report.load_stage_p95s(str(pt))
    assert stages["queue.wait"]["p95_s"] == pytest.approx(0.3)
