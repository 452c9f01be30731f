"""Semester simulator (sim/): the composed production scenario.

Tier-1 runs ONE seeded sim end-to-end (module-scoped fixture — every
assertion below reads the same run): >=1 TimeoutNow rolling restart, >=1
storage-recovery quarantine + rejoin, >=1 membership add/remove, and a
network-chaos campaign with a tutoring blackout, with SLOs asserted from
/metrics + /healthz and the acked-write ledger proving zero loss. A
scaled `slow`-marked soak runs the same harness harder; the wall-budget
guard keeps the tier-1 run inside its time box.
"""

import dataclasses
import time
from pathlib import Path

import pytest

from distributed_lms_raft_llm_tpu.config import SimConfig
from distributed_lms_raft_llm_tpu.sim import (
    SemesterSim,
    SimCluster,
    WorkloadGenerator,
    plan_events,
    trace_digest,
)

# Deliberately small but not trivial: ~90 ops across 12 actors, every
# event kind (fleet drills included — 3 tutoring nodes behind the
# cache-affinity router), and every SLO — in ~25 s of wall clock.
TIER1_CFG = SimConfig(
    seed=7, students=10, instructors=2, courses=2,
    duration_s=16.0, base_rate=6.0, workers=6, llm_budget_s=10.0,
    tutoring_nodes=3,
    slo_answer_p95_s=8.0, slo_degraded_rate_max=0.5,
    slo_tick_stalls_max=50,
)

# The tier-1 sim's time box (the fixture measures the WHOLE run: cluster
# boot, setup, workload, settle, audit, teardown). The workload phase is
# 16 s; everything around it has to fit in the remainder. Creeping past
# this means the sim no longer belongs in tier-1 — trim it or move it.
TIER1_WALL_BUDGET_S = 90.0


@pytest.fixture(scope="module")
def sim_run(tmp_path_factory):
    t0 = time.monotonic()
    record = SemesterSim(
        TIER1_CFG, str(tmp_path_factory.mktemp("semester"))
    ).run()
    return record, time.monotonic() - t0


def test_sim_end_to_end_slos_hold(sim_run):
    """The acceptance scenario: every SLO asserted from the cluster's
    /metrics + /healthz (and the ledger) holds across the full run."""
    record, _ = sim_run
    slos = record["slos"]
    assert slos["ok"], f"SLO failures: " + str({
        k: v for k, v in slos["checks"].items() if not v["ok"]
    })
    assert slos["checks"]["zero_acked_write_loss"]["ok"]
    assert record["acked_writes"] > 30, "the run must really write"
    assert record["ops_ok"] > 0.9 * record["ops_planned"], (
        "most ops must succeed despite the fault schedule"
    )


def test_sim_executed_every_event_kind(sim_run):
    """>=1 leadership transfer (rolling restart), >=1 storage-recovery
    quarantine+rejoin, >=1 membership add AND remove, >=1 chaos
    campaign — all executed through the real admin plane, none failed."""
    record, _ = sim_run
    failed = [e for e in record["events"] if not e["ok"]]
    assert not failed, f"events failed: {failed}"
    executed = record["events_executed"]
    for kind in ("rolling_restart", "quarantine", "membership_add",
                 "membership_remove", "chaos_campaign",
                 "tutoring_blackout", "tutoring_drain_rejoin",
                 "tutoring_autoscale", "bulk_grading_night",
                 "tutoring_stream_kill"):
        assert executed.get(kind, 0) >= 1, f"missing event kind {kind}"


def test_sim_bulk_grading_harvested_idle_lanes(sim_run):
    """PR-15 acceptance: the bulk-grading night's score job fanned to
    the tutoring fleet's background tenant via the LMS admin plane and
    COMPLETED in preemptible quanta while student traffic kept flowing —
    with interactive p95 untouched (the grading window is a NON-fault
    window, so a scoring-induced burn alert would have failed
    `no_false_alarms` above)."""
    record, _ = sim_run
    scoring = record["scoring"]
    assert scoring is not None
    assert scoring["jobs_completed"] >= 1, scoring
    assert scoring["jobs_failed"] == 0, scoring
    assert scoring["quanta"] >= 1 and scoring["scored_tokens"] > 0
    checks = record["slos"]["checks"]
    assert checks["bulk_scoring_completed"]["ok"], (
        checks["bulk_scoring_completed"]
    )


def test_sim_fleet_drills_spilled_hedged_and_restored_affinity(sim_run):
    """The tutoring-fleet acceptance: killing one of three tutoring
    nodes mid-traffic left measured evidence — >=1 router spill and >=1
    hedge win in the BENCH record — the drain-and-rejoin drill completed
    (ejection + warm-up rejoin counted), and no node ended the run out
    of the ring."""
    record, _ = sim_run
    fleet = record["tutoring_fleet"]
    assert fleet is not None and fleet["size"] == 3
    assert fleet["spills"] >= 1, fleet
    assert fleet["hedges"] >= 1 and fleet["hedge_wins"] >= 1, fleet
    assert fleet["ejections"] >= 1 and fleet["rejoins"] >= 1, fleet
    checks = record["slos"]["checks"]
    assert checks["fleet_spill_observed"]["ok"]
    assert checks["fleet_hedge_win_observed"]["ok"]
    assert checks["fleet_nodes_routable"]["ok"]
    # The per-node map survived to the verdict: every configured node
    # routable, with route/served attribution.
    states = {n["state"] for n in fleet["nodes"]}
    assert states <= {"ok", "warming"}, fleet["nodes"]


def test_sim_exercised_degraded_path(sim_run):
    """The tutoring blackout really produced degraded instructor-queue
    answers (client-observed: node counters can be wiped by the rolling
    restart, which is exactly why the sim keeps its own ledger)."""
    record, _ = sim_run
    assert record["degraded_answers"] >= 1
    assert record["asks"] > 10


def test_continuous_slo_engine_evaluated_and_alerted(sim_run):
    """PR-11 acceptance: the SLOs are evaluated in burn-rate windows
    DURING the run — >= 1 window evaluated per SLO, zero false alarms on
    the healthy baseline, and the injected tutoring blackout raises
    (then clears) at least one fast-window alert, recorded as timeline
    events and classified against the fault schedule."""
    record, _ = sim_run
    cont = record["slos"]["continuous"]
    assert cont is not None and cont["enabled"]
    for slo in ("answer_p95", "degraded_rate", "tick_stalls"):
        assert cont["windows_evaluated"].get(slo, 0) >= 1, slo
    checks = record["slos"]["checks"]
    assert checks["burn_windows_evaluated"]["ok"]
    assert checks["no_false_alarms"]["ok"], checks["no_false_alarms"]
    fast = [a for a in cont["alerts"]
            if a["window"] == "fast" and a["during_fault"]]
    assert fast, f"blackout raised no fast-window alert: {cont['alerts']}"
    assert any(a["cleared_at_s"] is not None for a in fast), (
        "the fast alert must clear once the fault passes"
    )
    # Alerts double as timeline events in the exported cluster timeline.
    kinds = [e["kind"] for e in record["timeline"]["cluster"]["events"]]
    assert "slo_alert_raised" in kinds and "slo_alert_cleared" in kinds


def test_timeline_export_feeds_capacity_model(sim_run):
    """PR-11 acceptance: the run's exported timeline + stage p95s feed
    `scripts/telemetry.py --capacity`, which emits the capacity-model
    JSON (req/s-per-node-at-SLO) the router and autoscaler consume."""
    import importlib.util
    import json

    record, _ = sim_run
    timeline = record["timeline"]
    assert timeline and len(timeline["cluster"]["points"]) >= 10
    assert "tutoring" in timeline["nodes"]
    spec = importlib.util.spec_from_file_location(
        "telemetry", str(Path(__file__).resolve().parent.parent
                         / "scripts" / "telemetry.py")
    )
    telemetry = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(telemetry)
    model = telemetry.fit_capacity(
        json.loads(json.dumps(record)),  # as the CLI would read it
        slo_p95_s=TIER1_CFG.slo_answer_p95_s,
        ceiling_tokens_per_s=61500.0,
    )
    assert model["metric"] == "capacity_req_s_per_node_at_slo"
    assert model["unit"] == "req/s/node"
    assert model["value"] > 0, "the sim served real load at SLO"
    assert model["samples"] >= 5
    # The echo engine never saturates the SLO in 16 s: the fit must say
    # so (lower bound), not fabricate a knee.
    assert model["slo_saturated"] is False
    assert model["service_time_p95_s"] is not None, (
        "flight-recorder stage p95s fold into the model"
    )


def test_sim_exercised_relevance_gate(sim_run):
    """The off-topic asks really hit the gate (KeywordGate in the sim
    cluster): both counters moved on the nodes' /metrics. Sums survive
    the rolling restart only on never-restarted nodes, so >= 1, not an
    exact count."""
    record, _ = sim_run
    assert record["gate_pass"] >= 1
    assert record["gate_reject"] >= 1


def test_keyword_gate_splits_workload_queries():
    """Every on-topic query passes against the assignment text and every
    off-topic one is rejected — with margin, so the threshold is not
    sitting on a knife edge."""
    from distributed_lms_raft_llm_tpu.sim.cluster import KeywordGate

    import distributed_lms_raft_llm_tpu.sim.workload as wl

    g = KeywordGate()
    for q in wl.ON_TOPIC_QUERIES:
        passed, sim = g.check(q, wl.ASSIGNMENT_TEXT)
        assert passed and sim >= 2 * g.threshold, (q, sim)
    for q in wl.OFF_TOPIC_QUERIES:
        passed, sim = g.check(q, wl.ASSIGNMENT_TEXT)
        assert not passed and sim == 0.0, (q, sim)
    # The ops bot's probes must pass against ITS assignment text (a
    # gated-out settle probe could never re-close a breaker).
    for probe in ("ops bot probe: what is Raft?", "ops bot settle probe?"):
        assert g.check(probe, "ops bot assignment")[0], probe


def test_sim_record_is_bench_schema(sim_run):
    """One JSON record, BENCH shape: headline metric + replay anchors."""
    record, _ = sim_run
    assert record["metric"] == "semester_sim_ask_p95_s"
    assert isinstance(record["value"], float)
    assert record["unit"] == "s"
    assert record["seed"] == TIER1_CFG.seed
    # Replayability: digests of the decision-level inputs.
    gen = WorkloadGenerator(TIER1_CFG)
    assert record["trace_digest"] == trace_digest(gen.ops())


def test_tier1_sim_wall_budget(sim_run):
    """CI guard: the tier-1 sim must stay inside its time box."""
    _, wall = sim_run
    assert wall < TIER1_WALL_BUDGET_S, (
        f"tier-1 semester sim took {wall:.1f}s (budget "
        f"{TIER1_WALL_BUDGET_S}s) — trim the config or demote it to slow"
    )


# ------------------------------------------------------ seeded determinism


def test_same_seed_same_trace_and_schedule():
    """Replayability contract: the op trace and the event schedule are
    pure functions of the config (seed included)."""
    a = WorkloadGenerator(TIER1_CFG).ops()
    b = WorkloadGenerator(TIER1_CFG).ops()
    assert [o.key() for o in a] == [o.key() for o in b]
    assert trace_digest(a) == trace_digest(b)
    assert [e.key() for e in plan_events(TIER1_CFG)] == [
        e.key() for e in plan_events(TIER1_CFG)
    ]


def test_different_seed_different_trace():
    other = dataclasses.replace(TIER1_CFG, seed=TIER1_CFG.seed + 1)
    assert trace_digest(WorkloadGenerator(TIER1_CFG).ops()) != trace_digest(
        WorkloadGenerator(other).ops()
    )
    assert [e.key() for e in plan_events(TIER1_CFG)] != [
        e.key() for e in plan_events(other)
    ]


def test_sim_config_rejects_degenerate_shapes():
    """Bad [sim] values fail at load like every other section — not as
    ZeroDivisionError/IndexError minutes into a run."""
    for bad in ({"courses": 0}, {"instructors": 0}, {"base_rate": 0.0},
                {"students": 0}, {"workers": 0}, {"duration_s": 0.0}):
        with pytest.raises(ValueError):
            dataclasses.replace(TIER1_CFG, **bad)


def test_diurnal_curve_shapes_the_trace():
    """The load really follows the day: the midday half of the run must
    carry more ops than the edges (amplitude 0 flattens it)."""
    cfg = dataclasses.replace(TIER1_CFG, duration_s=60.0, base_rate=12.0,
                              diurnal_amplitude=0.9)
    ops = WorkloadGenerator(cfg).ops()
    mid = sum(1 for o in ops if 15.0 <= o.at_s < 45.0)
    edges = len(ops) - mid
    assert mid > 1.3 * edges, (mid, edges)


# ------------------------------------------- fault/campaign introspection


def test_admin_faults_get_reports_campaigns(tmp_path):
    """Satellite: GET /admin/faults (the plane was write-only) returns
    the live fault + campaign configuration; campaigns install, report,
    and clear their specs."""
    cfg = dataclasses.replace(TIER1_CFG, events=False)
    cluster = SimCluster(str(tmp_path), cfg, nodes=1)
    cluster.start()
    try:
        nid = cluster.node_ids()[0]
        state = cluster.admin_get(nid, "/admin/faults")
        assert state["ok"] and state["faults"]["targets"] == {}
        assert state["campaign"]["active"] is False

        # One-shot spec shows up in the GET.
        cluster.admin_post(nid, "/admin/faults",
                           {"target": "tutoring", "drop": 0.5})
        state = cluster.admin_get(nid, "/admin/faults")
        assert state["faults"]["targets"]["tutoring"]["drop"] == 0.5

        # A campaign: phase visible while live, spec installed, and both
        # gone once cancelled.
        cluster.admin_post(nid, "/admin/faults", {"campaign": {
            "name": "introspection",
            "phases": [{"target": "*", "duration_s": 30.0, "drop": 0.25}],
        }})
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            state = cluster.admin_get(nid, "/admin/faults")
            if "*" in state["faults"]["targets"]:
                break
            time.sleep(0.05)
        assert state["campaign"]["active"] is True
        assert state["campaign"]["name"] == "introspection"
        assert state["campaign"]["phase"]["drop"] == 0.25
        assert state["faults"]["targets"]["*"]["drop"] == 0.25

        # The cancel POST's own response is authoritative: the teardown
        # has landed by the time it returns (CampaignRunner.stop), so no
        # polling — a stranded spec here is a regression.
        state = cluster.admin_post(nid, "/admin/faults",
                                   {"campaign_cancel": True})
        assert state["campaign"]["active"] is False
        assert "*" not in state["faults"]["targets"], (
            "cancelled campaign stranded its spec"
        )

        # Unknown spec fields in a campaign fail the POST up front.
        with pytest.raises(RuntimeError, match="unknown fault field"):
            cluster.admin_post(nid, "/admin/faults", {"campaign": {
                "name": "typo",
                "phases": [{"target": "*", "duration_s": 1.0, "dorp": 1.0}],
            }})
        # GET of an unknown admin path is a 404, not a crash.
        import urllib.error

        with pytest.raises(urllib.error.HTTPError):
            cluster.admin_get(nid, "/admin/nope")
    finally:
        cluster.stop()


# ------------------------------------------- sharded control plane (PR 16)

# A second, smaller seeded run with TWO Raft groups: the full fault
# schedule plus the group drills — a group-1 leader loss and a live
# course split (group 0 → group 1) under a chaos overlay at the diurnal
# peak. Every assertion below reads this one run.
GROUPS_CFG = SimConfig(
    seed=23, students=8, instructors=2, courses=2,
    duration_s=12.0, base_rate=6.0, workers=6, llm_budget_s=10.0,
    tutoring_nodes=1, bulk_scoring=False, lms_groups=2,
    slo_answer_p95_s=10.0, slo_degraded_rate_max=0.6,
    slo_tick_stalls_max=50,
)

GROUPS_WALL_BUDGET_S = 90.0


@pytest.fixture(scope="module")
def groups_run(tmp_path_factory):
    t0 = time.monotonic()
    record = SemesterSim(
        GROUPS_CFG, str(tmp_path_factory.mktemp("sharded"))
    ).run()
    return record, time.monotonic() - t0


def test_sharded_sim_slos_hold_with_zero_acked_loss(groups_run):
    """The PR-16 acceptance scenario: a live group split under load
    (chaos campaign active, diurnal peak) completes with every SLO —
    including zero acked-write loss — still green."""
    record, _ = groups_run
    slos = record["slos"]
    assert slos["ok"], "SLO failures: " + str({
        k: v for k, v in slos["checks"].items() if not v["ok"]
    })
    assert slos["checks"]["zero_acked_write_loss"]["ok"]
    assert slos["checks"]["groups_routable"]["ok"]
    assert slos["checks"]["reshard_completed"]["ok"], (
        slos["checks"]["reshard_completed"]
    )
    assert record["acked_writes"] > 20, "the run must really write"


def test_sharded_sim_ran_group_drills(groups_run):
    """Both group drills executed through the real admin plane: the
    targeted `raft:<gid>` leader loss recovered by re-election, and the
    mid-peak split flipped the routing map on every node."""
    record, _ = groups_run
    failed = [e for e in record["events"] if not e["ok"]]
    assert not failed, f"events failed: {failed}"
    executed = record["events_executed"]
    assert executed.get("group_leader_loss", 0) >= 1
    assert executed.get("group_split", 0) >= 1
    # The classic drills still run alongside the group ones.
    for kind in ("rolling_restart", "chaos_campaign", "membership_add",
                 "membership_remove"):
        assert executed.get(kind, 0) >= 1, f"missing event kind {kind}"


def test_sharded_sim_reshard_evidence_in_ledger(groups_run):
    """The ledger is group-aware: acked writes carry their owning group,
    the split left a reshard mark, and the end-of-run audit re-read
    every pre-split write through the POST-flip map (that is what
    `acked_across_reshard` counts)."""
    record, _ = groups_run
    groups = record["groups"]
    assert groups is not None and groups["n_groups"] == 2
    assert len(groups["reshards"]) >= 1
    move = groups["reshards"][0]
    assert move["src"] != move["dst"]
    assert set(groups["acked_by_group"]) == {"group0", "group1"}
    assert groups["acked_across_reshard"] >= 1, (
        "no acked write predated the split — the drill must run "
        "mid-workload, not after it"
    )
    # The flip bumped the replicated map exactly as many times as there
    # were completed handoffs.
    assert groups["routing_map"]["version"] == 1 + len(groups["reshards"])


def test_sharded_sim_topology_endpoint_shape(groups_run):
    """GET /admin/raft (satellite 3): the routing map plus one row per
    group with members/leader/term/applied/commit — what
    scripts/telemetry.py renders as per-group dashboard rows."""
    record, _ = groups_run
    groups = record["groups"]
    topo = groups["topology"]
    assert set(topo) == {"0", "1"}
    for gid, row in topo.items():
        assert row["leader"] is not None, f"group {gid} leaderless"
        assert row["term"] >= 1
        assert row["commit"] >= row["applied"] >= 0
        assert len(row["members"]) >= 3
    assert all(nid is not None for nid in groups["leaders"].values())


def test_sharded_sim_bench_record_fields(groups_run):
    """The BENCH record carries the sharding verdict inputs for replay:
    group count and the groups block itself."""
    record, _ = groups_run
    assert record["lms_groups"] == 2
    assert record["metric"] == "semester_sim_ask_p95_s"
    assert record["groups"]["expected_reshard"] is True


def test_sharded_sim_replicas_converged(groups_run):
    """PR 18 acceptance: at settle — AFTER the mid-peak group_split
    drill — every group's surviving replicas sit at one applied index
    with one state digest (the raft_state_digest chain), and the SLO
    layer turns that evidence into a `replicas_converged` verdict."""
    record, _ = groups_run
    check = record["slos"]["checks"]["replicas_converged"]
    assert check["ok"], check
    digests = record["groups"]["replica_digests"]
    assert digests["converged"] is True
    assert set(digests["groups"]) == {"0", "1"}
    for gid, rows in digests["groups"].items():
        assert len(rows) >= 2, f"group {gid} audited <2 replicas: {rows}"
        assert len({r["digest"] for r in rows.values()}) == 1, rows
        assert len({r["applied"] for r in rows.values()}) == 1, rows
        for r in rows.values():
            assert isinstance(r["digest"], str) and len(r["digest"]) == 16


def test_sharded_sim_wall_budget(groups_run):
    """CI guard: the sharded tier-1 sim must stay inside its time box."""
    _, wall = groups_run
    assert wall < GROUPS_WALL_BUDGET_S, (
        f"sharded semester sim took {wall:.1f}s (budget "
        f"{GROUPS_WALL_BUDGET_S}s) — trim the config or demote it to slow"
    )


def test_sim_lock_acquisition_graph_acyclic_and_consistent(sim_run):
    """The runtime half of the `lock-order` rule: across the whole sim
    (in-process cluster, every OrderedLock in every node), the recorded
    live acquisition graph has no violations (no re-entry on a
    non-reentrant lock, no cycle-closing edge), and composing it with
    the statically computed acquisition-order graph stays acyclic — the
    order the process actually walked never contradicts the order the
    lint rule proved from source."""
    from distributed_lms_raft_llm_tpu.analysis.concurrency import (
        ConcurrencyEngine,
    )
    from distributed_lms_raft_llm_tpu.analysis.core import (
        iter_sources,
        repo_root,
    )
    from distributed_lms_raft_llm_tpu.analysis.project import Project
    from distributed_lms_raft_llm_tpu.utils import locks

    _ = sim_run  # ordering only: the recorded graph is the run's output
    assert locks.violations() == [], locks.violations()
    runtime = locks.acquisition_edges()
    # The sim exercises breakers and metrics enough that at least one
    # nested acquisition must have been recorded; an empty graph means
    # the recording hook silently broke.
    assert runtime, "sim recorded no lock acquisition edges"
    locks.assert_acyclic()

    root = repo_root()
    engine = ConcurrencyEngine(Project(iter_sources(None, root=root),
                                       root=root))
    merged: dict = {}
    for src, dst in set(engine.static_order_shorts()) | runtime:
        merged.setdefault(src, set()).add(dst)
    # DFS cycle check over the merged graph.
    WHITE, GRAY, BLACK = 0, 1, 2
    color: dict = {}

    def visit(node: str, trail: tuple) -> None:
        color[node] = GRAY
        for nxt in sorted(merged.get(node, ())):
            c = color.get(nxt, WHITE)
            assert c != GRAY, (
                f"runtime acquisition order contradicts the static "
                f"order: cycle through {trail + (node, nxt)}"
            )
            if c == WHITE:
                visit(nxt, trail + (node,))
        color[node] = BLACK

    for start in sorted(merged):
        if color.get(start, WHITE) == WHITE:
            visit(start, ())


# ------------------------------------------------------------ tier-2 soak


@pytest.mark.slow
def test_semester_sim_soak_scaled(tmp_path):
    """The same harness at scale: more students, longer semester, the
    REAL paged JAX engine (shared-prefix cache on) behind tutoring, a
    concentrated same-course workload, and tighter stall bounds."""
    cfg = SimConfig(
        seed=11, students=48, instructors=4, courses=4,
        duration_s=90.0, base_rate=10.0, workers=12, llm_budget_s=15.0,
        tutoring_engine="tiny-paged", course_concentration=0.6,
        slo_answer_p95_s=15.0, slo_degraded_rate_max=0.5,
        slo_tick_stalls_max=200,
    )
    record = SemesterSim(cfg, str(tmp_path)).run()
    assert record["slos"]["ok"], record["slos"]
    assert not [e for e in record["events"] if not e["ok"]]
    for kind in ("rolling_restart", "quarantine", "membership_add",
                 "membership_remove", "chaos_campaign"):
        assert record["events_executed"].get(kind, 0) >= 1
    assert record["acked_writes"] > 150
    # Concentrated same-course traffic repeats the same course
    # questions, so the radix cache serves a real measured hit rate in
    # the verdict (at tiny scale the engine's 32-token window truncates
    # the shared context, so these are verbatim-repeat hits — the
    # lookup/splice/suffix-prefill path, not cross-question context
    # sharing, which tests/test_prefix_cache.py pins instead).
    assert record["prefix_cache_hit_rate"] is not None
    assert record["prefix_cache_hit_rate"] > 0.2
