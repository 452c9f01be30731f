"""Tier-1 gate: the tree is lint-clean under the full rule set.

The `tests/test_marker_audit.py` pattern generalized: every rule in the
catalog — per-file lexical AND whole-repo semantic (call graph, metrics
registry, config consistency) — runs over the package, scripts, and
tests, and any unsuppressed finding fails the suite; the bug classes the
rules encode cannot be reintroduced without a visible, attributable
`# lint: disable=` comment in the diff. Reversion pins below prove the
expensive acceptance cases stay caught: un-deriving either request-path
RPC timeout, or emitting an unregistered metric name, fails lint again.
"""

from pathlib import Path

from distributed_lms_raft_llm_tpu.analysis import all_rules, run_lint
from distributed_lms_raft_llm_tpu.analysis.core import (
    Source,
    iter_sources,
    repo_root,
)
from distributed_lms_raft_llm_tpu.analysis.project import Project
from distributed_lms_raft_llm_tpu.analysis.rules.atomicity_across_await import (
    AtomicityAcrossAwaitRule,
)
from distributed_lms_raft_llm_tpu.analysis.rules.await_under_lock import (
    AwaitUnderLockRule,
)
from distributed_lms_raft_llm_tpu.analysis.rules.cancellation_safety import (
    CancellationSafetyRule,
)
from distributed_lms_raft_llm_tpu.analysis.rules.deadline_flow import (
    DeadlineFlowRule,
)
from distributed_lms_raft_llm_tpu.analysis.rules.lock_order import (
    LockOrderRule,
)
from distributed_lms_raft_llm_tpu.analysis.rules.metrics_registry import (
    MetricsRegistryRule,
)
from distributed_lms_raft_llm_tpu.analysis.rules.trace_propagation import (
    TracePropagationRule,
)
from distributed_lms_raft_llm_tpu.utils import metrics_registry

REPO = Path(__file__).resolve().parent.parent
SERVICE = "distributed_lms_raft_llm_tpu/lms/service.py"
POOL = "distributed_lms_raft_llm_tpu/lms/tutoring_pool.py"


def test_tree_is_lint_clean():
    rules = all_rules()
    assert len(rules) >= 6, "the catalog must keep at least six active rules"
    findings = run_lint(rules=rules)
    assert not findings, (
        f"{len(findings)} unsuppressed lint finding(s):\n"
        + "\n".join(f.format() for f in findings)
        + "\n\nFix the code, or suppress an intentional case with "
        "`# lint: disable=<rule>` and a reason (see README: dlrl-lint)."
    )


def test_rule_set_covers_the_demonstrated_bug_classes():
    """The PR acceptance list: each demonstrated bug class has a live rule.
    Removing or renaming one must be a conscious, reviewed act."""
    names = {r.name for r in all_rules()}
    for required in (
        "canonical-pspec",           # PR-2: P() vs P(None, None) recompiles
        "no-host-sync-in-dispatch",  # paged-engine readback stalls
        "no-blocking-in-async",      # raft/serving loop stalls
        "no-orphan-task",            # dropped task handles (grpc_transport)
        "guarded-by",                # lock-guarded state (PR-1 review class)
        "tracer-hygiene",            # python control flow on tracers
        "slow-marker",               # tier-1 timeout protection
        "deadline-flow",             # PR-4: budget-dropping RPC timeouts
        "metrics-registry",          # PR-4: typo'd/undocumented series
        "config-consistency",        # PR-4: dead knobs, typo'd TOML keys
        "guarded-by-flow",           # PR-4: executor escape via call graph
        "durable-rename",            # PR-5: rename outliving its contents
        "pspec-flow",                # PR-6: semantic sharding divergence
        "donation-safety",           # PR-6: use-after-donate
        "dtype-flow",                # PR-6: silent hot-path widening
        "program-inventory",         # PR-6: jit entry points vs manifest
        "state-machine-determinism",  # PR-18: replica-diverging appliers
        "wire-taint",                # PR-18: unverified wire input at sinks
        "lock-order",                # PR-13: breaker-callback self-deadlock
        "atomicity-across-await",    # event-loop TOCTOU (shutdown races)
        "await-under-lock",          # threading lock held across a yield
        "cancellation-safety",       # teardown that loses CancelledError
    ):
        assert required in names, f"rule {required} missing from the catalog"


# ------------------------------------------------------- reversion pins


def _project_with_patch(rel: str, *edits) -> Project:
    """The real repo tree, with textual edits to one file — exactly what
    `git revert` of a sweep fix would produce."""
    root = repo_root()
    sources = iter_sources(None, root=root)
    patched = []
    for src in sources:
        if src.rel == rel:
            text = src.text
            for old, new in edits:
                assert old in text, f"pin is stale: {old!r} not in {rel}"
                text = text.replace(old, new, 1)
            src = type(src)(src.path, root=root, text=text)
        patched.append(src)
    return Project(patched, root=root)


def _project_with_patched_service(old: str, new: str) -> Project:
    return _project_with_patch(SERVICE, (old, new))


def test_reverting_blob_fetch_timeout_fix_fails_lint():
    project = _project_with_patched_service(
        "timeout=attempt_timeout,", "timeout=5,"
    )
    findings = [
        f for f in DeadlineFlowRule().check_project(project)
        if f.path == SERVICE
    ]
    assert findings, "a re-hardcoded FetchFile timeout must fail deadline-flow"


def test_reverting_replicate_timeout_fix_fails_lint():
    project = _project_with_patched_service(
        "SendFile(chunks(), timeout=attempt_timeout,",
        "SendFile(chunks(), timeout=30,",
    )
    findings = [
        f for f in DeadlineFlowRule().check_project(project)
        if f.path == SERVICE
    ]
    assert findings, "a re-hardcoded SendFile timeout must fail deadline-flow"


def test_metadata_dropping_egress_fails_lint():
    """PR 8 acceptance pin: strip trace_metadata() off the blob-fetch
    egress (what reverting the instrumentation sweep would do) and the
    x-trace-context chain breaks — trace-propagation must catch it."""
    project = _project_with_patched_service(
        "metadata=trace_metadata(),", ""
    )
    findings = [
        f for f in TracePropagationRule().check_project(project)
        if f.path == SERVICE and "FetchFile" in f.message
    ]
    assert findings, (
        "an egress that drops the trace metadata must fail trace-propagation"
    )


def test_bare_metadata_egress_fails_lint():
    """The subtler break: metadata still flows (the deadline budget), but
    without the wrapper the trace context is silently dropped. The
    GetLLMAnswer forward now lives in the fleet router
    (lms/tutoring_pool.py) — the pool is an egress-root module, so the
    same revert fails lint there."""
    project = _project_with_patch(
        POOL, (
            "\n                    metadata=trace_metadata(md),",
            "\n                    metadata=md,",
        )
    )
    findings = [
        f for f in TracePropagationRule().check_project(project)
        if f.path == POOL and "GetLLMAnswer" in f.message
    ]
    assert findings, (
        "an egress whose metadata bypasses trace_metadata() must fail "
        "trace-propagation"
    )


def test_pool_metadata_dropping_egress_fails_lint():
    """Fleet-router pin: strip the metadata= keyword off the pool's
    tutoring forward entirely and trace-propagation must catch it (the
    x-served-by/waterfall chain would silently break)."""
    project = _project_with_patch(
        POOL, ("\n                    metadata=trace_metadata(md),", "")
    )
    findings = [
        f for f in TracePropagationRule().check_project(project)
        if f.path == POOL and "GetLLMAnswer" in f.message
    ]
    assert findings, (
        "a pool egress that drops metadata= must fail trace-propagation"
    )


def test_pool_literal_timeout_fails_lint():
    """Fleet-router pin: re-hardcoding the forward's timeout (dropping
    the live Deadline budget) in tutoring_pool.py must fail
    deadline-flow — the pool's async functions are rule roots even
    though the call graph can't see `self.pool.forward`."""
    project = _project_with_patch(
        POOL, ("timeout=self._attempt_timeout(deadline),", "timeout=30,")
    )
    findings = [
        f for f in DeadlineFlowRule().check_project(project)
        if f.path == POOL
    ]
    assert findings, (
        "a re-hardcoded pool forward timeout must fail deadline-flow"
    )


ROUTER = "distributed_lms_raft_llm_tpu/lms/group_router.py"


def test_router_literal_timeout_fails_lint():
    """PR 16 acceptance pin: the group router's leader forwards derive
    their timeout from the caller's live Deadline budget. Re-hardcoding
    one (what reverting the sweep would do) must fail deadline-flow —
    the router is an egress-root module like the tutoring pool."""
    project = _project_with_patch(ROUTER, (
        "stub.Register(request, timeout=timeout, "
        "metadata=trace_metadata(md))",
        "stub.Register(request, timeout=30, "
        "metadata=trace_metadata(md))",
    ))
    findings = [
        f for f in DeadlineFlowRule().check_project(project)
        if f.path == ROUTER
    ]
    assert findings, (
        "a re-hardcoded router forward timeout must fail deadline-flow"
    )


def test_router_metadata_bypass_fails_lint():
    """PR 16 acceptance pin: the router's cross-node forwards carry the
    trace context (plus group/hops/deadline metadata) through
    trace_metadata(). Bypassing the wrapper on one forward must fail
    trace-propagation."""
    project = _project_with_patch(ROUTER, (
        "stub.Register(request, timeout=timeout, "
        "metadata=trace_metadata(md))",
        "stub.Register(request, timeout=timeout, metadata=md)",
    ))
    findings = [
        f for f in TracePropagationRule().check_project(project)
        if f.path == ROUTER and "Register" in f.message
    ]
    assert findings, (
        "a router egress whose metadata bypasses trace_metadata() must "
        "fail trace-propagation"
    )


def test_stream_forward_metadata_drop_fails_lint():
    """PR 20 acceptance pin: the router's server-streaming forward is
    held to the same trace contract as its unary forwards — the
    async-for egress shape. Stripping trace_metadata() off the
    StreamLLMAnswer forward (what reverting the streaming sweep would
    do) must fail trace-propagation, and dropping its timeout must fail
    deadline-flow even though the call is never awaited directly."""
    project = _project_with_patch(ROUTER, (
        "stub.StreamLLMAnswer(\n"
        "                request, timeout=timeout, "
        "metadata=trace_metadata(md)\n"
        "            )",
        "stub.StreamLLMAnswer(\n"
        "                request, timeout=timeout, metadata=md\n"
        "            )",
    ))
    findings = [
        f for f in TracePropagationRule().check_project(project)
        if f.path == ROUTER and "StreamLLMAnswer" in f.message
    ]
    assert findings, (
        "a metadata-dropping StreamLLMAnswer forward must fail "
        "trace-propagation"
    )
    project = _project_with_patch(ROUTER, (
        "stub.StreamLLMAnswer(\n"
        "                request, timeout=timeout, "
        "metadata=trace_metadata(md)\n"
        "            )",
        "stub.StreamLLMAnswer(\n"
        "                request, metadata=trace_metadata(md)\n"
        "            )",
    ))
    findings = [
        f for f in DeadlineFlowRule().check_project(project)
        if f.path == ROUTER and "StreamLLMAnswer" in f.message
    ]
    assert findings, (
        "a timeout-less StreamLLMAnswer forward must fail deadline-flow"
    )


def test_unregistered_metric_name_fails_lint():
    project = _project_with_patched_service(
        '"tutoring_degraded"', '"tutoring_degarded"'
    )
    findings = [
        f for f in MetricsRegistryRule().check_project(project)
        if f.path == SERVICE and "tutoring_degarded" in f.message
    ]
    assert findings, "a typo'd metric name must fail metrics-registry"


SLO = "distributed_lms_raft_llm_tpu/sim/slo.py"


def test_slo_read_of_undeclared_series_fails_lint():
    """PR-11 acceptance pin: SLO bounds read metric names through the
    registry constants + shared snapshot readers, and the
    metrics-registry rule checks the READ sites — reverting a constant
    back to a (typo'd) literal makes the bound silently read 0 forever,
    and must fail lint."""
    project = _project_with_patch(SLO, (
        "snap_counter(s, metric.TUTORING_DEGRADED)",
        'snap_counter(s, "tutoring_degarded")',
    ))
    findings = [
        f for f in MetricsRegistryRule().check_project(project)
        if f.path == SLO and "tutoring_degarded" in f.message
    ]
    assert findings, "an SLO read of an undeclared series must fail " \
        "metrics-registry"


def test_slo_windowed_read_of_undeclared_series_fails_lint():
    """Same class at the timeline window queries: a burn-rate evaluator
    bound to a never-declared series must fail lint."""
    project = _project_with_patch(SLO, (
        "self.cluster.counter_rate(metric.RAFT_TICK_STALLS,\n"
        "                                             window_s, now)",
        'self.cluster.counter_rate("raft_tick_stals",\n'
        "                                             window_s, now)",
    ))
    findings = [
        f for f in MetricsRegistryRule().check_project(project)
        if f.path == SLO and "raft_tick_stals" in f.message
    ]
    assert findings, "a windowed read of an undeclared series must fail " \
        "metrics-registry"


# ------------------------------------------- reversion pins (absint, PR 6)


PAGED = "distributed_lms_raft_llm_tpu/engine/paged.py"


def test_semantically_divergent_state_plane_spec_fails_lint():
    """Re-introducing a state-plane spec that differs in MEANING (both
    spellings individually canonical, so `canonical-pspec` stays silent)
    must fail pspec-flow — the class behind the PR-2 recompile. Since the
    plane table took over the policy, the divergence is a producer that
    stops consulting the table: _canon_state respelling every plane onto
    dp disagrees with the table's declared specs."""
    from distributed_lms_raft_llm_tpu.analysis.rules.pspec_flow import (
        PSpecFlowRule,
    )

    project = _project_with_patch(PAGED, (
        "sh = jax.sharding.NamedSharding(self.mesh, _plane_spec(name))",
        'sh = jax.sharding.NamedSharding(self.mesh, '
        'jax.sharding.PartitionSpec("dp"))',
    ))
    findings = [
        f for f in PSpecFlowRule().check_project(project) if f.path == PAGED
    ]
    assert findings, "a dispatch-boundary respell under a different " \
        "sharding must fail pspec-flow"
    assert any("plane table" in f.message for f in findings), \
        "the finding must name the plane table the producer disagrees with"


def test_unrebound_donated_state_fails_lint():
    """Donating the live SlotState without rebinding `self.state` in the
    same statement leaves the engine pointing at deleted buffers — the
    exact failure PagedEngine.reset documents."""
    from distributed_lms_raft_llm_tpu.analysis.rules.donation_safety import (
        DonationSafetyRule,
    )

    project = _project_with_patch(PAGED, (
        "self.state, *outs = self._megastep(\n"
        "                self.params, self.state, rngs\n"
        "            )",
        "outs = self._megastep(\n"
        "                self.params, self.state, rngs\n"
        "            )[1:]",
    ))
    findings = [
        f for f in DonationSafetyRule().check_project(project)
        if f.path == PAGED
    ]
    assert findings, "a donated self.state with no rebinding must fail " \
        "donation-safety"


def test_removing_warmup_coverage_fails_lint():
    """Gutting warmup's megastep coverage (the direct dispatch AND the
    drain that reaches it through the call graph) must fail
    program-inventory —
    the static half; partial removals that static reachability cannot see
    are the runtime guard's half (tests/test_program_inventory.py)."""
    from distributed_lms_raft_llm_tpu.analysis.rules.program_inventory import (
        ProgramInventoryRule,
    )

    project = _project_with_patch(PAGED, (
        "self.state = self._megastep(\n"
        "                        self.params, self.state, rngs\n"
        "                    )[0]",
        "pass",
    ), (
        'rid = self.submit("warmup")\n        self.drain()',
        "rid = 0",
    ))
    findings = [
        f for f in ProgramInventoryRule().check_project(project)
        if "warmup no longer covers" in f.message
    ]
    assert findings, "a warmup that cannot reach _megastep must fail " \
        "program-inventory"


def test_donating_a_shared_prefix_block_fails_lint():
    """PR-10 acceptance pin: shared-prefix tree blocks are immutable
    shared structure — an in-place write (donation) to a shared block
    plane would free KV other admissions still splice from. Donating the
    block argument of the splice program must fail donation-safety."""
    from distributed_lms_raft_llm_tpu.analysis.rules.donation_safety import (
        DonationSafetyRule,
    )

    project = _project_with_patch(PAGED, (
        "partial(_stage_block_program), donate_argnums=(0,),",
        "partial(_stage_block_program), donate_argnums=(0, 1),",
    ))
    findings = [
        f for f in DonationSafetyRule().check_project(project)
        if f.path == PAGED and "blk" in f.message
    ]
    assert findings, "a donated shared block plane must fail " \
        "donation-safety"


def test_uninventoried_fused_admission_jit_entry_fails_lint():
    """PR-12 acceptance pin: the fused-admission program family
    (_stage/_stage_block) is inventoried like every other jit entry — a
    new staged-admission program added without regenerating the manifest
    must fail program-inventory."""
    from distributed_lms_raft_llm_tpu.analysis.rules.program_inventory import (
        ProgramInventoryRule,
    )

    project = _project_with_patch(PAGED, (
        "self._stage = jax.jit(",
        "self._rogue_stage = jax.jit(\n"
        "            partial(_stage_program), donate_argnums=(0,),\n"
        "        )\n"
        "        self._stage = jax.jit(",
    ))
    findings = [
        f for f in ProgramInventoryRule().check_project(project)
        if "uninventoried" in f.message
    ]
    assert findings, "a staged-admission jit entry missing from the " \
        "manifest must fail program-inventory"


def test_host_readback_in_staged_reap_fails_lint():
    """PR-12 acceptance pin: the staged-admission reap learns flips from
    planes read INSIDE `with intended_transfer():` — the one sanctioned
    sync point. A host readback of the flipped plane outside it (what
    reverting the batched-reap design to an eager per-flip sync would
    look like) must fail no-host-sync-in-dispatch."""
    from distributed_lms_raft_llm_tpu.analysis.rules.host_sync import (
        HostSyncInDispatchRule,
    )

    project = _project_with_patch(PAGED, (
        "                col = flipped[:, slot]",
        "                col = np.asarray(flipped_dev)[:, slot]",
    ))
    findings = HostSyncInDispatchRule().check(project.sources[PAGED])
    assert findings, "a host readback in the staged-admission reap " \
        "outside intended_transfer() must fail no-host-sync-in-dispatch"


SCORING = "distributed_lms_raft_llm_tpu/engine/scoring.py"


def test_host_sync_in_score_quantum_loop_fails_lint():
    """PR-15 acceptance pin: engine/scoring.py is a dispatch module — a
    bare `.item()` dropped into the quantum loop (a per-quantum device
    round trip on the serving chip) must fail no-host-sync-in-dispatch,
    same as it would in the decode path."""
    from distributed_lms_raft_llm_tpu.analysis.rules.host_sync import (
        HostSyncInDispatchRule,
    )

    project = _project_with_patch(SCORING, (
        'tokens = sum(int(r["tokens"]) for r in results)',
        "tokens = device_total.item()",
    ))
    findings = HostSyncInDispatchRule().check(project.sources[SCORING])
    assert findings, "a bare .item() in the scoring quantum loop must " \
        "fail no-host-sync-in-dispatch"


def test_uninventoried_score_jit_entry_fails_lint():
    """PR-15 acceptance pin: the score program is inventoried like every
    other jit entry — a second scoring program added without
    regenerating the manifest must fail program-inventory."""
    from distributed_lms_raft_llm_tpu.analysis.rules.program_inventory import (
        ProgramInventoryRule,
    )

    project = _project_with_patch(PAGED, (
        "self._score = jax.jit(",
        "self._rogue_score = jax.jit(\n"
        "            partial(_score_program, cfg=self.cfg, "
        "model=self.family)\n"
        "        )\n"
        "        self._score = jax.jit(",
    ))
    findings = [
        f for f in ProgramInventoryRule().check_project(project)
        if "uninventoried" in f.message
    ]
    assert findings, "a scoring jit entry missing from the manifest " \
        "must fail program-inventory"


def test_uninventoried_jit_entry_fails_lint():
    from distributed_lms_raft_llm_tpu.analysis.rules.program_inventory import (
        ProgramInventoryRule,
    )

    project = _project_with_patch(PAGED, (
        "self._grow = jax.jit(",
        "self._rogue = jax.jit(\n"
        "            _grow_state_program, static_argnums=(1,), "
        "donate_argnums=(0,)\n"
        "        )\n"
        "        self._grow = jax.jit(",
    ))
    findings = [
        f for f in ProgramInventoryRule().check_project(project)
        if "uninventoried" in f.message
    ]
    assert findings, "a new jit entry point missing from the manifest " \
        "must fail program-inventory"


# ------------------------------- reversion pins (effects & taint, PR 18)


STATE = "distributed_lms_raft_llm_tpu/lms/state.py"


def test_clock_read_in_applier_fails_lint():
    """PR 18 acceptance pin: a wall-clock read inside a replicated
    applier (each replica would stamp its OWN time and the state digests
    diverge) must fail state-machine-determinism. Timestamps are minted
    leader-side pre-propose and ride the Entry."""
    from distributed_lms_raft_llm_tpu.analysis.rules \
        .state_machine_determinism import StateMachineDeterminismRule

    project = _project_with_patch(STATE, (
        'assignment["grade"] = a["grade"]',
        'assignment["grade"] = a["grade"]\n'
        '            assignment["graded_at"] = time.time()',
    ))
    findings = [
        f for f in StateMachineDeterminismRule().check_project(project)
        if f.path == STATE and "reads-clock" in f.message
    ]
    assert findings, (
        "time.time() in _apply_gradeassignment must fail "
        "state-machine-determinism"
    )


def test_rng_read_in_applier_fails_lint():
    """Same class, RNG flavor: minting an id inside an applier gives
    every replica a different id for the same Entry. Ids come from
    lms/minting.py BEFORE propose."""
    from distributed_lms_raft_llm_tpu.analysis.rules \
        .state_machine_determinism import StateMachineDeterminismRule

    project = _project_with_patch(STATE, (
        'assignment["grade"] = a["grade"]',
        'assignment["grade"] = uuid.uuid4().int',
    ))
    findings = [
        f for f in StateMachineDeterminismRule().check_project(project)
        if f.path == STATE and "reads-rng" in f.message
    ]
    assert findings, (
        "uuid.uuid4() in _apply_gradeassignment must fail "
        "state-machine-determinism"
    )


def test_unordered_apply_iteration_fails_lint():
    """PR 18 sweep pin: the _apply_dropkeys bug class — iterating a set
    while building replicated structure makes insertion order depend on
    per-process hash randomization. Reverting the dict.fromkeys fix must
    fail state-machine-determinism."""
    from distributed_lms_raft_llm_tpu.analysis.rules \
        .state_machine_determinism import StateMachineDeterminismRule

    project = _project_with_patch(STATE, (
        'users = list(dict.fromkeys(a["users"]))',
        'users = set(a["users"])',
    ))
    findings = [
        f for f in StateMachineDeterminismRule().check_project(project)
        if f.path == STATE and "unordered-iter" in f.message
    ]
    assert findings, (
        "set iteration writing replicated state in _apply_dropkeys must "
        "fail state-machine-determinism"
    )


def test_unsigned_group_metadata_read_fails_lint():
    """PR 18 acceptance pin: routing trust decisions read x-lms-group
    through _signed_md (HMAC-verified). Bypassing the verifier with the
    raw metadata reader (what reverting PR 16's hardening would do) must
    fail wire-taint."""
    from distributed_lms_raft_llm_tpu.analysis.rules.wire_taint import (
        WireTaintRule,
    )

    project = _project_with_patch(ROUTER, (
        "raw = self._signed_md(context).get(GROUP_METADATA_KEY)",
        "raw = _metadata_get(context, GROUP_METADATA_KEY)",
    ))
    findings = [
        f for f in WireTaintRule().check_project(project)
        if f.path == ROUTER and "x-lms-group" in f.message
    ]
    assert findings, (
        "reading x-lms-group without _signed_md must fail wire-taint"
    )


def test_secret_equality_compare_fails_lint():
    """PR 18 sweep pin: password verification uses
    hmac.compare_digest — reverting to `==` reintroduces the
    timing-oracle compare and must fail wire-taint."""
    from distributed_lms_raft_llm_tpu.analysis.rules.wire_taint import (
        WireTaintRule,
    )

    project = _project_with_patch(STATE, (
        'return hmac.compare_digest(\n'
        '            user["password"], '
        'hash_password(password, user.get("salt", ""))\n'
        '        )',
        'return user["password"] == hash_password('
        'password, user.get("salt", ""))',
    ))
    findings = [
        f for f in WireTaintRule().check_project(project)
        if f.path == STATE and "compare_digest" in f.message
    ]
    assert findings, (
        "a == compare against the stored password hash must fail "
        "wire-taint"
    )


# ------------------------------------------- concurrency reversion pins


BATCHER = "distributed_lms_raft_llm_tpu/engine/batcher.py"
TRANSPORT = "distributed_lms_raft_llm_tpu/raft/grpc_transport.py"
RESILIENCE = "distributed_lms_raft_llm_tpu/utils/resilience.py"
METRICS_IMPL = "distributed_lms_raft_llm_tpu/utils/metrics.py"


def test_pr13_breaker_callback_deadlock_reconstruction_fails_lint():
    """The PR-13 incident, reconstructed: make _on_breaker_change read
    the live (locked) state_code() of a sibling breaker again instead of
    the cached code. The interprocedural chain — transition fires the
    callback under CircuitBreaker._lock, the callback's lockset (via the
    sibling's state property) re-enters the same declaration-site lock —
    must fail lock-order, with the dynamic callback invocation site
    among the findings."""
    project = _project_with_patch(POOL, (
        "self._breaker_codes[node.index] = CircuitBreaker._STATE_CODES[new]",
        "self._breaker_codes[node.index] = node.breaker.state_code()",
    ))
    findings = LockOrderRule().check_project(project)
    assert findings, (
        "re-reading live breaker state from the state-change callback "
        "must fail lock-order"
    )
    assert any(
        f.path == RESILIENCE and "cb(...)" in f.message for f in findings
    ), "the callback invocation under CircuitBreaker._lock must be flagged"


def test_await_under_threading_lock_fails_lint():
    """What a careless async refactor of Metrics would produce: a
    suspension point inside the `with self._lock:` critical section.
    Metrics._lock is a threading lock (OrderedLock), so the lock would
    stay held across the yield and every other task touching metrics
    blocks the loop thread."""
    project = _project_with_patch(METRICS_IMPL, (
        "    def set_gauge(self",
        "    async def render_async(self):\n"
        "        with self._lock:\n"
        "            await asyncio.sleep(0)\n"
        "            return dict(self._gauges)\n"
        "\n"
        "    def set_gauge(self",
    ))
    findings = [
        f for f in AwaitUnderLockRule().check_project(project)
        if f.path == METRICS_IMPL
    ]
    assert findings, (
        "an await inside a threading-lock critical section must fail "
        "await-under-lock"
    )


def test_forgotten_cancel_turns_absorb_into_swallow_fails_lint():
    """The canceller-absorb allowance is precise: drop the .cancel()
    call from the batcher's close() and the same `except CancelledError:
    pass` becomes a genuine cancellation swallow (awaiting a task it
    never cancelled), which must fail cancellation-safety."""
    root = repo_root()
    path = root / BATCHER
    text = path.read_text()
    old = "            self._runner.cancel()\n"
    assert old in text, "pin is stale: batcher close() no longer cancels"
    src = Source(path, root=root, text=text.replace(old, "", 1))
    rule = CancellationSafetyRule()
    findings = [
        f for f in rule.check(src)
        if not src.suppressed(f.rule, f.line) and "swallows" in f.message
    ]
    assert findings, (
        "an un-cancelled CancelledError absorb must fail "
        "cancellation-safety"
    )


def test_reverting_transport_close_snapshot_fix_fails_lint():
    """Revert the grpc transport's snapshot-then-clear shutdown fix
    (clear() back after the awaits) and the clear once again acts on a
    pre-await read of a live dict — atomicity-across-await must flag
    it."""
    project = _project_with_patch(TRANSPORT, (
        "        channels = list(self._channels.values())\n"
        "        self._channels.clear()\n"
        "        self._stubs.clear()\n"
        "        for channel in channels:\n"
        "            await channel.close()\n",
        "        for channel in self._channels.values():\n"
        "            await channel.close()\n"
        "        self._channels.clear()\n"
        "        self._stubs.clear()\n",
    ))
    findings = [
        f for f in AtomicityAcrossAwaitRule().check_project(project)
        if f.path == TRANSPORT and "_channels" in f.message
    ]
    assert findings, (
        "clearing the channel dict after awaiting closes must fail "
        "atomicity-across-await"
    )


# ------------------------------------------------------ lint wall budget


def test_full_lint_run_stays_within_wall_budget():
    """The suite runs the full rule set several times (here, the CLI
    test, fixture tests); the shared AST cache keeps that cheap. A cold
    full run measures low-20s on a loaded dev box (the interprocedural
    rules build a whole-tree call graph + concurrency engine); 30 s
    leaves noise headroom while an accidental O(files^2) regression —
    which blows past minutes — still fails loudly."""
    import time

    t0 = time.monotonic()
    findings = run_lint()
    dt = time.monotonic() - t0
    assert not findings
    assert dt < 30.0, f"full lint run took {dt:.1f}s (budget 30s)"


# --------------------------------------------------- registry <-> README


def test_metrics_registry_declarations_are_live():
    specs = metrics_registry.all_metrics()
    assert len(specs) >= 25
    kinds = {s.kind for s in specs}
    assert kinds <= {"counter", "gauge", "histogram"}
    # The names the rest of the suite depends on stay declared.
    for name in ("llm_ttft", "ttft", "shed_expired", "shed_overload",
                 "spec_tokens_per_window", "raft_tick_lag",
                 "blob_fetch_budget_exhausted", "replicate_budget_exhausted"):
        assert metrics_registry.is_declared(name), name


def test_readme_metrics_table_matches_registry():
    """README's metrics catalog is generated from the registry
    (scripts/gen_metrics_table.py --write); drift fails tier-1."""
    text = (REPO / "README.md").read_text()
    begin, end = "<!-- metrics-table:begin -->", "<!-- metrics-table:end -->"
    assert begin in text and end in text, "README lost the table markers"
    block = text[text.index(begin): text.index(end) + len(end)]
    want = f"{begin}\n{metrics_registry.render_markdown_table()}\n{end}"
    assert block == want, (
        "README metrics table is stale; run "
        "`python scripts/gen_metrics_table.py --write`"
    )
