"""One declarative config for the whole deployment (TOML).

The reference configures by editing source: hardcoded server address maps
(reference: GUI_RAFT_LLM_SourceCode/lms_server.py:1454-1460), a hardcoded
tutoring IP (:39), client address lists (lms_gui_final.py:23-29), sampling
constants (tutoring_server.py:22-28), and the 0.6 gate threshold (:1267) —
README.md:101-102 literally instructs editing the files. Here one TOML file
describes the cluster topology, Raft timing, tutoring engine (model /
checkpoint / mesh / quantization / sampling), BERT gate, and client, and
every entrypoint consumes it:

    python -m ...serving.lms_server --config cluster.toml --id 3
    python -m ...serving.tutoring_server --config cluster.toml
    python -m ...client.cli --config cluster.toml

CLI flags still work and override file values (two-phase parse: the file
fills argparse defaults, explicit flags win). See configs/cluster.toml for
a full reference-topology example.
"""

from __future__ import annotations

import dataclasses
import tomllib
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class ClusterConfig:
    """[cluster] — the 5-node Raft topology and its timing."""

    nodes: Dict[int, str] = dataclasses.field(default_factory=dict)
    data_dir: str = "lms_data"  # per-node state under <data_dir>/node<id>
    election_timeout: float = 0.5
    heartbeat_interval: float = 0.1
    snapshot_every: int = 64
    metrics_period: float = 60.0
    linearizable_reads: bool = True

    @property
    def addresses(self) -> Dict[int, str]:
        return dict(self.nodes)


@dataclasses.dataclass
class SamplingConfig:
    """[sampling] — reference defaults (tutoring_server.py:22-28)."""

    temperature: float = 0.7
    top_k: int = 50
    top_p: float = 0.9
    repetition_penalty: float = 1.2
    max_new_tokens: int = 128
    approx_top_k: bool = False  # ~0.95-recall top-k (speed not measured
    #                             on the chip)


@dataclasses.dataclass
class TutoringConfig:
    """[tutoring] — the TPU inference node."""

    address: str = "127.0.0.1:50054"
    model: str = "gpt2"
    checkpoint: Optional[str] = None
    vocab: Optional[str] = None
    merges: Optional[str] = None
    tokenizer_json: Optional[str] = None
    tp: int = 1
    ep: int = 1                  # expert-parallel ways (MoE presets)
    quant: Optional[str] = None  # "int8" = weight-only int8
    kv_quant: bool = False
    spec_tokens: int = 0         # speculative decoding draft window (exact)
    max_batch: int = 8           # the slot count where `slots` is absent
    slots: Optional[int] = None
    chunk: int = 16              # tokens (spec: verify windows) per
    #                              device chunk (one step program; a
    #                              megastep fuses K of them per dispatch)
    megastep: int = 1            # the K controller's starting rung —
    #                              chunks fused into one device-resident
    #                              dispatch (1 = the plain chunk loop)
    megastep_max: int = 0        # controller ceiling; K grows toward
    #                              it while the pending queue is empty and,
    #                              under load, is capped at the chunks until
    #                              the next guaranteed slot-free (0 = follow
    #                              `megastep`). Worst-case admission wait is
    #                              K*chunk device steps.
    inflight: int = 2            # dispatched-but-unread programs kept
    #                              in flight (dispatch pipelining depth;
    #                              1 = serialized dispatch-sync-reap)
    prefix_cache: bool = False   # radix shared-prefix KV cache —
    #                              prompts sharing a course/assignment
    #                              context prefill it once; later requests
    #                              splice the cached blocks and prefill
    #                              only their uncached suffix
    prefix_cache_blocks: int = 512  # device-block budget of the
    #                              shared-prefix tree (16 tokens/block);
    #                              ref-count-pinned blocks are never
    #                              evicted, LRU leaves go first
    prefill_chunk_tokens: int = 32  # arriving prompts are staged
    #                              into SlotState and prefilled this many
    #                              tokens per decode iteration INSIDE the
    #                              megastep program (>= 1). Admission
    #                              latency is bounded by scan iterations,
    #                              not by a prefill dispatch of its own
    draft_source: str = "prompt_lookup"  # with spec: "prompt_lookup"
    #                              (most-recent n-gram continuation) or
    #                              "ngram" (per-slot modal-continuation
    #                              table — higher acceptance at
    #                              temperature>0)
    auth_key_file: Optional[str] = None

    def __post_init__(self) -> None:
        if self.prefill_chunk_tokens < 1:
            raise ValueError("[tutoring] prefill_chunk_tokens must be >= 1")

    @property
    def port(self) -> int:
        return int(self.address.rsplit(":", 1)[1])


@dataclasses.dataclass
class TutoringFleetConfig:
    """[tutoring_fleet] — cache-affinity routing across N tutoring nodes
    (lms/tutoring_pool.py). One section because the knobs compose into
    one policy: the ring places same-course traffic on the node already
    holding its radix prefix blocks, the spill/hedge knobs bound the
    tail when that node is slow or down, and the drain/warm-up knobs
    govern elastic membership without cold-starting every course's
    cache. Empty `addresses` = a one-node fleet at [tutoring].address
    (full back-compat)."""

    addresses: List[str] = dataclasses.field(default_factory=list)
    # Optional per-node /healthz endpoints (host:port of each node's
    # --metrics-port plane), same order as `addresses`: enables the
    # router's health poller (queue-depth signal, drain-driven ejection
    # and rejoin, half-open breaker recovery probes).
    health_addresses: List[str] = dataclasses.field(default_factory=list)
    hedge_after_s: float = 0.35     # hedge the forward to the second
    #                                 choice after this silence; 0 = off
    queue_spill_depth: int = 8      # spill when the affinity node's
    #                                 serving queue is deeper than this
    #                                 (and the second choice's is not)
    warmup_s: float = 5.0           # rejoin warm-up ramp length
    warmup_weight: float = 0.25     # initial key-share weight of a
    #                                 rejoined/added node (ramps to 1.0
    #                                 over warmup_s)
    health_poll_s: float = 1.0      # router health-poll cadence
    stream_stall_s: float = 2.0     # streaming forwards: max silence
    #                                 between chunks before the stream is
    #                                 declared wedged — the breaker takes
    #                                 the failure and the pool resumes the
    #                                 stream at the delivered offset on
    #                                 the spill node; 0 = no stall watch

    def __post_init__(self) -> None:
        if self.health_addresses and len(self.health_addresses) != len(
            self.addresses
        ):
            raise ValueError(
                "[tutoring_fleet] health_addresses must be empty or "
                "match addresses one-to-one"
            )
        if self.hedge_after_s < 0 or self.health_poll_s <= 0:
            raise ValueError(
                "[tutoring_fleet] needs hedge_after_s >= 0 and "
                "health_poll_s > 0"
            )
        if not 0.0 < self.warmup_weight <= 1.0 or self.warmup_s < 0:
            raise ValueError(
                "[tutoring_fleet] needs 0 < warmup_weight <= 1 and "
                "warmup_s >= 0"
            )
        if self.queue_spill_depth < 1:
            raise ValueError(
                "[tutoring_fleet] queue_spill_depth must be >= 1"
            )
        if self.stream_stall_s < 0:
            raise ValueError(
                "[tutoring_fleet] stream_stall_s must be >= 0"
            )


@dataclasses.dataclass
class SessionsConfig:
    """[sessions] — multi-turn tutoring sessions (streaming path).

    One section because the knobs compose into one policy: a session id
    rides the routing affinity key (turn N+1 lands on the node already
    holding turn N's KV blocks), the serving node keeps the session
    transcript for `ttl_s` and publishes it into the radix prefix cache
    under a session pin of the same TTL, and `max_sessions` bounds what
    one node retains (oldest-idle sessions are dropped first — their
    pinned blocks fall back to plain LRU)."""

    ttl_s: float = 600.0     # session transcript + prefix-pin lifetime;
    #                          refreshed on every turn
    max_sessions: int = 256  # per-node live-session cap (0 = unbounded)

    def __post_init__(self) -> None:
        if self.ttl_s <= 0:
            raise ValueError("[sessions] ttl_s must be > 0")
        if self.max_sessions < 0:
            raise ValueError("[sessions] max_sessions must be >= 0")


@dataclasses.dataclass
class ScoringConfig:
    """[scoring] — the background bulk-scoring tenant on the tutoring
    node (engine/scoring.py). One section because the knobs compose into
    one policy: `enabled` makes the score program warmup-covered (the
    first instructor bulk job pays zero live XLA compiles) and starts
    the co-scheduled tenant (quanta run only while the interactive
    pending queue is empty, yielding at single-dispatch boundaries);
    the caps bound what one admin POST can park on the chip and how
    much finished-job state `GET /admin/score` retains."""

    enabled: bool = False
    max_job_texts: int = 4096   # admission cap per bulk job (texts)
    jobs_retained: int = 32     # finished jobs kept for GET /admin/score

    def __post_init__(self) -> None:
        if self.max_job_texts < 1 or self.jobs_retained < 1:
            raise ValueError(
                "[scoring] needs max_job_texts >= 1 and jobs_retained >= 1"
            )


@dataclasses.dataclass
class GateConfig:
    """[gate] — the BERT relevance gate on the LMS leader."""

    model: Optional[str] = None  # e.g. "bert-base-uncased" | "tiny"; None = off
    checkpoint: Optional[str] = None
    vocab: Optional[str] = None
    threshold: float = 0.6       # reference: lms_server.py:1267
    quant: Optional[str] = None  # weight-only int8 for the gate encoder


@dataclasses.dataclass
class ResilienceConfig:
    """[resilience] — overload & failure behavior of the query path.

    One section because the knobs only make sense together: the client's
    overall budget bounds every retry; the LMS forwards the *remaining*
    budget to tutoring (keeping `deadline_floor_s` headroom for the
    degraded fallback); tutoring sheds queue-expired work and bounds
    admission at `queue_depth`; the breaker turns a dead tutoring node
    into O(1) degraded answers instead of stacked timeouts.
    """

    # Client side (client/client.py).
    request_timeout_s: float = 60.0   # overall budget per logical op
    llm_timeout_s: float = 120.0      # overall budget for ask_llm
    backoff_base_s: float = 0.05      # full-jitter exponential backoff
    backoff_max_s: float = 2.0
    # LMS → tutoring hop (lms/service.py).
    tutoring_timeout_s: float = 120.0  # cap when the client sent no budget
    deadline_floor_s: float = 0.25     # below this, degrade instead of forward
    breaker_failure_threshold: int = 5
    breaker_recovery_s: float = 10.0
    breaker_half_open_max: int = 1
    # Intra-cluster file RPCs (lms/service.py). Each per-peer attempt is
    # capped by these AND by the live budget: the requester's remaining
    # deadline for blob fetch-on-miss, one replication budget per upload
    # for the leader's SendFile sweep (anti-entropy heals skipped peers).
    blob_fetch_timeout_s: float = 5.0   # per-peer FetchFile cap
    replicate_timeout_s: float = 30.0   # per-peer SendFile cap
    replicate_budget_s: float = 60.0    # whole-sweep budget per upload
    # Tutoring admission (engine/batcher.py); 0 = unbounded.
    queue_depth: int = 64
    # utils/faults.py seed for the chaos admin plane.
    fault_seed: int = 0


@dataclasses.dataclass
class StorageConfig:
    """[storage] — durability and recovery behavior of the WAL, the LMS
    state snapshot, and the blob store (raft/storage.py,
    lms/persistence.py). One section because the knobs trade off as a
    unit: checksums decide what corruption is *detectable*, the fsync
    policy decides what a crash can *lose*, and the recovery mode decides
    what a node *does* about damage it finds.
    """

    checksums: bool = True   # write v2 CRC-framed WAL records + snapshot
    #                          integrity headers; False = legacy v1 format
    #                          (rollback escape hatch; v1 always loads)
    fsync: str = "always"    # "always" | "never" — fsync each WAL append;
    #                          "never" is a dev/bench mode that trades
    #                          crash durability for append latency
    recovery: str = "rejoin"  # on corrupt WAL/snapshot: "rejoin" discards
    #                           local state and restores from the leader
    #                           (InstallSnapshot); "fail" refuses to start

    def __post_init__(self) -> None:
        # A typo'd policy must fail loudly at load time: `fsync = "on"`
        # silently mapping to fsync-disabled would trade away durability
        # with no warning.
        if self.fsync not in ("always", "never"):
            raise ValueError(
                f"[storage] fsync must be 'always' or 'never', "
                f"got {self.fsync!r}"
            )
        if self.recovery not in ("rejoin", "fail"):
            raise ValueError(
                f"[storage] recovery must be 'rejoin' or 'fail', "
                f"got {self.recovery!r}"
            )


@dataclasses.dataclass
class GroupsConfig:
    """[groups] — the sharded control plane: N independent Raft groups
    hosting partitioned LMS state behind the course-keyed router
    (lms/group_router.py). `count = 1` (or the section absent) keeps the
    single-group world byte-compatible: no router, no extra Raft ports,
    existing WAL/snapshot files load unchanged. With `count > 1` every
    server hosts one member of EVERY group (group 0 doubles as the meta
    group holding the replicated routing map) and each extra group's
    Raft plane listens at the node's base port + `port_stride * gid`.
    """

    count: int = 1          # Raft groups (1 = today's single-group world)
    port_stride: int = 1000  # group gid's Raft port = base + stride * gid
    secret: str = ""        # shared router HMAC key: signs the x-lms-*
    #                         control metadata of forwarded legs so a
    #                         client cannot forge group targeting or
    #                         forced auth salts/tokens. Every node of a
    #                         deployment must use the same value; empty
    #                         (default) disables forgery protection but
    #                         keeps routers interoperable.

    def __post_init__(self) -> None:
        if self.count < 1:
            raise ValueError("[groups] count must be >= 1")
        if self.port_stride < 1:
            raise ValueError("[groups] port_stride must be >= 1")


@dataclasses.dataclass
class SimConfig:
    """[sim] — the semester simulator (sim/): one continuously-verified
    production scenario composing the whole fault arsenal under SLOs.
    Workload shape (students, diurnal curve), operations schedule, and the
    SLO bounds the end-of-run checker asserts from `/metrics`/`/healthz`
    all live here so a failed run replays from one seed + one section.
    """

    seed: int = 0                 # workload trace + event schedule RNG
    students: int = 24
    instructors: int = 2
    courses: int = 3
    duration_s: float = 30.0      # wall-clock length of the workload phase
    base_rate: float = 8.0        # mean op arrival rate (ops/s)
    diurnal_amplitude: float = 0.6  # 0 = flat load, 1 = full day/night swing
    days: float = 1.0             # diurnal cycles compressed into the run
    workers: int = 8              # client worker threads driving the trace
    llm_budget_s: float = 10.0    # per-ask_llm overall client budget
    course_concentration: float = 0.0  # 0 = actors hash uniformly onto
    #                                courses and ask_llm prompts stay bare;
    #                                > 0 skews actors toward the first
    #                                courses AND prefixes on-topic asks
    #                                with their course's deterministic
    #                                assignment context (the shared-prefix
    #                                cache's target workload); 1 = all
    #                                traffic on course0
    tutoring_nodes: int = 1       # tutoring fleet size: N in-process
    #                               tutoring nodes behind the LMS
    #                               routing tier (cache-affinity ring,
    #                               spill, hedging); > 1 adds the fleet
    #                               drills to the operations schedule
    #                               (kill-one-of-N blackout,
    #                               drain-and-rejoin, autoscale)
    tutoring_engine: str = "echo"  # "echo" (wire-complete stand-in) or
    #                                "tiny-paged" (the real engine at tiny
    #                                size + shared-prefix radix cache)
    events: bool = True           # run the operations schedule (transfer,
    #                               quarantine, membership, chaos campaign)
    slo_answer_p95_s: float = 6.0    # ask_llm p95 bound (client + /metrics)
    slo_degraded_rate_max: float = 0.5  # degraded answers / llm requests
    slo_tick_stalls_max: int = 50    # bound on summed raft_tick_stalls
    continuous_slos: bool = True  # evaluate the SLOs in fast/slow burn-rate
    #                               windows DURING the run (sim/slo.py
    #                               ContinuousSloEngine over a live cluster
    #                               scrape), not only at run end; alerts
    #                               land in the verdict and the BENCH record
    bulk_scoring: bool = True     # run the "bulk grading night" event: an
    #                               instructor-scale score job fanned to the
    #                               tutoring fleet mid-run via the LMS
    #                               admin plane; the background tenant must
    #                               complete it WITHOUT moving interactive
    #                               p95 (a scoring-induced burn alert is a
    #                               false alarm — it fails the verdict)
    telemetry_sample_s: float = 0.25  # scrape/evaluate cadence of the
    #                               in-run telemetry loop (cluster /metrics
    #                               poll + burn-rate evaluation)
    session_fraction: float = 0.25  # fraction of students that run a
    #                               follow-up-question CHAIN (streamed,
    #                               session-sticky, prefix-spliced turns)
    #                               instead of independent one-shot asks;
    #                               0 disables the conversational workload
    session_turns: int = 3        # turns per follow-up chain (turn 1 cold,
    #                               turns 2..N splice the session prefix)
    session_ttl_s: float = 30.0   # sim-scale session pin TTL handed to the
    #                               tutoring nodes' session stores
    slo_turn_ttft_p95_s: float = 4.0  # per-turn time-to-first-token p95
    #                               bound over streamed session turns —
    #                               the latency SLO conversational turns
    #                               are judged by (TTFT, not full-answer)
    lms_groups: int = 1           # Raft groups hosting the sharded LMS
    #                               state (lms/group_router.py); > 1 boots
    #                               the router + per-group Raft planes and
    #                               adds the group drills (per-group
    #                               leader loss, live split mid-peak) to
    #                               the operations schedule

    def __post_init__(self) -> None:
        if self.telemetry_sample_s <= 0:
            raise ValueError("[sim] telemetry_sample_s must be > 0")
        if self.lms_groups < 1:
            raise ValueError("[sim] lms_groups must be >= 1")
        if self.tutoring_engine not in ("echo", "tiny-paged"):
            raise ValueError(
                f"[sim] tutoring_engine must be 'echo' or 'tiny-paged', "
                f"got {self.tutoring_engine!r}"
            )
        if self.students < 1 or self.workers < 1 or self.duration_s <= 0:
            raise ValueError("[sim] needs students/workers >= 1 and "
                             "duration_s > 0")
        if self.courses < 1 or self.instructors < 1:
            raise ValueError("[sim] needs courses/instructors >= 1")
        if self.base_rate <= 0:
            raise ValueError("[sim] base_rate must be > 0")
        if self.tutoring_nodes < 1:
            raise ValueError("[sim] tutoring_nodes must be >= 1")
        if not 0.0 <= self.course_concentration <= 1.0:
            raise ValueError("[sim] course_concentration must be in [0, 1]")
        if not 0.0 <= self.session_fraction <= 1.0:
            raise ValueError("[sim] session_fraction must be in [0, 1]")
        if self.session_turns < 1:
            raise ValueError("[sim] session_turns must be >= 1")
        if self.session_ttl_s <= 0 or self.slo_turn_ttft_p95_s <= 0:
            raise ValueError("[sim] session_ttl_s and slo_turn_ttft_p95_s "
                             "must be > 0")


@dataclasses.dataclass
class TracingConfig:
    """[tracing] — the flight-recorder request tracer (utils/tracing.py).
    One section because the knobs trade off as a unit: the ring bounds
    steady-state memory, the exemplar/flagged pins decide which traces
    survive eviction, and the span cap bounds a single runaway request.
    """

    enabled: bool = True          # span collection + x-trace-context headers
    ring_size: int = 256          # retained traces (beyond pins); oldest out
    exemplars_per_route: int = 4  # slowest-N pinned per route
    flagged_max: int = 64         # pinned degraded/error/deadline traces
    max_spans_per_trace: int = 512  # per-trace span cap (then 'truncated')

    def __post_init__(self) -> None:
        if self.ring_size < 1 or self.max_spans_per_trace < 1:
            raise ValueError(
                "[tracing] ring_size and max_spans_per_trace must be >= 1"
            )
        if self.exemplars_per_route < 0 or self.flagged_max < 0:
            raise ValueError(
                "[tracing] exemplars_per_route and flagged_max must be >= 0"
            )


@dataclasses.dataclass
class TelemetryConfig:
    """[telemetry] — the timeline/burn-rate observability plane
    (utils/timeline.py, utils/scrape.py, scripts/telemetry.py). One
    section because the knobs trade off as a unit: the sample interval
    and ring length bound what `GET /admin/timeline` remembers, the
    fast/slow windows + burn thresholds define when the multi-window
    burn-rate evaluators page.
    """

    enabled: bool = True            # per-node TimelineSampler + /admin/timeline
    sample_interval_s: float = 1.0  # node-local snapshot cadence
    ring_points: int = 600          # retained samples per node (~10 min @ 1 s)
    fast_window_s: float = 60.0     # paging window: burn must ALSO be
    #                                 recent (SRE workbook multi-window)
    slow_window_s: float = 600.0    # sustained-evidence window
    fast_burn: float = 1.2          # fast-window burn-rate threshold
    #                                 (consumption rate / budget rate)
    slow_burn: float = 1.0          # slow-window threshold (>= 1 means the
    #                                 budget is being spent faster than it
    #                                 accrues)

    def __post_init__(self) -> None:
        if self.sample_interval_s <= 0 or self.ring_points < 2:
            raise ValueError(
                "[telemetry] needs sample_interval_s > 0 and "
                "ring_points >= 2"
            )
        if self.fast_window_s <= 0 or self.slow_window_s < self.fast_window_s:
            raise ValueError(
                "[telemetry] needs 0 < fast_window_s <= slow_window_s"
            )
        if self.fast_burn <= 0 or self.slow_burn <= 0:
            raise ValueError("[telemetry] burn thresholds must be > 0")


@dataclasses.dataclass
class AppConfig:
    cluster: ClusterConfig = dataclasses.field(default_factory=ClusterConfig)
    tutoring: TutoringConfig = dataclasses.field(default_factory=TutoringConfig)
    tutoring_fleet: TutoringFleetConfig = dataclasses.field(
        default_factory=TutoringFleetConfig
    )
    sessions: SessionsConfig = dataclasses.field(
        default_factory=SessionsConfig
    )
    sampling: SamplingConfig = dataclasses.field(default_factory=SamplingConfig)
    scoring: ScoringConfig = dataclasses.field(default_factory=ScoringConfig)
    gate: GateConfig = dataclasses.field(default_factory=GateConfig)
    resilience: ResilienceConfig = dataclasses.field(
        default_factory=ResilienceConfig
    )
    groups: GroupsConfig = dataclasses.field(default_factory=GroupsConfig)
    storage: StorageConfig = dataclasses.field(default_factory=StorageConfig)
    sim: SimConfig = dataclasses.field(default_factory=SimConfig)
    tracing: TracingConfig = dataclasses.field(default_factory=TracingConfig)
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig
    )

    @property
    def client_servers(self) -> List[str]:
        return [self.cluster.nodes[k] for k in sorted(self.cluster.nodes)]


def _build(cls, table: Dict[str, Any], path: str):
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(table) - set(fields)
    if unknown:
        raise ValueError(
            f"unknown key(s) {sorted(unknown)} in [{path}] "
            f"(known: {sorted(fields)})"
        )
    return cls(**table)


def load_config(path: str) -> AppConfig:
    """Parse a TOML deployment file into an AppConfig (strict keys)."""
    with open(path, "rb") as fh:
        raw = tomllib.load(fh)
    unknown = set(raw) - {"cluster", "tutoring", "tutoring_fleet",
                          "sessions", "sampling", "scoring", "gate",
                          "resilience", "groups", "storage", "sim",
                          "tracing", "telemetry"}
    if unknown:
        raise ValueError(f"unknown section(s) {sorted(unknown)} in {path}")

    cluster_tbl = dict(raw.get("cluster", {}))
    # TOML keys are strings; node ids are ints.
    if "nodes" in cluster_tbl:
        cluster_tbl["nodes"] = {
            int(k): str(v) for k, v in cluster_tbl["nodes"].items()
        }
    return AppConfig(
        cluster=_build(ClusterConfig, cluster_tbl, "cluster"),
        tutoring=_build(TutoringConfig, dict(raw.get("tutoring", {})),
                        "tutoring"),
        tutoring_fleet=_build(TutoringFleetConfig,
                              dict(raw.get("tutoring_fleet", {})),
                              "tutoring_fleet"),
        sessions=_build(SessionsConfig, dict(raw.get("sessions", {})),
                        "sessions"),
        sampling=_build(SamplingConfig, dict(raw.get("sampling", {})),
                        "sampling"),
        scoring=_build(ScoringConfig, dict(raw.get("scoring", {})),
                       "scoring"),
        gate=_build(GateConfig, dict(raw.get("gate", {})), "gate"),
        resilience=_build(ResilienceConfig, dict(raw.get("resilience", {})),
                          "resilience"),
        groups=_build(GroupsConfig, dict(raw.get("groups", {})), "groups"),
        storage=_build(StorageConfig, dict(raw.get("storage", {})),
                       "storage"),
        sim=_build(SimConfig, dict(raw.get("sim", {})), "sim"),
        tracing=_build(TracingConfig, dict(raw.get("tracing", {})),
                       "tracing"),
        telemetry=_build(TelemetryConfig, dict(raw.get("telemetry", {})),
                         "telemetry"),
    )


# --------------------------------------------------- entrypoint adapters


_UNSET = object()


def apply_file_defaults(
    args, parser, overrides: Dict[str, Any], *,
    argv: Optional[List[str]],
) -> None:
    """Two-phase CLI/TOML merge, shared by every entrypoint: the file fills
    each value the command line left unset; explicitly passed flags win.

    Explicitness is detected by re-parsing `argv` (the exact list the
    caller parsed; None = sys.argv, keyword-required so callers can't
    forget to thread it) onto a namespace whose dests are pre-seeded with
    a sentinel: argparse only assigns defaults to attributes the namespace
    lacks, so a dest still holding the sentinel afterwards was never given
    on the command line. (Comparing values against `parser.get_default` —
    the previous scheme — misreads an explicit flag that happens to equal
    its parser default, e.g. `--gate-threshold 0.6` would lose to a TOML
    value of 0.7.) Caveat: absent optional POSITIONALS are still assigned
    their defaults by argparse (overwriting the sentinel), so positional
    dests must be merged by hand, never via `overrides` — both that and
    typo'd keys are rejected below.
    """
    import argparse as _argparse

    flag_dests = {a.dest for a in parser._actions if a.option_strings}
    bad = set(overrides) - flag_dests
    if bad:
        raise ValueError(
            f"overrides name non-flag or unknown parser dest(s): "
            f"{sorted(bad)} (positionals can't be probed for explicitness)"
        )
    probe = _argparse.Namespace(**{a.dest: _UNSET for a in parser._actions})
    parser.parse_known_args(argv, namespace=probe)
    for name, value in overrides.items():
        if getattr(probe, name, _UNSET) is _UNSET:
            setattr(args, name, value)


def client_kwargs(cfg: AppConfig) -> Dict[str, Any]:
    """LMSClient constructor kwargs from [resilience]."""
    r = cfg.resilience
    return dict(
        request_timeout_s=r.request_timeout_s,
        llm_timeout_s=r.llm_timeout_s,
        backoff_base_s=r.backoff_base_s,
        backoff_max_s=r.backoff_max_s,
    )


def sampling_params(cfg: AppConfig):
    from .engine import SamplingParams

    s = cfg.sampling
    return SamplingParams(
        temperature=s.temperature, top_k=s.top_k, top_p=s.top_p,
        repetition_penalty=s.repetition_penalty,
        max_new_tokens=s.max_new_tokens,
        approx_top_k=s.approx_top_k,
    )


def engine_config(cfg: AppConfig):
    """EngineConfig for the tutoring node described by [tutoring]+[sampling]."""
    from .engine import EngineConfig

    t = cfg.tutoring
    return EngineConfig(
        model=t.model, checkpoint=t.checkpoint, vocab_path=t.vocab,
        merges_path=t.merges, tokenizer_json=t.tokenizer_json,
        sampling=sampling_params(cfg), tp=t.tp, ep=t.ep, quant=t.quant,
        kv_quant=t.kv_quant, spec_tokens=t.spec_tokens,
        draft_source=t.draft_source,
        scoring=cfg.scoring.enabled,
    )


def raft_config(cfg: AppConfig):
    from .raft import RaftConfig

    c = cfg.cluster
    return RaftConfig(
        election_timeout_min=c.election_timeout / 2,
        election_timeout_max=c.election_timeout,
        heartbeat_interval=c.heartbeat_interval,
    )
