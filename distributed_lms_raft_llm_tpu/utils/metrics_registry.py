"""The single source of truth for every metric series this repo emits.

Before this module, metric names were string literals scattered across
`lms/`, `serving/`, `engine/`, and `utils/` — a typo'd name shipped an
always-zero dashboard panel silently, and nothing said what a series
meant or whether it was a counter or a gauge. Now every series is
declared exactly once, with its kind and a help string:

    from ..utils import metrics_registry as metric
    metrics.inc(metric.TUTORING_DEGRADED)        # or the literal name —
    metrics.inc("tutoring_degraded")             # lint checks both

The `metrics-registry` lint rule (analysis/rules/metrics_registry.py)
reads THIS file's declarations as pure AST and then proves, project-wide,
that every name passed to `Metrics.inc/set_gauge/hist/time` is declared
here — undeclared literals, typos, duplicates, and undocumented series
all fail `scripts/lint.py`. Declarations must therefore stay literal
calls to `counter()`/`gauge()`/`histogram()` at module level (the rule
enforces that too). The README's metrics table is rendered from here
(`python scripts/gen_metrics_table.py --write`), so docs cannot drift
from what servers actually export.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List

COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    name: str
    kind: str
    help: str


_REGISTRY: Dict[str, MetricSpec] = {}


def _declare(kind: str, name: str, help: str) -> str:
    if not _NAME_RE.match(name):
        raise ValueError(f"metric name {name!r} must match {_NAME_RE.pattern}")
    if not help.strip():
        raise ValueError(f"metric {name!r} needs a help string")
    if name in _REGISTRY:
        raise ValueError(f"metric {name!r} declared twice")
    _REGISTRY[name] = MetricSpec(name=name, kind=kind, help=help)
    return name


def counter(name: str, help: str) -> str:
    """Declare a monotonically increasing count; returns the name."""
    return _declare(COUNTER, name, help)


def gauge(name: str, help: str) -> str:
    """Declare a last-value reading (a ratio or size, never a latency)."""
    return _declare(GAUGE, name, help)


def histogram(name: str, help: str) -> str:
    """Declare a latency histogram (seconds; /metrics renders percentiles)."""
    return _declare(HISTOGRAM, name, help)


def all_metrics() -> List[MetricSpec]:
    """Every declared series, name-sorted (the docs/table order)."""
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def is_declared(name: str) -> bool:
    return name in _REGISTRY


def spec(name: str) -> MetricSpec:
    return _REGISTRY[name]


def render_markdown_table() -> str:
    """The README metrics catalog, one row per declared series."""
    lines = [
        "| name | kind | meaning |",
        "|---|---|---|",
    ]
    for m in all_metrics():
        lines.append(f"| `{m.name}` | {m.kind} | {m.help} |")
    return "\n".join(lines)


# =========================================================== declarations
#
# LMS service (lms/service.py) — the student-facing RPC plane.

REGISTER = counter("register", "Register RPCs received")
LOGIN = counter("login", "Login RPCs received")
POST = counter("post", "Post RPCs received (materials, assignments, queries)")
LLM_REQUESTS = counter(
    "llm_requests",
    "GetLLMAnswer RPCs received (LMS leader and tutoring node each count "
    "their own)",
)
GATE_PASS = counter(
    "gate_pass", "queries the BERT relevance gate accepted"
)
GATE_REJECT = counter(
    "gate_reject", "queries the BERT relevance gate refused"
)
LLM_TTFT = histogram(
    "llm_ttft",
    "LMS-side student-query latency: gate check + tutoring forward "
    "(the BASELINE north-star is its p50)",
)
TUTORING_DEGRADED = counter(
    "tutoring_degraded",
    "queries answered by the degraded instructor-queue fallback",
)
TUTORING_FAILURES = counter(
    "tutoring_failures", "tutoring forwards that failed (RPC error)"
)
TUTORING_DUPLICATES = counter(
    "tutoring_duplicates",
    "tutoring forwards deliberately delivered twice by the `duplicate` "
    "chaos fault",
)
TUTORING_BUDGET_EXHAUSTED = counter(
    "tutoring_budget_exhausted",
    "queries degraded because the client's remaining deadline budget was "
    "under the floor",
)
TUTORING_BREAKER_REJECTIONS = counter(
    "tutoring_breaker_rejections",
    "queries degraded because the tutoring circuit breaker was open",
)
TUTORING_BREAKER_STATE = gauge(
    "tutoring_breaker_state",
    "tutoring circuit breaker state (0 closed / 1 open / 2 half-open)",
)
TUTORING_BREAKER_CLOSED = counter(
    "tutoring_breaker_closed", "breaker transitions into CLOSED"
)
TUTORING_BREAKER_OPEN = counter(
    "tutoring_breaker_open", "breaker transitions into OPEN"
)
TUTORING_BREAKER_HALF_OPEN = counter(
    "tutoring_breaker_half_open", "breaker transitions into HALF_OPEN"
)
BLOB_FETCH_ON_MISS = counter(
    "blob_fetch_on_miss",
    "blobs healed from a peer after committed metadata referenced a "
    "locally missing file",
)
BLOB_FETCH_BUDGET_EXHAUSTED = counter(
    "blob_fetch_budget_exhausted",
    "blob fetch-on-miss sweeps skipped because the request's remaining "
    "deadline budget was under the floor (metadata-only response instead "
    "of a doomed peer sweep)",
)
REPLICATE_BUDGET_EXHAUSTED = counter(
    "replicate_budget_exhausted",
    "file-replication peers skipped because the per-upload replication "
    "budget ran out mid-sweep (anti-entropy heals them later)",
)

# Tutoring fleet router (lms/tutoring_pool.py) — cache-affinity routing,
# spill, hedging, and elastic membership across N tutoring nodes.

TUTORING_SPILLS = counter(
    "tutoring_spills",
    "tutoring forwards served by a non-affinity fleet node (the router "
    "spilled past the ring's first choice: open breaker, deep queue, "
    "insufficient budget, or the affinity node failed/was ejected)",
)
TUTORING_HEDGES = counter(
    "tutoring_hedges",
    "hedged duplicate sends issued after the affinity node sat on a "
    "forward past hedge_after_s (tail-tolerance; the loser is cancelled)",
)
TUTORING_HEDGE_WINS = counter(
    "tutoring_hedge_wins",
    "tutoring answers won by the hedged (second-choice) send — the tail "
    "latency the hedge actually shaved",
)
TUTORING_NODE_EJECTIONS = counter(
    "tutoring_node_ejections",
    "fleet members the router ejected from the ring (drain observed via "
    "/healthz or a draining refusal on the wire)",
)
TUTORING_NODE_REJOINS = counter(
    "tutoring_node_rejoins",
    "ejected fleet members re-admitted to the ring (drain ended or an "
    "operator joined them back); each rejoin starts a warm-up ramp so "
    "the node's prefix cache refills before it takes its full key share",
)
TUTORING_FLEET_SIZE = gauge(
    "tutoring_fleet_size",
    "routable tutoring fleet members (configured minus ejected/draining)",
)
STREAM_RESUMES = counter(
    "stream_resumes",
    "streamed answers resumed at the client's delivered token offset on "
    "another fleet node after the serving stream broke mid-answer (node "
    "death, open breaker, drain, or a per-chunk stall) — the "
    "resumable-stream contract's failover path; never a restart",
)
STREAM_STALLS = counter(
    "stream_stalls",
    "streamed forwards declared wedged because no chunk arrived within "
    "stream_stall_s (the stream was open but silent); each counts "
    "against the node's breaker and triggers a resume-at-offset",
)

# Breaker state -> transition counter, used by the LMS breaker observer.
# Living HERE keeps the mapping inside the declared namespace: the lint
# rule treats any name expression rooted at this module as declared by
# construction.
BREAKER_TRANSITION_COUNTERS: Dict[str, str] = {
    "closed": TUTORING_BREAKER_CLOSED,
    "open": TUTORING_BREAKER_OPEN,
    "half_open": TUTORING_BREAKER_HALF_OPEN,
}

# LMS group router (lms/group_router.py) — course-sharded control plane.
# Aggregate series only: per-group detail is deliberately served by
# GET /admin/raft instead of runtime-formatted metric names, which this
# registry forbids.

ROUTER_GROUP_FORWARDS = counter(
    "router_group_forwards",
    "LMS RPCs the router forwarded to another node because that node "
    "leads the subject's Raft group",
)
ROUTER_FANOUT_READS = counter(
    "router_fanout_reads",
    "cross-group reads (course materials, unanswered queries) fanned "
    "out to every group's leader and merged",
)
ROUTER_FROZEN_REJECTIONS = counter(
    "router_frozen_rejections",
    "writes/reads refused with UNAVAILABLE because the subject was "
    "frozen or tombstoned mid-reshard (the client retries against the "
    "flipped routing map; never a silent drop)",
)
ROUTER_UNSIGNED_METADATA = counter(
    "router_unsigned_metadata_rejections",
    "RPCs whose x-lms-* control metadata (group targeting, forced auth "
    "salt/token) carried no valid router HMAC and was ignored — a "
    "client forgery or a router-secret mismatch across the deployment",
)
RESHARD_STEPS = counter(
    "reshard_steps",
    "journaled reshard handoff steps persisted to the meta group "
    "(begin/frozen/installed/committed/done)",
)
RESHARD_COMPLETED = counter(
    "reshard_completed",
    "reshard handoffs that reached 'done': slice installed on the "
    "target, map flipped, source copy dropped behind tombstones",
)
ROUTING_MAP_VERSION = gauge(
    "routing_map_version",
    "version of the replicated course->group routing map this router "
    "last parsed from the meta group",
)

# Tutoring node (serving/tutoring_server.py + engine/batcher.py).

LLM_UNAUTHORIZED = counter(
    "llm_unauthorized",
    "direct-dial queries refused for lacking the LMS leader's HMAC ticket",
)
LLM_FAILURES = counter(
    "llm_failures", "generation failures surfaced to the client"
)
ANSWER_LATENCY = histogram(
    "answer_latency", "full GetLLMAnswer latency on the tutoring node"
)
TTFT = histogram(
    "ttft",
    "engine-measured time between a request's prefill and its first "
    "decoded token",
)
TUTORING_DRAINING = gauge(
    "tutoring_draining",
    "1 while this tutoring node is draining (POST /admin/drain): new "
    "requests are refused while in-flight work finishes and the fleet "
    "router ejects the node from its ring",
)
TUTORING_DRAIN_REJECTIONS = counter(
    "tutoring_drain_rejections",
    "requests refused because this tutoring node was draining (the "
    "router spills them to another fleet member)",
)
STREAM_CHUNKS = counter(
    "stream_chunks",
    "StreamLLMAnswer chunks sent (LMS leader and tutoring node each "
    "count their own side of the stream)",
)
SESSION_ACTIVE = gauge(
    "session_active",
    "live multi-turn tutoring sessions this node holds transcripts for "
    "([sessions] ttl_s expiry, max_sessions cap)",
)
SESSION_PINNED_BLOCKS = gauge(
    "session_pinned_blocks",
    "shared-prefix KV blocks held resident by live session pins (soft "
    "pins: TTL-expired first under eviction pressure, then "
    "soonest-expiry live pins — hard refcount pins are never evicted)",
)
SHED_EXPIRED = counter(
    "shed_expired",
    "requests dropped because their deadline budget expired before "
    "prefill dispatched",
)
SHED_OVERLOAD = counter(
    "shed_overload",
    "requests refused at admission because the bounded queue was full "
    "(RESOURCE_EXHAUSTED on the wire)",
)
SPEC_TOKENS_PER_WINDOW = gauge(
    "spec_tokens_per_window",
    "speculation effectiveness: mean emitted tokens per verify window "
    "(1.0 = nothing accepted, ceiling spec_tokens+1)",
)
SPEC_ACCEPTED_TOKENS = counter(
    "spec_accepted_tokens",
    "tokens speculation produced beyond the guaranteed one per verify "
    "window",
)
MEGASTEP_K = gauge(
    "megastep_k",
    "live megastep controller value: device chunks fused per host "
    "dispatch (1 = plain chunk loop; grows toward megastep_max when "
    "idle, capped at the next guaranteed slot-free horizon while "
    "admissions wait)",
)
MEGASTEP_DEAD_LANE_TOKENS = counter(
    "megastep_dead_lane_tokens",
    "pad token positions decoded by slots that finished inside a "
    "megastep before its boundary let the host reap them (spec-mode "
    "lanes count spec_tokens+1 positions each; megastep overhead, zero "
    "at K=1)",
)
HOST_DISPATCHES_PER_TOKEN = gauge(
    "host_dispatches_per_token",
    "host program dispatches paid per emitted token on the paged engine "
    "(cumulative ratio; the megastep exists to shrink it)",
)
PREFIX_CACHE_HIT_TOKENS = counter(
    "prefix_cache_hit_tokens",
    "prompt tokens whose KV was spliced from the shared-prefix radix "
    "cache instead of being re-prefilled (the device time the cache "
    "saves)",
)
PREFIX_CACHE_EVICTIONS = counter(
    "prefix_cache_evictions",
    "shared-prefix KV blocks evicted under the block budget (LRU "
    "unpinned leaves; blocks a live slot references are never freed)",
)
PREFIX_CACHE_BLOCKS_USED = gauge(
    "prefix_cache_blocks_used",
    "shared-prefix KV blocks currently resident in the radix tree "
    "(may transiently exceed the budget while every leaf is pinned)",
)
PREFIX_CACHE_HIT_RATE = gauge(
    "prefix_cache_hit_rate",
    "cumulative fraction of admitted prompt tokens served from the "
    "shared-prefix cache (hit tokens / prompt tokens since queue start)",
)
SERVING_TOKENS_PER_S = gauge(
    "serving_tokens_per_s",
    "recent serving throughput on the paged engine: emitted tokens per "
    "second over the last few seconds of reaps — the numerator the "
    "capacity model (scripts/telemetry.py --capacity) holds against a "
    "measured ceiling given with --ceiling",
)
SERVING_QUEUE_DEPTH = gauge(
    "serving_queue_depth",
    "requests admitted but not yet in a device batch (the bound "
    "`max_queue` is enforced against), sampled at each scheduling "
    "round — queue growth at flat tokens/s is the saturation signal "
    "the capacity model and autoscaler watch",
)
SERVING_TP = gauge(
    "serving_tp",
    "tensor-parallel ways of the serving engine's mesh — the factor the "
    "paged KV planes shard their heads axis by (partition."
    "PAGED_PLANE_SPECS), joining per-chip gauges back to the mesh they "
    "were measured on",
)
SERVING_KV_BYTES_PER_CHIP = gauge(
    "serving_kv_bytes_per_chip",
    "HBM the paged slot KV working set occupies on EACH chip at the "
    "current cache width (total KV bytes / tp — the heads-axis sharding "
    "splits the planes evenly) — the per-node residency ceiling "
    "multi-chip paged serving raises to chip-count x HBM",
)

# Background bulk-scoring tenant (engine/scoring.py + engine/batcher.py):
# idle-lane harvest — preemptible score quanta co-scheduled behind
# interactive traffic, driving the chip toward its saturation ceiling.

SCORING_TOKENS_PER_S = gauge(
    "scoring_tokens_per_s",
    "recent background-scoring throughput: tokens scored per second over "
    "the last few seconds of quanta — the scoring tenant's half of the "
    "tenant-split utilization view (serving_tokens_per_s is the "
    "interactive half)",
)
SCORING_QUANTA = counter(
    "scoring_quanta",
    "single-dispatch scoring quanta executed (one batch-bucket forward "
    "each — the preemption granularity interactive arrivals wait behind "
    "at most one of)",
)
SCORING_SCORED_TOKENS = counter(
    "scoring_scored_tokens",
    "corpus tokens the background tenant has scored (bulk grading / "
    "relevance / calibration texts; the cumulative companion of the "
    "scoring_tokens_per_s gauge)",
)
SCORING_JOBS_COMPLETED = counter(
    "scoring_jobs_completed",
    "bulk score jobs run to completion by the background tenant",
)
SCORING_JOBS_FAILED = counter(
    "scoring_jobs_failed",
    "bulk score jobs that failed (the job fails; the serving loop and "
    "other jobs keep going)",
)
SCORE_TRUNCATED_TEXTS = counter(
    "score_truncated_texts",
    "scored texts longer than the length-bucket limit whose PREFIX was "
    "scored (each carries a per-item truncated flag so relevance evals "
    "can't silently read a prefix score as a full-document score)",
)
SCORE_PREEMPT_WAIT_MS = counter(
    "score_preempt_wait_ms",
    "milliseconds interactive requests waited behind an in-flight "
    "scoring quantum before admission resumed (bounded by one quantum "
    "per arrival — the scoring tenant's preemption-latency account)",
)

# Per-program engine dispatch wall time (host-side: the time the serving
# loop spends issuing each compiled program; device compute overlaps it
# under pipelining). Names key the program-inventory entries — the
# serving queues map the engine's reported program name through
# ENGINE_PROGRAM_HISTOGRAMS below, and the same measurements become
# `engine.<program>` spans on the request trace.

ENGINE_PROG_MEGASTEP = histogram(
    "engine_prog_megastep",
    "paged-engine _megastep program dispatch wall time (K chunks of "
    "decode fused into one device-resident dispatch)",
)
ENGINE_PROG_GROW = histogram(
    "engine_prog_grow",
    "paged-engine _grow program dispatch wall time (cache width "
    "transition)",
)
ENGINE_PROG_STAGE = histogram(
    "engine_prog_stage",
    "paged-engine _stage program dispatch wall time (admission: "
    "arming a slot's staged prompt; the prefill itself runs inside the "
    "megastep scan)",
)
ENGINE_PROG_STAGE_BLOCK = histogram(
    "engine_prog_stage_block",
    "paged-engine _stage_block program dispatch wall time (admission: "
    "cached shared-prefix blocks spliced into a slot's "
    "pages, a stored run of 128, a run of up to 16 or a single block a "
    "call; one observation per call)",
)
ENGINE_PROG_EXPORT_BLOCK = histogram(
    "engine_prog_export_block",
    "paged-engine _export_block program dispatch wall time (one prompt "
    "or session block, or one stored run of 128, copied out of a cache "
    "into the radix tree, or a block cut out of a stored run; one "
    "observation per call)",
)
ENGINE_PROG_SCORE = histogram(
    "engine_prog_score",
    "score program dispatch wall time (one background-scoring quantum: "
    "a full-sequence batch-bucket forward — the preemption granularity)",
)

# Engine-reported program name -> declared histogram, used by the serving
# queue (engine/batcher.py). Living HERE keeps the mapping inside the
# declared namespace (see BREAKER_TRANSITION_COUNTERS).
ENGINE_PROG_RESTORE_STATE = histogram(
    "engine_prog_restore_state",
    "paged-engine _restore_state program dispatch wall time (admission "
    "of a recurrent family: a prefix hit's state snapshot put into the "
    "slot's rows of the state planes)",
)
ENGINE_PROG_EXPORT_STATE = histogram(
    "engine_prog_export_state",
    "paged-engine _export_state program dispatch wall time (a recurrent "
    "family: the state a prefill snapshotted, copied out of the slot's "
    "snapshot rows for the prefix tree)",
)
ENGINE_PROGRAM_HISTOGRAMS: Dict[str, str] = {
    "megastep": ENGINE_PROG_MEGASTEP,
    "grow": ENGINE_PROG_GROW,
    "stage": ENGINE_PROG_STAGE,
    "stage_block": ENGINE_PROG_STAGE_BLOCK,
    "export_block": ENGINE_PROG_EXPORT_BLOCK,
    "restore_state": ENGINE_PROG_RESTORE_STATE,
    "export_state": ENGINE_PROG_EXPORT_STATE,
    "score": ENGINE_PROG_SCORE,
}

# The paged engine's loop, counted where the work happens (engine/paged.py
# `_count` / `_observe`, drained by PagedQueue through `pop_loop_stats`
# and `pop_dispatch_stats`). Counters, so a reader differences them over
# any window; the run-long ratio gauges above are computed from them.

ENGINE_TOKENS_EMITTED = counter(
    "engine_tokens_emitted",
    "tokens the paged engine handed to requests (first tokens and reaped "
    "decode tokens): the denominator of every per-token ratio",
)
ENGINE_DISPATCHES = counter(
    "engine_dispatches",
    "compiled programs the paged engine's host loop dispatched (every "
    "engine.prog.* span, per-block copies included): what "
    "host_dispatches_per_token divides",
)
ENGINE_PROMPT_TOKENS_ADMITTED = counter(
    "engine_prompt_tokens_admitted",
    "prompt tokens of the requests admitted to a slot (as served, after "
    "the engine's cut to its largest prompt bucket)",
)
ENGINE_PREFILL_TOKENS = counter(
    "engine_prefill_tokens",
    "prompt tokens admitted less the shared-prefix hit: the positions "
    "the prefill (in the scan, or its own program) really computes",
)
ENGINE_SCAN_ITERATIONS = counter(
    "engine_scan_iterations",
    "decode scan iterations the device was sent (K x chunk a megastep, "
    "chunk a step; a verify window each in spec mode)",
)
ENGINE_LANE_STEPS = counter(
    "engine_lane_steps",
    "engine_scan_iterations x slots: the lanes' budget, of which decode "
    "tokens, staged, overrun and dead lane-steps are parts and the rest "
    "ran empty",
)
ENGINE_STAGED_LANE_STEPS = counter(
    "engine_staged_lane_steps",
    "lane-steps held by a request still prefilling in the scan: for a "
    "slot staged at dispatch, the scan iterations (rows) before its flip "
    "(all of them if it did not flip)",
)
ENGINE_OVERRUN_LANE_STEPS = counter(
    "engine_overrun_lane_steps",
    "lane-steps run for a request past its last token: the rest of the "
    "dispatch in which the host's budget cap ended it (the device does "
    "not know the cap), and every later dispatch sent with the request "
    "still in its slot (none once the slot has been handed on: "
    "engine_slots_handed_on)",
)
ENGINE_ATTN_POSITIONS_READ = counter(
    "engine_attn_positions_read",
    "positions of its K and V rows the decode kernel that goes by a "
    "lane's length fetches (ops/attention.py quant_decode_attention: the "
    "length rounded up to whole blocks of 32), summed over every decode "
    "lane-step of the dispatches reaped, live lanes and dead ones (an "
    "empty lane keeps its last tenant's length, a staged one is parked at "
    "the width); the host replays the lanes' lengths from what a reap "
    "reads anyway. Counted where the engine's planes are the kernel's "
    "(folded int8, one group, a width of whole blocks, no speculation), "
    "whatever backend runs, and absent elsewhere",
)
ENGINE_ATTN_POSITIONS_HELD = counter(
    "engine_attn_positions_held",
    "the cache's width summed over the same lane-steps: what the two "
    "products over the whole planes read, and engine_attn_positions_read's "
    "denominator",
)
ENGINE_SLOTS_HANDED_ON = counter(
    "engine_slots_handed_on",
    "slots staged for the next request while their previous request's "
    "end was still in flight: its budget cap was certain to lie in the "
    "dispatches not yet reaped, so the slot did not wait for that reap. "
    "Over the requests finished, the share of ends the host foresaw",
)
ENGINE_ONE_CHUNK_DISPATCHES = counter(
    "engine_one_chunk_dispatches",
    "dispatches the K controller sent at K = 1 while work waited for a "
    "slot: some answer's end was certain within one chunk, so the "
    "dispatch ends there and the slot is handed on. Over the "
    "engine_prog_megastep histogram's count, how often the one-chunk "
    "floor engaged; with engine_scan_iterations, the rows a dispatch "
    "carried",
)
ENGINE_PREFILL_PASSES = counter(
    "engine_prefill_passes",
    "in-scan prefill passes the device ran: one forward pass over a chunk "
    "of prefill_chunk_tokens positions for each of the oldest staged "
    "slots, at the head of every scan iteration that found a slot staged; "
    "counted on the device and read back at the dispatch's reap",
)
ENGINE_PREFILL_PASS_SLOTS = counter(
    "engine_prefill_pass_slots",
    "slot-chunks the in-scan prefill passes served (a pass serves up to "
    "128 / prefill_chunk_tokens staged slots a chunk each): over "
    "engine_prefill_passes, the staged slots that shared one reading of "
    "the weights",
)
ENGINE_PREFILL_CROWDED_PASSES = counter(
    "engine_prefill_crowded_passes",
    "in-scan prefill passes that found two or more slots staged: over "
    "engine_prefill_passes, how often a pass had more than one slot to "
    "serve",
)
ENGINE_PREFILL_CROWDED_NARROW_PASSES = counter(
    "engine_prefill_crowded_narrow_passes",
    "of engine_prefill_crowded_passes, those in a dispatch of more than "
    "one chunk, whose program serves one slot a pass whatever is staged: "
    "the passes that had more than one slot to serve and served one",
)
MOE_PICKS = counter(
    "moe_picks",
    "expert picks computed by the routed layers (live tokens x experts "
    "per token x expert layers), summed on the device over a dispatch's "
    "forward passes and read back at its reap; only a family with routed "
    "experts counts (models/afmoe.py, models/axk1.py)",
)
MOE_EXPERTS_REACHED = counter(
    "moe_experts_reached",
    "experts that got at least one pick, summed over expert layers and "
    "forward passes: the experts whose weights a pass reads",
)
MOE_EXPERT_SEATS = counter(
    "moe_expert_seats",
    "experts there were to reach: experts held x expert layers, once per "
    "forward pass (reached / seats is the share of the expert weights a "
    "pass reads; an idle lane that routed would raise it)",
)
MOE_PICKS_HELD = counter(
    "moe_picks_held",
    "of moe_picks, those that landed on an expert this process holds: "
    "only a family that holds a share of each layer's experts counts "
    "(models/axk1.py `experts_held`; held / picks is the share of the "
    "routed work this chip does, 12 / 192 where the router is fair to it)",
)
MOE_PASSES_BOUNDED = counter(
    "moe_passes_bounded",
    "forward passes of a routed layer whose grouped products had fewer "
    "rows to run over than the pass had picks: a share of the layer's "
    "experts, whose held picks sort into a static prefix of the rows, "
    "what a fair router sends the share and twelve deviations more where "
    "that is not every row (models/moe.py `held_rows`; a share of a half "
    "and more runs whole); summed over expert layers, only a family that "
    "holds a share under a half counts",
)
MOE_PASSES_COMPACTED = counter(
    "moe_passes_compacted",
    "of moe_passes_bounded, the passes whose held picks fit the prefix, "
    "so that the products ran over it alone; the others ran over every "
    "row, and no pick is dropped either way (compacted / bounded is how "
    "often the bound engages: 1 where the router is fair to the share)",
)
ENGINE_ATTN_LANE_STEPS = counter(
    "engine_attn_lane_steps",
    "decode lane-steps of a block-sparse attention layer (live lanes x "
    "sparse layers), summed on the device over a dispatch's decode steps "
    "and read back at its reap (models/minicpm_sala.py); in a family of "
    "conv and attention layers (models/lfm2.py) the live tokens' passes "
    "through an attention layer, decode and prefill alike, beside "
    "engine_conv_lane_steps",
)
ENGINE_CONV_LANE_STEPS = counter(
    "engine_conv_lane_steps",
    "live tokens' passes through a gated short-convolution layer (live "
    "tokens x conv layers, a decode lane or a prefill position that is "
    "real), summed on the device over a dispatch's forward passes and "
    "read back at its reap; only models/lfm2.py counts. Over it plus "
    "engine_attn_lane_steps: the conv layers' share of the operator "
    "passes, the cut's ten of thirteen wherever no layer kind was lost "
    "and no dead lane counted",
)
ENGINE_SPARSE_LANE_STEPS = counter(
    "engine_sparse_lane_steps",
    "of engine_attn_lane_steps, those whose query stood at or past "
    "dense_len and read the blocks it chose (the kernels sparse_select "
    "and sparse_decode), not its row",
)
ENGINE_SPARSE_KEYS_ATTENDED = counter(
    "engine_sparse_keys_attended",
    "keys the lane-steps of engine_attn_lane_steps attended: the chosen "
    "blocks' keys at or behind the query on the sparse arm, every key "
    "of the context on the dense one",
)
ENGINE_SPARSE_KEYS_IN_CONTEXT = counter(
    "engine_sparse_keys_in_context",
    "keys the contexts of the same lane-steps held (attended / in "
    "context is the share of a row the sparse layers read)",
)
ENGINE_TOKENS_PAST_WINDOW = counter(
    "engine_tokens_past_window",
    "tokens emitted at a position at or past the model's sliding_window, "
    "where every window layer drops keys; counted on the host at the "
    "reap, of the same tokens as engine_tokens_emitted",
)
ENGINE_STATE_SNAPSHOTS_TAKEN = counter(
    "engine_state_snapshots_taken",
    "state snapshots of a recurrent family (models/mamba2.py) that "
    "entered the prefix tree: the state a prefill left at a block "
    "boundary, exported when its flip was reaped",
)
ENGINE_STATE_SNAPSHOTS_RESTORED = counter(
    "engine_state_snapshots_restored",
    "admissions of a recurrent family that started from a state "
    "snapshot of the prefix tree instead of from zeros",
)
ENGINE_PREFIX_TOKENS_RECOMPUTED_FOR_STATE = counter(
    "engine_prefix_tokens_recomputed_for_state",
    "prompt tokens whose keys and values the prefix tree matched but "
    "which were prefilled again because no state snapshot stood that "
    "deep (a recurrent family's hit is only as long as its deepest "
    "snapshot); over engine_prompt_tokens_admitted it is the share of "
    "the prompts the snapshots' placement costs",
)
ENGINE_ADMISSIONS = counter(
    "engine_admissions",
    "prompts the paged engine admitted into a slot (staged: prompt "
    "written, the prefix hit spliced, the in-scan prefill armed)",
)
ENGINE_STAGE_BLOCK_LAUNCHES = counter(
    "engine_stage_block_launches",
    "launches of the prefix splice program (`_stage_block`), of every "
    "kind: a lone block, a run of up to 16 blocks concatenated on the "
    "device, a stored run handed over in one array a plane; over "
    "engine_admissions it is what an admission's hit costs the thread "
    "that launches",
)
ENGINE_PREFIX_TOKENS_FROM_RUNS = counter(
    "engine_prefix_tokens_from_runs",
    "prefix-hit tokens that were spliced from stored runs of the prefix "
    "tree (engine/prefix_cache.py `RunBlock`: a long edge's blocks in one "
    "array a plane, one launch a run); over prefix_cache_hit_tokens it is "
    "the share of the hits the stored runs served",
)
ENGINE_TIMED_DISPATCHES = counter(
    "engine_timed_dispatches",
    "megasteps of ONE chunk (K = 1) whose device time the dispatch ledger "
    "read (engine/spans.py DispatchLedger): device time as the host saw "
    "two completions apart, each seen at the end of the call into the "
    "runtime that began with the dispatch unfinished (the reap's read, or "
    "a launch that met the runtime's full queue), the later dispatch "
    "queued while the earlier ran, so nothing idle lies between. Over "
    "these the "
    "engine_timed_* sums are those least squares needs for device_us = b "
    "+ n x narrow passes + w x wide passes",
)
ENGINE_TIMED_ITERATIONS = counter(
    "engine_timed_iterations",
    "scan iterations of the engine_timed_dispatches (each its chunk)",
)
ENGINE_TIMED_DEVICE_US = counter(
    "engine_timed_device_us",
    "device time as the host saw two completions apart, summed over the "
    "engine_timed_dispatches, microseconds: the megastep, the copies of "
    "its few KB of results, and the admission's small programs launched "
    "before it (stage, stage_block, restore_state, the key splits); "
    "nothing is subtracted",
)
ENGINE_TIMED_NARROW_PASSES = counter(
    "engine_timed_narrow_passes",
    "prefill passes of one row the engine_timed_dispatches ran (their "
    "passes less the crowded ones)",
)
ENGINE_TIMED_WIDE_PASSES = counter(
    "engine_timed_wide_passes",
    "prefill passes of four rows the engine_timed_dispatches ran (the "
    "passes that found two or more slots staged, at K = 1)",
)
ENGINE_TIMED_NARROW_SQ = counter(
    "engine_timed_narrow_sq",
    "sum over the engine_timed_dispatches of (narrow passes) squared",
)
ENGINE_TIMED_WIDE_SQ = counter(
    "engine_timed_wide_sq",
    "sum over the engine_timed_dispatches of (wide passes) squared",
)
ENGINE_TIMED_NARROW_X_WIDE = counter(
    "engine_timed_narrow_x_wide",
    "sum over the engine_timed_dispatches of narrow passes x wide passes",
)
ENGINE_TIMED_US_X_NARROW = counter(
    "engine_timed_us_x_narrow",
    "sum over the engine_timed_dispatches of device time as the host saw "
    "two completions apart (microseconds) x narrow passes",
)
ENGINE_TIMED_US_X_WIDE = counter(
    "engine_timed_us_x_wide",
    "sum over the engine_timed_dispatches of device time as the host saw "
    "two completions apart (microseconds) x wide passes",
)
ENGINE_TIMED_BARE_DISPATCHES = counter(
    "engine_timed_bare_dispatches",
    "of engine_timed_dispatches, those that ran no prefill pass: the "
    "decode iterations alone",
)
ENGINE_TIMED_BARE_DEVICE_US = counter(
    "engine_timed_bare_device_us",
    "device time as the host saw two completions apart, summed over the "
    "engine_timed_bare_dispatches, microseconds; over their iterations "
    "(bare dispatches x chunk) it is a decode iteration's device time, "
    "read directly",
)
ENGINE_TIMED_LONG_DISPATCHES = counter(
    "engine_timed_long_dispatches",
    "megasteps of a longer rung (K > 1: an idle server grows into them, "
    "and their pass is always narrow) whose device time the dispatch "
    "ledger read",
)
ENGINE_TIMED_LONG_ITERATIONS = counter(
    "engine_timed_long_iterations",
    "scan iterations of the engine_timed_long_dispatches (K x chunk each)",
)
ENGINE_TIMED_LONG_DEVICE_US = counter(
    "engine_timed_long_device_us",
    "device time as the host saw two completions apart, summed over the "
    "engine_timed_long_dispatches, microseconds",
)
ENGINE_UNTIMED_DISPATCHES_LATE = counter(
    "engine_untimed_dispatches_late",
    "megasteps reaped whose completion no call into the runtime saw: "
    "they finished while the host walked tokens or stood between two "
    "steps (or two finished inside one call), so the dispatch is untimed "
    "and anchors nothing (a pause of the HOST shows here and as a stall "
    "in the loop's budget)",
)
ENGINE_UNTIMED_DISPATCHES_UNANCHORED = counter(
    "engine_untimed_dispatches_unanchored",
    "megasteps seen completing whose predecessor's completion was not "
    "seen (it was late), or that were sent to a dry device, and the "
    "engine's first dispatch, the first after a reset and the reaps of "
    "the drain once nothing is live: "
    "untimed, but the next is timed from it. Timed (one chunk and long) "
    "and untimed (late and unanchored) together are every megastep "
    "reaped",
)
ENGINE_DISPATCHES_DEVICE_DRY = counter(
    "engine_dispatches_device_dry",
    "megasteps sent while the newest one in flight was already finished "
    "(or none was in flight): the device ran out of megasteps before "
    "this one was sent",
)
ENGINE_BARE_ITERATION_DEVICE = histogram(
    "engine_bare_iteration_device",
    "device time as the host saw two completions apart of a one-chunk "
    "dispatch that ran no prefill pass, over its iterations: seconds a "
    "decode iteration, one observation a bare timed dispatch. A pause ON "
    "the device is an observation many times the median",
)
ENGINE_STATE_SNAPSHOT_BYTES = gauge(
    "engine_state_snapshot_bytes",
    "bytes of the state snapshots the prefix tree holds for a recurrent "
    "family (bounded in number, dropped least recently used first and "
    "with their nodes)",
)
QUEUE_WAIT = histogram(
    "queue_wait",
    "engine submit -> popped from the pending queue for admission, per "
    "request (waiting for a slot)",
)
INCOMING_WAIT = histogram(
    "incoming_wait",
    "PagedQueue.submit -> the engine has the request, per request: its "
    "stay in the queue's incoming list until the loop's next turn, plus "
    "the tokenizer's pass inside engine.submit (the queue.submit span). "
    "Before queue_wait's clock starts: incoming_wait + queue_wait + "
    "prefill_wait is the server's whole share of a first token",
)
PREFILL_WAIT = histogram(
    "prefill_wait",
    "popped for admission -> first token on the host, per request: "
    "staging, the prefill and the pipeline's lag (queue_wait + this = "
    "ttft)",
)
ENGINE_REAP_WAIT = histogram(
    "engine_reap_wait",
    "host blocked on the device's results, per reaped dispatch (the "
    "engine.reap.wait span)",
)
ENGINE_HOST_TURN = histogram(
    "engine_host_turn",
    "one turn of the serving loop (from before engine.step is handed to "
    "its thread to the return of queue.between_steps) less the wall of "
    "its engine.reap.wait spans. NOT the host's own work: the host waits "
    "for the device inside the dispatch call too, so under load this "
    "reads one or two whole dispatches of device time "
    "(engine_host_work is the host's work)",
)
ENGINE_HOST_WORK = histogram(
    "engine_host_work",
    "the host's own work in one turn of the serving loop: the CPU time "
    "(time.thread_time) of engine.step on its thread and of "
    "queue.between_steps on the loop's, one observation a turn; the "
    "turn's engine_loop_host_work_us in seconds",
)
ENGINE_LOOP_WALL_US = counter(
    "engine_loop_wall_us",
    "wall of the serving loop's turns, microseconds: each from before "
    "engine.step is handed to its thread to the return of "
    "queue.between_steps (queue.idle is no turn). device_wait + "
    "host_work + stall, exactly",
)
ENGINE_LOOP_DEVICE_WAIT_US = counter(
    "engine_loop_device_wait_us",
    "of engine_loop_wall_us, the step's thread asleep inside a call into "
    "the runtime: wall less CPU time of the engine.reap.wait, "
    "engine.prog.* and engine.keys spans under engine.step (whichever "
    "call meets the runtime's full launch queue sleeps until the device "
    "finishes a program)",
)
ENGINE_LOOP_HOST_WORK_US = counter(
    "engine_loop_host_work_us",
    "of engine_loop_wall_us, the loop's own CPU time: engine.step's on "
    "its thread plus queue.between_steps' on the loop's. The wall less "
    "this and engine_loop_device_wait_us is the turn's stall: neither "
    "this code's CPU nor a wait for the device (the hand-off between the "
    "threads, the GIL held by others, the process descheduled)",
)
ENGINE_LOOP_CPU_US_ADMIT = counter(
    "engine_loop_cpu_us_admit",
    "CPU time of the engine.admit spans, the engine.prog.* calls inside "
    "them included, microseconds; with its four siblings it sums to "
    "engine_loop_host_work_us less engine.step's self time",
)
ENGINE_LOOP_CPU_US_DISPATCH = counter(
    "engine_loop_cpu_us_dispatch",
    "CPU time of the engine.dispatch spans (the megastep call: a runtime "
    "that spins where it waits would show here), microseconds",
)
ENGINE_LOOP_CPU_US_REAP_WAIT = counter(
    "engine_loop_cpu_us_reap_wait",
    "CPU time of the engine.reap.wait spans (copying a dispatch's results "
    "to numpy), microseconds",
)
ENGINE_LOOP_CPU_US_REAP_HOST = counter(
    "engine_loop_cpu_us_reap_host",
    "CPU time of the engine.reap.host spans (the token walk, decoding, "
    "the prefix tree's exports), microseconds",
)
ENGINE_LOOP_CPU_US_BETWEEN_STEPS = counter(
    "engine_loop_cpu_us_between_steps",
    "CPU time of the queue.between_steps spans on the loop's thread "
    "(metrics, stream chunks, futures, arrivals), microseconds",
)
ENGINE_DECODE_LANES = histogram(
    "engine_decode_lanes",
    "decode tokens reaped from a dispatch / its scan iterations, per "
    "reaped dispatch: the batch size a step really ran at. The value is "
    "a count of LANES (at most the slot count), not seconds",
)
ENGINE_STAGED_ITERATIONS = histogram(
    "engine_staged_iterations",
    "scan iterations a request held a lane while staged, observed once "
    "per request at its flip: the rows of every dispatch reaped before "
    "the flip's, plus the flip's row in its own. The value is a count of "
    "ITERATIONS, not seconds",
)
STREAM_CHUNK_GAP = histogram(
    "stream_chunk_gap",
    "time since the stream's previous chunk, per chunk pushed to a "
    "streaming client (the gap a student sees between bursts)",
)

# Engine-reported short name -> declared series (see
# ENGINE_PROGRAM_HISTOGRAMS for why the mappings live here).
ENGINE_LOOP_COUNTERS: Dict[str, str] = {
    "prompt_tokens": ENGINE_PROMPT_TOKENS_ADMITTED,
    "prefill_tokens": ENGINE_PREFILL_TOKENS,
    "scan_iterations": ENGINE_SCAN_ITERATIONS,
    "lane_steps": ENGINE_LANE_STEPS,
    "staged_lane_steps": ENGINE_STAGED_LANE_STEPS,
    "overrun_lane_steps": ENGINE_OVERRUN_LANE_STEPS,
    "attn_positions_read": ENGINE_ATTN_POSITIONS_READ,
    "attn_positions_held": ENGINE_ATTN_POSITIONS_HELD,
    "slots_handed_on": ENGINE_SLOTS_HANDED_ON,
    "one_chunk_dispatches": ENGINE_ONE_CHUNK_DISPATCHES,
    "prefill_passes": ENGINE_PREFILL_PASSES,
    "prefill_pass_slots": ENGINE_PREFILL_PASS_SLOTS,
    "prefill_crowded_passes": ENGINE_PREFILL_CROWDED_PASSES,
    "prefill_crowded_narrow_passes": ENGINE_PREFILL_CROWDED_NARROW_PASSES,
    "moe_picks": MOE_PICKS,
    "moe_experts_reached": MOE_EXPERTS_REACHED,
    "moe_expert_seats": MOE_EXPERT_SEATS,
    "moe_picks_held": MOE_PICKS_HELD,
    "moe_passes_bounded": MOE_PASSES_BOUNDED,
    "moe_passes_compacted": MOE_PASSES_COMPACTED,
    "attn_lane_steps": ENGINE_ATTN_LANE_STEPS,
    "conv_lane_steps": ENGINE_CONV_LANE_STEPS,
    "sparse_lane_steps": ENGINE_SPARSE_LANE_STEPS,
    "sparse_keys_attended": ENGINE_SPARSE_KEYS_ATTENDED,
    "sparse_keys_in_context": ENGINE_SPARSE_KEYS_IN_CONTEXT,
    "tokens_past_window": ENGINE_TOKENS_PAST_WINDOW,
    "state_snapshots_taken": ENGINE_STATE_SNAPSHOTS_TAKEN,
    "state_snapshots_restored": ENGINE_STATE_SNAPSHOTS_RESTORED,
    "prefix_tokens_recomputed_for_state":
        ENGINE_PREFIX_TOKENS_RECOMPUTED_FOR_STATE,
    "admissions": ENGINE_ADMISSIONS,
    "stage_block_launches": ENGINE_STAGE_BLOCK_LAUNCHES,
    "prefix_tokens_from_runs": ENGINE_PREFIX_TOKENS_FROM_RUNS,
    # The dispatch ledger (engine/spans.py `DispatchLedger`).
    "timed_dispatches": ENGINE_TIMED_DISPATCHES,
    "timed_iterations": ENGINE_TIMED_ITERATIONS,
    "timed_device_us": ENGINE_TIMED_DEVICE_US,
    "timed_narrow_passes": ENGINE_TIMED_NARROW_PASSES,
    "timed_wide_passes": ENGINE_TIMED_WIDE_PASSES,
    "timed_narrow_sq": ENGINE_TIMED_NARROW_SQ,
    "timed_wide_sq": ENGINE_TIMED_WIDE_SQ,
    "timed_narrow_x_wide": ENGINE_TIMED_NARROW_X_WIDE,
    "timed_us_x_narrow": ENGINE_TIMED_US_X_NARROW,
    "timed_us_x_wide": ENGINE_TIMED_US_X_WIDE,
    "timed_bare_dispatches": ENGINE_TIMED_BARE_DISPATCHES,
    "timed_bare_device_us": ENGINE_TIMED_BARE_DEVICE_US,
    "timed_long_dispatches": ENGINE_TIMED_LONG_DISPATCHES,
    "timed_long_iterations": ENGINE_TIMED_LONG_ITERATIONS,
    "timed_long_device_us": ENGINE_TIMED_LONG_DEVICE_US,
    "untimed_dispatches_late": ENGINE_UNTIMED_DISPATCHES_LATE,
    "untimed_dispatches_unanchored": ENGINE_UNTIMED_DISPATCHES_UNANCHORED,
    "dispatches_device_dry": ENGINE_DISPATCHES_DEVICE_DRY,
    # One turn's budget (engine/spans.py `turn_budget`), counted by
    # PagedQueue from the spans the engine drains with the rest.
    "loop_wall_us": ENGINE_LOOP_WALL_US,
    "loop_device_wait_us": ENGINE_LOOP_DEVICE_WAIT_US,
    "loop_host_work_us": ENGINE_LOOP_HOST_WORK_US,
    "loop_cpu_us_admit": ENGINE_LOOP_CPU_US_ADMIT,
    "loop_cpu_us_dispatch": ENGINE_LOOP_CPU_US_DISPATCH,
    "loop_cpu_us_reap_wait": ENGINE_LOOP_CPU_US_REAP_WAIT,
    "loop_cpu_us_reap_host": ENGINE_LOOP_CPU_US_REAP_HOST,
    "loop_cpu_us_between_steps": ENGINE_LOOP_CPU_US_BETWEEN_STEPS,
}
ENGINE_LOOP_HISTOGRAMS: Dict[str, str] = {
    "queue_wait": QUEUE_WAIT,
    "prefill_wait": PREFILL_WAIT,
    "reap_wait": ENGINE_REAP_WAIT,
    "decode_lanes": ENGINE_DECODE_LANES,
    "staged_iterations": ENGINE_STAGED_ITERATIONS,
    "host_work": ENGINE_HOST_WORK,
    "bare_iteration_device": ENGINE_BARE_ITERATION_DEVICE,
}

# Storage layer (raft/storage.py + lms/persistence.py via lms/node.py).

WAL_TORN_TAIL_TRUNCATIONS = counter(
    "wal_torn_tail_truncations",
    "Raft WAL replays that dropped a torn final record (crash mid-append; "
    "the record was never acked durable)",
)
WAL_CORRUPT_RECORDS = counter(
    "wal_corrupt_records",
    "Raft WAL records that failed CRC/framing checks mid-file (bit rot / "
    "merged short write) — the node refuses to trust the log and recovers "
    "per [storage].recovery",
)
SNAPSHOT_INTEGRITY_FAILURES = counter(
    "snapshot_integrity_failures",
    "LMS state snapshots that failed their integrity header check at load",
)
STORAGE_RECOVERING = gauge(
    "storage_recovering",
    "1 while this node has discarded corrupt local storage and is "
    "rejoining via leader replication / InstallSnapshot; 0 once healed",
)
STALE_TMP_FILES_REMOVED = counter(
    "stale_tmp_files_removed",
    "orphaned atomic-write temp files (.raftwal.* / .lmssnap.* / .blob*) "
    "swept at boot, leaked by a crash between mkstemp and rename",
)

# Chaos admin plane (utils/faults.py CampaignRunner, serving/lms_server.py).

FAULT_CAMPAIGN_PHASES = counter(
    "fault_campaign_phases",
    "fault-campaign phases the admin plane applied (each phase installs "
    "one injector spec for its duration, then clears it)",
)

# Semester simulator (sim/): client-side series the harness exports in its
# BENCH record; the SLO checker reads them next to the cluster's /metrics.

SIM_OPS_OK = counter(
    "sim_ops_ok", "simulated student/instructor ops that succeeded"
)
SIM_OPS_FAILED = counter(
    "sim_ops_failed",
    "simulated ops that failed terminally (retries and budget exhausted)",
)
SIM_OPS_DROPPED = counter(
    "sim_ops_dropped",
    "simulated ops shed unexecuted because their worker fell further "
    "behind the trace than the lag bound (closed-loop overload, not a "
    "cluster failure)",
)
SIM_OP_LATENCY = histogram(
    "sim_op_latency", "client-observed latency of every simulated op"
)
SIM_ASK_LATENCY = histogram(
    "sim_ask_latency",
    "client-observed ask_llm latency (its p95 is the semester-sim answer "
    "SLO)",
)
SIM_DEGRADED_ANSWERS = counter(
    "sim_degraded_answers",
    "ask_llm calls answered by the degraded instructor-queue fallback, "
    "as seen by the simulated clients",
)
SIM_EVENTS_INJECTED = counter(
    "sim_events_injected",
    "operations-schedule events the semester sim executed (transfers, "
    "quarantines, membership changes, chaos campaigns)",
)
SIM_RYW_VIOLATIONS = counter(
    "sim_ryw_violations",
    "read-your-writes violations the in-run ledger auditor observed "
    "(a write acked before the read started was not visible)",
)
SIM_ACKED_WRITE_LOSSES = counter(
    "sim_acked_write_losses",
    "acked writes the end-of-run ledger audit could not find in the "
    "cluster (the zero-acked-write-loss SLO; must stay 0)",
)
SIM_SLO_VIOLATIONS = counter(
    "sim_slo_violations", "semester-sim SLO checks that failed"
)
SIM_BURN_ALERTS = counter(
    "sim_burn_alerts",
    "burn-rate alerts the continuous SLO engine raised during the run "
    "(fast- and slow-window; each is also recorded as a timeline event "
    "and classified against the injected-fault phases in the verdict)",
)
SIM_SESSION_TURNS = counter(
    "sim_session_turns",
    "streamed follow-up-chain turns the simulated students completed "
    "(each is one StreamLLMAnswer call carrying a session id)",
)
SIM_SESSION_TURNS_FAILED = counter(
    "sim_session_turns_failed",
    "streamed session turns that failed terminally; the rest of that "
    "chain is abandoned (later turns need the transcript)",
)
SIM_STREAM_RESUMES = counter(
    "sim_stream_resumes",
    "client-observed resume-at-offset failovers: streamed asks that "
    "lost their stream after the first delivered byte and continued "
    "from the delivered token offset on a retry",
)
SIM_STREAM_DIGEST_MISMATCH = counter(
    "sim_stream_digest_mismatch",
    "streamed answers whose assembled text failed the final chunk's "
    "digest check — a duplicated or dropped token somewhere in the "
    "stream; the verdict requires 0",
)
SIM_TURN_TTFT = histogram(
    "sim_turn_ttft",
    "client-observed time to first streamed token per session turn "
    "(its p95 is the per-turn conversational SLO)",
)

# Raft runner (utils/guards.py LoopWatchdog wired by lms/node.py).

RAFT_TICK_LAG = histogram(
    "raft_tick_lag",
    "how late each Raft tick ran versus its schedule (stalls here are "
    "the precursor of spurious elections)",
)
RAFT_TICK_STALLS = counter(
    "raft_tick_stalls",
    "Raft ticks later than 10 heartbeat intervals (each also logged)",
)
RAFT_STATE_DIGEST = gauge(
    "raft_state_digest",
    "low 32 bits of the replica's state-digest chain at its applied "
    "index (LMSState.digest folded per apply; replicas of one group at "
    "the same applied index must report the same value — divergence "
    "here is state-machine nondeterminism)",
)

# Serving event loop (utils/guards.py LoopWatchdog heartbeat wired by the
# gRPC server entry points): handler stalls become visible series instead
# of being inferred from latency tails.

SERVING_TICK_LAG = histogram(
    "serving_tick_lag",
    "how late the serving event loop's heartbeat ran versus its schedule "
    "(a stall here means a handler blocked the loop)",
)
SERVING_TICK_STALLS = counter(
    "serving_tick_stalls",
    "serving-loop heartbeats later than the stall threshold (each also "
    "logged)",
)

# Lock-order auditing (utils/locks.py OrderedLock, debug recording mode):
# the runtime counterpart of the lock-order lint rule.

LOCK_ORDER_VIOLATIONS = counter(
    "lock_order_violations",
    "lock acquisitions that re-entered a held non-reentrant lock or "
    "closed a cycle in the live acquisition-order graph (recorded by "
    "utils/locks.py OrderedLock when debug recording is on; each also "
    "lands in locks.violations() with the offending edge)",
)


if __name__ == "__main__":  # pragma: no cover - convenience
    print(render_markdown_table())
