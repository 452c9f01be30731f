"""The engine loop on the profiler's clock (engine/spans.py).

Pinned here: every engine program lowers to a module named after its
function (none to `jit__unknown`); under `jax.profiler` the host plane
holds the loop's spans, each `engine.prog.*` inside its parent; the new
counters conserve tokens and lane-steps over a run; the block programs
reach their histograms; the jax-free modules stay jax-free; and every
turn of the serving loop is parted into host work, device wait and stall,
which four per-layer metrics of the benchmark read.
"""

import asyncio
import glob
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks import readers
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    PagedQueue,
    SamplingParams,
    batcher,
    spans,
)
from distributed_lms_raft_llm_tpu.utils import metrics_registry as metric
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

MAX_NEW = 8
SLOTS = 2
CTX = "the raft leader election protocol works by "
PROMPTS = [
    CTX + "choosing a leader",
    CTX + "replicating a log",
    "what is paging?",
    CTX + "electing nodes",
    CTX + "choosing a leader",
]


def make_engine(**kw):
    return PagedEngine(
        EngineConfig(
            model="tiny",
            sampling=SamplingParams.greedy(max_new_tokens=MAX_NEW),
            length_buckets=(16, 32), batch_buckets=(1, 2),
            dtype=jnp.float32, **kw,
        ),
        slots=SLOTS, chunk=2, megastep=2, megastep_max=4,
        prefix_cache=True, prefix_cache_blocks=64, prefix_block_tokens=4,
        prefill_chunk_tokens=4,
    )


# ------------------------------------------------------- (a) program names


@pytest.fixture(scope="module")
def lowering_args():
    """Abstract arguments for every program of one plain and one
    speculative engine (lowering runs nothing and donates nothing)."""
    eng = make_engine(scoring=True)
    spec = make_engine(spec_tokens=2)
    i32 = jnp.asarray(0, jnp.int32)
    ids = jnp.zeros((1, 16), jnp.int32)
    rng = jax.random.key(0)
    with eng.mesh:
        blk = jax.eval_shape(eng._export_block, eng.state.cache, i32, i32)
    return {
        "_export_block": (eng, (eng.state.cache, i32, i32)),
        "_megastep": (eng, (eng.params, eng.state, eng._step_keys(2))),
        "_spec_megastep": (spec, (spec.params, spec.state,
                                  spec._step_keys(2))),
        "_stage": (eng, (eng.state, i32, ids, i32, i32, i32,
                         jax.random.key_data(rng))),
        "_stage_block": (eng, (eng.state, blk, i32, i32)),
        "_grow": (eng, (eng.state, eng.widths[-1])),
        "_score": (eng, (eng.params, jnp.zeros((1, 16), jnp.int32),
                         jnp.ones((1, 16), bool))),
    }


@pytest.mark.parametrize("attr,module", [
    ("_export_block", "jit__export_block_program"),
    ("_megastep", "jit__megastep_program"),
    ("_spec_megastep", "jit__megastep_program"),
    ("_stage", "jit__stage_program"),
    ("_stage_block", "jit__stage_block_program"),
    ("_grow", "jit__grow_state_program"),
    ("_score", "jit__score_program"),
])
def test_program_lowers_under_its_function_name(lowering_args, attr, module):
    eng, args = lowering_args[attr]
    jitted = getattr(eng, attr.replace("_spec", ""))
    with eng.mesh:
        text = jitted.lower(*args).as_text()
    assert f"module @{module} " in text
    assert "jit__unknown" not in text


# ------------------------------------- (b) spans in the profiler's trace


def _serve(engine, prompts, metrics=None, stream=False):
    """Drive `prompts` through a PagedQueue, all submitted at once;
    returns the answers (or, streaming, each request's deltas)."""

    async def one(queue, prompt):
        if not stream:
            return await queue.submit(prompt)
        return [d async for d in queue.submit_stream(prompt)]

    async def run():
        queue = PagedQueue(engine, metrics=metrics)
        await queue.start()
        try:
            return await asyncio.gather(*(one(queue, p) for p in prompts))
        finally:
            await queue.close()

    return asyncio.run(run())


def test_host_plane_holds_the_loop_spans_nested(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    engine = make_engine()
    engine.warmup()
    # Every span the sink is handed, one by one: name -> walls in order.
    handed = {}
    add = spans.ProgramLog.__call__

    def add_and_keep(log, sp):
        handed.setdefault(sp.name, []).append(sp.wall_s)
        add(log, sp)

    monkeypatch.setattr(spans.ProgramLog, "__call__", add_and_keep)
    # What the sink summed, drain after drain (the queue drains it a turn).
    sink = {}
    pop = engine.pop_loop_stats

    def pop_and_keep():
        out = pop()
        for name, total in out[2].items():
            n, wall_s = sink.get(name, (0, 0.0))
            sink[name] = (n + total.n, wall_s + total.wall_s)
        return out

    engine.pop_loop_stats = pop_and_keep
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        answers = _serve(engine, PROMPTS, Metrics())
    finally:
        jax.profiler.stop_trace()
    pop_and_keep()
    assert len(answers) == len(PROMPTS)
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    # One list of (name, start, end) per thread line (the lines share a
    # name, the process's).
    lines = [
        [(e.name, e.start_ns, e.start_ns + e.duration_ns)
         for e in line.events if e.name.startswith(("engine.", "queue."))]
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU" for line in plane.lines
    ]
    names = {name for evs in lines for name, _, _ in evs}
    assert {"engine.step", "engine.admit", "engine.dispatch",
            "engine.reap.wait", "engine.reap.host", "engine.keys",
            "queue.between_steps", "queue.idle",
            "engine.prog.megastep", "engine.prog.stage",
            "engine.prog.stage_block", "engine.prog.export_block"} <= names
    parents = {"engine.keys": ("engine.admit", "engine.dispatch"),
               "engine.prog.megastep": ("engine.dispatch",),
               "engine.prog.stage": ("engine.admit",),
               "engine.prog.stage_block": ("engine.admit",),
               "engine.prog.grow": ("engine.admit",),
               "engine.prog.export_block": ("engine.reap.host",)}
    checked = 0
    for evs in lines:
        for name, start, end in evs:
            if name not in parents:
                continue
            assert any(
                pname in parents[name] and ps <= start and end <= pe
                for pname, ps, pe in evs
            ), f"{name} at {start} lies in no {parents[name]}"
            checked += name.startswith("engine.prog.")
        # Every phase of a turn lies inside that turn's engine.step.
        steps = [(s, e) for n, s, e in evs if n == "engine.step"]
        for name, start, end in evs:
            if name in ("engine.admit", "engine.dispatch",
                        "engine.reap.wait", "engine.reap.host"):
                assert any(s <= start and end <= e for s, e in steps), name
    assert checked >= 2 * len(PROMPTS)
    # The sink's sums are the host plane's: as many spans of every name,
    # their sums the sums of the spans it was handed, and (a name's spans
    # do not overlap, so both are in time's order) the MEDIAN span of a
    # name within the few microseconds a span's own clock reads lie
    # outside its annotation, from both sides. Not each span, nor their
    # sum: with six test workers on the machine one annotation in a run
    # reads half a millisecond LONGER than the monotonic reading that
    # encloses it (25.299 ms for 24.803: the profiler's clock is another
    # clock, runs at another rate, 0.12 ms a second faster here, and is
    # read on a thread that is now and then descheduled between a span's
    # clock read and its annotation's, for a time slice that no bound on
    # microseconds survives), and a step that takes a second under load is
    # no step of 0.2 s. A clock read out of place would show in every
    # span, and so in the median, whichever side it fell on.
    plane = {}
    for evs in lines:
        for name, start, end in evs:
            plane.setdefault(name, []).append((start, (end - start) / 1e9))
    assert set(sink) == {n for n in plane if n.startswith("engine.")}
    for name, (n, wall_s) in sink.items():
        walls = handed[name]
        inside = [d for _, d in sorted(plane[name])]
        assert len(inside) == n == len(walls), name
        assert wall_s == pytest.approx(sum(walls), rel=1e-9), name
        outside = sorted(w - d for d, w in zip(inside, walls))
        median = outside[(n - 1) // 2]
        assert -(1e-4 + 5e-4 * max(walls)) <= median, (name, outside)
        assert median <= 5e-4 + 0.01 * max(walls), (name, outside)


# ------------------------------------------------ (c), (e) conservation


def _counters_after(engine, prompts, stream=False):
    metrics = Metrics()
    out = _serve(engine, prompts, metrics, stream=stream)
    return metrics.snapshot(), out


def test_counters_conserve_tokens_and_lane_steps():
    engine = make_engine()
    engine.warmup()
    warmup_tokens = engine.total_generated_tokens
    snap, deltas = _counters_after(engine, PROMPTS, stream=True)
    c, lat = snap["counters"], snap["latency"]
    n = len(PROMPTS)
    # Tokens: emitted = what the requests were handed (an eos is emitted
    # but never streamed); every request got its budget or an eos.
    streamed = sum(d.count for ds in deltas for d in ds)
    assert (c["engine_tokens_emitted"]
            == engine.total_generated_tokens - warmup_tokens)
    assert streamed <= c["engine_tokens_emitted"] <= n * MAX_NEW
    assert c["engine_tokens_emitted"] >= n
    # Prompts: admitted = the prompts' lengths as served; what the prefix
    # cache did not hold is what the prefill computed.
    served = sum(min(len(engine.tokenizer.encode(p)), engine.bucket)
                 for p in PROMPTS)
    assert c["engine_prompt_tokens_admitted"] == served
    assert (c["engine_prefill_tokens"] + c["prefix_cache_hit_tokens"]
            == served)
    assert c["prefix_cache_hit_tokens"] > 0
    assert snap["gauges"]["prefix_cache_hit_rate"] == pytest.approx(
        c["prefix_cache_hit_tokens"] / served)
    assert snap["gauges"]["host_dispatches_per_token"] == pytest.approx(
        c["engine_dispatches"] / c["engine_tokens_emitted"])
    # One wait of each kind per request, and they add up to the ttft.
    assert lat["queue_wait"]["count"] == n
    assert lat["prefill_wait"]["count"] == n
    assert lat["ttft"]["count"] == n
    assert (lat["queue_wait"]["mean_s"] + lat["prefill_wait"]["mean_s"]
            == pytest.approx(lat["ttft"]["mean_s"], rel=1e-6))
    # Lanes: the budget is iterations x slots, and its named parts fit.
    assert c["engine_lane_steps"] == c["engine_scan_iterations"] * SLOTS
    decode = c["engine_tokens_emitted"] - n  # first tokens: the prefill's
    parts = (decode + c.get("megastep_dead_lane_tokens", 0)
             + c["engine_staged_lane_steps"]
             + c["engine_overrun_lane_steps"])
    assert 0 < parts <= c["engine_lane_steps"]
    assert c["engine_staged_lane_steps"] > 0
    # Staged lanes are counted in ROWS (single scan iterations), and each
    # request's rows are observed once, at its flip: the histogram's sum
    # is the counter.
    staged = lat["engine_staged_iterations"]
    assert staged["count"] == n
    assert staged["mean_s"] * n == pytest.approx(
        c["engine_staged_lane_steps"])
    # Two slots staged together, served one chunk an iteration in
    # staging order: the second waits out the first's chunks, row by
    # row, and nobody waits a 16-iteration chunk per prefill chunk.
    assert 0 < staged["max_s"] < c["engine_scan_iterations"]
    # engine_decode_lanes holds lanes, one observation per reaped
    # dispatch, none above the slot count.
    assert lat["engine_decode_lanes"]["count"] == lat[
        "engine_reap_wait"]["count"]
    assert 0 < lat["engine_decode_lanes"]["max_s"] <= SLOTS
    assert lat["engine_decode_lanes"]["mean_s"] * lat[
        "engine_decode_lanes"]["count"] * MAX_NEW >= decode / 4
    assert lat["engine_host_turn"]["count"] > 0
    # A stream's chunks after its first each observed their gap.
    chunks = sum(len(ds) for ds in deltas)
    assert lat["stream_chunk_gap"]["count"] == chunks - n
    # Every key the engine reports is a declared series.
    engine.submit("again")
    engine.drain()
    counts, observations, _ = engine.pop_loop_stats()
    assert set(counts) <= set(metric.ENGINE_LOOP_COUNTERS)
    assert set(observations) <= set(metric.ENGINE_LOOP_HISTOGRAMS)


def test_block_programs_reach_their_histograms():
    """`stage_block` and `export_block` were timed and then dropped; now
    one observation per call, and `engine_dispatches` counts exactly the
    observations."""
    programs = ("stage_block", "export_block", "stage", "megastep")
    engine = make_engine()
    engine.warmup()
    snap, _ = _counters_after(engine, PROMPTS)
    lat = snap["latency"]
    for prog in programs:
        assert lat[metric.ENGINE_PROGRAM_HISTOGRAMS[prog]]["count"] > 0, prog
    assert snap["counters"]["engine_dispatches"] == sum(
        h["count"] for name, h in lat.items()
        if name.startswith("engine_prog_"))


# ------------------------------------------------- (f) the loop's budget


def _burn(seconds):
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def test_a_span_sums_into_its_parent_and_a_runtime_call_counts_once():
    """Nested spans on one thread: a parent's self time is its own less
    its children's, a sleep inside a runtime call is `wait_s` all the way
    up and counted once where calls nest, a sleep outside one is nobody's
    wait, and an exception closes a span without a record."""
    log = spans.ProgramLog(8)
    with spans.Span("engine.step", log) as step:
        with spans.Span("engine.reap.wait", log) as outer:
            with spans.Span(spans.PROG + "megastep", log) as call:
                time.sleep(0.02)
            time.sleep(0.01)
        with spans.Span("engine.reap.host", log) as host:
            time.sleep(0.01)
            _burn(0.01)
        with spans.Span("engine.dispatch", log) as dispatch:
            with spans.Span(spans.KEYS, log) as keys:
                time.sleep(0.01)
        with pytest.raises(RuntimeError):
            with spans.Span("engine.admit", log):
                raise RuntimeError("no record of this one")
        with spans.Span("engine.admit", log):
            pass
    assert 0.02 <= call.wait_s <= call.wall_s
    assert call.wait_s + 0.01 <= outer.wait_s <= outer.wall_s
    assert host.wait_s == 0.0 and host.cpu_s >= 0.01
    assert host.wall_s - host.cpu_s >= 0.01  # asleep, and nobody's wait
    assert 0.01 <= keys.wait_s == dispatch.wait_s
    assert step.wait_s == outer.wait_s + keys.wait_s
    assert step.kids_wall_s == pytest.approx(
        outer.wall_s + host.wall_s + dispatch.wall_s,
        abs=1e-3)  # and the two admits'
    for sp in (step, outer, call, host):
        assert 0.0 <= sp.cpu_s <= sp.wall_s
    sums = log.pop_sums()
    assert {name: total.n for name, total in sums.items()} == {
        "engine.step": 1, "engine.reap.wait": 1, "engine.reap.host": 1,
        "engine.prog.megastep": 1, "engine.admit": 1, "engine.dispatch": 1,
        "engine.keys": 1}
    assert sums["engine.step"].self_wall_s == pytest.approx(
        step.wall_s - step.kids_wall_s)
    assert sums["engine.reap.wait"].self_cpu_s == pytest.approx(
        outer.cpu_s - call.cpu_s)
    assert log.pop_sums() == {} and log.dispatches == 1
    # The turn's parts from these sums: cut to fit a wall that is too
    # short for them, each >= 0, and exact where it is long enough.
    with spans.Span(spans.BETWEEN_STEPS) as between:
        _burn(0.002)
    whole = spans.turn_budget(1.0, sums, between)
    assert whole["loop_wall_us"] == 1_000_000
    assert whole["loop_device_wait_us"] == round(step.wait_s * 1e6)
    assert whole["loop_host_work_us"] == round(
        (step.cpu_s + between.cpu_s) * 1e6)
    assert whole["loop_cpu_us_reap_host"] == round(host.cpu_s * 1e6)
    short = spans.turn_budget(0.015, sums, between)
    assert (short["loop_device_wait_us"] + short["loop_host_work_us"]
            == short["loop_wall_us"] == 15_000)
    assert spans.turn_budget(0.5, {}, between)["loop_device_wait_us"] == 0
    # A coarse CPU clock (the chip machine's ticks 10 ms) gives a 1 ms call
    # a whole tick: its wait reads negative and its work more than the wall.
    ticked = spans.SpanSum()
    ticked.cpu_s, ticked.wait_s = 0.010, -0.009
    cut = spans.turn_budget(0.002, {"engine.step": ticked}, between)
    assert (cut["loop_wall_us"], cut["loop_host_work_us"],
            cut["loop_device_wait_us"]) == (2_000, 2_000, 0)


@pytest.fixture(scope="module")
def warm_engine():
    engine = make_engine()
    engine.warmup()
    return engine


def _serve_turns(engine, monkeypatch, turns):
    """Serve PROMPTS, appending every turn's budget, as `spans.turn_budget`
    returned it, to `turns`; returns the /metrics snapshot."""

    def recording(wall_s, sums, between):
        turns.append(spans.turn_budget(wall_s, sums, between))
        return turns[-1]

    monkeypatch.setattr(batcher, "turn_budget", recording)
    metrics = Metrics()
    _serve(engine, PROMPTS, metrics)
    return metrics.snapshot()


def _parts(turn):
    """(device_wait, host_work, stalled) of one turn, microseconds."""
    wait, work = turn["loop_device_wait_us"], turn["loop_host_work_us"]
    return wait, work, turn["loop_wall_us"] - wait - work


def test_every_turn_is_parted_three_ways(warm_engine, monkeypatch):
    turns = []
    snap = _serve_turns(warm_engine, monkeypatch, turns)
    assert len(turns) >= MAX_NEW // 4
    phases = ["loop_cpu_us_" + key for key in spans.STEP_PHASES]
    for turn in turns:
        wait, work, stalled = _parts(turn)
        assert all(isinstance(v, int) for v in turn.values())
        assert wait >= 0 and work >= 0 and stalled >= 0
        assert wait + work + stalled == turn["loop_wall_us"] > 0
        # The phases are siblings under engine.step: with the loop's own
        # span they are the host's work less engine.step's self time.
        by_phase = (sum(turn[k] for k in phases)
                    + turn["loop_cpu_us_between_steps"])
        assert by_phase <= work + len(phases) + 1  # each rounded alone
    # The counters are the turns' sums, and each turn was observed once.
    c, lat = snap["counters"], snap["latency"]
    for key in turns[0]:
        assert c[metric.ENGINE_LOOP_COUNTERS[key]] == sum(
            t[key] for t in turns), key
    work_hist = lat[metric.ENGINE_LOOP_HISTOGRAMS["host_work"]]
    assert work_hist["count"] == lat["engine_host_turn"]["count"] == len(turns)
    assert work_hist["mean_s"] * len(turns) == pytest.approx(
        c["engine_loop_host_work_us"] / 1e6)
    assert c["engine_loop_host_work_us"] > 0


@pytest.mark.parametrize("where,part,phase", [
    ("_megastep", 0, None),
    ("_walk", 1, "loop_cpu_us_reap_host"),
    ("step", 2, None),
], ids=["a_sleep_in_the_megastep_call_is_device_wait",
        "a_busy_loop_in_the_walk_is_host_work",
        "a_sleep_after_the_step_returns_is_stall"])
def test_fifty_milliseconds_land_where_they_belong(
        warm_engine, monkeypatch, where, part, phase):
    """50 ms put into one turn: asleep inside the runtime call, burning
    CPU in the reap's host half, or asleep on the step's thread after
    `engine.step` closed (before `_between_steps`)."""
    turns, at = [], []  # `at`: which turn the 50 ms went into
    inner = getattr(warm_engine, where)

    def spend_once():
        if not at:
            at.append(len(turns))
            _burn(0.05) if where == "_walk" else time.sleep(0.05)

    def with_fifty_ms(*args):
        if where != "step":
            spend_once()
            return inner(*args)
        out = inner(*args)
        spend_once()
        return out

    monkeypatch.setattr(warm_engine, where, with_fifty_ms)
    # Under six workers the machine can put 50 ms of its own into the same
    # turn (ROADMAP D22): such a turn is served again, twice at most.
    for _ in range(3):
        del turns[:], at[:]
        _serve_turns(warm_engine, monkeypatch, turns)
        parts = _parts(turns[at[0]])
        if all(p < 45_000 for i, p in enumerate(parts) if i != part):
            break
    assert parts[part] >= 45_000, parts
    assert all(p < 45_000 for i, p in enumerate(parts) if i != part), parts
    if phase:
        assert turns[at[0]][phase] >= 45_000


NEW_METRICS = {
    "host_work_p95_ms": ("ms", "lower"),
    "loop_host_work_share": ("%", "lower"),
    "loop_device_wait_share": ("%", "higher"),
    "loop_stalled_share": ("%", "lower"),
}
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served_ctx(warm_engine):
    """A synthetic `ctx` as `benchmarks/run.py` hands a reader: the
    server's /metrics at a mark and after a served window."""
    metrics = Metrics()
    _serve(warm_engine, PROMPTS[:2], metrics)
    marked = metrics.snapshot()
    _serve(warm_engine, PROMPTS, metrics)
    window = {name: {"p95_s": metrics.hist(name).window_percentile(3600, 95)}
              for name in metrics.snapshot()["latency"]}
    return {"marked": {"metrics": marked},
            "collected": {"metrics": metrics.snapshot(), "window": window}}


def _without_the_budget(ctx):
    """The same documents from a program without this PR's series."""
    def strip(series):
        return {k: v for k, v in series.items()
                if not k.startswith(("engine_loop_", "engine_host_work"))}

    def strip_all(metrics):
        return {section: strip(series) for section, series in metrics.items()}

    return {"marked": {"metrics": strip_all(ctx["marked"]["metrics"])},
            "collected": {
                "metrics": strip_all(ctx["collected"]["metrics"]),
                "window": strip(ctx["collected"]["window"])}}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_budget_metric_is_a_data_file_on_a_reader_that_is_there(
        name, served_ctx, moves_a_reported_metric):
    spec = _load("benchmarks", "layer_metrics", name + ".json")
    # The reader is one the benchmark has, and its series are declared
    # and reach /metrics on the served path.
    readers.resolve(spec["reader"])
    args = spec["args"]
    series = ([args["histogram"]] if "histogram" in args else
              [t["counter"] for t in args["numerator"] + args["denominator"]])
    reached = served_ctx["collected"]["metrics"]
    for one in series:
        assert metric.is_declared(one), one
        assert one in (set(metric.ENGINE_LOOP_COUNTERS.values())
                       | set(metric.ENGINE_LOOP_HISTOGRAMS.values()))
        assert one in reached["counters"] or one in reached["latency"], one
    # Found by its name, not by its place: later PRs append metrics.
    per_layer = _load("BENCHMARK.json")["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == name]
    unit, better = NEW_METRICS[name]
    moves_a_reported_metric(entry)
    assert {k: v for k, v in entry.items() if k != "moves"} == {
        "name": name, "unit": unit, "better": better,
        "source": "program_span",
        "layer": "paged engine (engine/paged.py)",
    }
    assert entry["layer"] in {m["layer"] for m in per_layer
                              if m["name"] not in NEW_METRICS}
    # A number on this program, nothing (and no raise) on the parent's.
    value = readers.read(spec["reader"], args, served_ctx)
    assert value is not None and 0 <= value < (100.01 if unit == "%" else 1e4)
    assert readers.read(spec["reader"], args,
                        _without_the_budget(served_ctx)) is None


def test_the_three_shares_sum_to_a_hundred(served_ctx):
    shares = []
    for name in sorted(NEW_METRICS):
        if NEW_METRICS[name][0] == "%":
            spec = _load("benchmarks", "layer_metrics", name + ".json")
            shares.append(readers.read(spec["reader"], spec["args"],
                                       served_ctx))
    assert len(shares) == 3
    assert sum(shares) == pytest.approx(100.0, abs=1e-9)


# ------------------------------------------------------ (d) jax-free edge


def test_tracing_and_client_import_without_jax():
    code = (
        "import sys\n"
        "import distributed_lms_raft_llm_tpu.utils.tracing\n"
        "import distributed_lms_raft_llm_tpu.client.client\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
