"""The engine loop on the profiler's clock (engine/spans.py).

Pinned here: every engine program lowers to a module named after its
function (none to `jit__unknown`); under `jax.profiler` the host plane
holds the loop's spans, each `engine.prog.*` inside its parent; the new
counters conserve tokens and lane-steps over a run; the block programs
reach their histograms; and the jax-free modules stay jax-free.
"""

import asyncio
import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    PagedQueue,
    SamplingParams,
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


def test_host_plane_holds_the_loop_spans_nested(tmp_path):
    from jax.profiler import ProfileData

    engine = make_engine()
    engine.warmup()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        answers = _serve(engine, PROMPTS, Metrics())
    finally:
        jax.profiler.stop_trace()
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
            "engine.reap.wait", "engine.reap.host", "queue.between_steps",
            "queue.idle", "engine.prog.megastep", "engine.prog.stage",
            "engine.prog.stage_block", "engine.prog.export_block"} <= names
    parents = {"engine.prog.megastep": "engine.dispatch",
               "engine.prog.stage": "engine.admit",
               "engine.prog.stage_block": "engine.admit",
               "engine.prog.grow": "engine.admit",
               "engine.prog.export_block": "engine.reap.host"}
    checked = 0
    for evs in lines:
        for name, start, end in evs:
            if not name.startswith("engine.prog."):
                continue
            assert any(
                pname == parents[name] and ps <= start and end <= pe
                for pname, ps, pe in evs
            ), f"{name} at {start} lies in no {parents[name]}"
            checked += 1
        # Every phase of a turn lies inside that turn's engine.step.
        steps = [(s, e) for n, s, e in evs if n == "engine.step"]
        for name, start, end in evs:
            if name in ("engine.admit", "engine.dispatch",
                        "engine.reap.wait", "engine.reap.host"):
                assert any(s <= start and end <= e for s, e in steps), name
    assert checked >= 2 * len(PROMPTS)


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
    counts, observations = engine.pop_loop_stats()
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
