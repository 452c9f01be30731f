"""Self-checks of the benchmark's own code (not part of the tier-1 suite):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import asyncio
import json
import math
import os
import re
import statistics
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import roofline, stats, traffic  # noqa: E402


def load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def words():
    return traffic.Words()


CELLS = [("tiny-qa", {"rate_per_s": 3.0}), ("deadline-herd", {"students": 64})]


# ------------------------------------------------------------------ traffic


@pytest.mark.parametrize("name,cell", CELLS)
def test_generator_is_a_pure_function_of_the_seed(words, name, cell):
    spec = load("traffic", name + ".json")
    big = 2 ** 31 + 12345
    a = traffic.Traffic(spec, cell, big, 40, 256, words)
    b = traffic.Traffic(spec, cell, big, 40, 256, words)
    c = traffic.Traffic(spec, cell, big + 1, 40, 256, words)
    assert a.digest() == b.digest()
    assert a.digest() != c.digest()


@pytest.mark.parametrize("name,cell", CELLS)
def test_every_seed_gets_the_same_work_in_another_order(words, name, cell):
    spec = load("traffic", name + ".json")
    a = traffic.Traffic(spec, cell, 1, 40, 256, words).describe()
    b = traffic.Traffic(spec, cell, 2, 40, 256, words).describe()
    for key in ("requests_described", "requests_per_course",
                "query_tokens_sum", "queries_the_engine_will_cut"):
        assert a[key] == b[key]
    assert a["digest"] != b["digest"]


def test_the_seed_alone_orders_a_closed_loop(words):
    """Who starts when, in which course and with which question length is
    the seed's: the same multisets, shuffled, not one order turned."""
    spec = load("traffic", "deadline-herd.json")
    a = traffic.Traffic(spec, {"students": 64}, 1, 40, 256, words)
    b = traffic.Traffic(spec, {"students": 64}, 2, 40, 256, words)
    assert sorted(a.starts) == sorted(b.starts) and a.starts != b.starts
    assert max(a.starts) < spec["start_spread_s"]
    assert sorted(a.course_of) == sorted(b.course_of)
    assert a.course_of != b.course_of
    turned = [a.starts[i:] + a.starts[:i] for i in range(64)]
    assert b.starts not in turned


def test_open_loop_has_rate_times_seconds_requests_inside_the_window(words):
    spec = load("traffic", "tiny-qa.json")
    t = traffic.Traffic(spec, {"rate_per_s": 3.0}, 7, 40, 256, words)
    assert len(t.requests) == 120
    dues = [r.due_s for r in t.requests]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 40
    assert [r.course for r in t.requests].count(0) == 72


def test_text_has_the_stated_length_in_tokens(words):
    import random

    rng = random.Random(3)
    for n in (1, 2, 7, 56, 152):
        assert words.count(words.text(rng, n)) == n


def test_queries_the_engine_will_cut_are_sent_and_counted(words):
    """The cell's prompts fit the 256 tokens the shipped engine serves;
    ISSUE 26's contexts do not, and are sent whole and counted."""
    spec = load("traffic", "deadline-herd.json")
    fits = traffic.Traffic(spec, {"students": 32}, 1, 40, 256, words)
    assert fits.describe()["queries_the_engine_will_cut"] == 0
    long = dict(spec, courses=[{"context_tokens": n, "share": s}
                               for n, s in ((200, 60), (350, 30), (600, 10))])
    doc = traffic.Traffic(long, {"students": 32}, 1, 40, 256,
                          words).describe()
    assert doc["queries_the_engine_will_cut"] == doc["requests_described"]
    roomy = traffic.Traffic(long, {"students": 32}, 1, 40, 1024, words)
    assert roomy.describe()["queries_the_engine_will_cut"] == 0


# -------------------------------------------------------------------- stats


def test_percentile_is_the_programs_nearest_rank():
    from distributed_lms_raft_llm_tpu.utils.metrics import (
        percentile_of_sorted,
    )

    import random

    rng = random.Random(0)
    for n in (1, 2, 3, 10, 19, 20, 21, 100, 137):
        xs = [rng.random() for _ in range(n)]
        for p in (0, 1, 50, 90, 95, 99, 100):
            assert stats.percentile(xs, p) == percentile_of_sorted(
                sorted(xs), p)


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


# ----------------------------------------------------------------- roofline


def test_bytes_and_operations_by_hand():
    from benchmarks.families.gpt2 import roofline as counted

    xl = load("configs", "gpt2-xl.json")
    small = {"n_layer": 12, "n_embd": 768, "n_head": 12, "vocab_size": 50257}
    # gpt2-xl: 48 x 12 x 1600^2 + 50257 x 1600 int8, 4 x (48 x 9 x 1600 +
    # 50257) of scales, 2 x (48 x 13 x 1600 + 3200) of vectors.
    assert counted.weight_bytes(xl) == (
        1474560000 + 80411200 + 4 * 741457 + 2 * 1001600)
    assert counted.weight_bytes(xl) == 1559940228
    assert counted.weight_bytes(small) == (
        84934656 + 38597376 + 4 * 133201 + 2 * 121344)
    # K and V: 2 x layers x width int8, and a float32 scale per head each.
    assert counted.kv_bytes_per_token(xl) == 48 * 2 * 1600 + 4 * 48 * 2 * 25
    assert counted.kv_bytes_per_token(small) == 12 * 2 * 768 + 4 * 12 * 2 * 12
    assert counted.decode_ops(xl, 16, 4000) == (
        2.0 * (1474560000 + 80411200) * 16 + 4.0 * 48 * 1600 * 4000)
    # one step of 16 slots at a mean context of 250, the steps being the
    # entries of the loop entered most often
    trace = {"loops": [["%while.7 (s32[])", 1.0], ["%while.3 (s32[])", 0.5]],
             "span_counters": {"engine_scan_iterations": 3}}
    cost = counted.cost(xl, trace, 16, 250)
    assert cost["steps"] == 1 and cost["steps_less_counter"] == -2
    assert cost["bytes"] == 1559940228 + 16 * 250 * 163200
    assert cost["ops"] == counted.decode_ops(xl, 1.0, 250) * 16
    least = roofline.least_seconds(cost, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(
        (1559940228 + 16 * 250 * 163200) / 819e9)
    assert least["steps"] == 1
    # a trace that counted no loop: nothing to read, never a share of 0
    assert counted.cost(xl, {"loops": []}, 16, 250) is None


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# ---------------------------------------------------------------- reference


def test_reference_agrees_with_the_programs_forward_at_tiny_width():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import check
    from benchmarks.families.gpt2 import compare, reference, weights
    from distributed_lms_raft_llm_tpu.models import registry

    config = load("configs", "tiny.json")
    family, cfg = registry.resolve("tiny", jnp.float32, jnp.float32)
    compare.check_sizes(config, cfg)
    w = weights.make(11, weights.sizes_of(config), jnp.float32)
    ids = check.sequences(11, 1, 40, config["vocab_size"])[0]
    want, _, _ = reference.forward(w, ids, config)
    with jax.default_matmul_precision("highest"):
        got, _ = family.forward(weights.program_tree(w), cfg,
                                jnp.asarray(ids)[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5, rtol=1e-4)
    assert float(check.distance(got[0], want)) < 1e-5


def test_the_served_precision_passes_and_every_control_fails():
    """The comparison of `correct` at sizes a test run can hold: the
    program's int8 path at the tiny preset is inside every limit; each
    control (the reference with one stated precision a step lower) is
    outside at least one, at 12 layers of 768 (gpt2's own width)."""
    from benchmarks import check, serve
    from benchmarks.families.gpt2 import compare, reference

    config = load("configs", "tiny.json")
    engine = serve.build_engine(config, 5)
    got = check.compare(engine.family, engine.cfg, engine.params, config, 5)
    assert got["ok"]
    assert all(got["worst"][k] < got["limits"][k] / 2 for k in got["limits"])
    limits = got["limits"]
    assert limits == config["check"]["limits"] == load(
        "configs", "gpt2-xl.json")["check"]["limits"]
    mid = {"family": "gpt2", "vocab_size": 2048, "n_positions": 64,
           "n_embd": 768, "n_layer": 12, "n_head": 12,
           "layer_norm_epsilon": 1e-5}
    for seed in (1, 2, 3):
        seqs = check.sequences(seed, 1, 48, 2048)
        want = check.reference_side(mid, seed, seqs)
        for name in reference.CONTROLS:
            ctl = check.reference_side(mid, seed, seqs, name)
            for c, w in zip(ctl, want):
                read = compare.readings(c, w)
                assert not check.verdict([read], limits)["ok"], (name, read)
    with pytest.raises(ValueError):
        check.reference_side(mid, 1, seqs, "int2_everything")
    # a reading without a limit, or a limit without a reading, is an error
    with pytest.raises(ValueError):
        check.verdict([read], dict(limits, routing=0.1))


def test_weights_take_a_seed_above_32_signed_bits():
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families.gpt2 import weights

    sizes = weights.sizes_of(load("configs", "tiny.json"))
    a = weights.make(2 ** 31 + 5, sizes)["wte"]
    b = weights.make(2 ** 31 + 5, sizes)["wte"]
    c = weights.make(5, sizes)["wte"]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    bf = weights.make(5, sizes, jnp.bfloat16)["wte"]
    assert np.array_equal(np.asarray(bf), np.asarray(c.astype(jnp.bfloat16)))


@pytest.mark.parametrize("name", ["gpt2-xl", "tiny"])
def test_quiet_tokens_are_the_ones_that_break_a_streamed_text(name):
    """Any sequence of the other ids decodes to a text of which every
    shorter decode is a prefix, which is what the program's stream slices
    by; a quiet id followed by its other half is not, and the quiet rows
    of the embedding are the damped ones."""
    import random

    import numpy as np

    from benchmarks import serve
    from benchmarks.families.gpt2 import weights
    from distributed_lms_raft_llm_tpu.utils import tokenizer

    config = load("configs", name + ".json")
    quiet = weights.quiet_ids(config)
    vocab = int(config["vocab_size"])
    if config["tokenizer"] == "bpe":
        tok = tokenizer.BPETokenizer.from_files(serve.VOCAB, serve.MERGES)
    else:
        tok = tokenizer.ByteTokenizer(vocab)
    assert quiet and all(0 <= i < vocab for i in quiet)
    sound = [i for i in range(vocab) if i not in set(quiet)]
    rng = random.Random(7)
    for _ in range(50):
        ids = [rng.choice(sound) for _ in range(64)]
        text = tok.decode(ids)
        assert "\ufffd" not in text
        assert all(text.startswith(tok.decode(ids[:n])) for n in range(64))
    assert all("\ufffd" in tok.decode([i]) for i in quiet)
    if name == "tiny":
        sizes = weights.sizes_of(config)
        a = np.array(weights.of_config(5, config)["wte"])
        b = np.asarray(weights.make(5, sizes)["wte"])
        rows = list(quiet)
        np.testing.assert_allclose(a[rows], weights.QUIET * b[rows],
                                   rtol=1e-6)
        a[rows] = b[rows]
        assert np.array_equal(a, b)


# ------------------------------------------------------------ load generator


def test_open_loop_latency_counts_from_the_due_time(words):
    """A server that answers one question at a time, 0.15 s each, against
    arrivals every ~0.05 s: later requests wait, and their latency, taken
    from the due time, grows, while the generator is never late."""
    import grpc

    from benchmarks import run as run_lib
    from distributed_lms_raft_llm_tpu.proto import lms_pb2, rpc
    import hashlib

    class Stalled(rpc.TutoringServicer):
        def __init__(self):
            self.lock = asyncio.Lock()

        async def StreamLLMAnswer(self, request, context):
            async with self.lock:
                await asyncio.sleep(0.15)
            text = "ok"
            yield lms_pb2.StreamChunk(
                success=True, text=text, offset=0, count=1, final=True,
                digest=hashlib.sha256(text.encode()).hexdigest())

    spec = load("traffic", "tiny-qa.json")
    t = traffic.Traffic(spec, {"rate_per_s": 20.0}, 3, 1.0, 64, words)

    async def go():
        server = grpc.aio.server()
        rpc.add_TutoringServicer_to_server(Stalled(), server)
        port = server.add_insecure_port("127.0.0.1:0")
        await server.start()
        try:
            return await run_lib.run_load(t, f"127.0.0.1:{port}", 1.0, 30.0,
                                          16)
        finally:
            await server.stop(0)

    outcomes, t0 = asyncio.run(go())
    assert len(outcomes) == 20 and not any(o.error for o in outcomes)
    by_due = sorted(outcomes, key=lambda o: o.due)
    lat = [o.last - o.due for o in by_due]
    assert lat[-1] > lat[0] + 1.0          # 20 x 0.15 s of work in 1 s
    assert max(o.sent - o.due for o in outcomes) < 0.1
    assert max(o.last for o in outcomes) > t0 + 1.0   # drained past the window


# ------------------------------------------------------------------ readers


def _outcome(sent, query_tokens, chunks):
    from benchmarks import run as run_lib

    o = run_lib.Outcome(sent, query_tokens)
    o.sent, o.token_times = sent, chunks
    o.tokens = sum(n for _, n in chunks)
    return o


def test_tokens_of_a_span_are_spread_over_the_time_they_were_made_in():
    from benchmarks import readers

    # 16 tokens every second from t=1: the first chunk's were made in 0..1.
    o = _outcome(0.0, 300, [(1.0, 16), (2.0, 16), (3.0, 16)])
    ctx = {"outcomes": [o], "trace_span": (1.5, 2.5),
           "traffic_spec": {"template_tokens": 36},
           "config": {"serving": {"max_prompt_tokens": 256}}}
    tokens, context, seconds = readers.span_tokens(ctx)
    assert seconds == pytest.approx(1.0)
    assert tokens == pytest.approx(16.0)       # half of two chunks
    # prompt cut to 256; the chunks' mean positions are 16 + 7.5, 32 + 7.5
    assert context == pytest.approx(8 * (256 + 23.5) + 8 * (256 + 39.5))
    ctx["trace_span"] = (0.0, 10.0)
    assert readers.span_tokens(ctx)[0] == pytest.approx(48.0)
    assert readers.span_tokens(dict(ctx, trace_span=None)) is None


def test_counters_are_differenced_over_the_window():
    from benchmarks import readers

    def metrics(hit, rate, progs):
        return {"metrics": {
            "counters": {"prefix_cache_hit_tokens": hit},
            "gauges": {"prefix_cache_hit_rate": rate},
            "latency": {"engine_prog_megastep": {"count": progs},
                        "engine_prog_stage": {"count": 2 * progs},
                        "ttft": {"count": 999}}}}

    # before the window 100 of 400 prompt tokens hit; in it 300 of 600
    ctx = {"marked": metrics(100, 0.25, 10),
           "collected": metrics(400, 0.4, 30),
           "outcomes": [_outcome(0.0, 10, [(1.0, 100)]),
                        _outcome(0.0, 10, [(1.0, 20)])]}
    share = readers.counter_share(
        {"counter": "prefix_cache_hit_tokens",
         "ratio_gauge": "prefix_cache_hit_rate", "scale": 100.0}, ctx)
    assert share == pytest.approx(50.0)
    per_token = readers.histogram_counts_per_token(
        {"prefix": "engine_prog_"}, ctx)
    assert per_token == pytest.approx((90 - 30) / 120)
    # no hit at all in the window: a share of 0, not a missing metric
    ctx["collected"] = metrics(100, 0.1, 30)
    assert readers.counter_share(
        {"counter": "prefix_cache_hit_tokens",
         "ratio_gauge": "prefix_cache_hit_rate"}, ctx) == 0.0


# ----------------------------------------------------------- BENCHMARK.json

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")


def test_benchmark_json_keeps_to_the_contract():
    b = load(os.pardir, "BENCHMARK.json")
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks"] and 1 <= b["run_seconds"] <= 51
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", [])) <= cells
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
        spec = load("layer_metrics", m["name"] + ".json")
        from benchmarks import readers

        assert callable(readers.resolve(spec["reader"]))
    configs = {c["name"]: c for c in b["configs"]}
    for c in configs.values():
        assert NAME.match(c["name"]) and os.path.exists(
            os.path.join(REPO, c["file"]))
        assert load(os.pardir, c["file"])["reduced"] == c["reduced"]
        assert load(os.pardir, c["file"])["source"] == c["source"]
        from benchmarks import families

        assert load(os.pardir, c["file"])["family"] in families.names()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = load("workloads", w["name"] + ".json")
        assert cell["config"] == w["config"] in configs
        assert cell["traffic"] == w["traffic"]
        load("traffic", w["traffic"] + ".json")
    assert {w["config"] for w in b["workloads"]} == set(configs)
    assert len(json.dumps(b)) < 64 * 1024


def test_window_counters_are_what_grew_and_nothing_that_stood_still():
    from benchmarks import run as run_lib

    then = {"engine_prefill_passes": 10, "llm_requests": 4, "shed_queue": 1}
    now = {"engine_prefill_passes": 25, "llm_requests": 4, "shed_queue": 1,
           "engine_state_snapshots_restored": 3}
    assert run_lib.grew(now, then) == {
        "engine_prefill_passes": 15, "engine_state_snapshots_restored": 3}
    assert run_lib.grew(then, then) == {}


def test_every_end_to_end_name_is_computed_and_every_cell_reports_two():
    from benchmarks import run as run_lib

    b = load(os.pardir, "BENCHMARK.json")
    ctx = {"tokens_in_window": 5100, "seconds": 51.0, "setup_s": 60.0,
           "outcomes": [_outcome(0.0, 10, [(1.0, 100)])], "deadline_s": 120.0}
    for m in b["end_to_end"]:
        value, unit = run_lib.end_to_end(m["name"], ctx)
        assert value > 0 and unit == m["unit"]
    # A name that goes on after a dot is the same quantity a second time.
    assert run_lib.end_to_end("out_tok_s.mid", ctx) == (100.0, "tokens/s")
    with pytest.raises(run_lib.RunFailure):
        run_lib.end_to_end("tokens_out_s", ctx)
    for w in b["workloads"]:
        mine = [m["name"] for m in b["end_to_end"]
                if run_lib.applies(m, w["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        for m in b["per_layer"]:
            if "workloads" in m and w["name"] in m["workloads"]:
                assert m["moves"] in mine


def test_files_under_the_benchmark_are_named_from_name_characters():
    for root, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), REPO)
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


# -------------------------------------------------------------------- trace


def test_reduction_on_a_hand_made_trace():
    from benchmarks import trace

    ms = 1_000_000
    events = {
        "devices": [{"plane": "/device:TPU:0", "modules": [
            ["jit__unknown(111)", 10 * ms, 40 * ms],
            ["jit__unknown(111)", 60 * ms, 20 * ms],
            ["jit_convert_element_type(5)", 85 * ms, 1 * ms],
        ], "ops": [
            ["%while.1 = (s32[]) while(...)", 10 * ms, 40 * ms],
            ["%while.2 = (s32[]) while(...)", 10 * ms, 19 * ms],
            ["%fusion.7 = bf16[16,64]{1,0} fusion(...)", 10 * ms, 15 * ms],
            ["%while.2 = (s32[]) while(...)", 30 * ms, 20 * ms],
            ["%fusion.7 = bf16[16,64]{1,0} fusion(...)", 30 * ms, 18 * ms],
            ["%while.1 = (s32[]) while(...)", 60 * ms, 20 * ms],
            ["%while.2 = (s32[]) while(...)", 60 * ms, 20 * ms],
            ["%fusion.8 = f32[4]{0} fusion(...)", 62 * ms, 3 * ms],
            ["%copy.1 = f32[4]{0} copy(...)", 85 * ms, 1 * ms],
        ]}],
        "host": [
            ["python", "$batcher.py:797 _run", 0, 100 * ms],
            ["python", "$profiler.py:101 start_trace", 0, 5 * ms],
            ["python", "$profiler.py:213 stop_trace", 95 * ms, 5 * ms],
            ["python", "$selectors.py:451 select", 52 * ms, 2 * ms],
            ["python", "$paged.py:2381 _reap", 50 * ms, 9 * ms],
            ["python", "$paged.py:2280 step", 80 * ms, 4 * ms],
            ["main/1", "Execute", 86 * ms, 2 * ms],
        ],
    }
    got = trace.reduce(events)
    # from the return of start_trace to the call of stop_trace
    assert got["window_s"] == pytest.approx(0.090)
    assert got["busy_s"] == pytest.approx(0.061)      # 40 + 20 + 1 ms
    assert got["programs"] == {"jit__unknown": pytest.approx(0.060),
                               "jit_convert_element_type":
                               pytest.approx(0.001)}
    # every loop by its entries: %while.2 ran 3 times, %while.1 twice
    assert got["loops"] == [["%while.2 (s32[])", 3], ["%while.1 (s32[])", 2]]
    from benchmarks.families.gpt2 import roofline as counted

    assert counted.decode_steps(got) == 3
    # every operation is handed on, not the ten longest
    assert [n for n, _ in got["device_ops"]] == [
        "%fusion.7 bf16[16,64]", "%fusion.8 f32[4]", "%copy.1 f32[4]"]
    assert got["device_ops"][0] == ["%fusion.7 bf16[16,64]",
                                    pytest.approx(0.033)]
    gaps = dict(got["idle_gaps"])
    # idle: 5-10 and 86-95 under _run, 50-60 under _reap (a frame that only
    # waits, select, is passed over), 80-85 under step
    assert gaps["$paged.py:2381 _reap"] == pytest.approx(0.010)
    assert gaps["$paged.py:2280 step"] == pytest.approx(0.005)
    assert gaps["$batcher.py:797 _run"] == pytest.approx(0.014)
    assert sum(gaps.values()) == pytest.approx(0.090 - 0.061)


def test_reduction_on_the_recorded_trace():
    """A 40 ms slice of a real trace of gpt2-xl's megastep on a TPU v5e
    (PR 26), with the numbers the reduction gave when it was recorded and
    was checked by hand against the events."""
    from benchmarks import trace

    events = load("tests", "recorded", "recorded_trace.json")
    want = load("tests", "recorded", "recorded_trace_reduced.json")
    got = trace.reduce(events)
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert 0 < got["busy_s"] <= got["window_s"]
    from benchmarks.families.gpt2 import roofline as counted

    assert counted.decode_steps(got) == want["decode_steps"]
    assert got["programs"] == pytest.approx(want["programs"])
    # the ten longest were recorded; now every operation is handed on
    assert [n for n, _ in got["device_ops"][:10]] == [
        n for n, _ in want["device_ops"]]
    assert len(got["device_ops"]) == len(
        {n for n, _, _ in events["devices"][0]["ops"]
         if not n.startswith(trace.CONTAINERS)})
    # by hand: the ops of the slice, merged, are the busy time
    ops = events["devices"][0]["ops"]
    total, _ = trace.union_ns((s, s + d) for _, s, d in ops)
    assert got["busy_s"] == pytest.approx(total / 1e9)


# ------------------------------------------- the recorded sets of every cell


def _recorded_sets():
    folder = os.path.join(HERE, "recorded")
    for name in sorted(os.listdir(folder)):
        if name.startswith("spreads_"):
            doc = load("tests", "recorded", name)
            for cell, entry in sorted(doc["cells"].items()):
                yield pytest.param(doc, cell, entry, id=f"{name[:-5]}:{cell}")


@pytest.mark.parametrize("doc,cell,entry", _recorded_sets())
def test_a_recorded_cell_spreads_by_under_half_of_its_bound(doc, cell, entry):
    """The sets of runs a `benchmark` PR set a bound or a window from stay
    beside it (`recorded/spreads_*.json`, chip runs, six seeds a set): a
    later change of a bound, of `run_seconds` or of the cells a metric
    lists that leaves a cell spreading by more than half of a bound it
    reports under fails here, before a check refuses sound PRs over it."""
    bench = load(os.pardir, "BENCHMARK.json")
    assert doc["run_seconds"] == bench["run_seconds"], (
        "the window changed: the sets are to be taken again")
    assert cell in [w["name"] for w in bench["workloads"]]
    sets = [s["out_tok_s"] for s in entry["sets"]]
    assert all(len(s["out_tok_s"]) == len(s["seeds"]) >= 3
               for s in entry["sets"])
    reported = [m for m in bench["end_to_end"] if m["name"] != "setup_s"
                and cell in m.get("workloads", [cell])]
    assert sorted(m["name"] for m in reported) == sorted(entry["metrics"])
    # The cell is held to the least bound it reports under: that one has
    # to admit the cell's own runs, and the others then do.
    held = min(reported, key=lambda m: m["bound"])
    # Not too tight, by both readings of a set without its farthest run,
    # the quartiles' (what a check holds to half of a bound) and the
    # extremes' (ISSUE 50's): the mean over the sets, and by the quartiles
    # each set by itself too, since a check draws one.
    for some in [sets] + [[s] for s in sets]:
        assert stats.tight(some) <= held["bound"] / 2, held["name"]
    assert statistics.fmean(
        stats.trimmed_range(s) for s in sets) <= held["bound"] / 2
    # A side whose runs lie farther apart than the bound leaves a later
    # PR unresolved, whatever it changed.
    assert all(stats.trimmed_range(s) <= held["bound"] for s in sets)
    # Nor too loose, by the cell's OWN runs: at most eight times their
    # spread (1% is never too loose).
    assert held["bound"] <= max(0.01, 8 * stats.wide(sets)), held["name"]
    medians = [statistics.median(s) for s in sets]
    assert max(medians) - min(medians) <= held["bound"] * medians[0]


def test_the_two_readings_of_a_spread_by_hand():
    a = [100.0, 101.0, 102.0, 103.0, 104.0, 120.0]
    assert stats.without_farthest(a) == a[:5]
    # quartiles of five values lie at the 1.5th and 4.5th: 100.5 and 103.5
    assert stats.tight([a, a]) == pytest.approx(3.0 / 102.0)
    # all twelve: quartiles 101 and 104 around a median of 102.5
    assert stats.wide([a, a]) == pytest.approx(3.0 / 102.5)
    assert stats.spread(a) > 2 * stats.tight([a, a])
    # the extremes of the five that are kept, over the median of all six
    assert stats.trimmed_range(a) == pytest.approx(4.0 / 102.5)
