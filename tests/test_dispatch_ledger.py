"""The dispatch ledger (engine/spans.py `DispatchLedger`): each megastep's
device time read on the host's clock at its reap, two completions apart,
laid against what the dispatch held.

Pinned here: the class on a made-up clock (what is timed, what is `late`
and what `unanchored`; K = 1 against a longer rung; narrow and wide passes
from `passes` and `crowded`; the bare subset; the dry count; the sums of
products give back the costs a sequence was made from); the served engine
on the CPU (timed and untimed together are every megastep reaped, the
series reach `/metrics` under the registry's names); `k` on
`engine.prog.megastep` and the ledger's reading on `engine.reap.host` in a
profiler's trace; and `incoming_wait`, the one wait before `queue_wait`.
"""

import asyncio
import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.layer_readers import dispatch_ledger as reader
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    PagedQueue,
    SamplingParams,
    spans,
)
from distributed_lms_raft_llm_tpu.utils import metrics_registry as metric
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

CHUNK = 16


class Sink:
    """What an engine's `_count` and `_observe` keep, and a device made
    up: a dispatch is a flag the test sets when it finishes."""

    def __init__(self):
        self.counts, self.obs = {}, {}
        self.ledger = spans.DispatchLedger(self.count, self.observe)
        self.flying = []

    def count(self, **amounts):
        for name, n in amounts.items():
            self.counts[name] = self.counts.get(name, 0) + n

    def observe(self, name, value):
        self.obs.setdefault(name, []).append(value)

    def send(self):
        """A megastep's launch has returned."""
        done = [False]
        self.ledger.dispatched(lambda: done[0], self.ledger.device_dry())
        self.flying.append(done)
        return done

    def call(self, t, *finish):
        """A call into the runtime that ends at `t`; the dispatches of
        `finish` complete while it lasts."""
        self.ledger.call_begins()
        for done in finish:
            done[0] = True
        self.ledger.call_ended(t)

    def reap(self, t=None, k=1, passes=0, crowded=0, draining=False,
             seen=True):
        """Reap the oldest dispatch, its successor sent before, as the
        engine's loop goes: seen completing at `t` inside a call into the
        runtime, or (`seen` false) finished while the host was elsewhere."""
        if len(self.flying) < 2 and not draining:
            self.send()
        done = self.flying.pop(0)
        if seen:
            self.call(t, done)
        else:
            done[0] = True
            self.call(t)  # the reads return at once
        return self.ledger.reaped(k, k * CHUNK, passes, crowded, draining)


def _grown(counts):
    return {k: v for k, v in counts.items() if v}


# ------------------------------------------------ the class, made-up clock


def test_two_completions_seen_time_the_later_dispatch():
    s = Sink()
    s.send()
    assert s.reap(10.0) == 0          # the first: nothing to measure from
    assert s.reap(10.25) == 250_000   # theirs, and only theirs
    assert s.reap(10.35) == 100_000
    assert s.counts["untimed_dispatches_unanchored"] == 1
    assert s.counts["timed_dispatches"] == 2
    assert s.counts["timed_device_us"] == 350_000
    assert s.counts["timed_iterations"] == 2 * CHUNK
    assert s.counts["untimed_dispatches_late"] == 0


def test_a_completion_is_seen_in_whichever_call_waited_for_it():
    """Not the reap alone: a launch that met the runtime's full queue
    returns when the device finishes a program, and the dispatch is dated
    at that call's end; the reap that follows finds it finished and
    changes nothing."""
    s = Sink()
    first, second = s.send(), s.send()
    s.call(1.0)                  # a key split: nothing finished in it
    s.call(2.0, first)           # the next launch waited for `first`
    third = s.send()
    s.call(2.5)                  # the reap's reads return at once
    assert s.ledger.reaped(1, CHUNK, 0, 0) == 0   # the first
    s.call(2.3 + 0.5, second)    # an admission's program waited
    s.send()
    s.call(3.4)
    assert s.ledger.reaped(1, CHUNK, 0, 0) == 800_000   # 2.0 -> 2.8
    s.call(3.5, third)
    s.send()
    assert s.ledger.reaped(1, CHUNK, 0, 0) == 700_000   # 2.8 -> 3.5


def test_finished_where_nobody_looked_is_late_and_the_next_unanchored():
    s = Sink()
    s.send()
    s.reap(1.0)
    assert s.reap(1.1) == 100_000
    assert s.reap(1.5, seen=False) == 0   # completed some time before 1.5
    assert s.reap(1.6) == 0               # from when? not from 1.5
    assert s.reap(1.7) == 100_000         # but it anchors this one
    assert s.counts["untimed_dispatches_late"] == 1
    assert s.counts["untimed_dispatches_unanchored"] == 2
    assert s.counts["timed_dispatches"] == 2


def test_two_completions_inside_one_call_are_both_unseen():
    s = Sink()
    a, b = s.send(), s.send()
    s.send()
    s.call(1.0, a, b)     # which of the two ended it, and when the other?
    for _ in range(2):
        assert s.ledger.reaped(1, CHUNK, 0, 0) == 0
    assert s.counts["untimed_dispatches_late"] == 2


@pytest.mark.parametrize("how", ["first", "drain", "reset", "dry"])
def test_unanchored(how):
    s = Sink()
    s.send()
    if how == "first":
        assert s.reap(5.0) == 0
        assert _grown(s.counts) == {"dispatches_device_dry": 1,
                                    "untimed_dispatches_unanchored": 1}
    elif how == "drain":
        s.reap(5.0)
        assert s.reap(5.1, draining=True) == 0   # no dispatch follows it
        assert not s.flying
        s.send()                                 # to an idle device
        assert s.reap(5.5) == 0                  # and it anchors nothing
        assert s.counts["untimed_dispatches_unanchored"] == 3
        assert s.reap(5.6) == 100_000
    elif how == "reset":
        s.reap(5.0)
        s.ledger.reset()
        s.flying.clear()
        s.send()
        assert s.reap(5.1) == 0
        assert s.counts["untimed_dispatches_unanchored"] == 2
    else:
        # Sent after its predecessor had finished: the time between the
        # two completions holds the device's idle wait for it.
        s.reap(5.0)                 # sends the second, reaps the first
        (second,) = s.flying
        s.call(5.1, second)         # seen completing, nothing behind it
        s.send()                    # the third goes to a dry device
        s.flying.pop(0)
        assert s.ledger.reaped(1, CHUNK, 0, 0) == 100_000
        assert s.reap(5.4) == 0     # the third: seen, but not timed
        assert s.reap(5.5) == 100_000
        assert s.counts["dispatches_device_dry"] == 2
        assert s.counts["untimed_dispatches_unanchored"] == 2


def test_one_chunk_against_a_longer_rung():
    s = Sink()
    s.send()
    s.reap(0.0)
    assert s.reap(0.4, k=4, passes=3, crowded=2) == 400_000
    assert s.reap(0.5, k=1) == 100_000   # a long one anchors all the same
    assert s.counts["timed_long_dispatches"] == 1
    assert s.counts["timed_long_iterations"] == 4 * CHUNK
    assert s.counts["timed_long_device_us"] == 400_000
    # Its passes enter no sum of the fit: they are narrow whatever is
    # staged, and the fit is over the one-chunk dispatches alone.
    assert s.counts["timed_dispatches"] == 1
    assert s.counts["timed_narrow_passes"] == 0
    assert s.counts["timed_wide_passes"] == 0


def test_narrow_and_wide_passes_from_passes_and_crowded():
    s = Sink()
    s.send()
    s.reap(0.0)
    s.reap(0.2, passes=5, crowded=2)    # 3 narrow, 2 wide, 200,000 us
    s.reap(0.3, passes=1, crowded=0)    # 1 narrow, 100,000 us
    c = s.counts
    assert c["timed_narrow_passes"] == 4 and c["timed_wide_passes"] == 2
    assert c["timed_narrow_sq"] == 9 + 1 and c["timed_wide_sq"] == 4
    assert c["timed_narrow_x_wide"] == 6
    assert c["timed_us_x_narrow"] == 3 * 200_000 + 100_000
    assert c["timed_us_x_wide"] == 2 * 200_000
    assert "timed_bare_dispatches" not in c and not s.obs


def test_the_bare_subset():
    s = Sink()
    s.send()
    s.reap(0.0)
    s.reap(0.08)               # bare: 80 ms over 16 iterations
    s.reap(0.2, passes=2)      # not bare
    s.reap(0.296)              # bare: 96 ms
    assert s.counts["timed_dispatches"] == 3
    assert s.counts["timed_bare_dispatches"] == 2
    assert s.counts["timed_bare_device_us"] == 176_000
    assert s.obs["bare_iteration_device"] == pytest.approx([0.005, 0.006])


def test_the_dry_count():
    s = Sink()
    first = s.send()             # nothing in flight
    s.send()                     # the newest in flight still runs
    first[0] = True
    s.send()                     # the NEWEST still runs: not dry
    s.flying[1][0] = s.flying[2][0] = True
    s.send()                     # it had finished: the device ran dry
    assert s.counts == {"dispatches_device_dry": 2}


def test_finishing_while_the_launch_is_held_is_not_dry():
    """Dry is judged before the launch: a predecessor that finishes while
    the launch waits in the runtime's queue leaves the device waiting for
    the launch's last stretch alone, and the dispatch is timed."""
    s = Sink()
    first = s.send()
    s.reap(1.0)                          # sends the second, reaps `first`
    (second,) = s.flying
    dry = s.ledger.device_dry()
    assert not dry                       # the second still runs
    s.call(1.2, second)                  # the third's launch waited for it
    done = [False]
    s.ledger.dispatched(lambda: done[0], dry)
    s.flying.append(done)
    assert first[0] and s.counts["dispatches_device_dry"] == 1
    s.flying.pop(0)
    assert s.ledger.reaped(1, CHUNK, 0, 0) == 200_000    # the second
    assert s.reap(1.5) == 300_000                        # the third: timed


def test_every_verdict_is_a_series_from_the_first_reap_on():
    """A share over the four verdicts is read from /metrics, where a
    series appears at its first count: all four are counted at every
    reap, three of them by 0, and exactly one by 1."""
    s = Sink()
    s.send()
    s.reap(0.0)
    assert {v: s.counts[v] for v in spans.DispatchLedger.VERDICTS} == {
        "timed_dispatches": 0, "timed_long_dispatches": 0,
        "untimed_dispatches_late": 0, "untimed_dispatches_unanchored": 1}
    for kw in ({}, {"k": 2}, {"seen": False}, {"draining": True}):
        before = sum(s.counts[v] for v in spans.DispatchLedger.VERDICTS)
        s.reap(1.0, **kw)
        assert sum(s.counts[v]
                   for v in spans.DispatchLedger.VERDICTS) == before + 1


def test_the_sums_give_back_the_costs_they_were_made_from():
    """device_us = 80,000 + 5,000 narrow + 14,000 wide, exactly, over a
    hand-made sequence: the reader's normal equations return b, n, w."""
    s = Sink()
    t = 0.0
    s.send()
    s.reap(t)
    held = [(0, 0), (1, 0), (2, 1), (0, 2), (3, 0), (1, 1), (0, 0), (4, 2)]
    for narrow, wide in held * 3:
        t += (80_000 + 5_000 * narrow + 14_000 * wide) / 1e6
        s.reap(t, passes=narrow + wide, crowded=wide)
    grew = {k[len("timed_"):]: v for k, v in s.counts.items()
            if k.startswith("timed_")}
    costs = reader.fit(grew)
    assert costs["iteration"] == pytest.approx(80_000, rel=1e-6)
    assert costs["narrow_pass"] == pytest.approx(5_000, rel=1e-6)
    assert costs["wide_pass"] == pytest.approx(14_000, rel=1e-6)
    assert (s.counts["timed_bare_device_us"]
            == 80_000 * s.counts["timed_bare_dispatches"])


# ------------------------------------------------ the served engine, tiny


MAX_NEW = 8
SLOTS = 2
CTX = "the raft leader election protocol works by "
PROMPTS = [CTX + "choosing a leader", CTX + "replicating a log",
           "what is paging?", CTX + "electing nodes",
           CTX + "choosing a leader"]
LEDGER_KEYS = [k for k in metric.ENGINE_LOOP_COUNTERS
               if k.startswith(("timed_", "untimed_", "dispatches_device"))]


def make_engine():
    return PagedEngine(
        EngineConfig(
            model="tiny",
            sampling=SamplingParams.greedy(max_new_tokens=MAX_NEW),
            length_buckets=(16, 32), batch_buckets=(1, 2),
            dtype=jnp.float32,
        ),
        slots=SLOTS, chunk=2, megastep=2, megastep_max=4,
        prefix_cache=True, prefix_cache_blocks=64, prefix_block_tokens=4,
        prefill_chunk_tokens=4,
    )


def _serve(engine, prompts, metrics):
    async def run():
        queue = PagedQueue(engine, metrics=metrics)
        await queue.start()
        try:
            return await asyncio.gather(*(queue.submit(p) for p in prompts))
        finally:
            await queue.close()

    return asyncio.run(run())


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One served run of the tiny engine under the profiler: the metrics'
    snapshot and the host plane's engine spans with their attributes."""
    from jax.profiler import ProfileData

    engine = make_engine()
    engine.warmup()
    engine.pop_loop_stats()
    metrics = Metrics()
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        answers = _serve(engine, PROMPTS, metrics)
    finally:
        jax.profiler.stop_trace()
    assert len(answers) == len(PROMPTS)
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    # The step runs on whichever thread of the pool is free: the lines
    # together, in time's order.
    events = sorted(((e.start_ns, e.name, dict(e.stats))
                     for plane in ProfileData.from_file(path).planes
                     if plane.name == "/host:CPU"
                     for line in plane.lines for e in line.events
                     if e.name.startswith(("engine.", "queue."))),
                    key=lambda event: event[0])
    return metrics.snapshot(), [event[1:] for event in events]


def _ledger_counters(snap):
    return {key: snap["counters"].get(metric.ENGINE_LOOP_COUNTERS[key], 0)
            for key in LEDGER_KEYS}


def test_timed_and_untimed_are_every_megastep_reaped(served):
    snap, _ = served
    c = _ledger_counters(snap)
    reaped = snap["latency"]["engine_reap_wait"]["count"]
    assert reaped > 0
    assert (c["timed_dispatches"] + c["timed_long_dispatches"]
            + c["untimed_dispatches_late"]
            + c["untimed_dispatches_unanchored"]) == reaped
    # The first dispatch found nothing in flight.
    assert 1 <= c["dispatches_device_dry"] <= reaped


def test_timed_iterations_never_pass_the_scan_iterations(served):
    snap, _ = served
    c = _ledger_counters(snap)
    assert (c["timed_iterations"] + c["timed_long_iterations"]
            <= snap["counters"]["engine_scan_iterations"])
    assert c["timed_bare_dispatches"] <= c["timed_dispatches"]
    assert c["timed_bare_device_us"] <= c["timed_device_us"]
    bare = snap["latency"].get(
        metric.ENGINE_LOOP_HISTOGRAMS["bare_iteration_device"], {})
    assert bare.get("count", 0) == c["timed_bare_dispatches"]


@pytest.mark.parametrize("key", LEDGER_KEYS + ["bare_iteration_device"])
def test_a_ledger_series_is_declared_under_its_name(key):
    table = (metric.ENGINE_LOOP_HISTOGRAMS if key == "bare_iteration_device"
             else metric.ENGINE_LOOP_COUNTERS)
    assert table[key] == "engine_" + key
    assert metric.is_declared(table[key])
    help_ = {m.name: m.help for m in metric.all_metrics()}[table[key]]
    if key.endswith(("device_us", "bare_iteration_device", "us_x_narrow",
                     "us_x_wide")) or key == "timed_dispatches":
        assert "device time as the host saw two completions apart" in help_


def test_the_engine_reports_only_declared_keys():
    """Every key the ledger counts under reaches `pop_loop_stats` and is a
    key of the registry's tables; a timed dispatch is made by hand, since
    the CPU's dispatches are over before the host looks."""
    engine = make_engine()
    engine.warmup()
    engine.pop_loop_stats()
    engine.submit(PROMPTS[0])
    engine.drain()
    engine.pop_loop_stats()  # the CPU's own dispatches: now and then timed
    ledger = engine._ledger

    def dispatch(t, k=1, passes=0, crowded=0, seen=True):
        """Send one more, then the oldest in flight finishes inside a call
        that ends at `t` (or, not `seen`, outside any) and is reaped."""
        done = flying.pop(0)
        flying.append([False])
        ledger.dispatched(lambda done=flying[-1]: done[0], ledger.device_dry())
        ledger.call_begins()
        done[0] = True
        if not seen:
            ledger.call_begins()
        ledger.call_ended(t)
        return ledger.reaped(k, 2 * k, passes, crowded)

    flying = [[False]]
    ledger.dispatched(lambda done=flying[0]: done[0], ledger.device_dry())
    dispatch(1.0)
    dispatch(1.5, passes=3, crowded=1)
    dispatch(2.0)
    dispatch(2.5, k=2, passes=1, crowded=1)
    dispatch(3.0, seen=False)
    counts, observations, _ = engine.pop_loop_stats()
    assert set(LEDGER_KEYS) <= set(counts) <= set(metric.ENGINE_LOOP_COUNTERS)
    assert set(observations) <= set(metric.ENGINE_LOOP_HISTOGRAMS)
    assert observations["bare_iteration_device"] == [0.25]
    assert counts["timed_dispatches"] == 2
    assert counts["timed_long_device_us"] == 500_000
    # A reset forgets the anchor and what was in flight: the next dispatch
    # seen is unanchored.
    engine.reset()
    flying = [[False]]
    ledger.dispatched(lambda done=flying[0]: done[0], ledger.device_dry())
    dispatch(9.0)
    counts, _, _ = engine.pop_loop_stats()
    assert _grown(counts) == {"dispatches_device_dry": 1,
                              "untimed_dispatches_unanchored": 1}


def test_the_megastep_span_carries_its_rung(served):
    _, events = served
    progs = [stats for name, stats in events
             if name == "engine.prog.megastep"]
    sent = [stats for name, stats in events if name == "engine.dispatch"]
    assert progs and [p["k"] for p in progs] == [d["k"] for d in sent]
    assert all(p["k"] >= 1 for p in progs)


def test_the_reap_span_carries_the_ledgers_reading(served):
    snap, events = served
    hosts = [stats for name, stats in events if name == "engine.reap.host"]
    assert len(hosts) == snap["latency"]["engine_reap_wait"]["count"]
    for stats in hosts:
        assert set(stats) >= {"k", "passes", "wide", "device_us"}
        assert 0 <= stats["wide"] <= stats["passes"]
        assert stats["wide"] == 0 or stats["k"] == 1
        assert stats["device_us"] >= 0
    # Each rung sent is walked once, in order.
    sent = [stats["k"] for name, stats in events
            if name == "engine.prog.megastep"]
    assert [h["k"] for h in hosts] == sent
    c = snap["counters"]
    assert sum(h["passes"] for h in hosts) == c["engine_prefill_passes"]
    assert sum(h["device_us"] for h in hosts) == (
        c.get("engine_timed_device_us", 0)
        + c.get("engine_timed_long_device_us", 0))


# ---------------------------------------------- the wait before queue_wait


def test_the_submit_span_is_on_the_host_plane(served):
    _, events = served
    assert sum(name == "queue.submit" for name, _ in events) == len(PROMPTS)


def test_the_three_waits_fit_inside_submit_to_first_token():
    """`incoming_wait` + `queue_wait` + `prefill_wait` is the server's
    share of a first token: one request through the queue, and their sum
    is at most the time from `PagedQueue.submit` to its first token."""
    engine = make_engine()
    engine.warmup()
    metrics = Metrics()

    async def run():
        queue = PagedQueue(engine, metrics=metrics)
        await queue.start()
        try:
            t0 = time.monotonic()
            stream = queue.submit_stream(PROMPTS[0])
            await stream.__anext__()
            to_first = time.monotonic() - t0
            await stream.aclose()
            return to_first
        finally:
            await queue.close()

    to_first = asyncio.run(run())
    lat = metrics.snapshot()["latency"]
    assert metric.is_declared("incoming_wait")
    waits = [lat[name] for name in
             ("incoming_wait", "queue_wait", "prefill_wait")]
    assert [w["count"] for w in waits] == [1, 1, 1]
    assert all(w["max_s"] >= 0 for w in waits)
    assert 0 < sum(w["max_s"] for w in waits) <= to_first
    assert (lat["queue_wait"]["max_s"] + lat["prefill_wait"]["max_s"]
            == pytest.approx(lat["ttft"]["max_s"], rel=1e-6))
