"""Names for the engine's programs and spans for its host loop, on the
profiler's clock.

Two small helpers every engine module shares (they import jax, so they
live here and not in `utils/tracing.py`, which clients import):

- `named_partial(fn, **statics)`: the `functools.partial` each engine
  jits, with the function's name on it. `jax.jit` names a lowered module after
  `fun.__name__`; a bare partial has none, so every engine program used to
  print as `jit__unknown` in a device trace. A FRESH partial per call is
  kept on purpose: jax.jit shares one program cache across wrappers of the
  same function object, and per-engine cache identity is what the program
  inventory's exact counts rest on.
- `Span(name, sink)`: one interval, two clocks, two records. It opens a
  `jax.profiler.TraceAnnotation` (an event on this thread's line of the
  host plane when a profiler is attached, ~0.3 us when none is), reads the
  monotonic clock and this thread's CPU clock at both ends, and on a clean
  exit hands itself (`name`, `start_unix`, `wall_s`, `cpu_s`, `wait_s`) to
  `sink`. `wall_s - cpu_s` is the time the thread slept inside the span;
  `wait_s` is the part of that sleep that lay inside a call into the
  runtime (`is_runtime_call`: an `engine.prog.*` span, `engine.reap.wait`
  or `engine.keys`), its own or a child's.
  `ProgramLog` is the engine's sink: `engine.prog.<program>` spans
  become the (program, start, wall) entries `pop_program_times()` drains
  into the `engine_prog_*` histograms and the per-request flight recorder,
  and each counts as one host dispatch; every span, those included, is
  added into `sums` by its name (`SpanSum`), which the paged engine drains
  with `pop_loop_stats()`.
- `turn_budget(...)`: one turn of the serving loop (engine/batcher.py
  `PagedQueue._run`) parted into the host's own work, its wait for the
  device and what neither explains, in whole microseconds.
- `DispatchLedger`: each megastep's device time, read on the host's clock
  as two completions apart (each seen at the end of the call into the
  runtime that waited for it) and laid against what the dispatch held
  (its iterations, its prefill passes by their width).

Span names: `engine.step` > `engine.admit` | `engine.dispatch` |
`engine.reap.wait` | `engine.reap.host` (with `k`, `passes`, `wide` and
`device_us`: the ledger's reading of the dispatch it walks), each with
`engine.prog.*` children at the dispatch sites (`engine.prog.megastep`
with its `k`) and `engine.keys` where the next sampling keys are split
off (an admission, a dispatch); `queue.between_steps`, `queue.idle` and
`queue.submit` (the tokenizer's pass at an admission) on the serving
loop's thread (engine/batcher.py).
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax

PROG = "engine.prog."
STEP = "engine.step"
REAP_WAIT = "engine.reap.wait"
KEYS = "engine.keys"
BETWEEN_STEPS = "queue.between_steps"
# The step's phases: siblings under `engine.step`, and with
# `queue.between_steps` the places a turn's CPU time is counted by.
STEP_PHASES = {"admit": "engine.admit", "dispatch": "engine.dispatch",
               "reap_wait": REAP_WAIT, "reap_host": "engine.reap.host"}

# The innermost open span of each thread: a span's parent is the one that
# was open on its thread when it was entered.
_open = threading.local()


def named_partial(fn: Callable, **statics) -> Callable:
    """A fresh `partial(fn, **statics)` that jits as `jit_<fn.__name__>`."""
    bound = partial(fn, **statics)
    bound.__name__ = fn.__name__
    return bound


def is_runtime_call(name: str) -> bool:
    """Whether a span of this name is a call into the runtime, inside
    which a sleeping thread is waiting for the device: the read-back of a
    dispatch's results, a program of the inventory, or the small programs
    the key chain is made of."""
    return name in (REAP_WAIT, KEYS) or name.startswith(PROG)


class Span:
    """`with Span(name, sink, **attrs) as sp: ...`; afterwards `sp.wall_s`,
    `sp.cpu_s` (this thread's CPU time inside it), `sp.wait_s` (its sleep
    inside runtime calls) and what its children took of the first two
    (`kids_wall_s`, `kids_cpu_s`: its self time is its own less theirs).
    Entered and left on one thread; the CPU clock is read inside the
    monotonic one, so `cpu_s <= wall_s` up to the CPU clock's tick."""

    __slots__ = ("name", "sink", "start_unix", "wall_s", "cpu_s", "wait_s",
                 "kids_wall_s", "kids_cpu_s", "_t0", "_c0", "_ann",
                 "_parent")

    def __init__(self, name: str, sink: Optional[Callable] = None, **attrs):
        self.name, self.sink = name, sink
        self.start_unix = self.wall_s = self.cpu_s = self.wait_s = 0.0
        self.kids_wall_s = self.kids_cpu_s = 0.0
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self) -> "Span":
        self._parent = getattr(_open, "span", None)
        _open.span = self
        self.start_unix, self._t0 = time.time(), time.monotonic()
        self._ann.__enter__()
        self._c0 = time.thread_time()
        return self

    @property
    def ended_s(self) -> float:
        """When it was left, on the monotonic clock."""
        return self._t0 + self.wall_s

    def __exit__(self, exc_type, exc, tb) -> bool:
        cpu_s = time.thread_time() - self._c0
        self._ann.__exit__(exc_type, exc, tb)
        self.wall_s = wall_s = time.monotonic() - self._t0
        self.cpu_s = cpu_s
        if is_runtime_call(self.name):
            # All of its sleep, a nested call's (added by it below) too.
            # Not cut at zero: where the thread's CPU clock is coarse (a
            # tick of 10 ms on the chip's machine) a short call reads a
            # whole tick or none, and only the sums are right.
            self.wait_s = wall_s - cpu_s
        _open.span = parent = self._parent
        if parent is not None:
            parent.kids_wall_s += wall_s
            parent.kids_cpu_s += cpu_s
            parent.wait_s += self.wait_s
        if self.sink is not None and exc_type is None:
            self.sink(self)
        return False


class SpanSum:
    """The spans of one name a sink has taken since its last drain: how
    many, and the sums of their seconds (`self_*`: less their children's)."""

    __slots__ = ("n", "wall_s", "cpu_s", "wait_s", "self_wall_s",
                 "self_cpu_s")

    def __init__(self):
        self.n = 0
        self.wall_s = self.cpu_s = self.wait_s = 0.0
        self.self_wall_s = self.self_cpu_s = 0.0

    def add(self, sp: Span) -> None:
        self.n += 1
        self.wall_s += sp.wall_s
        self.cpu_s += sp.cpu_s
        self.wait_s += sp.wait_s
        self.self_wall_s += sp.wall_s - sp.kids_wall_s
        self.self_cpu_s += sp.cpu_s - sp.kids_cpu_s


NO_SPANS = SpanSum()


class ProgramLog:
    """An engine's record of its spans. Every span is added into `sums` by
    its name; `engine.prog.*` spans besides become entries: host dispatch
    walls (device compute overlaps them under pipelining; the call is what
    the serving loop spends) and their count. Bounded, so a caller that
    never drains it (bench loops, warmup) cannot grow it."""

    def __init__(self, cap: int):
        self.cap = cap
        self.entries: List[Tuple[str, float, float]] = []
        self.dispatches = 0
        self.sums: Dict[str, SpanSum] = {}

    def __call__(self, sp: Span) -> None:
        total = self.sums.get(sp.name)
        if total is None:
            total = self.sums[sp.name] = SpanSum()
        total.add(sp)
        if not sp.name.startswith(PROG):
            return
        self.dispatches += 1
        self.entries.append((sp.name[len(PROG):], sp.start_unix, sp.wall_s))
        if len(self.entries) > self.cap:
            del self.entries[: -self.cap]

    def pop(self) -> List[Tuple[str, float, float]]:
        out, self.entries = self.entries, []
        return out

    def pop_sums(self) -> Dict[str, SpanSum]:
        out, self.sums = self.sums, {}
        return out


def turn_budget(wall_s: float, sums: Dict[str, SpanSum],
                between: Span) -> Dict[str, int]:
    """One turn of the serving loop in whole microseconds, keyed as the
    metrics registry's ENGINE_LOOP_COUNTERS: `wall_s` from before the step
    was handed to its thread to the return of `_between_steps`, `sums` the
    step's spans (`ProgramLog.sums`), `between` the closed
    `queue.between_steps` span. `loop_host_work_us` is the CPU time of
    `engine.step` and of `between`, `loop_device_wait_us` the step's sleep
    inside runtime calls, and the wall less both is the turn's stall: time
    that was neither this code's CPU nor a wait for the device. The two
    are cut to fit the wall, so the three parts are each >= 0 and sum to
    it. `loop_cpu_us_*` say where the work was spent."""
    def us(seconds: float) -> int:
        return int(round(seconds * 1e6))

    step = sums.get(STEP, NO_SPANS)
    wall = us(wall_s)
    work = min(us(step.cpu_s + between.cpu_s), wall)
    out = {"loop_wall_us": wall, "loop_host_work_us": work,
           "loop_device_wait_us": min(max(us(step.wait_s), 0), wall - work),
           "loop_cpu_us_between_steps": us(between.cpu_s)}
    for key, name in STEP_PHASES.items():
        out["loop_cpu_us_" + key] = us(sums.get(name, NO_SPANS).cpu_s)
    return out


class DispatchLedger:
    """Device time of each megastep as the host saw two completions apart.

    The host is ahead of the device: it launches dispatch N+1 (and N+2)
    before it reaps N, and for most of its wall it sleeps inside a call
    into the runtime, which returns when the device finishes a program:
    the reap's blocking read where the results were still to come, else
    whichever launch met the runtime's full queue (the megastep's, a key
    split's, an admission program's). So a megastep's completion is SEEN
    where a call into the runtime began with it unfinished and ended with
    it finished (`is_ready()` on its tokens at both ends: `call_begins`,
    `call_ended`), and it is dated at that call's end. Where two
    consecutive dispatches were both seen completing, and the later was
    queued while the earlier still ran, the time between the two is the
    device's time for the later one, with nothing idle inside. No thread,
    no callback in a program, no profiler: a readiness flag at each end of
    the calls the engine's spans already wrap, handed in by the engine, so
    a test drives this with a made-up clock.

    What the interval holds besides the megastep, and nothing is
    subtracted: the copies of its few KB of results back to the host
    (started at the dispatch), and the admission's own small programs
    (`stage`, `stage_block`, `restore_state`, the key splits), which run
    on the device between two megasteps and so lie inside the interval of
    the dispatch they were launched before.

    A dispatch that finished outside any such call (while the host walked
    tokens, or between two steps), or together with its successor inside
    one, is untimed (`late`: the host was not there) and anchors nothing.
    One seen completing whose predecessor was not, or that was sent to a
    device that had run dry, and the engine's first, the first after a
    `reset()` and the reaps of the drain once nothing is live, are untimed
    too (`unanchored`) but anchor the next. Timed and untimed together are
    every megastep reaped. Counts go to `count(**amounts)` under the keys
    of the metrics registry's ENGINE_LOOP_COUNTERS, a bare one-chunk
    dispatch's seconds an iteration to `observe("bare_iteration_device",
    s)`: over the timed dispatches of ONE chunk the sums are those least
    squares needs for `us = b + n narrow + w wide`
    (`benchmarks/layer_readers/dispatch_ledger.py` solves them)."""

    __slots__ = ("_count", "_observe", "_anchor", "_flight", "_armed")
    # What a reaped megastep is, exactly one of.
    VERDICTS = ("timed_dispatches", "timed_long_dispatches",
                "untimed_dispatches_late", "untimed_dispatches_unanchored")
    _MISSED = -1.0  # a completion nobody saw, where a time would stand

    def __init__(self, count: Callable[..., None],
                 observe: Callable[[str, float], None]):
        self._count, self._observe = count, observe
        self.reset()

    def reset(self) -> None:
        # When the dispatch reaped last was SEEN completing, else None.
        self._anchor: Optional[float] = None
        # The dispatches in flight, oldest first: [is_ready, when it was
        # seen completing (None: not yet; _MISSED), sent to a dry device].
        self._flight: List[list] = []
        # Its place in `_flight`, between a call's two ends.
        self._armed: Optional[int] = None

    def call_begins(self) -> None:
        """Before a call into the runtime: the oldest dispatch in flight
        that is still unfinished is the one this call may see completing.
        One found finished here completed where nobody looked."""
        self._armed = None
        for i, entry in enumerate(self._flight):
            if entry[1] is None:
                if not entry[0]():
                    self._armed = i
                    return
                entry[1] = self._MISSED

    def call_ended(self, t: float) -> None:
        """After that call, at `t` (monotonic seconds): if the dispatch is
        finished now, `t` is when it completed, unless its successor
        finished inside the same call (then neither was seen)."""
        i, self._armed = self._armed, None
        if i is None or not self._flight[i][0]():
            return
        self._flight[i][1] = t
        if i + 1 < len(self._flight) and self._flight[i + 1][0]():
            self._flight[i][1] = self._flight[i + 1][1] = self._MISSED

    def device_dry(self) -> bool:
        """Before a megastep's launch: whether nothing is in flight or the
        newest in flight has already finished, so that the device ran out
        of megasteps before this one was sent. (One that finishes while
        the launch is held in the runtime's full queue leaves the device
        waiting for the launch's last stretch, under a millisecond, which
        then lies inside the interval: not dry.)"""
        newest = self._flight[-1] if self._flight else None
        return newest is None or newest[1] is not None or newest[0]()

    def dispatched(self, is_ready: Callable[[], bool], dry: bool) -> None:
        """After a megastep's launch returned; `is_ready()` says whether
        it has finished, `dry` what `device_dry()` said before it."""
        self._count(dispatches_device_dry=int(dry))
        self._flight.append([is_ready, None, dry])

    def reaped(self, k: int, iterations: int, passes: int, crowded: int,
               draining: bool = False) -> int:
        """The oldest dispatch in flight was read back (the reads are a
        call into the runtime like any other: it was seen completing in
        them or before, or missed). `k` chunks of `iterations` scan
        iterations in all; `passes` prefill passes, `crowded` of them
        with two or more slots staged: the wide pass at k = 1, a narrow
        one on a longer rung. `draining`: nothing is live, no dispatch
        follows this one. Returns the dispatch's device time in whole
        microseconds, 0 where it is untimed."""
        _, seen, dry = self._flight.pop(0)
        anchor = self._anchor
        late = seen is None or seen == self._MISSED
        self._anchor = None if late or draining else seen
        if late:
            verdict = "untimed_dispatches_late"
        elif anchor is None or dry or draining:
            verdict = "untimed_dispatches_unanchored"
        else:
            verdict = "timed_dispatches" if k == 1 else "timed_long_dispatches"
        # All four every time, three of them by 0: a series enters
        # /metrics at its first count, and a share over the four has to be
        # readable over a window in which one of them never occurred.
        self._count(**{v: int(v == verdict) for v in self.VERDICTS})
        if verdict.startswith("untimed"):
            return 0
        us = int(round((seen - anchor) * 1e6))
        if k > 1:
            self._count(timed_long_iterations=iterations,
                        timed_long_device_us=us)
            return us
        wide, narrow = crowded, passes - crowded
        self._count(timed_iterations=iterations, timed_device_us=us,
                    timed_narrow_passes=narrow, timed_wide_passes=wide,
                    timed_narrow_sq=narrow * narrow,
                    timed_wide_sq=wide * wide,
                    timed_narrow_x_wide=narrow * wide,
                    timed_us_x_narrow=us * narrow,
                    timed_us_x_wide=us * wide)
        if not passes:
            self._count(timed_bare_dispatches=1, timed_bare_device_us=us)
            self._observe("bare_iteration_device",
                          (seen - anchor) / iterations)
        return us
