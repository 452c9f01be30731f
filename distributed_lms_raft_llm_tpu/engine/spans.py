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

Span names: `engine.step` > `engine.admit` | `engine.dispatch` |
`engine.reap.wait` | `engine.reap.host`, each with `engine.prog.*`
children at the dispatch sites and `engine.keys` where the next sampling
keys are split off (an admission, a dispatch); `queue.between_steps` and
`queue.idle` on the serving loop's thread (engine/batcher.py).
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
