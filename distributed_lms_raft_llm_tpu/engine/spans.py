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
- `Span(name, sink)`: one interval, two records. It opens a
  `jax.profiler.TraceAnnotation` (an event on this thread's line of the
  host plane when a profiler is attached, ~0.3 us when none is) and on a
  clean exit hands itself (`name`, `start_unix`, `wall_s`) to `sink`.
  `ProgramLog` is the sink both engines use: `engine.prog.<program>` spans
  become the (program, start, wall) entries `pop_program_times()` drains
  into the `engine_prog_*` histograms and the per-request flight recorder,
  and each counts as one host dispatch.

Span names: `engine.step` > `engine.admit` | `engine.dispatch` |
`engine.reap.wait` | `engine.reap.host`, each with `engine.prog.*`
children at the dispatch sites; `queue.between_steps` and `queue.idle` on
the serving loop's thread (engine/batcher.py).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable, List, Optional, Tuple

import jax

PROG = "engine.prog."


def named_partial(fn: Callable, **statics) -> Callable:
    """A fresh `partial(fn, **statics)` that jits as `jit_<fn.__name__>`."""
    bound = partial(fn, **statics)
    bound.__name__ = fn.__name__
    return bound


class Span:
    """`with Span(name, sink, **attrs) as sp: ...`; `sp.wall_s` afterwards."""

    __slots__ = ("name", "sink", "start_unix", "wall_s", "_t0", "_ann")

    def __init__(self, name: str, sink: Optional[Callable] = None, **attrs):
        self.name, self.sink = name, sink
        self.start_unix = self.wall_s = 0.0
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self) -> "Span":
        self.start_unix, self._t0 = time.time(), time.monotonic()
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        self.wall_s = time.monotonic() - self._t0
        if self.sink is not None and exc_type is None:
            self.sink(self)
        return False


class ProgramLog:
    """An engine's record of its `engine.prog.*` spans: host dispatch walls
    (device compute overlaps them under pipelining; the call is what the
    serving loop spends) and their count. Bounded, so a caller that never
    drains it (bench loops, warmup) cannot grow it."""

    def __init__(self, cap: int):
        self.cap = cap
        self.entries: List[Tuple[str, float, float]] = []
        self.dispatches = 0

    def __call__(self, sp: Span) -> None:
        if not sp.name.startswith(PROG):
            return
        self.dispatches += 1
        self.entries.append((sp.name[len(PROG):], sp.start_unix, sp.wall_s))
        if len(self.entries) > self.cap:
            del self.entries[: -self.cap]

    def pop(self) -> List[Tuple[str, float, float]]:
        out, self.entries = self.entries, []
        return out
