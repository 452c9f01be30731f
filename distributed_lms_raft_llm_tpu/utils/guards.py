"""Runtime guards: the dynamic counterparts of the static lint rules.

`scripts/lint.py` catches dispatch-hygiene and asyncio-discipline bugs that
are visible in source; this module catches the ones that only exist at
runtime, with the SAME vocabulary so the two halves reinforce each other:

- `intended_transfer()` marks a sanctioned host<->device sync point. The
  static rule `no-host-sync-in-dispatch` accepts syncs inside this block,
  and under strict dispatch the jax transfer guard allows them — one
  marker serves both checkers.
- `strict_dispatch()` / `enable_strict_dispatch()` turn on
  `jax.transfer_guard_device_to_host("disallow")`: any device->host
  readback OUTSIDE an `intended_transfer()` block raises on backends that
  move bytes (TPU/GPU; the CPU backend's readbacks are zero-copy and never
  trip the guard — the static rule is the enforcement there). Exposed as
  the tutoring server's `--strict-dispatch` flag.
- `compile_count_guard(...)` generalizes PR 2's compile-count assertion:
  a context manager over jitted callables that raises `RecompileError`
  when the guarded region compiled more programs than allowed — the
  silent-recompile-per-request failure mode (`P()` vs `P(None, None)`)
  made mechanical. `compile_count_guard(expected_from_inventory(engine))`
  additionally cross-validates against the static program manifest
  (`engine/program_inventory.py`): at exit every inventoried program's
  cache size must EQUAL the manifest's expectation — more means warmup
  missed a program, fewer means the checked-in inventory is stale, and
  both directions raise.
- `LoopWatchdog` measures asyncio loop stalls: the Raft tick loop reports
  its scheduling lag here; lag lands in a Metrics histogram (exported via
  /metrics as `<name>_lag`) and stalls above the threshold warn and count
  (`<name>_stalls`). The static rule `no-blocking-in-async` prevents the
  common causes; the watchdog catches whatever slips through.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Tuple

log = logging.getLogger(__name__)


class RecompileError(AssertionError):
    """A guarded region compiled programs it promised not to (the warmup
    didn't cover a live code path — the PR-2 bug class)."""


class InventoryMismatchError(RecompileError):
    """The runtime program caches and the static manifest
    (engine/program_inventory.py) disagree — an uncovered program, a stale
    inventory entry, or drifted domain math. Regenerate with
    `python scripts/gen_program_inventory.py --write` if the change was
    intentional."""


# --------------------------------------------------------- transfer guards


# One-time flag: strict dispatch on a CPU backend warns exactly once per
# process (tests reset it to re-pin the warning).
_warned_cpu_noop = False


def _warn_if_cpu_noop() -> None:
    """The jax transfer guard only fires on backends where device->host
    readbacks move bytes; the CPU backend's readbacks are zero-copy and
    NEVER trip it, so `--strict-dispatch` on CPU would silently enforce
    nothing. Say so once — and point at the static rule
    (`no-host-sync-in-dispatch`) that IS the CPU-side enforcement."""
    global _warned_cpu_noop
    if _warned_cpu_noop:
        return
    import jax

    if jax.default_backend() == "cpu":
        _warned_cpu_noop = True
        log.warning(
            "strict dispatch: the jax transfer guard is a no-op on the CPU "
            "backend (readbacks are zero-copy) — unmarked syncs will NOT "
            "raise here; the `no-host-sync-in-dispatch` lint rule is the "
            "enforcement on CPU (see README: dlrl-lint)"
        )


@contextlib.contextmanager
def intended_transfer() -> Iterator[None]:
    """Mark a sanctioned host<->device sync point.

    Inside this block, device readbacks are allowed even under strict
    dispatch. The static rule `no-host-sync-in-dispatch` recognizes the
    same block lexically, so every sync in a dispatch module is either
    wrapped here (auditable, greppable) or a lint finding.
    """
    import jax

    with jax.transfer_guard_device_to_host("allow"):
        yield


@contextlib.contextmanager
def strict_dispatch() -> Iterator[None]:
    """Scoped strict mode: device->host readbacks outside
    `intended_transfer()` raise (on backends where readbacks are real
    transfers; on CPU this is a documented no-op — a one-time warning
    points at the lint rule that enforces there). Engine test fixtures
    wrap hot-path runs in this."""
    import jax

    _warn_if_cpu_noop()
    with jax.transfer_guard_device_to_host("disallow"):
        yield


def enable_strict_dispatch() -> None:
    """Process-wide strict mode (the `--strict-dispatch` server flag):
    every unmarked device->host readback from here on raises. Warmup and
    serving share the setting, so a sync the warmup path tolerates cannot
    hide in the live path."""
    import jax

    _warn_if_cpu_noop()
    jax.config.update("jax_transfer_guard_device_to_host", "disallow")
    log.info("strict dispatch: unmarked device->host transfers will raise")


# ------------------------------------------------------ compile-count guard


class _CompileCounts:
    """Snapshot of per-callable jit cache sizes."""

    def __init__(self, fns: Sequence[object]):
        self.fns = list(fns)
        self.baseline = [self._size(f) for f in self.fns]

    @staticmethod
    def _size(fn: object) -> int:
        size = getattr(fn, "_cache_size", None)
        if size is None:
            raise TypeError(
                f"{fn!r} is not a jitted callable (no _cache_size); pass "
                "the jax.jit result itself"
            )
        return int(size())

    def new_compiles(self) -> int:
        return sum(
            self._size(f) - b for f, b in zip(self.fns, self.baseline)
        )


class InventoryExpectation:
    """Absolute expected program-cache sizes for an engine's inventoried
    (warmup-covered) programs, from the static manifest. Built via
    `expected_from_inventory(engine)`; consumed by `compile_count_guard`.
    """

    def __init__(self, engine: object):
        from ..engine import program_inventory as _inv

        self.engine = engine
        self.expected = _inv.expected_counts(engine)  # attr -> size
        self.fns = {
            attr: getattr(engine, attr) for attr in sorted(self.expected)
        }

    def mismatches(self) -> Dict[str, Tuple[int, int]]:
        """{attr: (actual, expected)} for every program whose live cache
        size differs from the manifest expectation, in either direction."""
        out: Dict[str, Tuple[int, int]] = {}
        for attr, fn in self.fns.items():
            actual = _CompileCounts._size(fn)
            if actual != self.expected[attr]:
                out[attr] = (actual, self.expected[attr])
        return out


def expected_from_inventory(engine: object) -> InventoryExpectation:
    """The static<->runtime cross-validation mode of `compile_count_guard`:

        eng.warmup()
        with compile_count_guard(expected_from_inventory(eng)):
            ... live serving ...

    The region must compile nothing new (the classic warmup-coverage
    claim), AND at exit every program named by engine/program_inventory.py
    must hold EXACTLY the manifest's expected count — more means an
    uncovered program slipped through, fewer means the checked-in
    inventory overstates the domain (stale manifest). Either direction
    raises InventoryMismatchError.
    """
    return InventoryExpectation(engine)


@contextlib.contextmanager
def compile_count_guard(
    *fns: object, allow: int = 0, what: str = "guarded region"
) -> Iterator[_CompileCounts]:
    """Assert the region compiles at most `allow` new programs across the
    given jitted callables.

    Generalizes the PR-2 warmup-coverage guard: wrap the live serving path
    after warmup with `allow=0` and any program the warmup failed to cover
    — a spelling-different sharding, an unexpected shape — raises
    `RecompileError` at the moment it happens instead of shipping as a
    silent tens-of-seconds stall per request.

        with compile_count_guard(eng._megastep, eng._stage) as guard:
            eng.drain()
        # guard.new_compiles() also available for reporting

    Passing `expected_from_inventory(engine)` as the sole argument guards
    the engine's whole inventoried program set and additionally asserts
    the post-region cache sizes EQUAL the static manifest's expectations
    (see expected_from_inventory).
    """
    expectation: Optional[InventoryExpectation] = None
    if len(fns) == 1 and isinstance(fns[0], InventoryExpectation):
        expectation = fns[0]
        fns = tuple(expectation.fns.values())
        what = (
            f"{type(expectation.engine).__name__} inventoried program set"
            if what == "guarded region" else what
        )
    counts = _CompileCounts(fns)
    yield counts
    new = counts.new_compiles()
    if new > allow:
        raise RecompileError(
            f"{what} compiled {new} new program(s) (allowed {allow}): "
            "warmup does not cover a live code path — check for "
            "spelling-different shardings or unexpected shapes"
        )
    if expectation is not None:
        bad = expectation.mismatches()
        if bad:
            detail = ", ".join(
                f"{attr}: {actual} compiled vs {exp} inventoried"
                for attr, (actual, exp) in sorted(bad.items())
            )
            raise InventoryMismatchError(
                f"{what} disagrees with engine/program_inventory.py "
                f"({detail}) — more than inventoried means warmup missed a "
                "program; fewer means the manifest is stale "
                "(scripts/gen_program_inventory.py --write)"
            )


# ---------------------------------------------------------- loop watchdog


class LoopWatchdog:
    """Event-loop stall detector for a periodic asyncio task.

    The owner of a loop (the Raft tick loop) calls `observe(lag_s)` with
    how late each iteration ran versus its schedule; lag lands in a
    Metrics histogram (`<name>_lag`, seconds — /metrics renders latency
    percentiles) and stalls above `warn_above_s` increment the
    `<name>_stalls` counter and log a rate-limited warning. A stalled loop
    means SOMETHING blocked the thread — sync IO, a device readback, a
    long pure-Python apply — exactly what `raft/core.py`'s "nothing to
    lock" single-task design must never experience.

    For loops the caller does not own, `run()` is a standalone heartbeat
    coroutine: it sleeps `interval_s` and observes its own wake-up lag.
    """

    def __init__(
        self,
        metrics: Optional[Any] = None,
        *,
        name: str = "loop",
        warn_above_s: float = 0.25,
        warn_every_s: float = 10.0,
        clock: Callable[[], float] = time.monotonic,
        lag_metric: Optional[str] = None,
        stalls_metric: Optional[str] = None,
    ):
        self.metrics = metrics
        self.name = name
        self.warn_above_s = warn_above_s
        self.warn_every_s = warn_every_s
        self._clock = clock
        self._last_warn = 0.0
        self.max_lag_s = 0.0
        self.stalls = 0
        # Series names default to `<name>_lag`/`<name>_stalls`; wiring
        # sites that export to /metrics pin them from the metrics
        # registry instead (make_tick_watchdog), so the emitted names
        # stay declared.
        self.lag_metric = lag_metric or f"{name}_lag"
        self.stalls_metric = stalls_metric or f"{name}_stalls"

    def observe(self, lag_s: float) -> None:
        lag_s = max(0.0, float(lag_s))
        self.max_lag_s = max(self.max_lag_s, lag_s)
        if self.metrics is not None:
            # Generic infrastructure: the name is whatever the wiring site
            # chose (registry constants for the exported loops), so the
            # static declared-name check happens there, not here.
            self.metrics.hist(self.lag_metric).observe(lag_s)  # lint: disable=metrics-registry
        if lag_s <= self.warn_above_s:
            return
        self.stalls += 1
        if self.metrics is not None:
            self.metrics.inc(self.stalls_metric)  # lint: disable=metrics-registry
        now = self._clock()
        if now - self._last_warn >= self.warn_every_s:
            self._last_warn = now
            log.warning(
                "%s stalled %.0f ms (threshold %.0f ms): something is "
                "blocking the event loop (%d stalls so far)",
                self.name, lag_s * 1e3, self.warn_above_s * 1e3, self.stalls,
            )

    async def run(self, interval_s: float = 0.1) -> None:
        """Standalone heartbeat for loops the caller can't instrument."""
        import asyncio

        while True:
            before = self._clock()
            await asyncio.sleep(interval_s)
            self.observe(self._clock() - before - interval_s)


def make_tick_watchdog(
    metrics: Optional[Any] = None, *, tick_interval: float,
    name: str = "raft_tick", stall_factor: float = 10.0,
) -> Optional[LoopWatchdog]:
    """The Raft wiring: warn when a tick lands `stall_factor` intervals
    late (a 10 ms tick loop warning at 100 ms of lag — late enough to
    matter for heartbeats, early enough to catch before elections fire).
    Returns None without metrics so callers can wire unconditionally."""
    if metrics is None:
        return None
    # Pin the default wiring's series names from the registry so
    # `raft_tick_lag`/`raft_tick_stalls` stay declared-and-live under the
    # metrics-registry rule; a custom `name` keeps the derived pair.
    from . import metrics_registry

    default = name == "raft_tick"
    return LoopWatchdog(
        metrics, name=name, warn_above_s=tick_interval * stall_factor,
        lag_metric=metrics_registry.RAFT_TICK_LAG if default else None,
        stalls_metric=metrics_registry.RAFT_TICK_STALLS if default else None,
    )


def make_serving_watchdog(
    metrics: Any, *, warn_above_s: float = 0.25,
) -> LoopWatchdog:
    """`make_tick_watchdog` generalized to the gRPC serving event loop:
    the server entry points run `watchdog.run(interval)` as a standalone
    heartbeat task, so a handler that blocks the loop (sync IO, a device
    readback, a long pure-Python stretch) shows up as the
    `serving_tick_lag` histogram and `serving_tick_stalls` counter in
    /metrics instead of being inferred from p99 latency tails. Every
    server entrypoint owns a Metrics instance, so `metrics` is required —
    callers chain `.run()` directly."""
    from . import metrics_registry

    return LoopWatchdog(
        metrics, name="serving_tick", warn_above_s=warn_above_s,
        lag_metric=metrics_registry.SERVING_TICK_LAG,
        stalls_metric=metrics_registry.SERVING_TICK_STALLS,
    )
