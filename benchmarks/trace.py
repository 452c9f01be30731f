"""From the profiler's trace to numbers.

`extract` reads an `.xplane.pb` (with `jax.profiler.ProfileData`, so only the
chip-owning process calls it) into plain lists of events; `reduce` is
arithmetic on those lists and is what `benchmarks/tests` checks on the small
recorded trace kept there.

What a TPU v5e trace holds (looked at by hand, PR 26): plane
`/device:TPU:0` with the lines `XLA Modules` (one event per program
execution, named `jit_<function>(<hash>)`) and `XLA Ops` (one event per HLO
instruction executed, loops and conditionals as events that contain their
bodies'), and plane `/host:CPU` with one line per thread, `python` among
them (one event per Python call, named `$file.py:line function`). All on one
clock, in nanoseconds.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
CONTAINERS = ("%while", "%conditional", "%call")
# Python frames that only wait: never what the host was "doing".
WAITS = {"select", "run", "wait", "_worker", "acquire", "run_forever",
         "_run_once", "run_until_complete", "_bootstrap", "_bootstrap_inner"}


def extract(path: str) -> dict:
    """The events of one `.xplane.pb`: `modules` and `ops` of each device
    as [name, start_ns, duration_ns], `host` as [line, name, start_ns,
    duration_ns] for every thread's line (`python` holds the Python calls
    where the Python tracer was on)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": [], "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"plane": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            out["devices"].append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(
                    [line.name, e.name, e.start_ns, e.duration_ns]
                    for e in line.events)
    return out


def short(name: str) -> str:
    """`%fusion.5 = bf16[16,25,64]{...} fusion(...)` -> `%fusion.5
    bf16[16,25,64]`; a module's `jit_f(123)` -> `jit_f`."""
    if " = " in name:
        head, rest = name.split(" = ", 1)
        shape = rest.split("{", 1)[0].split(" ", 1)[0]
        return f"{head} {shape}"[:96]
    return re.sub(r"\(\d+\)$", "", name)


def union_ns(intervals) -> tuple:
    """Total length of the union of [start, end) intervals, and the merged
    intervals themselves."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


class _Host:
    """The host's events, indexed to answer what it was doing at a time."""

    def __init__(self, host):
        self.lines = {}
        for line, name, start, dur in host:
            self.lines.setdefault(line, []).append((start, start + dur, name))
        for evs in self.lines.values():
            evs.sort()
        self.starts = {k: [e[0] for e in v] for k, v in self.lines.items()}

    def label(self, t: float) -> str:
        """The innermost Python call that spans `t` (the one that started
        last), else another thread line's event, else nothing known."""
        for line in sorted(self.lines, key=lambda k: k != "python"):
            evs = self.lines[line]
            i = bisect.bisect_right(self.starts[line], t)
            for start, end, name in reversed(evs[max(0, i - 4000):i]):
                if end > t and name.rsplit(" ", 1)[-1] not in WAITS:
                    return name
        return "host: nothing recorded"


def reduce(events: dict) -> dict:
    """Busy and idle time, time per program, every loop's entries, every
    device operation's time and the idle gaps by what the host was doing,
    each list longest first and whole: what to print of them, and which
    loop's entries are a model's decode steps, is the caller's to say.
    Times and entries of several devices are averaged."""
    devices = [d for d in events["devices"] if d["modules"] or d["ops"]]
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "programs": {},
                "loops": [], "device_ops": [], "idle_gaps": []}
    every = [(s, s + d) for dev in devices
             for _, s, d in dev["modules"] + dev["ops"]]
    lo, hi = min(a for a, _ in every), max(b for _, b in every)
    # The traced window runs from the return of start_trace to the call of
    # stop_trace where the host's Python line shows them (the profiler's
    # own start and stop are not the program's idle time), else over the
    # device's events.
    for _, name, s, d in events["host"]:
        if name.endswith(" start_trace"):
            lo = min(lo, s + d)
        elif name.endswith(" stop_trace"):
            hi = max(hi, s)
    busy, programs = [], collections.Counter()
    ops, whiles = collections.Counter(), collections.Counter()
    gaps, host = collections.Counter(), _Host(events["host"])
    for dev in devices:
        # A loop's event contains its body's: the union counts time once.
        total, merged = union_ns(
            (max(s, lo), min(s + d, hi))
            for _, s, d in (dev["ops"] or dev["modules"]) if s + d > lo and s < hi)
        busy.append(total)
        for name, _, d in dev["modules"]:
            programs[short(name)] += d / 1e9 / len(devices)
        for name, _, d in dev["ops"]:
            if name.startswith(CONTAINERS):
                if name.startswith("%while"):
                    whiles[name] += 1
            else:
                ops[short(name)] += d / 1e9 / len(devices)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        # Label the long gaps (the sum of the rest goes under one name).
        idle.sort(key=lambda g: g[0] - g[1])
        for a, b in idle[:200]:
            gaps[host.label((a + b) / 2)] += (
                (b - a) / 1e9 / len(devices))
        rest = sum(b - a for a, b in idle[200:])
        if rest:
            gaps["gaps beyond the 200 longest"] += rest / 1e9 / len(devices)
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "programs": dict(programs),
        # One entry per loop instruction (two programs may both have a
        # `%while.2`: told apart by the whole instruction, printed short).
        "loops": [[short(n), c / len(devices)]
                  for n, c in whiles.most_common()],
        "device_ops": [[n, s] for n, s in ops.most_common()],
        "idle_gaps": [[n, s] for n, s in gaps.most_common()],
    }


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir: str) -> dict:
    return reduce(extract(find(trace_dir)))


def record(events: dict, start_ns: float, length_ns: float) -> dict:
    """A slice of `events` small enough to keep beside the tests: the
    events that lie wholly inside [start, start + length)."""
    end = start_ns + length_ns

    def inside(s, d):
        return s >= start_ns and s + d <= end

    return {
        "devices": [{
            "plane": dev["plane"],
            "modules": [e for e in dev["modules"] if inside(e[1], e[2])],
            "ops": [[short(e[0]), e[1], e[2]] for e in dev["ops"]
                    if inside(e[1], e[2])],
        } for dev in events["devices"]],
        "host": [e for e in events["host"] if inside(e[2], e[3])],
    }
