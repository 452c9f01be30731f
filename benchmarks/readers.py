"""Readers of the per-layer metrics. A metric's file
(`benchmarks/layer_metrics/<name>.json`) names one reader and its arguments;
a reader takes what a traced run gathered (`ctx`) and returns a number, or
None where it finds nothing to read, and the harness then leaves the metric
out of the result.

A reader's name is looked up in `READERS` below first, then as a file of
its own, `benchmarks/layer_readers/<reader>.py` with a `read(args, ctx)`:
a later reader is a new file there, and nothing here is edited for it.

`ctx` holds: `outcomes` (the client's view of every request), `seconds`,
`tokens_in_window`, `marked` and `collected` (the server's /metrics,
/healthz and window percentiles when the window opened and after its last
request was drained), `trace` (what `benchmarks/trace.py` reduced the
profiler's trace to, with `span_counters`, the growth of the server's
counters over the traced span) and `trace_span` (its start and end on the
client's clock), `config`, `cell`, `traffic_spec`, `device`.
"""

from __future__ import annotations

import importlib
import os
import re

from benchmarks import families, roofline, stats

READERS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "layer_readers")


def generator_lateness(args: dict, ctx: dict):
    """Percentile of (sent - due) over the requests, in milliseconds."""
    late = [o.sent - o.due for o in ctx["outcomes"] if o.sent is not None]
    if not late:
        return None
    return 1000.0 * stats.percentile(late, float(args["percentile"]))


def client_latency(args: dict, ctx: dict):
    """A percentile (or, with `percentile` "mean", the mean) of the client's
    own view, due -> first chunk (`ttft`) or due -> last token (`answer`),
    in milliseconds; a failed request counts as the client's deadline."""
    vals = []
    for o in ctx["outcomes"]:
        t = o.first if args["which"] == "ttft" else o.last
        vals.append(ctx["deadline_s"] if o.error or t is None else t - o.due)
    if not vals:
        return None
    if args["percentile"] == "mean":
        return 1000.0 * sum(vals) / len(vals)
    return 1000.0 * stats.percentile(vals, float(args["percentile"]))


def metrics_histogram(args: dict, ctx: dict):
    """A percentile (50 or 95) of one of the server's latency histograms
    over the measured window, scaled."""
    doc = ctx["collected"]["window"].get(args["histogram"], {})
    value = doc.get(f"p{int(args['percentile'])}_s")
    return None if value is None else value * float(args.get("scale", 1.0))


def _between(ctx: dict, section: str, pick) -> tuple:
    """(at the window's mark, at the collection after the drain) of a value
    `pick` takes from a section of the server's /metrics."""
    then = pick(ctx["marked"].get("metrics", {}).get(section, {}))
    now = pick(ctx["collected"]["metrics"].get(section, {}))
    return then, now


def counter_share(args: dict, ctx: dict):
    """A counter's growth between the mark and the collection, as a share of
    the growth of the total it is a part of. /metrics has the counter
    (`prefix_cache_hit_tokens`) and the cumulative ratio counter / total
    (`prefix_cache_hit_rate`) but no counter of the total, so the total at
    either end is counter / ratio. Where the ratio at the mark is still 0
    the total before the window is not known and is taken as 0."""
    h0, h1 = _between(ctx, "counters", lambda c: c.get(args["counter"], 0))
    r0, r1 = _between(ctx, "gauges", lambda g: g.get(args["ratio_gauge"]))
    if not r1 or h1 <= h0:
        return 0.0 if r1 is not None else None
    total = h1 / r1 - (h0 / r0 if r0 else 0.0)
    return float(args.get("scale", 1.0)) * (h1 - h0) / total


def histogram_counts_per_token(args: dict, ctx: dict):
    """Observations added, between the mark and the collection, to the
    server's histograms whose name starts with `prefix` (one per program
    the engine dispatched: `engine_prog_*`), per token delivered to the
    clients in the same span (every token of the window's requests)."""
    def count(latency):
        return sum(h.get("count", 0) for name, h in latency.items()
                   if name.startswith(args["prefix"]))

    then, now = _between(ctx, "latency", count)
    tokens = sum(o.tokens for o in ctx["outcomes"])
    if not tokens or now <= then:
        return None
    return (now - then) / tokens


def span_tokens(ctx: dict):
    """(tokens, sum of their context lengths, seconds) of the traced span,
    from the client's chunk times. A chunk's tokens were made one by one
    since the stream's previous chunk, so they are spread evenly over that
    time (the first chunk's over as long as the later ones took per token):
    the engine hands tokens over in bursts, and a span of a few seconds
    would otherwise gain or lose a burst at either end. A token's context
    is the prompt as served (query and template, cut to the engine's
    `max_prompt_tokens`) plus the answer before it."""
    if not ctx.get("trace_span"):
        return None
    a, b = ctx["trace_span"]
    template = int(ctx["traffic_spec"]["template_tokens"])
    cut = int(ctx["config"]["serving"]["max_prompt_tokens"])
    tokens = context = 0.0
    for o in ctx["outcomes"]:
        times = o.token_times
        if not times:
            continue
        total = sum(n for _, n in times)
        first_t, first_n = times[0]
        if len(times) > 1:
            pace = (times[-1][0] - first_t) / (total - first_n)
        else:
            pace = (first_t - o.sent) / first_n
        prompt = min(o.query_tokens + template, cut)
        lo, offset = first_t - pace * first_n, 0
        for t, n in times:
            if t > lo:
                share = max(0.0, min(t, b) - max(lo, a)) / (t - lo)
                tokens += n * share
                context += n * share * (prompt + offset + (n - 1) / 2.0)
            lo, offset = t, offset + n
    return tokens, context, b - a


def _program_seconds(ctx: dict, pattern: str):
    trace = ctx["trace"]
    if not trace:
        return None
    rx = re.compile(pattern)
    found = [s for name, s in trace["programs"].items() if rx.search(name)]
    return sum(found) if found else None


def trace_program_time(args: dict, ctx: dict):
    """Device time of the programs whose name matches `programs` per token
    the clients received, both within the traced span. Microseconds."""
    busy = _program_seconds(ctx, args["programs"])
    span = span_tokens(ctx)
    if busy is None or not span or not span[0]:
        return None
    tokens, _, seconds = span
    return 1e6 * (busy / ctx["trace"]["window_s"]) / (tokens / seconds)


def trace_idle(args: dict, ctx: dict):
    """Share of the traced span in which no operation ran on the device."""
    trace = ctx["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def roofline_share(args: dict, ctx: dict):
    """Least time the chip could take for the decode steps of the traced
    span over the device time of the programs that ran them, in percent.
    What a step costs, and which of the trace's counts is the steps, is the
    configuration's family's to say (`families/<family>/roofline.py`); the
    slot-tokens the steps advanced and the context each attended over are
    the clients' (`span_tokens`). The family's count goes on the `notes`
    line also where the trace has no device time to hold it against."""
    trace = ctx["trace"]
    span = span_tokens(ctx)
    if not trace or not span or not span[0]:
        return None
    tokens, context, seconds = span
    # Scaled to the traced window's own length (where the trace holds no
    # device event, a rehearsal, the client's span stands).
    slot_steps = tokens / seconds * (trace["window_s"] or seconds)
    cost = families.of_config(ctx["config"], ("roofline",)).roofline.cost(
        ctx["config"], trace, slot_steps, context / tokens)
    if not cost:
        return None
    busy = _program_seconds(ctx, args["programs"])
    note = dict(cost, slot_steps=slot_steps, mean_context=context / tokens,
                device_s=busy)
    ctx.setdefault("notes", {})[args.get("note", "roofline")] = note
    if not busy:
        return None
    least = roofline.least_seconds(cost, ctx["device"]["kind"])
    note.update(least)
    return 100.0 * least["seconds"] / busy


READERS = {
    "generator_lateness": generator_lateness,
    "client_latency": client_latency,
    "metrics_histogram": metrics_histogram,
    "counter_share": counter_share,
    "histogram_counts_per_token": histogram_counts_per_token,
    "trace_program_time": trace_program_time,
    "trace_idle": trace_idle,
    "roofline": roofline_share,
}


def in_files() -> list:
    """The readers that are files of their own."""
    return sorted(f[:-3] for f in os.listdir(READERS_DIR)
                  if f.endswith(".py") and f != "__init__.py")


def resolve(reader: str):
    """The function a reader's name stands for: `READERS`, then
    `benchmarks/layer_readers/<reader>.py`."""
    if reader in READERS:
        return READERS[reader]
    if reader not in in_files():
        raise KeyError(f"no reader is called {reader!r}: "
                       f"benchmarks/readers.py has {sorted(READERS)}, "
                       f"benchmarks/layer_readers has {in_files()}")
    return importlib.import_module(f"benchmarks.layer_readers.{reader}").read


def read(reader: str, args: dict, ctx: dict):
    return resolve(reader)(args, ctx)
