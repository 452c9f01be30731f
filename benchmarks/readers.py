"""Readers of the per-layer metrics. A metric's file
(`benchmarks/layer_metrics/<name>.json`) names one reader and its arguments;
a reader takes what a traced run gathered (`ctx`) and returns a number, or
None where it finds nothing to read, and the harness then leaves the metric
out of the result.

`ctx` holds: `outcomes` (the client's view of every request), `seconds`,
`tokens_in_window`, `marked` and `collected` (the server's /metrics,
/healthz and window percentiles when the window opened and after its last
request was drained), `trace` (what `benchmarks/trace.py` reduced the
profiler's trace to) and `trace_span` (its start and end on the client's
clock), `config`, `cell`, `traffic_spec`, `device`.
"""

from __future__ import annotations

import re

from benchmarks import roofline, stats


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
    Steps are counted in the trace; the slot-tokens they advanced and the
    context each attended over are the clients' (`span_tokens`)."""
    trace = ctx["trace"]
    busy = _program_seconds(ctx, args["programs"])
    steps = (trace or {}).get("decode_steps")
    span = span_tokens(ctx)
    if not busy or not steps or not span or not span[0]:
        return None
    tokens, context, seconds = span
    slot_steps = tokens / seconds * trace["window_s"]
    least = roofline.decode_least_seconds(
        ctx["config"], ctx["device"]["kind"], steps, slot_steps,
        context / tokens)
    ctx.setdefault("notes", {})[args.get("note", "roofline")] = dict(
        least, steps=steps, slot_steps=slot_steps,
        mean_context=context / tokens, device_s=busy)
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


def read(reader: str, args: dict, ctx: dict):
    if reader not in READERS:
        raise KeyError(f"no reader is called {reader!r}: "
                       f"benchmarks/readers.py has {sorted(READERS)}")
    return READERS[reader](args, ctx)
