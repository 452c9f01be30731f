"""Device time of the operations of a traced span whose name matches a
pattern: per token the clients received, or as the share of a floor.

    {"reader": "trace_op_time",
     "args": {"ops": "ragged-dot", "per": "token"}}
    {"reader": "trace_op_time",
     "args": {"ops": "ragged-dot", "floor": "experts_cost"}}

`ctx["trace"]["device_ops"]` has every operation's name and seconds
(`benchmarks/trace.py`: `%ragged-dot-none.3 f32[128,1024]`); `ops` is a
regular expression searched in the name. With `per: token` the result is
microseconds of those operations per token delivered within the span
(`readers.span_tokens`). With `floor`, a function of the configuration's
family (`families/<family>/roofline.py`, same signature as `cost`) says the
bytes and operations that work needs, and the result is its least time
(`roofline.least_seconds`) over the device time, in percent. Nothing where
the trace has no such operation (another model, a CPU rehearsal) or the
family has no such function.
"""

from __future__ import annotations

import re

from benchmarks import families, readers, roofline


def read(args: dict, ctx: dict):
    trace = ctx.get("trace")
    span = readers.span_tokens(ctx)
    if not trace or not span or not span[0]:
        return None
    rx = re.compile(args["ops"])
    busy = sum(s for name, s in trace.get("device_ops", []) if rx.search(name))
    if not busy:
        return None
    tokens, context, seconds = span
    window = trace["window_s"] or seconds
    if "floor" not in args:
        return 1e6 * (busy / window) / (tokens / seconds)
    fam = families.of_config(ctx["config"], ("roofline",)).roofline
    floor = getattr(fam, args["floor"], None)
    if floor is None:
        return None
    cost = floor(ctx["config"], trace, tokens / seconds * window,
                 context / tokens)
    if not cost:
        return None
    least = roofline.least_seconds(cost, ctx["device"]["kind"])
    ctx.setdefault("notes", {})[args.get("note", args["floor"])] = dict(
        least, device_s=busy)
    return 100.0 * least["seconds"] / busy
