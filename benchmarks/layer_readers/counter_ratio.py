"""One sum of the server's series over another, both differenced from the
window's mark to the collection after its drain.

    {"reader": "counter_ratio",
     "args": {"numerator": [{"counter": "engine_tokens_emitted"},
                            {"histogram": "prefill_wait", "times": -1}],
              "denominator": [{"counter": "engine_lane_steps"}],
              "scale": 100.0}}

A term is a counter of /metrics, or the observations a histogram has taken
(`histogram`: its `count`), times `times` (1 where left out). The program
puts a counter into /metrics at its first increment, so a numerator's
series that is not there reads 0; where the denominator did not grow, or
one of its series is missing, there is nothing to read.
"""

from __future__ import annotations


def _value(term: dict, metrics: dict):
    if "counter" in term:
        return metrics.get("counters", {}).get(term["counter"])
    return metrics.get("latency", {}).get(term["histogram"], {}).get("count")


def _sum(terms: list, metrics: dict) -> float:
    return sum(float(term.get("times", 1)) * (_value(term, metrics) or 0)
               for term in terms)


def read(args: dict, ctx: dict):
    then = ctx["marked"].get("metrics", {})
    now = ctx["collected"]["metrics"]
    above, below = args["numerator"], args["denominator"]
    if any(_value(term, now) is None for term in below):
        return None
    grew = _sum(below, now) - _sum(below, then)
    if grew <= 0:
        return None
    return (float(args.get("scale", 1.0))
            * (_sum(above, now) - _sum(above, then)) / grew)
