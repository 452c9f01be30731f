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


def _growth(terms: list, then: dict, now: dict, missing):
    """The terms' growth from `then` to `now`, summed; `missing` stands for
    a series /metrics does not have at the collection."""
    total = 0.0
    for term in terms:
        end = _value(term, now)
        if end is None:
            if missing is None:
                return None
            end = missing
        total += float(term.get("times", 1)) * (
            end - (_value(term, then) or 0))
    return total


def read(args: dict, ctx: dict):
    then = ctx["marked"].get("metrics", {})
    now = ctx["collected"]["metrics"]
    below = _growth(args["denominator"], then, now, None)
    if not below or below < 0:
        return None
    above = _growth(args["numerator"], then, now, 0)
    return float(args.get("scale", 1.0)) * above / below
