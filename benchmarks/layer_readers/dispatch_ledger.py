"""What a one-chunk dispatch costs the device, fitted over every such
dispatch the program's dispatch ledger timed from the window's mark to the
collection after its drain.

    {"reader": "dispatch_ledger", "args": {"of": "iteration"}}

The program (engine/spans.py `DispatchLedger`) reads each megastep's device
time on the host's clock, two completions apart, and keeps over the timed
dispatches of ONE chunk the sums least squares needs for

    device_us = b + n x narrow passes + w x wide passes

as counters of /metrics: `engine_timed_dispatches`, `_iterations`,
`_device_us`, `_narrow_passes`, `_wide_passes`, `_narrow_sq`, `_wide_sq`,
`_narrow_x_wide`, `_us_x_narrow`, `_us_x_wide`. This reader differences
them (as `counter_ratio` does) and solves the normal equations in plain
Python. `of` picks the cost:

- `iteration`: b x dispatches / iterations, microseconds: a scan iteration
  with nothing staged, the dispatch's entry and exit spread over its
  iterations;
- `narrow_pass`, `wide_pass`: n and w in milliseconds: what a prefill pass
  of one row and a pass of four add to a dispatch, the admission's own
  small programs with them.

A column whose sum of squares did not grow (a window that never ran such a
pass) is left out of the fit, and its cost is nothing to read. Nothing at
all where the series are absent (a program without the ledger), where
fewer than `min_dispatches` (20) dispatches were timed, or where the
equations are singular (every dispatch ran the same passes).
"""

from __future__ import annotations

PREFIX = "engine_timed_"
COLUMNS = ("narrow_pass", "wide_pass")


def solve(rows: list, rhs: list):
    """x of `rows` x = `rhs` by elimination with partial pivoting, or None
    where a pivot vanishes beside its column's largest entry."""
    n = len(rhs)
    a = [list(map(float, row)) + [float(y)] for row, y in zip(rows, rhs)]
    scale = [max(abs(v) for v in row[:n]) or 1.0 for row in a]
    for col in range(n):
        pivot = max(range(col, n), key=lambda r: abs(a[r][col]) / scale[r])
        if abs(a[pivot][col]) <= 1e-9 * scale[pivot]:
            return None
        a[col], a[pivot] = a[pivot], a[col]
        scale[col], scale[pivot] = scale[pivot], scale[col]
        for r in range(col + 1, n):
            f = a[r][col] / a[col][col]
            for c in range(col, n + 1):
                a[r][c] -= f * a[col][c]
    x = [0.0] * n
    for r in reversed(range(n)):
        x[r] = (a[r][n] - sum(a[r][c] * x[c] for c in range(r + 1, n))
                ) / a[r][r]
    return x


def fit(grew: dict):
    """{"iteration": b, "narrow_pass": n, "wide_pass": w} in microseconds a
    dispatch, a pass and a pass, from the growth of the `engine_timed_*`
    sums (keyed without the prefix); a cost whose column did not grow is
    left out (the program puts a counter into /metrics at its first
    increment, so a sum that is not there is 0). None where the equations
    are singular."""
    def grown(key):
        return grew.get(key, 0)

    sums = {"narrow_pass": grown("narrow_passes"),
            "wide_pass": grown("wide_passes")}
    squares = {"narrow_pass": grown("narrow_sq"),
               "wide_pass": grown("wide_sq")}
    with_us = {"narrow_pass": grown("us_x_narrow"),
               "wide_pass": grown("us_x_wide")}
    cols = [c for c in COLUMNS if squares[c] > 0]
    rows = [[grown("dispatches")] + [sums[c] for c in cols]]
    for c in cols:
        rows.append([sums[c]] + [
            squares[c] if d == c else grown("narrow_x_wide") for d in cols])
    x = solve(rows, [grown("device_us")] + [with_us[c] for c in cols])
    return None if x is None else dict(zip(["iteration"] + cols, x))


def read(args: dict, ctx: dict):
    then = ctx["marked"].get("metrics", {}).get("counters", {})
    now = ctx["collected"]["metrics"].get("counters", {})
    if PREFIX + "dispatches" not in now:
        return None
    grew = {k[len(PREFIX):]: v - then.get(k, 0) for k, v in now.items()
            if k.startswith(PREFIX)}
    iterations = grew.get("iterations", 0)
    if (grew["dispatches"] < int(args.get("min_dispatches", 20))
            or iterations <= 0):
        return None
    costs = fit(grew)
    if costs is None or args["of"] not in costs:
        return None
    if args["of"] == "iteration":
        return costs["iteration"] * grew["dispatches"] / iterations
    return costs[args["of"]] / 1000.0
