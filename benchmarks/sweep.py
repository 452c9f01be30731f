#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, once, on the chip:

    python benchmarks/sweep.py --workload <an open-loop cell> --rates 2,2.5,3,3.5,4 --seconds 40

One server start; each rate is offered for `--seconds` and drained. A rate
is sustained when nothing failed or was shed, the answers of the window's
second half took no more than 1.25 times those of its first and the requests
open when the window closed are no more than 1.5 times those open at its
middle plus 4 (no backlog growing over the window), and the median answer
took no more than 1.5 times that of the lowest rate tried (the queue has not
tipped into a standing backlog). The knee is the highest sustained rate
below the first that is not. The cell then runs at 4/5 of it: write that
number into the cell's file as `rate_per_s`, and the lines into PERF.md.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import statistics
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


async def sweep(args) -> int:
    from benchmarks import run as run_lib, stats, traffic as traffic_lib

    cell = run_lib.load_json("workloads", args.workload + ".json")
    config = run_lib.load_json("configs", cell["config"] + ".json")
    spec = run_lib.load_json("traffic", cell["traffic"] + ".json")
    if spec["generator"] != "open_loop":
        raise run_lib.RunFailure("only an open loop has a rate to sweep")
    max_new = int(config["serving"]["sampling"]["max_new_tokens"])
    words = traffic_lib.Words()
    workdir = tempfile.mkdtemp(prefix="lms_sweep_")
    child = run_lib.Child(workdir)
    sustained, base_p50, broken = [], None, False
    try:
        await child.start(os.path.join(HERE, "configs",
                                       cell["config"] + ".json"),
                          args.seed, args.platform)
        ready = await child.read("ready", run_lib.READY_TIMEOUT_S)
        run_lib.say("ready", phases=ready["phases"], device=ready["device"])
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic = traffic_lib.Traffic(
                spec, dict(cell, rate_per_s=rate), args.seed + i,
                args.seconds, int(config["serving"]["max_prompt_tokens"]),
                words)
            before = await child.ask("mark", "marked")
            outcomes, t0 = await run_lib.run_load(
                traffic, f"127.0.0.1:{ready['port']}", args.seconds,
                float(spec["client_deadline_s"]), max_new)
            after = await child.ask("collect", "collected")
            end = t0 + args.seconds
            failed = sum(1 for o in outcomes if o.error)
            open_at_end = sum(1 for o in outcomes
                              if o.last is None or o.last > end)
            mid = t0 + args.seconds / 2
            open_at_mid = sum(1 for o in outcomes if o.due <= mid and (
                o.last is None or o.last > mid))
            lat = [(o.due, o.last - o.due) for o in outcomes if not o.error]
            half = t0 + args.seconds / 2
            first = [v for d, v in lat if d < half]
            second = [v for d, v in lat if d >= half]
            growth = (statistics.mean(second) / statistics.mean(first)
                      if first and second else None)
            shed = sum(
                v - before["metrics"].get("counters", {}).get(k, 0)
                for k, v in after["metrics"].get("counters", {}).items()
                if k.startswith("shed_"))
            p50 = stats.percentile([v for _, v in lat], 50) if lat else None
            base_p50 = base_p50 if base_p50 is not None else p50
            ok = (failed == 0 and shed == 0 and p50 is not None
                  and growth is not None and growth <= 1.25
                  and open_at_end <= 1.5 * open_at_mid + 4
                  and p50 <= 1.5 * base_p50)
            broken = broken or not ok
            if ok and not broken:
                sustained.append(rate)
            tokens = sum(n for o in outcomes for t, n in o.token_times
                         if t <= end)
            run_lib.say(
                "rate", rate_per_s=rate, attempted=len(outcomes),
                failed=failed, shed=shed, open_at_window_end=open_at_end,
                open_at_window_middle=open_at_mid,
                completed_share=1.0 - open_at_end / max(1, len(outcomes)),
                second_half_over_first=growth, sustained=ok,
                out_tok_s=tokens / args.seconds,
                ttft_p95_ms=1000 * stats.percentile(
                    [(o.first or o.due + 120) - o.due for o in outcomes], 95),
                answer_p50_ms=1000 * stats.percentile(
                    [v for _, v in lat], 50) if lat else None,
                answer_p95_ms=1000 * stats.percentile(
                    [v for _, v in lat], 95) if lat else None,
                drain_s=max(o.last or end for o in outcomes) - end)
    finally:
        await child.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    knee = max(sustained) if sustained else None
    run_lib.say("knee", highest_sustained_rate=knee,
                four_fifths=None if knee is None else 0.8 * knee)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, rising")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=2147485001)
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"])
    args = ap.parse_args(argv)
    from benchmarks import run as run_lib

    try:
        return asyncio.run(sweep(args))
    except run_lib.RunFailure as e:
        print(f"sweep failed: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
