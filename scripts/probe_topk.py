#!/usr/bin/env python
"""What the sampler's exact top-k costs on the device over the whole row and
over groups, by the group's size (PERF.md section 6, PR 56 keeps the table;
`engine/sampling.py` `group_size` is the rule it gave).

A shape is a cell's decode row, [slots, vocabulary]. A reading is a form of
the selection: `jax.lax.top_k` over the row, and `sampling.grouped_top_k`
at each of `--groups` (the candidates it leaves go through the rule, as
served). Each runs behind the sampler's own producer (the
repetition penalty and the temperature, which the compiler fuses into
whatever reads the logits first), `--iters` times in one scan, each with a
temperature of its own so that nothing is hoisted, and a call is repeated
`--calls` times. `rows_apart` is the first token's form: `--apart` rows,
each selected on its own under `jax.vmap` (`engine/paged.py`
`_admission_chunk`). Every form's values and indices are compared with
`jax.lax.top_k`'s on float32 noise, on bfloat16-rounded and on
integer-rounded logits (ties across groups and at the k-th value; -0.0
beside 0.0), on the device that timed it.

One JSON line a reading on stdout; `platform` says where it ran, and only a
TPU's line is a measurement.

    chiprun -- python scripts/probe_topk.py
    JAX_PLATFORMS=cpu python scripts/probe_topk.py --platform cpu \\
        --shapes 8x20480 --iters 2 --calls 1
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# The six cells' decode rows (benchmarks/configs/*.json: slots, and the
# preset's vocabulary), in BENCHMARK.json's order.
SERVED = ("16x50257,16x200192,32x20480,16x65536,16x40960,48x73448")
K = 50


def build(select, apart):
    import jax
    import jax.numpy as jnp

    from distributed_lms_raft_llm_tpu.engine import sampling

    def whole(x, seen, temperature):
        logits = sampling.apply_repetition_penalty(x, seen, 1.2)
        return select(logits / temperature)

    def rows_apart(x, seen, temperature):
        return jax.vmap(
            lambda r, s: whole(r[None], s[None], temperature))(x, seen)

    one = rows_apart if apart else whole

    def call(x, seen, temperatures):
        return jax.lax.scan(
            lambda _, t: (None, one(x, seen, t)), None, temperatures)[1]

    return jax.jit(call), jax.jit(
        lambda x: one(x, jnp.zeros(x.shape, bool), 1.0))


def timed(fn, args, calls):
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    walls = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append(time.perf_counter() - t0)
    return first, walls


def probe(shape, args, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lms_raft_llm_tpu.engine import sampling

    b, v = (int(a) for a in shape.split("x"))
    rng = np.random.default_rng(args.seed)
    noise = rng.normal(size=(b, v)).astype(np.float32) * 3
    rows = {
        "float32": jnp.asarray(noise),
        "bfloat16_rounded": jnp.asarray(noise).astype(
            jnp.bfloat16).astype(jnp.float32),
        "integer_rounded": jnp.asarray(np.round(noise / 3)),
    }
    seen = jnp.asarray(rng.random((b, v)) < 0.001)
    temperatures = jnp.asarray(
        0.7 * (1.0 + 1e-3 * np.arange(args.iters)), jnp.float32)
    forms = [("one_stage", 0, lambda x: jax.lax.top_k(x, K))]
    forms += [
        ("grouped", g,
         lambda x, g=g: sampling.grouped_top_k(x, K, g))
        for g in args.groups if -(-v // g) >= K]
    want = {}
    for apart in dict.fromkeys((0, args.apart)):
        take = slice(0, apart) if apart else slice(None)
        for name, g, select in forms:
            call, once = build(select, apart)
            first, walls = timed(
                call, (rows["float32"][take], seen[take], temperatures),
                args.calls)
            exact = {}
            for kind, x in rows.items():
                vals, idx = (np.asarray(a).reshape(-1, K)
                             for a in once(x[take]))
                if name == "one_stage":
                    want[kind, apart] = vals, idx
                exact[kind] = bool(
                    np.array_equal(vals, want[kind, apart][0])
                    and np.array_equal(idx, want[kind, apart][1]))
            us = [w / args.iters * 1e6 for w in walls]
            print(json.dumps({
                "line": "probe_topk", "platform": dev.platform,
                "device_kind": dev.device_kind, "seed": args.seed,
                "rows": apart or b, "vocabulary": v, "k": K,
                "rows_apart": bool(apart), "form": name, "group": g,
                "selected": -(-v // g) + K * g if g else v,
                "rule_group": sampling.group_size(v, K),
                "us_a_pass": [round(u, 1) for u in us],
                "median_us": round(statistics.median(us), 1),
                "first_call_s": round(first, 2), "iters": args.iters,
                "exact": exact,
            }), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=SERVED,
                    help="comma-separated <rows>x<vocabulary>")
    ap.add_argument("--groups", default="16,32,64,128",
                    type=lambda s: [int(a) for a in s.split(",")])
    ap.add_argument("--apart", type=int, default=4,
                    help="rows of the first token's form (0: leave it out)")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=56)
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"])
    args = ap.parse_args(argv)

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if dev.platform != args.platform:
        print(f"JAX initialised {dev.platform!r}, the probe asked for "
              f"{args.platform!r}", file=sys.stderr)
        return 2
    for shape in args.shapes.split(","):
        probe(shape, args, dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
