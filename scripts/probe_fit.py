#!/usr/bin/env python
"""What a routed layer's grouped products cost on the device over a prefix
of a pass's sorted picks, by the prefix (PERF.md section 6 keeps the
readings; `models/moe.py` `held_rows` is what they size).

A shape is a cell's routed layers alone: `layers` layers of `held` expert
stacks at the widths the family holds them, `lanes` tokens of `picks` picks
among `among` experts, drawn from the seed by a router that is fair to the
share. The layers are `moe.grouped_swiglu` / `moe.grouped_relu2` themselves
(the sort, the `lax.cond`, its fallback over every row), `--iters` passes in
one scan so that a call is long beside its dispatch, each pass with picks of
its own; `held_rows` is replaced by each prefix in turn, and by the rows
themselves for the program without a `cond`. Every output is compared with
that program's to the bit, one more call's too whose router sends the share
more picks than any prefix holds (the fallback). A call is repeated
`--calls` times and every reading printed, so that run-to-run levels show.

Stacks are arguments of the jitted call, never closed over (a closed-over
stack is embedded as a constant: minutes of compiling a 2 GB program). One
JSON line a shape on stdout; `platform` says where it ran, and only a TPU's
line is a measurement.

    chiprun -- python scripts/probe_fit.py --shape kimi-linear-decode
    JAX_PLATFORMS=cpu python scripts/probe_fit.py --platform cpu --small
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

# name -> (layers, held, among, D, M, projections, lanes, picks, prefixes):
# D and M as the family pads its stacks (`pad_experts`), `lanes` the tokens
# of the pass, and the prefixes to read beside the pass's own rows.
SHAPES = {
    # kimi-linear.notes-herd's decode row: 16 lanes x 8 picks of 256.
    "kimi-linear-decode": (8, 64, 256, 2560, 1024, 3, 16, 8,
                           (48, 64, 80, 96, 112)),
    # ax-k1.notes-crowd's prefill pass of four rows of 32 positions.
    "ax-k1-wide-pass": (4, 12, 192, 7168, 2048, 3, 128, 8, (160, 256)),
    # ax-k1.notes-crowd's decode row: 32 lanes x 8 picks of 192.
    "ax-k1-decode": (4, 12, 192, 7168, 2048, 3, 32, 8, (48, 64)),
    # nemotron3-nano.notes-herd's decode row: 16 lanes x 6 picks of 128.
    "nemotron3-nano-decode": (4, 64, 128, 3072, 2048, 2, 16, 6, (80,)),
}


def draw_picks(rng, passes, layers, lanes, picks, among, held, biased):
    """int32 [passes, layers, lanes, picks]: each token's picks distinct
    among `among` experts, uniform (a router fair to the share), or with
    `biased` every pick on the share held (more than any prefix holds)."""
    import numpy as np

    reach = held if biased else among
    keys = rng.random((passes, layers, lanes, reach))
    return np.argsort(keys, axis=-1)[..., :picks].astype(np.int32)


def build(projections, among, fit):
    """The jitted call with `held_rows` giving `fit` (None: every row):
    (xs [P, S, D], picks [P, L, S, k], stacks) -> (x after the last layer
    [P, S, D], held experts' group sizes [P, L, E])."""
    import jax
    import jax.numpy as jnp

    from distributed_lms_raft_llm_tpu.models import moe

    grouped = moe.grouped_swiglu if projections == 3 else moe.grouped_relu2

    def one_pass(stacks, x, top_i):
        live = jnp.ones((x.shape[0],), bool)
        top_w = jnp.full(top_i.shape[1:], 1.0 / top_i.shape[-1], jnp.float32)
        sizes = []
        for layer, ws in enumerate(zip(*stacks)):
            y, n = grouped(x, top_i[layer], top_w, live, *ws, first=0,
                           among=among)
            x = x + y
            # Keep 240 layers in a row finite: unit rows, as a norm would.
            x = (x * jax.lax.rsqrt(jnp.mean(
                jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
            ).astype(x.dtype))
            sizes.append(n)
        return x, jnp.stack(sizes)

    def call(xs, picks, stacks):
        def body(_, xp):
            return None, one_pass(stacks, *xp)
        return jax.lax.scan(body, None, (xs, picks))[1]

    def traced(*args):
        # `_grouped` asks the module for the prefix when it is traced.
        real = moe.held_rows
        moe.held_rows = lambda rows, held, among_: rows if fit is None else fit
        try:
            return call(*args)
        finally:
            moe.held_rows = real

    return jax.jit(traced)


def timed(fn, args, calls):
    """Milliseconds of `calls` calls after the one that compiles, and the
    last call's result."""
    import jax

    out = []
    for i in range(calls + 1):
        t = time.perf_counter()
        got = jax.block_until_ready(fn(*args))
        if i:
            out.append(1e3 * (time.perf_counter() - t))
    return out, got


def probe(name, args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    layers, held, among, d, m, projections, lanes, picks, prefixes = (
        SHAPES[name])
    if args.small:
        layers, d, m = 2, 128, 64
    rows = lanes * picks
    rng = np.random.default_rng(args.seed)
    keys = jax.random.split(jax.random.key(args.seed), layers * projections)

    def stack(key, shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    shapes = [(held, d, m)] * (projections - 1) + [(held, m, d)]
    stacks = tuple(
        [stack(keys[layer * projections + j], shape)
         for layer in range(layers)] for j, shape in enumerate(shapes))
    xs = jnp.asarray(rng.standard_normal((args.iters, lanes, d)),
                     jnp.bfloat16)
    fair = draw_picks(rng, args.iters, layers, lanes, picks, among, held,
                      False)
    biased = draw_picks(rng, args.iters, layers, lanes, picks, among, held,
                        True)
    held_picks = (fair < held).sum(axis=(2, 3))

    whole = build(projections, among, None)
    ms, want = timed(whole, (xs, fair, stacks), args.calls)
    _, want_biased = timed(whole, (xs, biased, stacks), 0)
    assert int(np.asarray(want_biased[1]).sum(-1).min()) == rows
    readings = [{"prefix": rows, "cond": False, "ms": ms,
                 "pass_ms": statistics.median(ms) / args.iters}]
    for fit in prefixes:
        fn = build(projections, among, fit)
        ms, got = timed(fn, (xs, fair, stacks), args.calls)
        ms_biased, got_biased = timed(fn, (xs, biased, stacks), 1)
        same = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for pair in ((got, want), (got_biased, want_biased))
                   for a, b in zip(*pair))
        readings.append({
            "prefix": fit, "cond": True, "ms": ms,
            "pass_ms": statistics.median(ms) / args.iters,
            "passes_that_fit": int((held_picks <= fit).sum()),
            "fallback_pass_ms": ms_biased[0] / args.iters,
            "equal_to_the_bit": same,
        })
    base = readings[0]["pass_ms"]
    for r in readings:
        r["against_all_rows"] = r["pass_ms"] / base - 1.0
    dev = jax.devices()[0]
    return {
        "line": "probe_fit", "shape": name, "platform": dev.platform,
        "device_kind": dev.device_kind, "seed": args.seed,
        "small": args.small, "layers": layers, "held": held,
        "among": among, "stack": [d, m], "projections": projections,
        "rows": rows, "iters": args.iters, "passes": args.iters * layers,
        "held_picks_mean": float(held_picks.mean()),
        "held_picks_max": int(held_picks.max()), "readings": readings,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES),
                    help="once a shape (default: every shape)")
    ap.add_argument("--seed", type=int, default=4800000011)
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--iters", type=int, default=30,
                    help="passes in one call's scan")
    ap.add_argument("--small", action="store_true",
                    help="two layers of narrow stacks: the CPU's rehearsal")
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"])
    args = ap.parse_args(argv)

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform != args.platform:
        print(f"JAX initialised {platform!r}, the probe asked for "
              f"{args.platform!r}", file=sys.stderr)
        return 3
    ok = True
    for name in args.shape or sorted(SHAPES):
        line = probe(name, args)
        ok &= all(r.get("equal_to_the_bit", True) for r in line["readings"])
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
