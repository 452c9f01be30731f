#!/usr/bin/env python
"""What a routed layer's grouped products cost on the device by the rows
they are handed: the prefix of a pass's sorted picks, and the row tile
(PERF.md section 6 keeps the readings; `models/moe.py` `held_rows` and
`tiled_rows` are what they size).

A shape is a cell's routed layers alone: `layers` layers of `held` expert
stacks at the widths the family holds them, `lanes` tokens of `picks` picks
among `among` experts, drawn from the seed by a router that is fair to the
share. The layers are `moe.grouped_swiglu` / `moe.grouped_relu2` themselves
(the sort, the `lax.cond`, its fallback over every row), `--iters` passes in
one scan so that a call is long beside its dispatch, each pass with picks of
its own. A reading is (prefix, tile): `held_rows` is replaced by the prefix
(None: the rows themselves, the program without a `cond`) and `tiled_rows`
by the least odd multiple of the tile that holds the rows (None: the rows
as they are, what the products were handed before PR 51; the TPU's kernel
tiles a row count by the largest power of two that divides it). The first
reading is every row as it is. Every output is compared with that
program's to the bit, one more call's too whose router sends the share
more picks than any prefix holds (the fallback). A call is repeated
`--calls` times and every reading printed, so that run-to-run levels show.

Stacks are arguments of the jitted call, never closed over (a closed-over
stack is embedded as a constant: minutes of compiling a 2 GB program). One
JSON line a shape on stdout; `platform` says where it ran, and only a TPU's
line is a measurement.

    chiprun -- python scripts/probe_fit.py --shape trinity-mini-decode
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

# name -> (layers, held, among, D, M, projections, lanes, picks, readings):
# D and M as the family pads its stacks (`pad_experts`), `lanes` the tokens
# of the pass, and the (prefix, tile) pairs to read beside the pass's own
# rows as they are.
TILES = ((None, 16), (None, 32), (None, 64))
SHAPES = {
    # trinity-mini.notes-herd's decode row: 16 lanes x 8 picks, all 128
    # held: 128 rows as they are (ONE tile), and handed 144, 160 and 192.
    "trinity-mini-decode": (4, 128, 128, 2048, 1024, 3, 16, 8, TILES),
    # Its prefill pass of four rows of 32 positions: 1,024 rows.
    "trinity-mini-wide-pass": (4, 128, 128, 2048, 1024, 3, 128, 8, TILES),
    # A long pass of the kind the start's reference check runs: 2,304
    # positions, 144 rows a group, 18,432 rows against 18,464.
    "trinity-mini-long-pass": (4, 128, 128, 2048, 1024, 3, 2304, 8,
                               ((None, 32),)),
    # Between them: 16 and 32 rows a group (256 and 512 positions).
    "trinity-mini-pass-of-256": (4, 128, 128, 2048, 1024, 3, 256, 8,
                                 ((None, 32), (None, 64))),
    "trinity-mini-pass-of-512": (4, 128, 128, 2048, 1024, 3, 512, 8,
                                 ((None, 32), (None, 64))),
    # kimi-linear.notes-herd's decode row: 16 lanes x 8 picks of 256; the
    # prefix of 96 stays, its fallback's 128 rows become 160.
    "kimi-linear-decode": (8, 64, 256, 2560, 1024, 3, 16, 8,
                           ((48, None), (64, None), (80, None), (96, None),
                            (112, None), (96, 32))),
    # Its prefill pass of four rows: a prefix of 432 (tile 16) against 480.
    "kimi-linear-wide-pass": (8, 64, 256, 2560, 1024, 3, 128, 8,
                              ((432, None), (432, 32))),
    # ax-k1.notes-crowd's prefill pass of four rows of 32 positions.
    "ax-k1-wide-pass": (4, 12, 192, 7168, 2048, 3, 128, 8,
                        ((160, None), (256, None))),
    # ax-k1.notes-crowd's decode row: 32 lanes x 8 picks of 192; the prefix
    # of 64 against 96, the fallback's 256 against 288.
    "ax-k1-decode": (4, 12, 192, 7168, 2048, 3, 32, 8,
                     ((48, None), (64, None), (64, 32))),
    # nemotron3-nano.notes-herd's decode row: 16 lanes x 6 picks of 128.
    "nemotron3-nano-decode": (4, 64, 128, 3072, 2048, 2, 16, 6,
                              ((80, None),)),
    # Its prefill pass of four rows, whole: 768 rows against 800.
    "nemotron3-nano-wide-pass": (4, 64, 128, 3072, 2048, 2, 128, 6,
                                 ((None, 32),)),
    # lfm2-8b-a1b.notes-hall's decode row: 64 lanes x 4 picks over all 32
    # experts, 8 rows a group: 256 rows as they are (ONE tile) and handed
    # 288 and 320; M as published (1,792 = 7 x 256) and padded to 2,048.
    "lfm2-decode": (4, 32, 32, 2048, 1792, 3, 64, 4,
                    ((None, 32), (None, 64))),
    "lfm2-decode-padded": (4, 32, 32, 2048, 2048, 3, 64, 4,
                           ((None, 32), (None, 64))),
    # Its prefill pass of four rows of 32 positions: 512 rows, 16 a group.
    "lfm2-wide-pass": (4, 32, 32, 2048, 1792, 3, 128, 4, ((None, 32),)),
    "lfm2-wide-pass-padded": (4, 32, 32, 2048, 2048, 3, 128, 4,
                              ((None, 32),)),
}


def handed(rows: int, tile) -> int:
    """Rows the products are handed for `rows` at a reading's `tile`."""
    return rows if tile is None else tile * (-(-rows // tile) | 1)


def draw_picks(rng, passes, layers, lanes, picks, among, held, biased):
    """int32 [passes, layers, lanes, picks]: each token's picks distinct
    among `among` experts, uniform (a router fair to the share), or with
    `biased` every pick on the share held (more than any prefix holds)."""
    import numpy as np

    reach = held if biased else among
    keys = rng.random((passes, layers, lanes, reach))
    return np.argsort(keys, axis=-1)[..., :picks].astype(np.int32)


def build(projections, held, among, fit, tile):
    """The jitted call with `held_rows` giving `fit` (None: every row) and
    `tiled_rows` the rows' `handed` at `tile`: (xs [P, S, D], picks [P, L,
    S, k], stacks) -> (x after the last layer [P, S, D], held experts'
    group sizes [P, L, E])."""
    import jax
    import jax.numpy as jnp

    from distributed_lms_raft_llm_tpu.models import moe

    grouped = moe.grouped_swiglu if projections == 3 else moe.grouped_relu2
    first = None if held == among else 0

    def one_pass(stacks, x, top_i):
        live = jnp.ones((x.shape[0],), bool)
        top_w = jnp.full(top_i.shape[1:], 1.0 / top_i.shape[-1], jnp.float32)
        sizes = []
        for layer, ws in enumerate(zip(*stacks)):
            y, n = grouped(x, top_i[layer], top_w, live, *ws, first=first,
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
        # `_grouped` asks the module for both when it is traced.
        real = moe.held_rows, moe.tiled_rows
        moe.held_rows = lambda rows, held_, among_: fit or rows
        moe.tiled_rows = lambda rows, group: handed(rows, tile)
        try:
            return call(*args)
        finally:
            moe.held_rows, moe.tiled_rows = real

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

    layers, held, among, d, m, projections, lanes, picks, pairs = (
        SHAPES[name])
    if args.small:
        layers, d, m, lanes = 2, 128, 64, min(lanes, 144)
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

    dev = jax.devices()[0]
    whole = build(projections, held, among, None, None)
    ms, want = timed(whole, (xs, fair, stacks), args.calls)
    _, want_biased = timed(whole, (xs, biased, stacks), 0)
    assert int(np.asarray(want_biased[1]).sum(-1).min()) == rows
    readings = [{"prefix": rows, "tile": None, "handed": rows,
                 "cond": False, "ms": ms,
                 "pass_ms": statistics.median(ms) / args.iters}]
    for fit, tile in pairs:
        fit = min(fit or rows, rows)
        fn = build(projections, held, among, fit, tile)
        ms, got = timed(fn, (xs, fair, stacks), args.calls)
        ms_biased, got_biased = timed(fn, (xs, biased, stacks), 1)
        same = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for pair in ((got, want), (got_biased, want_biased))
                   for a, b in zip(*pair))
        readings.append({
            "prefix": fit, "tile": tile, "handed": handed(fit, tile),
            "handed_fallback": handed(rows, tile), "cond": fit < rows,
            "ms": ms, "pass_ms": statistics.median(ms) / args.iters,
            "passes_that_fit": int((held_picks <= fit).sum()),
            "fallback_pass_ms": ms_biased[0] / args.iters,
            "equal_to_the_bit": same,
        })
    base = readings[0]["pass_ms"]
    for r in readings:
        r["against_all_rows"] = r["pass_ms"] / base - 1.0
        r["platform"] = dev.platform
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
