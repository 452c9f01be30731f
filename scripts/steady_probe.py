#!/usr/bin/env python
"""What one decode step and one prefill chunk cost on the device, for a
benchmark configuration's own engine (PERF.md section 5 keeps the readings).

Builds the engine as `benchmarks/serve.py` does (weights drawn from the
seed) and times a megastep of `--chunks` chunks (K) at the widest cache
width, from the host's clock around a call that ends in `block_until_ready`:

- with nothing staged and no lane live: every iteration is the decode step
  over all slots. "A dead lane computes what a live one does" holds for the
  weights and for attention that XLA's products run over the whole width;
  a kernel that goes by a lane's length (`ops/attention.py`
  `quant_decode_attention`: gpt2-xl's int8 planes) reads ONE block of an
  empty lane, so this reading flatters such a configuration's attention;
- with `--contexts`, every lane live at a context of its own (`cell`: drawn
  from the seed over what the configuration's first cell sends, a course, the
  server's template, a question and a share of the answer; or `LO:HI`, or a
  list the lanes cycle through): the decode step at the lengths the cell has,
  which is the reading to compare two trees by where attention goes by
  lengths. A context is cut to the width less the dispatch's iterations;
- with slots staged on a prompt of the longest bucket (`--staged`: how
  many, one reading each; all of them where left out), so that every
  iteration also runs one prefill pass, which serves the oldest staged
  slots a chunk each (`engine/paged.py` `_admission_chunk`: up to 128 /
  `prefill_chunk` of them in the program of one chunk, `--chunks 1`; the
  oldest alone in a longer rung's): the difference is the pass. One slot
  staged is a pass of one row, all of them a full one.

The state is donated, so each call gets a fresh one (made and staged
outside the timed region). One JSON line on stdout; `platform` says where
it ran, and only a TPU's line is a measurement.

    chiprun -- python scripts/steady_probe.py --config gpt2-xl
    JAX_PLATFORMS=cpu python scripts/steady_probe.py --config tiny --platform cpu
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from functools import partial

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def timed(engine, make_state, calls: int, k: int) -> tuple:
    """Milliseconds of `calls` megastep dispatches, each on a state of its
    own (the first call of a shape compiles and is not among them), and
    the state the last one left with `_megastep_program`'s `served`: its
    prefill passes, the slot-chunks they served, the passes that found two
    or more staged."""
    import jax

    out = []
    for i in range(calls + 1):
        state = jax.block_until_ready(make_state())
        keys = jax.block_until_ready(engine._step_keys(k))
        t = time.perf_counter()
        with engine.mesh:
            state, *res = engine._megastep(engine.params, state, keys)
        jax.block_until_ready((state, res))
        if i:
            out.append(1e3 * (time.perf_counter() - t))
    # Before a routed family's counts.
    return out, state, res[-2 if engine.family.routed else -1].tolist()


def cell_contexts(config: dict, draw, lanes: int) -> list:
    """`lanes` contexts as the configuration's first cell has them in the
    middle of its window: a course's context by its share, the server's
    template, a question (the traffic's clipped log-normal) and a uniform
    share of the answer. Only a traffic file with `courses` and
    `question_tokens` can be read so."""
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        cell = next(w for w in json.load(fh)["workloads"]
                    if w["config"] == config["name"])
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           f"{cell['traffic']}.json"), encoding="utf-8") as fh:
        traffic = json.load(fh)
    courses, q = traffic["courses"], traffic["question_tokens"]
    share = [c["share"] for c in courses]
    course = draw.choice(len(courses), size=lanes,
                         p=[x / sum(share) for x in share])
    question = draw.lognormal(0.0, q["sigma"], lanes) * q["median"]
    out = draw.integers(
        0, config["serving"]["sampling"]["max_new_tokens"], lanes)
    return [int(courses[c]["context_tokens"] + traffic["template_tokens"]
                + min(max(round(x), q["lo"]), q["hi"]) + o)
            for c, x, o in zip(course, question, out)]


def traced_ops(engine, make_state, trace_dir: str, top: int,
               k: int) -> dict:
    """One more megastep under the profiler: the device's time by
    operation, longest first (`benchmarks/trace.py`)."""
    import jax

    from benchmarks import trace

    state = jax.block_until_ready(make_state())
    keys = jax.block_until_ready(engine._step_keys(k))
    jax.profiler.start_trace(trace_dir)
    with engine.mesh:
        jax.block_until_ready(engine._megastep(engine.params, state, keys))
    jax.profiler.stop_trace()
    got = trace.reduce_dir(trace_dir)
    return {"busy_s": got["busy_s"], "loops": got["loops"][:4],
            "device_ops": got["device_ops"][:top]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a name under benchmarks/configs/ (gpt2-xl, ...)")
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--staged", type=int, action="append",
                    help="slots to stage for the staged reading, once a "
                         "reading (default: every slot)")
    ap.add_argument("--chunks", type=int, default=1, choices=[1, 2, 4, 8],
                    help="chunks a dispatch (K): 1 is what the cells' "
                         "controller dispatches while work waits, and the "
                         "program that holds the pass of several rows; a "
                         "longer rung serves one slot a pass")
    ap.add_argument("--contexts", action="append", default=[],
                    help="also time the dispatch with every lane live, "
                         "once a reading: "
                         "`cell` (the first cell's contexts, drawn from "
                         "the seed), LO:HI (uniform), or N,N,... (the "
                         "lanes cycle through the list)")
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"])
    ap.add_argument("--trace-dir", default=None,
                    help="also trace one megastep of each kind into this "
                         "directory and print its longest operations")
    ap.add_argument("--top", type=int, default=16)
    args = ap.parse_args(argv)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           f"{args.config}.json"), encoding="utf-8") as fh:
        config = json.load(fh)

    import jax
    import numpy as np

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from benchmarks import serve

    platform = jax.devices()[0].platform
    if platform != args.platform:
        print(f"JAX initialised {platform!r}, the probe asked for "
              f"{args.platform!r}", file=sys.stderr)
        return 3
    engine = serve.build_engine(config, args.seed)
    width, bucket = max(engine.widths), engine.bucket
    iterations = args.chunks * engine.chunk

    def idle():
        return engine._init_state(width)

    def staged(slots):
        state = idle()
        # A prompt of its own a slot, drawn from the seed: a routed
        # family's chunk reads the experts its tokens pick, and a prompt
        # of one repeated token picks the same few at every position.
        draw = np.random.default_rng(args.seed)
        with engine.mesh:
            for slot in range(slots):
                ids = draw.integers(0, engine.cfg.vocab_size, (1, bucket),
                                    dtype=np.int32)
                state = engine._stage(
                    engine._canon_state(state), engine._i32(slot), ids,
                    np.int32(bucket), np.int32(0), np.int32(slot),
                    jax.random.key_data(jax.random.key(slot)),
                    *engine._snap_arg(0),
                )
        return engine._canon_state(state)

    def live(contexts):
        # Every lane decoding at its context: what the planes hold does
        # not move a time, the lengths the attention goes by do.
        state = idle()
        put = lambda x, like: jax.device_put(  # noqa: E731
            np.asarray(x, like.dtype), like.sharding)
        return engine._canon_state(state._replace(
            cache=state.cache._replace(
                length=put(contexts, state.cache.length)),
            active=put(np.ones(engine.slots), state.active),
            stage_len=put(contexts, state.stage_len)))

    t_idle, after, _ = timed(engine, idle, args.calls, args.chunks)
    m_idle = statistics.median(t_idle)
    lived, live_states = [], []
    for spec in args.contexts:
        draw = np.random.default_rng(args.seed)
        if spec == "cell":
            contexts = cell_contexts(config, draw, engine.slots)
        elif ":" in spec:
            lo, hi = map(int, spec.split(":"))
            contexts = draw.integers(lo, hi + 1, engine.slots).tolist()
        else:
            given = [int(x) for x in spec.split(",")]
            contexts = [given[i % len(given)] for i in range(engine.slots)]
        contexts = [min(c, width - iterations) for c in contexts]
        t_live, _, _ = timed(engine, partial(live, contexts), args.calls,
                             args.chunks)
        live_states.append(partial(live, contexts))
        lived.append({
            "contexts": contexts, "megastep_live_ms": t_live,
            "step_live_ms": statistics.median(t_live) / iterations})
    passes = []
    for slots in args.staged or [engine.slots]:
        t_staged, _, (ran, served, _) = timed(
            engine, partial(staged, slots), args.calls, args.chunks)
        passes.append({
            "staged": slots, "megastep_staged_ms": t_staged,
            "passes": ran, "slot_chunks_served": served,
            "pass_ms": (statistics.median(t_staged) - m_idle) / ran,
        })
    traces = {}
    if args.trace_dir:
        for name, make in (("idle", idle),
                           ("staged", partial(staged, passes[-1]["staged"])),
                           *(("live", make) for make in live_states[:1])):
            traces[f"trace_{name}"] = traced_ops(
                engine, make, os.path.join(args.trace_dir, name), args.top,
                args.chunks)
    print(json.dumps({
        "line": "steady_probe", "config": args.config, "platform": platform,
        "device_kind": jax.devices()[0].device_kind, "seed": args.seed,
        "slots": engine.slots, "width": width, "k": args.chunks,
        "iterations": iterations, "prefill_chunk": engine.prefill_chunk,
        "planes": {name: [str(x.dtype), *x.shape] for name, x
                   in after.cache._asdict().items()
                   if x is not None and x.ndim > 1},
        "megastep_idle_ms": t_idle, "step_ms": m_idle / iterations,
        "live": lived,
        "passes": passes, **traces,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
