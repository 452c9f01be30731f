"""Per-op device-time breakdown of one int8 decode call, fusion-correlated.

A trace names the costly fusions but not what is INSIDE them. This script
closes that gap:

1. runs one `generate_ids` (prefill + 128-step while_loop decode) under
   `jax.profiler.trace` and aggregates the device lane per op;
2. lowers/compiles the same decode program and extracts each hot fusion's
   fused-computation body from the optimized HLO, so every `fusion.N` line
   in the output carries the opcodes (and the largest tensor shapes) it
   executes;
3. writes chiprun_out/profiles/decode_<config>_batch<B>.json (the
   directory a chip call brings back; git-ignored).

Usage: python scripts/profile_decode.py [--batch 8] [--bf16]
           [--greedy] [--spec-tokens 8] [--out ...]
(--spec-tokens profiles the speculative verify-window loop of
engine/spec.py instead of the plain 128-step while_loop decode.)

Dispatch-gap mode: `--megastep K` profiles the PAGED engine's host loop
instead of the device ops — it runs the same workload through the chunk
loop (K=1) and through K-chunk megasteps, and reports host round trips
per emitted token plus per-program dispatch wall times before/after, so
the dispatch-gap share of decode latency is visible without a device
trace. (Chunk-loop dispatch gaps are what megasteps exist to remove.)

(Every timed region ends in a host readback of its result, so a timing
never measures the enqueue alone.)
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_engine(batch: int, quant: bool, spec_tokens: int = 0,
                 greedy: bool = False, tp: int = 1, ep: int = 1):
    from bench import ensure_local_artifacts
    from distributed_lms_raft_llm_tpu.engine import (
        EngineConfig, SamplingParams, TutoringEngine,
    )

    sampling = (SamplingParams.greedy(max_new_tokens=128) if greedy
                else SamplingParams.reference_defaults(max_new_tokens=128))
    cfg = EngineConfig(
        model="gpt2",
        **ensure_local_artifacts(),
        sampling=sampling,
        quant="int8" if quant else None,
        kv_quant=quant,
        spec_tokens=spec_tokens,
        batch_buckets=(batch,),
        length_buckets=(64,),
        tp=tp,
        ep=ep,
    )
    return TutoringEngine(cfg)


def trace_events(trace_dir: str):
    """Load every *.trace.json.gz under trace_dir; yield complete events."""
    for path in glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    ):
        with gzip.open(path, "rt") as fh:
            data = json.load(fh)
        names = {}  # (pid, tid) -> lane name from metadata events
        pids = {}
        for ev in data.get("traceEvents", []):
            if ev.get("ph") == "M" and ev.get("name") == "thread_name":
                names[(ev.get("pid"), ev.get("tid"))] = ev["args"]["name"]
            elif ev.get("ph") == "M" and ev.get("name") == "process_name":
                pids[ev.get("pid")] = ev["args"]["name"]
        for ev in data.get("traceEvents", []):
            if ev.get("ph") == "X":
                lane = names.get((ev.get("pid"), ev.get("tid")), "")
                proc = pids.get(ev.get("pid"), "")
                yield proc, lane, ev


def aggregate_device_ops(trace_dir: str):
    """Sum device-lane op durations by name; return (total_ms, [op rows])."""
    per_op = collections.Counter()
    per_op_count = collections.Counter()
    for proc, lane, ev in trace_events(trace_dir):
        # Device lanes are under the TPU/device process, XLA Ops threads.
        text = f"{proc}/{lane}".lower()
        if "xla op" not in text and "tensorflow op" not in text:
            continue
        name = ev.get("name", "?")
        per_op[name] += ev.get("dur", 0) / 1000.0  # us -> ms
        per_op_count[name] += 1
    rows = [
        {"op": op, "ms": round(ms, 3), "count": per_op_count[op]}
        for op, ms in per_op.most_common()
    ]
    return round(sum(per_op.values()), 2), rows


def fusion_bodies(hlo_text: str):
    """Map fusion instruction name -> opcode summary of its computation.

    Optimized HLO prints `%name = ... fusion(...), kind=..., calls=%comp`;
    each `%comp` is a computation block whose instruction opcodes tell us
    what the fusion actually does (scatter, iota-compare, reduce, dot...).
    """
    # computation name -> list of "opcode shape" strings
    comps: dict[str, list[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        m = re.match(r"\s*%?([\w\.\-]+)\s*\([^)]*\)\s*->\s*.*{\s*$", line)
        if m:
            current = m.group(1)
            comps[current] = []
            continue
        if current is not None:
            if line.strip() == "}":
                current = None
                continue
            im = re.match(
                r"\s*(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)",
                line,
            )
            if im:
                comps[current].append(f"{im.group(2)} {im.group(1)}")
    # fusion instr -> calls= (line-based: shapes nest parens/braces — e.g.
    # tuple outputs with T(8,128) tilings — so a single regex over the whole
    # instruction is fragile)
    fus = {}
    for line in hlo_text.splitlines():
        if " fusion(" not in line or "calls=" not in line:
            continue
        nm = re.match(r"\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=", line)
        cm = re.search(r"calls=%?([\w\.\-]+)", line)
        if nm and cm:
            fus[nm.group(1)] = cm.group(1)
    out = {}
    for name, comp in fus.items():
        ops = comps.get(comp, [])
        # Opcode histogram + the biggest shapes, compact.
        hist = collections.Counter(o.split()[0] for o in ops)
        big = sorted(
            (o for o in ops if "[" in o),
            key=lambda o: -eval_size(o.split()[1]),
        )[:4]
        out[name] = {
            "opcodes": dict(hist.most_common()),
            "largest": big,
        }
    return out


def eval_size(shape: str) -> int:
    m = re.search(r"\[([\d,]*)\]", shape)
    if not m or not m.group(1):
        return 0
    n = 1
    for d in m.group(1).split(","):
        n *= int(d)
    return n


def profile_megastep(args) -> None:
    """Host-dispatch-gap profile of the paged engine: the same request
    mix through the chunk loop and through --megastep K, with host round
    trips per token and per-program dispatch walls side by side."""
    import time

    import numpy as np

    from distributed_lms_raft_llm_tpu.engine import (
        EngineConfig, PagedEngine, SamplingParams,
    )

    # --model tiny runs the random-init test model so the dispatch-gap
    # profile works off-chip (CPU-speed smoke of the tooling itself;
    # dispatch COUNTS are model-independent, only the walls change).
    tiny = args.model == "tiny"
    max_new = 16 if tiny else 128
    paths = {}
    if not tiny:
        from bench import ensure_local_artifacts

        paths = ensure_local_artifacts()
    sampling = (
        SamplingParams.greedy(max_new_tokens=max_new) if args.greedy
        else SamplingParams.reference_defaults(max_new_tokens=max_new)
    )
    cfg = EngineConfig(
        model=args.model,
        sampling=sampling,
        quant=None if args.bf16 or tiny else "int8",
        kv_quant=not (args.bf16 or tiny),
        spec_tokens=args.spec_tokens,
        length_buckets=(16,) if tiny else (64,),
        batch_buckets=(args.batch,),
        tp=args.tp,
        ep=args.ep,
        **paths,
    )
    def run(megastep: int) -> dict:
        # Re-seeded per run: both the K=1 and K=args.megastep passes must
        # measure the IDENTICAL workload, or the before/after ratio
        # compares two different prompt sets.
        rng = np.random.default_rng(0)
        eng = PagedEngine(cfg, slots=args.batch, chunk=args.chunk,
                          megastep=megastep, megastep_max=megastep)
        plen = 8 if tiny else 48
        prompts = [
            eng.tokenizer.decode(
                rng.integers(0, eng.tokenizer.vocab_size, plen).tolist()
            )
            for _ in range(2 * args.batch)
        ]
        eng.warmup()
        eng.pop_dispatch_stats()
        eng.pop_program_times()
        t0 = time.monotonic()
        for p in prompts:
            eng.submit(p)
        eng.drain()
        wall = time.monotonic() - t0
        dispatches, tokens, dead, _stall_ms, _stalled = \
            eng.pop_dispatch_stats()
        per_prog: dict = {}
        for pname, _start, wall_s in eng.pop_program_times():
            n, tot = per_prog.get(pname, (0, 0.0))
            per_prog[pname] = (n + 1, tot + wall_s)
        return {
            "megastep": megastep,
            "host_dispatches": dispatches,
            "emitted_tokens": tokens,
            "host_dispatches_per_token": (
                round(dispatches / tokens, 4) if tokens else None
            ),
            "megastep_dead_lane_tokens": dead,
            "tokens_per_sec": round(tokens / wall, 1),
            "dispatch_wall_ms": {
                name: {"count": n, "mean_ms": round(tot / n * 1000, 2)}
                for name, (n, tot) in sorted(per_prog.items())
            },
        }

    before = run(1)
    after = run(args.megastep)
    out_path = args.out or os.path.join(
        REPO, "chiprun_out", "profiles",
        f"megastep_dispatch_gap_k{args.megastep}_chunk{args.chunk}"
        f"_batch{args.batch}.json",
    )
    payload = {
        "description": (
            "Host dispatch-gap profile of the paged engine: identical "
            f"workload (2x{args.batch} requests, {max_new} new tokens) "
            "through "
            f"the chunk loop (megastep=1) and through {args.megastep}-"
            "chunk device-resident megasteps; host round trips per "
            "emitted token is the ratio the megastep attacks"
        ),
        "chunk": args.chunk,
        "before": before,
        "after": after,
    }
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=1)
    print(f"wrote {out_path}")
    for row in (before, after):
        print(
            f"  megastep={row['megastep']:<3} dispatches/token="
            f"{row['host_dispatches_per_token']} "
            f"tok/s={row['tokens_per_sec']} "
            f"dead_lanes={row['megastep_dead_lane_tokens']}"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--bf16", action="store_true",
                    help="profile the bf16 config instead of int8+int8kv")
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace-dir", default="/tmp/decode_trace")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="profile the speculative decode path (pair with "
                         "--greedy; engine/spec.py verify windows)")
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--megastep", type=int, default=0,
                    help="dispatch-gap mode: profile the PAGED engine's "
                         "host loop at K-chunk megasteps vs the chunk "
                         "loop (host round trips per token before/after)")
    ap.add_argument("--model", default="gpt2", choices=["gpt2", "tiny"],
                    help="dispatch-gap mode: tiny = random-init test "
                         "model (CPU-speed smoke of the profile tooling; "
                         "dispatch counts are model-independent)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="paged device chunk size (dispatch-gap mode)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel ways; the paged engine shards "
                         "its slot KV cache heads axis over tp too, so a "
                         "tp>1 dispatch-gap profile measures the sharded "
                         "step programs")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel ways (MoE models only)")
    args = ap.parse_args()

    if args.megastep:
        profile_megastep(args)
        return

    import jax
    import numpy as np

    eng = build_engine(args.batch, quant=not args.bf16,
                       spec_tokens=args.spec_tokens,
                       greedy=args.greedy, tp=args.tp, ep=args.ep)
    if args.spec_tokens:
        # A REAL prompt: an all-zeros one is 64 repeated tokens, which
        # prompt-lookup drafting predicts near-perfectly — the profile
        # would show best-case window counts, not representative ones.
        prompt = (
            "You are an intelligent assistant. Answer the following "
            "question clearly and concisely.\nQuestion: Explain how "
            "leader election works in the Raft consensus algorithm and "
            "why a quorum is needed.\nAnswer:"
        )
        ids, mask, _ = eng.encode_prompts([prompt] * args.batch)
    else:
        ids = np.zeros((args.batch, 64), np.int32)
        mask = np.ones((args.batch, 64), bool)
    eng.generate_ids(ids, mask)  # compile + warm
    import shutil

    shutil.rmtree(args.trace_dir, ignore_errors=True)
    with jax.profiler.trace(args.trace_dir):
        result = eng.generate_ids(ids, mask)  # device_get inside = sync
    del result

    total_ms, rows = aggregate_device_ops(args.trace_dir)

    # HLO bodies for the decode program (the dominant while_loop lives
    # there); prefill adds its own fusions — correlate against both.
    import jax.numpy as jnp

    with eng.mesh:
        state = eng._prefill(
            eng.params, input_ids=jnp.asarray(ids),
            prompt_mask=jnp.asarray(mask), rng=jax.random.key(0),
        )
        if args.spec_tokens:
            lowered = eng._decode.lower(eng.params, state, jnp.asarray(ids))
        else:
            lowered = eng._decode.lower(eng.params, state)
        hlo = lowered.compile().as_text()
    bodies = fusion_bodies(hlo)

    for row in rows[:60]:
        base = row["op"].split("(")[0]
        if base in bodies:
            row["hlo"] = bodies[base]

    label = "bf16" if args.bf16 else "int8w_int8kv"
    if args.greedy:
        label += "_greedy"
    if args.spec_tokens:
        label += f"_spec{args.spec_tokens}"
    out_path = args.out or os.path.join(
        REPO, "chiprun_out", "profiles",
        f"decode_{label}_batch{args.batch}.json",
    )
    payload = {
        "description": (
            f"Device-time breakdown of ONE generate_ids call (64-token "
            f"prompt prefill + decode to 128 tokens), GPT-2-small batch "
            f"{args.batch}, {label}; fusions annotated with their "
            f"fused-computation opcode histograms from the optimized HLO "
            f"of the decode program"
        ),
        "total_device_ms": total_ms,
        "ops_ms": rows[:80],
    }
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=1)
    print(f"wrote {out_path}  total_device_ms={total_ms}")
    for row in rows[:12]:
        extra = ""
        if "hlo" in row:
            extra = " " + ",".join(
                f"{k}x{v}" for k, v in row["hlo"]["opcodes"].items()
            )
        print(f"  {row['ms']:9.2f} ms x{row['count']:<5} {row['op'][:60]}{extra[:90]}")


if __name__ == "__main__":
    main()
