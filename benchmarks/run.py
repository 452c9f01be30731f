#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is `benchmarks/workloads/<name>.json`: a configuration
(`configs/<config>.json`), a traffic mix (`traffic/<traffic>.json`) and the
cell's own parameters. This process generates the load (asyncio gRPC on
loopback against `Tutoring.StreamLLMAnswer`) and never imports jax; its one
child, `benchmarks/serve.py`, owns the chip. Earlier lines of the standard
output are JSON objects labelled `"line"`; the LAST line is the result the
driver reads. No accelerator, or anything else that stops the run: no result
line and a non-zero exit code. (`--platform cpu` rehearses the whole command
on the CPU at a tiny size: its line says `correct: false`, carries no
metric, and the exit code is 4.)
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

READY_TIMEOUT_S = 1150.0   # a cold start compiles every serving program
COMMAND_TIMEOUT_S = 300.0


class RunFailure(Exception):
    pass


def say(line: str, **doc) -> None:
    print(json.dumps({"line": line, **doc}), flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except OSError as e:
        raise RunFailure(f"cannot read {path}: {e}") from e


def free_ports(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


# ----------------------------------------------------------------- the child


class Child:
    """`benchmarks/serve.py`: started once, spoken to in JSON lines, always
    reaped by pid."""

    def __init__(self, workdir: str):
        self.workdir = workdir
        self.proc = None
        self.log_path = os.path.join(workdir, "serve.log")

    async def start(self, config_path: str, seed: int, platform: str):
        port, metrics_port = free_ports(2)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (REPO, env.get("PYTHONPATH")) if p)
        self._log = open(self.log_path, "w")
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HERE, "serve.py"),
            "--config", config_path, "--seed", str(seed),
            "--port", str(port), "--metrics-port", str(metrics_port),
            "--platform", platform,
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            stderr=self._log, cwd=self.workdir, env=env,
            limit=64 * 1024 * 1024,
        )

    async def read(self, want: str, timeout: float) -> dict:
        """The next line whose event is `want`; other events are passed on
        as lines of this run's output, an error or an exit ends the run."""
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise RunFailure(f"no {want!r} from the server in {timeout}s")
            try:
                raw = await asyncio.wait_for(self.proc.stdout.readline(), left)
            except asyncio.TimeoutError:
                continue
            if not raw:
                raise RunFailure(
                    f"the server exited before {want!r}: {self.log_tail()}")
            try:
                doc = json.loads(raw)
            except ValueError:
                continue
            event = doc.pop("event", None)
            if event == want:
                return doc
            if event == "error":
                raise RunFailure(f"the server said: {doc.get('error')}")
            say(f"server_{event}", **doc)

    async def ask(self, cmd: str, want: str, **args) -> dict:
        self.proc.stdin.write(
            (json.dumps({"cmd": cmd, **args}) + "\n").encode())
        await self.proc.stdin.drain()
        return await self.read(want, COMMAND_TIMEOUT_S)

    def log_tail(self, n: int = 4000) -> str:
        try:
            with open(self.log_path, errors="replace") as fh:
                return fh.read()[-n:]
        except OSError:
            return ""

    async def stop(self) -> None:
        if self.proc is None:
            return
        if self.proc.returncode is None:
            try:
                self.proc.stdin.write(b'{"cmd": "quit"}\n')
                await self.proc.stdin.drain()
                await asyncio.wait_for(self.proc.wait(), 20)
            except (asyncio.TimeoutError, ConnectionError, OSError):
                pass
        if self.proc.returncode is None:
            self.proc.kill()
            await self.proc.wait()
        self._log.close()


# ------------------------------------------------------------------ the load


class Outcome:
    """One request, as the client saw it."""

    __slots__ = ("due", "sent", "first", "last", "tokens", "chunks", "error",
                 "token_times", "query_tokens", "broken_chars", "broken_ends")

    def __init__(self, due: float, query_tokens: int = 0):
        self.due, self.sent, self.query_tokens = due, None, query_tokens
        self.first = self.last = None
        self.tokens, self.chunks, self.error = 0, 0, None
        self.token_times = []  # (arrival, count) of each chunk
        self.broken_chars = False  # U+FFFD in the streamed text
        self.broken_ends = 0  # chunks before the last that end in one


async def ask_stream(stub, lms_pb2, query: str, out: Outcome, deadline_s: float,
                     max_new: int) -> None:
    """One streamed question; fills `out`, never raises for the request's
    own failure."""
    import grpc

    out.sent = time.monotonic()
    delivered, parts, final = 0, [], None
    try:
        call = stub.StreamLLMAnswer(lms_pb2.StreamRequest(query=query),
                                    timeout=deadline_s)
        async for chunk in call:
            now = time.monotonic()
            if not chunk.success:
                out.error = f"server refused: {chunk.text[:80]}"
                return
            if chunk.count > 0:
                if chunk.offset != delivered:
                    out.error = (f"offset {chunk.offset} after {delivered} "
                                 "delivered: not monotone and gap-free")
                    return
                if out.first is None:
                    out.first = now
                delivered += chunk.count
                out.token_times.append((now, chunk.count))
                parts.append(chunk.text)
            out.chunks += 1
            if chunk.final:
                final, out.last = chunk, now
                break
    except grpc.aio.AioRpcError as e:
        out.error = f"rpc {e.code().name}"
        return
    out.tokens = delivered
    out.broken_chars = any("\ufffd" in part for part in parts)
    out.broken_ends = sum(p.endswith("\ufffd") for p in parts[:-1])
    if final is None:
        out.error = "stream ended without a final chunk"
    elif delivered > max_new:
        # Fewer is an answer that ended in EOS; even none at all is one.
        out.error = f"{delivered} tokens, over {max_new}"
    elif hashlib.sha256(
            "".join(parts).strip().encode()).hexdigest() != final.digest:
        out.error = "digest does not match the streamed text"
    if out.first is None:
        out.first = out.last


async def run_load(traffic, address: str, seconds: float, deadline_s: float,
                   max_new: int, on_open=None):
    """Offer the traffic for `seconds`, drain what was asked in the window,
    return (outcomes, t0)."""
    import grpc

    from distributed_lms_raft_llm_tpu.proto import lms_pb2, rpc

    outcomes, tasks = [], []
    async with grpc.aio.insecure_channel(address) as channel:
        stub = rpc.TutoringStub(channel)
        warm = [Outcome(0.0) for _ in traffic.warmup]
        await asyncio.gather(*(
            ask_stream(stub, lms_pb2, w.query, out, deadline_s, max_new)
            for w, out in zip(traffic.warmup, warm)))
        for out in warm:
            if out.error:
                raise RunFailure(f"warm-up question failed: {out.error}")
        t0 = await on_open() if on_open else time.monotonic()
        end = t0 + seconds

        async def one(req, due):
            out = Outcome(due, req.query_tokens)
            outcomes.append(out)
            await ask_stream(stub, lms_pb2, req.query, out, deadline_s,
                             max_new)

        if traffic.generator == "open_loop":
            for req in traffic.requests:
                due = t0 + req.due_s
                wait = due - time.monotonic()
                if wait > 0:
                    await asyncio.sleep(wait)
                tasks.append(asyncio.create_task(one(req, due)))
        else:
            async def student(s: int):
                due, k = t0 + traffic.starts[s], 0
                await asyncio.sleep(max(0.0, due - time.monotonic()))
                while due < end:
                    await one(traffic.next_request(s, k, due - t0), due)
                    due, k = time.monotonic(), k + 1

            tasks = [asyncio.create_task(student(s))
                     for s in range(traffic.students)]
            await asyncio.sleep(max(0.0, end - time.monotonic()))
        await asyncio.gather(*tasks)
    return outcomes, t0


# --------------------------------------------------------------- the metrics


def end_to_end(name: str, ctx: dict):
    """An end-to-end metric by its name: `ttft_p<N>_ms`, `answer_p<N>_ms`
    (nearest rank over every request asked in the window, a failed one
    counting as the client's deadline), `ttft_mean_ms`, `answer_mean_ms`,
    `out_tok_s`, `setup_s`. A name may go on after a dot
    (`out_tok_s.mid`): the same quantity, listed a second time with a
    bound and a `workloads` list of its own, for the cells whose runs keep
    a finer bound than the quantity's every cell can."""
    from benchmarks import readers

    name = name.split(".", 1)[0]
    m = re.fullmatch(r"(ttft|answer)_(?:p(\d+)|(mean))_ms", name)
    if m:
        return readers.client_latency(
            {"which": m.group(1), "percentile": m.group(2) or m.group(3)},
            ctx), "ms"
    if name == "out_tok_s":
        return ctx["tokens_in_window"] / ctx["seconds"], "tokens/s"
    if name == "setup_s":
        return ctx["setup_s"], "s"
    raise RunFailure(f"no end-to-end metric is called {name!r}")


def grew(now: dict, then: dict) -> dict:
    """What each series grew by, the ones that did not move left out."""
    return {k: v - then.get(k, 0) for k, v in sorted(now.items())
            if v != then.get(k, 0)}


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


async def run(args) -> int:
    from benchmarks import readers, traffic as traffic_lib

    bench = load_json(os.pardir, "BENCHMARK.json")
    layer_specs = {m["name"]: load_json("layer_metrics", m["name"] + ".json")
                   for m in bench["per_layer"] if applies(m, args.workload)}
    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    spec = load_json("traffic", cell["traffic"] + ".json")
    seconds = float(args.seconds)
    deadline_s = float(spec["client_deadline_s"])
    max_new = int(config["serving"]["sampling"]["max_new_tokens"])
    traffic = traffic_lib.Traffic(
        spec, cell, args.seed, seconds,
        int(config["serving"]["max_prompt_tokens"]))
    say("traffic", workload=args.workload, seed=args.seed, seconds=seconds,
        **traffic.describe())

    workdir = tempfile.mkdtemp(prefix="lms_bench_")
    child = Child(workdir)
    trace_dir = os.path.join(workdir, "trace")
    try:
        await child.start(os.path.join(HERE, "configs",
                                       cell["config"] + ".json"),
                          args.seed, args.platform)
        ready = await child.read("ready", READY_TIMEOUT_S)
        say("ready", **ready)
        device = ready["device"]
        marks = {}

        async def on_open():
            marks["setup_s"] = time.monotonic() - T_PROCESS_START
            marks["marked"] = await child.ask("mark", "marked")
            return time.monotonic()

        tracer = None
        if args.trace:
            async def trace_span():
                # The last seconds of the window: the state is steady there,
                # and writing the trace then falls into the drain.
                while "marked" not in marks:
                    await asyncio.sleep(0.05)
                span = min(float(cell["trace_seconds"]), seconds / 2)
                await asyncio.sleep(seconds - span)
                await child.ask("trace_start", "trace_started", dir=trace_dir,
                                python=int(cell.get("trace_python", 1)))
                began = time.monotonic()
                await asyncio.sleep(span)
                marks["trace_span"] = (began, time.monotonic())
                marks["trace_stopped"] = await child.ask(
                    "trace_stop", "trace_stopped")

            tracer = asyncio.create_task(trace_span())
        outcomes, t0 = await run_load(
            traffic, f"127.0.0.1:{ready['port']}", seconds, deadline_s,
            max_new, on_open)
        if tracer is not None:
            await tracer
        collected = await child.ask(
            "collect", "collected",
            histograms=sorted({m["args"]["histogram"]
                               for m in layer_specs.values()
                               if m["reader"] == "metrics_histogram"}))
        reduced = None
        if args.trace:
            reduced = await child.ask("trace_reduce", "trace_reduced",
                                      dir=trace_dir)
    finally:
        await child.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    # -- what the run did
    failed = [o for o in outcomes if o.error]
    tokens_in_window = sum(
        n for o in outcomes for t, n in o.token_times if t <= t0 + seconds)
    ctx = {
        "outcomes": outcomes, "seconds": seconds, "deadline_s": deadline_s,
        "tokens_in_window": tokens_in_window, "setup_s": marks["setup_s"],
        "collected": collected, "marked": marks["marked"], "trace": reduced,
        "config": config, "cell": cell, "device": device, "t0": t0,
        "traffic_spec": spec, "trace_span": marks.get("trace_span"),
    }
    say("requests", attempted=len(outcomes), failed=len(failed),
        errors=sorted({o.error for o in failed})[:8],
        tokens_in_window=tokens_in_window,
        tokens_total=sum(o.tokens for o in outcomes),
        short_answers=sum(1 for o in outcomes
                          if not o.error and o.tokens < max_new),
        answers_with_broken_characters=sum(
            1 for o in outcomes if o.broken_chars),
        chunks_ending_in_a_broken_character=sum(
            o.broken_ends for o in outcomes),
        tokens_per_5s=[sum(n for o in outcomes for t, n in o.token_times
                           if lo <= t - t0 < lo + 5)
                       for lo in range(0, int(seconds) + 20, 5)],
        trace_stop_s=marks.get("trace_stopped", {}).get("stop_s"),
        drain_s=max((o.last or 0.0) for o in outcomes) - (t0 + seconds)
        if outcomes else None)

    # What the server counted over the window, mark to collect: the series
    # the per-layer readers divide, on every run, so that an untraced run's
    # `out_tok_s` can be laid beside its own passes, dispatches and lanes.
    then, now = marks["marked"].get("metrics", {}), collected["metrics"]

    def observed(doc: dict) -> dict:
        return {k: h.get("count", 0)
                for k, h in doc.get("latency", {}).items()}

    say("window_counters", window_s=collected.get("window_s"),
        counters=grew(now.get("counters", {}), then.get("counters", {})),
        observations=grew(observed(now), observed(then)))

    say("latencies_ms", **{
        f"{which}_{p if p == 'mean' else 'p' + p}": readers.client_latency(
            {"which": which, "percentile": p}, ctx)
        for which in ("ttft", "answer")
        for p in ("mean", "50", "75", "90", "95")})

    # -- correct: every number compared, beside its limit
    counters = collected["metrics"].get("counters", {})
    shed = sum(v for k, v in counters.items() if k.startswith("shed_"))
    compiled = (collected["compile_cache"]["requests"]
                - marks["marked"]["compile_cache"]["requests"])
    broken = [o.error for o in failed if not o.error.startswith("rpc ")
              and not o.error.startswith("server refused")]
    ref = ready["reference_check"]

    def held(what, value, op, limit, ok):
        return dict(what=what, value=value, op=op, limit=limit, ok=bool(ok))

    # One line for every number the configuration's family is held to, by
    # the name its `check.limits` gives the limit under.
    compared = [
        held(f"reference_{key}", ref["worst"][key], "<=", limit,
             ref["worst"][key] <= limit)
        for key, limit in ref["limits"].items()
    ] + [
        held("reference_comparison", bool(ref["ok"]), "==", True, ref["ok"]),
        held("compilations_in_window", compiled, "==", 0, compiled == 0),
        held("answers_breaking_a_guarantee", len(broken), "==", 0,
             not broken),
        held("shed_not_seen_by_a_client", max(0, shed - len(failed)), "==", 0,
             shed <= len(failed)),
        held("platform", device["platform"], "==", "tpu",
             device["platform"] == "tpu"),
    ]
    for line in compared:
        say("compared", **line)
    correct = all(line["ok"] for line in compared)

    # -- the metrics
    e2e = {}
    for m in bench["end_to_end"]:
        if applies(m, args.workload):
            value, unit = end_to_end(m["name"], ctx)
            e2e[m["name"]] = {"value": value, "unit": unit}
    metrics = e2e
    if args.trace:
        say("end_to_end_of_the_traced_run", **e2e)
        metrics = {}
        units = {m["name"]: m["unit"] for m in bench["per_layer"]}
        for name, spec_m in layer_specs.items():
            value = readers.read(spec_m["reader"], spec_m.get("args", {}),
                                 ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        # The counts a family may take for its decode steps, side by side:
        # the trace's loops by their entries, and what the server's
        # counters grew by over the traced span.
        say("notes", span_tokens=readers.span_tokens(ctx),
            loops=(reduced or {}).get("loops", [])[:5],
            span_counters=(reduced or {}).get("span_counters"),
            **ctx.get("notes", {}))
    # The most memory in use while the window's requests were served (the
    # allocator's own peak is set-up: PERF.md section 4).
    memory = collected.get("window_memory", {})
    say("memory", window_peak_bytes_in_use=memory.get("peak_bytes_in_use"),
        samples=memory.get("samples"), every_s=memory.get("every_s"),
        process_peak_bytes_in_use=collected["device_memory"].get(
            "peak_bytes_in_use"),
        bytes_in_use_after=collected["device_memory"].get("bytes_in_use"),
        bytes_in_use_before=ready["device_memory"].get("bytes_in_use"))
    result = {
        "correct": correct, "attempted": len(outcomes),
        "failed": len(failed), "metrics": metrics,
        "device": {
            "platform": device["platform"], "kind": device["kind"],
            "count": device["count"],
            "memory_peak_bytes": memory.get("peak_bytes_in_use"),
        },
    }
    if args.trace and reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    # Every number compared beside its limit, last on the result's line.
    result["compared"] = {
        line["what"]: {"value": line["value"], "limit": line["limit"]}
        for line in compared}
    if "jax" in sys.modules:
        raise RunFailure("the load-generating parent imported jax")
    # The same lines close the standard error: of a run that is not correct
    # the driver's record keeps the end of that.
    for line in compared:
        print(json.dumps({"line": "compared", **line}), file=sys.stderr,
              flush=True)
    if args.platform != "tpu":
        # A rehearsal: never a result.
        say("rehearsal_not_a_result", **result["metrics"])
        result.update(correct=False, metrics={})
        print(json.dumps(result), flush=True)
        return 4
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                    help="cpu rehearses the command; it is never a result")
    args = ap.parse_args(argv)
    try:
        code = asyncio.run(run(args))
    except RunFailure as e:
        print(f"benchmark run failed: {e}", file=sys.stderr)
        return 1
    except ImportError as e:
        print(f"benchmark run failed: the program is not importable from "
              f"{REPO}: {e}", file=sys.stderr)
        return 2
    return code


if __name__ == "__main__":
    sys.exit(main())
