"""The benchmark's launcher: the one process that touches the chip.

Started by `benchmarks/run.py` (which never imports jax). It makes the
weights from `--seed` as the configuration's family draws them
(`benchmarks/families/<family>/weights.py`), builds the program's
`PagedEngine` from the configuration file's serving settings, runs the
reference comparison (`benchmarks/check.py`), warms the engine up, and
serves it through the program's own `serve_async`, as
`serving/tutoring_server.main` does. Then it takes commands, one JSON
object a line, on its standard input and answers each with one line on its
standard output:

    {"cmd": "mark"}                      start of the measured window
    {"cmd": "collect"}                   /metrics, /healthz, window percentiles,
                                         the most memory in use since the mark
    {"cmd": "trace_start", "dir": ...}   start jax.profiler
    {"cmd": "trace_stop"}                stop it
    {"cmd": "trace_reduce", ...}         reduce the trace (benchmarks/trace.py);
                                         with it `span_counters`, the growth
                                         of /metrics' counters from
                                         trace_start to trace_stop
    {"cmd": "quit"}                      stop the server and exit 0

Every line it prints is a JSON object with an `"event"` key; logs go to the
standard error.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

VOCAB = os.path.join(HERE, "vocab", "vocab.json")
MERGES = os.path.join(HERE, "vocab", "merges.txt")


def emit(event: str, **doc) -> None:
    print(json.dumps({"event": event, **doc}), flush=True)


def build_engine(config: dict, seed: int):
    """The program's paged engine in the configuration's serving settings,
    holding the weights the configuration's family draws from `seed`. The
    engine casts, quantises and places them itself: they reach it where a
    checkpoint-less start draws its own, through the program family's
    `init_params`."""
    from benchmarks import families
    from distributed_lms_raft_llm_tpu.engine import (
        EngineConfig,
        PagedEngine,
        SamplingParams,
    )
    from distributed_lms_raft_llm_tpu.models import registry

    s = config["serving"]
    if s["engine"] != "paged":
        raise ValueError(f"this launcher serves the paged engine, not "
                         f"{s['engine']!r}")
    model = config["registry_model"]
    econf = EngineConfig(
        model=model,
        # "bytes" (the tiny rehearsal preset's 384 ids hold no BPE) loads
        # the program's byte fallback.
        vocab_path=VOCAB if config.get("tokenizer", "bpe") == "bpe" else None,
        merges_path=MERGES,
        sampling=SamplingParams.reference_defaults(**s["sampling"]),
        tp=s["tp"], quant=s["quant"], kv_quant=s["kv_quant"],
        spec_tokens=s["spec_tokens"], draft_source=s["draft_source"],
        scoring=s["scoring"], length_buckets=tuple(s["length_buckets"]),
        seed=int(seed) % (2 ** 31 - 1),
    )
    weights = families.of_config(config).weights
    family, factory = registry.PRESETS[model]
    held = [weights.program_tree(weights.of_config(
        seed, config, econf.param_dtype))]
    registry.PRESETS[model] = (
        family._replace(init_params=lambda _key, _cfg: held.pop()), factory)
    try:
        return PagedEngine(
            econf, slots=s["slots"], chunk=s["chunk"], inflight=s["inflight"],
            megastep=s["megastep"], megastep_max=s["megastep_max"],
            prefix_cache=s["prefix_cache"],
            prefix_cache_blocks=s["prefix_cache_blocks"],
            prefill_chunk_tokens=s["prefill_chunk_tokens"],
        )
    finally:
        registry.PRESETS[model] = (family, factory)


class MemoryWatch:
    """The most bytes in use on the first device between `start` and `stop`,
    read every `every_s` by a thread. The allocator's own peak runs over
    the process's whole life, set-up included (the float32 draw of the
    weights, the reference's float32 copy), and cannot be reset; what a
    deployment holds is what serving holds, and a program's temporaries
    count while it runs."""

    def __init__(self, every_s: float = 0.01):
        import jax

        self.device = jax.local_devices()[0]
        self.every_s, self.peak, self.samples = every_s, None, 0
        self._stop = threading.Event()
        self._thread = None

    def _run(self):
        while not self._stop.wait(self.every_s):
            stats = self.device.memory_stats() or {}
            if "bytes_in_use" in stats:
                self.samples += 1
                self.peak = max(self.peak or 0, stats["bytes_in_use"])

    def start(self):
        self.stop()
        self.peak, self.samples = None, 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def stop(self) -> dict:
        if self._thread is not None:
            self._stop.set()
            self._thread.join()
            self._thread = None
        return {"peak_bytes_in_use": self.peak, "samples": self.samples,
                "every_s": self.every_s}


def device_doc() -> dict:
    from distributed_lms_raft_llm_tpu.parallel.mesh import (
        device_info,
        device_memory,
    )

    return {"device": device_info(), "device_memory": device_memory()}


async def serve(args, config: dict, t_start: float) -> int:
    import jax

    from benchmarks import check
    from distributed_lms_raft_llm_tpu.serving.tutoring_server import (
        serve_async,
    )
    from distributed_lms_raft_llm_tpu.utils.compilation import cache_stats
    from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

    s = config["serving"]
    phases = {}
    t = time.monotonic()
    engine = build_engine(config, args.seed)
    phases["weights_and_engine_s"] = time.monotonic() - t

    t = time.monotonic()
    verdict = check.compare(engine.family, engine.cfg, engine.params, config,
                            args.seed)
    phases["reference_check_s"] = time.monotonic() - t
    emit("reference_check", **verdict)

    t = time.monotonic()
    engine.warmup()
    phases["warmup_s"] = time.monotonic() - t

    metrics = Metrics()
    server = await serve_async(
        args.port, engine, max_batch=s["max_batch"],
        max_queue=s["queue_depth"], metrics=metrics,
        metrics_port=args.metrics_port, node_id=f"tut-{args.port}",
        scoring=s["scoring"],
        scoring_max_job_texts=s["scoring_max_job_texts"],
        scoring_jobs_retained=s["scoring_jobs_retained"],
        session_ttl_s=s["session_ttl_s"], session_max=s["session_max"],
    )
    emit("ready", port=server._port, metrics_port=server._health.port,
         phases=phases, since_start_s=time.monotonic() - t_start,
         compile_cache=cache_stats(), reference_check=verdict,
         **device_doc())

    loop = asyncio.get_running_loop()
    commands: asyncio.Queue = asyncio.Queue()

    def read_stdin():
        for line in sys.stdin:
            loop.call_soon_threadsafe(commands.put_nowait, line)
        loop.call_soon_threadsafe(commands.put_nowait, '{"cmd": "quit"}')

    threading.Thread(target=read_stdin, daemon=True).start()

    mark = time.monotonic()
    tracing = False
    span_counters = {}
    memory = MemoryWatch()
    try:
        while True:
            cmd = json.loads(await commands.get())
            name = cmd["cmd"]
            if name == "mark":
                mark = time.monotonic()
                memory.start()
                emit("marked", compile_cache=cache_stats(),
                     metrics=metrics.snapshot())
            elif name == "collect":
                span = time.monotonic() - mark
                window = {}
                for hist in cmd.get("histograms", []):
                    h = metrics.hist(hist)
                    window[hist] = {
                        f"p{p}_s": h.window_percentile(span, p)
                        for p in (50, 95)
                    }
                emit("collected", metrics=metrics.snapshot(), window=window,
                     window_s=span, compile_cache=cache_stats(),
                     window_memory=memory.stop(), **device_doc())
            elif name == "trace_start":
                # Python calls are traced too (they label the idle gaps);
                # the programs' HLO is left out of the file.
                options = jax.profiler.ProfileOptions()
                options.enable_hlo_proto = False
                options.python_tracer_level = int(cmd.get("python", 1))
                jax.profiler.start_trace(cmd["dir"], profiler_options=options)
                tracing = True
                at_start = metrics.snapshot()["counters"]
                emit("trace_started", t=time.time())
            elif name == "trace_stop":
                # What the program's counters grew by over the traced span,
                # as the host counted (an iteration when it is reaped).
                span_counters = {
                    k: v - at_start.get(k, 0)
                    for k, v in metrics.snapshot()["counters"].items()}
                # Writing the trace takes several times its span: off the
                # loop, so that the requests still open are served.
                t = time.monotonic()
                await loop.run_in_executor(None, jax.profiler.stop_trace)
                tracing = False
                emit("trace_stopped", stop_s=time.monotonic() - t)
            elif name == "trace_reduce":
                from benchmarks import trace

                t = time.monotonic()
                reduced = await loop.run_in_executor(
                    None, trace.reduce_dir, cmd["dir"])
                emit("trace_reduced", reduce_s=time.monotonic() - t,
                     span_counters=span_counters, **reduced)
            elif name == "quit":
                break
            else:
                emit("error", error=f"unknown command {name!r}")
    finally:
        memory.stop()
        if tracing:
            jax.profiler.stop_trace()
        await server.stop(1.0)
        for task in (server._metrics_task, server._watchdog_task):
            task.cancel()
        await asyncio.gather(server._metrics_task, server._watchdog_task,
                             return_exceptions=True)
        await server._queue.close()
        if server._health is not None:
            await server._health.stop()
    emit("bye")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="path of the configuration file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--metrics-port", type=int, required=True)
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                    help="cpu is the rehearsal: it never counts as a result")
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    with open(args.config, encoding="utf-8") as fh:
        config = json.load(fh)

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from distributed_lms_raft_llm_tpu.utils.compilation import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    doc = device_doc()
    emit("device", **doc)
    if doc["device"]["platform"] != args.platform:
        emit("error", error=f"JAX initialised {doc['device']['platform']!r}, "
             f"the run asked for {args.platform!r}")
        return 3
    return asyncio.run(serve(args, config, t_start))


if __name__ == "__main__":
    sys.exit(main())
