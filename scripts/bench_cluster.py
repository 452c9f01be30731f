"""North-star end-to-end bench: `LMS.GetLLMAnswer` through the FULL stack.

BASELINE's student-visible latency is defined at the LMS `GetLLMAnswer`
entry point — linearizable read fence, session check, BERT relevance gate,
HMAC'd fan-out to the TPU tutoring node, generation, and the answer back
through the leader (reference path: GUI_RAFT_LLM_SourceCode/
lms_gui_final.py:900-929 -> lms_server.py:1237-1274). `benchmarks/run.py`
measures the tutoring node alone; this script boots the real deployment —
3 Raft LMS nodes (quorum of the reference's 5-node topology) + the gate +
the tutoring server, all from configs/cluster.toml artifacts — registers N
student accounts over real gRPC, uploads an assignment each, and fires
N x M concurrent `ask_llm` queries.

Prints ONE JSON line: answer-latency p50/p90/p95 (for a unary RPC the
student-visible TTFT IS the answer latency), throughput, and the gate
pass/reject split.

    python scripts/bench_cluster.py [--students 8] [--queries 4]
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CONFIG = os.path.join(REPO, "configs", "cluster.toml")

QUESTIONS = [
    "How does Raft consensus elect a leader after a network partition?",
    "Explain the difference between eventual and linearizable consistency.",
    "Why does two-phase commit block when the coordinator fails?",
    "How does a KV cache speed up autoregressive decoding?",
]

ASSIGNMENT = (
    b"Homework: explain the Raft consensus algorithm - leader election, "
    b"log replication, commitment, and safety under network partitions; "
    b"compare with two-phase commit and discuss consistency models."
)


def boot(args) -> list:
    """Start 3 LMS nodes + the tutoring node as subprocesses; return them."""
    procs = []
    env = dict(os.environ)
    tmp = args.workdir

    def spawn(cmd, log_name):
        log = open(os.path.join(tmp, log_name), "w")
        p = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT
        )
        p._log_path = log.name
        procs.append(p)
        return p

    spawn(
        [sys.executable, "-m",
         "distributed_lms_raft_llm_tpu.serving.tutoring_server",
         "--config", CONFIG],
        "tutoring.log",
    )
    for i in (1, 2, 3):
        spawn(
            [sys.executable, "-m",
             "distributed_lms_raft_llm_tpu.serving.lms_server",
             "--config", CONFIG, "--id", str(i),
             "--data-dir", os.path.join(tmp, f"node{i}")],
            f"lms{i}.log",
        )
    return procs


def run_bench(args) -> dict:
    from distributed_lms_raft_llm_tpu import config as config_lib
    from distributed_lms_raft_llm_tpu.client.client import LMSClient

    cfg = config_lib.load_config(CONFIG)
    servers = [cfg.cluster.nodes[k] for k in sorted(cfg.cluster.nodes)][:3]

    def setup(sid: int):
        c = LMSClient(servers, discovery_rounds=30, discovery_backoff_s=3.0)
        user = f"bench_student_{os.getpid()}_{sid}"
        c.register(user, "pw12345", "student")
        assert c.login(user, "pw12345"), f"login failed for {user}"
        assert c.upload_assignment("hw1.txt", ASSIGNMENT)
        # One untimed warm query so per-bucket first-compile (if any) and
        # channel setup don't land in the measured window.
        c.ask_llm(QUESTIONS[sid % len(QUESTIONS)])
        return c

    def timed_queries(arg) -> list:
        sid, c = arg
        lat = []
        for q in range(args.queries):
            t0 = time.monotonic()
            resp = c.ask_llm(QUESTIONS[(sid + q) % len(QUESTIONS)])
            dt = time.monotonic() - t0
            assert resp.response, "empty GetLLMAnswer response"
            gated = "does not appear related" in resp.response
            lat.append((dt, bool(resp.success), gated))
        return lat

    with concurrent.futures.ThreadPoolExecutor(args.students) as pool:
        clients = list(pool.map(setup, range(args.students)))
        # Only the steady-state query phase is timed: registration, login,
        # upload, and the warm queries all happened above.
        t0 = time.monotonic()
        per_student = list(pool.map(timed_queries, enumerate(clients)))
        wall = time.monotonic() - t0
    for c in clients:
        c.close()

    flat = [x for lats in per_student for x in lats]
    # Gate rejections short-circuit before the tutoring fan-out (success
    # with an advisory message) — a different, much cheaper code path, so
    # they are counted but kept OUT of the answer-latency percentiles.
    ok = sorted(dt for dt, success, gated in flat if success and not gated)
    gated = sum(1 for _, _, g in flat if g)
    n = len(ok)
    assert n >= 0.8 * len(flat), (
        f"only {n}/{len(flat)} queries reached the tutoring node "
        f"({gated} gate-rejected)"
    )
    pct = lambda p: round(ok[min(int(n * p), n - 1)], 3)  # noqa: E731
    return {
        "metric": "lms_get_llm_answer_e2e_p50_s",
        "value": pct(0.50),
        "unit": "s",
        "students": args.students,
        "queries_per_student": args.queries,
        "p90_s": pct(0.90),
        "p95_s": pct(0.95),
        "count": n,
        "gate_rejected": gated,
        "requests_per_s": round(n / wall, 2),
        "wall_s": round(wall, 1),
        "stack": "gui-client-lib -> LMS leader (read fence + session + "
                 "BERT gate) -> HMAC fan-out -> TPU tutoring (paged int8)",
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--students", type=int, default=8)
    ap.add_argument("--queries", type=int, default=4)
    ap.add_argument("--startup-wait", type=float, default=150.0,
                    help="max seconds to wait for cluster + engine warmup")
    ap.add_argument("--keep-workdir", action="store_true")
    args = ap.parse_args()
    args.workdir = tempfile.mkdtemp(prefix="bench_cluster_")

    procs = boot(args)
    try:
        # Wait for the tutoring server's warmup (it logs "listening").
        deadline = time.monotonic() + args.startup_wait
        tut_log = os.path.join(args.workdir, "tutoring.log")
        while time.monotonic() < deadline:
            if os.path.exists(tut_log) and "listening" in open(tut_log).read():
                break
            if any(p.poll() is not None for p in procs):
                for p in procs:
                    if p.poll() is not None:
                        sys.stderr.write(open(p._log_path).read()[-2000:])
                raise SystemExit("a server process died during startup")
            time.sleep(2)
        else:
            raise SystemExit("tutoring server did not come up in time")
        print(json.dumps(run_bench(args)))
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if not args.keep_workdir:
            shutil.rmtree(args.workdir, ignore_errors=True)


if __name__ == "__main__":
    main()
