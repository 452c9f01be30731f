"""One queue serves, whatever engine it is handed: the contract it relies on.

`engine.batcher.PagedQueue` is the only serving queue. Three engines stand
behind it in this tree: `PagedEngine` (the server, the benchmark, the
sim's tier-2 soak), the simulator's JAX-free double `sim.cluster.EchoEngine`
(the sim and the fleet, streaming, tracing and resilience tests), and that
double with ONE slot (tests/test_resilience.py's `FakePagedEngine`). A
double that drifts from the real engine's contract must fail HERE, not in
a soak:

- every engine x every behaviour the queue relies on;
- a streamed answer through `TutoringService` over the double comes off
  the double's own token channel, resumable at any offset;
- an object without the contract is refused when the server starts;
- the options of the deleted second queue are refused by name, and both
  shipped TOMLs still yield the served engine's arguments.
"""

import asyncio
import hashlib
import os
import textwrap
from unittest import mock

import jax.numpy as jnp
import pytest

from distributed_lms_raft_llm_tpu.config import load_config
from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    PagedQueue,
    SamplingParams,
)
from distributed_lms_raft_llm_tpu.engine.batcher import ENGINE_CONTRACT
from distributed_lms_raft_llm_tpu.proto import lms_pb2
from distributed_lms_raft_llm_tpu.serving import tutoring_server
from distributed_lms_raft_llm_tpu.sim.cluster import EchoEngine, echo_tokens
from distributed_lms_raft_llm_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPTS = ["what is a quorum?", "why does raft elect one leader a term?",
           "explain log replication", "who votes?", "what is a term?"]


def _tiny_paged():
    return PagedEngine(
        EngineConfig(
            model="tiny", sampling=SamplingParams.greedy(max_new_tokens=8),
            length_buckets=(16, 32), batch_buckets=(1, 2),
            dtype=jnp.float32,
        ),
        slots=2, chunk=2,
    )


ENGINES = {
    "paged-tiny": _tiny_paged,
    "echo": lambda: EchoEngine(0.0, chunk=3),
    "echo-one-slot": lambda: EchoEngine(0.0, slots=1, chunk=3),
}


@pytest.fixture(scope="module", params=sorted(ENGINES))
def engine(request):
    """One engine a kind for the module (the tiny PagedEngine compiles
    once); every case starts from `reset()`, which is part of what is
    tested."""
    return ENGINES[request.param]()


@pytest.fixture
def fresh(engine):
    engine.reset()
    engine.pop_ttfts()
    return engine


def _drain(engine):
    out = {}
    while engine.has_work:
        for rid, text in engine.step():
            assert rid not in out, f"request {rid} answered twice"
            out[rid] = text
    return out


def test_engine_has_the_contract(fresh):
    assert [n for n in ENGINE_CONTRACT if not hasattr(fresh, n)] == []
    PagedQueue(fresh)  # and the queue takes it


def test_an_answer_is_returned_once(fresh):
    rids = [fresh.submit(p) for p in PROMPTS]
    assert len(set(rids)) == len(PROMPTS)
    out = _drain(fresh)
    assert sorted(out) == sorted(rids)
    assert all(isinstance(t, str) for t in out.values())
    assert fresh.step() == []  # nothing is answered again
    # One first token a request, measured from its submit.
    ttfts = fresh.pop_ttfts()
    assert sorted(ttfts) == sorted(rids)
    assert all(t >= 0 for t in ttfts.values()) and fresh.pop_ttfts() == {}


def test_backlog_counts_what_waits_and_cancel_takes_it_out(fresh):
    rids = [fresh.submit(p) for p in PROMPTS]
    assert fresh.backlog == len(PROMPTS)  # nothing holds a slot yet
    assert fresh.cancel_pending(rids[-1])
    assert not fresh.cancel_pending(rids[-1])  # gone is gone
    assert fresh.backlog == len(PROMPTS) - 1
    out = _drain(fresh)
    assert rids[-1] not in out and sorted(out) == sorted(rids[:-1])
    # A request that reached a slot is no longer pending.
    assert not fresh.cancel_pending(rids[0])


def test_has_work_is_false_after_the_drain(fresh):
    assert not fresh.has_work
    fresh.submit(PROMPTS[0])
    assert fresh.has_work
    _drain(fresh)
    assert not fresh.has_work and fresh.backlog == 0


def test_reset_clears_slots_and_backlog(fresh):
    for p in PROMPTS:
        fresh.submit(p)
    fresh.step()  # some hold slots, the rest wait
    fresh.reset()
    assert not fresh.has_work and fresh.backlog == 0
    assert fresh.step() == []
    rid = fresh.submit(PROMPTS[0])  # and it serves again
    assert list(_drain(fresh)) == [rid]


def test_stream_snapshot_only_grows(fresh):
    rid = fresh.submit(PROMPTS[1])
    fresh.stream_watch(rid)
    seen, growths = [], 0
    while fresh.has_work:
        fresh.step()
        snap = fresh.stream_snapshot([rid]).get(rid)
        if snap is None:
            continue  # staged and not live yet, or already finished
        assert snap[:len(seen)] == seen, "a streamed token was retracted"
        growths += len(snap) > len(seen)
        seen = list(snap)
    assert growths >= 1, "the answer never showed on the token channel"
    final = fresh.pop_final_tokens()[rid]
    assert list(final[:len(seen)]) == seen
    fresh.stream_unwatch(rid)


def test_final_tokens_decode_to_the_answer(fresh):
    rids = [fresh.submit(p) for p in PROMPTS[:3]]
    for rid in rids[:2]:
        fresh.stream_watch(rid)
    out = _drain(fresh)
    finals = fresh.pop_final_tokens()
    assert sorted(finals) == sorted(rids[:2])  # the watched ones alone
    for rid in rids[:2]:
        assert fresh.decode_tokens(finals[rid]) == out[rid]
    assert fresh.pop_final_tokens() == {}


# ------------------------------------------- the double's token channel


QUERY = "how does a follower learn that its leader has failed, and when?"


async def _stream(service, resume_offset=0):
    chunks = []
    async for ch in service.StreamLLMAnswer(
        lms_pb2.StreamRequest(token="tok", query=QUERY,
                              resume_offset=resume_offset), None
    ):
        chunks.append(ch)
    return chunks


@pytest.mark.parametrize("resume_offset", [0, 2, 7, 10_000])
def test_double_streams_its_own_tokens_resumably(resume_offset):
    """What the deleted re-chunking facade promised, on the real token
    channel: `offset` + `count` are monotone and gap-free from the resume
    offset, the concatenation is the unary answer's suffix at that TOKEN,
    and the digest commits to the whole answer."""
    async def run():
        metrics = Metrics()
        queue = PagedQueue(EchoEngine(0.001, chunk=3), metrics=metrics)
        await queue.start()
        service = tutoring_server.TutoringService(queue, metrics,
                                                  node_id="double")
        try:
            unary = await service.GetLLMAnswer(
                lms_pb2.QueryRequest(token="tok", query=QUERY), None)
            return unary, await _stream(service, resume_offset)
        finally:
            await queue.close()

    unary, chunks = asyncio.run(run())
    assert unary.success
    toks = echo_tokens(unary.response)
    start = min(resume_offset, len(toks))
    delivered = start
    for ch in chunks:
        assert ch.success and ch.offset == delivered
        delivered += ch.count
    assert delivered == len(toks)
    assert [c.final for c in chunks] == [False] * (len(chunks) - 1) + [True]
    assert "".join(c.text for c in chunks) == "".join(toks[start:])
    assert chunks[-1].digest == hashlib.sha256(
        unary.response.encode()).hexdigest()
    if resume_offset == 0:
        # Several chunks, each a step's tokens: not one re-chunked text.
        assert len(chunks) > 2 and all(c.count <= 3 for c in chunks)


# ------------------------------------------------------ refusals by name


def test_serve_async_refuses_an_engine_without_the_contract():
    class AnswersInBatches:  # the deleted queue's engine contract
        def answer_batch(self, prompts):
            return list(prompts)

    async def run():
        await tutoring_server.serve_async(0, AnswersInBatches())

    with pytest.raises(TypeError, match="step") as err:
        asyncio.run(run())
    assert "PagedQueue needs an engine with" in str(err.value)


@pytest.mark.parametrize("section,line,named", [
    ("tutoring", "paged = true", "paged"),
    ("tutoring", "max_wait_ms = 10.0", "max_wait_ms"),
    ("sim", 'tutoring_engine = "tiny"', "tiny"),
])
def test_loader_refuses_the_deleted_options_by_name(tmp_path, section, line,
                                                    named):
    f = tmp_path / "old.toml"
    f.write_text(textwrap.dedent(f"""
        [cluster.nodes]
        1 = "127.0.0.1:50051"
        [{section}]
        {line}
    """))
    with pytest.raises(ValueError) as err:
        load_config(str(f))
    assert named in str(err.value) and section in str(err.value)


@pytest.mark.parametrize("name", ["cluster.toml", "dev.toml"])
def test_shipped_toml_yields_paged_engine_arguments(name):
    """`tutoring_server.main --config <shipped file>` reaches
    `PagedEngine(...)` with the file's slots, chunk and ladder: there is
    no other engine for it to build."""
    path = os.path.join(REPO, "configs", name)
    t = load_config(path).tutoring
    built = {}

    class _Stop(Exception):
        pass

    def capture(config, **kwargs):
        built.update(config=config, **kwargs)
        raise _Stop

    with mock.patch.object(tutoring_server, "PagedEngine",
                           side_effect=capture):
        with pytest.raises(_Stop):
            tutoring_server.main(["--config", path, "--jax-platform", "cpu"])
    assert built["config"].model == t.model
    assert built["config"].kv_quant == t.kv_quant
    assert built["slots"] == (t.slots or t.max_batch)
    assert (built["chunk"], built["megastep"], built["megastep_max"]) == (
        t.chunk, t.megastep, t.megastep_max)
    assert built["prefix_cache"] == t.prefix_cache
    assert built["prefill_chunk_tokens"] == t.prefill_chunk_tokens
