"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so that every sharding / collective
path (tp/dp/sp ring attention, pjit train step) is exercised without TPU
hardware: `JAX_PLATFORMS=cpu` plus `jax_num_cpu_devices=8`.

The persistent compile cache of a test run lives in one fixed directory
OUTSIDE the checkout (unless the environment already names one), so tier-1
neither grows the tree nor leaves this machine's CPU entries in the
in-checkout cache directory that a chip run would copy along
(utils/compilation.py).
"""

import os
import tempfile

# Inherited by the server subprocesses some tests spawn.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "dlrl_tpu_test_xla_cache"),
)

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected >=8 virtual devices, got {len(devices)}"
    return devices[:8]


@pytest.fixture
def strict_dispatch_guard():
    """Engine tests opt in to dispatch-hygiene assertion mode: any
    device->host readback outside `with intended_transfer():` raises on
    backends where readbacks are real transfers (utils/guards.py; the
    static rule no-host-sync-in-dispatch is the CPU-side enforcement)."""
    from distributed_lms_raft_llm_tpu.utils.guards import strict_dispatch

    with strict_dispatch():
        yield


@pytest.fixture
def ordered_locks():
    """Lock-order assertion mode: every OrderedLock acquisition during
    the test feeds the live acquisition graph (utils/locks.py), and the
    fixture asserts it acyclic — with no re-entry and no cycle-closing
    edge — on teardown. The runtime counterpart of the `lock-order`
    lint rule; the semester sim enables the same recording itself."""
    from distributed_lms_raft_llm_tpu.utils import locks

    locks.reset()
    with locks.recording():
        yield locks
    locks.assert_acyclic()


@pytest.fixture(scope="session")
def moves_a_reported_metric():
    """`check(entry)`: a per-layer metric of BENCHMARK.json says which
    end-to-end metric it should move. That is one the benchmark HAS, and
    one every cell of the per-layer metric REPORTS; which one, and under
    what name, is the benchmark's to say and no test's to pin."""
    import json

    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = [w["name"] for w in bench["workloads"]]
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}

    def check(entry):
        assert entry["moves"] in end_to_end, entry
        moved = end_to_end[entry["moves"]]
        assert set(entry.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), (entry, moved)

    return check


@pytest.fixture
def eos_first_engine(monkeypatch):
    """`make(config, prompt, **kw)`: a PagedEngine for which the greedy
    answer to `prompt` starts with eos, and pad differs from eos — the
    slot is dead from its flip and its lane holds pad filler. No program
    is patched: one engine learns the answer's first token, and the next
    one's tokenizer is loaded with that token as its eos."""
    from distributed_lms_raft_llm_tpu.engine import PagedEngine
    from distributed_lms_raft_llm_tpu.utils import tokenizer as tok_lib

    def make(config, prompt, **kw):
        probe = PagedEngine(config, **kw)
        rid = probe.submit(prompt)
        probe.stream_watch(rid)
        probe.drain()
        first = probe.pop_final_tokens()[rid][0]
        assert first != 0
        real = tok_lib.load_gpt2_tokenizer

        def load(*a, **k):
            tok = real(*a, **k)
            tok.eos_id, tok.pad_id = first, 0
            return tok

        monkeypatch.setattr(tok_lib, "load_gpt2_tokenizer", load)
        return PagedEngine(config, **kw)

    return make


@pytest.fixture
def stage_last_prompt():
    """`stage(engine, prompts)`: serve all but the last prompt one after
    another, stage the last (its prefix hit spliced, nothing dispatched
    yet) and read every plane of the cache that has a positions axis as
    the splice left it, then serve it: (planes, that admission's counters,
    every answer). Two engines that differ only in how the tree holds its
    blocks must agree on all three but the launches."""
    import numpy as np

    def stage(engine, prompts):
        answers = []
        for prompt in prompts[:-1]:
            rid = engine.submit(prompt)
            answers.append(engine.drain()[rid])
        engine.pop_loop_stats()
        rid = engine.submit(prompts[-1])
        engine._stage_admissions()
        counts = dict(engine.pop_loop_stats()[0])
        cache = engine.state.cache
        planes = {name: np.asarray(getattr(cache, name))
                  for name in ("k", "v", "ks", "vs", "pool")
                  if getattr(cache, name) is not None}
        answers.append(engine.drain()[rid])
        return planes, counts, answers

    return stage
