"""The rehearsal family `gpt2_moe` (configuration `tiny-moe`): a second
family through PR 29's seam, as new files only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def config():
    return load("configs", "tiny-moe.json")


def test_reference_agrees_with_the_programs_forward_at_tiny_width(config):
    """Float32 on both sides, one pass over the whole sequence: the same
    logits, and the routing the reference reports (with its capacity drops)
    is the one the program's expert layer answers the probe with."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import check
    from benchmarks.families.gpt2_moe import compare, reference, weights
    from distributed_lms_raft_llm_tpu.models import registry

    family, cfg = registry.resolve("moe-tiny", jnp.float32, jnp.float32)
    compare.check_sizes(config, cfg)
    w = weights.of_config(11, config, jnp.float32)
    ids = check.sequences(11, 1, 40, config["vocab_size"])[0]
    whole = dict(config, check=dict(config["check"], prompt_tokens=40))
    want, _, _, routing = reference.forward(w, ids, whole)
    del compare._heard[:]
    with compare._probed(), jax.default_matmul_precision("highest"):
        got, _ = family.forward(weights.program_tree(w), cfg,
                                jnp.asarray(ids)[None])
    jax.effects_barrier()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5, rtol=1e-4)
    heard = np.stack(compare._heard)
    assert heard.shape == (2, 40, 4)
    np.testing.assert_allclose(heard, np.asarray(routing), atol=1e-6)
    # 40 tokens x 2 picks on 4 experts with 25 seats each: the draw of seed
    # 11 drops 14 picks in the first layer and 9 in the second, and both
    # sides drop the same
    sent = (np.asarray(routing) > 0).sum(axis=-1)
    assert (2 - sent).sum(axis=1).tolist() == [14, 9]
    assert compare.routing_disagreement(heard, routing) == 0.0


def test_the_capacity_rule_by_hand():
    """Three seats to an expert, first choices before second, earlier
    tokens first."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families.gpt2_moe import reference

    assert reference.capacity(32, 2, 4, 1.25) == 20
    assert reference.capacity(1, 2, 4, 1.25) == 1
    picks = jnp.asarray([[0, 1], [0, 1], [0, 2], [0, 1], [1, 0]])
    kept = np.asarray(reference._seated(picks, 4, 3))
    # expert 0: tokens 0, 1, 2 are seated, token 3's first choice is not,
    # nor token 4's second; expert 1: token 4's first choice comes before
    # every second choice, so tokens 0 and 1 get the other two seats
    assert kept.tolist() == [[True, True], [True, True], [True, True],
                             [False, False], [True, False]]


def test_the_served_precision_passes_and_every_control_fails(config):
    """The program's int8 path is inside every limit of the file; each
    control is outside at least one; and a program that routes otherwise
    (every token's experts turned by one) fails the routing's limit alone,
    as far as the readings can say."""
    import numpy as np

    from benchmarks import check, serve
    from benchmarks.families.gpt2_moe import compare, reference

    limits = config["check"]["limits"]
    assert set(limits) == {
        "logits_distance", "logits_worst_position_distance",
        "keys_and_values_distance", "routing_disagreement"}
    engine = serve.build_engine(config, 5)
    assert engine.family.name == "gpt2_moe"
    got = check.compare(engine.family, engine.cfg, engine.params, config, 5)
    assert got["ok"], got["worst"]
    for seed in (1, 2, 3):
        seqs = check.sequences_of(config, seed)[:1]
        want = check.reference_side(config, seed, seqs)
        for name in reference.CONTROLS:
            ctl = check.reference_side(config, seed, seqs, name)
            read = compare.readings(ctl[0], want[0])
            assert not check.verdict([read], limits)["ok"], (name, read)
        turned = want[0][:3] + (np.roll(np.asarray(want[0][3]), 1, axis=-1),)
        with pytest.raises(ValueError):   # no position is routed alike
            compare.readings(turned, want[0])
        assert compare.routing_disagreement(turned[3], want[0][3]) > 0.5
    with pytest.raises(ValueError):
        check.reference_side(config, 1, seqs, "int2_everything")


def test_a_prompt_that_leaves_pads_in_its_bucket_is_refused(config):
    from benchmarks.families.gpt2_moe import compare

    with pytest.raises(ValueError):
        compare.program(None, None, None, [1] * 40,
                        dict(config["check"], prompt_tokens=20))


def test_bytes_and_operations_by_hand(config):
    from benchmarks import roofline
    from benchmarks.families.gpt2_moe import roofline as counted

    # 2 layers, 32 wide, 4 heads, 384 tokens, experts of 128, 4 of them, 2
    # a token. Trunk: 2 x 4 x 32^2 + 384 x 32 int8, 4 x (2 x 4 x 32 + 384)
    # of scales, 2 x (2 x 8 x 32 + 64) of vectors, 2 x 2 x 32 x 4 of router.
    assert counted.trunk_bytes(config) == (
        8192 + 12288 + 4 * 640 + 2 * 576 + 512)
    # an expert: 2 x 32 x 128 int8, (128 + 32) scales and biases
    assert counted.expert_bytes(config) == 8192 + 4 * 160 + 2 * 160
    assert counted.kv_bytes_per_token(config) == 2 * 2 * 32 + 4 * 2 * 2 * 4
    assert counted.experts_reached(config, 1.0) == 2.0    # not all four
    assert counted.experts_reached(config, 0.5) == 1.0
    assert counted.experts_reached(config, 16.0) == 4.0   # never more
    assert counted.slot_ops(config, 10) == 2.0 * (
        2 * (4096 + 128 + 2 * 8192) + 12288) + 4.0 * 2 * 32 * 10
    # 10 steps by the counter that advanced 10 slot-tokens: one slot a step
    trace = {"span_counters": {"engine_scan_iterations": 10},
             "loops": [["%while.4 (s32[])", 12.0]]}
    cost = counted.cost(config, trace, 10.0, 20.0)
    assert cost["steps"] == 10 and cost["steps_less_loop"] == -2
    assert cost["experts_reached_per_layer"] == 2.0
    assert cost["bytes"] == (
        10 * (counted.trunk_bytes(config) + 2 * 2 * 9152) + 10 * 20 * 192)
    assert cost["ops"] == counted.slot_ops(config, 20.0) * 10
    assert roofline.least_seconds(cost, "TPU v5 lite")["bound"] == "memory"
    assert counted.cost(config, {"span_counters": {}}, 10.0, 20.0) is None


def test_the_rehearsal_cell_is_new_files_only_and_not_in_the_benchmark():
    cell = load("workloads", "tiny-moe.deadline-herd.json")
    assert cell["config"] == "tiny-moe" and cell["traffic"] == "tiny-herd"
    bench = load(os.pardir, "BENCHMARK.json")
    assert "tiny-moe" not in {c["name"] for c in bench["configs"]}
    assert all(not w["name"].startswith("tiny")
               for w in bench["workloads"])
