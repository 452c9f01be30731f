"""`quant_decode_attention` (ops/attention.py): a decode step's attention
over folded int8 planes that reads each lane's live positions and nothing
past them, held to `attend_quant`'s folded arm (models/common.py), which is
what every other backend runs. The kernel runs interpreted here; the real
megastep's compile for a described v5e is tests/test_chip_compile.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lms_raft_llm_tpu.models import common
from distributed_lms_raft_llm_tpu.ops import attention as attention_ops

BLOCK = attention_ops.QUANT_DECODE_BLOCK
LAYERS, HEADS, HEAD_DIM = 2, 3, 64  # rows of 192 bytes folded into 256 lanes


def _operands(seed, lanes, width, lengths, hole=False):
    """(q, k, ks, v, vs, mask) with int8 planes folded as the engines hold
    them; `hole`: a position below every length the mask leaves out."""
    rng = np.random.default_rng(seed)
    f = common.folded_width(HEADS, HEAD_DIM)
    planes = []
    for _ in range(2):
        p = rng.integers(-127, 128, (LAYERS, lanes, 1, width, f),
                         dtype=np.int8)
        p[..., HEADS * HEAD_DIM:] = 0
        planes.append(p)
    ks, vs = (rng.uniform(1e-3, 2e-2, (LAYERS, lanes, HEADS, width)).astype(
        np.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(lanes, HEADS, 1, HEAD_DIM)),
                    jnp.bfloat16)
    mask = np.arange(width)[None, :] < np.asarray(lengths)[:, None]
    if hole:
        mask[:, 2] = False
    return (q, planes[0], ks, planes[1], vs,
            jnp.asarray(mask[:, None, None, :]))


def _lengths(case: str, lanes: int, width: int) -> np.ndarray:
    """A lane's length by the case's name, and beside it (16 lanes) every
    other edge, so that a lane's copies land beside another's leftovers."""
    first = {"one": 1, "below-edge": BLOCK - 1, "edge": BLOCK,
             "past-edge": BLOCK + 1, "whole": width, "zero": 0,
             "hole": width - 7}[case]
    rest = [width, 1, BLOCK + 1, 0, BLOCK - 1, 3 * BLOCK, width - 1, BLOCK,
            2 * BLOCK + 5, width, 7, 0, width - BLOCK, 65, 2]
    return np.asarray(([first] + rest)[:lanes])


@pytest.mark.parametrize("width", [128, 384])
@pytest.mark.parametrize("lanes", [1, 16])
@pytest.mark.parametrize("case", ["one", "below-edge", "edge", "past-edge",
                                  "whole", "zero", "hole"])
def test_kernel_is_attend_quant_on_live_lanes_and_reads_nothing_past_them(
        case, lanes, width):
    lengths = _lengths(case, lanes, width)
    q, k, ks, v, vs, mask = _operands(
        lanes * width, lanes, width, lengths, hole=case == "hole")
    read = attention_ops.mask_lengths(mask)
    if case != "hole":
        np.testing.assert_array_equal(np.asarray(read), lengths)
    layer = 1
    ref = common.attend_quant(q, k[layer], ks[layer], v[layer], vs[layer],
                              mask)
    got = common._quant_decode(q, jnp.asarray(k), ks, jnp.asarray(v), vs,
                               layer, mask, read, interpret=True)
    assert got.shape == ref.shape and got.dtype == ref.dtype
    ref, got = np.asarray(ref, np.float32), np.asarray(got, np.float32)
    live = lengths > 0
    # bfloat16 outputs of float32 sums taken in another order.
    np.testing.assert_allclose(got[live], ref[live], rtol=0,
                               atol=2 ** -7 * np.abs(ref).max())
    assert not got[~live].any()  # a lane at 0 reads nothing: zeros
    # Garbage at and past a lane's length, in the planes and the scales,
    # changes nothing: those rows are not fetched, and what the buffers
    # hold there meets a probability of 0.
    for lane, n in enumerate(lengths):
        k[:, lane, :, n:], v[:, lane, :, n:] = 99, -99
        ks[:, lane, :, n:], vs[:, lane, :, n:] = 1e4, -1e4
    again = common._quant_decode(q, jnp.asarray(k), ks, jnp.asarray(v), vs,
                                 layer, mask, read, interpret=True)
    np.testing.assert_array_equal(np.asarray(again, np.float32), got)


def test_a_shorter_length_than_the_mask_reads_that_far():
    """The engine may hand a dead lane length 0 whatever its mask says:
    the lengths bound what is attended, and the mask only narrows it."""
    lanes, width = 2, 128
    q, k, ks, v, vs, mask = _operands(5, lanes, width, [100, 100])
    short = jnp.asarray([40, 0], jnp.int32)
    got = common._quant_decode(q, jnp.asarray(k), ks, jnp.asarray(v), vs, 0,
                               mask, short, interpret=True)
    cut = jnp.asarray(np.arange(width)[None, :] < np.asarray([40, 0])[:, None]
                      )[:, None, None, :]
    ref = common.attend_quant(q, k[0], ks[0], v[0], vs[0], cut)
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32), np.asarray(ref[0], np.float32),
        rtol=0, atol=2 ** -7 * float(jnp.abs(ref[0]).max()))
    assert not np.asarray(got[1], np.float32).any()


@pytest.mark.parametrize("lengths,width,fetched", [
    ([0, 1, 31, 32, 33, 263, 384, 400], 384,
     [0, 32, 32, 32, 64, 288, 384, 384]),
    ([5, 160], 160, [32, 160]),
])
def test_positions_fetched_are_whole_blocks(lengths, width, fetched):
    got = attention_ops.quant_decode_positions(np.asarray(lengths), width)
    np.testing.assert_array_equal(got, fetched)


def test_mask_lengths_is_the_last_visible_position_plus_one():
    mask = np.zeros((4, 1, 2, 12), bool)
    mask[0, 0, 0, :5] = True
    mask[1, 0, 1, 11] = True               # the window's LAST query sees it
    mask[2, 0, 0, [0, 3, 7]] = True        # holes below the length
    np.testing.assert_array_equal(
        np.asarray(attention_ops.mask_lengths(jnp.asarray(mask))),
        [5, 12, 8, 0])


def _lowered_for_tpu(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _shapes(lanes=4, rows=4, heads=HEADS, groups=1, width=128, t=1,
            feature=None):
    sd = jax.ShapeDtypeStruct
    f = feature or common.folded_width(heads // groups, HEAD_DIM)
    return (sd((lanes, heads, t, HEAD_DIM), jnp.bfloat16),
            sd((LAYERS, rows, groups, width, f), jnp.int8),
            sd((LAYERS, rows, heads, width), jnp.float32),
            sd((LAYERS, rows, groups, width, f), jnp.int8),
            sd((LAYERS, rows, heads, width), jnp.float32),
            sd((), jnp.int32), sd((lanes, 1, t, width), jnp.bool_),
            sd((lanes,), jnp.int32))


def _today(q, k, ks, v, vs, layer, mask, lengths, rows=None):
    """What `attend_fn` (models/gpt2.py) ran before the kernel."""
    del lengths
    return common.attend_quant(
        q, common.layer_rows(k, layer, rows),
        common.layer_rows(ks, layer, rows),
        common.layer_rows(v, layer, rows),
        common.layer_rows(vs, layer, rows), mask)


def _layer(q, k, ks, v, vs, layer, mask, lengths, rows=None):
    return common.attend_quant_layer(q, k, ks, v, vs, layer, rows, mask,
                                     lengths)


def test_a_decode_step_over_folded_planes_lowers_to_the_kernel_on_a_tpu():
    text = _lowered_for_tpu(_layer, *_shapes())
    assert "quant_decode" in text and "tpu_custom_call" in text
    # ... and to the two products for every other backend.
    cpu = jax.jit(_layer).lower(*_shapes()).as_text()
    assert "tpu_custom_call" not in cpu
    assert "tpu_custom_call" not in _lowered_for_tpu(_today, *_shapes())


@pytest.mark.parametrize("why,shapes", [
    ("a window of queries", dict(t=3)),
    ("tp groups", dict(heads=4, groups=2)),
    ("an unfolded plane", dict(groups=HEADS, feature=HEAD_DIM)),
    ("a width of no whole blocks", dict(width=48)),
])
def test_engagement_is_shapes_alone(why, shapes):
    """Everything but the decode step over folded planes of one group and
    whole blocks lowers, for a TPU too, to the text it lowered to."""
    args = _shapes(**shapes)
    assert not attention_ops.quant_decode_engages(args[0].shape,
                                                  args[1].shape), why
    assert _lowered_for_tpu(_layer, *args) == _lowered_for_tpu(
        _today, *args).replace("_today", "_layer"), why


def test_a_batch_that_addresses_rows_of_a_wider_cache_takes_the_products():
    """`rows` (a prefill pass's ragged batch) reads its own rows by
    `layer_rows`; the kernel reads lane i's planes at row i."""
    args = _shapes(lanes=2, rows=4)
    rows = jax.ShapeDtypeStruct((2,), jnp.int32)
    with_rows = lambda *a: _layer(*a[:-1], rows=a[-1])  # noqa: E731
    assert "tpu_custom_call" not in _lowered_for_tpu(with_rows, *args, rows)
    # A batch narrower than the planes without `rows` is nobody's form.
    assert not attention_ops.quant_decode_engages(args[0].shape,
                                                  args[1].shape)


# ------------------------- the engine's count of what the kernel fetches

def _engine(**kw):
    from distributed_lms_raft_llm_tpu.engine import (
        EngineConfig, PagedEngine, SamplingParams)

    return PagedEngine(
        EngineConfig(model="tiny", dtype=jnp.float32,
                     sampling=SamplingParams.greedy(max_new_tokens=16),
                     length_buckets=(16, 48), batch_buckets=(1, 2, 4), **kw),
        slots=3, chunk=2, inflight=3, megastep=2, megastep_max=2,
        prefill_chunk_tokens=4)


PROMPTS = ["what is raft?", "hello world", "k", "why a log? " * 3,
           "explain paging", "who leads?", "terms and votes " * 2, "a b c"]


def test_the_host_replays_every_lanes_length_as_the_device_moves_it():
    """`engine_attn_positions_read` is counted from a copy of
    `cache.length` and `active` the host replays a reap at a time
    (`_LaneLengths`), through staging, flips, ends by the host's cap,
    slots handed on, three dispatches in flight and a growth of the cache:
    drained, the copy IS the device's planes, so every lane-step's length
    was the device's."""
    eng = _engine(kv_quant=True)
    assert eng.state.cache.k.shape[-1] != eng.cfg.head_dim  # folded
    for prompt in PROMPTS:
        eng.submit(prompt)
    eng.drain()
    width = eng.state.cache.k.shape[3]
    assert width == 64 and eng._attn_width() == width
    lanes = eng._lanes
    none = np.zeros((0, eng.slots), np.int64)
    assert not lanes.sent  # every dispatch reaped
    lanes.replay(lanes.ops, none, none.astype(bool), none,
                 np.zeros((eng.slots,), np.int64), eng.tokenizer.eos_id,
                 width)  # the kills no dispatch has carried yet
    np.testing.assert_array_equal(lanes.length,
                                  np.asarray(eng.state.cache.length))
    np.testing.assert_array_equal(lanes.active,
                                  np.asarray(eng.state.active))
    counts = eng.pop_loop_stats()[0]
    read, held = (counts["attn_positions_read"],
                  counts["attn_positions_held"])
    # Widths 32 and 64 were served: between a block a lane-step and all.
    assert 32 * counts["lane_steps"] <= held <= 64 * counts["lane_steps"]
    assert BLOCK * counts["lane_steps"] <= read < held
    assert read % BLOCK == 0


def test_an_engine_whose_attention_reads_whole_planes_counts_nothing():
    eng = _engine()  # float planes: `attend`, not `attend_quant_layer`
    assert eng._lanes is None and eng._attn_width() == 0
    eng.submit(PROMPTS[0])
    eng.drain()
    assert not [name for name in eng.pop_loop_stats()[0]
                if name.startswith("attn_positions")]
