"""One in-scan prefill pass serves the oldest staged slots together.

`engine/paged.py` `_prefill_pass` forwards a chunk for each of the oldest
staged slots in ONE pass over the weights, as a ragged batch, and
`_admission_chunk` gives it `PASS_ROWS // prefill_chunk` rows where two
slots or more are staged. What a slot's own positions compute must not
depend on who shares its pass: here the pass of several rows is held, to
the bit and for every served family at its tiny size, to the passes of one
row it replaces (the same function at a width of one: the oldest staged
slot alone, a pass each), with fewer slots staged than a pass has rows
(bare rows, which must write nothing anywhere), exactly as many, and one
more (who waits, by `stage_seq` and never by slot index). The host's half
(`PagedEngine._walk`) takes two flips in one row of the planes.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lms_raft_llm_tpu.engine import (
    EngineConfig,
    PagedEngine,
    SamplingParams,
    paged,
)
from distributed_lms_raft_llm_tpu.engine.paged import _Request
from distributed_lms_raft_llm_tpu.models import registry

C, WIDE = 4, 4          # a chunk's positions; the rows of a wide pass
SLOTS, WIDTH = 7, 32    # five to stage, slot 5 idle, slot 6 live
IDLE, LIVE = 5, 6
LEFT = [1, C - 1, C, C + 1, 2 * C + 3]  # tokens a staged slot has left
CURSORS = [0, 3, 8, 5, 0]               # where its spliced prefix ends
EOS = PAD = 0

FAMILIES = [("tiny", True), ("afmoe-tiny", False), ("axk1-tiny", False),
            ("nemotronh-tiny", False), ("kimilinear-tiny", False)]
IDS = ["gpt2-int8-kv", "afmoe", "axk1", "nemotron_h", "kimi_linear"]


def _random(key, plane, name):
    if plane.dtype == jnp.int8:
        return jax.random.randint(key, plane.shape, -127, 128,
                                  jnp.int32).astype(jnp.int8)
    if name in ("ks", "vs"):
        return jax.random.uniform(key, plane.shape, plane.dtype, 0.005, 0.02)
    return (0.1 * jax.random.normal(key, plane.shape)).astype(plane.dtype)


@pytest.fixture(scope="module", params=FAMILIES, ids=IDS)
def served(request):
    """(family, cfg, the pass of one row, of WIDE rows and the admission
    phase that chooses between them, a state nobody is staged in): every
    plane of every row holds something of its own, slot LIVE is
    mid-answer."""
    preset, quant_kv = request.param
    family, cfg = registry.resolve(preset, jnp.float32)
    if quant_kv:
        cfg = dataclasses.replace(cfg, quant_kv=True)
    params = family.init_params(jax.random.key(0), cfg)
    state = paged._fresh_state(family, cfg, SLOTS, WIDTH)
    keys = iter(jax.random.split(jax.random.key(1), 16))
    cache = state.cache._replace(**{
        name: _random(next(keys), plane, name)
        for name, plane in state.cache._asdict().items()
        if plane is not None and name != "length"})
    state = state._replace(
        cache=cache._replace(length=state.cache.length.at[LIVE].set(9)),
        active=state.active.at[LIVE].set(True),
        tok=state.tok.at[LIVE].set(5),
        stage_len=state.stage_len.at[LIVE].set(4))
    if state.snap_ssm is not None:
        state = state._replace(
            snap_ssm=_random(next(keys), state.snap_ssm, "ssm"),
            snap_conv=_random(next(keys), state.snap_conv, "conv"))

    statics = dict(cfg=cfg, model=family, eos_id=EOS, pad_id=PAD,
                   sampling=SamplingParams.reference_defaults(),
                   prefill_chunk=C)

    def admit(s):
        paged.PASS_ROWS, kept = WIDE * C, paged.PASS_ROWS
        try:
            return paged._admission_chunk(params, s, wide=True, **statics)
        finally:
            paged.PASS_ROWS = kept

    return (family, cfg,
            jax.jit(partial(paged._prefill_pass, params, width=1, **statics)),
            jax.jit(partial(paged._prefill_pass, params, width=WIDE,
                            **statics)),
            jax.jit(admit), state)


def stage(state, cfg, slots):
    """Stage `slots` in that order on the last of LEFT and CURSORS; the
    longest snapshots its state at the end of its first chunk."""
    left, cursors = LEFT[-len(slots):], CURSORS[-len(slots):]
    for seq, (slot, n, cur) in enumerate(zip(slots, left, cursors)):
        ids = jax.random.randint(jax.random.key(100 + slot), (1, WIDTH), 1,
                                 cfg.vocab_size)
        state = paged._stage_program(
            state, slot, ids, cur + n, cur, seq,
            jax.random.key_data(jax.random.key(200 + slot)),
            cur + C if n > 2 * C else 0)
    return state, dict(zip(slots, (-(-n // C) for n in left)))


def drain(program, state, alone=False):
    """Passes until nobody is staged: (state, per pass flipped, firsts,
    served and a routed family's counts). `alone`: a pass sees the oldest
    staged slot and nobody else, whatever its width."""
    passes = []
    while bool(np.any(state.staged)):
        wait = jnp.zeros_like(state.staged)
        if alone:
            oldest = jnp.argmin(jnp.where(
                state.staged, state.stage_seq, jnp.iinfo(jnp.int32).max))
            wait = state.staged & (jnp.arange(SLOTS) != oldest)
        state, *out = program(state._replace(staged=state.staged & ~wait))
        state = state._replace(staged=state.staged | wait)
        passes.append([np.asarray(x) for x in out])
        assert len(passes) < 64
    return state, passes


def schedule(chunks, rows):
    """By hand: the pass at which each slot flips, and each pass's
    (passes, slots, found two or more staged) count, `rows` oldest a pass
    in staging order."""
    todo, flips, served = dict(chunks), {}, []
    while todo:
        now = list(todo)[:rows]
        served.append([1, len(now), int(len(todo) > 1)])
        for slot in now:
            todo[slot] -= 1
            if not todo[slot]:
                flips[slot] = len(served) - 1
                del todo[slot]
    return flips, served


def leaves(state):
    return {jax.tree_util.keystr(path): np.asarray(x) for path, x
            in jax.tree_util.tree_flatten_with_path(state)[0]}


@pytest.mark.parametrize("staged", [1, 2, WIDE, WIDE + 1])
def test_a_wide_pass_equals_the_passes_it_replaces(served, staged):
    family, cfg, narrow, wide, _, state = served
    # A slot restaged at a lower index is the younger: slot 4 is oldest.
    slots = list(range(staged))[::-1]
    start, chunks = stage(state, cfg, slots)
    before = leaves(start)
    one, one_passes = drain(narrow, start)
    got, passes = drain(wide, start)

    # Every plane, scale, latent, state and snapshot row, every first
    # token, length, seen mask and transcript: to the bit.
    want = leaves(one)
    if family.latent_cache:
        # The absorbed attention's products have the batch for their one
        # batch axis, and XLA's CPU backend runs such a product as a plain
        # matrix product where the batch is ONE, summing in another order:
        # a pass of one row differs from a row of a wider pass in the last
        # bit, whoever shares it. So these families are held to the bit
        # to one slot a pass through the SAME program, every other row
        # bare, and to the pass of one row as closely as that allows.
        for name, leaf in leaves(got).items():
            np.testing.assert_allclose(leaf, want[name], rtol=1e-5,
                                       atol=1e-6, err_msg=name)
        one, one_passes = drain(wide, start, alone=True)
        want = leaves(one)
    for name, leaf in leaves(got).items():
        np.testing.assert_array_equal(leaf, want[name], err_msg=name)
    # What was neither staged nor served holds what it held.
    others = [s for s in range(SLOTS) if s not in slots]
    for name in ("k", "v", "ks", "vs", "ssm", "conv"):
        plane = getattr(got.cache, name)
        if plane is not None:
            np.testing.assert_array_equal(
                np.asarray(plane)[:, others],
                before[f".cache.{name}"][:, others], err_msg=name)
    if got.snap_ssm is not None:
        # The longest prompt, staged last, is the one that snapshots.
        kept = [s for s in range(SLOTS) if s != slots[-1]]
        for name in ("snap_ssm", "snap_conv"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name))[:, kept],
                before[f".{name}"][:, kept], err_msg=name)
            assert np.any(np.asarray(getattr(got, name))[:, slots[-1]]
                          != before[f".{name}"][:, slots[-1]])

    # The row of every flip and the passes' counts, as worked out by hand.
    for program_passes, rows in ((one_passes, 1), (passes, WIDE)):
        flips, count = schedule(chunks, rows)
        assert len(program_passes) == len(count)
        assert [p[2][:2].tolist() for p in program_passes] == [
            c[:2] for c in count]
        for slot, at in flips.items():
            column = [bool(p[0][slot]) for p in program_passes]
            assert column == [i == at for i in range(len(count))], slot
        for p in program_passes:
            assert not p[0][others].any()
    # (`one_passes` of a latent family saw one slot staged at a time.)
    assert [p[2][2] for p in passes] == [c[2] for c in count]
    firsts = {slot: int(passes[at][1][slot])
              for slot, at in schedule(chunks, WIDE)[0].items()}
    assert firsts == {slot: int(one_passes[at][1][slot])
                      for slot, at in schedule(chunks, 1)[0].items()}
    assert firsts == {slot: int(got.tok[slot]) for slot in slots}

    if not family.routed:
        return
    # A routed family's counts: a pick a real position and expert picked,
    # never one for a bare row or a pad tail; an expert's seat, and an
    # expert reached, ONCE a pass however many rows share it.
    names = list(family.counters)
    picks, reached, seats = (names.index(n) for n in (
        "moe_picks", "moe_experts_reached", "moe_expert_seats"))
    real = sum(LEFT[-staged:])
    a_token = one_passes[0][3][picks] // min(C, LEFT[-staged:][0])
    a_pass = one_passes[0][3][seats]
    assert a_token > 0 and a_pass > 0
    for program_passes in (one_passes, passes):
        assert sum(p[3][picks] for p in program_passes) == real * a_token
        assert all(p[3][seats] == a_pass for p in program_passes)
        assert all(0 < p[3][reached] <= a_pass for p in program_passes)
    if staged == 1:
        assert ([p[3].tolist() for p in passes]
                == [p[3].tolist() for p in one_passes])
    else:
        # The first wide pass holds every staged slot's first chunk (the
        # oldest WIDE of them): it reaches what any of them reaches alone
        # and no more than all of them together.
        alone = []
        for slot in slots[:WIDE]:
            only, _ = stage(state, cfg, slots)
            only = only._replace(staged=only.staged & (
                jnp.arange(SLOTS) == slot))
            alone.append(int(narrow(only)[-1][reached]))
        assert max(alone) <= passes[0][3][reached] <= min(
            sum(alone), a_pass)
    if family.name == "afmoe":
        le = cfg.num_expert_layers
        assert a_token == cfg.num_experts_per_tok * le
        assert a_pass == cfg.num_experts * le


def test_the_oldest_by_stage_seq_are_served_first(served):
    """WIDE + 1 staged: the first pass serves the WIDE oldest by
    `stage_seq`, and the youngest, restaged into the LOWEST slot index,
    waits for a row however low its index."""
    _, cfg, _, wide, _, state = served
    slots = [4, 3, 2, 1, 0]
    start, _ = stage(state, cfg, slots)
    after, _, _, count, *_ = wide(start)
    moved = np.asarray(after.stage_cursor) - np.asarray(start.stage_cursor)
    assert moved[:5].tolist() == [0, C, C, C, C]
    assert moved[5:].tolist() == [0, 0]
    assert np.asarray(count).tolist() == [1, WIDE, 1]
    # Slots 4, 3 and 2 had a chunk or less left: they flipped, and the
    # next pass has a row for slot 0 beside slot 1's second chunk.
    assert np.asarray(after.staged)[:5].tolist() == [
        True, True, False, False, False]
    again, _, _, count, *_ = wide(after)
    moved = np.asarray(again.stage_cursor) - np.asarray(after.stage_cursor)
    assert moved.tolist() == [C, C, 0, 0, 0, 0, 0]
    assert np.asarray(count).tolist() == [1, 2, 1]


def test_the_pass_is_as_wide_as_what_is_staged_asks_for(served):
    """`_admission_chunk`: nothing staged, nothing runs and nothing moves;
    one slot staged, the pass of one row; two or more, the pass of
    `PASS_ROWS // prefill_chunk` rows, the oldest first."""
    family, cfg, narrow, wide, admit, state = served
    got = admit(state)
    for name, leaf in leaves(got[0]).items():
        np.testing.assert_array_equal(leaf, leaves(state)[name])
    assert not np.asarray(got[1]).any() and np.asarray(got[3]).tolist() == [
        0, 0, 0]
    assert all(not np.asarray(c).any() for c in got[4:])
    for slots, program in (([2], narrow), ([1, 0], wide),
                           ([4, 3, 2, 1, 0], wide)):
        start, _ = stage(state, cfg, slots)
        got, want = leaves(admit(start)), leaves(program(start))
        assert np.asarray(got["[3]"]).tolist() == [
            1, min(len(slots), WIDE), int(len(slots) > 1)]
        for name, leaf in got.items():
            np.testing.assert_array_equal(leaf, want[name], err_msg=name)


def test_two_flips_in_one_row_reach_the_host_each_with_its_own_token():
    """`_walk` reads the `flipped` / `firsts` planes a slot's column at a
    time: two staged requests that flip at the same scan iteration both
    start at that row, each with its own first token, and each observes
    its staged iterations once."""
    eng = PagedEngine(
        EngineConfig(model="tiny", batch_buckets=(1, 2, 4),
                     dtype=jnp.float32, length_buckets=(16,),
                     sampling=SamplingParams.greedy(max_new_tokens=8)),
        slots=4, chunk=2, inflight=2, megastep=2, megastep_max=2)
    pad = eng.tokenizer.pad_id
    new = partial(_Request, prompt_len=4, max_new=8, live=False,
                  submit_time=0.0, popped_time=0.0)
    a, b, c = new(rid=0, tokens=[1] * 4), new(rid=1, tokens=[2] * 4), new(
        rid=2, tokens=[3] * 4, staged_rows=3)
    eng._slot_req = [a, b, c, None]
    flipped = np.zeros((2, 2, 4), bool)
    firsts = np.full((2, 2, 4), pad, np.int32)
    flipped[0, 1, [0, 2]] = True        # row 1: slots 0 AND 2
    firsts[0, 1, [0, 2]] = [11, 13]
    toks = np.full((2, 2, 4), pad, np.int32)
    toks[:, :, 0] = [[90, 21], [22, 23]]  # row 0 is pre-flip filler
    toks[:, :, 2] = [[91, 31], [32, 33]]
    active = np.zeros((2, 4), np.int8)
    active[:, [0, 2]] = 1
    done = eng._walk(toks, None, active, flipped, firsts, [a, b, c, None])
    assert done == []
    assert a.live and a.tokens == [11, 21, 22, 23]
    assert c.live and c.tokens == [13, 31, 32, 33]
    assert not b.live and b.staged_rows == 4 and b.tokens == [2] * 4
    counts, observations, _ = eng.pop_loop_stats()
    assert observations["staged_iterations"] == [1.0, 3.0 + 1.0]
    assert len(observations["prefill_wait"]) == 2
    # Rows before a flip, and every row of the slot still staged.
    assert counts["staged_lane_steps"] == 1 + 1 + 4
