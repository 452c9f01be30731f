"""Ask the TPU v5e's compiler, without a chip, whether it accepts the main
path's kernel and step programs at the widths users run.

The TPU compiler is installed wherever the tests run; it compiles for a
chip that is DESCRIBED (`v5e:2x2`), not attached. A compile that passes
here is not a chip run — nothing executes — but it refuses what the chip
would refuse: a kernel over its VMEM scope, a misaligned block, a program
that does not fit HBM, a sharding that leaves a plane whole on one device.

Everything that touches the topology lives in the module-scoped fixtures
of THIS file: only the xdist worker that runs this file loads the TPU
library (one process at a time may hold it), and every worker collects the
same tests.
"""

import dataclasses
import math
import os
import re
from functools import cache, partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from distributed_lms_raft_llm_tpu import config as config_lib
from distributed_lms_raft_llm_tpu.engine import paged
from distributed_lms_raft_llm_tpu.engine.draft import build_drafts
from distributed_lms_raft_llm_tpu.engine.sampling import SamplingParams
from distributed_lms_raft_llm_tpu.models import quant, registry
from distributed_lms_raft_llm_tpu.parallel import mesh as mesh_lib
from distributed_lms_raft_llm_tpu.parallel import partition
from distributed_lms_raft_llm_tpu.utils import tokenizer as tok_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16 * 1024**3  # one TPU v5e chip

# The megastep's two texts: the program of ONE chunk holds the prefill pass
# of several rows beside the pass of one, a longer rung (2, 4, 8: they
# differ in the outer scan's length alone) the pass of one row and a state
# carried by that scan.
BOTH_RUNGS = pytest.mark.parametrize(
    "chunks", [1, 2], ids=["one-chunk", "two-chunks"])


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _with(tree, shardings):
    """Shape tree -> the same shapes carrying `shardings` (a matching tree,
    or one sharding for every leaf)."""
    if not isinstance(shardings, (dict, tuple, list)):
        one = shardings
        shardings = jax.tree.map(lambda _: one, tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings,
    )


# ------------------------------------------------- the paged step programs

class _Production:
    """The serving configuration of configs/cluster.toml, as shapes: what
    `PagedEngine.__init__` derives, without placing anything on a device."""

    def __init__(self):
        cfg = config_lib.load_config(
            os.path.join(REPO, "configs", "cluster.toml")
        )
        t = cfg.tutoring
        assert (t.model, t.quant, t.kv_quant) == ("gpt2", "int8", True)
        self.t = t
        econf = config_lib.engine_config(cfg)
        self.sampling = econf.sampling
        self.family, mcfg = registry.resolve(
            t.model, econf.dtype, econf.param_dtype
        )
        self.cfg = dataclasses.replace(mcfg, quant_kv=True)
        self.slots = t.slots
        self.bucket = min(
            max(econf.length_buckets),
            self.cfg.max_position_embeddings - self.sampling.max_new_tokens,
        )
        self.width = paged.cfg_tmax(self.cfg, self.sampling, self.bucket)
        tok = tok_lib.load_gpt2_tokenizer()  # no vocab file: byte ids
        self.ids = dict(eos_id=tok.eos_id, pad_id=tok.pad_id)
        self.params = jax.eval_shape(
            lambda: quant.quantize_params(
                self.family.init_params(jax.random.key(0), self.cfg),
                self.family.name,
            )
        )
        self.state = self.fresh_state()
        self.key = jax.eval_shape(lambda: jax.random.key(0))
        self.keys = jax.eval_shape(
            lambda: jax.random.split(jax.random.key(0), 1)
        )
        self.statics = dict(cfg=self.cfg, sampling=self.sampling,
                            model=self.family)

    def fresh_state(self, groups: int = 1):
        """The idle state's shapes; `groups` is the engine's tp ways (the
        folded int8 planes keep that many head groups to shard)."""
        return jax.eval_shape(
            partial(paged._fresh_state, self.family, self.cfg, self.slots,
                    self.width, groups)
        )

    def megastep(self):
        """The fused-admission megastep the production config dispatches
        at every rung; K rides in on the key stack's shape (here K=1)."""
        return jax.jit(
            partial(paged._megastep_program, chunk=self.t.chunk,
                    spec_tokens=0, prefill_chunk=self.t.prefill_chunk_tokens,
                    draft_fn=build_drafts, **self.ids, **self.statics),
            donate_argnums=(1,),
        )


@pytest.fixture(scope="module")
def prod():
    return _Production()


def _device_bytes(ma) -> int:
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_paged_decode_programs_compile_and_fit_one_v5e(one_chip, prod):
    params = _with(prod.params, one_chip)
    state = _with(prod.state, one_chip)
    mega = prod.megastep().lower(
        params, state, _with(prod.keys, one_chip)
    ).compile()
    step = jax.jit(
        partial(paged._step_program, chunk=prod.t.chunk, **prod.ids,
                **prod.statics),
        donate_argnums=(1,),
    ).lower(params, state, _with(prod.key, one_chip)).compile()
    for compiled in (mega, step):
        # Weights + the widest slot cache + temporaries, with room left
        # for the prefix-cache blocks and the other widths' programs.
        assert _device_bytes(compiled.memory_analysis()) < HBM_BYTES // 4


@pytest.mark.parametrize(
    "program", ["stage", "stage_block", "stage_block_run", "export_block"])
def test_paged_admission_programs_compile_and_fit_one_v5e(one_chip, prod,
                                                         program):
    """What an admission dispatches beside the megastep, at production's
    widest cache: arming a slot, splicing one shared block or a run of
    them into its pages, and copying a block out for the tree."""
    from distributed_lms_raft_llm_tpu.engine.prefix_cache import BLOCK_TOKENS
    from distributed_lms_raft_llm_tpu.engine.program_inventory import (
        STAGE_RUN_BLOCKS,
    )

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = _with(prod.state, one_chip)
    i32 = sd((), jnp.int32)
    export = partial(paged._export_block_program, block=BLOCK_TOKENS)
    blk = _with(jax.eval_shape(export, prod.state.cache, 0, 0), one_chip)
    key_data = jax.eval_shape(
        lambda: jax.random.key_data(jax.random.key(0)))
    fn, donate, args = {
        "stage": (paged._stage_program, (0,), (
            state, i32, sd((1, prod.bucket), jnp.int32), i32, i32, i32,
            sd(key_data.shape, key_data.dtype))),
        "stage_block": (paged._stage_block_program, (0,),
                        (state, blk, i32, i32)),
        "stage_block_run": (paged._stage_block_program, (0,), (
            state, (blk,) * STAGE_RUN_BLOCKS, i32, i32, i32)),
        "export_block": (export, (), (state.cache, i32, i32)),
    }[program]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    # The donated state aliases into the output: the splice holds one
    # cache, not two.
    assert _device_bytes(compiled.memory_analysis()) < HBM_BYTES // 4


def test_paged_megastep_tp4_shards_planes_over_four_chips(topo, one_chip,
                                                         prod):
    """The same step on a tp=4 mesh of the described 2x2: the KV planes and
    the sharded weights must arrive as quarters — the check that nothing
    the plane table shards was left whole on one device."""
    mesh = mesh_lib.make_mesh({"tp": 4, "dp": -1}, devices=topo.devices)
    rules = partition.RULES_FOR[prod.family.name]
    p_sh = partition.shardings_for(prod.params, mesh, rules)

    def plane(name):
        return NamedSharding(mesh, partition.PAGED_PLANE_SPECS[name])

    state = prod.fresh_state(groups=4)
    assert state.cache.k.shape[2] == 4
    s_sh = state._replace(
        cache=state.cache._replace(**{
            f: plane(f"cache.{f}") for f in ("k", "v", "ks", "vs", "length")
        }),
        # (a recurrent family's snapshot planes are None for this one)
        **{f: plane(f) for f in state._fields
           if f != "cache" and getattr(state, f) is not None},
    )
    replicated = NamedSharding(mesh, jax.sharding.PartitionSpec())
    args = (_with(prod.params, p_sh), _with(state, s_sh),
            _with(prod.keys, replicated))
    with mesh:
        sharded = prod.megastep().lower(*args).compile()
    whole = prod.megastep().lower(
        _with(prod.params, one_chip), _with(state, one_chip),
        _with(prod.keys, one_chip),
    ).compile()

    def nbytes(x, shape=None):
        n = x.dtype.itemsize
        for d in shape or x.shape:
            n *= d
        return n

    leaves = [x for x in jax.tree.leaves(args)
              if not jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key)]
    per_device = sum(
        nbytes(x, x.sharding.shard_shape(x.shape)) for x in leaves
    )
    split = [x for x in leaves
             if x.sharding.shard_shape(x.shape) != x.shape]
    # What the rules shard is most of the bytes, and each lands as 1/4.
    assert sum(nbytes(x) for x in split) > 0.8 * sum(map(nbytes, leaves))
    assert all(
        4 * nbytes(x, x.sharding.shard_shape(x.shape)) == nbytes(x)
        for x in split
    )
    got = sharded.memory_analysis().argument_size_in_bytes
    one = whole.memory_analysis().argument_size_in_bytes
    assert abs(got - per_device) < 0.05 * per_device, (got, per_device)
    assert got < 0.4 * one, (got, one)
    assert "all-reduce" in sharded.as_text()


# ------------- a share's grouped products: a prefix, and all rows behind it

# A grouped product in a compiled module's text, and its rows.
_PRODUCT = re.compile(r"ragged-dot\S* = bf16\[(\d+),\d+\]")


def _bounded_products(text: str) -> dict:
    """(rows of the first branch's grouped products, rows of the second's)
    -> how many `conditional`s of a compiled module's text have such
    branches: `moe._grouped`'s `lax.cond`, whose first branch runs the
    products over every sorted pick and whose second over the held picks'
    prefix. A branch's products are those in its own computation and in
    what it calls as a fusion; a conditional or a loop inside it is another
    conditional's or nobody's (the admission's `cond` around a whole prefill
    pass counts for nothing here)."""
    calls, rows, pairs, comp = {}, {}, [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            comp = head.group(1)
            calls[comp], rows[comp] = set(), set()
            continue
        if comp is None or not line.startswith(" "):
            continue
        calls[comp] |= set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)",
                                      line))
        product = _PRODUCT.search(line)
        if product:
            rows[comp].add(int(product.group(1)))
        for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
            pairs.append(re.findall(r"%?([\w.\-]+)", group))

    def reached(c, seen):
        if c in seen:
            return set()
        seen.add(c)
        return rows.get(c, set()).union(
            *(reached(d, seen) for d in calls.get(c, ())))

    found = {}
    for branches in pairs:
        got = tuple(tuple(sorted(reached(b, set()))) for b in branches)
        if len(got) == 2 and all(len(r) == 1 for r in got):
            key = (got[0][0], got[1][0])
            found[key] = found.get(key, 0) + 1
    return found


def _product_rows(text: str) -> set:
    """The row counts of every grouped product in a compiled module's
    text; each must be an odd multiple of 32 (`moe.tiled_rows`): the TPU's
    kernel tiles its rows by the largest power of two that divides their
    count, and an expert it visits multiplies a whole tile."""
    rows = set(map(int, _PRODUCT.findall(text)))
    assert rows and all(n % 64 == 32 for n in rows), rows
    return rows


def test_bounded_products_reads_a_module_text():
    text = """
%fused_dot (p: bf16[96,64]) -> bf16[96,32] {
  ROOT %ragged-dot-none.3 = bf16[96,32]{1,0} custom-call(%p, %w, %n)
}
%all_rows (t: (bf16[128,64])) -> (f32[128,32]) {
  %ragged-dot-metadata.1 = s32[4,8]{1,0} custom-call(%n)
  %ragged-dot-none.1 = bf16[128,32]{1,0:T(8,128)(2,1)} custom-call(%x, %w, %n)
  %ragged-dot-none.2 = bf16[128,64]{1,0} custom-call(%y, %v, %n)
}
%prefix (t: (bf16[128,64])) -> (f32[128,32]) {
  %fusion.1 = bf16[96,32]{1,0} fusion(%x), kind=kOutput, calls=%fused_dot
}
%idle (t: (s32[])) -> (s32[]) {
  ROOT %tuple = (s32[]) tuple(%t)
}
%pass (t: (s32[])) -> (s32[]) {
  %conditional.1 = (f32[128,32]{1,0}) conditional(%fits, %a, %b), branch_computations={%all_rows, %prefix}
}
ENTRY %main.1 (a: s32[]) -> s32[] {
  %conditional.2 = (s32[]) conditional(%staged, %a, %b), branch_computations={%idle, %pass}
}
"""
    assert _bounded_products(text) == {(128, 96): 1}
    assert _bounded_products(text.replace("bf16[96,", "bf16[128,")) == {
        (128, 128): 1}
    with pytest.raises(AssertionError):
        _product_rows(text)                  # 128 rows are ONE tile
    assert _product_rows(text.replace("bf16[128,", "bf16[160,")) == {
        96, 160}


# ------------------------- the sampler's selections by their width (PR 56)

def _selection_widths(text: str) -> dict:
    """{"TopK": widths, "sort": widths}: the length of the axis each
    selection of a compiled module's text runs over. The TPU's compiler
    spells `lax.top_k` as a custom call to `TopK` over its operand's last
    axis, or, where it finds the row short or batched, as a `sort` with a
    slice behind it."""
    shapes, found = {}, {"TopK": set(), "sort": set()}
    lines = text.splitlines()
    for line in lines:
        op = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \(?(\w+\[[\d,]*\])", line)
        if op:
            shapes[op.group(1)] = [
                int(d) for d in re.findall(r"\d+", op.group(2).split("[")[1])]
    for line in lines:
        call = re.search(r" custom-call\(%?([\w.\-]+)[,)].*"
                         r"custom_call_target=\"TopK\"", line)
        if call:
            found["TopK"].add(shapes[call.group(1)][-1])
        sort = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = .* sort\(.*dimensions=\{(\d+)\}",
            line)
        if sort:
            found["sort"].add(shapes[sort.group(1)][int(sort.group(2))])
    return found


def test_selection_widths_reads_a_module_text():
    text = """
%fused_computation (param_0.1: f32[16,200192]) -> (f32[16,50], s32[16,50]) {
  %param_0.1 = f32[16,200192]{1,0:T(8,128)} parameter(0)
  %fusion.1 = f32[16,200192]{1,0:T(8,128)} fusion(%param_0.1), kind=kLoop, calls=%fused_computation.1
  ROOT %custom-call.1 = (f32[16,50]{1,0:T(8,128)}, s32[16,50]{1,0:T(8,128)}) custom-call(%fusion.1), custom_call_target="TopK", called_computations={%gt}
}
ENTRY %main.1 (x.1: f32[16,200192]) -> f32[16,50] {
  %reshape.3 = f32[16,6400]{1,0:T(8,128)S(1)} reshape(%fusion.9)
  %custom-call.2 = (f32[16,50]{1,0:T(8,128)S(1)}, s32[16,50]{1,0:T(8,128)S(1)}) custom-call(%reshape.3), custom_call_target="TopK", called_computations={%gt}
  %custom-call.3 = f32[800,128]{1,0:T(8,128)S(1)} custom-call(), custom_call_target="AllocateBuffer"
  %sort = (f32[16,1564]{1,0:T(8,128)}, s32[16,1564]{1,0:T(8,128)S(1)}) sort(%bitcast.1, %iota), dimensions={1}, is_stable=true, to_apply=%gt
  %sort.2 = (f32[4,1,73448]{2,0,1:T(4,128)S(1)}, s32[4,1,73448]{2,0,1:T(4,128)S(1)}) sort(%a, %b), dimensions={2}, to_apply=%gt
  %sort.3 = s32[1024]{0:T(1024)} sort(%c), dimensions={0}, to_apply=%lt
}
"""
    assert _selection_widths(text) == {
        "TopK": {200192, 6400}, "sort": {1564, 73448, 1024}}


# ------------------------------------- Trinity-Mini's cut (models/afmoe.py)

def test_trinity_mini_cut_step_and_megastep_compile_and_fit_one_v5e(one_chip):
    """`trinity-mini-1d4e` at the published widths, from shapes alone, in
    the serving settings of benchmarks/configs/trinity-mini.json (16 slots,
    width 2,688, chunk 16, fused admission at 32 tokens): bfloat16 weights
    of 8.5 GB beside the cache, the grouped expert products as the TPU's
    own kernel, and no copy of a layer's experts into it."""
    family, cfg = registry.resolve("trinity-mini-1d4e", jnp.bfloat16,
                                   jnp.bfloat16)
    sampling = SamplingParams.reference_defaults()
    params = _with(jax.eval_shape(
        lambda: family.init_params(jax.random.key(0), cfg)), one_chip)
    assert sum(x.size for x in jax.tree.leaves(params)) == 4_241_534_720
    state = _with(jax.eval_shape(
        partial(paged._fresh_state, family, cfg, 16, 2688)), one_chip)
    common = dict(chunk=16, eos_id=50256, pad_id=50256, cfg=cfg,
                  sampling=sampling, model=family)
    mega = jax.jit(
        partial(paged._megastep_program, spec_tokens=0, prefill_chunk=32,
                draft_fn=build_drafts, **common), donate_argnums=(1,),
    ).lower(params, state, _with(jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), 1)), one_chip)).compile()
    step = jax.jit(
        partial(paged._step_program, **common), donate_argnums=(1,),
    ).lower(params, state, _with(jax.eval_shape(
        lambda: jax.random.key(0)), one_chip)).compile()
    expert_stack = 128 * 2048 * 1024 * 2
    for compiled in (mega, step):
        ma = compiled.memory_analysis()
        assert _device_bytes(ma) < 0.75 * HBM_BYTES
        # A sliced or copied stack of a layer's experts would be a
        # temporary of 537 MB a projection.
        assert ma.temp_size_in_bytes < 2 * expert_stack
        assert "ragged-dot" in compiled.as_text()
        # Every expert held: no prefix, and no `cond` around the products.
        assert _bounded_products(compiled.as_text()) == {}
    # A decode row's 16 lanes x 8 picks = 128 sorted rows are handed to the
    # products as 160, five tiles of 32 and not one of 128; the one-chunk
    # megastep's prefill passes of 256 and 1,024 rows as 288 and 1,056.
    assert _product_rows(step.as_text()) == {160}
    assert _product_rows(mega.as_text()) == {160, 288, 1056}
    # The sampler's top-50 of 200,192 logits: the 1,564 maxima of groups of
    # 128, the 400 maxima of groups of 16 among the 50 picked groups'
    # 6,400, and 800 candidates. No selection of the program, the first
    # token's rows under `vmap` among them, is as wide as the 4,096 columns
    # from which the compiler calls its `TopK` (the parent: a call over
    # `f32[16,200192]` a decode row, a sort of `f32[4,1,200192]` a pass),
    # and the view the groups are read through is the logits' own bytes:
    # nothing as wide as the vocabulary is copied or padded in the scans.
    for compiled in (mega, step):
        text = compiled.as_text()
        widths = _selection_widths(text)
        assert not widths["TopK"] and max(widths["sort"]) == 1564
        assert {1564, 400, 800} <= widths["sort"]
        for op in ("copy", "pad"):
            assert _copies_inside_loops(text, "f32[16,200192]", op) == []
    assert re.search(r"ragged-dot\S* = bf16\[160,2048\]", step.as_text())


@BOTH_RUNGS
def test_ax_k1_cut_megastep_runs_absorbed_over_the_latent_cache(one_chip,
                                                                chunks):
    """`ax-k1-1d4e-12of192` at the published widths, from shapes alone, in
    the serving settings of benchmarks/configs/ax-k1.json (32 slots, width
    2,688, chunk 16, prefill chunks of 32): 6.98 GB of bfloat16
    weights beside a latent cache of 0.50 GB; decode attention is the
    kernel `mla_decode` over the cache's one plane, nothing holds the
    cache expanded to heads, and the plane is not copied whole inside the
    loops; the held experts go through the TPU's grouped kernel."""
    family, cfg = registry.resolve("ax-k1-1d4e-12of192", jnp.bfloat16,
                                   jnp.bfloat16)
    params = _with(jax.eval_shape(
        lambda: family.init_params(jax.random.key(0), cfg)), one_chip)
    assert sum(x.size for x in jax.tree.leaves(params)) == 3_491_257_344
    state = jax.eval_shape(partial(paged._fresh_state, family, cfg, 32, 2688))
    assert state.cache.v is None
    assert state.cache.k.shape == (5, 32, 1, 2688, 576)
    mega = jax.jit(
        partial(paged._megastep_program, chunk=16, spec_tokens=0,
                prefill_chunk=32, draft_fn=build_drafts, eos_id=50256,
                pad_id=50256, cfg=cfg, model=family,
                sampling=SamplingParams.reference_defaults()),
        donate_argnums=(1,),
    ).lower(params, _with(state, one_chip), _with(jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), chunks)),
        one_chip)).compile()
    ma = mega.memory_analysis()
    assert _device_bytes(ma) < 0.75 * HBM_BYTES
    assert ma.temp_size_in_bytes < 2 * 1024**3
    text = mega.as_text()
    assert "ragged-dot" in text and "mla_decode" in text
    # 12 of 192 experts held: the grouped products run over the first 64
    # of a pass's 256 sorted picks (a decode step's 32 lanes, a prefill
    # pass of one row's 32 positions: a fair router's 16 +- 3.9 held picks
    # and twelve deviations), and over all of them as the fallback; a
    # prefill pass of four rows has 1,024 picks, and runs over the first
    # 160 of them, or over all: in the program of one chunk alone. The
    # products are handed those rows in tiles of 32 (`moe.tiled_rows`): 96
    # for the 64, 288 for the 256, 1,056 for the 1,024; 160 stays.
    wide = {(1056, 160): 4} if chunks == 1 else {}
    assert _bounded_products(text) == {(288, 96): 8, **wide}
    assert _product_rows(text) == {96, 288} | {n for p in wide for n in p}
    for rows in (96, 288):
        assert re.search(rf"ragged-dot\S* = bf16\[{rows},7168\]", text)
    # Keys or values of the 64 heads over the cache's width: [.., 64,
    # 2688, 128 | 192 | 256] or its transpose, for one lane or for all.
    expanded = re.findall(
        r"(?:bf16|f32)\[(?:\d+,)*(?:64,2688|2688,64),(?:128|192|256)\]",
        text)
    assert expanded == []
    for plane in ("bf16[5,32,1,2688,576]", "bf16[5,32,2688,576]"):
        assert plane in text
        assert _copies_inside_loops(text, plane) == []


@BOTH_RUNGS
def test_nemotron3_nano_cut_megastep_updates_the_state_in_place(one_chip,
                                                                chunks):
    """`nemotron3-nano-9l-64of128` at the published widths, from shapes
    alone, in the serving settings of benchmarks/configs/nemotron3-nano.json
    (16 slots, width 2,688, chunk 8, prefill chunks of 32): 6.5 GB
    of bfloat16 weights beside the float32 state planes; the decode step's
    state update is the kernel `ssm_step`, no copy of a whole `ssm` plane
    (the cache's or the snapshot rows') lies inside the scans, and the held
    experts' stacks enter the TPU's grouped kernel as whole buffers: padded
    to whole tiles of 512 (`nemotron_h.pad_experts`), they rest as the
    kernel takes them, and no copy of a stack is made anywhere (unpadded,
    `wu` [64, 2688, 1856] rests D-minor and every dispatch copied all
    four, 638 MB each)."""
    family, cfg = registry.resolve("nemotron3-nano-9l-64of128", jnp.bfloat16,
                                   jnp.bfloat16)
    params = _with(jax.eval_shape(
        lambda: family.init_params(jax.random.key(0), cfg)), one_chip)
    # The published 3,166,244,352 and the experts' padding to [3072, 2048].
    assert sum(x.size for x in jax.tree.leaves(params)) == (
        3_166_244_352 + 4 * 64 * 2 * (3072 * 2048 - 2688 * 1856))
    state = jax.eval_shape(partial(paged._fresh_state, family, cfg, 16, 2688))
    assert state.cache.k.shape == (1, 16, 2, 2688, 128)
    assert state.cache.ssm.shape == state.snap_ssm.shape == (
        4, 16, 64, 64, 128)
    assert state.cache.conv.shape == (4, 16, 3, 6144)
    mega = jax.jit(
        partial(paged._megastep_program, chunk=8, spec_tokens=0,
                prefill_chunk=32, draft_fn=build_drafts, eos_id=50256,
                pad_id=50256, cfg=cfg, model=family,
                sampling=SamplingParams.reference_defaults()),
        donate_argnums=(1,),
    ).lower(params, _with(state, one_chip), _with(jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), chunks)),
        one_chip)).compile()
    ma = mega.memory_analysis()
    assert _device_bytes(ma) < 0.75 * HBM_BYTES
    assert ma.temp_size_in_bytes < 1024**3
    text = mega.as_text()
    assert "ragged-dot" in text and "ssm_step" in text
    # A half of the experts held runs whole (`moe.held_rows`): a decode
    # row's 96 picks, a one-row pass's 192 and the pass of four rows' 768
    # (the program of one chunk alone; K = 2, 4, 8 differ in the outer
    # scan's length alone), so no program has a conditional from a routed
    # layer.
    assert _bounded_products(text) == {}
    # A decode row's 96 rows are three tiles of 32 as they are: its
    # products are the ones they were; the passes' 192 and 768 rows are
    # handed as 224 and 800 (`moe.tiled_rows`).
    for rows, there in ((96, True), (224, True), (800, chunks == 1)):
        assert bool(re.search(
            rf"ragged-dot\S* = bf16\[{rows},3072\]", text)) == there
    wide = {800} if chunks == 1 else set()
    assert _product_rows(text) == {96, 224} | wide
    plane = "f32[4,16,64,64,128]"
    assert plane in text
    assert _copies_inside_loops(text, plane) == []
    for stack in ("bf16[64,3072,2048]", "bf16[64,2048,3072]"):
        assert stack in text
        assert not re.search(re.escape(stack) + r"\S* copy\(", text)


@BOTH_RUNGS
def test_kimi_linear_cut_megastep_updates_both_planes_in_place(one_chip,
                                                               chunks):
    """`kimi-linear-9l-64of256` at the published widths, from shapes alone,
    in the serving settings of benchmarks/configs/kimi-linear.json (16
    slots, width 2,688, chunk 8, prefill chunks of 32): 9.35 GB of
    bfloat16 weights as held beside the float32 state planes and the
    latent; the decode step's state update is the kernel `kda_step` and its
    attention the kernel `mla_decode`, no copy of a whole `ssm` plane (the
    cache's or the snapshot rows') or of the latent plane lies inside the
    scans, and the held experts' stacks, padded to whole tiles of 512
    (`kimi_linear.pad_experts`), enter the TPU's grouped kernel as whole
    buffers: no copy of a stack is made anywhere."""
    family, cfg = registry.resolve("kimi-linear-9l-64of256", jnp.bfloat16,
                                   jnp.bfloat16)
    params = _with(jax.eval_shape(
        lambda: family.init_params(jax.random.key(0), cfg)), one_chip)
    # The published 4,272,540,512 and the experts' padding to [2560, 1024].
    assert sum(x.size for x in jax.tree.leaves(params)) == (
        4_272_540_512 + 8 * 64 * 3 * (2560 - 2304) * 1024)
    state = jax.eval_shape(partial(paged._fresh_state, family, cfg, 16, 2688))
    assert state.cache.k.shape == (2, 16, 1, 2688, 576)
    assert state.cache.v is None
    assert state.cache.ssm.shape == state.snap_ssm.shape == (
        7, 16, 32, 128, 128)
    assert state.cache.conv.shape == (7, 16, 3, 12288)
    mega = jax.jit(
        partial(paged._megastep_program, chunk=8, spec_tokens=0,
                prefill_chunk=32, draft_fn=build_drafts, eos_id=50256,
                pad_id=50256, cfg=cfg, model=family,
                sampling=SamplingParams.reference_defaults()),
        donate_argnums=(1,),
    ).lower(params, _with(state, one_chip), _with(jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), chunks)),
        one_chip)).compile()
    ma = mega.memory_analysis()
    assert _device_bytes(ma) < 0.8 * HBM_BYTES
    assert ma.temp_size_in_bytes < 1024**3
    text = mega.as_text()
    assert "ragged-dot" in text and "kda_step" in text
    assert "mla_decode" in text
    # 64 of 256 experts held: a decode row's grouped products run over
    # the first 96 of its 128 sorted picks (a fair router's 32 +- 4.9 held
    # picks and twelve deviations) inside a `conditional` beside the
    # products over all 128; a prefill pass of one row over 160 of 256,
    # the pass of four rows (the program of one chunk alone) over 432 of
    # 1,024; eight routed layers each. The `cond`s lie inside the scans,
    # and neither plane is copied for them (below).
    # What the products are handed is in tiles of 32 (`moe.tiled_rows`):
    # the prefixes of 96 and 160 as they are, 480 for the 432, and the
    # fallbacks' 128, 256 and 1,024 as 160, 288 and 1,056.
    wide = {(1056, 480): 8} if chunks == 1 else {}
    assert _bounded_products(text) == {(160, 96): 8, (288, 160): 8, **wide}
    assert _product_rows(text) == {96, 160, 288} | {n for p in wide for n in p}
    for rows in (96, 160):
        for columns in (1024, 2560):
            assert re.search(
                rf"ragged-dot\S* = bf16\[{rows},{columns}\]", text)
    for plane in ("f32[7,16,32,128,128]", "bf16[2,16,2688,576]"):
        assert plane in text
        assert _copies_inside_loops(text, plane) == []
    for stack in ("bf16[64,2560,1024]", "bf16[64,1024,2560]"):
        assert stack in text
        assert not re.search(re.escape(stack) + r"\S* copy\(", text)


@BOTH_RUNGS
def test_lfm2_cut_megastep_shifts_the_windows_in_place(one_chip, chunks):
    """`lfm2-8b-a1b-13l` at the published widths, from shapes alone, in the
    serving settings of benchmarks/configs/lfm2-8b-a1b.json (64 slots,
    width 2,816, chunk 8, prefill chunks of 32, answers of 256): 10.42 GB
    of bfloat16 weights as held beside 1.11 GB of keys and values and the
    conv layers' windows; the conv operator's decode step is the kernel
    `shortconv_step`, which the compiler accepts over a bfloat16 plane of
    two rows a slot and which updates it where it lies (the plane is 5 MB:
    the one-row pass parks it in the chip's fast memory and back, which
    is the compiler's to choose). All 32 experts are held: the
    grouped products run over every row, in tiles of 32 (a decode row's
    256 picks as 288, a one-row pass's 128 as 160, the pass of four rows'
    512 as 544), with no conditional from a routed layer, and the stacks,
    their inner width padded to 2,048 (`lfm2.pad_experts`), enter the
    TPU's grouped kernel as whole buffers. The keys' and values' planes
    are carried with a position's 8 heads in one row of 512 and nothing
    copies them in the decode scan or in the pass of four rows; the
    one-row pass of the program of one chunk copies the KEYS' plane twice
    (the compiler schedules that pass's read of a layer's row across the
    next layer's scatter, and lays the conditional's result with the unit
    axis elsewhere; the values' plane, written the same way, has
    neither): PERF.md section 7 carries it as an open item, and this test
    holds it to those two."""
    family, cfg = registry.resolve("lfm2-8b-a1b-13l", jnp.bfloat16,
                                   jnp.bfloat16)
    params = _with(jax.eval_shape(
        lambda: family.init_params(jax.random.key(0), cfg)), one_chip)
    # The published 4,606,249,728 and the experts' padding to 2,048.
    assert sum(x.size for x in jax.tree.leaves(params)) == (
        4_606_249_728 + 12 * 32 * 3 * 2048 * (2048 - 1792))
    state = jax.eval_shape(partial(paged._fresh_state, family, cfg, 64, 2816))
    assert state.cache.k.shape == state.cache.v.shape == (3, 64, 1, 2816, 512)
    assert state.cache.ssm is None and state.snap_ssm is None
    assert state.cache.conv.shape == state.snap_conv.shape == (
        10, 64, 2, 2048)
    sampling = dataclasses.replace(SamplingParams.reference_defaults(),
                                   max_new_tokens=256)
    mega = jax.jit(
        partial(paged._megastep_program, chunk=8, spec_tokens=0,
                prefill_chunk=32, draft_fn=build_drafts, eos_id=50256,
                pad_id=50256, cfg=cfg, model=family, sampling=sampling),
        donate_argnums=(1,),
    ).lower(params, _with(state, one_chip), _with(jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), chunks)),
        one_chip)).compile()
    ma = mega.memory_analysis()
    assert _device_bytes(ma) < 0.8 * HBM_BYTES
    assert ma.temp_size_in_bytes < 1024**3
    text = mega.as_text()
    assert "ragged-dot" in text and "shortconv_step" in text
    assert _bounded_products(text) == {}
    wide = {544} if chunks == 1 else set()
    assert _product_rows(text) == {160, 288} | wide
    for rows in (160, 288):
        assert re.search(rf"ragged-dot\S* = bf16\[{rows},2048\]", text)
    assert "bf16[10,64,2,2048]" in text
    planes = [n for shape in ("bf16[3,64,1,2816,512]", "bf16[3,64,2816,512]",
                              "bf16[1,64,2816,512]", "bf16[64,2816,512]")
              for n in _copies_inside_loops(text, shape)]
    assert len(planes) == (2 if chunks == 1 else 0), planes
    stack = "bf16[32,2048,2048]"
    assert stack in text
    assert not re.search(re.escape(stack) + r"\S* copy\(", text)


@BOTH_RUNGS
def test_minicpm_sala_cut_megastep_reads_chosen_blocks_in_place(one_chip,
                                                                chunks):
    """`minicpm-sala-8l` at the published widths, from shapes alone, in the
    serving settings of benchmarks/configs/minicpm-sala.json (48 slots,
    width 33,536, chunk 8, prefill chunks of 32, answers of 512): 5.64 GB of
    bfloat16 weights beside 3.3 GB of keys and values, their pooled plane
    and the float32 Lightning state; the decode step's selection, attention
    and state update are the kernels `sparse_select`, `sparse_decode` and
    `lightning_step`, and no copy of a whole key, value, pooled or state
    plane lies inside the scans."""
    family, cfg = registry.resolve("minicpm-sala-8l", jnp.bfloat16,
                                   jnp.bfloat16)
    params = _with(jax.eval_shape(
        lambda: family.init_params(jax.random.key(0), cfg)), one_chip)
    assert sum(x.size for x in jax.tree.leaves(params)) == 2_820_545_280
    state = jax.eval_shape(partial(paged._fresh_state, family, cfg, 48, 33536))
    assert state.cache.k.shape == state.cache.v.shape == (
        2, 48, 2, 33536, 128)
    assert state.cache.pool.shape == (2, 48, 2, 2176, 128)
    assert state.cache.ssm.shape == state.snap_ssm.shape == (
        6, 48, 32, 128, 128)
    assert state.cache.conv is None and state.snap_conv is None
    mega = jax.jit(
        partial(paged._megastep_program, chunk=8, spec_tokens=0,
                prefill_chunk=32, draft_fn=build_drafts, eos_id=50256,
                pad_id=50256, cfg=cfg, model=family,
                sampling=SamplingParams.reference_defaults(
                    max_new_tokens=512)),
        donate_argnums=(1,),
    ).lower(params, _with(state, one_chip), _with(jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), chunks)),
        one_chip)).compile()
    ma = mega.memory_analysis()
    assert _device_bytes(ma) < 0.9 * HBM_BYTES
    text = mega.as_text()
    for kernel in ("sparse_select", "sparse_decode", "lightning_step"):
        assert kernel in text
    for plane in ("bf16[2,48,2,33536,128]", "bf16[2,48,2,2176,128]",
                  "f32[6,48,32,128,128]"):
        assert plane in text
        assert _copies_inside_loops(text, plane) == []
    # The sampler's top-50 of 73,448 logits: 574 group maxima, 400 among
    # the picked groups' 6,400, 800 candidates (the parent's text called
    # `TopK` over `f32[48,73448]` and sorted `f32[4,1,73448]` for a pass's
    # first tokens). The vocabulary is no multiple of 128, so the 24
    # columns of `-inf` that fill the last group are a `pad` of their own;
    # no copy beside it.
    widths = _selection_widths(text)
    assert not widths["TopK"] and max(widths["sort"]) == 800
    assert {574, 400, 800} <= widths["sort"]
    for logits in ("f32[48,73448]", "f32[48,73472]"):
        assert _copies_inside_loops(text, logits) == []


@pytest.mark.parametrize(
    "program", ["export_run", "stage_stored_run", "block_of_a_run"])
def test_stored_run_programs_compile_and_write_in_place_on_one_v5e(one_chip,
                                                                  program):
    """What a 32,768-token reader's admission and publish launch since the
    tree holds a long edge as stored runs, at `minicpm-sala.reader-herd`'s
    cache (48 slots x 33,536, keys, values and the pooled plane): a run of
    STORED_RUN_BLOCKS blocks cut out of a slot, the run written into a slot
    (the donated state aliases into the output, and nothing the size of a
    plane is copied or held beside it), and a block cut out of a run for a
    cache too narrow for it."""
    from distributed_lms_raft_llm_tpu.engine.prefix_cache import BLOCK_TOKENS
    from distributed_lms_raft_llm_tpu.engine.program_inventory import (
        STORED_RUN_BLOCKS, width_holds_stored_run)
    from distributed_lms_raft_llm_tpu.models.common import KVCache

    family, cfg = registry.resolve("minicpm-sala-8l", jnp.bfloat16,
                                   jnp.bfloat16)
    assert width_holds_stored_run(33536, BLOCK_TOKENS, STORED_RUN_BLOCKS)
    assert not width_holds_stored_run(768, BLOCK_TOKENS, STORED_RUN_BLOCKS)
    state = _with(jax.eval_shape(
        partial(paged._fresh_state, family, cfg, 48, 33536)), one_chip)
    tokens = STORED_RUN_BLOCKS * BLOCK_TOKENS
    export_run = partial(paged._export_block_program, block=tokens,
                         pool_stride=cfg.pool_stride)
    run = _with(jax.eval_shape(export_run, state.cache, 0, 0), one_chip)
    assert run.k.shape == run.v.shape == (2, 1, 2, tokens, 128)
    assert run.pool.shape == (2, 1, 2, tokens // cfg.pool_stride, 128)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    fn, donate, args = {
        "export_run": (export_run, (), (state.cache, i32, i32)),
        "stage_stored_run": (paged._stage_block_program, (0,),
                             (state, run, i32, i32, i32)),
        "block_of_a_run": (
            partial(paged._export_block_program, block=BLOCK_TOKENS,
                    pool_stride=cfg.pool_stride), (),
            (KVCache(k=run.k, v=run.v, length=None, pool=run.pool),
             i32, i32)),
    }[program]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    ma = compiled.memory_analysis()
    assert _device_bytes(ma) < 0.9 * HBM_BYTES
    # A key plane is 3.3 GB: temporaries stay the size of a few runs.
    assert ma.temp_size_in_bytes < 64 * 2 ** 20
    if program == "stage_stored_run":
        # Keys and values 3.3 GB, the Lightning state and its snapshot
        # rows 1.2 GB: all of the state comes back in its own buffers.
        assert ma.alias_size_in_bytes > 4.5e9


# ------------------- a prefill chunk touches its slot's pages in place

def _copies_inside_loops(text: str, shape: str, op: str = "copy") -> list:
    """Names of the `copy` operations (or another operation's, by its
    name) of `shape` that a compiled module's text holds in a computation
    some `while` or `conditional` reaches (its body, condition or
    branches, and whatever those call). Copies in the entry computation
    run once a dispatch and are not listed."""
    calls, copies, roots, comp = {}, {}, set(), None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            comp = head.group(1)
            calls[comp], copies[comp] = set(), []
            continue
        if comp is None or not line.startswith(" "):
            continue
        called = set(re.findall(
            r"(?:calls|body|condition|to_apply|true_computation|"
            r"false_computation)=%?([\w.\-]+)", line))
        for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
            called.update(re.findall(r"%?([\w.\-]+)", group))
        calls[comp] |= called
        # A `while` or a `conditional` (their result is a tuple, whose
        # text has spaces: told by their attributes, not by the opcode).
        if re.search(r"\b(?:body|branch_computations|true_computation)=",
                     line):
            roots |= called
        found = re.match(
            rf"\s*(?:ROOT )?%?([\w.\-]+) = (\S+) {op}\(", line)
        if found and found.group(2).startswith(shape):
            copies[comp].append(found.group(1))
    inside, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c not in inside:
            inside.add(c)
            todo.extend(calls.get(c, ()))
    return sorted(n for c in inside for n in copies.get(c, ()))


def test_copies_inside_loops_reads_a_module_text():
    text = """
%fused_copy (p: s8[2,4]) -> s8[2,4] {
  ROOT %copy.1 = s8[2,4]{0,1} copy(%p)
}
%branch_1 (t: (s8[2,4])) -> (s8[2,4]) {
  %copy.2 = s8[2,4]{0,1:T(8,128)(4,1)} copy(%x)
  %copy.3 = s8[2,8]{0,1} copy(%y)
  %fusion.1 = s8[2,4]{1,0} fusion(%copy.2), kind=kLoop, calls=%fused_copy
}
%branch_0 (t: (s8[2,4])) -> (s8[2,4]) {
  ROOT %tuple = (s8[2,4]{1,0}) tuple(%t)
}
%body (c: (s8[2,4])) -> (s8[2,4]) {
  %conditional.1 = (s8[2,4]{1,0}, s32[]) conditional(%i, %a, %b), branch_computations={%branch_0, %branch_1}
}
%cond (c: (s8[2,4])) -> pred[] {
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}
ENTRY %main.1 (a: s8[2,4]) -> s8[2,4] {
  %copy.4 = s8[2,4]{0,1} copy(%a)
  %while.1 = (s8[2,4]{1,0}, s32[]) while(%copy.4), condition=%cond, body=%body
}
"""
    assert _copies_inside_loops(text, "s8[2,4]") == ["copy.1", "copy.2"]
    assert _copies_inside_loops(text, "s8[2,8]") == ["copy.3"]
    assert _copies_inside_loops(text, "s8[4,2]") == []


@cache
def _benchmark_megastep(one_chip, preset, quant_kv, width, chunks):
    """(compiled, state shapes) of a benchmark configuration's megastep
    (16 slots, chunk 16, prefill chunks of 32, K = `chunks`) for a described
    v5e, lowered from shapes alone: the parameters and results carry the
    layouts the runtime gives arrays at rest, which is what one dispatch
    hands the next."""
    family, cfg = registry.resolve(preset, jnp.bfloat16, jnp.bfloat16)
    init = partial(family.init_params, jax.random.key(0), cfg)
    if quant_kv:
        cfg = dataclasses.replace(cfg, quant_kv=True)
        params = jax.eval_shape(
            lambda: quant.quantize_params(init(), family.name))
    else:
        params = jax.eval_shape(init)
    state = jax.eval_shape(
        partial(paged._fresh_state, family, cfg, 16, width))
    compiled = jax.jit(
        partial(paged._megastep_program, chunk=16, spec_tokens=0,
                prefill_chunk=32, draft_fn=build_drafts, eos_id=50256,
                pad_id=50256, cfg=cfg, model=family,
                sampling=SamplingParams.reference_defaults()),
        donate_argnums=(1,),
    ).lower(
        _with(params, one_chip), _with(state, one_chip),
        _with(jax.eval_shape(
            lambda: jax.random.split(jax.random.key(0), chunks)),
            one_chip),
    ).compile()
    return compiled, state


@BOTH_RUNGS
@pytest.mark.parametrize("preset,quant_kv,width,max_temps", [
    # int8 weights and int8 K/V, as benchmarks/configs/gpt2-xl.json serves
    # it. 5.80 GB of temporaries while a chunk sliced its slot's pages out
    # and back (two slot-major copies of 1.26 GB among them), 3.23 while
    # the scans carried the planes padded (the test below), 0.90 since.
    ("gpt2-xl", True, 384, 4 * 1024**3),
    ("trinity-mini-1d4e", False, 2688, None),
])
def test_megastep_touches_a_staged_slots_pages_in_place(
        one_chip, preset, quant_kv, width, max_temps, chunks):
    """The megastep of both benchmark configurations (16 slots, chunk 16,
    prefill chunks of 32) for a described v5e: no copy of a whole K
    or V plane inside the scans or the staged branch. A chunk that reaches
    its slot's pages through a private `[L, 1, H, W, Dh]` cache makes the
    compiler relay both planes slot-major and back, four whole-plane
    copies for 32 tokens. What the entry computation copies, once a
    dispatch, is the next test's where the planes are GPT-2's."""
    compiled, state = _benchmark_megastep(
        one_chip, preset, quant_kv, width, chunks)
    k = state.cache.k
    plane = f"{'s8' if quant_kv else 'bf16'}[{','.join(map(str, k.shape))}]"
    text = compiled.as_text()
    assert plane in text
    assert _copies_inside_loops(text, plane) == []
    if max_temps is not None:
        assert compiled.memory_analysis().temp_size_in_bytes < max_temps


# ------------------- gpt2-xl's int8 planes tile without padding

def _tiled_bytes(shape: str) -> tuple:
    """(bytes as tiled, bytes of the elements) of an `s8` array as a
    compiled module writes it, `s8[48,16,1,384,1664]{4,3,1,0,2:T(8,128)
    (4,1)}`: four int8 rows to a sublane, so the tile covers (32, 128)
    elements of the two minor-most dimensions."""
    dims, order = re.match(
        r"s8\[([\d,]+)\]\{([\d,]+):T\(8,128\)\(4,1\)", shape).groups()
    dims = [int(d) for d in dims.split(",")]
    order = [int(d) for d in order.split(",")]
    tiled = 1
    for i, d in enumerate(dims):
        tile = {order[0]: 128, order[1]: 32}.get(i, 1)
        tiled *= -(-d // tile) * tile
    return tiled, math.prod(dims)


def test_tiled_bytes_reads_a_layout():
    # The parent's planes inside its scans: (25, 64) under a (32, 128) tile.
    tiled, held = _tiled_bytes("s8[48,16,25,384,64]{4,2,3,1,0:T(8,128)(4,1)}")
    assert (held, round(tiled / held, 2)) == (48 * 16 * 25 * 384 * 64, 2.56)
    tiled, held = _tiled_bytes("s8[48,16,1,384,1664]{4,3,1,0,2:T(8,128)(4,1)}")
    assert tiled == held


@BOTH_RUNGS
def test_gpt2_xl_int8_planes_ride_the_scans_unpadded(one_chip, chunks):
    """gpt2-xl's megastep at int8 K/V, 16 slots, width 384, for a
    described v5e. Every whole `s8` plane in the module (the parameters,
    what the two `while` loops carry, the results) is tiled at most 5%
    over the bytes of the heads it holds; the parameters and the results
    have the layout the loops carry, the feature axis minor-most and the
    positions next (a plane whose feature axis is no multiple of 128 lanes
    is put positions-minor at rest, and every dispatch then relays it on
    its way in and out); no whole-plane copy is left anywhere; the scale
    planes are nowhere heads-minor (25 of 128 lanes, and a relay of every
    layer's slice for the scores: what a scatter of their columns brings);
    and the temporaries are under 1.8 GB (3.23 GB with the planes `[L, B,
    H, T, Dh]`, which the scans carried tiled over (25, 64): 2.56 times
    their bytes; 0.67 now). A change that brings the padded tile or the
    relays back fails here before it reaches the chip."""
    compiled, state = _benchmark_megastep(
        one_chip, "gpt2-xl", True, 384, chunks)
    text = compiled.as_text()
    k = state.cache.k
    assert k.shape == (48, 16, 1, 384, 1664) and k.dtype == jnp.int8
    held = 48 * 16 * 25 * 384 * 64
    planes = set(re.findall(r"s8\[48,16,[\d,]+\]\{[^}]*\}", text))
    assert planes
    for plane in planes:
        tiled, _ = _tiled_bytes(plane)
        assert tiled <= 1.05 * held, plane
        order = re.search(r"\{([\d,]+):", plane).group(1).split(",")
        dims = plane[3:plane.index("]")].split(",")
        minor = [int(dims[int(i)]) for i in order if dims[int(i)] != "1"]
        assert minor[:2] == [1664, 384], plane
    copies = [line.strip() for line in text.splitlines()
              if re.search(r"= s8\[48,16,[\d,]+\]\S* copy(-start)?\(", line)]
    assert copies == []
    scales = set(re.findall(r"f32\[48,16,25,384\]\{([\d,]+):", text))
    assert scales and all(order.startswith("3,") for order in scales), scales
    assert compiled.memory_analysis().temp_size_in_bytes < 1.8e9


@BOTH_RUNGS
def test_gpt2_xl_decode_attention_is_one_kernel_over_live_rows(one_chip,
                                                              chunks):
    """gpt2-xl's megastep as `test_gpt2_xl_int8_planes_ride_the_scans_
    unpadded` compiles it: the decode step's layer body holds ONE Mosaic
    call, `quant_decode` (ops/attention.py: each lane's live rows of K and
    V, read where the planes lie), and neither of `attend_quant`'s two
    products over a layer's whole planes is left anywhere in the module
    (the in-scan prefill chunk unfolds its rows: other products). The
    kernel's view of the planes, a layer's lanes one after the other, is a
    bitcast of what the scans carry: tiled (32, 128) over positions and
    features, and nowhere copied."""
    compiled, _ = _benchmark_megastep(one_chip, "gpt2-xl", True, 384, chunks)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, [line[:120] for line in calls]
    assert re.search(r"/decode/while/body/[^\"]*quant_decode/pallas_call",
                     calls[0]), calls[0][:400]
    for product in ("bghf,bgsf->bghs", "bghs,bgsf->bghf"):
        assert product not in text, product
    views = set(re.findall(r"s8\[768,384,1664\]\{[^}]*\}", text))
    assert views and all(
        view.endswith("{2,1,0}")                      # the call's constraint
        or view.endswith("{2,1,0:T(8,128)(4,1)}") for view in views), views
    assert not [line for line in text.splitlines()
                if re.search(r"= s8\[768,384,1664\]\S* copy(-start)?\(", line)]
    assert _copies_inside_loops(text, "s8[768,384,1664]") == []
