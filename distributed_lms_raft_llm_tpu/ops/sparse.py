"""The decode step of a block-sparse attention layer (InfLLM-v2, MiniCPM4's
`sparse_config`): a lane reads the blocks it CHOSE and not its row.

Three Pallas kernels, each named in a device trace by its own name, and the
plain `jax.numpy` between them (`models/minicpm_sala.py` calls them on the
TPU through `jax.lax.platform_dependent`; every other backend runs the
model's general form, which is also the prefill chunk's):

- `sparse_append`: one lane a grid step. The step's new key and value go
  into their group of `stride` positions of the stacked planes, and the
  group's pooled entry is computed anew from its keys, each block read,
  changed and written where it lies (the planes are aliased to the outputs).
  A scatter of the step's [B, Hkv, D] rows into planes the other two kernels
  pin to their default layout made the compiler re-lay both planes, 3.3 GB
  each at MiniCPM-SALA's sizes, twice a layer and step.
- `sparse_select`: one (lane, key head) a grid step. The group's query heads
  [G, D] against the lane's pooled plane [NP, D], read out of the stacked
  plane through a scalar-prefetched layer index; each head's softmax over
  the pooled keys that lie wholly behind the query; the heads summed: [NP]
  float32 a lane and key head. The pooled plane crosses HBM once. What
  follows on those [B, Hkv, NP] numbers is small and stays XLA's
  (`block_scores`, `choose`: the largest score of the pooled keys that
  overlap a block, the blocks always taken, `jax.lax.top_k`).
- `sparse_decode`: one (lane, key head) a grid step. The chosen blocks' keys
  and values are copied out of the stacked K and V planes where they lie in
  HBM into two VMEM buffers, a DMA a block (the planes never enter the
  kernel as blocks: a lane at 32 k reads 64 blocks of 524); one masked
  softmax of the group's [G, D] queries over them; [G, D] out. A lane below
  `dense_len` brings EVERY block up to its position (at most `dense_len /
  block`), which is the dense answer.

Pooled plane (`KVCache.pool`, [La, B, Hkv, NP, D], the cache's dtype): entry
b is the mean of the keys of the b-th GROUP of `stride` positions, [stride
b, stride (b + 1)). A pooled key's window is `2 stride` keys, two groups:
`c_j = (pool[j] + pool[j + 1]) / 2`, and its score is the mean of the two
entries' scores, which the readers take (a product is linear). So an entry
is a function of its own group's keys alone and belongs to them as a key
belongs to its position: a prefix block of `t` tokens carries the `t /
stride` entries of its own groups, and a splice puts them where the keys go,
with no seam to mend. NP is `pool_len(width)`: a width's groups rounded up
to whole lane tiles of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# A block that is always taken scores above every sum of softmaxes.
TAKEN = 1e9
# Entries of the pooled plane a grid step of `sparse_append` holds.
ENTRIES_AT_ONCE = 16


def pool_len(width: int, stride: int) -> int:
    """Entries of the pooled plane of a cache `width` positions wide."""
    return -(-(width // stride) // LANES) * LANES


def visible(entries: int, stride: int, pos: jax.Array) -> jax.Array:
    """[..., NP] bool: the pooled keys c_j (groups j and j + 1) whose last
    key, stride (j + 2) - 1, is at or behind `pos` [...]."""
    j = jnp.arange(entries, dtype=jnp.int32)
    return stride * (j + 2) - 1 <= pos[..., None]


def block_scores(probs: jax.Array, per_block: int, blocks: int) -> jax.Array:
    """Pooled keys' scores [..., NP] (c_j's at j) -> block scores [...,
    blocks]: block m's is the largest of the pooled keys whose window
    overlaps it, c_j for j from `per_block` m - 1 to `per_block` (m + 1) - 1
    (`per_block` = block / stride)."""
    span = per_block * blocks
    probs = jnp.pad(probs, [(0, 0)] * (probs.ndim - 1) + [
        (1, max(span + per_block - probs.shape[-1], 0))])
    best = probs[..., :span]
    for i in range(1, per_block + 1):
        best = jnp.maximum(best, probs[..., i:i + span])
    return best[..., ::per_block]


def choose(scores: jax.Array, pos: jax.Array, *, block: int, topk: int,
           init_blocks: int, window: int) -> jax.Array:
    """The `topk` blocks a query at `pos` [...] reads, [..., topk] int32,
    from block scores [..., NB]: the first `init_blocks` and the blocks of
    the last `window` keys always, the best-scoring others after them (a tie
    goes to the earlier block); a block past the query's own scores below
    every other and, where fewer than `topk` lie behind it, fills the list:
    the keys' own causal mask drops it."""
    m = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    pos = pos[..., None]
    causal = m <= pos // block
    taken = (m < init_blocks) | (m >= jnp.maximum(pos - window + 1, 0)
                                 // block)
    scores = jnp.where(taken, TAKEN, scores)
    scores = jnp.where(causal, scores, -1.0)
    return jax.lax.top_k(scores, topk)[1].astype(jnp.int32)


# ------------------------------------------------------------ sparse_append


def _append_kernel(l_ref, pos_ref, kn_ref, vn_ref, k_ref, v_ref, p_ref,
                   ko_ref, vo_ref, po_ref, *, stride: int):
    del l_ref  # consumed by the BlockSpec index maps
    pos = pos_ref[pl.program_id(0)]
    k, v, p = k_ref[0, 0], v_ref[0, 0], p_ref[0, 0]        # [Hkv, ., D]
    row = jax.lax.broadcasted_iota(jnp.int32, k.shape, 1) == pos % stride
    k = jnp.where(row, kn_ref[0], k)
    ko_ref[0, 0] = k
    vo_ref[0, 0] = jnp.where(row, vn_ref[0], v)
    mean = jnp.sum(k.astype(jnp.float32), axis=1, keepdims=True) / stride
    at = (jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
          == (pos // stride) % ENTRIES_AT_ONCE)
    po_ref[0, 0] = jnp.where(at, mean.astype(p.dtype), p)


def sparse_append(k: jax.Array, v: jax.Array, pool: jax.Array, layer,
                  k_new: jax.Array, v_new: jax.Array, pos: jax.Array, *,
                  stride: int, interpret: bool = False):
    """A decode step's keys and values into the stacked planes, in place:
    k, v [La, B, Hkv, W, D], pool [La, B, Hkv, NP, D], k_new, v_new
    [B, Hkv, 1, D], pos [B] int32 (each lane's own position, below W) ->
    (k, v, pool) with row `pos` of every lane written and the pooled entry
    of its group computed anew from the group's keys."""
    _, b, hkv, _, d = k.shape
    e = ENTRIES_AT_ONCE

    def lane(i, l, p):
        return (i, 0, 0, 0)

    def group(i, l, p):
        return (l[0], i, 0, p[i] // stride, 0)

    def entries(i, l, p):
        return (l[0], i, 0, p[i] // stride // e, 0)

    return pl.pallas_call(
        functools.partial(_append_kernel, stride=stride),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hkv, 1, d), lane),
                pl.BlockSpec((1, hkv, 1, d), lane),
                pl.BlockSpec((1, 1, hkv, stride, d), group),
                pl.BlockSpec((1, 1, hkv, stride, d), group),
                pl.BlockSpec((1, 1, hkv, e, d), entries),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hkv, stride, d), group),
                pl.BlockSpec((1, 1, hkv, stride, d), group),
                pl.BlockSpec((1, 1, hkv, e, d), entries),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (k, v, pool)],
        # Operands 0 and 1 are the prefetched layer index and positions;
        # the planes are updated where they lie.
        input_output_aliases={4: 0, 5: 1, 6: 2},
        name="sparse_append",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32)[None], pos.astype(jnp.int32),
      k_new.astype(k.dtype), v_new.astype(v.dtype), k, v, pool)


# ------------------------------------------------------------ sparse_select


def _select_kernel(l_ref, pos_ref, q_ref, c_ref, o_ref, *, scale: float,
                   stride: int):
    del l_ref  # consumed by the BlockSpec index maps
    pos = pos_ref[pl.program_id(0)]
    q, c = q_ref[0, 0], c_ref[0, 0, 0]                       # [G,D], [NP,D]
    t = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # c_j's score is the mean of its two groups' (entries j and j + 1).
    s = 0.5 * (t + pltpu.roll(t, t.shape[1] - 1, 1))
    j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    vis = stride * (j + 2) - 1 <= pos
    s = jnp.where(vis, s, NEG_INF)
    e = jnp.where(vis, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    o_ref[0, 0] = jnp.sum(p, axis=0, keepdims=True)          # [1, NP]


def sparse_select(pool: jax.Array, layer, q: jax.Array, pos: jax.Array, *,
                  scale: float, stride: int,
                  interpret: bool = False) -> jax.Array:
    """Pooled scores of a decode step: pool [La, B, Hkv, NP, D], q
    [B, Hkv, G, D] (cast to the plane's dtype), pos [B] int32 (the query's
    own position) -> [B, Hkv, NP] float32: at j, the group's heads'
    softmaxes over the visible pooled keys, summed, of c_j."""
    _, b, hkv, np_, d = pool.shape
    g = q.shape[2]
    out = pl.pallas_call(
        functools.partial(_select_kernel, scale=scale, stride=stride),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda i, j, l, p: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, 1, np_, d),
                             lambda i, j, l, p: (l[0], i, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, np_),
                                   lambda i, j, l, p: (i, j, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, 1, np_), jnp.float32),
        name="sparse_select",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32)[None], pos.astype(jnp.int32),
      q.astype(pool.dtype), pool)
    return out[:, :, 0]


# ------------------------------------------------------------ sparse_decode


def _decode_kernel(l_ref, idx_ref, cnt_ref, q_ref, bias_ref, k_hbm, v_hbm,
                   o_ref, kbuf, vbuf, sem, *, scale: float, block: int,
                   gather: int):
    i, j = pl.program_id(0), pl.program_id(1)
    layer, n = l_ref[0], cnt_ref[i]

    @pl.when((i == 0) & (j == 0))
    def _():
        # What a masked slot of the value buffer holds is multiplied by a
        # probability of 0: it must be finite, so the buffers start at 0
        # (later steps leave earlier lanes' keys and values there).
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(slot, blk):
        at = pl.ds(blk * block, block)
        to = pl.ds(slot * block, block)
        return (pltpu.make_async_copy(k_hbm.at[layer, i, j, at, :],
                                      kbuf.at[to, :], sem.at[0]),
                pltpu.make_async_copy(v_hbm.at[layer, i, j, at, :],
                                      vbuf.at[to, :], sem.at[1]))

    def start(slot, carry):
        for c in copies(slot, idx_ref[i, j * gather + slot]):
            c.start()
        return carry

    def wait(slot, carry):
        for c in copies(0, 0):
            c.wait()
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)
    q = q_ref[0, 0]                                           # [G, D]
    s = jax.lax.dot_general(q, kbuf[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(bias_ref[0, 0] == 0.0, s, NEG_INF)          # [G, slots]
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(bias_ref[0, 0] == 0.0, p, 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jax.lax.dot_general(p.astype(vbuf.dtype), vbuf[...],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0] = (o / denom).astype(o_ref.dtype)


def sparse_decode(k: jax.Array, v: jax.Array, layer, q: jax.Array,
                  idx: jax.Array, count: jax.Array, bias: jax.Array, *,
                  scale: float, block: int,
                  interpret: bool = False) -> jax.Array:
    """Attention of a decode step over chosen blocks: k, v the stacked
    planes [La, B, Hkv, W, D]; q [B, Hkv, G, D]; idx [B, Hkv, N] int32 the
    blocks each (lane, key head) reads, of which the first `count` [B] are
    copied; bias [B, Hkv, 1, N block] float32, 0 where slot s of the
    gathered keys (key `idx[s // block] block + s % block`) may be attended
    and `NEG_INF` where not (a slot past `count`, a key past the query)
    -> [B, Hkv, G, D] in q's dtype."""
    _, b, hkv, _, d = k.shape
    g, n = q.shape[2], idx.shape[2]
    slots = n * block
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block=block, gather=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hkv),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda i, j, *_: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, 1, slots), lambda i, j, *_: (i, j, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d), lambda i, j, *_: (i, j, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((slots, d), k.dtype),
                pltpu.VMEM((slots, d), v.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        name="sparse_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32)[None],
      idx.reshape(b, hkv * n).astype(jnp.int32), count.astype(jnp.int32),
      q.astype(k.dtype), bias, k, v)
