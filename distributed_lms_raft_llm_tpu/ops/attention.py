"""Pallas TPU kernels: fused single-token (decode) attention, over a latent
(MLA) cache (`latent_decode_attention`) and over folded int8 key and value
planes of which each lane's live positions alone are read
(`quant_decode_attention`, at the end of the file).

The autoregressive decode step is HBM-bandwidth-bound: every step streams
the whole cache once per layer. XLA compiles the absorbed form's two
products into separate score and weighted-sum fusions with a f32
[B, H, S] intermediate between them, and reads the latent rows twice;
`latent_decode_attention` computes q·C^T → masked softmax → ·C in one
pass per cache row, so the row crosses HBM exactly once per layer.

The kernel reads the layer's rows directly out of the STACKED plane
([L, B, S, C], the scan carry) via a scalar-prefetched layer index —
slicing the layer out first (`dynamic_index_in_dim`) and handing pallas
the slice costs an HBM copy per layer.

Scope: decode only (one query token per row). Prefill and training keep
the XLA einsum path — there the query dimension is large, the MXU is busy,
and XLA's tiling is already the right schedule.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# VMEM the cache block of one grid step may take, double-buffering
# included. A TPU v5e core scopes a kernel to 16 MiB; the rest is left to
# the q/bias/out blocks and the f32 score and probability rows.
_KV_VMEM_BUDGET = 12 * 1024 * 1024


def _latent_decode_kernel(l_ref, q_ref, c_ref, bias_ref, o_ref, *,
                          scale: float):
    """One cache row: all heads' absorbed queries [H, C] against the row's
    latent [S, C], which is keys and values both. The row crosses HBM once;
    scores and softmax in f32."""
    del l_ref  # consumed by the BlockSpec index maps
    q, c = q_ref[0], c_ref[0, 0]
    sc = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [H, S]
    sc = sc * scale + bias_ref[0]
    p = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True))
    denom = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [H, C]
    o_ref[0] = (o / denom).astype(o_ref.dtype)


def latent_decode_fits(s: int, c: int, itemsize: int) -> bool:
    """Whether one row's latent [S, C], double-buffered at whole 128-lane
    tiles, fits the kernel's VMEM budget (2,688 x 576 bfloat16 does: 6.9
    MB); `models/mla.py` refuses a decode step over a longer cache."""
    return 2 * s * -(-c // 128) * 128 * itemsize <= _KV_VMEM_BUDGET


def latent_decode_attention(q: jax.Array, plane: jax.Array, layer: int,
                            bias: jax.Array, scale: float, *,
                            interpret: bool = False) -> jax.Array:
    """Fused decode attention over one layer of a latent (MLA) cache, in
    the absorbed form: keys and values are the same cached rows.

    q      [B, H, C] — a decode step's queries folded into the latent
           ([q_nope Wuk^T | q_rope]; models/mla.py)
    plane  [L, B, S, C] — the stacked latent cache, [c_kv | k_rope]
    layer  which layer's rows to attend over
    bias   [B, 1, S] f32 — additive mask (0 = attend, NEG_INF = not)
    returns softmax(q . plane^T * scale + bias) . plane, [B, H, C] in q's
    dtype: the caller keeps the columns that are values (c_kv's).

    The grid is the batch: one step reads one row's [S, C] once, where
    XLA's two products read it twice, and relaid the whole plane
    slot-minor first (C = 576 is four and a half lane tiles; by the
    compiler's own text for a described v5e, a copy of all layers' plane
    after every layer's scatter). The row is read out of the STACKED plane
    through a scalar-prefetched layer index.
    """
    b, h, c = q.shape
    s = plane.shape[2]
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, c), lambda i, l: (i, 0, 0)),
                pl.BlockSpec((1, 1, s, c), lambda i, l: (l[0], i, 0, 0)),
                pl.BlockSpec((1, 1, s), lambda i, l: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, c), lambda i, l: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, c), q.dtype),
        name="mla_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32)[None], q, plane, bias)


def mask_to_bias(mask: jax.Array) -> jax.Array:
    """[B, 1, T, S] boolean attend-mask -> [B, 1, S] additive f32 bias
    (layer-invariant: compute once per decode step, outside the layer scan)."""
    return jnp.where(mask[:, 0, 0, :], 0.0, NEG_INF).astype(jnp.float32)[
        :, None, :
    ]


def mask_lengths(mask: jax.Array) -> jax.Array:
    """[B, 1, T, S] boolean attend-mask -> [B] int32: the last position a
    row's mask lets it see, plus one (0 where it sees nothing). Layer-
    invariant, like `mask_to_bias`: once a decode step, outside the layer
    scan."""
    past = jnp.arange(1, mask.shape[-1] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(mask[:, 0], past, 0), axis=(1, 2))


# --------------------------------------------------------------- quant_decode

# Positions a fetch: an int8 tile's 32 sublanes, so a copy moves whole tiles
# of the plane.
QUANT_DECODE_BLOCK = 32
# Positions the products' forms step by and lanes a product takes (both
# below), and the buffers a plane's copies rotate through: two lanes' rows
# in flight beside the one computed on (one: 28 us a layer of the cell, not
# 25; three: no better).
_ROWS_STEP, _LANES_A_PRODUCT, _DEPTH = 128, 512, 3


def quant_decode_engages(q_shape, plane_shape) -> bool:
    """Whether a decode step's attention over folded int8 planes is
    `quant_decode_attention`'s, by its operands' shapes alone: one query a
    row ([B, H, 1, Dh]) against stacked planes [L, B, 1, T, F] whose rows
    are folded (F is not Dh), one group, a width of whole blocks."""
    b, _, t, head_dim = q_shape
    return (t == 1 and plane_shape[1] == b and plane_shape[2] == 1
            and plane_shape[-1] != head_dim
            and plane_shape[3] % QUANT_DECODE_BLOCK == 0)


def quant_decode_positions(lengths, width: int):
    """What `quant_decode_attention` fetches of a lane's `width` positions,
    by its length (numpy or jax integers): whole blocks."""
    blk = QUANT_DECODE_BLOCK
    return ((lengths + blk - 1) // blk * blk).clip(0, width)


def _quant_decode_kernel(l_ref, len_ref, q_ref, lanes_ref, ks_ref, vs_ref,
                         mask_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, ks_scr,
                         vs_scr, sem, *, heads: int, head_dim: int,
                         total: int):
    """One lane a grid step: its live rows of K and V, copied while the
    lanes before it compute; scores, a float32 softmax over the live row
    and the values, `attend_quant`'s folded arm term for term."""
    lane = pl.program_id(0)
    row0, (depth, t, f) = l_ref[0] * total, kbuf.shape
    blk, dtype = QUANT_DECODE_BLOCK, q_ref.dtype

    def runs(lane, slot):
        """(wanted, K's copy, V's copy) for a lane's live blocks, in runs
        of a power of two blocks each: a run a set bit of the count,
        longest first, so a width of 12 blocks is four copies a plane at
        the most."""
        n = (len_ref[lane] + blk - 1) // blk
        out = []
        for bit in reversed(range((t // blk).bit_length())):
            run = blk << bit
            if run > t:
                continue
            at = pl.ds(pl.multiple_of((n >> (bit + 1) << (bit + 1)) * blk,
                                      blk), run)
            out.append(((n >> bit) & 1 == 1, *(
                pltpu.make_async_copy(
                    hbm.at[row0 + lane, at, :], buf.at[slot, at, :],
                    sem.at[slot, p])
                for p, (hbm, buf) in enumerate(((k_hbm, kbuf),
                                                (v_hbm, vbuf))))))
        return out

    def each(lane, slot, do):
        for wanted, *pair in runs(lane, slot):
            @pl.when(wanted)
            def _(pair=pair):
                for copy in pair:
                    do(copy)

    @pl.when(lane == 0)
    def _():
        # Rows past the heads' are multiplied by a query of zeros: they
        # must be finite.
        ks_scr[...] = jnp.zeros_like(ks_scr)
        vs_scr[...] = jnp.zeros_like(vs_scr)

    # Lane `depth - 1` ahead starts its copies now; the first lane starts
    # its own and those of the lanes between as well. (One loop, so that
    # the copies' code is traced and lowered once for starting and once
    # for waiting: sixteen megasteps lower this kernel at every start.)
    def start(ahead, carry):
        each(ahead, ahead % depth, lambda copy: copy.start())
        return carry

    jax.lax.fori_loop(jnp.where(lane == 0, 0, lane + depth - 1),
                      jnp.minimum(lane + depth, total), start, 0)
    slot, n = lane % depth, len_ref[lane]
    each(lane, slot, lambda copy: copy.wait())
    lanes = lanes_ref[...].astype(jnp.float32)                # [Hp, F]
    q_rows = (q_ref[0].astype(jnp.float32) * lanes).astype(dtype)
    ks_scr[0:heads, :] = ks_ref[0, 0]
    vs_scr[0:heads, :] = vs_ref[0, 0]
    tiles = [slice(c, min(c + _LANES_A_PRODUCT, f))
             for c in range(0, f, _LANES_A_PRODUCT)]

    def rows_of(buf, rows, tile):
        """int8 rows as the products take them. Ten vector operations a
        tile of 32 rows and 128 lanes, the kernel's largest cost beside
        the matrix unit's: it is written `_LANES_A_PRODUCT` lanes at a
        time, next to the product that takes them, so that the compiler
        runs the one under the other (converted whole first, in a loop of
        its own, a layer of the cell took 32 us and not 25; 128 lanes at a
        time run no faster and are four times the operations to trace and
        lower at every start)."""
        return buf[slot, 0:rows, tile].astype(jnp.float32).astype(dtype)

    @pl.when(n <= 0)
    def _():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    # One form of the products and the softmax for every `_ROWS_STEP`
    # positions of live blocks: a tile of the matrix unit. (Steps of 64 and
    # of 32 gained 0.6 us of a layer's 25 and doubled, and doubled again,
    # what every one of an engine's sixteen megasteps traces and lowers at
    # a start: `setup_s`.)
    fetched = (n + blk - 1) // blk * blk
    steps = [*range(0, t, _ROWS_STEP), t]
    for below, rows in zip(steps, steps[1:]):
        @pl.when((fetched > below) & (fetched <= rows))
        def _(rows=rows):
            s = sum(jax.lax.dot_general(
                q_rows[:, c], rows_of(kbuf, rows, c),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) for c in tiles)
            s = s * ks_scr[:, 0:rows]                         # [Hp, rows]
            # `x / sqrt(Dh)` as `attend_quant` writes it; the same bits
            # by a product where the root is a power of two (Dh = 64).
            root = math.sqrt(head_dim)
            s = s * (1.0 / root) if root == 2 ** round(
                math.log2(root)) else s / root
            pos = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            # Rows past the blocks copied hold an earlier lane's int8 or
            # nothing yet: their scores are never looked at (a select),
            # and their values meet a probability of exactly 0.
            s = jnp.where((mask_ref[0][:, 0:rows] != 0) & (pos < n), s,
                          NEG_INF)
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            p = e * pl.reciprocal(jnp.sum(e, axis=-1, keepdims=True))
            p = (p * vs_scr[:, 0:rows]).astype(dtype)
            # Each lane of the row keeps its own head's sum.
            o_ref[0] = jnp.concatenate([jnp.sum(
                jax.lax.dot_general(
                    p, rows_of(vbuf, rows, c), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * lanes[:, c],
                axis=0, keepdims=True) for c in tiles],
                axis=1).astype(o_ref.dtype)


def quant_decode_attention(q: jax.Array, lanes: jax.Array, k: jax.Array,
                           ks: jax.Array, v: jax.Array, vs: jax.Array,
                           layer, mask: jax.Array, lengths: jax.Array, *,
                           head_dim: int,
                           interpret: bool = False) -> jax.Array:
    """A decode step's attention over one layer of folded int8 planes,
    reading of each lane the positions its context holds and nothing past
    them (`models/common.py` `attend_quant`'s folded arm is its reference
    and every other backend's form).

    q        [B, 1, F] — a step's queries, each row's heads folded side by
             side (`fold_heads`)
    lanes    [H, F] — ones where lane f of a folded row is head h's
    k, v     int8 [L * B, T, F] — the stacked planes [L, B, 1, T, F] as
             the layer scan carries them, the step's own row written, a
             layer's lanes one after the other (a reshape: as an operand
             of five axes the planes took the layout of this call's, the
             unit axis of the one group sits elsewhere in the one the
             admission's `cond` gives them, and the compiler copied both
             whole at every iteration)
    ks, vs   f32 [L, B, H, T] — a scale a head and position
    layer    which layer's rows to attend over
    mask     [B, 1, 1, T] bool — what a lane may see
    lengths  [B] int32 — a lane reads positions below its length alone
             (`mask_lengths`; 0: it reads nothing and its row is zeros)
    returns  [B, 1, F] in q's dtype, folded as q is.

    The planes stay where they lie (`pl.ANY`): a lane's live rows come by
    manual copies in whole int8 tiles of `QUANT_DECODE_BLOCK` positions, the
    next two lanes' while this one computes; a fusion cannot skip rows by a
    value it reads at run time. What the copies leave of the buffers is an
    earlier lane's int8, finite, and meets a probability of exactly 0.
    """
    b, _, f = q.shape
    h, t = ks.shape[2], k.shape[1]
    hp = -(-h // 16) * 16  # whole bfloat16 tiles of the matrix unit's rows
    lanes = jnp.pad(lanes.astype(q.dtype), ((0, hp - h), (0, 0)))
    lane = lambda i, *_: (i, 0, 0)  # noqa: E731
    scales = pl.BlockSpec((1, 1, h, t), lambda i, l, n: (l[0], i, 0, 0))
    return pl.pallas_call(
        functools.partial(_quant_decode_kernel, heads=h, head_dim=head_dim,
                          total=b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, 1, f), lane),
                pl.BlockSpec((hp, f), lambda i, *_: (0, 0)),
                scales, scales,
                pl.BlockSpec((1, 1, t), lane),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, f), lane),
            scratch_shapes=[
                pltpu.VMEM((_DEPTH, t, f), k.dtype),
                pltpu.VMEM((_DEPTH, t, f), v.dtype),
                pltpu.VMEM((hp, t), jnp.float32),
                pltpu.VMEM((hp, t), jnp.float32),
                pltpu.SemaphoreType.DMA((_DEPTH, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, f), q.dtype),
        # A lane's copies are started by the lanes before it.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="quant_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32)[None], lengths.astype(jnp.int32),
      q, lanes, ks, vs, mask[:, 0].astype(jnp.int32), k, v)
