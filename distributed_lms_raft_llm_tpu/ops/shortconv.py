"""The decode step of a gated short convolution (LFM2's `conv` operator):
every live slot's window of last inputs read once and written once, in
place.

    u   = B * z                                  (the served dtype's product)
    c   = sum_{j < K-1} w_j window_j + w_{K-1} u (float32)
    y   = C * c
    window <- [window_1 .. window_{K-2}, u]      where the lane is live

per slot, channel by channel: a causal depthwise convolution of K taps whose
whole state is the K-1 inputs before the token, gated on both sides and with
no activation, bias or norm. `shortconv_step` is the Pallas kernel (named
`shortconv_step` in a device trace; `models/lfm2.py` picks it on the TPU
through `jax.lax.platform_dependent`), `shortconv_step_reference` the same
update in plain `jax.numpy` for every other backend. Both take the STACKED
plane `KVCache.conv` [Lc, S, K-1, C] and a layer index and hand the plane
back: the kernel aliases it to its output and visits the one layer's blocks
(`ops/ssm.py` `ssm_step`'s way), so no layer's windows, let alone the plane,
are copied. `B`, `C` and `z` are read where the input projection left them,
as three column blocks of its one output [S, 3C].

A lane that is not live keeps its window bit for bit: the kernel writes
back what it read there, by a select, whatever `B`, `C` and `z` hold (its
`y` is computed and is nobody's).

It is a kernel for the name and for the update in place, not for speed: at
64 lanes it moves about 2 MB a layer and step (PERF.md section 6, PR 57,
has its time beside XLA's own fusion of the plain form).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Slots a grid step holds where the slots are whole multiples of it (a
# bfloat16 tile's sublanes); any other count of slots is one block.
SLOT_BLOCK = 16


def _shortconv_step_kernel(l_ref, win_ref, b_ref, c_ref, z_ref, live_ref,
                           w_ref, out_ref, y_ref, *, taps: int):
    """`SLOT_BLOCK` slots of one layer: windows [sb, K-1, C], the gates
    and the input [sb, C] each, live [sb, 1] int32, the taps [K, C]."""
    del l_ref  # consumed by the BlockSpec index maps
    f32 = jnp.float32
    win = win_ref[0]                                         # [sb, K-1, C]
    u = (b_ref[...].astype(f32) * z_ref[...].astype(f32)).astype(win.dtype)
    w = w_ref[...].astype(f32)
    # The taps in the chunk form's order (`models/mamba2.py` `causal_conv`):
    # the oldest input first, the token's own last.
    conv = win[:, 0, :].astype(f32) * w[0:1]
    for j in range(1, taps - 1):
        conv = conv + win[:, j, :].astype(f32) * w[j:j + 1]
    conv = conv + u.astype(f32) * w[taps - 1:taps]
    y_ref[...] = (c_ref[...].astype(f32) * conv).astype(y_ref.dtype)
    live = live_ref[...] > 0                                 # [sb, 1]
    for j in range(taps - 1):
        new = u if j == taps - 2 else win[:, j + 1, :]
        out_ref[0, :, j, :] = jnp.where(live, new, win[:, j, :])


def shortconv_step(plane: jax.Array, layer, bcz: jax.Array, live: jax.Array,
                   w: jax.Array, *, interpret: bool = False):
    """One decode step of layer `layer` of the stacked window plane
    [Lc, S, K-1, C], for all S slots: (the plane with the live slots'
    windows of that layer shifted, y [S, C] in the plane's dtype).

    bcz [S, 3C]: the input projection's output, `B | C | z`; live [S]
    bool; w [K, C]: the taps, the oldest input's first. The grid is over
    blocks of `SLOT_BLOCK` slots; the layer's blocks are found in the
    stacked plane through a scalar-prefetched index."""
    _, s, k1, c = plane.shape
    sb = SLOT_BLOCK if s % SLOT_BLOCK == 0 else s
    window = pl.BlockSpec((1, sb, k1, c), lambda i, l: (l[0], i, 0, 0))
    # `B`, `C`, `z`: column blocks 0, 1, 2 of the projection's output.
    columns = [pl.BlockSpec((sb, c), lambda i, l, j=j: (i, j))
               for j in range(3)]
    plane, y = pl.pallas_call(
        functools.partial(_shortconv_step_kernel, taps=k1 + 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s // sb,),
            in_specs=[
                window, *columns,
                pl.BlockSpec((sb, 1), lambda i, l: (i, 0)),
                pl.BlockSpec((k1 + 1, c), lambda i, l: (0, 0)),
            ],
            out_specs=[window, pl.BlockSpec((sb, c), lambda i, l: (i, 0))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(plane.shape, plane.dtype),
            jax.ShapeDtypeStruct((s, c), plane.dtype),
        ],
        # Operand 0 is the prefetched layer index; the plane is updated
        # where it lies.
        input_output_aliases={1: 0},
        name="shortconv_step",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32)[None], plane, bcz, bcz, bcz,
      live.astype(jnp.int32)[:, None], w)
    return plane, y


def shortconv_step_reference(plane: jax.Array, layer, bcz: jax.Array,
                             live: jax.Array, w: jax.Array):
    """`shortconv_step` in plain `jax.numpy` (every backend but the TPU)."""
    f32 = jnp.float32
    c = plane.shape[-1]
    b, gate, z = bcz[:, :c], bcz[:, c:2 * c], bcz[:, 2 * c:]
    win = jax.lax.dynamic_index_in_dim(plane, layer, 0, keepdims=False)
    u = (b.astype(f32) * z.astype(f32)).astype(plane.dtype)
    seq = jnp.concatenate([win, u[:, None]], axis=1)          # [S, K, C]
    conv = sum(seq[:, j].astype(f32) * w[j].astype(f32)
               for j in range(seq.shape[1]))
    y = (gate.astype(f32) * conv).astype(plane.dtype)
    new = jnp.where(live[:, None, None], seq[:, 1:], win)
    return jax.lax.dynamic_update_index_in_dim(plane, new, layer, 0), y
