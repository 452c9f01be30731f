"""The decode step of a Lightning linear-attention layer: every slot's matrix
state read once and written once, in place.

    S_h <- lambda_h S_h + k_h v_h^T          S_h [K, V] float32
    o_h  = S_h^T q_h

per slot and head h: linear attention with ONE decay a head (Lightning
Attention's ALiBi-sloped constants, the caller's), no correction term and no
gate on the state. `lightning_step` is the Pallas kernel (named
`lightning_step` in a device trace; `models/minicpm_sala.py` picks it on the
TPU through `jax.lax.platform_dependent`), `lightning_step_reference` the
same update in plain `jax.numpy` for every other backend. Both take the
STACKED plane [Ll, S, H, K, V] and a layer index and hand the plane back:
the kernel aliases it to its output and visits the one layer's blocks
(`ops/kda.py` is the pattern).

A lane that is not live comes with decay 1 and k 0 (the caller's): its state
is written back as it was read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Heads a grid step holds: 8 x [128, 128] float32 is 512 KB in and out.
HEADS_AT_ONCE = 8


def _lightning_step_kernel(l_ref, s_ref, cols_ref, v_ref, so_ref, o_ref, *,
                           heads: int):
    """One slot's `heads` heads: states [heads, K, V], K on the sublanes.
    `cols` holds what varies along K as columns, a head a lane: the decay
    (repeated down K) in its first `heads` lanes, then k and q; `v` the
    values as rows over V."""
    del l_ref  # consumed by the BlockSpec index maps
    for h in range(heads):
        decay, k, q = (cols_ref[0, 0, :, i * heads + h:i * heads + h + 1]
                       for i in range(3))                         # [K, 1]
        s = s_ref[0, 0, h] * decay + k * v_ref[0, 0, h:h + 1, :]  # [K, V]
        so_ref[0, 0, h] = s
        o_ref[0, 0, h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)


def lightning_step(plane: jax.Array, layer, q: jax.Array, k: jax.Array,
                   v: jax.Array, decay: jax.Array, *,
                   interpret: bool = False):
    """One decode step of layer `layer` of the stacked state plane
    [Ll, S, H, K, V] float32, for all S slots: (the plane with that layer's
    states advanced, o [S, H, V] float32). q, k [S, H, K]; v [S, H, V];
    decay [S, H] in (0, 1]. The grid is (slot, group of `HEADS_AT_ONCE`
    heads), each state read once and written once."""
    _, s, h, kd, vd = plane.shape
    per = HEADS_AT_ONCE if h % HEADS_AT_ONCE == 0 else h
    g = h // per
    f32 = jnp.float32

    def cols_of(x):                               # [S, H, K] -> [S,G,K,per]
        return x.astype(f32).reshape(s, g, per, kd).transpose(0, 1, 3, 2)

    cols = jnp.concatenate(
        [cols_of(jnp.broadcast_to(decay.astype(f32)[..., None], (s, h, kd))),
         cols_of(k), cols_of(q)], axis=-1)                    # [S, G, K, 3 per]
    rows = v.astype(f32).reshape(s, g, per, vd)
    plane, o = pl.pallas_call(
        functools.partial(_lightning_step_kernel, heads=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, g),
            in_specs=[
                pl.BlockSpec((1, 1, per, kd, vd),
                             lambda i, j, l: (l[0], i, j, 0, 0)),
                pl.BlockSpec((1, 1, kd, 3 * per),
                             lambda i, j, l: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, per, vd), lambda i, j, l: (i, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, per, kd, vd),
                             lambda i, j, l: (l[0], i, j, 0, 0)),
                pl.BlockSpec((1, 1, per, vd), lambda i, j, l: (i, j, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(plane.shape, plane.dtype),
            jax.ShapeDtypeStruct((s, g, per, vd), f32),
        ],
        # Operand 0 is the prefetched layer index; the plane is updated
        # where it lies.
        input_output_aliases={1: 0},
        name="lightning_step",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32)[None], plane, cols, rows)
    return plane, o.reshape(s, h, vd)


def lightning_step_reference(plane: jax.Array, layer, q: jax.Array,
                             k: jax.Array, v: jax.Array, decay: jax.Array):
    """`lightning_step` in plain `jax.numpy` (every backend but the TPU)."""
    f32 = jnp.float32
    q, k, v, decay = (x.astype(f32) for x in (q, k, v, decay))
    state = jax.lax.dynamic_index_in_dim(plane, layer, 0, keepdims=False)
    state = (state * decay[..., None, None]
             + k[..., None] * v[..., None, :])                  # [S,H,K,V]
    o = jnp.sum(state * q[..., None], axis=-2)
    return jax.lax.dynamic_update_index_in_dim(plane, state, layer, 0), o
