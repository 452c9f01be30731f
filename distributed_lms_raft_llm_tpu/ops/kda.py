"""The decode step of a Kimi Delta Attention (KDA) layer: every slot's matrix
state read once and written once, in place.

    S_h <- diag(a_h) S_h                     S_h [K, V] float32, a_h [K]
    u    = beta_h (v_h - S_h^T k_h)          the delta rule's correction
    S_h <- S_h + k_h u^T
    o_h  = S_h^T q_h

per slot and head h: a gated delta rule whose decay is one number a CHANNEL
of the key (Kimi Linear, 2025), not one a head. `kda_step` is the Pallas
kernel (named `kda_step` in a device trace; `models/kda.py` picks it on the
TPU through `jax.lax.platform_dependent`), `kda_step_reference` the same
update in plain `jax.numpy` for every other backend. Both take the STACKED
plane [Lk, S, H, K, V] and a layer index and hand the plane back: the kernel
aliases it to its output and visits the one layer's blocks, so no copy of a
layer's states, let alone of the plane, is made (`ops/ssm.py` is the
pattern).

A lane that is not live comes with decay 1 and beta 0 (the caller's): its
state is written back as it was read, bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Heads a grid step of the kernel holds: 8 x [128, 128] float32 is 512 KB in
# and 512 KB out, and 8 rows are a float32 tile's sublanes.
HEADS_AT_ONCE = 8


def _kda_step_kernel(l_ref, s_ref, cols_ref, v_ref, so_ref, o_ref, *,
                     heads: int):
    """One slot's `heads` heads: states [heads, K, V], K on the sublanes.
    `cols` holds what varies along K as columns, a head a lane: the decay
    in its first `heads` lanes, then k, beta k and q; `v` the values as
    rows over V."""
    del l_ref  # consumed by the BlockSpec index maps
    for h in range(heads):
        decay, k, kb, q = (
            cols_ref[0, 0, :, i * heads + h:i * heads + h + 1]
            for i in range(4))                                    # [K, 1]
        s = s_ref[0, 0, h] * decay                                # [K, V]
        u = v_ref[0, 0, h:h + 1, :] - jnp.sum(s * k, axis=0, keepdims=True)
        s = s + kb * u
        so_ref[0, 0, h] = s
        o_ref[0, 0, h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)


def _heads_at_once(h: int) -> int:
    return HEADS_AT_ONCE if h % HEADS_AT_ONCE == 0 else h


def kda_step(plane: jax.Array, layer, q: jax.Array, k: jax.Array,
             v: jax.Array, decay: jax.Array, beta: jax.Array, *,
             interpret: bool = False):
    """One decode step of layer `layer` of the stacked state plane
    [Lk, S, H, K, V] float32, for all S slots: (the plane with that layer's
    states advanced, o [S, H, V] float32).

    q, k, decay [S, H, K] (q scaled and both normalised by the caller;
    decay = exp(g), in (0, 1]); v [S, H, V]; beta [S, H]. The grid is
    (slot, group of `HEADS_AT_ONCE` heads), each state read once and
    written once. The layer's blocks are found in the stacked plane through
    a scalar-prefetched index, as `ssm_step` finds its own."""
    _, s, h, kd, vd = plane.shape
    per = _heads_at_once(h)
    g = h // per
    f32 = jnp.float32

    def cols_of(x):                                   # [S, H, K] -> [S,G,K,per]
        return x.astype(f32).reshape(s, g, per, kd).transpose(0, 1, 3, 2)

    kf = k.astype(f32)
    cols = jnp.concatenate(
        [cols_of(decay), cols_of(kf), cols_of(kf * beta.astype(f32)[..., None]),
         cols_of(q)], axis=-1)                                 # [S, G, K, 4 per]
    rows = v.astype(f32).reshape(s, g, per, vd)
    plane, o = pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, g),
            in_specs=[
                pl.BlockSpec((1, 1, per, kd, vd),
                             lambda i, j, l: (l[0], i, j, 0, 0)),
                pl.BlockSpec((1, 1, kd, 4 * per),
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
        name="kda_step",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32)[None], plane, cols, rows)
    return plane, o.reshape(s, h, vd)


def kda_step_reference(plane: jax.Array, layer, q: jax.Array, k: jax.Array,
                       v: jax.Array, decay: jax.Array, beta: jax.Array):
    """`kda_step` in plain `jax.numpy` (every backend but the TPU)."""
    f32 = jnp.float32
    q, k, v, decay, beta = (x.astype(f32) for x in (q, k, v, decay, beta))
    state = jax.lax.dynamic_index_in_dim(plane, layer, 0, keepdims=False)
    state = state * decay[..., None]                            # [S,H,K,V]
    u = v - jnp.sum(state * k[..., None], axis=-2)
    state = state + (k * beta[..., None])[..., None] * u[..., None, :]
    o = jnp.sum(state * q[..., None], axis=-2)
    return jax.lax.dynamic_update_index_in_dim(plane, state, layer, 0), o
