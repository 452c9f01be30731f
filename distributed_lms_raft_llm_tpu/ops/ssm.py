"""The decode step of a state-space (Mamba-2) layer: every slot's recurrent
state read once and written once, in place.

    S_h <- exp(dt_h A_h) S_h + dt_h x_h B_g^T        S_h [P, N] float32
    y_h  = S_h C_g                                   (D x is the caller's)

per slot and head h of group g. `ssm_step` is the Pallas kernel (named
`ssm_step` in a device trace; `models/mamba2.py` picks it on the TPU through
`jax.lax.platform_dependent`), `ssm_step_reference` the same update in plain
`jax.numpy` for every other backend. Both take the STACKED plane
[Lm, S, H, P, N] and a layer index and hand the plane back: the kernel
aliases it to its output and visits the one layer's blocks, so no copy of a
layer's states, let alone of the plane, is made (a gather and a scatter of
`[S, H, P, N]` float32 inside the decode scan would invite one).

A lane that is not live comes with decay 1 and input 0 (`dt` = 0, the
caller's): its state is written back as it was read, bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_step_kernel(l_ref, s_ref, cols_ref, bc_ref, so_ref, y_ref, *,
                     heads: int):
    """One slot's heads of one group: states [heads, P, N]. `cols` holds
    what varies along P as columns (P on the sublanes, a head a lane):
    dt x in its first `heads` lanes, the decay, repeated down P, in the
    next; `bc` the group's B and C as rows over N."""
    del l_ref  # consumed by the BlockSpec index maps
    b_row, c_row = bc_ref[0, 0, 0:1, :], bc_ref[0, 0, 1:2, :]    # [1, N]
    for h in range(heads):
        dx = cols_ref[0, 0, :, h:h + 1]                           # [P, 1]
        decay = cols_ref[0, 0, :, heads + h:heads + h + 1]        # [P, 1]
        s = s_ref[0, 0, h] * decay + dx * b_row                   # [P, N]
        so_ref[0, 0, h] = s
        y_ref[0, 0, :, h:h + 1] = jnp.sum(s * c_row, axis=-1, keepdims=True)


def _operands(dtx: jax.Array, decay: jax.Array, b: jax.Array, c: jax.Array):
    """The kernel's small operands from the step's: dtx [S, H, P] (dt x),
    decay [S, H], b / c [S, G, N] -> cols [S, G, P, 2 H/G], bc [S, G, 2, N],
    float32."""
    s, h, p = dtx.shape
    g = b.shape[1]
    per = h // g
    dx = dtx.reshape(s, g, per, p).transpose(0, 1, 3, 2)
    dec = jnp.broadcast_to(decay.reshape(s, g, 1, per), (s, g, p, per))
    return (jnp.concatenate([dx, dec], axis=-1).astype(jnp.float32),
            jnp.stack([b, c], axis=2).astype(jnp.float32))


def ssm_step(plane: jax.Array, layer, dtx: jax.Array, decay: jax.Array,
             b: jax.Array, c: jax.Array, *, interpret: bool = False):
    """One decode step of layer `layer` of the stacked state plane
    [Lm, S, H, P, N] float32, for all S slots: (the plane with that
    layer's states advanced, y [S, H, P] float32).

    dtx [S, H, P]: dt x; decay [S, H]: exp(dt A); b, c [S, G, N]: the
    groups' input and output projections of the state. The grid is (slot,
    group): one step holds a group's H/G states of one slot (8 x [64, 128]
    float32, 256 KB in, 256 KB out), each read once and written once. The
    layer's blocks are found in the stacked plane through a scalar-
    prefetched index, as `latent_decode_attention` finds its rows."""
    lm, s, h, p, n = plane.shape
    g = b.shape[1]
    per = h // g
    cols, bc = _operands(dtx, decay, b, c)
    plane, y = pl.pallas_call(
        functools.partial(_ssm_step_kernel, heads=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, g),
            in_specs=[
                pl.BlockSpec((1, 1, per, p, n),
                             lambda i, j, l: (l[0], i, j, 0, 0)),
                pl.BlockSpec((1, 1, p, 2 * per),
                             lambda i, j, l: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, 2, n), lambda i, j, l: (i, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, per, p, n),
                             lambda i, j, l: (l[0], i, j, 0, 0)),
                pl.BlockSpec((1, 1, p, per), lambda i, j, l: (i, j, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(plane.shape, plane.dtype),
            jax.ShapeDtypeStruct((s, g, p, per), jnp.float32),
        ],
        # Operand 0 is the prefetched layer index; the plane is updated
        # where it lies.
        input_output_aliases={1: 0},
        name="ssm_step",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32)[None], plane, cols, bc)
    return plane, y.transpose(0, 1, 3, 2).reshape(s, h, p)


def ssm_step_reference(plane: jax.Array, layer, dtx: jax.Array,
                       decay: jax.Array, b: jax.Array, c: jax.Array):
    """`ssm_step` in plain `jax.numpy` (every backend but the TPU)."""
    h, g = plane.shape[2], b.shape[1]
    b_h = jnp.repeat(b.astype(jnp.float32), h // g, axis=1)     # [S, H, N]
    c_h = jnp.repeat(c.astype(jnp.float32), h // g, axis=1)
    state = jax.lax.dynamic_index_in_dim(plane, layer, 0, keepdims=False)
    state = (state * decay[:, :, None, None]
             + dtx[..., None] * b_h[:, :, None, :])
    y = jnp.sum(state * c_h[:, :, None, :], axis=-1)
    return jax.lax.dynamic_update_index_in_dim(plane, state, layer, 0), y
