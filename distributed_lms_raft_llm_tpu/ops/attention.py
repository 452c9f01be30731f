"""Pallas TPU kernel: fused single-token (decode) attention over a latent
(MLA) cache.

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
