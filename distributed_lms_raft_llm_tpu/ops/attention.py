"""Pallas TPU kernel: fused single-token (decode) attention.

The autoregressive decode step is HBM-bandwidth-bound: every step streams
the whole KV cache once per layer. XLA compiles `attend`'s einsum chain
(models/common.py:attend) into separate score and weighted-sum fusions
with a f32 [B, H, S] intermediate between them; this kernel computes
q·K^T → masked softmax → ·V in one pass per batch row, so K and V each
cross HBM exactly once per layer and nothing round-trips in between.

The kernel reads the layer's K/V directly out of the STACKED cache
([L, B, Hkv, S, Dh], the scan carry) via a scalar-prefetched layer index —
slicing the layer out first (`dynamic_index_in_dim`) and handing pallas
the slice costs a 2×[B,Hkv,S,Dh] HBM copy per layer, which measured
SLOWER than the XLA einsum path it was meant to beat.

Scope: decode only (one query token per row). Prefill and training keep
the XLA einsum path — there the query dimension is large, the MXU is busy,
and XLA's tiling is already the right schedule. Grouped-query models pass
kv_heads < num_heads; the kernel indexes the shared KV head directly, so
the repeat_kv materialization is skipped too. Capability parity note: the
reference has no analogue (HF torch `model.generate` on CPU, reference:
GUI_RAFT_LLM_SourceCode/tutoring_server.py:21-29); this file exists purely
to buy TPU headroom.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# VMEM the K and V blocks of one grid step may take, double-buffering
# included. A TPU v5e core scopes a kernel to 16 MiB; the rest is left to
# the q/bias/out blocks and the f32 score and probability rows.
_KV_VMEM_BUDGET = 12 * 1024 * 1024


def _kv_heads_per_step(kv_heads: int, s: int, dh: int, itemsize: int) -> int:
    """Largest divisor of `kv_heads` whose K+V blocks fit the VMEM budget.

    A [S, Dh] head tile occupies ceil(Dh/128) full 128-lane rows in VMEM
    whatever Dh is (Dh=64 pads to 128), and the pipeline double-buffers
    each input block — so one KV head costs 2 (K, V) x 2 (buffers) x
    S x lanes x itemsize. All heads in one step is the fewest grid steps
    (gpt2: 12 heads x 1 MiB = 12 MiB at S=1024 bf16); wider models split
    the head axis across grid steps instead (gpt2-large: 20 heads -> two
    steps of 10)."""
    lanes = -(-dh // 128) * 128
    per_head = 2 * 2 * s * lanes * itemsize
    fit = max(1, _KV_VMEM_BUDGET // per_head)
    return max(d for d in range(1, kv_heads + 1)
               if kv_heads % d == 0 and d <= fit)


def _decode_attn_kernel(l_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, *,
                        kv_block: int, group: int, scale: float):
    """One batch row x one block of `kv_block` KV heads: the block's
    [kv_block*group, Dh] queries against its [kv_block, S, Dh] K/V.

    Heads run as a static loop of 2-D dots — Mosaic does not lower batched
    dot_general, and per-head [1, Dh] x [Dh, S] products keep everything in
    VMEM anyway. Scores and softmax accumulate in f32; the weighted sum
    returns to the cache dtype only at the end.
    """
    del l_ref  # consumed by the BlockSpec index maps
    bias = bias_ref[0]  # [1, S] additive mask: 0 or NEG_INF
    for h in range(kv_block * group):
        qh = q_ref[0, 0, h][None, :]  # [1, Dh]
        sc = jax.lax.dot_general(
            qh, k_ref[0, 0, h // group], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [1, S]
        sc = sc * scale + bias
        m = jnp.max(sc, axis=-1, keepdims=True)
        p = jnp.exp(sc - m)
        denom = jnp.sum(p, axis=-1, keepdims=True)
        oh = jax.lax.dot_general(
            p.astype(k_ref.dtype), v_ref[0, 0, h // group],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [1, Dh]
        o_ref[0, 0, h] = ((oh / denom)[0]).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     layer: jax.Array, bias: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """Fused decode attention against one layer of the stacked KV cache.

    q        [B, H, 1, Dh] — the decode step's queries
    k_cache  [L, B, Hkv, S, Dh] — the scan-carried stacked cache
    v_cache  [L, B, Hkv, S, Dh]
    layer    [] int32 — which layer's K/V to attend against
    bias     [B, 1, S] f32 — additive mask (0 = attend, NEG_INF = not)
    returns  [B, H, 1, Dh] in q's dtype.

    The grid is (batch row, KV-head block): `_kv_heads_per_step` sizes the
    block so K and V fit VMEM at any supported model width. Query heads are
    viewed as [B, blocks, heads-per-block, Dh] so each step's q/out block
    spans whole trailing dims (a [10, 64] slab of a [20, 64] array is not a
    legal TPU block; a whole [10, 64] trailing pair is).

    Gating lives in the engine (`EngineConfig.fused_attention` sets the
    model config's `fused_decode_attention`, unsharded-mesh only); this
    function assumes a TPU backend unless `interpret` (the CPU parity
    test's mode) is set.
    """
    b, h, t, dh = q.shape
    _, _, hkv, s, _ = k_cache.shape
    assert t == 1, "decode_attention handles one query token per row"
    scale = 1.0 / (dh ** 0.5)
    group = h // hkv
    kv_block = _kv_heads_per_step(hkv, s, dh, k_cache.dtype.itemsize)
    blocks = hkv // kv_block
    hb = kv_block * group  # query heads per grid step

    out = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel, kv_block=kv_block, group=group, scale=scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, blocks),
            in_specs=[
                pl.BlockSpec((1, 1, hb, dh), lambda i, j, l: (i, j, 0, 0)),
                pl.BlockSpec(
                    (1, 1, kv_block, s, dh),
                    lambda i, j, l: (l[0], i, j, 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, kv_block, s, dh),
                    lambda i, j, l: (l[0], i, j, 0, 0),
                ),
                pl.BlockSpec((1, 1, s), lambda i, j, l: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, hb, dh), lambda i, j, l: (i, j, 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((b, blocks, hb, dh), q.dtype),
        interpret=interpret,
    )(layer[None].astype(jnp.int32), q[:, :, 0, :].reshape(b, blocks, hb, dh),
      k_cache, v_cache, bias)
    return out.reshape(b, h, 1, dh)


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
    through a scalar-prefetched layer index, as `decode_attention` reads
    K and V.
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
