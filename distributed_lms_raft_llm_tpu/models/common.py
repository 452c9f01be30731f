"""Shared functional building blocks for the JAX model zoo.

Models here are *pure functions over parameter pytrees* (nested dicts of
jnp arrays) rather than stateful modules: that keeps them trivially
compatible with `jax.jit`/`pjit`, lets partition specs be assigned by
tree-path regex (see `parallel.partition`), and makes HF-checkpoint
conversion a plain dict transform (`models.convert`).

Conventions
-----------
- Per-layer weights are **stacked along a leading layer axis** and the
  transformer trunk runs as a single `lax.scan` over that axis: compile time
  is O(1) in depth and the MXU sees one fused block program.
- Matmuls run in the config's compute dtype (bfloat16 on TPU) with layer
  norm, attention scores and softmax accumulated in float32 for stability
  (residual adds stay in the compute dtype, as is standard for inference).
- Attention is written against a fixed-size key/value window so the same
  code path serves training (no cache) and static-shape TPU decode (cache of
  length `max_len` updated in place via `lax.dynamic_update_slice`).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large finite negative: avoids NaNs from (-inf) - (-inf)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    """LayerNorm in float32 regardless of input dtype; returns input dtype."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def dense(x: jax.Array, w, b: Optional[jax.Array] = None) -> jax.Array:
    """x @ w (+ b). Weights stored [in, out] so no transposes reach the MXU.

    `w` is either a dense array or a weight-only-int8 pair
    `{"q": int8 [in, out], "s": f32 [out]}` (models/quant.py): the int8
    operand streams from HBM at half the bytes, the convert to the compute
    dtype fuses into the matmul's operand load, and the per-out-channel
    scale folds into the output.
    """
    if isinstance(w, dict):
        y = jnp.einsum("...i,io->...o", x, w["q"].astype(x.dtype))
        y = y * w["s"].astype(y.dtype)
    else:
        y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


class KVCache(NamedTuple):
    """Static-shape per-model KV cache.

    k, v: [num_layers, batch, num_kv_heads, max_len, head_dim]; a family
            declares its own planes in `init_cache`, each [L, B, H, T, F]
            with its own H and F, and `v` is None where there is one plane
            (models/mla.py: a latent cache, one head, no values).
    length: [] int32 — number of valid positions already written.
    ks, vs: per-slot dequantization scales [L, B, Hkv, max_len] f32 when the
            cache is int8-quantized (halves the HBM bytes the decode loop
            streams per layer — see `quantize_kv`/`attend_quant`); None for
            a full-precision cache.

    A single scalar length serves the whole batch; per-sequence raggedness is
    handled above the model by the engine's bucketing/batching (engine.paged
    generalizes this to per-slot lengths).
    """

    k: jax.Array
    v: Optional[jax.Array]
    length: jax.Array
    ks: Optional[jax.Array] = None
    vs: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.ks is not None

    @classmethod
    def create(
        cls,
        num_layers: int,
        batch: int,
        num_kv_heads: int,
        max_len: int,
        head_dim: int,
        dtype=jnp.bfloat16,
        quantized: bool = False,
    ) -> "KVCache":
        shape = (num_layers, batch, num_kv_heads, max_len, head_dim)
        if quantized:
            sshape = shape[:-1]
            return cls(
                k=jnp.zeros(shape, jnp.int8),
                v=jnp.zeros(shape, jnp.int8),
                length=jnp.zeros((), jnp.int32),
                ks=jnp.zeros(sshape, jnp.float32),
                vs=jnp.zeros(sshape, jnp.float32),
            )
        return cls(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            length=jnp.zeros((), jnp.int32),
        )


def layer_rows(plane: jax.Array, layer,
               rows: Optional[jax.Array]) -> jax.Array:
    """What a batch attends over in one layer of a stacked cache plane
    ([L, rows, ...]): the layer's whole plane when `rows` is None (batch
    element i owns cache row i), else the given rows of it, [B, ...] — a
    batch that addresses rows of a wider cache (`forward`'s `rows`) reads
    its own rows' pages and nobody else's."""
    if rows is None:
        return jax.lax.dynamic_index_in_dim(plane, layer, 0, keepdims=False)
    return plane[layer, rows]


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-(batch, head, slot) int8: [B, H, T, Dh] -> (int8 same
    shape, f32 [B, H, T] scales). One scale per cache slot keeps the
    dequant outside the attention dots (scores scale by ks on the
    un-contracted slot axis; vs folds into the probabilities)."""
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf), axis=-1) / 127.0  # [B, H, T]
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def attend_quant(
    q: jax.Array,
    k_q: jax.Array,
    ks: jax.Array,
    v_q: jax.Array,
    vs: jax.Array,
    mask: jax.Array,
) -> jax.Array:
    """`attend` against an int8 cache: q [B,H,T,Dh], k_q/v_q int8
    [B,H,S,Dh], ks/vs f32 [B,H,S], mask [B,1,T,S].

    Both dequant multiplies stay OUTSIDE the dots — ks scales the score
    matrix on its un-contracted slot axis, vs folds into the (tiny)
    probability matrix — so the int8 operands feed the MXU directly and
    HBM sees half the bytes of a bf16 cache.
    """
    dtype = q.dtype
    head_dim = q.shape[-1]
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k_q.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    scores = scores * ks[:, :, None, :]
    scores = scores / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = (probs * vs[:, :, None, :]).astype(dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v_q.astype(dtype))


def attend(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array,
) -> jax.Array:
    """Multi-head attention core on [B, H, T, Dh] tensors, f32 softmax.

    mask: broadcastable to [B, H, Tq, Tk]; True = may attend.
    """
    dtype = q.dtype
    head_dim = q.shape[-1]
    # Accumulate scores in f32 on the MXU (bf16 inputs, f32 accumulation).
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    scores = scores / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def split_heads(x: jax.Array, num_heads: int) -> jax.Array:
    """[B, T, H*Dh] -> [B, H, T, Dh]."""
    b, t, _ = x.shape
    return x.reshape(b, t, num_heads, -1).transpose(0, 2, 1, 3)


def merge_heads(x: jax.Array) -> jax.Array:
    """[B, H, T, Dh] -> [B, T, H*Dh]."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def causal_window_mask(q_positions: jax.Array, num_keys: int,
                       window: Optional[int] = None) -> jax.Array:
    """Mask for attention against a fixed-size cache window.

    q_positions: [B, Tq] absolute positions of the queries.
    Key slot j holds absolute position j; it is visible iff j <= q_position
    and, with a sliding `window`, j > q_position - window (a query sees
    itself and the window - 1 keys before it).
    Returns [B, 1, Tq, num_keys] boolean.
    """
    key_pos = jnp.arange(num_keys, dtype=q_positions.dtype)
    mask = key_pos[None, None, :] <= q_positions[:, :, None]
    if window is not None:
        mask = mask & (key_pos[None, None, :] > q_positions[:, :, None] - window)
    return mask[:, None, :, :]


def repeat_kv(x: jax.Array, repeats: int) -> jax.Array:
    """Expand grouped KV heads [B, Hkv, T, Dh] -> [B, Hkv*repeats, T, Dh]."""
    if repeats == 1:
        return x
    b, h, t, d = x.shape
    x = jnp.broadcast_to(x[:, :, None], (b, h, repeats, t, d))
    return x.reshape(b, h * repeats, t, d)
