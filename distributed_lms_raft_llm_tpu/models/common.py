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

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention as attention_ops

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
            FOLDED planes (`create`'s `groups`; models/gpt2.py's int8 cache)
            are [L, B, G, T, F]: the H/G heads of a group side by side in
            one row a position, F = (H/G)*Dh rounded up to whole lanes.
            Why: inside the layer scans an int8 plane is tiled (32, 128)
            over its two minor-most axes; over (H, Dh) = (25, 64) that is
            (32, 128), 2.56 times gpt2-xl's bytes, over (T, F) = (384, 1664)
            it is 1.04 times (`folds_heads`). Positions stay on axis 3 and
            G is the axis `tp` shards, so the engines' splices, exports and
            growth see the planes they are promised.
    length: [] int32 — number of valid positions already written.
    ks, vs: per-slot dequantization scales [L, B, Hkv, max_len] f32 when the
            cache is int8-quantized (halves the HBM bytes the decode loop
            streams per layer — see `quantize_kv`/`attend_quant`); None for
            a full-precision cache. One scale a head and position, folded
            planes too.
    ssm, conv: a recurrent family's per-row state (models/mamba2.py), None
            for every other: `ssm` [Lm, B, H, P, N] float32, the Lm
            state-space layers' states, and `conv` [Lm, B, K-1, C], the
            last K-1 inputs of their causal convolutions. Planes WITHOUT a
            positions axis: nothing of them grows with the context, and a
            forward pass that runs over a row MOVES them, so they have no
            dead region a lane may scribble on. The family advances a
            row's state once a `live` token and leaves it bit-equal on
            every other (pad positions, idle lanes); whoever hands a row to
            a new sequence resets it (`engine/paged.py` `_stage_program`:
            zeros, or a snapshot restored). `k`/`v` of such a family hold
            its attention layers alone, [La, B, Hkv, T, Dh].
    pool:   a family that selects what it reads (models/minicpm_sala.py)
            keeps its selector's keys beside the keys they summarise, None
            for every other: [La, B, Hkv, NP, Dh], one POOLED key for every
            `cfg.pool_stride` positions of a row, NP the row's groups
            rounded up to whole lane tiles (`ops/sparse.py`). A plane WITH a
            positions axis of its own, that many times shorter than `k`'s:
            it is grown, spliced, exported and evicted with the keys, a
            prefix block carrying the entries of its own positions.

    A single scalar length serves the whole batch; per-sequence raggedness is
    handled above the model by the engine's bucketing/batching (engine.paged
    generalizes this to per-slot lengths).
    """

    k: jax.Array
    v: Optional[jax.Array]
    length: jax.Array
    ks: Optional[jax.Array] = None
    vs: Optional[jax.Array] = None
    ssm: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    pool: Optional[jax.Array] = None

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
        groups: Optional[int] = None,
    ) -> "KVCache":
        shape = (num_layers, batch, num_kv_heads, max_len, head_dim)
        if quantized:
            sshape = shape[:-1]
            if groups:  # folded planes (class docstring); scales per head
                shape = (num_layers, batch, groups, max_len,
                         folded_width(num_kv_heads // groups, head_dim))
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
    # One dynamic slice a row, not a gather: the TPU's compiler splits a
    # gather of several rows this long into gathers over SLICES OF THE
    # WHOLE PLANE, which it copies out first, every layer. The rows are
    # made a value of their own (the barrier) before anything reads them:
    # read where they lie by a product that is scheduled around the
    # layer's write into the same plane, they made the compiler copy a
    # recurrent family's whole state plane between the layers of a pass
    # (tests/test_chip_compile.py holds it to none), and so did ONE row
    # read as `plane[layer, rows]` or as a bare slice in a program that
    # holds the pass of several rows too: one form for any number of rows.
    # A row past the last reads the last (a slice's start is clamped, as a
    # gather's index is).
    zero = jnp.zeros((), jnp.int32)
    return jax.lax.optimization_barrier(jnp.concatenate([
        jax.lax.dynamic_slice(
            plane, (layer, rows[i]) + (zero,) * (plane.ndim - 2),
            (1, 1) + plane.shape[2:])[0]
        for i in range(rows.shape[0])]))


def write_scales(plane: jax.Array, layer, rows: Optional[jax.Array],
                 slots: jax.Array, scales: jax.Array) -> jax.Array:
    """A ragged batch's new scales [B, H, T] into one layer of a scale
    plane [L, R, H, W], at positions `slots` [B, T] of the rows `rows`
    ([B]; None: row i for element i), as a select over the layer's rows
    and not a scatter of [H]-columns: a scatter makes the scans carry the
    plane heads-minor (25 of 128 lanes), and every layer then relays its
    [R, H, W] slice positions-minor for the scores. A slot past the width
    matches nothing and is dropped, as the scatter drops it."""
    cur = layer_rows(plane, layer, rows)
    hit = slots[:, None, :, None] == jnp.arange(plane.shape[-1])  # [B,1,T,W]
    new = jnp.sum(jnp.where(hit, scales[..., None], 0.0), axis=2)
    cur = jnp.where(jnp.any(hit, axis=2), new, cur)
    if rows is None:
        return jax.lax.dynamic_update_index_in_dim(plane, cur, layer, 0)
    return plane.at[layer, rows].set(cur)


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-(batch, head, slot) int8: [B, H, T, Dh] -> (int8 same
    shape, f32 [B, H, T] scales). One scale per cache slot keeps the
    dequant outside the attention dots (scores scale by ks on the
    un-contracted slot axis; vs folds into the probabilities). The scales
    are per head, taken BEFORE a family folds the heads into one row
    (`fold_heads`): the bytes and scales are the same either way."""
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf), axis=-1) / 127.0  # [B, H, T]
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


LANES = 128  # the TPU's minor tile dimension


def folds_heads(head_dim: int, quantized: bool) -> bool:
    """Whether a family folds its heads into the feature axis of its K/V
    planes: an int8 plane `[.., H, T, Dh]` is tiled (32, 128) over (H, Dh)
    inside the layer scans, so gpt2-xl's (25, 64) is read, and multiplied,
    as (32, 128): 2.56 times its bytes. Folded, the tile falls on
    (T, H*Dh) = (384, 1600 -> 1664): 4%."""
    return quantized and head_dim % LANES != 0


def folded_width(heads: int, head_dim: int) -> int:
    """The feature axis of a folded plane: the heads' bytes rounded up to
    whole lanes, so that the layout an array has at rest (the runtime puts
    the lane-multiple axis minor-most) is the one the scans carry."""
    return -(-heads * head_dim // LANES) * LANES


def fold_heads(x: jax.Array, groups: int) -> jax.Array:
    """[.., H, T, Dh] -> [.., G, T, F]: a group's heads side by side in one
    row a position (G = 1: a token's whole H*Dh bytes), zeros up to
    F = `folded_width`."""
    *lead, h, t, d = x.shape
    x = x.reshape(*lead, groups, h // groups, t, d)
    x = jnp.swapaxes(x, -3, -2).reshape(*lead, groups, t, h // groups * d)
    pad = folded_width(h // groups, d) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def unfold_heads(x: jax.Array, heads: int, head_dim: int) -> jax.Array:
    """`fold_heads` undone: [.., G, T, F] -> [.., heads, T, head_dim]."""
    *lead, g, t, _ = x.shape
    x = x[..., :heads // g * head_dim]
    x = x.reshape(*lead, g, t, heads // g, head_dim)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, heads, t, head_dim)


def _head_lanes(heads: int, width: int, head_dim: int, dtype) -> jax.Array:
    """[heads, width] ones where lane f of a folded row is head f //
    head_dim's: a row a head, each over its own lanes."""
    lane_head = jnp.arange(width)[None, :] // head_dim
    return (lane_head == jnp.arange(heads)[:, None]).astype(dtype)


def attend_quant(
    q: jax.Array,
    k_q: jax.Array,
    ks: jax.Array,
    v_q: jax.Array,
    vs: jax.Array,
    mask: jax.Array,
) -> jax.Array:
    """`attend` against an int8 cache: q [B,H,T,Dh], k_q/v_q int8
    [B,H,S,Dh] or folded [B,G,S,F] (`fold_heads`), ks/vs f32 [B,H,S],
    mask [B,1,T,S].

    Both dequant multiplies stay OUTSIDE the dots — ks scales the score
    matrix on its un-contracted slot axis, vs folds into the (tiny)
    probability matrix — so the int8 operands feed the MXU directly and
    HBM sees half the bytes of a bf16 cache.

    Over folded planes a decode step (T = 1) never splits a row's lanes
    into (H, Dh), which is the padded tile again: the query goes
    block-diagonal, head h's Dh values on head h's lanes of row h and
    zeros beside, so one product over the whole row gives every head's
    score, the products and the f32 sums of the per-head form with zeros
    added; the values come back as [heads, F] rows of which each lane
    keeps its own head's. A window of queries (a prefill chunk's one row,
    a speculative window) unfolds the rows it attends over instead.
    """
    dtype = q.dtype
    b, h, t, head_dim = q.shape
    folded = k_q.shape[-1] != head_dim
    if folded and t > 1:
        k_q = unfold_heads(k_q, h, head_dim)
        v_q = unfold_heads(v_q, h, head_dim)
        folded = False
    if folded:
        g, s, width = k_q.shape[1:]
        lanes = _head_lanes(h // g, width, head_dim, dtype)
        q_rows = fold_heads(q, g) * lanes  # [B,G,1,F] -> [B,G,H/G,F]
        scores = jnp.einsum(
            "bghf,bgsf->bghs", q_rows, k_q.astype(dtype),
            preferred_element_type=jnp.float32,
        ).reshape(b, h, 1, s)
    else:
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k_q.astype(dtype),
            preferred_element_type=jnp.float32,
        )
    scores = scores * ks[:, :, None, :]
    scores = scores / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = (probs * vs[:, :, None, :]).astype(dtype)
    if not folded:
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v_q.astype(dtype))
    rows = jnp.einsum("bghs,bgsf->bghf", probs.reshape(b, g, h // g, s),
                      v_q.astype(dtype))
    out = jnp.sum(rows * lanes, axis=2, keepdims=True)  # [B,G,1,F]
    return unfold_heads(out, h, head_dim)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _quant_decode(q, k_q, ks, v_q, vs, layer, mask, lengths,
                  interpret: bool = False) -> jax.Array:
    """`attend_quant_layer`'s kernel arm: the queries folded as the planes'
    rows are, the planes as a layer's lanes one after the other (a
    reshape), the kernel's rows unfolded to [B, H, 1, Dh]. Jitted for its
    TRACE (the compiler inlines it): the kernel's body is some thousand
    operations, and an engine's start traces the decode step twice a
    cache width (`engine/paged.py` `_chunk`, wide and not)."""
    _, h, _, head_dim = q.shape
    out = attention_ops.quant_decode_attention(
        fold_heads(q, 1)[:, 0],
        _head_lanes(h, k_q.shape[-1], head_dim, q.dtype),
        k_q.reshape(-1, *k_q.shape[3:]), ks,
        v_q.reshape(-1, *v_q.shape[3:]), vs, layer, mask, lengths,
        head_dim=head_dim, interpret=interpret)
    return unfold_heads(out[:, None], h, head_dim)


def attend_quant_layer(
    q: jax.Array,
    k_q: jax.Array,
    ks: jax.Array,
    v_q: jax.Array,
    vs: jax.Array,
    layer,
    rows: Optional[jax.Array],
    mask: jax.Array,
    lengths: jax.Array,
) -> jax.Array:
    """`attend_quant` over one layer of the STACKED int8 planes
    ([L, R, G, S, F], scales [L, R, H, S]) as a layer scan carries them,
    the step's own rows written; `rows` as `layer_rows` takes them,
    `lengths` [B] what `attention_ops.mask_lengths` reads off the mask.

    A decode step over folded planes (one query a row, one group, the
    batch the planes' own rows, a width of whole blocks:
    `quant_decode_engages`, shapes alone) is on the TPU ONE kernel that
    reads each lane's live positions out of the planes where they lie
    (`ops/attention.py` `quant_decode_attention`), and `attend_quant`'s two
    products over the layer's whole planes everywhere else: the kernel's
    reference, a prefill chunk, a speculative window, `tp` groups."""
    def products():
        return attend_quant(
            q, layer_rows(k_q, layer, rows), layer_rows(ks, layer, rows),
            layer_rows(v_q, layer, rows), layer_rows(vs, layer, rows), mask)

    if rows is None and attention_ops.quant_decode_engages(
            q.shape, k_q.shape):
        return jax.lax.platform_dependent(
            tpu=lambda: _quant_decode(q, k_q, ks, v_q, vs, layer, mask,
                                      lengths),
            default=products)
    return products()


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
