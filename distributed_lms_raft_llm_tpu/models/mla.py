"""Multi-head latent attention (MLA, the DeepSeek-V2/V3 line) and its cache,
for any family that has it: this module names no model.

A token's keys and values are not cached. What is cached, per token and
layer, is the latent they are projected from and the one rotary key all
heads share:

    c_q = Nq(x Wqa)                       [q_lora_rank]
    q   = c_q Wqb -> heads x (nope | rope)
    [c_kv | k_r] = x Wkva                 [kv_lora_rank + rope]
    c_kv = Nkv(c_kv);  k_r = RoPE(k_r);  q_r = RoPE(q_r)
    [k_nope | v] = c_kv Wkvb -> heads x (nope + v)
    scores = (q_nope . k_nope + q_r . k_r) * scale,  o = softmax(scores) v

**The cache** is a `KVCache` of ONE plane with one head: `k` [L, B, 1, T,
kv_lora_rank + rope] holds `c_kv` after its norm and, behind it, `k_r`
after its rotation, nothing else; there is no `v` plane (576 values a token
and layer at the published sizes, 1,152 B in bfloat16, where the heads' own
keys and values would be 40,960 B). The plane keeps the axes every cache of
this repo has (layer, row, head, slot, feature), so the paged engine's
splice, export, growth and the prefix tree carry it by its own shape, as
they carry whatever planes a family's `init_cache` declares. One plane and
not two: a rotary plane 64 wide is half a lane tile, and the TPU's compiler
kept it slot-minor for the scores and relaid all of it (110 MB at 64 slots
x 2,688) for every layer's scatter, five whole-plane copies a decode step
by its own text for a described v5e.

**Attention runs absorbed**, whatever T: `Wkvb`'s two halves are folded into
the query and the output instead of expanding the cache to heads,

    q_lat = q_nope Wuk^T                  [heads, kv_lora_rank]
    scores = ([q_lat | q_r] . [c_kv | k_r]) * scale
    o = (softmax(scores) . c_kv) Wuv

which is the same mathematics (a product's brackets moved; both terms of
the scores in one product over the plane's 576 columns) and reads each
cached token once for all heads. The output product runs over the whole
plane too and its last `rope` columns are dropped: a slice of the plane
into the product would be a copy of it. A decode step over the whole cache
(T = 1, every row its own) is one kernel on the TPU
(`ops/attention.latent_decode_attention`, named `mla_decode` in a trace),
which reads a row once for scores and output both; a prefill chunk, a
whole bucket and every other backend take the same products from XLA. The
platform picks (`jax.lax.platform_dependent`), no flag does. The program's tree therefore holds the two
halves apart, `wuk` and `wuv` [kv_lora_rank, heads, nope | v]: sliced out
of one matrix inside the step they would be copied every call.

Two things are read off the configuration's own keys, for the two families
that have this attention (`models/axk1.py`, `models/kimi_linear.py`):
`q_lora_rank` None is ONE query projection `wq` with no query norm, and
`mla_use_nope` true is NO rotation at all: the 64 "rope" columns are then a
plain key part all heads share, the scale is `(nope + rope) ** -0.5`
(`rope_scaling` None), and cache, absorbed products and kernel are the same.

Rotary embedding is YaRN over the rope dimensions (`yarn_inv_freq`, on
`llama.rope`'s rotate-half machinery); the softmax scale carries YaRN's
`mscale_all_dim` squared, and the tables' own factor `mscale /
mscale_all_dim` multiplies cos and sin (1 where the two are equal).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import attention as attention_ops
from .common import NEG_INF, KVCache, dense, layer_rows, rms_norm
from .llama import rope

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Yarn:
    """`rope_scaling` of type `yarn`, by `config.json`'s own keys."""

    factor: float = 32.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: Yarn) -> np.ndarray:
    """The dim/2 inverse frequencies: the base's own where a dimension
    turns more than `beta_fast` times over the original context, the base's
    over `factor` where it turns fewer than `beta_slow` times, a linear
    blend between (float32, computed at trace time)."""
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra, inter = 1.0 / theta ** exps, 1.0 / (yarn.factor * theta ** exps)

    def correction_dim(turns):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (turns * 2 * math.pi))
                ) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rotates(cfg) -> bool:
    """Whether the shared key part and its query part are rotated: not
    where the configuration says `mla_use_nope` (Kimi-Linear: the 64 "rope"
    columns are a plain shared key part, and other layers carry the
    order)."""
    return not getattr(cfg, "mla_use_nope", False)


def softmax_scale(cfg) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_scaling is None:
        return scale
    m = yarn_mscale(cfg.rope_scaling.factor, cfg.rope_scaling.mscale_all_dim)
    return scale * m * m


def init_cache(num_layers: int, batch: int, max_len: int, cfg,
               dtype=None) -> KVCache:
    """The latent cache: one plane, [c_kv | k_r] a token, and no `v`."""
    return KVCache(
        k=jnp.zeros((num_layers, batch, 1, max_len,
                     cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                    dtype or cfg.dtype),
        v=None,
        length=jnp.zeros((), jnp.int32),
    )


def init_params(keys, cfg, normal, ones) -> Params:
    """One layer's attention tree from six keys; `normal(key, *shape)`
    and `ones(*shape)` are the family's draws. `q_lora_rank` None: one
    query projection `wq` and no query norm."""
    d, h = cfg.hidden_size, cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    query = ({"wq": normal(keys[0], d, h * (dn + dr))} if qr is None else
             {"wqa": normal(keys[0], d, qr), "qn": {"scale": ones(qr)},
              "wqb": normal(keys[1], qr, h * (dn + dr))})
    return {
        **query,
        "wkva": normal(keys[2], d, kr + dr), "kvn": {"scale": ones(kr)},
        "wuk": normal(keys[3], kr, h, dn), "wuv": normal(keys[4], kr, h, dv),
        "wo": normal(keys[5], h * dv, d),
    }


def attention(h: jax.Array, ap: Params, cfg, layer: int,
              positions: jax.Array, q_slots: jax.Array, mask: jax.Array,
              plane: Optional[jax.Array], offset: jax.Array,
              rows: Optional[jax.Array]):
    """h [B, T, D] (normed) -> (attention's output [B, T, D], the cache's
    plane with this layer's tokens written, or None without a cache).
    `plane` is the cache's WITHOUT its head axis, [L, B, S, kr + rope]
    (`squeeze`): carried with the unit axis through a scatter, the TPU's
    compiler places that axis differently in the layouts of a
    conditional's two sides and copies the whole plane to reconcile them.
    `mask` [B, 1, T, S] bool over the key slots; `offset`, `q_slots`,
    `rows` as the families' `forward` has them (scalar or per-row ragged
    offset; the cache slots the T tokens go to; the cache rows a ragged
    batch addresses)."""
    b, t, _ = h.shape
    nh, eps = cfg.num_heads, cfg.rms_norm_eps
    dn, dr, kr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    if rotates(cfg):
        yarn = cfg.rope_scaling
        inv_freq = yarn_inv_freq(dr, cfg.rope_theta, yarn)
        table = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(
            yarn.factor, yarn.mscale_all_dim)

        def rotate(x):  # [B, H, T, rope]
            return rope(x, positions, cfg.rope_theta, inv_freq=inv_freq,
                        table_scale=table)
    else:
        def rotate(x):
            return x

    if cfg.q_lora_rank is None:
        q = dense(h, ap["wq"])
    else:
        c_q = rms_norm(dense(h, ap["wqa"]), ap["qn"]["scale"], eps)
        q = dense(c_q, ap["wqb"])
    q = q.reshape(b, t, nh, dn + dr).transpose(0, 2, 1, 3)
    q_nope, q_r = q[..., :dn], rotate(q[..., dn:])
    kva = dense(h, ap["wkva"])
    c_kv = rms_norm(kva[..., :kr], ap["kvn"]["scale"], eps)   # [B, T, kr]
    k_r = rotate(kva[:, None, :, kr:])[:, 0]                  # [B, T, rope]
    latent = jnp.concatenate([c_kv, k_r], axis=-1)           # [B, T, kr+dr]

    if plane is not None:
        new = latent.astype(plane.dtype)
        if offset.ndim == 1:
            # Ragged slots: each row's T tokens at its own offset;
            # out-of-range tails are dropped, never clamped.
            at_rows = (jnp.arange(b) if rows is None else rows)[:, None]
            plane = plane.at[layer, at_rows, q_slots, :].set(new)
        else:
            zero = jnp.zeros((), jnp.int32)
            plane = jax.lax.dynamic_update_slice(
                plane, new[None], (layer, zero, offset, zero))
        latent = layer_rows(plane, layer, rows).astype(h.dtype)  # [B,S,kr+dr]

    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bhtn,khn->bhtk", q_nope,
                           ap["wuk"].astype(h.dtype))
        q_cat = jnp.concatenate([q_lat, q_r], axis=-1)        # [B,H,T,kr+dr]
    scale = softmax_scale(cfg)

    def products(latent):
        with jax.named_scope("mla.scores"):
            scores = jnp.einsum("bhtc,bsc->bhts", q_cat, latent,
                                preferred_element_type=jnp.float32)
            scores = jnp.where(mask, scores * scale, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        with jax.named_scope("mla.out"):
            return jnp.einsum("bhts,bsc->bhtc", probs, latent)

    def kernel():
        with jax.named_scope("mla.scores"):
            return attention_ops.latent_decode_attention(
                q_cat[:, :, 0], plane, layer,
                attention_ops.mask_to_bias(mask), scale)[:, :, None]

    if plane is not None and t == 1 and rows is None:
        # A decode step over the whole cache: on the TPU one kernel that
        # reads each row once; the same products by XLA elsewhere. A row
        # the kernel cannot hold is refused for every backend: XLA's
        # products on the TPU relay the whole plane at every layer, a
        # cliff nobody would see.
        if not attention_ops.latent_decode_fits(
                plane.shape[2], plane.shape[3], plane.dtype.itemsize):
            raise ValueError(
                f"a latent cache {plane.shape[2]} tokens wide does not fit "
                "the decode kernel's VMEM (ops/attention.py "
                "latent_decode_fits); splitting a row over the kernel's "
                "grid is not built: serve shorter length buckets")
        o_all = jax.lax.platform_dependent(
            tpu=kernel, default=lambda: products(latent))
    else:
        o_all = products(latent)
    with jax.named_scope("mla.out"):
        o = jnp.einsum("bhtk,khv->bthv", o_all[..., :kr],
                       ap["wuv"].astype(h.dtype))
    return dense(o.reshape(b, t, -1), ap["wo"]), plane


def squeeze(cache: Optional[KVCache]) -> Optional[jax.Array]:
    """The latent cache's plane without its head axis, for `attention`."""
    return None if cache is None else cache.k[:, :, 0]


def unsqueeze(plane: jax.Array, cache: KVCache, t: int) -> KVCache:
    """The cache with `attention`'s plane back in it, `t` tokens on."""
    return KVCache(k=plane[:, :, None], v=None, length=cache.length + t)


def split_kv_b(kv_b: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """A checkpoint's `kv_b_proj` [kv_lora_rank, heads x (nope + v)] (stored
    [in, out]) into the two halves the program holds apart."""
    w = kv_b.reshape(cfg.kv_lora_rank, cfg.num_heads,
                     cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]
