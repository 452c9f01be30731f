"""OpenBMB's `minicpm_sala` decoder (MiniCPM-SALA, 9 B) in pure-functional
JAX: a hybrid of two mixers under one muP-scaled trunk, the mixer chosen by
the layer's entry in `mixer_types` (8 `minicpm4` among 24 `lightning-attn`):

    h  = E[ids] * scale_emb
    h += scale_depth / sqrt(PUBLISHED layers) * mixer(N1(h))
    h += scale_depth / sqrt(PUBLISHED layers) * SwiGLU(N2(h))
    logits = head(N(h) / (hidden_size / dim_model_base))

- `minicpm4`: InfLLM-v2 block-sparse attention, 32 query heads on 2 key
  heads, no position signal, q and k RMS-normalised a head, a sigmoid gate
  `Wg x` on the heads' output. A query below `dense_len` attends causally to
  every key. At or past it, it attends to the keys of `topk` blocks of
  `block_size`, chosen once for the 16 heads of a key head's group: the
  group's heads' softmaxes over the POOLED keys (means over `kernel_size`
  keys, one a `kernel_stride`) that lie wholly behind the query are summed;
  a block scores the largest of the pooled keys that overlap it; the first
  `init_blocks` and the blocks of the last `window_size` keys are always
  taken, the best others fill the set. The choice is by the QUERY'S OWN
  POSITION, in a prefill chunk as in a decode step (the published prefill
  decides by the length of its call, which a chunked prefill under a prefix
  cache cannot reproduce). `KVCache.k`/`.v` [La, B, 2, T, 128] hold these
  layers' keys and values and `KVCache.pool` [La, B, 2, NP, 128] their
  pooled keys (`ops/sparse.py` has the plane's layout and why a prefix block
  carries its entries whole).
- `lightning-attn`: Lightning linear attention, 32 heads of 128: q and k
  RMS-normalised a head and then rotated (theta 10,000), `S <- lambda_h S +
  k v^T`, `o = S^T q / sqrt(128)`, `lambda_h = exp(-s_h (1 - l / (L - 1) +
  1e-5))` with the ALiBi slopes `s_h = 2^(-8 h / H)`, h from 1, and l the
  layer's PUBLISHED index of the published L; then `RMSNorm_head(o) *
  sigmoid(Wg x)` and `Wo`. The state is `KVCache.ssm` [Ll, B, 32, 128, 128]
  float32; there is no convolution and `KVCache.conv` is None.

Two forms of each mixer, which must agree: THE STEP (T = 1 over every row of
the cache: on the TPU the kernels `sparse_append`, `sparse_select`,
`sparse_decode` and `lightning_step`, `ops/sparse.py` and
`ops/lightning.py`) and THE GENERAL
form (a prefill chunk for the rows `rows` names, a whole bucket, a forward
without a cache; every backend but the TPU runs the step through it too):
the selection as a mask over the row's keys, the state by a chunked scan
whose decays are `exp` of differences of cumulative sums, never positive.

Layers are a list of per-layer trees, unrolled (`models/afmoe.py`). A cut
(`minicpm-sala-8l`: the published layers 17 to 24) keeps the published depth
in `published_layers` and its first layer's published index in
`layer_offset`: the muP residual scale and the Lightning decays are the
published model's. Same family surface and forward contract as the other
families (`models/registry.py`). `live` [B] or [B, T] says which tokens are
real: **a token that is not live leaves its row's Lightning state as it
was**; where it is not given it is read off `kv_mask`, else every token is
live. With `aux` the forward hands out {"counts": int32 [4] (`COUNTERS`),
"selection": bool [La, B, 2, T, NB], the blocks each query chose}.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import lightning as lightning_ops
from ..ops import sparse
from . import quant
from .afmoe import batch_slots, swiglu
from .common import (
    NEG_INF,
    KVCache,
    attend,
    causal_window_mask,
    dense,
    layer_rows,
    merge_heads,
    rms_norm,
    split_heads,
)
from .llama import rope

Params = Dict[str, Any]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# `mixer_types` as published: the sparse layers among the 32.
PUBLISHED_SPARSE = (0, 9, 16, 17, 22, 29, 30, 31)
PUBLISHED_MIXERS = tuple(SPARSE if i in PUBLISHED_SPARSE else LIGHTNING
                         for i in range(32))

# What the decode steps of the sparse layers count, in the order of
# `counts` (the engine's counters `engine_<name>`): lane-steps of a sparse
# layer, those of them past `dense_len`, the keys they attended and the keys
# their contexts held.
COUNTERS = ("attn_lane_steps", "sparse_lane_steps", "sparse_keys_attended",
            "sparse_keys_in_context")

# Positions a sub-chunk of the Lightning chunk form holds.
SUB_CHUNK = 32
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaConfig:
    vocab_size: int = 73448
    max_position_embeddings: int = 524288
    hidden_size: int = 4096
    num_layers: int = 32            # the layers HELD
    published_layers: int = 32      # config.json: num_hidden_layers
    layer_offset: int = 0           # published index of the first layer held
    mixer_types: Tuple[str, ...] = PUBLISHED_MIXERS
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    intermediate_size: int = 16384
    lightning_heads: int = 32       # lightning_nh = lightning_nkv
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    # MiniCPM4's published sparse_config (InfLLM-v2).
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # The engines set this for an int8 cache; this family has none.
    quant_kv: bool = False

    def __post_init__(self):
        s = self.kernel_stride
        if (len(self.mixer_types) != self.num_layers
                or self.kernel_size != 2 * s or self.block_size % s
                or self.dense_len % self.block_size
                or self.init_blocks + self.window_size // self.block_size + 1
                > self.topk):
            raise ValueError(
                f"minicpm_sala: {len(self.mixer_types)} mixers for "
                f"{self.num_layers} layers, or a sparse_config the pooled "
                f"plane cannot hold (kernel {self.kernel_size} = 2 x stride "
                f"{s}, block {self.block_size} and dense_len "
                f"{self.dense_len} whole strides and blocks, the blocks "
                f"always taken within topk {self.topk})")

    # What the paged engine reads (engine/paged.py): positions one entry of
    # the pooled plane stands for.
    @property
    def pool_stride(self) -> int:
        return self.kernel_stride

    def is_sparse(self, layer: int) -> bool:
        return self.mixer_types[layer] == SPARSE

    def index(self, layer: int) -> int:
        """Layer `layer`'s index among the layers of its own mixer."""
        return sum(m == self.mixer_types[layer]
                   for m in self.mixer_types[:layer])

    @property
    def sparse_layers(self) -> int:
        return sum(m == SPARSE for m in self.mixer_types)

    @property
    def gather_blocks(self) -> int:
        """Blocks a decode lane may bring: `topk` chosen, or every block
        below `dense_len`."""
        return max(self.topk, self.dense_len // self.block_size)

    @classmethod
    def minicpm_sala(cls, **kw) -> "MiniCPMSalaConfig":
        """openbmb/MiniCPM-SALA as published: 32 layers, 9 B."""
        return cls(**kw)

    @classmethod
    def minicpm_sala_8l(cls, **kw) -> "MiniCPMSalaConfig":
        """A pipeline stage of 8 of the 32 layers, whole on one chip: the
        published layers 17 to 24 (`minicpm4` at 17 and 22, Lightning at
        18-21, 23, 24: two periods at the published 1 : 3), every width."""
        return cls(num_layers=8, layer_offset=17,
                   mixer_types=PUBLISHED_MIXERS[17:25], **kw)

    @classmethod
    def tiny(cls, **kw) -> "MiniCPMSalaConfig":
        """Test size: the cut's shape at widths, and a sparse_config, a CPU
        test can afford (past 16 positions a query chooses 6 blocks of 4)."""
        kw.setdefault("vocab_size", 384)
        kw.setdefault("max_position_embeddings", 64)
        return cls(
            hidden_size=32, num_layers=8, layer_offset=17,
            mixer_types=PUBLISHED_MIXERS[17:25], num_heads=4, num_kv_heads=2,
            head_dim=8, intermediate_size=64, lightning_heads=4,
            lightning_head_dim=8, kernel_size=4, kernel_stride=2,
            block_size=4, init_blocks=1, window_size=8, topk=6, dense_len=16,
            **kw)


def depth_scale(cfg: MiniCPMSalaConfig) -> float:
    """What every residual branch is multiplied by: the PUBLISHED depth's."""
    return cfg.scale_depth / math.sqrt(cfg.published_layers)


def log_decay(cfg: MiniCPMSalaConfig, layer: int) -> jax.Array:
    """[H] float32, <= 0: layer `layer`'s (from 0, of the layers held)
    log lambda_h, by its PUBLISHED index (module docstring)."""
    h = cfg.lightning_heads
    slopes = 2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
    depth = (cfg.layer_offset + layer) / (cfg.published_layers - 1)
    return -slopes * (1.0 - depth + 1e-5)


def init_params(rng: jax.Array, cfg: MiniCPMSalaConfig) -> Params:
    """Seeded weights, each leaf drawn in the parameter dtype."""
    d, pd = cfg.hidden_size, cfg.param_dtype
    std = 0.02

    def norm(key, *shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(pd)

    def ones(*shape):
        return jnp.ones(shape, pd)

    def layer(key, i):
        ks = jax.random.split(key, 8)
        if cfg.is_sparse(i):
            hq, hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        else:
            hq = hk = cfg.lightning_heads
            dh = cfg.lightning_head_dim
        attn = {"wq": norm(ks[0], d, hq * dh), "wk": norm(ks[1], d, hk * dh),
                "wv": norm(ks[2], d, hk * dh), "wg": norm(ks[3], d, hq * dh),
                "wo": norm(ks[4], hq * dh, d),
                "qn": {"scale": ones(dh)}, "kn": {"scale": ones(dh)}}
        if not cfg.is_sparse(i):
            attn["on"] = {"scale": ones(dh)}
        f = cfg.intermediate_size
        return {"ln1": {"scale": ones(d)}, "ln2": {"scale": ones(d)},
                "attn": attn,
                "mlp": {"wg": norm(ks[5], d, f), "wu": norm(ks[6], d, f),
                        "wd": norm(ks[7], f, d)}}

    keys = jax.random.split(rng, cfg.num_layers + 2)
    return {
        "embed": norm(keys[0], cfg.vocab_size, d),
        "layers": [layer(keys[2 + i], i) for i in range(cfg.num_layers)],
        "lnf": {"scale": ones(d)},
        "lm_head": norm(keys[1], cfg.vocab_size, d),
    }


def init_cache(cfg: MiniCPMSalaConfig, batch: int, max_len: int,
               dtype=None, groups=None) -> KVCache:
    """The sparse layers' keys, values and pooled keys, and the Lightning
    layers' state beside them (`models/common.py` `KVCache`). `groups`
    (models/registry.py): bfloat16 planes of 128-wide heads tile as they
    are."""
    if cfg.quant_kv:
        raise ValueError("minicpm_sala serves the published bfloat16 cache "
                         "and a float32 state: kv_quant is not supported")
    if max_len % cfg.block_size:
        raise ValueError(f"minicpm_sala reads its cache in whole blocks of "
                         f"{cfg.block_size} positions: a width of {max_len} "
                         f"is not one")
    dtype = dtype or cfg.dtype
    la = cfg.sparse_layers
    cache = KVCache.create(la, batch, cfg.num_kv_heads, max_len, cfg.head_dim,
                           dtype)
    h, kd = cfg.lightning_heads, cfg.lightning_head_dim
    return cache._replace(
        pool=jnp.zeros((la, batch, cfg.num_kv_heads,
                        sparse.pool_len(max_len, cfg.kernel_stride),
                        cfg.head_dim), dtype),
        ssm=jnp.zeros((cfg.num_layers - la, batch, h, kd, kd), jnp.float32))


# --------------------------------------------------------------- lightning


def _chunk_scan(q, k, v, g, state):
    """Linear attention with a scalar decay a head over T positions from
    `state`, in the chunked form: q, k [B, T, H, K], v [B, T, H, V] (k is 0
    where not live), g [B, T, H] (log decay, <= 0, 0 where not live), state
    [B, H, K, V]; all float32 -> (o [B, T, H, V], the state after T-1)."""
    b, t, h, _ = q.shape
    n = min(t, SUB_CHUNK)
    pad = -t % n
    if pad:  # g = 0 and k = 0 move nothing
        q, k, v, g = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
                      for x in (q, k, v, g))
    nq = (t + pad) // n
    seen = jnp.tril(jnp.ones((n, n), bool))               # s <= t

    def sub(state, part):
        q, k, v, g = part                                 # [B, Q, H, ...]
        cs = jnp.cumsum(g, axis=1)                        # [B, Q, H], <= 0
        # exp(G_t - G_s) for s <= t alone: the differences are <= 0.
        decay = jnp.exp(jnp.where(seen[None, :, :, None],
                                  cs[:, :, None] - cs[:, None, :], -jnp.inf))
        qk = jnp.einsum("bthc,bshc->bhts", q, k, precision=_HI)
        qk = qk * decay.transpose(0, 3, 1, 2)
        o = (jnp.einsum("bthc,bhcv->bthv", q * jnp.exp(cs)[..., None], state,
                        precision=_HI)
             + jnp.einsum("bhts,bshv->bthv", qk, v, precision=_HI))
        out = jnp.exp(cs[:, -1:] - cs)[..., None] * k     # [B, Q, H, K]
        state = (jnp.exp(cs[:, -1])[..., None, None] * state
                 + jnp.einsum("bshc,bshv->bhcv", out, v, precision=_HI))
        return state, o

    parts = tuple(x.reshape(b, nq, n, *x.shape[2:]).swapaxes(0, 1)
                  for x in (q, k, v, g))
    if nq == 1:
        state, o = sub(state, tuple(x[0] for x in parts))
        return o[:, :t], state
    state, os_ = jax.lax.scan(sub, state, parts)
    return os_.swapaxes(0, 1).reshape(b, nq * n, h, -1)[:, :t], state


def lightning(x: jax.Array, ap: Params, cfg: MiniCPMSalaConfig, layer: int,
              positions: jax.Array, live: jax.Array,
              ssm: Optional[jax.Array], rows: Optional[jax.Array]):
    """One Lightning mixer over x [B, T, D] -> (out [B, T, D], ssm). `ssm`
    is the cache's stacked state plane (None: every sequence starts from
    zeros and nothing is kept) and `layer` the layer's index of the layers
    held; batch element i owns row i, or row `rows[i]`. T = 1 over every row
    of the plane is the step form, all else the chunk form."""
    b, t, _ = x.shape
    h, kd = cfg.lightning_heads, cfg.lightning_head_dim
    f32 = jnp.float32
    eps = cfg.rms_norm_eps
    with jax.named_scope("lightning.proj"):
        q = rms_norm(split_heads(dense(x, ap["wq"]), h), ap["qn"]["scale"],
                     eps)
        k = rms_norm(split_heads(dense(x, ap["wk"]), h), ap["kn"]["scale"],
                     eps)
        v = split_heads(dense(x, ap["wv"]), h)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        gate = dense(x, ap["wg"])
    # The activations are the served dtype's values; the state and
    # everything that multiplies it stay float32. [B, T, H, K].
    q, k, v = (a.astype(f32).transpose(0, 2, 1, 3) for a in (q, k, v))
    q = q * kd ** -0.5
    k = jnp.where(live[..., None, None], k, 0.0)
    g = jnp.where(live[..., None], log_decay(cfg, layer), 0.0)  # [B, T, H]
    index = cfg.index(layer)
    with jax.named_scope("lightning.scan"):
        if ssm is not None and t == 1 and rows is None:
            step = (q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]))
            ssm, o = jax.lax.platform_dependent(
                ssm, *step,
                tpu=lambda s, *ops: lightning_ops.lightning_step(
                    s, index, *ops),
                default=lambda s, *ops: lightning_ops.lightning_step_reference(
                    s, index, *ops))
            o = o[:, None]
        else:
            state = (jnp.zeros((b, h, kd, kd), f32) if ssm is None
                     else layer_rows(ssm, index, rows))
            o, state = _chunk_scan(q, k, v, g, state)
            if ssm is not None:
                ssm = ssm.at[index if rows is None else (index, rows)].set(
                    state)
    with jax.named_scope("lightning.out"):
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + eps)
        o = o * ap["on"]["scale"].astype(f32) * jax.nn.sigmoid(
            gate.astype(f32).reshape(b, t, h, kd))
        out = dense(o.reshape(b, t, h * kd).astype(x.dtype), ap["wo"])
    return out, ssm


# ------------------------------------------------------------ sparse layers


def pooled_keys(k: jax.Array, stride: int) -> jax.Array:
    """A whole sequence's pooled plane from its keys [B, Hkv, T, D]: entry b
    the mean of the keys of group b (`ops/sparse.py`); a group the sequence
    does not fill has none."""
    *lead, t, d = k.shape
    groups = k[..., :t // stride * stride, :].astype(jnp.float32).reshape(
        *lead, t // stride, stride, d)
    return jnp.mean(groups, axis=-2).astype(k.dtype)


def _write_general(ck, cv, pool, index: int, rows, offset, k, v,
                   stride: int):
    """The planes with a batch's new keys and values k, v [B, Hkv, T, D]
    written at each row's own `offset` [B], and the pooled entries of the
    groups they touch computed anew from the keys now in the plane: a row at
    a time, each slice cut out, changed and put back where it lies (a
    scatter of the rows makes the compiler re-lay the planes it is handed:
    `ops/sparse.py`). A row past the cache's last (a bare row of a prefill
    pass) writes nothing. An entry is final once its group's last key is
    real; a later write to the group computes it again."""
    b, hkv, t, d = k.shape
    width, zero = ck.shape[3], jnp.zeros((), jnp.int32)
    # The groups a write can touch: one for a step's token, else one more
    # than whole groups for a chunk that starts inside a group.
    n_g = 1 if t == 1 else min(-(-t // stride) + 1, width // stride)
    for i in range(b):
        row = jnp.asarray(i, jnp.int32) if rows is None else rows[i]
        real = row < ck.shape[1]
        off = offset[i]

        def put(plane, new, at, real=real, row=row):
            at = (jnp.asarray(index, jnp.int32), row, zero, at, zero)
            old = jax.lax.dynamic_slice(plane, at, (1, 1) + new.shape)
            return jax.lax.dynamic_update_slice(
                plane, jnp.where(real, new[None, None].astype(plane.dtype),
                                 old), at)

        ck = put(ck, k[i], off)
        cv = put(cv, v[i], off)
        first = jnp.clip(off // stride, 0, width // stride - n_g)
        keys = jax.lax.dynamic_slice(
            ck, (jnp.asarray(index, jnp.int32), jnp.minimum(
                row, ck.shape[1] - 1), zero, first * stride, zero),
            (1, 1, hkv, n_g * stride, d))[0, 0]
        pool = put(pool, pooled_keys(keys, stride), first)
    return ck, cv, pool


def _selection(q, pool, pos, cfg: MiniCPMSalaConfig, blocks: int):
    """The general form's choice: q [B, Hkv, G, T, D], pool [B, Hkv, NP, D],
    pos [B, T] -> the blocks each query and key head reads, [B, Hkv, T,
    topk] int32 (`ops/sparse.py` `choose`)."""
    t = jnp.einsum("bhgtd,bhnd->bhgtn", q, pool.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    t = t * cfg.head_dim ** -0.5
    # c_j's score is the mean of its two groups' (entries j and j + 1).
    s = 0.5 * (t + jnp.pad(t[..., 1:], [(0, 0)] * 4 + [(0, 1)]))
    vis = sparse.visible(pool.shape[2], cfg.kernel_stride, pos)[:, None, None]
    s = jnp.where(vis, s, NEG_INF)
    e = jnp.where(vis, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    scores = sparse.block_scores(
        jnp.sum(p, axis=2), cfg.block_size // cfg.kernel_stride, blocks)
    return sparse.choose(
        scores, pos[:, None, :], block=cfg.block_size,
        topk=min(cfg.topk, blocks), init_blocks=cfg.init_blocks,
        window=cfg.window_size)


def _chosen(idx: jax.Array, blocks: int) -> jax.Array:
    """[..., topk] block indices -> [..., blocks] bool."""
    return jnp.any(idx[..., None] == jnp.arange(blocks, dtype=jnp.int32),
                   axis=-2)


def _attend_general(q, k, v, pool, pos, mask, cfg: MiniCPMSalaConfig):
    """q [B, nh, T, D] over the rows' keys and values [B, Hkv, S, D] under
    `mask` [B, 1, T, S] (causal, valid slots): dense where the query's
    position `pos` [B, T] is below `dense_len`, else over the keys of the
    chosen blocks -> (out [B, nh, T, D], chosen [B, Hkv, T, NB] bool)."""
    b, nh, t, dh = q.shape
    nkv = cfg.num_kv_heads
    groups = nh // nkv
    s = k.shape[2]
    blocks = -(-s // cfg.block_size)
    idx = _selection(q.reshape(b, nkv, groups, t, dh), pool, pos, cfg, blocks)
    chosen = _chosen(idx, blocks)
    keys = jnp.repeat(chosen, cfg.block_size, axis=-1)[..., :s]
    keys = (keys | (pos < cfg.dense_len)[:, None, :, None]) & mask
    a = attend(q.reshape(b, nkv, groups * t, dh), k, v,
               jnp.tile(keys, (1, 1, groups, 1)))
    return a.reshape(b, nh, t, dh), chosen


def _attend_step(q, ck, cv, pool, index: int, pos,
                 cfg: MiniCPMSalaConfig):
    """The decode step's attention on the TPU: q [B, nh, 1, D], the stacked
    planes, pos [B] -> (out [B, nh, 1, D], chosen [B, Hkv, 1, NB])."""
    b, nh, _, dh = q.shape
    nkv, bs = cfg.num_kv_heads, cfg.block_size
    blocks = ck.shape[3] // bs
    topk = min(cfg.topk, blocks)
    n = min(cfg.gather_blocks, blocks)
    scale = dh ** -0.5
    qg = q.reshape(b, nkv, nh // nkv, dh)
    probs = sparse.sparse_select(pool, index, qg, pos, scale=scale,
                                 stride=cfg.kernel_stride)
    idx = sparse.choose(
        sparse.block_scores(probs, bs // cfg.kernel_stride, blocks),
        pos[:, None], block=bs, topk=topk, init_blocks=cfg.init_blocks,
        window=cfg.window_size)                           # [B, Hkv, topk]
    full = pos < cfg.dense_len
    every = jnp.arange(n, dtype=jnp.int32)
    bring = jnp.where(full[:, None, None], every,
                      jnp.pad(idx, [(0, 0), (0, 0), (0, n - topk)]))
    count = jnp.where(full, jnp.minimum(pos // bs + 1, n), topk)
    key_pos = (bring[..., None] * bs
               + jnp.arange(bs, dtype=jnp.int32)).reshape(b, nkv, n * bs)
    ok = ((key_pos <= pos[:, None, None])
          & (jnp.repeat(every, bs) < count[:, None, None]))
    o = sparse.sparse_decode(
        ck, cv, index, qg, bring, count,
        jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)[:, :, None],
        scale=scale, block=bs)
    return o.reshape(b, nh, 1, dh), _chosen(idx, blocks)[:, :, None]


def forward(
    params: Params,
    cfg: MiniCPMSalaConfig,
    input_ids: jax.Array,
    cache: Optional[KVCache] = None,
    positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    live: Optional[jax.Array] = None,
    aux: bool = False,
    rows: Optional[jax.Array] = None,
):
    """Run the decoder; returns (logits [B, T, V] float32, updated cache),
    and with `aux` a third value (module docstring). `positions` drive the
    Lightning layers' rotation; the sparse layers' masks and choices are
    built on cache SLOTS, which are a row's own positions (prompts are
    right-padded from slot 0)."""
    b, t = input_ids.shape
    given = live is not None
    offset, q_slots, positions, live = batch_slots(
        input_ids, cache, positions, live, rows)
    num_keys = t if cache is None else cache.k.shape[3]
    mask = causal_window_mask(q_slots, num_keys)
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, None, :]
        if not given:
            # A token whose own key slot is masked is padding.
            live = jnp.take_along_axis(
                kv_mask, jnp.minimum(q_slots, kv_mask.shape[1] - 1), axis=1)
    eps, dh = cfg.rms_norm_eps, cfg.head_dim
    nh, nkv, stride = cfg.num_heads, cfg.num_kv_heads, cfg.kernel_stride
    ck = cv = pool = ssm = None
    if cache is not None:
        ck, cv, pool, ssm = cache.k, cache.v, cache.pool, cache.ssm
        if (pool is None or num_keys % cfg.block_size
                or pool.shape[3] != sparse.pool_len(num_keys, stride)):
            raise ValueError(
                f"minicpm_sala: a cache {num_keys} positions wide without "
                f"the pooled plane of that width (models/minicpm_sala.py "
                f"init_cache). A sparse layer reads blocks by their slots, "
                f"a row's own positions: rows are right-padded from slot 0 "
                f"and grown with their pooled plane (engine/paged.py); the "
                f"bucketed generator's left-padded cache is not built for "
                f"this family")
    off_rows = jnp.broadcast_to(offset, (b,))
    step = cache is not None and t == 1 and rows is None
    selections = []

    def sparse_attention(h, ap, layer):
        nonlocal ck, cv, pool
        index = cfg.index(layer)
        q = rms_norm(split_heads(dense(h, ap["wq"]), nh), ap["qn"]["scale"],
                     eps)
        k = rms_norm(split_heads(dense(h, ap["wk"]), nkv), ap["kn"]["scale"],
                     eps)
        v = split_heads(dense(h, ap["wv"]), nkv)
        if cache is None:
            a, chosen = _attend_general(
                q, k, v, pooled_keys(k, stride), q_slots, mask, cfg)
        else:
            if step:
                ck, cv, pool = jax.lax.platform_dependent(
                    ck, cv, pool, k, v,
                    tpu=lambda ck, cv, pool, k, v: sparse.sparse_append(
                        ck, cv, pool, index, k, v, q_slots[:, 0],
                        stride=stride),
                    default=lambda ck, cv, pool, k, v: _write_general(
                        ck, cv, pool, index, rows, off_rows, k, v, stride))
            else:
                ck, cv, pool = _write_general(ck, cv, pool, index, rows,
                                              off_rows, k, v, stride)

            def general(q, ck, cv, pool):
                return _attend_general(
                    q, layer_rows(ck, index, rows).astype(q.dtype),
                    layer_rows(cv, index, rows).astype(q.dtype),
                    layer_rows(pool, index, rows), q_slots, mask, cfg)

            if step:
                a, chosen = jax.lax.platform_dependent(
                    q, ck, cv, pool,
                    tpu=lambda q, ck, cv, pool: _attend_step(
                        q, ck, cv, pool, index, q_slots[:, 0], cfg),
                    default=general)
            else:
                a, chosen = general(q, ck, cv, pool)
        selections.append(chosen)
        gate = jax.nn.sigmoid(dense(h, ap["wg"]).astype(jnp.float32))
        a = merge_heads(a)
        return dense((a.astype(jnp.float32) * gate).astype(a.dtype),
                     ap["wo"])

    x = quant.embed_lookup(params["embed"], input_ids).astype(cfg.dtype)
    x = x * jnp.asarray(cfg.scale_emb, cfg.dtype)
    branch = jnp.asarray(depth_scale(cfg), cfg.dtype)
    for layer, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["ln1"]["scale"], eps)
        if cfg.is_sparse(layer):
            with jax.named_scope("attn.sparse"):
                a = sparse_attention(h, lp["attn"], layer)
        else:
            with jax.named_scope("attn.lightning"):
                a, ssm = lightning(h, lp["attn"], cfg, layer, positions,
                                   live, ssm, rows)
        x = x + a * branch
        with jax.named_scope("mlp.dense"):
            x = x + swiglu(rms_norm(x, lp["ln2"]["scale"], eps),
                           lp["mlp"]) * branch
    new_cache = None
    if cache is not None:
        new_cache = cache._replace(k=ck, v=cv, pool=pool, ssm=ssm,
                                   length=cache.length + t)
    x = rms_norm(x, params["lnf"]["scale"], eps)
    x = x / jnp.asarray(cfg.hidden_size / cfg.dim_model_base, x.dtype)
    logits = quant.unembed(x, params["lm_head"])
    if not aux:
        return logits, new_cache
    return logits, new_cache, {
        "counts": _counts(cfg, q_slots, live) if t == 1 else jnp.zeros(
            (len(COUNTERS),), jnp.int32),
        "selection": jnp.stack(selections)}


def _counts(cfg: MiniCPMSalaConfig, q_slots, live) -> jax.Array:
    """`COUNTERS` of one decode step: the sparse layers' live lanes, those
    past `dense_len`, the keys they attended (the chosen blocks' keys at or
    behind the query: `topk` blocks of which the query's own is partial) and
    the keys their contexts held."""
    pos, lanes = q_slots[:, 0], live[:, 0]
    past = lanes & (pos >= cfg.dense_len)
    bs = cfg.block_size
    held = jnp.where(lanes, pos + 1, 0)
    attended = jnp.where(past, cfg.topk * bs - (bs - 1 - pos % bs), held)
    return cfg.sparse_layers * jnp.stack([
        jnp.sum(lanes, dtype=jnp.int32), jnp.sum(past, dtype=jnp.int32),
        jnp.sum(attended, dtype=jnp.int32), jnp.sum(held, dtype=jnp.int32)])


def params_from_hf(sd, cfg: MiniCPMSalaConfig) -> Params:
    """The published checkpoint's names into this tree, as far as the
    `config.json` gives them: `model.layers.<i>.self_attn.{q,k,v,o}_proj`,
    `.q_norm`, `.k_norm`, the output gate `.o_gate` (`attn_use_output_gate`
    / `use_output_gate`) and, in a Lightning layer, the output norm
    `.o_norm` (`use_output_norm`); `input_layernorm`,
    `post_attention_layernorm`, `mlp.{gate,up,down}_proj`; `model.norm`,
    `model.embed_tokens`, `lm_head`. Linears are stored [out, in] there and
    [in, out] here; a cut reads the published layers `layer_offset` on."""
    pd = cfg.param_dtype

    def lin(name):
        return jnp.asarray(sd[name + ".weight"], pd).T

    def vec(name):
        return {"scale": jnp.asarray(sd[name + ".weight"], pd)}

    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{cfg.layer_offset + i}"
        a = p + ".self_attn"
        attn = {"wq": lin(a + ".q_proj"), "wk": lin(a + ".k_proj"),
                "wv": lin(a + ".v_proj"), "wg": lin(a + ".o_gate"),
                "wo": lin(a + ".o_proj"), "qn": vec(a + ".q_norm"),
                "kn": vec(a + ".k_norm")}
        if not cfg.is_sparse(i):
            attn["on"] = vec(a + ".o_norm")
        layers.append({
            "ln1": vec(p + ".input_layernorm"),
            "ln2": vec(p + ".post_attention_layernorm"), "attn": attn,
            "mlp": {"wg": lin(p + ".mlp.gate_proj"),
                    "wu": lin(p + ".mlp.up_proj"),
                    "wd": lin(p + ".mlp.down_proj")}})
    return {
        "embed": jnp.asarray(sd["model.embed_tokens.weight"], pd),
        "layers": layers,
        "lnf": vec("model.norm"),
        "lm_head": jnp.asarray(sd["lm_head.weight"], pd),
    }
