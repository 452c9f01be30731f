"""Liquid AI's `lfm2_moe` decoder (LFM2-8B-A1B and its family) in
pure-functional JAX: a hybrid whose token mixer is, in three layers of four,
a GATED SHORT CONVOLUTION, and grouped-query attention in the fourth; a
dense SwiGLU in the leading layers and routed experts after them.

    h = E[ids];  h += Op(RMSNorm(h));  h += FF(RMSNorm(h))   a layer
    logits = RMSNorm(h) E^T                    (`embedding_norm`, tied head)

- `conv` (`conv_L_cache` K = 3): `[B | C | z] = x W_in` (D -> 3D); `u = B *
  z`; `c_t = sum_j w_j u_{t-K+1+j}`, a causal depthwise convolution, one
  K-tap filter a channel, the inputs before the sequence's start zero; `y =
  C * c`; `out = y W_out`. No activation, no bias, no norm inside. What a
  sequence carries is its last K-1 inputs `u`, `KVCache.conv` [Lc, B, K-1,
  D]: 8 KB a layer and row at the published width, and NO `ssm` plane (the
  first family whose recurrent state is the window alone). The decode step
  (T = 1 over every row of the cache) is `ops/shortconv.py`
  `shortconv_step`, on the TPU one Pallas kernel a layer that shifts the
  live rows' windows where they lie (`jax.lax.platform_dependent` picks it;
  plain `jax.numpy` elsewhere); a prefill chunk is `models/mamba2.py`
  `causal_conv` without its activation: the window carried from chunk to
  chunk, and the window kept the one that ends at a row's last live
  position;
- `full_attention`: `q = RoPE(RMSNorm_head(x Wq))`, `k = RoPE(RMSNorm_head(x
  Wk))`, `v = x Wv`; grouped keys and values (32 query heads on 8 of 64),
  the per-head norm (a learned weight of `head_dim`) BEFORE the rotation,
  the rotation over the whole head (`llama.rope`, halves rotated), causal
  softmax at `head_dim ** -0.5`, no bias. `KVCache.k` / `.v` hold the
  attention layers alone, and a head of 64 is half a lane tile: the planes
  are declared [La, B, 1, T, Hkv * Dh], a position's 8 key heads side by
  side in ONE row of 512 (`fold_kv`; `models/common.py` `KVCache`'s folded
  planes at one group), and the trunk carries them without the unit axis
  (`models/mla.py`'s way). As [.., 8, T, 64] the runtime rests a bfloat16
  plane positions-minor, and the compiled megastep relaid both whole
  planes, 1.1 GB each, heads-and-64-minor (padded to 128 lanes) and back
  around every attention layer of every pass; as [.., 4, T, 128] it
  carried them heads-minor for the scatters and copied every attention
  layer's slice, 369 MB, positions-minor for the products at every decode
  step, as it does trinity-mini's [.., 4, T, 128] (the compiler's own text
  for a described v5e, PR 57; `tests/test_chip_compile.py` holds this
  family to none of either). A query head meets its key head's 64 columns
  of the row: it is laid into them in a row-wide query with zeros beside
  it, so one product over the row gives its scores, and of the values' 512
  columns it keeps its own 64 (`attend_folded`: eight times the scores'
  operations, 0.2 ms a decode step at 64 lanes, for one read of the row by
  all 32 query heads);
- the feed-forward: SwiGLU `w2(silu(w1 x) * w3 x)`, dense in the layers
  whose PUBLISHED index is below `num_dense_layers`, after them
  `num_experts` routed experts, `num_experts_per_tok` a token:
  `moe.route_sigmoid` (sigmoid scores in float32; the choice by score +
  `expert_bias`, the weights the chosen scores over their sum + 1e-6, times
  `routed_scaling_factor`), `moe.grouped_swiglu`, no shared expert, no
  capacity and no drops. A process may hold a SHARE of a layer's experts
  (`experts_held`, as `models/axk1.py`): it routes over all of them and
  computes its own part.

A cut keeps layers `layer_offset` .. `layer_offset + num_layers - 1` of the
published `published_layers` and addresses them by their PUBLISHED index:
which are attention and which dense is read off it (`layer_types` holds the
kept layers' types, `is_dense` adds the offset).

Same family surface and forward contract as the other families
(`models/registry.py`): `positions` drive the rotation and nothing else;
cache slots written at `cache.length`, scalar or per-row ragged; `kv_mask`
marks valid key slots; `rows` names the cache rows a ragged batch
addresses; T = 1, a chunk or a bucket. `live` [B] or [B, T] says which
tokens are real: **a token that is not live leaves its row's windows as
they were**, bit for bit, and reaches no expert. Where `live` is not given
it is read off `kv_mask` (a token whose own key slot is masked is padding),
else every token is live.

`pad_experts` holds the experts' stacks with their inner width in whole
tiles of the TPU's grouped product (PERF.md section 6, PR 57, has the
probe), the added columns and rows zero: `silu(0) * 0 = 0`, so the expert
computed is the published one to the bit.

The trunk is a list of per-layer trees, unrolled, as afmoe's (whose
`batch_slots` and `swiglu` it uses).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import shortconv
from . import afmoe, quant
from .afmoe import batch_slots, swiglu
from .common import (
    NEG_INF,
    KVCache,
    causal_window_mask,
    dense,
    layer_rows,
    merge_heads,
    rms_norm,
    split_heads,
)
from .llama import rope
from .mamba2 import causal_conv
from .moe import grouped_swiglu, route_sigmoid

Params = Dict[str, Any]

CONV, FULL = "conv", "full_attention"
# `layer_types` as published: the attention layers among the 24.
PUBLISHED_ATTENTION = (2, 6, 10, 14, 18, 21)
PUBLISHED_TYPES = tuple(FULL if i in PUBLISHED_ATTENTION else CONV
                        for i in range(24))

# afmoe's three counts, the picks that landed on the experts held (all of
# them, where a process holds the layer whole), and the live tokens'
# passes through an attention and through a conv layer (the engine's
# counters `engine_attn_lane_steps`, `engine_conv_lane_steps`).
COUNTERS = afmoe.COUNTERS + ("moe_picks_held", "attn_lane_steps",
                             "conv_lane_steps")


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    max_position_embeddings: int = 128000
    hidden_size: int = 2048
    num_layers: int = 24            # the layers HELD
    published_layers: int = 24      # config.json: num_hidden_layers
    layer_offset: int = 0           # published index of the first layer held
    layer_types: Tuple[str, ...] = PUBLISHED_TYPES   # of the layers held
    # Published layers 0 .. num_dense_layers - 1 have a dense SwiGLU.
    num_dense_layers: int = 2
    num_heads: int = 32             # num_attention_heads
    num_kv_heads: int = 8           # num_key_value_heads
    head_dim: int = 64
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32           # the router's width
    # (first, count) of the experts this process holds; None = all.
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 4
    route_norm: bool = True         # norm_topk_prob
    route_scale: float = 1.0        # routed_scaling_factor
    route_eps: float = 1e-6         # beside the chosen scores' sum
    conv_kernel: int = 3            # conv_L_cache
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5      # norm_eps
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # The engines set this for an int8 cache; this family has none.
    quant_kv: bool = False

    def __post_init__(self):
        if (len(self.layer_types) != self.num_layers
                or set(self.layer_types) - {CONV, FULL}
                or self.layer_offset + self.num_layers
                > self.published_layers):
            raise ValueError(
                f"lfm2: {len(self.layer_types)} layer_types "
                f"{sorted(set(self.layer_types))} for {self.num_layers} "
                f"layers from published layer {self.layer_offset} of "
                f"{self.published_layers}")

    @property
    def num_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    def is_attention(self, layer: int) -> bool:
        return self.layer_types[layer] == FULL

    def is_dense(self, layer: int) -> bool:
        """Whether held layer `layer` is one of the leading dense layers,
        by its PUBLISHED index."""
        return self.layer_offset + layer < self.num_dense_layers

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def index(self, layer: int) -> int:
        """Layer `layer`'s index among the held layers of its own kind:
        its place in the planes that only its kind has."""
        return self.layer_types[:layer].count(self.layer_types[layer])

    @classmethod
    def lfm2_8b_a1b(cls, **kw) -> "Lfm2MoeConfig":
        """LiquidAI/LFM2-8B-A1B as published: 24 layers (18 conv, 6
        attention; 2 dense, 22 routed), 8.3 B parameters."""
        return cls(**kw)

    @classmethod
    def lfm2_8b_a1b_13l(cls, **kw) -> "Lfm2MoeConfig":
        """A pipeline stage of 13 of the 24 layers, whole on one chip: the
        published layers 1 to 13 (layer 1 conv with the dense SwiGLU, then
        three whole periods: attention at 2, 6, 10, conv at 3-5, 7-9,
        11-13), all 32 experts, every width, the whole vocabulary."""
        return cls(num_layers=13, layer_offset=1,
                   layer_types=PUBLISHED_TYPES[1:14], **kw)

    @classmethod
    def tiny(cls, **kw) -> "Lfm2MoeConfig":
        """Test size: the cut's shape (published layers 1 to 13) at widths
        a CPU test can afford."""
        kw.setdefault("vocab_size", 384)
        kw.setdefault("max_position_embeddings", 64)
        return cls(
            hidden_size=32, num_layers=13, layer_offset=1,
            layer_types=PUBLISHED_TYPES[1:14], num_heads=4, num_kv_heads=2,
            head_dim=8, intermediate_size=64, moe_intermediate_size=16,
            num_experts=8, num_experts_per_tok=2, **kw,
        )


TILE = 512


def fold_kv(x: jax.Array) -> jax.Array:
    """[B, Hkv, T, Dh] -> [B, T, Hkv * Dh]: a position's key heads side by
    side in one row (module docstring)."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def attend_folded(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
                  kv_heads: int) -> jax.Array:
    """Grouped-query attention over folded rows: q [B, H, T, Dh], k / v
    [B, S, Hkv * Dh], mask [B, 1, T, S] -> [B, H, T, Dh]; float32 scores
    and softmax, scale Dh ** -0.5."""
    b, h, t, dh = q.shape
    groups = h // kv_heads
    eye = jnp.eye(kv_heads, dtype=q.dtype)
    # Query head g of key head i in lanes [i * Dh, (i + 1) * Dh) of a
    # row-wide query, zeros beside.
    wide = jnp.einsum("bigtd,ij->bigtjd",
                      q.reshape(b, kv_heads, groups, t, dh), eye)
    wide = wide.reshape(b, h * t, kv_heads * dh)
    scores = jnp.einsum("bqd,bkd->bqk", wide, k,
                        preferred_element_type=jnp.float32) * dh ** -0.5
    scores = jnp.where(jnp.tile(mask[:, 0], (1, h, 1)), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bqk,bkd->bqd", probs, v)
    out = jnp.einsum("bigtjd,ij->bigtd",
                     out.reshape(b, kv_heads, groups, t, kv_heads, dh), eye)
    return out.reshape(b, h, t, dh)


def pad_experts(wg: jax.Array, wu: jax.Array, wd: jax.Array):
    """Routed experts' stacks as published, wg / wu [E, D, M] and wd
    [E, M, D], with M padded with zeros to whole tiles of the grouped
    product (1,792 -> 2,048; module docstring). A width within one tile
    (the test sizes) stays."""
    m = wg.shape[2]
    more = (-m % TILE) if m > TILE else 0
    if not more:
        return wg, wu, wd
    cols = [(0, 0), (0, 0), (0, more)]
    return (jnp.pad(wg, cols), jnp.pad(wu, cols),
            jnp.pad(wd, [(0, 0), (0, more), (0, 0)]))


def init_params(rng: jax.Array, cfg: Lfm2MoeConfig) -> Params:
    """Seeded weights, each leaf drawn in the parameter dtype."""
    d, dh, pd = cfg.hidden_size, cfg.head_dim, cfg.param_dtype
    qd, kvd = cfg.num_heads * dh, cfg.num_kv_heads * dh
    e, m = cfg.num_experts_held, cfg.moe_intermediate_size
    std = 0.02

    def norm(key, *shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(pd)

    def ones(*shape):
        return jnp.ones(shape, pd)

    def layer(key, i):
        ks = jax.random.split(key, 8)
        lp = {"ln1": {"scale": ones(d)}, "ln2": {"scale": ones(d)}}
        if cfg.is_attention(i):
            lp["attn"] = {
                "wq": norm(ks[0], d, qd), "wk": norm(ks[1], d, kvd),
                "wv": norm(ks[2], d, kvd), "wo": norm(ks[3], qd, d),
                "qn": {"scale": ones(dh)}, "kn": {"scale": ones(dh)}}
        else:
            lp["conv"] = {
                "w_in": norm(ks[0], d, 3 * d),
                "conv_w": (cfg.conv_kernel ** -0.5 * jax.random.normal(
                    ks[1], (cfg.conv_kernel, d), jnp.float32)).astype(pd),
                "w_out": norm(ks[2], d, d)}
        if cfg.is_dense(i):
            f = cfg.intermediate_size
            lp["mlp"] = {"wg": norm(ks[4], d, f), "wu": norm(ks[5], d, f),
                         "wd": norm(ks[6], f, d)}
        else:
            kr, kb, kg, ku, kd = jax.random.split(ks[7], 5)
            wg, wu, wd = pad_experts(norm(kg, e, d, m), norm(ku, e, d, m),
                                     norm(kd, e, m, d))
            lp["moe"] = {
                # Router columns an order above the other matrices, so a
                # token's experts differ by more than a rounding; a bias
                # that is not zero, so the choice and the weights differ.
                "wr": (10 * std * jax.random.normal(
                    kr, (d, cfg.num_experts), jnp.float32)).astype(pd),
                "br": 0.05 * jax.random.normal(
                    kb, (cfg.num_experts,), jnp.float32),
                "wg": wg, "wu": wu, "wd": wd}
        return lp

    keys = jax.random.split(rng, cfg.num_layers + 1)
    return {
        "embed": norm(keys[0], cfg.vocab_size, d),
        "layers": [layer(keys[1 + i], i) for i in range(cfg.num_layers)],
        "lnf": {"scale": ones(d)},
    }


def init_cache(cfg: Lfm2MoeConfig, batch: int, max_len: int,
               dtype=None, groups=None) -> KVCache:
    """Keys and values of the attention layers alone, a position's key
    heads in one row (module docstring), and beside them the conv layers' windows,
    `conv` [Lc, B, K-1, D] in the cache's dtype; no `ssm` plane
    (`models/common.py` `KVCache`). `groups` (models/registry.py) is
    ignored: the engines refuse tp > 1 for this family."""
    if cfg.quant_kv:
        raise ValueError("lfm2 serves the published bfloat16 cache: "
                         "kv_quant is not supported")
    dtype = dtype or cfg.dtype
    cache = KVCache.create(cfg.count(FULL), batch, 1, max_len,
                           cfg.num_kv_heads * cfg.head_dim, dtype)
    return cache._replace(conv=jnp.zeros(
        (cfg.count(CONV), batch, cfg.conv_kernel - 1, cfg.hidden_size),
        dtype))


def short_conv(x: jax.Array, cp: Params, live: jax.Array,
               conv: Optional[jax.Array] = None, layer: int = 0,
               rows: Optional[jax.Array] = None):
    """One conv operator over x [B, T, D] -> (out [B, T, D], the window
    plane). `conv` is the cache's stacked plane [Lc, R, K-1, D] and
    `layer` this operator's index in it; batch element i owns row i, or
    row `rows[i]`. None: every sequence starts from a zero window and
    nothing is kept. `live` [B, T] bool. T = 1 over every row of the plane
    is the step form (the kernel), all else the chunk form."""
    b, t, d = x.shape
    with jax.named_scope("conv.in_proj"):
        bcz = dense(x, cp["w_in"])
    with jax.named_scope("conv.taps"):
        if conv is not None and t == 1 and rows is None:
            conv, y = jax.lax.platform_dependent(
                conv, bcz[:, 0], live[:, 0], cp["conv_w"],
                tpu=lambda p, *ops: shortconv.shortconv_step(p, layer, *ops),
                default=lambda p, *ops: shortconv.shortconv_step_reference(
                    p, layer, *ops))
            y = y[:, None]
        else:
            gate_in, gate_out, z = (bcz[..., i * d:(i + 1) * d]
                                    for i in range(3))
            window = (jnp.zeros((b, cp["conv_w"].shape[0] - 1, d), x.dtype)
                      if conv is None else layer_rows(conv, layer, rows))
            c, window = causal_conv(gate_in * z, window, cp, live, act=None)
            y = (gate_out.astype(jnp.float32) * c).astype(x.dtype)
            if conv is not None:
                conv = conv.at[layer if rows is None
                               else (layer, rows)].set(window)
    with jax.named_scope("conv.out_proj"):
        return dense(y, cp["w_out"]), conv


def moe_mlp(h: jax.Array, mp: Params, cfg, live: jax.Array):
    """A routed layer's feed-forward, [B, T, D] -> ([B, T, D], chosen
    experts [B, T, k], group sizes of the experts held): `afmoe.moe_mlp`
    with the family's epsilon in the weights and no shared expert."""
    b, t, d = h.shape
    x = h.reshape(b * t, d)
    held = cfg.experts_held
    with jax.named_scope("moe.route"):
        top_i, top_w = route_sigmoid(
            x, mp["wr"], mp.get("br"), cfg.num_experts_per_tok,
            cfg.route_norm, cfg.route_scale, cfg.route_eps)
    with jax.named_scope("moe.experts"):
        y, sizes = grouped_swiglu(
            x, top_i, top_w, live.reshape(b * t), mp["wg"], mp["wu"],
            mp["wd"], first=held[0] if held else None,
            among=cfg.num_experts)
    return y.reshape(b, t, d), top_i.reshape(b, t, -1), sizes


def forward(
    params: Params,
    cfg: Lfm2MoeConfig,
    input_ids: jax.Array,
    cache: Optional[KVCache] = None,
    positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    live: Optional[jax.Array] = None,
    aux: bool = False,
    rows: Optional[jax.Array] = None,
):
    """Run the decoder; returns (logits [B, T, V] float32, updated cache),
    and with `aux` a third value, {"counts": int32 [6] (`COUNTERS`),
    "routing": int32 [Le, B, T, k], "attn_in": [La, B, T, D], what the
    attention layers' projections were given, "moe_in" and "moe_out":
    [Le, B, T, D], what the routed layers' experts were given and what
    they gave}. Contract in the module docstring."""
    b, t = input_ids.shape
    eps = cfg.rms_norm_eps
    nh, nkv = cfg.num_heads, cfg.num_kv_heads

    given = live is not None
    offset, q_slots, positions, live = batch_slots(input_ids, cache,
                                                   positions, live, rows)
    num_keys = t if cache is None else cache.k.shape[3]
    mask = causal_window_mask(q_slots, num_keys)
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, None, :]
        if not given:
            # A token whose own key slot is masked is padding.
            live = jnp.take_along_axis(
                kv_mask, jnp.minimum(q_slots, kv_mask.shape[1] - 1), axis=1)

    ck = cv = conv = None
    if cache is not None:
        # The planes without their unit head axis.
        ck, cv, conv = cache.k[:, :, 0], cache.v[:, :, 0], cache.conv
    zero = jnp.zeros((), jnp.int32)
    at_rows = (jnp.arange(b) if rows is None else rows)[:, None]

    def attention(h, ap, layer):
        nonlocal ck, cv
        q = split_heads(dense(h, ap["wq"]), nh)
        k = split_heads(dense(h, ap["wk"]), nkv)
        v = split_heads(dense(h, ap["wv"]), nkv)
        # The per-head norm BEFORE the rotation.
        q = rope(rms_norm(q, ap["qn"]["scale"], eps), positions,
                 cfg.rope_theta)
        k = rope(rms_norm(k, ap["kn"]["scale"], eps), positions,
                 cfg.rope_theta)
        k, v = fold_kv(k), fold_kv(v)
        if cache is not None:
            k_w, v_w = k.astype(ck.dtype), v.astype(cv.dtype)
            if offset.ndim == 1:
                # Ragged slots: each row's T tokens at its own offset;
                # out-of-range tails are dropped, never clamped.
                ck = ck.at[layer, at_rows, q_slots, :].set(k_w)
                cv = cv.at[layer, at_rows, q_slots, :].set(v_w)
            else:
                start = (layer, zero, offset, zero)
                ck = jax.lax.dynamic_update_slice(ck, k_w[None], start)
                cv = jax.lax.dynamic_update_slice(cv, v_w[None], start)
            k = layer_rows(ck, layer, rows).astype(q.dtype)
            v = layer_rows(cv, layer, rows).astype(q.dtype)
        return dense(merge_heads(attend_folded(q, k, v, mask, nkv)),
                     ap["wo"])

    x = quant.embed_lookup(params["embed"], input_ids).astype(cfg.dtype)
    routing, attn_in, moe_in, moe_out = [], [], [], []
    # What `aux` hands out is what the products consumed, to the bit: left
    # to itself the TPU's compiler makes the handed copy in a fusion of its
    # own, from the residual's two addends in float32 (excess precision),
    # a rounding or two from the products' operand.
    handed = jax.lax.optimization_barrier if aux else (lambda h: h)
    counts = jnp.zeros((len(COUNTERS),), jnp.int32)
    n_live = jnp.sum(live).astype(jnp.int32)
    for layer, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["ln1"]["scale"], eps)
        if cfg.is_attention(layer):
            h = handed(h)
            attn_in.append(h)
            with jax.named_scope("attn.full"):
                y = attention(h, lp["attn"], cfg.index(layer))
        else:
            y, conv = short_conv(h, lp["conv"], live, conv,
                                 cfg.index(layer), rows)
        x = x + y
        h = rms_norm(x, lp["ln2"]["scale"], eps)
        if "moe" in lp:
            h = handed(h)
            y, top_i, sizes = moe_mlp(h, lp["moe"], cfg, live)
            y = handed(y)
            routing.append(top_i)
            moe_in.append(h)
            moe_out.append(y)
            held = jnp.sum(sizes)
            counts = counts.at[:4].add(jnp.stack([
                n_live * top_i.shape[-1],
                jnp.sum(sizes > 0).astype(jnp.int32),
                jnp.asarray(sizes.shape[0], jnp.int32), held]))
        else:
            with jax.named_scope("mlp.dense"):
                y = swiglu(h, lp["mlp"])
        x = x + y
    counts = counts.at[4:].add(
        n_live * jnp.asarray([cfg.count(FULL), cfg.count(CONV)], jnp.int32))
    new_cache = None
    if cache is not None:
        new_cache = cache._replace(k=ck[:, :, None], v=cv[:, :, None],
                                   length=cache.length + t, conv=conv)
    # The head is the embedding (`tie_word_embeddings`).
    logits, *rest = afmoe.head({**params, "lm_head": params["embed"]}, cfg,
                               x, counts, routing, aux)
    if aux:
        rest[0].update(attn_in=jnp.stack(attn_in), moe_in=jnp.stack(moe_in),
                       moe_out=jnp.stack(moe_out))
    return (logits, new_cache, *rest)


def params_from_hf(sd, cfg: Lfm2MoeConfig) -> Params:
    """The published checkpoint's names into this tree, the held layers by
    their published index: `model.layers.<i>.operator_norm`, `ffn_norm`,
    `conv.{in_proj,conv,out_proj}`, `self_attn.{q,k,v,out}_proj`,
    `self_attn.{q,k}_layernorm`, `feed_forward.{w1,w3,w2}` (gate, up,
    down) or `feed_forward.gate`, `feed_forward.expert_bias`,
    `feed_forward.experts.<e>.{w1,w3,w2}`; `model.embed_tokens`,
    `model.embedding_norm`; the head is the embedding. Linears are stored
    [out, in] there and [in, out] here, the convolution [C, 1, K] there and
    [K, C] here; of the experts only `experts_held` are read."""
    pd = cfg.param_dtype

    def lin(name):
        return jnp.asarray(sd[name + ".weight"], pd).T

    def vec(name):
        return {"scale": jnp.asarray(sd[name + ".weight"], pd)}

    def mlp(prefix):
        return {"wg": lin(prefix + ".w1"), "wu": lin(prefix + ".w3"),
                "wd": lin(prefix + ".w2")}

    first, count = cfg.experts_held or (0, cfg.num_experts)
    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{cfg.layer_offset + i}"
        lp = {"ln1": vec(p + ".operator_norm"), "ln2": vec(p + ".ffn_norm")}
        if cfg.is_attention(i):
            a = p + ".self_attn"
            lp["attn"] = {"wq": lin(a + ".q_proj"), "wk": lin(a + ".k_proj"),
                          "wv": lin(a + ".v_proj"), "wo": lin(a + ".out_proj"),
                          "qn": vec(a + ".q_layernorm"),
                          "kn": vec(a + ".k_layernorm")}
        else:
            c = p + ".conv"
            lp["conv"] = {
                "w_in": lin(c + ".in_proj"),
                "conv_w": jnp.asarray(sd[c + ".conv.weight"], pd)[:, 0].T,
                "w_out": lin(c + ".out_proj")}
        f = p + ".feed_forward"
        if cfg.is_dense(i):
            lp["mlp"] = mlp(f)
        else:
            experts = [mlp(f"{f}.experts.{e}")
                       for e in range(first, first + count)]
            wg, wu, wd = pad_experts(*(jnp.stack([x[k] for x in experts])
                                       for k in ("wg", "wu", "wd")))
            lp["moe"] = {"wr": lin(f + ".gate"),
                         "br": jnp.asarray(sd[f + ".expert_bias"],
                                           jnp.float32),
                         "wg": wg, "wu": wu, "wd": wd}
        layers.append(lp)
    return {
        "embed": jnp.asarray(sd["model.embed_tokens.weight"], pd),
        "layers": layers,
        "lnf": vec("model.embedding_norm"),
    }
