"""NVIDIA's `nemotron_h` decoder (Nemotron-3-Nano and its family) in
pure-functional JAX: a hybrid whose every block is ONE mixer behind ONE
norm, `h += mixer(RMSNorm(h))`, the mixer chosen by the block's letter in
`hybrid_override_pattern`:

- `M`, a Mamba-2 mixer (`models/mamba2.py`): a recurrent state per
  sequence, `KVCache.ssm` and `KVCache.conv`, and no keys or values;
- `E`, routed experts: DeepSeek-V3's router (`moe.route_sigmoid`: sigmoid
  scores in float32, the choice by score + `e_score_correction_bias`, the
  weights the chosen scores over their sum times `routed_scaling_factor`;
  `n_group` 1 and `topk_group` 1 are no group limit), experts that are NOT
  SwiGLU, `W_down relu(W_up x)^2` (`moe.grouped_relu2`), one shared expert
  of the same form added to every token, no capacity and no drops. As in
  `models/axk1.py` a process may hold a SHARE of a layer's experts
  (`experts_held`): it routes over all of them and computes its own part;
- `*`, attention: grouped keys and values, no bias, causal, and NO position
  signal (the published modelling code applies no rotary embedding; the
  Mamba layers carry the order). `KVCache.k` / `.v` hold the attention
  layers alone, [La, B, Hkv, T, Dh].

Final RMSNorm, untied head, no biases but the convolution's. Same family
surface and forward contract as the other families (`models/registry.py`):
`positions` are accepted and unused; cache slots written at `cache.length`,
scalar or per-row ragged; `kv_mask` marks valid key slots; `rows` names the
cache rows a ragged batch addresses; T = 1, a chunk or a bucket. `live`
[B] or [B, T] says which tokens are real, and here it does more than keep
them from the experts: **a token that is not live leaves its row's
recurrent state as it was**, bit for bit (`models/mamba2.py`). Where `live`
is not given it is read off `kv_mask` (a token whose own key slot is masked
is padding), else every token is live.

**The experts' stacks are padded to whole tiles.** `wu` is held
[E, Dp, Mp] and `wd` [E, Mp, Dp], D and M each rounded up to a multiple of
512 (2,688 -> 3,072, 1,856 -> 2,048), the added rows and columns zero, and
the layer pads its input's columns with zeros and cuts its output back to
D: the result is the published expert's to the bit (`relu(0)^2 = 0`, and a
zero row or column adds nothing). Why, both from the chip (PERF.md section
6, PR 40): the TPU's grouped product tiles its operands by the largest
power of two that divides a dimension, and 2,688 = 21 x 128 and 1,920 =
15 x 128 leave it 128: an up and a down product over 36 reached experts
took 6.30 ms at [2688, 1920], 14% of what their bytes need, against 1.30
ms at [3072, 2048] (85% of the padded bytes' floor, 67% of the published
bytes'), 1.63 at [2816, 2048] and 2.41 at [2688, 2048]; at the published
shapes the routed layers were 92% of the cell's device time. And an array's
layout at rest is the runtime's choice, which puts a lane-multiple axis
minor-most (section 6, PR 35): of [64, 2688, 1856] that is D, while the
grouped product takes its stacks M-minor, and the compiled megastep copied
all four layers' `wu`, 638 MB each, at the head of every dispatch (the
compiler's own text for a described v5e). The padding costs 26% more bytes
an expert read and 1.33 GB of HBM. The product's ROWS are tiled the same
way, and `moe.tiled_rows` hands it a pass's sorted picks in tiles of 32 (a
decode row's 96 as they are, a pass's 192 and 768 as 224 and 800: PERF.md
section 6, PR 51). `pad_experts` is the one place that knows.

The trunk is a list of per-layer trees, unrolled, as afmoe's (whose
`batch_slots` and `head` it uses): the grouped expert product takes whole
stacks, and a block differs from its neighbour in kind.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import afmoe, mamba2, quant
from .afmoe import batch_slots, head
from .common import (
    KVCache,
    attend,
    causal_window_mask,
    dense,
    layer_rows,
    merge_heads,
    rms_norm,
    split_heads,
)
from .moe import grouped_relu2, route_sigmoid

Params = Dict[str, Any]

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"

# afmoe's three counts and, a chip holding a share of a layer's experts,
# the picks that landed on the share (as `axk1.COUNTERS`).
COUNTERS = afmoe.COUNTERS + ("moe_picks_held",)

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    max_position_embeddings: int = 262144
    hidden_size: int = 2688
    pattern: str = PUBLISHED_PATTERN   # hybrid_override_pattern
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    num_heads: int = 32                # num_attention_heads
    num_kv_heads: int = 2              # num_key_value_heads
    head_dim: int = 128
    moe_intermediate_size: int = 1856
    shared_intermediate_size: int = 3712  # moe_shared_expert_intermediate_size
    num_experts: int = 128             # n_routed_experts: the router's width
    # (first, count) of the experts this process holds; None = all.
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 6
    route_norm: bool = True            # norm_topk_prob
    route_scale: float = 2.5           # routed_scaling_factor
    rms_norm_eps: float = 1e-5         # layer_norm_epsilon
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # The engines set this for an int8 cache; this family has none
    # (`init_cache` refuses).
    quant_kv: bool = False

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def num_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    def index(self, layer: int) -> int:
        """Block `layer`'s index among the blocks of its own kind: its
        place in the planes that only its kind has."""
        return self.pattern[:layer].count(self.pattern[layer])

    @classmethod
    def nemotron3_nano(cls, **kw) -> "NemotronHConfig":
        """nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 as published: 52
        blocks (23 M, 23 E, 6 *), 31.6 B parameters."""
        return cls(**kw)

    @classmethod
    def nemotron3_nano_9l_share(cls, **kw) -> "NemotronHConfig":
        """One chip's part of a deployment in which 2 chips share each
        layer, every width as published: the published blocks 0 to 8
        (`MEMEM*EME`), experts 0 to 63 of each layer's 128, half the
        vocabulary."""
        return cls(pattern=PUBLISHED_PATTERN[:9], experts_held=(0, 64),
                   vocab_size=65536, **kw)

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        """Test size: the cut's shape (`MEMEM*EME`, a share of the
        experts) at widths a CPU test can afford."""
        kw.setdefault("vocab_size", 384)
        kw.setdefault("max_position_embeddings", 64)
        kw.setdefault("experts_held", (0, 8))
        return cls(
            hidden_size=32, pattern=PUBLISHED_PATTERN[:9], mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, num_heads=4,
            num_kv_heads=2, head_dim=8, moe_intermediate_size=16,
            shared_intermediate_size=32, num_experts=16,
            num_experts_per_tok=3, **kw,
        )


LANES, TILE = 128, 512


def _whole(n: int) -> int:
    """`n` rounded up to whole tiles of the grouped product (whole lanes
    where a tile would more than double it: the test sizes)."""
    unit = TILE if n > TILE else LANES
    return -(-n // unit) * unit


def pad_experts(wu: jax.Array, wd: jax.Array):
    """Routed experts' stacks as published, wu [E, D, M] and wd [E, M, D],
    with D and M padded with zeros to whole tiles (module docstring)."""
    _, d, m = wu.shape
    dp, mp = _whole(d) - d, _whole(m) - m
    return (jnp.pad(wu, [(0, 0), (0, dp), (0, mp)]),
            jnp.pad(wd, [(0, 0), (0, mp), (0, dp)]))


def init_params(rng: jax.Array, cfg: NemotronHConfig) -> Params:
    """Seeded weights, each leaf drawn in the parameter dtype."""
    d, dh, pd = cfg.hidden_size, cfg.head_dim, cfg.param_dtype
    qd, kvd = cfg.num_heads * dh, cfg.num_kv_heads * dh
    e, m = cfg.num_experts_held, cfg.moe_intermediate_size
    std = 0.02

    def norm(key, *shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(pd)

    def ones(*shape):
        return jnp.ones(shape, pd)

    def layer(key, kind):
        ks = jax.random.split(key, 6)
        lp = {"ln": {"scale": ones(d)}}
        if kind == MAMBA:
            lp["mamba"] = mamba2.init_params(ks[0], cfg, norm, ones)
        elif kind == ATTENTION:
            lp["attn"] = {"wq": norm(ks[0], d, qd), "wk": norm(ks[1], d, kvd),
                          "wv": norm(ks[2], d, kvd), "wo": norm(ks[3], qd, d)}
        else:
            wu, wd = pad_experts(norm(ks[1], e, d, m), norm(ks[2], e, m, d))
            lp["moe"] = {
                # Router columns an order above the other matrices, so a
                # token's experts differ by more than a rounding.
                "wr": (10 * std * jax.random.normal(
                    ks[0], (d, cfg.num_experts), jnp.float32)).astype(pd),
                "br": jnp.zeros((cfg.num_experts,), jnp.float32),
                "wu": wu, "wd": wd,
                "shared": {
                    "wu": norm(ks[3], d, cfg.shared_intermediate_size),
                    "wd": norm(ks[4], cfg.shared_intermediate_size, d)},
            }
        return lp

    keys = jax.random.split(rng, cfg.num_layers + 2)
    return {
        "embed": norm(keys[0], cfg.vocab_size, d),
        "layers": [layer(keys[2 + i], kind)
                   for i, kind in enumerate(cfg.pattern)],
        "lnf": {"scale": ones(d)},
        "lm_head": norm(keys[1], cfg.vocab_size, d),
    }


def init_cache(cfg: NemotronHConfig, batch: int, max_len: int,
               dtype=None, groups=None) -> KVCache:
    """Keys and values of the attention blocks alone, and the Mamba
    blocks' state planes beside them (`models/common.py` `KVCache`).
    `groups` (models/registry.py): bfloat16 planes of 128-wide heads tile
    unpadded as they are."""
    if cfg.quant_kv:
        raise ValueError("nemotron_h serves the published bfloat16 cache "
                         "and a float32 state: kv_quant is not supported")
    dtype = dtype or cfg.dtype
    cache = KVCache.create(cfg.count(ATTENTION), batch, cfg.num_kv_heads,
                           max_len, cfg.head_dim, dtype)
    ssm, conv = mamba2.init_state(cfg, cfg.count(MAMBA), batch, dtype)
    return cache._replace(ssm=ssm, conv=conv)


def relu2(x: jax.Array, mp: Params) -> jax.Array:
    return dense(jnp.square(jax.nn.relu(dense(x, mp["wu"]))), mp["wd"])


def moe_mlp(h: jax.Array, mp: Params, cfg, live: jax.Array):
    """An `E` block's mixer, [B, T, D] -> ([B, T, D], chosen experts
    [B, T, k], group sizes of the experts held): `afmoe.moe_mlp` with
    relu^2 experts of two projections."""
    b, t, d = h.shape
    x = h.reshape(b * t, d)
    held = cfg.experts_held
    with jax.named_scope("moe.route"):
        top_i, top_w = route_sigmoid(
            x, mp["wr"], mp.get("br"), cfg.num_experts_per_tok,
            cfg.route_norm, cfg.route_scale)
    with jax.named_scope("moe.experts"):
        # The stacks' padded rows meet zero columns (`pad_experts`).
        wide = jnp.pad(x, [(0, 0), (0, mp["wu"].shape[1] - d)])
        y, sizes = grouped_relu2(wide, top_i, top_w, live.reshape(b * t),
                                 mp["wu"], mp["wd"],
                                 first=held[0] if held else None,
                                 among=cfg.num_experts)
        y = y[:, :d]
    with jax.named_scope("moe.shared"):
        y = y + relu2(x, mp["shared"])
    return y.reshape(b, t, d), top_i.reshape(b, t, -1), sizes


def forward(
    params: Params,
    cfg: NemotronHConfig,
    input_ids: jax.Array,
    cache: Optional[KVCache] = None,
    positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    live: Optional[jax.Array] = None,
    aux: bool = False,
    rows: Optional[jax.Array] = None,
):
    """Run the decoder; returns (logits [B, T, V] float32, updated cache),
    and with `aux` a third value, {"counts": int32 [4] (`COUNTERS`),
    "routing": int32 [Le, B, T, k], "attn_in": [La, B, T, D], what the
    attention blocks' projections were given (a comparison reads the keys
    and values against its own float32 product of it)}. Contract in the
    module docstring."""
    b, t = input_ids.shape
    eps, dh = cfg.rms_norm_eps, cfg.head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    groups = nh // nkv

    given = live is not None
    offset, q_slots, _, live = batch_slots(input_ids, cache, positions, live,
                                           rows)
    num_keys = t if cache is None else cache.k.shape[3]
    mask = causal_window_mask(q_slots, num_keys)
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, None, :]
        if not given:
            # A token whose own key slot is masked is padding.
            live = jnp.take_along_axis(
                kv_mask, jnp.minimum(q_slots, kv_mask.shape[1] - 1), axis=1)
    # Query heads of one kv head ride the query axis (afmoe's fold).
    mask = jnp.tile(mask, (1, 1, groups, 1))

    ck = cv = planes = None
    if cache is not None:
        ck, cv, planes = cache.k, cache.v, (cache.ssm, cache.conv)
    zero = jnp.zeros((), jnp.int32)
    at_rows = (jnp.arange(b) if rows is None else rows)[:, None]

    def attention(h, ap, layer):
        nonlocal ck, cv
        q = split_heads(dense(h, ap["wq"]), nh)
        k = split_heads(dense(h, ap["wk"]), nkv)
        v = split_heads(dense(h, ap["wv"]), nkv)
        if cache is not None:
            k_w, v_w = k.astype(ck.dtype), v.astype(cv.dtype)
            if offset.ndim == 1:
                # Ragged slots: each row's T tokens at its own offset;
                # out-of-range tails are dropped, never clamped.
                ck = ck.at[layer, at_rows, :, q_slots, :].set(
                    k_w.transpose(0, 2, 1, 3))
                cv = cv.at[layer, at_rows, :, q_slots, :].set(
                    v_w.transpose(0, 2, 1, 3))
            else:
                start = (layer, zero, zero, offset, zero)
                ck = jax.lax.dynamic_update_slice(ck, k_w[None], start)
                cv = jax.lax.dynamic_update_slice(cv, v_w[None], start)
            k = layer_rows(ck, layer, rows).astype(q.dtype)
            v = layer_rows(cv, layer, rows).astype(q.dtype)
        a = attend(q.reshape(b, nkv, groups * t, dh), k, v, mask)
        return dense(merge_heads(a.reshape(b, nh, t, dh)), ap["wo"])

    x = quant.embed_lookup(params["embed"], input_ids).astype(cfg.dtype)
    routing, attn_in = [], []
    counts = jnp.zeros((len(COUNTERS),), jnp.int32)
    for layer, (kind, lp) in enumerate(zip(cfg.pattern, params["layers"])):
        h = rms_norm(x, lp["ln"]["scale"], eps)
        if kind == MAMBA:
            y, planes = mamba2.mixer(h, lp["mamba"], cfg, live, planes,
                                     cfg.index(layer), rows)
        elif kind == ATTENTION:
            attn_in.append(h)
            with jax.named_scope("attn.full"):
                y = attention(h, lp["attn"], cfg.index(layer))
        else:
            y, top_i, sizes = moe_mlp(h, lp["moe"], cfg, live)
            routing.append(top_i)
            counts = counts + jnp.stack([
                jnp.sum(live).astype(jnp.int32) * top_i.shape[-1],
                jnp.sum(sizes > 0).astype(jnp.int32),
                jnp.asarray(sizes.shape[0], jnp.int32),
                jnp.sum(sizes)])
        x = x + y
    new_cache = None
    if cache is not None:
        new_cache = cache._replace(k=ck, v=cv, length=cache.length + t,
                                   ssm=planes[0], conv=planes[1])
    logits, *rest = head(params, cfg, x, counts, routing, aux)
    if aux:
        rest[0]["attn_in"] = jnp.stack(attn_in)
    return (logits, new_cache, *rest)


def params_from_hf(sd, cfg: NemotronHConfig) -> Params:
    """The published checkpoint's names into this tree:
    `backbone.layers.<i>.norm` and `.mixer.*` (Mamba: `in_proj`, `conv1d`,
    `dt_bias`, `A_log`, `D`, `norm`, `out_proj`; attention:
    `{q,k,v,o}_proj`; experts: `gate` with `e_score_correction_bias`,
    `experts.<e>.{up,down}_proj`, `shared_experts.{up,down}_proj`),
    `backbone.embeddings`, `backbone.norm_f`, `lm_head`. Linears are stored
    [out, in] there and [in, out] here, the convolution [C, 1, K] there and
    [K, C] here; of the experts only `experts_held` are read."""
    pd = cfg.param_dtype

    def lin(name):
        return jnp.asarray(sd[name + ".weight"], pd).T

    def vec(name):
        return {"scale": jnp.asarray(sd[name + ".weight"], pd)}

    def f32(name):
        return jnp.asarray(sd[name], jnp.float32)

    first, count = cfg.experts_held or (0, cfg.num_experts)
    layers = []
    for i, kind in enumerate(cfg.pattern):
        p = f"backbone.layers.{i}"
        m = p + ".mixer"
        lp = {"ln": vec(p + ".norm")}
        if kind == MAMBA:
            lp["mamba"] = {
                "w_in": lin(m + ".in_proj"),
                "conv_w": jnp.asarray(sd[m + ".conv1d.weight"], pd)[:, 0].T,
                "conv_b": jnp.asarray(sd[m + ".conv1d.bias"], pd),
                "dt_bias": f32(m + ".dt_bias"), "a_log": f32(m + ".A_log"),
                "d": f32(m + ".D"), "norm": vec(m + ".norm"),
                "w_out": lin(m + ".out_proj")}
        elif kind == ATTENTION:
            lp["attn"] = {"wq": lin(m + ".q_proj"), "wk": lin(m + ".k_proj"),
                          "wv": lin(m + ".v_proj"), "wo": lin(m + ".o_proj")}
        else:
            experts = [(lin(f"{m}.experts.{e}.up_proj"),
                        lin(f"{m}.experts.{e}.down_proj"))
                       for e in range(first, first + count)]
            wu, wd = pad_experts(jnp.stack([u for u, _ in experts]),
                                 jnp.stack([d for _, d in experts]))
            lp["moe"] = {
                "wr": lin(m + ".gate"),
                "br": f32(m + ".gate.e_score_correction_bias"),
                "wu": wu, "wd": wd,
                "shared": {"wu": lin(m + ".shared_experts.up_proj"),
                           "wd": lin(m + ".shared_experts.down_proj")}}
        layers.append(lp)
    return {
        "embed": jnp.asarray(sd["backbone.embeddings.weight"], pd),
        "layers": layers,
        "lnf": vec("backbone.norm_f"),
        "lm_head": jnp.asarray(sd["lm_head.weight"], pd),
    }
