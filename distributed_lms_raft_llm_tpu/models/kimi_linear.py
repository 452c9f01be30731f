"""Moonshot's `kimi_linear` decoder (Kimi-Linear-48B-A3B) in pure-functional
JAX: a hybrid of two mixers under one trunk, `h += mixer(N1(h))`,
`h += mlp(N2(h))`, the mixer chosen by the layer's number in
`linear_attn_config`:

- `kda_layers`: Kimi Delta Attention (`models/kda.py`), a gated delta rule
  with a decay per channel: a MATRIX state a head and sequence,
  `KVCache.ssm` [Lk, B, H, K, V] float32, and a convolution window over
  three projections, `KVCache.conv` [Lk, B, 3, 3 H K]; no keys or values;
- `full_attn_layers`: latent attention (`models/mla.py`) with ONE query
  projection (`q_lora_rank` null) and NO rotation of either 64-wide part
  (`mla_use_nope` true, `rope_scaling` null: the KDA layers carry the
  order), scale `(128 + 64) ** -0.5`. `KVCache.k` [La, B, 1, T, 576] holds
  the latent of these layers alone and there is no `v` plane.

The MLP is dense SwiGLU in the first `num_dense_layers` layers and after
them DeepSeek-V3's routed experts, whose keys the configuration's are:
sigmoid scores in float32, the choice by score + `e_score_correction_bias`
(one group), the weights the chosen scores over their sum times
`routed_scaling_factor`, SwiGLU experts, one shared expert on every token,
no capacity and no drops (`afmoe.moe_mlp`). As in `models/axk1.py` a process
may hold a SHARE of a layer's experts (`experts_held`): it routes over all
of them and computes its own part.

**The experts' stacks are padded to whole tiles.** `wg` and `wu` are held
[E, Dp, M] and `wd` [E, M, Dp], D = 2,304 = 9 x 256 rounded up to 2,560,
whole tiles of 512 (`nemotron_h.pad_experts`, which found the TPU's grouped
product tiling an operand by the largest power of two that divides a
dimension), the added rows and columns zero; `afmoe.moe_mlp` pads its
input's columns with zeros and cuts its output back to D, so the result is
the published expert's to the bit. Probed alone on the chip before it was
built (PERF.md section 6, PR 44): the three grouped products over 26 reached
experts of 64 and 128 rows took 0.835 ms at [2304, 1024], 54% of what their
bytes need, and 0.708 ms at [2560, 1024] (63% of the published bytes' time,
70% of the padded bytes'). The padding costs 11% more bytes an expert read
and 0.81 GB of HBM. `pad_experts` is the one place that knows. The rows are
tiled too, by the same rule: `moe.tiled_rows` hands the products a pass's
sorted picks in tiles of 32 (the decode row's prefix of 96 as it is, its
fallback's 128 as 160, the wide pass's 432 as 480).

Final RMSNorm, untied head, no biases. The trunk is afmoe's
(`afmoe.run_layers`, `afmoe.head`): a list of per-layer trees, unrolled.
Same family surface and forward contract as the other families
(`models/registry.py`): `positions` are accepted and unused; cache slots
written at `cache.length`, scalar or per-row ragged; `kv_mask` marks valid
key slots; `rows` names the cache rows a ragged batch addresses; T = 1, a
chunk or a bucket. `live` [B] or [B, T] says which tokens are real, and as
in `models/nemotron_h.py` **a token that is not live leaves its row's
recurrent state as it was**, bit for bit. Where `live` is not given it is
read off `kv_mask` (a token whose own key slot is masked is padding), else
every token is live.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import afmoe, kda, mla, nemotron_h, quant
from .afmoe import batch_slots, head, run_layers
from .common import KVCache, causal_window_mask

Params = Dict[str, Any]

# afmoe's three counts and a share's three (`afmoe.SHARE_COUNTERS`).
COUNTERS = afmoe.SHARE_COUNTERS

# `linear_attn_config` as published, layers numbered from 1.
PUBLISHED_FULL = (4, 8, 12, 16, 20, 24, 27)
PUBLISHED_KDA = tuple(i for i in range(1, 28) if i not in PUBLISHED_FULL)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    max_position_embeddings: int = 1048576  # model_max_length
    hidden_size: int = 2304
    num_layers: int = 27            # config.json: num_hidden_layers
    num_dense_layers: int = 1       # first_k_dense_replace
    # linear_attn_config: the layers of each mixer, numbered from 1.
    kda_layers: Tuple[int, ...] = PUBLISHED_KDA
    full_attn_layers: Tuple[int, ...] = PUBLISHED_FULL
    kda_num_heads: int = 32         # linear_attn_config.num_heads
    kda_head_dim: int = 128         # linear_attn_config.head_dim
    kda_conv_kernel: int = 4        # linear_attn_config.short_conv_kernel_size
    num_heads: int = 32             # num_attention_heads
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    rope_theta: float = 10000.0
    rope_scaling: Optional[mla.Yarn] = None
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_experts: int = 256          # the router's width
    # (first, count) of the experts this process holds; None = all.
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 8    # num_experts_per_token
    num_shared_experts: int = 1
    route_norm: bool = True         # moe_renormalize
    route_scale: float = 2.446      # routed_scaling_factor
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # The engines set this for an int8 cache; this family has none
    # (`init_cache` refuses).
    quant_kv: bool = False

    @property
    def num_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    def is_kda(self, layer: int) -> bool:
        """Whether layer `layer` (from 0) mixes by KDA."""
        return layer + 1 in self.kda_layers

    def index(self, layer: int) -> int:
        """Layer `layer`'s index among the layers of its own mixer: its
        place in the planes that only its kind has."""
        own = self.kda_layers if self.is_kda(layer) else self.full_attn_layers
        return own.index(layer + 1)

    @classmethod
    def kimi_linear(cls, **kw) -> "KimiLinearConfig":
        """moonshotai/Kimi-Linear-48B-A3B-Instruct as published: 27 layers
        (20 KDA, 7 MLA), 48 B parameters."""
        return cls(**kw)

    @classmethod
    def kimi_linear_9l_share(cls, **kw) -> "KimiLinearConfig":
        """One chip's part of a deployment in which 4 chips share each
        layer, every width as published: the published layers 1 to 9 (the
        dense layer and two periods of three KDA to one MLA), experts 0 to
        63 of each layer's 256, a quarter of the vocabulary."""
        return cls(num_layers=9, kda_layers=(1, 2, 3, 5, 6, 7, 9),
                   full_attn_layers=(4, 8), experts_held=(0, 64),
                   vocab_size=40960, **kw)

    @classmethod
    def tiny(cls, **kw) -> "KimiLinearConfig":
        """Test size: the cut's shape (nine layers, a share of the
        experts) at widths a CPU test can afford."""
        kw.setdefault("vocab_size", 384)
        kw.setdefault("max_position_embeddings", 64)
        kw.setdefault("experts_held", (0, 8))
        return cls(
            hidden_size=32, num_layers=9, kda_layers=(1, 2, 3, 5, 6, 7, 9),
            full_attn_layers=(4, 8), kda_num_heads=4, kda_head_dim=8,
            num_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, intermediate_size=64,
            moe_intermediate_size=16, num_experts=32, num_experts_per_tok=4,
            **kw,
        )


def pad_experts(wg: jax.Array, wu: jax.Array, wd: jax.Array):
    """Routed SwiGLU experts' stacks as published, wg, wu [E, D, M] and wd
    [E, M, D], with D and M padded with zeros to whole tiles (module
    docstring; `nemotron_h.pad_experts` for three stacks)."""
    _, d, m = wg.shape
    dp, mp = nemotron_h._whole(d) - d, nemotron_h._whole(m) - m
    up = [(0, 0), (0, dp), (0, mp)]
    return (jnp.pad(wg, up), jnp.pad(wu, up),
            jnp.pad(wd, [(0, 0), (0, mp), (0, dp)]))


def init_params(rng: jax.Array, cfg: KimiLinearConfig) -> Params:
    """Seeded weights, each leaf drawn in the parameter dtype."""
    d, pd = cfg.hidden_size, cfg.param_dtype
    e, m = cfg.num_experts_held, cfg.moe_intermediate_size
    std = 0.02

    def norm(key, *shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(pd)

    def ones(*shape):
        return jnp.ones(shape, pd)

    def mlp(key, width):
        kg, ku, kd = jax.random.split(key, 3)
        return {"wg": norm(kg, d, width), "wu": norm(ku, d, width),
                "wd": norm(kd, width, d)}

    def layer(key, i):
        ks = jax.random.split(key, 12)
        lp = {"ln1": {"scale": ones(d)}, "ln2": {"scale": ones(d)},
              "attn": (kda.init_params(ks[0], cfg, norm, ones)
                       if cfg.is_kda(i)
                       else mla.init_params(ks[:6], cfg, norm, ones))}
        if i < cfg.num_dense_layers:
            lp["mlp"] = mlp(ks[6], cfg.intermediate_size)
        else:
            wg, wu, wd = pad_experts(norm(ks[8], e, d, m),
                                     norm(ks[9], e, d, m),
                                     norm(ks[10], e, m, d))
            lp["moe"] = {
                # Router columns an order above the other matrices, so a
                # token's experts differ by more than a rounding.
                "wr": (10 * std * jax.random.normal(
                    ks[7], (d, cfg.num_experts), jnp.float32)).astype(pd),
                "br": jnp.zeros((cfg.num_experts,), jnp.float32),
                "wg": wg, "wu": wu, "wd": wd,
                "shared": mlp(ks[11], m * cfg.num_shared_experts),
            }
        return lp

    keys = jax.random.split(rng, cfg.num_layers + 2)
    return {
        "embed": norm(keys[0], cfg.vocab_size, d),
        "layers": [layer(keys[2 + i], i) for i in range(cfg.num_layers)],
        "lnf": {"scale": ones(d)},
        "lm_head": norm(keys[1], cfg.vocab_size, d),
    }


def init_cache(cfg: KimiLinearConfig, batch: int, max_len: int,
               dtype=None, groups=None) -> KVCache:
    """The latent plane of the MLA layers alone, and the KDA layers' state
    planes beside it (`models/common.py` `KVCache`). `groups`
    (models/registry.py): the latent plane has one head."""
    if cfg.quant_kv:
        raise ValueError("kimi_linear serves the published bfloat16 latent "
                         "cache and a float32 state: kv_quant is not "
                         "supported")
    dtype = dtype or cfg.dtype
    cache = mla.init_cache(len(cfg.full_attn_layers), batch, max_len, cfg,
                           dtype)
    ssm, conv = kda.init_state(cfg, len(cfg.kda_layers), batch, dtype)
    return cache._replace(ssm=ssm, conv=conv)


def forward(
    params: Params,
    cfg: KimiLinearConfig,
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
    "routing": int32 [Le, B, T, k], "attn_in": [La, B, T, D], what the MLA
    layers' projections were given (a comparison reads the latent cache
    against its own float32 product of it)}. Contract in the module
    docstring."""
    t = input_ids.shape[1]
    given = live is not None
    offset, q_slots, positions, live = batch_slots(
        input_ids, cache, positions, live, rows)
    mask = causal_window_mask(q_slots, t if cache is None
                              else cache.k.shape[3])
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, None, :]
        if not given:
            # A token whose own key slot is masked is padding.
            live = jnp.take_along_axis(
                kv_mask, jnp.minimum(q_slots, kv_mask.shape[1] - 1), axis=1)
    latent = mla.squeeze(cache)
    planes = None if cache is None else (cache.ssm, cache.conv)
    attn_in = []

    def mixer(h, ap, layer):
        nonlocal latent, planes
        if cfg.is_kda(layer):
            out, planes = kda.mixer(h, ap, cfg, live, planes,
                                    cfg.index(layer), rows)
            return out
        attn_in.append(h)
        out, latent = mla.attention(h, ap, cfg, cfg.index(layer), positions,
                                    q_slots, mask, latent, offset, rows)
        return out

    x = quant.embed_lookup(params["embed"], input_ids).astype(cfg.dtype)
    x, counts, routing = run_layers(
        params, cfg, x, live, mixer,
        lambda layer: "attn.kda" if cfg.is_kda(layer) else "attn.mla",
        COUNTERS)
    new_cache = None
    if cache is not None:
        new_cache = cache._replace(k=latent[:, :, None],
                                   length=cache.length + t,
                                   ssm=planes[0], conv=planes[1])
    logits, *rest = head(params, cfg, x, counts, routing, aux)
    if aux:
        rest[0]["attn_in"] = jnp.stack(attn_in)
    return (logits, new_cache, *rest)


def params_from_hf(sd, cfg: KimiLinearConfig) -> Params:
    """The published checkpoint's names into this tree:
    `model.layers.<i>.self_attn.*` (KDA: `{q,k,v}_proj`, `{q,k,v}_conv1d`,
    `f_{a,b}_proj`, `A_log`, `dt_bias`, `b_proj`, `g_{a,b}_proj`, `o_norm`,
    `o_proj`; MLA: `q_proj`, `kv_a_proj_with_mqa`, `kv_a_layernorm`,
    `kv_b_proj`, `o_proj`), `input_layernorm`, `post_attention_layernorm`,
    `mlp.{gate,up,down}_proj` or `block_sparse_moe.{gate, experts.<e>.{w1,
    w3, w2}, shared_experts}`. Linears are stored [out, in] there and
    [in, out] here, a convolution [C, 1, K] there and [K, C] here, the
    three projections and their convolutions side by side; of the experts
    only `experts_held` are read."""
    pd = cfg.param_dtype

    def lin(name):
        return jnp.asarray(sd[name + ".weight"], pd).T

    def vec(name):
        return {"scale": jnp.asarray(sd[name + ".weight"], pd)}

    def f32(name):
        return jnp.asarray(sd[name], jnp.float32)

    def mlp(prefix, names=("gate_proj", "up_proj", "down_proj")):
        return {k: lin(f"{prefix}.{n}") for k, n in zip(("wg", "wu", "wd"),
                                                        names)}

    first, count = cfg.experts_held or (0, cfg.num_experts)
    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        a = p + ".self_attn"
        lp = {"ln1": vec(p + ".input_layernorm"),
              "ln2": vec(p + ".post_attention_layernorm")}
        if cfg.is_kda(i):
            lp["attn"] = {
                "w_qkv": jnp.concatenate(
                    [lin(f"{a}.{n}_proj") for n in "qkv"], axis=1),
                "conv_w": jnp.concatenate(
                    [jnp.asarray(sd[f"{a}.{n}_conv1d.weight"], pd)[:, 0].T
                     for n in "qkv"], axis=1),
                "w_fa": lin(a + ".f_a_proj"), "w_fb": lin(a + ".f_b_proj"),
                "dt_bias": f32(a + ".dt_bias").reshape(-1),
                "a_log": f32(a + ".A_log").reshape(-1),
                "w_b": lin(a + ".b_proj"),
                "w_ga": lin(a + ".g_a_proj"), "w_gb": lin(a + ".g_b_proj"),
                "norm": vec(a + ".o_norm"), "w_out": lin(a + ".o_proj")}
        else:
            wuk, wuv = mla.split_kv_b(lin(a + ".kv_b_proj"), cfg)
            lp["attn"] = {"wq": lin(a + ".q_proj"),
                          "wkva": lin(a + ".kv_a_proj_with_mqa"),
                          "kvn": vec(a + ".kv_a_layernorm"),
                          "wuk": wuk, "wuv": wuv, "wo": lin(a + ".o_proj")}
        if i < cfg.num_dense_layers:
            lp["mlp"] = mlp(p + ".mlp")
        else:
            m = p + ".block_sparse_moe"
            experts = [mlp(f"{m}.experts.{e}", ("w1", "w3", "w2"))
                       for e in range(first, first + count)]
            wg, wu, wd = pad_experts(*(jnp.stack([x[k] for x in experts])
                                       for k in ("wg", "wu", "wd")))
            lp["moe"] = {
                "wr": lin(m + ".gate"),
                "br": f32(m + ".gate.e_score_correction_bias"),
                "wg": wg, "wu": wu, "wd": wd,
                "shared": mlp(m + ".shared_experts"),
            }
        layers.append(lp)
    return {
        "embed": jnp.asarray(sd["model.embed_tokens.weight"], pd),
        "layers": layers,
        "lnf": vec("model.norm"),
        "lm_head": jnp.asarray(sd["lm_head.weight"], pd),
    }
