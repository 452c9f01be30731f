"""SK Telecom's `axk1` decoder (A.X-K1) in pure-functional JAX: latent
attention (MLA) in every layer, a leading dense layer, then layers of
routed experts with a shared expert, of which this process may hold a
share.

Same family surface and forward contract as gpt2.py / llama.py / afmoe.py
(absolute `positions`, cache slots written at `cache.length`, scalar or
per-row ragged; `kv_mask` marks valid key slots; `rows` names the cache
rows a ragged batch addresses; T = 1, a chunk or a bucket). From the
published `config.json`, whose keys are DeepSeek-V3's, and, where they do
not say, from that model's modelling code:

- pre-norm, two RMSNorms a layer: `h += Attn(N1(h))`, `h += Mlp(N2(h))`;
  final RMSNorm, untied `lm_head`, no biases;
- attention: `models/mla.py` (low-rank q and kv projections with their
  norms, one rotary key shared by all heads, YaRN over the rotary
  dimensions, absorbed products over a latent cache of `kv_lora_rank +
  qk_rope_head_dim` values a token and layer);
- MLP: SwiGLU, dense in the first `num_dense_layers` layers
  (`first_k_dense_replace`); after them `num_experts` routed experts
  (`n_routed_experts`), `num_experts_per_tok` a token by sigmoid scores
  with no balancing bias and no group limit (`topk_method` is `none`),
  weights normalised over the chosen and times `route_scale`
  (`routed_scaling_factor`), no capacity and no drops, plus one shared
  expert.

**A share of a layer's experts.** One layer's 192 experts are 16.9 GB in
bfloat16, so a deployment spreads them over chips. `experts_held` = (first,
count) says which this process holds: the expert stacks are [count, ..],
the router keeps all `num_experts` outputs and its picks, and the routed
layer computes its own experts' part of the result plus the shared
expert's (`afmoe.moe_mlp`, `moe.grouped_swiglu`). What the absent experts
would add is their chips' to add: no code here stands in for them. A
share has its grouped products run over a prefix of a pass's sorted picks,
where the held ones are: the share's fair part of the pass and twelve
deviations more (`moe.held_rows`: 64 of a decode row's 256), wherever that
is not every row, and over all of them in a pass whose held picks
outnumber the prefix.

The trunk is afmoe's (`afmoe.run_layers`, `afmoe.head`): a list of
per-layer trees, unrolled, the same `aux` record (`counts` has three more
entries: the picks that landed on the share held, the routed layers'
passes whose products had such a prefix, and those that fit it).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import afmoe, mla, quant
from .afmoe import batch_slots, head, run_layers
from .common import KVCache, causal_window_mask

Params = Dict[str, Any]

# afmoe's three counts and, a chip holding a share of a layer's experts,
# the picks that landed on the share, the routed layers' passes whose
# products were bounded to a prefix of the rows, and those that fit it.
COUNTERS = afmoe.SHARE_COUNTERS


@dataclasses.dataclass(frozen=True)
class AxK1Config:
    vocab_size: int = 163840
    max_position_embeddings: int = 131072
    hidden_size: int = 7168
    num_layers: int = 61            # config.json: num_hidden_layers
    num_dense_layers: int = 1       # first_k_dense_replace
    num_heads: int = 64             # num_attention_heads
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_experts: int = 192          # n_routed_experts: the router's width
    # (first, count) of the experts this process holds; None = all.
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1     # n_shared_experts
    route_norm: bool = True         # norm_topk_prob
    route_scale: float = 2.5        # routed_scaling_factor
    rope_theta: float = 10000.0
    rope_scaling: mla.Yarn = mla.Yarn()
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # The engines set this for an int8 cache; the latent cache has none
    # (`init_cache` refuses).
    quant_kv: bool = False

    @property
    def num_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @classmethod
    def ax_k1(cls, **kw) -> "AxK1Config":
        """skt/A.X-K1 as published: 61 layers, 519B parameters (for the
        record: one expert layer alone is 17 GB)."""
        return cls(**kw)

    @classmethod
    def ax_k1_1d4e_share(cls, **kw) -> "AxK1Config":
        """One chip's part of a deployment in which 16 chips share each
        layer, every width as published: the dense layer and four of the
        60 expert layers, experts 0 to 11 of each layer's 192, an eighth
        of the vocabulary."""
        return cls(num_layers=5, experts_held=(0, 12), vocab_size=20480,
                   **kw)

    @classmethod
    def tiny(cls, **kw) -> "AxK1Config":
        """Test size: the cut's shape (1 dense + 4 expert layers, a share
        of the experts) at widths a CPU test can afford."""
        kw.setdefault("vocab_size", 384)
        kw.setdefault("max_position_embeddings", 64)
        kw.setdefault("experts_held", (0, 8))
        return cls(
            hidden_size=32, num_layers=5, num_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
            v_head_dim=8, intermediate_size=64, moe_intermediate_size=16,
            num_experts=32, num_experts_per_tok=4,
            rope_scaling=mla.Yarn(factor=4.0,
                                  original_max_position_embeddings=16),
            **kw,
        )


def init_params(rng: jax.Array, cfg: AxK1Config) -> Params:
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
              "attn": mla.init_params(ks[:6], cfg, norm, ones)}
        if i < cfg.num_dense_layers:
            lp["mlp"] = mlp(ks[6], cfg.intermediate_size)
        else:
            lp["moe"] = {
                # Router columns an order above the other matrices, so a
                # token's experts differ by more than a rounding.
                "wr": (10 * std * jax.random.normal(
                    ks[7], (d, cfg.num_experts), jnp.float32)).astype(pd),
                "wg": norm(ks[8], e, d, m), "wu": norm(ks[9], e, d, m),
                "wd": norm(ks[10], e, m, d),
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


def init_cache(cfg: AxK1Config, batch: int, max_len: int,
               dtype=None, groups=None) -> KVCache:
    # `groups` (models/registry.py): the latent plane has one head.
    if cfg.quant_kv:
        raise ValueError("axk1 serves the published bfloat16 latent cache: "
                         "kv_quant is not supported")
    return mla.init_cache(cfg.num_layers, batch, max_len, cfg, dtype)


def forward(
    params: Params,
    cfg: AxK1Config,
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
    "routing": int32 [Le, B, T, k]}. Contract as afmoe.forward."""
    t = input_ids.shape[1]
    offset, q_slots, positions, live = batch_slots(
        input_ids, cache, positions, live, rows)

    mask = causal_window_mask(q_slots, t if cache is None
                              else cache.k.shape[3])
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, None, :]
    plane = mla.squeeze(cache)

    def attention(h, ap, layer):
        nonlocal plane
        out, plane = mla.attention(h, ap, cfg, layer, positions, q_slots,
                                   mask, plane, offset, rows)
        return out

    x = quant.embed_lookup(params["embed"], input_ids).astype(cfg.dtype)
    x, counts, routing = run_layers(params, cfg, x, live, attention,
                                    lambda layer: "attn.mla", COUNTERS)
    new_cache = None
    if cache is not None:
        new_cache = mla.unsqueeze(plane, cache, t)
    logits, *rest = head(params, cfg, x, counts, routing, aux)
    return (logits, new_cache, *rest)


def params_from_hf(sd, cfg: AxK1Config) -> Params:
    """The published checkpoint's names (DeepSeek-V3's) into this tree:
    `model.layers.<i>.self_attn.{q_a_proj, q_a_layernorm, q_b_proj,
    kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}`,
    `input_layernorm`, `post_attention_layernorm`, `mlp.{gate,up,down}_proj`
    or `mlp.gate.weight` (the router), `mlp.experts.<e>.*`,
    `mlp.shared_experts.*`. Linears are stored [out, in] there and
    [in, out] here; the rotary columns of `q_b_proj` and
    `kv_a_proj_with_mqa` are interleaved pairs there and halves here
    (rotate-half), so they are permuted once, at load; of the experts
    only `experts_held` are read."""
    pd = cfg.param_dtype
    dn, dr, kr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    halves = jnp.concatenate([jnp.arange(0, dr, 2), jnp.arange(1, dr, 2)])

    def lin(name):
        return jnp.asarray(sd[name + ".weight"], pd).T

    def vec(name):
        return {"scale": jnp.asarray(sd[name + ".weight"], pd)}

    def mlp(prefix):
        return {"wg": lin(prefix + ".gate_proj"),
                "wu": lin(prefix + ".up_proj"),
                "wd": lin(prefix + ".down_proj")}

    first, count = cfg.experts_held or (0, cfg.num_experts)
    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        a = p + ".self_attn"
        wqb = lin(a + ".q_b_proj").reshape(-1, cfg.num_heads, dn + dr)
        wqb = jnp.concatenate([wqb[..., :dn], wqb[..., dn:][..., halves]],
                              axis=-1).reshape(wqb.shape[0], -1)
        wkva = lin(a + ".kv_a_proj_with_mqa")
        wkva = jnp.concatenate([wkva[:, :kr], wkva[:, kr:][:, halves]],
                               axis=-1)
        wuk, wuv = mla.split_kv_b(lin(a + ".kv_b_proj"), cfg)
        lp = {
            "ln1": vec(p + ".input_layernorm"),
            "ln2": vec(p + ".post_attention_layernorm"),
            "attn": {"wqa": lin(a + ".q_a_proj"),
                     "qn": vec(a + ".q_a_layernorm"), "wqb": wqb,
                     "wkva": wkva, "kvn": vec(a + ".kv_a_layernorm"),
                     "wuk": wuk, "wuv": wuv, "wo": lin(a + ".o_proj")},
        }
        if i < cfg.num_dense_layers:
            lp["mlp"] = mlp(p + ".mlp")
        else:
            experts = [mlp(f"{p}.mlp.experts.{e}")
                       for e in range(first, first + count)]
            lp["moe"] = {
                "wr": lin(p + ".mlp.gate"),
                **{k: jnp.stack([x[k] for x in experts])
                   for k in ("wg", "wu", "wd")},
                "shared": mlp(p + ".mlp.shared_experts"),
            }
        layers.append(lp)
    return {
        "embed": jnp.asarray(sd["model.embed_tokens.weight"], pd),
        "layers": layers,
        "lnf": vec("model.norm"),
        "lm_head": jnp.asarray(sd["lm_head.weight"], pd),
    }
