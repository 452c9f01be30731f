"""Arcee's `afmoe` decoder (Trinity-Mini and its family) in pure-functional
JAX: leading dense layers, then layers of routed experts with a shared
expert; sliding-window and full attention mixed by a per-layer pattern.

Same family surface and forward contract as gpt2.py / llama.py (absolute
`positions`, cache slots written at `cache.length`, scalar or per-row
ragged; `kv_mask` marks valid key slots; T = 1, a chunk or a bucket), one
`KVCache` [L, B, Hkv, T, Dh] for every layer. What differs, from the
published `config.json` and `transformers`' `modeling_afmoe.py`:

- `h0 = E[ids] * sqrt(hidden)` (`mup_enabled`), untied `lm_head`;
- four RMSNorms a layer: before AND after each sublayer,
  `h += N2(Attn(N1(h)))`, `h += N4(Mlp(N3(h)))`;
- attention: `head_dim` is a key of its own (not hidden / heads), grouped
  keys and values, `q` and `k` RMS-normalised per head, a sigmoid gate
  `Wg x` on the heads' output before `Wo`, no biases. A layer's type says
  two things at once: `sliding_attention` layers rotate `q` and `k`
  (`llama.rope`, all of head_dim) and see the last `sliding_window` keys;
  `full_attention` layers see every key and carry NO position signal;
- MLP: SwiGLU, dense in the first `num_dense_layers` layers; after them
  `num_experts` routed experts, `num_experts_per_tok` a token by sigmoid
  scores (`moe.route_sigmoid`), no capacity and no drops
  (`moe.grouped_swiglu`), plus `num_shared_experts` shared ones.

Layers are NOT one stacked scan (ROADMAP D4): a layer differs from its
neighbour in mask, rotary and MLP kind, and the grouped expert product is
a kernel call whose operands must be whole buffers: an [L, E, D, M] stack
sliced per layer costs a copy of the layer's experts every call (1.6 GB at
Trinity-Mini's sizes; the compiler's own text for a described v5e shows
it). So `params["layers"]` is a list of per-layer trees and the trunk is
unrolled: compile time grows with depth, the serving cut has five layers.

Beside (logits, cache), `forward(..., aux=True)` hands out what routing
did: `counts` int32 [3] (picks computed, experts reached, expert seats
offered = experts x expert layers) and `routing` int32 [Le, B, T, k], the
chosen experts. `live` [B] or [B, T] says which tokens are real: idle and
parked lanes and pad positions route nowhere and reach no expert.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import quant
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
from .llama import rope
from .moe import grouped_swiglu, held_rows, route_sigmoid

Params = Dict[str, Any]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    max_position_embeddings: int = 131072
    hidden_size: int = 2048
    num_layers: int = 32            # config.json: num_hidden_layers
    num_dense_layers: int = 2
    # One of SLIDING / FULL per layer; empty = the published rule, every
    # `global_attn_every_n_layers`-th layer full.
    layer_types: Tuple[str, ...] = ()
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    num_heads: int = 32             # num_attention_heads
    num_kv_heads: int = 4           # num_key_value_heads
    head_dim: int = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # The engines set this for an int8 cache; this family has none (the
    # published precision is bfloat16) and refuses in `init_cache`.
    quant_kv: bool = False

    @property
    def types(self) -> Tuple[str, ...]:
        if self.layer_types:
            if len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"{len(self.layer_types)} layer_types for "
                    f"{self.num_layers} layers")
            return self.layer_types
        n = self.global_attn_every_n_layers
        return tuple(FULL if (i + 1) % n == 0 else SLIDING
                     for i in range(self.num_layers))

    @property
    def num_expert_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @classmethod
    def trinity_mini(cls, **kw) -> "AfmoeConfig":
        """arcee-ai/Trinity-Mini as published: 32 layers, 26B parameters
        (for the record: no single chip holds it)."""
        return cls(**kw)

    @classmethod
    def trinity_mini_1d4e(cls, **kw) -> "AfmoeConfig":
        """Trinity-Mini cut in depth alone, every width as published: one
        of the two leading dense layers (published layer 0) and one whole
        period of the pattern, the expert layers 4 to 7."""
        return cls(num_layers=5, num_dense_layers=1,
                   layer_types=(SLIDING,) * 4 + (FULL,), **kw)

    @classmethod
    def tiny(cls, **kw) -> "AfmoeConfig":
        """Test size: the cut's shape (1 dense + 4 expert layers, the
        period sliding x3, full) at widths a CPU test can afford."""
        kw.setdefault("vocab_size", 384)
        kw.setdefault("max_position_embeddings", 64)
        kw.setdefault("sliding_window", 8)
        return cls(
            hidden_size=32, num_layers=5, num_dense_layers=1,
            layer_types=(SLIDING,) * 4 + (FULL,), num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=64,
            moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
            num_shared_experts=1, **kw,
        )


def init_params(rng: jax.Array, cfg: AfmoeConfig) -> Params:
    """Seeded weights, each leaf drawn in the parameter dtype (a float32
    draw of the real sizes would not fit beside its own cast)."""
    d, dh, pd = cfg.hidden_size, cfg.head_dim, cfg.param_dtype
    qd, kvd = cfg.num_heads * dh, cfg.num_kv_heads * dh
    e, m = cfg.num_experts, cfg.moe_intermediate_size
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
        lp = {
            "ln1": {"scale": ones(d)}, "ln1p": {"scale": ones(d)},
            "ln2": {"scale": ones(d)}, "ln2p": {"scale": ones(d)},
            "attn": {
                "wq": norm(ks[0], d, qd), "wk": norm(ks[1], d, kvd),
                "wv": norm(ks[2], d, kvd), "wg": norm(ks[3], d, qd),
                "wo": norm(ks[4], qd, d),
                "qn": {"scale": ones(dh)}, "kn": {"scale": ones(dh)},
            },
        }
        if i < cfg.num_dense_layers:
            lp["mlp"] = mlp(ks[5], cfg.intermediate_size)
        else:
            lp["moe"] = {
                # Router columns an order above the other matrices, so a
                # token's experts differ by more than a rounding.
                "wr": (10 * std * jax.random.normal(
                    ks[6], (d, e), jnp.float32)).astype(pd),
                "br": jnp.zeros((e,), jnp.float32),
                "wg": norm(ks[7], e, d, m), "wu": norm(ks[8], e, d, m),
                "wd": norm(ks[9], e, m, d),
                "shared": mlp(ks[10], m * cfg.num_shared_experts),
            }
        return lp

    keys = jax.random.split(rng, cfg.num_layers + 2)
    return {
        "embed": norm(keys[0], cfg.vocab_size, d),
        "layers": [layer(keys[2 + i], i) for i in range(cfg.num_layers)],
        "lnf": {"scale": ones(d)},
        "lm_head": norm(keys[1], cfg.vocab_size, d),
    }


def init_cache(cfg: AfmoeConfig, batch: int, max_len: int,
               dtype=None, groups=None) -> KVCache:
    # `groups` (models/registry.py): a bfloat16 plane of 128-wide heads
    # tiles unpadded as it is.
    if cfg.quant_kv:
        raise ValueError("afmoe serves the published bfloat16 cache: "
                         "kv_quant is not supported")
    return KVCache.create(cfg.num_layers, batch, cfg.num_kv_heads, max_len,
                          cfg.head_dim, dtype or cfg.dtype)


def swiglu(x: jax.Array, mp: Params) -> jax.Array:
    return dense(jax.nn.silu(dense(x, mp["wg"])) * dense(x, mp["wu"]),
                 mp["wd"])


def moe_mlp(h: jax.Array, mp: Params, cfg, live: jax.Array):
    """An expert layer's MLP, [B, T, D] -> ([B, T, D], chosen experts
    [B, T, k], group sizes of the experts held [E held]); `live` [B, T]
    bool. A configuration whose `experts_held` is (first, count) holds
    that share of the layer's `num_experts` (the stacks are [count, ..]):
    it routes over all of them and computes its own experts' part plus
    the shared expert's (`moe.grouped_swiglu`). A tree without `br` has
    no balancing bias. Stacks held wider than D (`models/kimi_linear.py`
    `pad_experts`: zero rows and columns up to whole tiles of the grouped
    product) meet zero columns of the input, and the output is cut back."""
    b, t, d = h.shape
    x = h.reshape(b * t, d)
    held = getattr(cfg, "experts_held", None)
    with jax.named_scope("moe.route"):
        top_i, top_w = route_sigmoid(
            x, mp["wr"], mp.get("br"), cfg.num_experts_per_tok,
            cfg.route_norm, cfg.route_scale)
    with jax.named_scope("moe.experts"):
        wide = mp["wg"].shape[1]
        y, sizes = grouped_swiglu(
            x if wide == d else jnp.pad(x, [(0, 0), (0, wide - d)]),
            top_i, top_w, live.reshape(b * t), mp["wg"], mp["wu"], mp["wd"],
            first=held[0] if held else None, among=cfg.num_experts)
        if wide != d:
            y = y[:, :d]
    with jax.named_scope("moe.shared"):
        y = y + swiglu(x, mp["shared"])
    return y.reshape(b, t, d), top_i.reshape(b, t, -1), sizes


# What a routed family counts a forward pass, in the order of `counts`:
# picks computed, experts reached, expert seats offered (experts held x
# expert layers). A family that holds a share of a layer's experts names
# three more after them (`SHARE_COUNTERS`): `moe_picks_held`, the picks
# that landed on the share (here every pick does); `moe_passes_bounded`,
# the routed layers' passes whose products had fewer rows to run over than
# picks (`moe.held_rows`: the share's fair part of the pass and twelve
# deviations more, where that is not every row); and
# `moe_passes_compacted`, those of them whose held picks fit
# (`nemotron_h`, whose half of the experts always runs whole, names the
# first alone and counts in its own loop). A family's
# tuple is the one thing that says which: its forward hands it to
# `run_layers` (`layer_counts`), and `registry.ModelFamily.counters` to
# the engine.
COUNTERS = ("moe_picks", "moe_experts_reached", "moe_expert_seats")
SHARE_COUNTERS = COUNTERS + ("moe_picks_held", "moe_passes_bounded",
                             "moe_passes_compacted")


def layer_counts(counters, cfg, live: jax.Array, top_i: jax.Array,
                 sizes: jax.Array) -> jax.Array:
    """One routed layer's pass in a family's `counters`, int32
    [len(counters)]: `moe_mlp`'s picks [B, T, k] and held experts' group
    sizes, `live` the tokens that routed."""
    held = jnp.sum(sizes)
    seen = [held, jnp.sum(sizes > 0).astype(jnp.int32),
            jnp.asarray(sizes.shape[0], jnp.int32)]
    if counters != COUNTERS:
        fit = held_rows(top_i.size, sizes.shape[0], cfg.num_experts)
        bounded = jnp.asarray(fit < top_i.size, jnp.int32)
        seen = [jnp.sum(live).astype(jnp.int32) * top_i.shape[-1],
                *seen[1:], held, bounded, bounded * (held <= fit)]
    return jnp.stack(seen)


def run_layers(params: Params, cfg, x: jax.Array, live: jax.Array,
               attention, scope, counters=COUNTERS):
    """The unrolled trunk of a list-of-layers family, from the embedded
    tokens to the last layer's output: x [B, T, D] -> (x, counts, routing
    [Le] of [B, T, k]). Pre-norm sublayers, each with a norm AFTER it too
    where the layer's tree has one (`ln1p`, `ln2p`: afmoe's four norms a
    layer; a tree without them is the plain `h += Attn(N1(h))`,
    `h += Mlp(N2(h))`). `attention(h, ap, layer)` is the family's own,
    cache and all; `scope(layer)` names its span. A layer with a `moe`
    subtree is routed (`moe_mlp`), one with `mlp` a dense SwiGLU.
    `counters` is the family's tuple of count names (above)."""
    eps = cfg.rms_norm_eps
    routing = []
    counts = jnp.zeros((len(counters),), jnp.int32)
    for layer, lp in enumerate(params["layers"]):
        with jax.named_scope(scope(layer)):
            a = attention(rms_norm(x, lp["ln1"]["scale"], eps), lp["attn"],
                          layer)
            if "ln1p" in lp:
                a = rms_norm(a, lp["ln1p"]["scale"], eps)
            x = x + a
        h = rms_norm(x, lp["ln2"]["scale"], eps)
        if "moe" in lp:
            y, top_i, sizes = moe_mlp(h, lp["moe"], cfg, live)
            routing.append(top_i)
            counts = counts + layer_counts(counters, cfg, live, top_i, sizes)
        else:
            with jax.named_scope("mlp.dense"):
                y = swiglu(h, lp["mlp"])
        if "ln2p" in lp:
            y = rms_norm(y, lp["ln2p"]["scale"], eps)
        x = x + y
    return x, counts, routing


def head(params: Params, cfg, x: jax.Array, counts, routing, aux: bool):
    """Final norm and the untied head; with `aux` the routing's record
    beside the logits (module docstring)."""
    x = rms_norm(x, params["lnf"]["scale"], cfg.rms_norm_eps)
    logits = quant.unembed(x, params["lm_head"])
    if not aux:
        return (logits,)
    b, t = x.shape[:2]
    return logits, {
        "counts": counts,
        "routing": (jnp.stack(routing) if routing
                    else jnp.zeros((0, b, t, cfg.num_experts_per_tok),
                                   jnp.int32)),
    }


def batch_slots(input_ids: jax.Array, cache: Optional[KVCache],
                positions: Optional[jax.Array], live: Optional[jax.Array],
                rows: Optional[jax.Array]):
    """What `forward`'s contract leaves to defaults, for a batch [B, T]:
    (offset, the cache slots its tokens go to [B, T], positions [B, T],
    live [B, T] bool). The offset is the cache's length, scalar or per
    row; positions default to the slots, every token to live."""
    b, t = input_ids.shape
    offset = jnp.zeros((), jnp.int32) if cache is None else cache.length
    off_row = offset[:, None] if offset.ndim else offset[None, None]
    q_slots = jnp.broadcast_to(
        off_row + jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
    if positions is None:
        positions = q_slots
    if live is None:
        live = jnp.ones((b, t), bool)
    live = jnp.broadcast_to(live.reshape(b, -1), (b, t))
    if rows is not None and offset.ndim != 1:
        raise ValueError("rows names the cache rows of a ragged batch "
                         "(per-row cache.length)")
    return offset, q_slots, positions, live


def forward(
    params: Params,
    cfg: AfmoeConfig,
    input_ids: jax.Array,
    cache: Optional[KVCache] = None,
    positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    live: Optional[jax.Array] = None,
    aux: bool = False,
    rows: Optional[jax.Array] = None,
):
    """Run the decoder; returns (logits [B, T, V] float32, updated cache),
    and with `aux` a third value, {"counts": int32 [3], "routing": int32
    [Le, B, T, k]} (module docstring). Contract as gpt2.forward / llama.
    forward: positions drive the rotary embedding of the sliding layers and
    nothing else; masks are built on cache SLOTS, which differ from
    positions by a row's padding only, so the window is the same distance
    in both; `rows` names the cache rows a ragged batch addresses."""
    b, t = input_ids.shape
    eps, dh = cfg.rms_norm_eps, cfg.head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    groups = nh // nkv

    offset, q_slots, positions, live = batch_slots(
        input_ids, cache, positions, live, rows)

    num_keys = t if cache is None else cache.k.shape[3]
    masks = {}
    for kind, window in ((SLIDING, cfg.sliding_window), (FULL, None)):
        if kind in cfg.types:
            m = causal_window_mask(q_slots, num_keys, window)
            if kv_mask is not None:
                m = m & kv_mask[:, None, None, :]
            # Query heads of one kv head ride the query axis (below).
            masks[kind] = jnp.tile(m, (1, 1, groups, 1))

    x = quant.embed_lookup(params["embed"], input_ids).astype(cfg.dtype)
    if cfg.mup_enabled:
        x = x * jnp.asarray(math.sqrt(cfg.hidden_size), cfg.dtype)

    ck = cv = None
    if cache is not None:
        ck, cv = cache.k, cache.v
    zero = jnp.zeros((), jnp.int32)
    at_rows = (jnp.arange(b) if rows is None else rows)[:, None]

    def attention(h, ap, kind, layer):
        nonlocal ck, cv
        q = split_heads(dense(h, ap["wq"]), nh)
        k = split_heads(dense(h, ap["wk"]), nkv)
        v = split_heads(dense(h, ap["wv"]), nkv)
        q = rms_norm(q, ap["qn"]["scale"], eps)
        k = rms_norm(k, ap["kn"]["scale"], eps)
        if kind == SLIDING:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
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
        # Grouped keys and values without repeating them: the `groups`
        # query heads of a kv head are folded into the query axis.
        a = attend(q.reshape(b, nkv, groups * t, dh), k, v, masks[kind])
        a = merge_heads(a.reshape(b, nh, t, dh))
        gate = jax.nn.sigmoid(dense(h, ap["wg"]).astype(jnp.float32))
        return dense((a.astype(jnp.float32) * gate).astype(a.dtype),
                     ap["wo"])

    def attend_layer(h, ap, layer):
        return attention(h, ap, cfg.types[layer], layer)

    x, counts, routing = run_layers(
        params, cfg, x, live, attend_layer,
        lambda layer: ("attn.window" if cfg.types[layer] == SLIDING
                       else "attn.full"))
    new_cache = None
    if cache is not None:
        new_cache = KVCache(k=ck, v=cv, length=cache.length + t)
    logits, *rest = head(params, cfg, x, counts, routing, aux)
    return (logits, new_cache, *rest)


def params_from_hf(sd, cfg: AfmoeConfig) -> Params:
    """The published checkpoint's names into this tree: `model.layers.<i>.
    self_attn.{q,k,v,o,gate}_proj`, `{q,k}_norm`, `input_layernorm`,
    `post_attention_layernorm`, `pre_mlp_layernorm`, `post_mlp_layernorm`,
    `mlp.{gate,up,down}_proj` or `mlp.router.gate`, `mlp.expert_bias`,
    `mlp.experts.<e>.*`, `mlp.shared_experts.*`. Linears are stored
    [out, in] there and [in, out] here."""
    pd = cfg.param_dtype

    def lin(name):
        return jnp.asarray(sd[name + ".weight"], pd).T

    def vec(name):
        return {"scale": jnp.asarray(sd[name + ".weight"], pd)}

    def mlp(prefix):
        return {"wg": lin(prefix + ".gate_proj"),
                "wu": lin(prefix + ".up_proj"),
                "wd": lin(prefix + ".down_proj")}

    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        a = p + ".self_attn"
        lp = {
            "ln1": vec(p + ".input_layernorm"),
            "ln1p": vec(p + ".post_attention_layernorm"),
            "ln2": vec(p + ".pre_mlp_layernorm"),
            "ln2p": vec(p + ".post_mlp_layernorm"),
            "attn": {"wq": lin(a + ".q_proj"), "wk": lin(a + ".k_proj"),
                     "wv": lin(a + ".v_proj"), "wg": lin(a + ".gate_proj"),
                     "wo": lin(a + ".o_proj"),
                     "qn": vec(a + ".q_norm"), "kn": vec(a + ".k_norm")},
        }
        if i < cfg.num_dense_layers:
            lp["mlp"] = mlp(p + ".mlp")
        else:
            experts = [mlp(f"{p}.mlp.experts.{e}")
                       for e in range(cfg.num_experts)]
            lp["moe"] = {
                "wr": lin(p + ".mlp.router.gate"),
                "br": jnp.asarray(sd[p + ".mlp.expert_bias"], jnp.float32),
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
