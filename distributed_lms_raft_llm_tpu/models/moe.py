"""Mixture-of-Experts GPT-2 with expert parallelism (the `ep` mesh axis).

Beyond-reference capability (the reference serves dense GPT-2 only —
GUI_RAFT_LLM_SourceCode/tutoring_server.py:10-12): every transformer
block's dense MLP becomes E feed-forward experts behind a learned top-k
router, executed the canonical TPU way (GShard / Switch Transformer):

- **Static-shape dispatch/combine einsums, no gather loops.** Each token's
  top-k experts and its position within each expert's capacity buffer are
  computed with one_hot + cumsum (pure static ops), giving a dispatch
  tensor [S, E, C] and a weight-carrying combine tensor of the same shape.
  Expert inputs are then one einsum ("sec,sd->ecd"), the expert FFNs are
  batched matmuls over the leading E axis (MXU-friendly), and outputs
  come back through the transposed einsum. Tokens over capacity are
  dropped (combine weight 0) and ride the residual stream — the standard
  Switch behavior, bounded compute per step by construction.
- **Expert parallelism = shard the E axis.** Partition rules place
  `blocks/moe/{wi,bi,wo,bo}` on the `ep` mesh axis
  (parallel/partition.py); under jit the dispatch einsum's contraction
  against ep-sharded expert weights makes XLA insert the all-to-all /
  reduce-scatter collectives itself — no hand-written comm, exactly like
  the tp rules. Composes with tp/dp on the other axes.
- **Everything else is the GPT-2 trunk.** `forward` IS gpt2.forward: the
  block routes through this MLP when its params carry a `moe` subtree, so
  the KV cache, bucketed prefill, while_loop decode, ragged paged slots,
  and speculative verification all work unchanged.

Top-k routing follows the Mixtral convention: softmax over all experts,
keep the k largest, renormalize their weights. `capacity_factor` scales
the per-expert buffer C = ceil(cf * S * k / E); cf >= E disables dropping
entirely (C >= S*k: every slot pick fits even if all land on one expert).

Capacity caveat: with dropping active, a token's output depends on what
else shares its forward pass (whether it wins a buffer slot) — inherent
to Switch-style capacity, not a bug. Consequences: group-batched serving
is deterministic per batch but not per request, and speculative decoding
(engine/spec.py) verifies against window-context distributions that can
differ from step-context ones, so its exactness guarantee holds for MoE
only at cf >= E (no drops). Decode-sized forwards (S = batch) rarely
drop in practice; raise capacity_factor where bit-stability matters.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import gpt2

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPT2MoEConfig(gpt2.GPT2Config):
    num_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25

    @classmethod
    def moe_small(cls, **kw) -> "GPT2MoEConfig":
        """GPT-2-small trunk, 8 experts x top-2 (~124M active / ~680M total)."""
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "GPT2MoEConfig":
        kw.setdefault("vocab_size", 384)
        kw.setdefault("max_position_embeddings", 64)
        kw.setdefault("num_experts", 4)
        kw.setdefault("experts_per_token", 2)
        return cls(hidden_size=32, num_layers=2, num_heads=4, **kw)


def init_params(rng: jax.Array, cfg: GPT2MoEConfig) -> Params:
    """GPT-2 init with each block's `mlp` replaced by a `moe` subtree:
    router [L, D, E] plus per-expert FFN stacks [L, E, D, M] / [L, E, M, D].
    """
    params = gpt2.init_params(rng, cfg)
    d, l, m, e = (cfg.hidden_size, cfg.num_layers, cfg.mlp_dim,
                  cfg.num_experts)
    keys = jax.random.split(jax.random.fold_in(rng, 17), 3)
    std = 0.02
    proj_std = std / jnp.sqrt(2.0 * l)
    pd = cfg.param_dtype

    def norm(key, shape, s):
        return (s * jax.random.normal(key, shape)).astype(pd)

    params["blocks"].pop("mlp")
    params["blocks"]["moe"] = {
        "wr": norm(keys[0], (l, d, e), std),
        "wi": norm(keys[1], (l, e, d, m), std),
        "bi": jnp.zeros((l, e, m), pd),
        "wo": norm(keys[2], (l, e, m, d), proj_std),
        "bo": jnp.zeros((l, e, d), pd),
    }
    return params


def capacity(cfg: GPT2MoEConfig, tokens: int) -> int:
    return max(
        1,
        math.ceil(
            cfg.capacity_factor * tokens * cfg.experts_per_token
            / cfg.num_experts
        ),
    )


def moe_mlp(h: jax.Array, mp: Dict[str, jax.Array], cfg,
            return_aux: bool = False):
    """The expert layer: [B, T, D] -> [B, T, D] (residual not included).

    mp holds ONE layer's slice of the stacked moe params (wr [D, E],
    wi [E, D, M], bi [E, M], wo [E, M, D], bo [E, D]) — gpt2.forward's
    lax.scan slices the leading layer axis before calling in here.

    return_aux=True additionally returns this layer's Switch load-balance
    scalar (E * sum_e frac_top1_e * mean_prob_e; 1.0 when perfectly
    balanced) for the training objective — computed from the router probs
    already in hand, so the serving path pays nothing for it.
    """
    b, t, d = h.shape
    s = b * t
    e = cfg.num_experts
    k = cfg.experts_per_token
    c = capacity(cfg, s)
    x = h.reshape(s, d)

    # Router in f32: tiny matmul, and softmax/top-k stability matters.
    logits = jnp.einsum("sd,de->se", x.astype(jnp.float32),
                        mp["wr"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # [S, E]
    top_w, top_i = jax.lax.top_k(probs, k)                   # [S, k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)   # renormalize

    # Position of each (slot, token) within its expert's capacity buffer.
    # Slot-major priority: every token's FIRST choice outranks any token's
    # second choice — the deterministic GShard ordering.
    oh = jax.nn.one_hot(top_i, e, dtype=jnp.int32)           # [S, k, E]
    ohf = oh.transpose(1, 0, 2).reshape(k * s, e)            # slot-major
    pos = jnp.cumsum(ohf, axis=0) - ohf                      # [k*s, E]
    pos = jnp.sum(pos * ohf, axis=-1)                        # [k*s]
    keep = pos < c

    slot_oh = jax.nn.one_hot(pos, c, dtype=jnp.float32)      # [k*s, C]
    disp_f = (
        ohf.astype(jnp.float32)[:, :, None]
        * slot_oh[:, None, :]
        * keep.astype(jnp.float32)[:, None, None]
    ).reshape(k, s, e, c)
    dispatch = jnp.sum(disp_f, axis=0)                       # [S, E, C] 0/1
    w_f = top_w.transpose(1, 0).reshape(k, s, 1, 1)
    combine = jnp.sum(disp_f * w_f, axis=0)                  # [S, E, C]

    dtype = h.dtype
    expert_in = jnp.einsum(
        "sec,sd->ecd", dispatch.astype(dtype), x
    )                                                        # [E, C, D]

    def expert_dense(inp, spec, w):
        """Batched expert matmul; weight-only-int8 pairs {q, s} dequantize
        via the per-out-channel scale AFTER the dot (the int8 operand
        streams at half the bytes, same scheme as common.dense)."""
        if isinstance(w, dict):
            y = jnp.einsum(spec, inp, w["q"].astype(inp.dtype))
            return y * w["s"].astype(y.dtype)[:, None, :]
        return jnp.einsum(spec, inp, w.astype(inp.dtype))

    mid = expert_dense(expert_in, "ecd,edm->ecm", mp["wi"])
    mid = jax.nn.gelu(
        mid + mp["bi"].astype(mid.dtype)[:, None, :], approximate=True
    )
    out = expert_dense(mid, "ecm,emd->ecd", mp["wo"])
    out = out + mp["bo"].astype(out.dtype)[:, None, :]
    y = jnp.einsum("sec,ecd->sd", combine.astype(dtype), out)
    y = y.reshape(b, t, d)
    if not return_aux:
        return y
    frac = jnp.mean(oh[:, 0].astype(jnp.float32), axis=0)  # top-1 share
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))
    return y, aux


def route_sigmoid(x: jax.Array, wr: jax.Array, bias: jax.Array, k: int,
                  norm: bool, scale: float, eps: float = 1e-20):
    """Sigmoid routing (the DeepSeek-V3 line, afmoe): x [S, D] ->
    (experts [S, k] int32, weights [S, k] float32).

    Scores are `sigmoid(x wr)` over ALL experts, in float32 at full
    precision (a TPU's default float32 product is one bfloat16 pass, and
    two experts' scores can differ by less than that rounds). The k experts
    are the top-k of `score + bias` (`bias` None: of the scores): the
    per-expert balancing bias takes part in the CHOICE only, the weights
    are the chosen experts' own scores, over their sum (+ `eps`: a
    family's published epsilon, models/lfm2.py's 1e-6) when `norm`, times
    `scale`.
    """
    logits = jnp.einsum("sd,de->se", x.astype(jnp.float32),
                        wr.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    _, top_i = jax.lax.top_k(choice, k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if norm:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + eps)
    return top_i.astype(jnp.int32), top_w * scale


def grouped_swiglu(x: jax.Array, top_i: jax.Array, top_w: jax.Array,
                   live: jax.Array, wg: jax.Array, wu: jax.Array,
                   wd: jax.Array, first=None, among=None):
    """Routed SwiGLU experts without capacity and without drops:
    x [S, D], top_i / top_w [S, k], live [S] bool -> (y [S, D],
    group sizes [E] int32).

    The S*k picks are sorted by expert and every projection is ONE grouped
    product over the expert stacks (`jax.lax.ragged_dot`: wg, wu [E, D, M],
    wd [E, M, D]; on TPU a grouped-matmul kernel that visits only the
    (row tile, expert) pairs that exist), then weighted and summed back
    per token. The kernel tiles the ROWS too, by the largest power of two
    that divides their count, and an expert it visits multiplies a whole
    tile for the one or two rows that are its own: the products are handed
    `tiled_rows` rows, the sorted picks and rows of no expert behind them,
    so that a tile is 32 rows whatever the pass (a decode row's 16 lanes x
    8 picks = 128 rows were ONE tile; handed 160 they are five), unless
    the pass is long enough for a fair router's groups to outgrow a tile.
    Shapes are static, and each row's product stands alone, so a token's
    output does not depend on what shares its forward pass. A
    token that is not `live` (an idle or parked lane, a pad position)
    contributes no pick: its picks sort behind every group and belong to
    none, so it reaches no expert, and an expert nobody picked has group
    size 0 and is not read. The stacks must be whole buffers: a slice of
    a layer-stacked [L, E, D, M] array into the kernel is a copy of a
    layer's experts per call (models/afmoe.py keeps one leaf a layer).

    `first` (None: the stacks are all the experts there are) says the
    stacks are a SHARE of the layer's experts, `first` .. `first` + E - 1
    of those the router chose among: a chip's experts in an expert-parallel
    deployment. The routing and its weights are over all experts (`top_w`
    comes normalised over a token's k picks); a pick of an absent expert
    is then dropped like a token that is not live, and `y` is this share's
    part of the layer's result, to which the absent experts' chips would
    add theirs. Group sizes are the held experts'.

    `among` (with `first`) is how many experts the router chose among.
    The held picks sort first, so where the share is small the products
    run over a static prefix of the sorted rows alone (`held_rows`), and
    over all of them, under a `lax.cond`, in a pass whose held picks
    outnumber that prefix: a bound on the rows, never a capacity, and no
    pick is dropped. Where the prefix is every row (`first` None, a share
    of a half of the experts and more, or one whose twelve deviations
    cover the pass: a quarter of 32 rows) there is no `cond` and the
    program is the one without `among`.
    """
    def experts(xs, sizes):
        gate = jax.lax.ragged_dot(xs, wg.astype(x.dtype), sizes)
        up = jax.lax.ragged_dot(xs, wu.astype(x.dtype), sizes)
        return jax.lax.ragged_dot(jax.nn.silu(gate) * up,
                                  wd.astype(x.dtype), sizes)

    return _grouped(x, top_i, top_w, live, wg.shape[0], first, among,
                    experts)


def grouped_relu2(x: jax.Array, top_i: jax.Array, top_w: jax.Array,
                  live: jax.Array, wu: jax.Array, wd: jax.Array,
                  first=None, among=None):
    """`grouped_swiglu` for routed experts of TWO projections,
    `W_down relu(W_up x)^2` (wu [E, D, M], wd [E, M, D]; models/
    nemotron_h.py): the same sort, grouped products, `first`, `among` and
    group sizes."""
    def experts(xs, sizes):
        up = jax.lax.ragged_dot(xs, wu.astype(x.dtype), sizes)
        return jax.lax.ragged_dot(jnp.square(jax.nn.relu(up)),
                                  wd.astype(x.dtype), sizes)

    return _grouped(x, top_i, top_w, live, wu.shape[0], first, among,
                    experts)


# How many deviations past the fair router's mean `held_rows` reaches, and
# the multiple of rows its prefix is rounded up to (a bfloat16 tile's
# sublanes).
HELD_ROWS_DEVIATIONS = 12
HELD_ROWS_MULTIPLE = 16


def held_rows(rows: int, held: int, among: int) -> int:
    """How many of a pass's `rows` sorted picks the grouped products of a
    share of `held` of the router's `among` experts run over: what a fair
    router sends to the share and twelve of its deviations more (a pick
    lands on the share with p = held / among: rows p + 12 sqrt(rows p
    (1 - p))), rounded up to the kernel's row multiple, and never more
    than `rows`. At 32 lanes x 8 picks and 12 of 192 held, 64 of 256 rows
    against 16 +- 3.9 held picks; at 16 lanes x 8 and 64 of 256 held, 96
    of 128 against 32 +- 4.9: the deviation falls against the mean as the
    mean grows, which a factor over the mean cannot follow. Past the
    prefix the products run over every row. A share of a half of the
    experts and more runs over every row always: the held picks are half
    of a pass and more before any deviation, and such a share's programs
    are the ones it had (`nemotron3-nano`: nothing to gain over 80 of a
    decode row's 96, PERF.md section 6, PR 48)."""
    if 2 * held >= among:
        return rows
    p = held / among
    fit = math.ceil(rows * p + HELD_ROWS_DEVIATIONS
                    * math.sqrt(rows * p * (1.0 - p)))
    fit = -(-fit // HELD_ROWS_MULTIPLE) * HELD_ROWS_MULTIPLE
    return min(fit, rows)


# The rows of a tile of the grouped products, where `tiled_rows` has its way.
ROW_TILE = 32


def tiled_rows(rows: int, group: float) -> int:
    """How many rows the grouped products are handed for `rows` sorted
    picks: the least count that holds them and whose largest power-of-two
    divisor is `ROW_TILE`, an odd multiple of 32 (128 -> 160, 256 -> 288,
    1,024 -> 1,056, 64 -> 96, 432 -> 480; 96 and 160 stay). The TPU's
    grouped product tiles its rows by that divisor, and every expert it
    visits multiplies a whole tile: a bound on the tile, never a capacity
    (the rows added belong to no expert and are cut off again). `group` is
    a fair router's mean rows an expert in the pass; a pass whose groups
    outgrow the tile is left as it is, since there the kernel reads an
    expert once a tile it spans (2,304 positions x 8 picks over 128
    experts, 144 rows a group: 34.1 ms as they are, 58.3 in tiles of 32;
    PERF.md section 6, PR 51)."""
    if group > ROW_TILE:
        return rows
    return ROW_TILE * (-(-rows // ROW_TILE) | 1)


def _grouped(x, top_i, top_w, live, e: int, first, among, experts):
    """The routed layer around its experts' products (`grouped_swiglu`'s
    contract): `experts(xs, sizes)` takes the picks' rows sorted by expert
    [S*k, D], or a prefix of them that holds every group, with rows of no
    expert behind them up to `tiled_rows`, and the `e` held experts' group
    sizes."""
    s, k = top_i.shape
    here = live[:, None]
    fit, routed = s * k, e
    if first is not None:
        top_i = top_i - first
        here = here & (top_i >= 0) & (top_i < e)
        if among is not None:
            fit, routed = held_rows(s * k, e, among), among
    expert = jnp.where(here, top_i, e).reshape(s * k)
    order = jnp.argsort(expert, stable=True)
    sizes = jnp.zeros((e + 1,), jnp.int32).at[expert].add(1)[:e]

    def over(rows):
        """The products of the first `rows` sorted picks, [S*k, D]."""
        token = order // k if rows == s * k else order[:rows] // k
        more = tiled_rows(rows, s * k / routed) - rows
        if more:    # behind every group: token 0's row, an expert's never
            token = jnp.pad(token, (0, more))
        out = experts(x[token], sizes)
        if more:
            out = out[:rows]
        return out if rows == s * k else jnp.pad(
            out, [(0, s * k - rows), (0, 0)])

    # Every group lies in the first `fit` rows, or all rows are run.
    out = (over(s * k) if fit == s * k else
           jax.lax.cond(jnp.sum(sizes) <= fit, partial(over, fit),
                        partial(over, s * k)))
    # Rows past the last group belong to no expert; whatever the kernel
    # left there is dropped, not scaled by a zero weight.
    w = top_w.reshape(s * k)[order]
    out = jnp.where((expert[order] < e)[:, None],
                    out.astype(jnp.float32) * w[:, None], 0.0)
    y = out[jnp.argsort(order)].reshape(s, k, -1).sum(axis=1)
    return y.astype(x.dtype), sizes


def load_balance_loss(params: Params, cfg: GPT2MoEConfig,
                      hidden: jax.Array, layer: int) -> jax.Array:
    """Switch aux loss for one layer: E * sum_e(frac_tokens_e * mean_prob_e).
    Exposed for training experiments; serving ignores it."""
    mp = jax.tree.map(lambda a: a[layer], params["blocks"]["moe"])
    b, t, d = hidden.shape
    x = hidden.reshape(b * t, d).astype(jnp.float32)
    probs = jax.nn.softmax(x @ mp["wr"].astype(jnp.float32), axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(
        jax.nn.one_hot(top1, cfg.num_experts, dtype=jnp.float32), axis=0
    )
    return cfg.num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))


def forward_with_aux(params: Params, cfg: GPT2MoEConfig,
                     input_ids: jax.Array):
    """Full-sequence forward returning (logits, mean load-balance aux) —
    the training path. ONE trunk: gpt2.forward with its aux side channel
    on (collect_moe_aux), so the training and serving forwards cannot
    drift, and ring attention (cfg.ring_mesh) composes with the aux the
    same way it does for dense training."""
    logits, _, aux = gpt2.forward(
        params, cfg, input_ids, collect_moe_aux=True
    )
    return logits, aux


# The family surface: the trunk IS gpt2.forward (apply_block routes the
# MLP through moe_mlp when the block params carry a `moe` subtree).
forward = gpt2.forward
init_cache = gpt2.init_cache


def params_from_hf(sd, cfg):
    """Load an MoE checkpoint. There is no public HF GPT-2-MoE layout, so
    checkpoints use the NATIVE tree layout with slash-joined key paths
    (written by train.checkpoint.export_model) — rebuilt into the param
    pytree here so `EngineConfig(model="gpt2-moe", checkpoint=...)`
    serves a locally-trained MoE through the standard path."""
    if not any("/" in k for k in sd):
        raise ValueError(
            "MoE checkpoints use the native slash-joined layout (written "
            "by train export); this file looks like an HF state dict, "
            "which has no GPT-2-MoE counterpart"
        )
    tree: Params = {}
    for key, value in sd.items():
        parts = key.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.asarray(value, cfg.param_dtype)
    missing = {"wte", "wpe", "blocks", "lnf"} - set(tree)
    if missing or "moe" not in tree.get("blocks", {}):
        raise ValueError(
            f"native MoE checkpoint is missing {sorted(missing) or ['blocks/moe']}"
        )
    return tree
