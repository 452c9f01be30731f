"""Partition rules: tree-path regex -> PartitionSpec.

Parameters are plain nested dicts, so sharding assignment is a pure function
of the flattened key path — the idiomatic JAX pattern for 1D/2D weight
sharding (cf. public fmengine/EasyLM-style `match_partition_rules`; pattern
reimplemented here for our stacked-layer layout).

Weight layout reminders (models/gpt2.py, models/bert.py, models/llama.py):
per-layer tensors carry a leading layer axis L, linears are [in, out].
Megatron-style TP: column-parallel QKV/FFN-in (shard the out dim),
row-parallel attn-out/FFN-out (shard the in dim) — one psum per block pair,
inserted automatically by XLA from these specs.
"""

from __future__ import annotations

import logging
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

log = logging.getLogger(__name__)

P = PartitionSpec

Rules = Sequence[Tuple[str, PartitionSpec]]

# GPT-2 family (stacked blocks; layer axis first, replicated).
#
# Weight-only int8 (models/quant.py) replaces a dense leaf `X` with the pair
# `X/q` (int8, same shape) and `X/s` (f32 scales): `q` shards exactly like
# the dense leaf; `s` is the leaf's shape minus the contracted `in` axis
# (per-out-channel scales) — so column-parallel leaves shard their scales
# over tp and row-parallel leaves replicate them (the scale applies after
# the tp psum). Embedding tables scale per ROW (quantize_embedding), so
# their `s` is [V], vocab-sharded like `q`'s leading axis.
#
# Spelling: trailing Nones are dropped everywhere (P() not P(None, None),
# P(None, "tp") not P(None, "tp", None)) — PartitionSpec pads with None,
# and one canonical spelling per layout keeps spelling-keyed jit caches
# from silently recompiling (the canonical-pspec lint rule enforces this;
# see engine/paged._plane_spec for the incident).
GPT2_RULES: List[Tuple[str, PartitionSpec]] = [
    (r"wte(/q)?$", P("tp")),       # vocab-sharded embedding
    (r"wte/s$", P("tp")),
    (r"wpe$", P()),
    (r"blocks/attn/wqkv(/q)?$", P(None, None, "tp")),   # column parallel
    (r"blocks/attn/wqkv/s$", P(None, "tp")),
    (r"blocks/attn/bqkv$", P(None, "tp")),
    (r"blocks/attn/wo(/q)?$", P(None, "tp")),     # row parallel
    (r"blocks/attn/wo/s$", P()),
    (r"blocks/attn/bo$", P()),
    (r"blocks/mlp/wi(/q)?$", P(None, None, "tp")),
    (r"blocks/mlp/wi/s$", P(None, "tp")),
    (r"blocks/mlp/bi$", P(None, "tp")),
    (r"blocks/mlp/wo(/q)?$", P(None, "tp")),
    (r"blocks/mlp/wo/s$", P()),
    (r"blocks/mlp/bo$", P()),
    (r"ln|lnf", P()),                    # norms replicated
    (r".*", P()),
]

# Llama family: Megatron TP like GPT-2; q/k/v/gate/up column-parallel,
# o/down row-parallel; untied vocab-sharded embed + lm_head.
LLAMA_RULES: List[Tuple[str, PartitionSpec]] = [
    (r"embed(/q)?$", P("tp")),
    (r"embed/s$", P("tp")),
    (r"lm_head(/q)?$", P("tp")),
    (r"lm_head/s$", P("tp")),
    (r"blocks/attn/w[qkv](/q)?$", P(None, None, "tp")),
    (r"blocks/attn/w[qkv]/s$", P(None, "tp")),
    (r"blocks/attn/wo(/q)?$", P(None, "tp")),
    (r"blocks/attn/wo/s$", P()),
    (r"blocks/mlp/w[gu](/q)?$", P(None, None, "tp")),
    (r"blocks/mlp/w[gu]/s$", P(None, "tp")),
    (r"blocks/mlp/wd(/q)?$", P(None, "tp")),
    (r"blocks/mlp/wd/s$", P()),
    (r"ln|lnf", P()),
    (r".*", P()),
]

BERT_RULES: List[Tuple[str, PartitionSpec]] = [
    (r"embeddings/word(/q)?$", P("tp")),
    (r"embeddings/word/s$", P("tp")),
    (r"embeddings/(position|token_type)$", P()),
    (r"blocks/attn/wqkv(/q)?$", P(None, None, "tp")),
    (r"blocks/attn/wqkv/s$", P(None, "tp")),
    (r"blocks/attn/bqkv$", P(None, "tp")),
    (r"blocks/attn/wo(/q)?$", P(None, "tp")),
    (r"blocks/attn/wo/s$", P()),
    (r"blocks/mlp/wi(/q)?$", P(None, None, "tp")),
    (r"blocks/mlp/wi/s$", P(None, "tp")),
    (r"blocks/mlp/wo(/q)?$", P(None, "tp")),
    (r"blocks/mlp/wo/s$", P()),
    (r".*", P()),
]

# GPT-2-MoE (models/moe.py): the dense trunk shards like GPT-2; the
# expert stacks [L, E, D, M] shard their EXPERT axis over `ep` — under jit
# the dispatch/combine einsums against ep-sharded weights make XLA place
# each expert's FFN on its shard and insert the all-to-alls, exactly as
# the tp specs imply the Megatron psums. The tiny router is replicated.
MOE_RULES: List[Tuple[str, PartitionSpec]] = [
    (r"blocks/moe/wr$", P()),
    (r"blocks/moe/w[io](/q)?$", P(None, "ep")),
    (r"blocks/moe/w[io]/s$", P(None, "ep")),
    (r"blocks/moe/b[io]$", P(None, "ep")),
] + GPT2_RULES

# afmoe (models/afmoe.py): a list of per-layer trees, no leading layer
# axis. Attention and the dense and shared SwiGLUs shard like Llama's
# (q/k/v/gate/up column-parallel, o/down row-parallel). The routed
# experts [E, D, M], the router, its bias and the norms are replicated:
# the grouped product takes whole stacks (a chip's share of a layer's
# experts is a configuration's `experts_held`, models/axk1.py; spreading
# one layer's stacks over `ep` with the exchange between the shares is
# ROADMAP S4, and the engines refuse ep > 1 for this family). At tp = 1
# every spec degrades to replication.
AFMOE_RULES: List[Tuple[str, PartitionSpec]] = [
    (r"embed$", P("tp")),
    (r"lm_head$", P("tp")),
    (r"layers/\d+/attn/w[qkvg]$", P(None, "tp")),
    (r"layers/\d+/attn/wo$", P("tp")),
    (r"layers/\d+/(mlp|moe/shared)/w[gu]$", P(None, "tp")),
    (r"layers/\d+/(mlp|moe/shared)/wd$", P("tp")),
    (r".*", P()),
]

# axk1 (models/axk1.py): afmoe's tree with latent attention (models/mla.py)
# and, in the expert stacks, the share of a layer's experts this process
# holds. Everything is replicated: the engines refuse tp > 1 (the latent
# cache has no heads axis to shard) and ep > 1 (the share is the
# configuration's, `experts_held`; the exchange between shares is not
# built) for this family.
AXK1_RULES: List[Tuple[str, PartitionSpec]] = [
    (r".*", P()),
]

# nemotron_h (models/nemotron_h.py): one mixer a block. Everything is
# replicated: the engines refuse tp > 1 (the Mamba blocks' state is
# [Lm, S, H, P, N] and sharding its heads, with the mixer's projections
# column- and row-parallel beside them, is not built) and ep > 1 (the share
# of a layer's experts is the configuration's, as axk1's).
NEMOTRON_H_RULES: List[Tuple[str, PartitionSpec]] = [
    (r".*", P()),
]

# kimi_linear (models/kimi_linear.py): everything replicated, as axk1 and
# nemotron_h and for both their reasons: the latent plane has no heads axis
# and the KDA layers' matrix state [Lk, S, H, K, V] would want its heads
# sharded beside the mixer's projections, which is not built (the engines
# refuse tp > 1 twice over); the share of a layer's experts is the
# configuration's, so ep > 1 is refused too.
KIMI_LINEAR_RULES: List[Tuple[str, PartitionSpec]] = [
    (r".*", P()),
]

# minicpm_sala (models/minicpm_sala.py): everything replicated, as the
# other families with a recurrent state: the Lightning layers' matrix state
# [Ll, S, H, K, V] would want its heads sharded beside the mixer's
# projections, which is not built (the engines refuse tp > 1), and the
# sparse layers have two key heads.
MINICPM_SALA_RULES: List[Tuple[str, PartitionSpec]] = [
    (r".*", P()),
]

# lfm2_moe (models/lfm2.py): everything replicated, as the four other
# routed families' stacks: the engines refuse tp > 1 for a family with a
# recurrent state (the conv layers' windows [Lc, S, K-1, D] have no heads
# axis; sharding their channels beside the operator's projections is not
# built) and ep > 1 (a share of a layer's experts is the configuration's,
# `experts_held`; the exchange between shares is not built).
LFM2_RULES: List[Tuple[str, PartitionSpec]] = [
    (r".*", P()),
]

# Rule set per model-family name (models/registry.py ModelFamily.name).
# (The bucketed engine's KV-cache sharding — [L, B, Hkv, T, Dh]: batch
# over dp, heads over tp — is derived by jit's sharding propagation from
# the param/batch specs. The PAGED engine's slot-state planes are the
# exception: they cross program boundaries as explicit host-held arrays,
# so their shardings are pinned by the plane table below instead of
# re-derived per program.)
RULES_FOR = {
    "gpt2": GPT2_RULES,
    "llama": LLAMA_RULES,
    "bert": BERT_RULES,
    "gpt2_moe": MOE_RULES,
    "afmoe": AFMOE_RULES,
    "axk1": AXK1_RULES,
    "nemotron_h": NEMOTRON_H_RULES,
    "kimi_linear": KIMI_LINEAR_RULES,
    "minicpm_sala": MINICPM_SALA_RULES,
    "lfm2_moe": LFM2_RULES,
}

# ---------------------------------------------- paged state plane table
#
# The paged engine's per-plane sharding policy, keyed by PLANE NAME (the
# attribute chain past the state/cache root — the same key
# `analysis/absint.collect_plane_puts` derives from producer call sites,
# so the `pspec-flow` lint rule can check every producer against this
# table). ONE semantic sharding per named plane across all producers:
# `_init_state`'s birth puts, `_canon_state`'s dispatch-boundary
# respells and the prefix-cache block
# canonicalization all resolve specs HERE and nowhere else.
#
# KV planes shard their heads axis over tp: slot cache k/v are
# [L, S, Hkv, T, Dh] and the int8-KV scale planes ks/vs are
# [L, S, Hkv, T] — heads is axis 2 in both (a family that folds its heads
# into the feature axis keeps tp head GROUPS there, [L, S, tp, T, F]:
# models/common.py `fold_heads`) — so one spec spelling,
# P(None, None, "tp"), serves the pair; the prefix tree's immutable
# KVBlock runs ([L, 1, H, B, Dh] / [L, 1, H, B]) share the layout and
# the spec, making a radix hit splice tp-sharded blocks without a
# gather. Host-state planes (positions, masks, transcripts, staged
# cursors, rng keys) are genuinely replicated and keep the canonical
# `P()` spelling — the PR-2 recompile incident's fix, now per plane.
# MoE expert planes are PARAMS (MOE_RULES shards their expert axis over
# ep above); no slot-state plane carries an expert axis, so `ep` does
# not appear here — state planes replicate over ep exactly like dp.
#
# On a tp=1 mesh P(None, None, "tp") degrades to replication (the
# shard_tree doctrine: axes of size 1 are harmless), so one table
# serves every mesh.
PAGED_PLANE_SPECS: Dict[str, PartitionSpec] = {
    # SlotState.cache planes (engine/paged.SlotState).
    "cache.k": P(None, None, "tp"),
    "cache.v": P(None, None, "tp"),
    "cache.ks": P(None, None, "tp"),
    "cache.vs": P(None, None, "tp"),
    "cache.length": P(),
    # A selecting family's pooled keys (models/minicpm_sala.py: pool
    # [La, S, Hkv, NP, Dh]): heads on axis 2 as the keys they summarise.
    "cache.pool": P(None, None, "tp"),
    # A recurrent family's state planes (models/mamba2.py: ssm
    # [Lm, S, H, P, N], conv [Lm, S, K-1, C]; no positions axis) and the
    # per-slot snapshot planes the prefill fills (engine/paged.SlotState).
    # `ssm` has its heads on axis 2 as the KV planes have theirs and takes
    # their spelling (it is the one the programs' outputs propagate, and
    # the axis `tp` would shard; the engines refuse tp > 1 for such a
    # family today, so it is replication). `conv` is replicated.
    "cache.ssm": P(None, None, "tp"),
    "cache.conv": P(),
    "snap_ssm": P(None, None, "tp"),
    "snap_conv": P(),
    "snap_at": P(),
    # Bare KVCache / prefix KVBlock planes (single-slot prefill caches
    # and the radix tree's block runs share the heads-at-axis-2 layout).
    "k": P(None, None, "tp"),
    "v": P(None, None, "tp"),
    "ks": P(None, None, "tp"),
    "vs": P(None, None, "tp"),
    "pool": P(None, None, "tp"),
    "length": P(),
    "ssm": P(None, None, "tp"),
    "conv": P(),
    # Host-state planes: replicated, canonical spelling.
    "tok": P(),
    "active": P(),
    "seen": P(),
    "transcript": P(),
    "staged": P(),
    "stage_cursor": P(),
    "stage_len": P(),
    "stage_seq": P(),
    "stage_rng": P(),
}


def supported_tp(num_kv_heads: int) -> List[int]:
    """The tp ways that shard `num_kv_heads` KV heads evenly: the
    ascending divisors. The paged plane table splits the heads axis
    across tp shards, so any other way would leave ragged head shards
    (gpt2-large's 20 heads admit [1, 2, 4, 5, 10, 20] — not 8)."""
    return [d for d in range(1, num_kv_heads + 1) if num_kv_heads % d == 0]


def validate_tp_heads(num_kv_heads: int, tp: int, model: str) -> None:
    """Reject a tp that does not divide the KV head count — loudly, with
    the exact supported divisors, instead of padding heads (a padded
    head's KV would cost real HBM and attention bandwidth on every
    shard, the resource tp exists to split)."""
    if tp > 1 and num_kv_heads % tp:
        raise ValueError(
            f"tp={tp} does not divide {model!r}'s {num_kv_heads} KV "
            f"heads; the paged KV planes shard the heads axis evenly — "
            f"supported tp ways for this model: "
            f"{supported_tp(num_kv_heads)}"
        )


def tree_paths(tree: Any) -> List[str]:
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(_key_str(k) for k in kp) for kp, _ in paths]


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def _fit_spec(spec: PartitionSpec, shape: Tuple[int, ...], mesh: Mesh,
              path: str) -> PartitionSpec:
    """`spec` with every axis that does not split `shape` evenly on `mesh`
    replicated instead (canonical spelling: trailing Nones dropped).

    The published GPT-2 vocab (50,257 = 29 x 1733) has no even split over
    any tp the head counts admit, so a vocab-sharded embedding cannot be
    placed at real width; replicating that one table (its lookup and the
    logits matmul then run whole on every shard) keeps the rest of the
    model sharded, where refusing would put gpt2 at tp > 1 out of reach.
    """
    fitted = []
    for dim, axes in zip(shape, tuple(spec)):
        names = (axes,) if isinstance(axes, str) else tuple(axes or ())
        ways = 1
        for name in names:
            ways *= mesh.shape.get(name, 1)
        fitted.append(axes if dim % ways == 0 else None)
    while fitted and fitted[-1] is None:
        fitted.pop()
    if tuple(fitted) != tuple(spec):
        log.warning("%s %s does not split evenly as %s on mesh %s: "
                    "placed as %s", path, shape, spec, dict(mesh.shape),
                    P(*fitted))
    return P(*fitted)


def match_partition_rules(rules: Rules, tree: Any,
                          mesh: Optional[Mesh] = None) -> Any:
    """Return a pytree of PartitionSpec matching `tree`'s structure. With
    `mesh`, a rule's spec is fitted to each leaf's shape (`_fit_spec`)."""

    def spec_for(path: str, leaf) -> PartitionSpec:
        if getattr(leaf, "ndim", 0) == 0:
            return P()
        for pattern, spec in rules:
            if re.search(pattern, path):
                if mesh is None:
                    return spec
                return _fit_spec(spec, leaf.shape, mesh, path)
        raise ValueError(f"no partition rule matched {path!r}")

    paths_and_leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = [
        spec_for("/".join(_key_str(k) for k in kp), leaf)
        for kp, leaf in paths_and_leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


def shard_tree(tree: Any, mesh: Mesh, rules: Rules) -> Any:
    """Device-put a pytree with NamedShardings derived from the rules.

    Specs naming axes of size 1 are harmless; on a single-device mesh this
    degrades to replication, so the same code path runs on 1 chip or 256.
    """
    specs = match_partition_rules(rules, tree, mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )


def shardings_for(tree: Any, mesh: Mesh, rules: Rules) -> Any:
    """Pytree of NamedSharding (for jit in_shardings/out_shardings)."""
    specs = match_partition_rules(rules, tree, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))
