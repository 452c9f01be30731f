"""GPT-2 as a pure-functional JAX model (TPU-first rewrite).

Capability parity target: the reference tutoring backend loads HF
`GPT2LMHeadModel` and calls `.generate` through PyTorch
(reference: GUI_RAFT_LLM_SourceCode/tutoring_server.py:10-12, 21-29). Here
the model is a jitted function over a parameter pytree; generation lives in
`engine.generate` (KV-cache decode under `lax.while_loop`), and weights come
from `models.convert.gpt2_params_from_hf` without any torch dependency.

Layout notes (TPU-first):
- All per-layer weights are stacked on a leading layer axis and the trunk is
  one `lax.scan` — O(1) compile time in depth.
- QKV is a single fused [D, 3D] matmul feeding the MXU.
- Attention runs against a static-size KV window (`common.KVCache`) so the
  decode step has fixed shapes for XLA.
- Sequence slots are used for causality (left-padding friendly); learned
  position embeddings are indexed by an explicit per-row `positions` array.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import quant
from ..ops import attention as attention_ops
from .common import (
    KVCache,
    attend,
    attend_quant_layer,
    fold_heads,
    folds_heads,
    causal_window_mask,
    dense,
    layer_norm,
    layer_rows,
    merge_heads,
    quantize_kv,
    split_heads,
    unfold_heads,
    write_scales,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_position_embeddings: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.float32  # compute dtype; bfloat16 on TPU
    param_dtype: Any = jnp.float32
    # int8 KV cache with per-slot scales (common.quantize_kv): halves the
    # HBM bytes every decode step streams for attention. Set by the engine
    # (EngineConfig.kv_quant).
    quant_kv: bool = False
    # Long-context sequence parallelism: a jax.sharding.Mesh with an `sp`
    # axis of size > 1 routes FULL-SEQUENCE attention (cache is None — the
    # training / long-context scoring direction) through
    # parallel.ring.ring_attention, with q/k/v sequence-sharded over `sp`
    # and K/V blocks rotating on ppermute. Exact (online-softmax) causal
    # attention; decode stays on the tp/dp cache path (ring.py scope note).
    # Mesh is hashable, so cfg stays a valid jit static argument.
    ring_mesh: Any = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def mlp_dim(self) -> int:
        return 4 * self.hidden_size

    # Published GPT-2 family sizes (124M/355M/774M/1.5B).
    @classmethod
    def small(cls, **kw) -> "GPT2Config":
        return cls(**kw)

    @classmethod
    def medium(cls, **kw) -> "GPT2Config":
        return cls(hidden_size=1024, num_layers=24, num_heads=16, **kw)

    @classmethod
    def large(cls, **kw) -> "GPT2Config":
        return cls(hidden_size=1280, num_layers=36, num_heads=20, **kw)

    @classmethod
    def xl(cls, **kw) -> "GPT2Config":
        return cls(hidden_size=1600, num_layers=48, num_heads=25, **kw)

    @classmethod
    def tiny(cls, **kw) -> "GPT2Config":
        """Test-size config (fast CPU golden tests vs HF)."""
        kw.setdefault("vocab_size", 384)
        kw.setdefault("max_position_embeddings", 64)
        return cls(hidden_size=32, num_layers=2, num_heads=4, **kw)


def init_params(rng: jax.Array, cfg: GPT2Config) -> Params:
    """Random init matching GPT-2's scheme (normal 0.02, scaled residual proj)."""
    d, l, m = cfg.hidden_size, cfg.num_layers, cfg.mlp_dim
    keys = jax.random.split(rng, 6)
    std = 0.02
    proj_std = std / jnp.sqrt(2.0 * l)
    pd = cfg.param_dtype

    def norm(key, shape, s):
        return (s * jax.random.normal(key, shape)).astype(pd)

    return {
        "wte": norm(keys[0], (cfg.vocab_size, d), std),
        "wpe": norm(keys[1], (cfg.max_position_embeddings, d), std),
        "blocks": {
            "ln1": {"scale": jnp.ones((l, d), pd), "bias": jnp.zeros((l, d), pd)},
            "attn": {
                "wqkv": norm(keys[2], (l, d, 3 * d), std),
                "bqkv": jnp.zeros((l, 3 * d), pd),
                "wo": norm(keys[3], (l, d, d), proj_std),
                "bo": jnp.zeros((l, d), pd),
            },
            "ln2": {"scale": jnp.ones((l, d), pd), "bias": jnp.zeros((l, d), pd)},
            "mlp": {
                "wi": norm(keys[4], (l, d, m), std),
                "bi": jnp.zeros((l, m), pd),
                "wo": norm(keys[5], (l, m, d), proj_std),
                "bo": jnp.zeros((l, d), pd),
            },
        },
        "lnf": {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)},
    }


def init_cache(cfg: GPT2Config, batch: int, max_len: int, dtype=None,
               groups: Optional[int] = None) -> KVCache:
    """`groups`: the head groups a served cache keeps as its axis 2 (the
    engines pass their tp ways, 1 on one chip). Where the int8 planes'
    tile would be padded (`folds_heads`) they are then declared folded,
    `[L, B, G, T, (H/G)*Dh]`. Left out, the planes are `[L, B, H, T, Dh]`
    whatever the tile, and `forward` folds them on its way in."""
    fold = groups and folds_heads(cfg.head_dim, cfg.quant_kv)
    return KVCache.create(
        cfg.num_layers, batch, cfg.num_heads, max_len, cfg.head_dim,
        dtype or cfg.dtype, quantized=cfg.quant_kv,
        groups=groups if fold else None,
    )


def apply_block(x, lp, attend_fn, cfg: GPT2Config, collect_aux: bool = False):
    """One transformer block; `attend_fn(q, k_new, v_new) -> context` owns
    cache handling + attention so every path (dense, ring, cached decode,
    pipeline stage) shares one copy of the math. Blocks whose params carry
    a `moe` subtree instead of `mlp` route the feed-forward through the
    expert layer (models/moe.py) — same trunk, cache, and decode paths.

    collect_aux=True returns (x, aux) where aux is the block's MoE
    load-balance scalar (0 for dense blocks) — the training objective's
    side channel."""
    eps = cfg.layer_norm_eps
    h = layer_norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"], eps)
    qkv = dense(h, lp["attn"]["wqkv"], lp["attn"]["bqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    a = attend_fn(
        split_heads(q, cfg.num_heads),
        split_heads(k, cfg.num_heads),
        split_heads(v, cfg.num_heads),
    )
    x = x + dense(merge_heads(a), lp["attn"]["wo"], lp["attn"]["bo"])
    h2 = layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"], eps)
    if "moe" in lp:
        from . import moe as moe_lib

        if collect_aux:
            y, aux = moe_lib.moe_mlp(h2, lp["moe"], cfg, return_aux=True)
            return x + y, aux
        return x + moe_lib.moe_mlp(h2, lp["moe"], cfg)
    m = dense(h2, lp["mlp"]["wi"], lp["mlp"]["bi"])
    m = jax.nn.gelu(m, approximate=True)  # GPT-2 uses the tanh approximation
    x = x + dense(m, lp["mlp"]["wo"], lp["mlp"]["bo"])
    if collect_aux:
        return x, jnp.zeros((), jnp.float32)
    return x


def trunk_layer(lp, h, *, cfg: GPT2Config):
    """One block in full-sequence causal mode: the `layer_fn(lp, h) -> h`
    shape `parallel.pipeline.pipeline_trunk` consumes. The causal mask is
    rebuilt from h's shape so the function closes over nothing traced
    (shard_map stage bodies take all operands as arguments)."""
    t = h.shape[1]
    pos = jnp.arange(t)
    mask = (pos[None, :] <= pos[:, None])[None, None]
    return apply_block(h, lp, lambda q, k, v: attend(q, k, v, mask), cfg)


def forward_pipelined(
    params: Params,
    cfg: GPT2Config,
    input_ids: jax.Array,
    mesh,
    *,
    n_micro: int,
    batch_spec=None,
    remat: bool = False,
) -> jax.Array:
    """Full-sequence forward with the stacked trunk sharded over the mesh's
    `pp` axis (parallel.pipeline.pipeline_trunk, GPipe microbatching).

    Embedding, final layer norm, and the tied unembedding run under jit's
    ordinary sharding; the L blocks run as pp pipeline stages, each device
    holding L/pp layers. Returns logits identical (up to float error) to
    `forward(params, cfg, input_ids)[0]` — parity-tested. `batch_spec`
    forwards to pipeline_trunk for dp composition of the microbatched
    activations.
    """
    from ..parallel.pipeline import pipeline_trunk

    if mesh.shape.get("tp", 1) > 1:
        raise ValueError(
            "forward_pipelined does not compose with tp (the pipeline "
            "stage body has no tensor-parallel collectives); use pp x dp"
        )
    _, t = input_ids.shape
    positions = jnp.arange(t, dtype=jnp.int32)[None, :]
    x = quant.embed_lookup(params["wte"], input_ids) + params["wpe"][positions]
    x = x.astype(cfg.dtype)
    layer_fn = lambda lp, h: trunk_layer(lp, h, cfg=cfg)  # noqa: E731
    if remat:
        # Recompute each stage layer's activations in the backward pass —
        # the pipeline holds every microbatch's activations live through
        # its fori_loop, so remat matters MORE here than in the scan trunk.
        layer_fn = jax.checkpoint(layer_fn)
    x = pipeline_trunk(
        layer_fn,
        params["blocks"],
        x,
        mesh,
        n_micro=n_micro,
        batch_spec=batch_spec,
    )
    x = layer_norm(x, params["lnf"]["scale"], params["lnf"]["bias"],
                   cfg.layer_norm_eps)
    return quant.unembed(x, params["wte"])


def forward(
    params: Params,
    cfg: GPT2Config,
    input_ids: jax.Array,
    cache: Optional[KVCache] = None,
    positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    collect_moe_aux: bool = False,
    rows: Optional[jax.Array] = None,
):
    """Run the transformer; returns (logits [B, T, V] float32, updated cache).

    cache      — None for full-sequence (training / golden) mode; a KVCache
                 for incremental prefill/decode. New keys are written at slot
                 offset `cache.length`, which is a scalar (whole-batch
                 offset, engine.generate) or per-row [B] (ragged slots,
                 engine.paged — T must be 1 in that mode). PRECONDITION:
                 callers must ensure `cache.length + T <= max_len` and
                 positions stay below `max_position_embeddings` — JAX clamps
                 out-of-bounds dynamic_update_slice/gather indices silently,
                 which would corrupt the newest KV slots instead of raising.
                 The engine enforces this (generate caps max_new_tokens).
    positions  — [B, T] indices into the learned position table. Defaults to
                 slot indices (contiguous, no padding). The engine passes
                 per-row positions when prompts are left-padded.
    kv_mask    — [B, num_keys] validity of each key slot (False = padding).
    rows       — ragged slots only: [B] int32, the cache row batch element i
                 addresses, for a batch narrower than the cache (the paged
                 engine's prefill chunk: one staged slot of the live
                 multi-slot cache). Its new keys are scattered into those
                 rows in place, it attends over those rows alone, every
                 other row comes back untouched, and `cache.length` is
                 per batch element, [B]. Default: row i for element i.
    collect_moe_aux — full-sequence (cache=None) mode only: additionally
                 return the mean per-layer MoE load-balance scalar
                 (models/moe.py; 0 for dense blocks) as a third element —
                 the training objective's side channel. Composes with
                 ring attention (the aux rides the scan carry either way).
    """
    b, t = input_ids.shape
    eps = cfg.layer_norm_eps
    num_heads = cfg.num_heads
    default_positions = positions is None

    offset = jnp.zeros((), jnp.int32) if cache is None else cache.length
    off_row = offset[:, None] if offset.ndim else offset[None, None]
    q_slots = off_row + jnp.arange(t, dtype=jnp.int32)[None, :]
    q_slots = jnp.broadcast_to(q_slots, (b, t))
    if positions is None:
        positions = q_slots

    x = quant.embed_lookup(params["wte"], input_ids) + params["wpe"][positions]
    x = x.astype(cfg.dtype)

    num_keys = t if cache is None else cache.k.shape[3]
    mask = causal_window_mask(q_slots, num_keys)  # [B, 1, T, num_keys]
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, None, :]

    def block(x, layer_params, attend_fn):
        return apply_block(x, layer_params, attend_fn, cfg)

    if cache is None:
        ring = (
            cfg.ring_mesh is not None
            and cfg.ring_mesh.shape.get("sp", 1) > 1
        )
        if ring:
            # Ring attention computes exact CAUSAL attention from absolute
            # block offsets; padding masks / custom position tables are the
            # cache path's business.
            if kv_mask is not None or not default_positions:
                raise ValueError(
                    "ring attention (cfg.ring_mesh) supports full causal "
                    "sequences only: no kv_mask, default positions"
                )
            from ..parallel.ring import ring_attention

            attend_full = lambda q, k, v: ring_attention(  # noqa: E731
                q, k, v, cfg.ring_mesh
            )
        else:
            attend_full = lambda q, k, v: attend(q, k, v, mask)  # noqa: E731

        if collect_moe_aux:

            def body_aux(carry, lp):
                h, aux = carry
                y, a = apply_block(h, lp, attend_full, cfg,
                                   collect_aux=True)
                return (y, aux + a), None

            (x, moe_aux), _ = jax.lax.scan(
                body_aux, (x, jnp.zeros((), jnp.float32)), params["blocks"]
            )
        else:

            def body(carry, lp):
                return block(carry, lp, attend_full), None

            x, _ = jax.lax.scan(body, x, params["blocks"])
        new_cache = None
    else:
        if collect_moe_aux:
            raise ValueError(
                "collect_moe_aux is a full-sequence (training) channel; "
                "the cached decode path does not accumulate it"
            )
        # The stacked cache rides the scan CARRY (updated in place per layer
        # via dynamic_update_slice at the layer index), not the scan xs/ys.
        # Threading it through xs/ys makes XLA re-stack — i.e. copy — the
        # whole cache every step, which measured ~2× the entire decode-step
        # roofline on a v5e; as carry the update aliases and the decode step
        # drops from ~1.23 ms to ~0.66 ms (batch 8, GPT-2-small).
        zero = jnp.zeros((), jnp.int32)
        if rows is not None and offset.ndim != 1:
            raise ValueError(
                "rows names the cache rows of a ragged batch (per-row "
                "cache.length)"
            )
        quant_kv = cfg.quant_kv
        # Int8 planes whose tile would be padded ride the scan folded
        # (common.folds_heads). A served cache arrives that way
        # (`init_cache`'s `groups`); one declared `[L, B, H, T, Dh]` is
        # folded here and handed back as it came.
        k0, v0 = cache.k, cache.v
        folded = folds_heads(cfg.head_dim, quant_kv)
        fold_here = folded and k0.shape[2:] == (num_heads, num_keys,
                                                cfg.head_dim)
        if fold_here:
            k0, v0 = fold_heads(k0, 1), fold_heads(v0, 1)
        # What each row's mask lets it see, once a pass: the decode
        # kernel reads a lane's planes that far (`attend_quant_layer`).
        lengths = attention_ops.mask_lengths(mask) if quant_kv else None

        def body(carry, xs):
            x, ck, cv, cks, cvs = carry
            lp, layer = xs
            updated = {}

            def attend_fn(q, k_new, v_new):
                if quant_kv:
                    k_w, k_s = quantize_kv(k_new)
                    v_w, v_s = quantize_kv(v_new)
                else:
                    k_w, v_w = k_new.astype(ck.dtype), v_new.astype(cv.dtype)
                if folded:  # a token's heads as one row a group
                    k_w = fold_heads(k_w, ck.shape[2])
                    v_w = fold_heads(v_w, cv.shape[2])
                cks2, cvs2 = cks, cvs
                if offset.ndim == 1:
                    # Ragged slots: scatter each row's T new tokens at its
                    # own offset (T=1 for paged decode; T=k+1 for the
                    # speculative verify window — engine.spec). Advanced
                    # indices [B,1] rows × [B,T] slots land in front, so
                    # values go [B, T, H, Dh] ([B, T, G, (H/G)*Dh] folded).
                    at_rows = (jnp.arange(k_new.shape[0]) if rows is None
                               else rows)[:, None]
                    slots = offset[:, None] + jnp.arange(t)[None, :]
                    ck2 = ck.at[layer, at_rows, :, slots, :].set(
                        k_w.transpose(0, 2, 1, 3)
                    )
                    cv2 = cv.at[layer, at_rows, :, slots, :].set(
                        v_w.transpose(0, 2, 1, 3)
                    )
                    if quant_kv:
                        cks2 = write_scales(cks, layer, rows, slots, k_s)
                        cvs2 = write_scales(cvs, layer, rows, slots, v_s)
                else:
                    start = (layer, zero, zero, offset, zero)
                    ck2 = jax.lax.dynamic_update_slice(ck, k_w[None], start)
                    cv2 = jax.lax.dynamic_update_slice(cv, v_w[None], start)
                    if quant_kv:
                        s_start = (layer, zero, zero, offset)
                        cks2 = jax.lax.dynamic_update_slice(
                            cks, k_s[None], s_start
                        )
                        cvs2 = jax.lax.dynamic_update_slice(
                            cvs, v_s[None], s_start
                        )
                updated.update(k=ck2, v=cv2, ks=cks2, vs=cvs2)
                if quant_kv:
                    return attend_quant_layer(
                        q, ck2, cks2, cv2, cvs2, layer, rows, mask, lengths)
                k_att = layer_rows(ck2, layer, rows)
                v_att = layer_rows(cv2, layer, rows)
                return attend(
                    q, k_att.astype(q.dtype), v_att.astype(q.dtype), mask
                )

            y = block(x, lp, attend_fn)
            return (y, updated["k"], updated["v"], updated["ks"],
                    updated["vs"]), None

        layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, new_k, new_v, new_ks, new_vs), _ = jax.lax.scan(
            body, (x, k0, v0, cache.ks, cache.vs),
            (params["blocks"], layers),
        )
        if fold_here:
            new_k = unfold_heads(new_k, num_heads, cfg.head_dim)
            new_v = unfold_heads(new_v, num_heads, cfg.head_dim)
        new_cache = KVCache(k=new_k, v=new_v, length=cache.length + t,
                            ks=new_ks, vs=new_vs)

    x = layer_norm(x, params["lnf"]["scale"], params["lnf"]["bias"], eps)
    # Tied unembedding (reference model ties lm_head to wte); f32 accumulation
    # so sampling sees full-precision logits even in bfloat16 compute.
    logits = quant.unembed(x, params["wte"])
    if collect_moe_aux:
        return logits, new_cache, moe_aux / cfg.num_layers
    return logits, new_cache
