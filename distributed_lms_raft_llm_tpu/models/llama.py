"""Llama-family decoder (RoPE + RMSNorm + GQA + SwiGLU) in pure-functional JAX.

Extends the serving model zoo beyond the reference's GPT-2 (reference:
GUI_RAFT_LLM_SourceCode/tutoring_server.py:10-12) to the Llama architecture
(BASELINE.json config 5: Llama-3-8B tp-sharded). Same conventions as
gpt2.py: per-layer weights stacked on a leading layer axis, linears
[in, out], a single `lax.scan` trunk, and the KV cache carried through the
scan CARRY (see gpt2.py for why xs/ys threading is ~2× slower on TPU).

Llama-specific:
- RMSNorm (no biases anywhere in the network);
- rotary position embeddings applied to q/k at their absolute positions —
  HF's rotate_half convention so converted checkpoints are bit-compatible;
- grouped-query attention: num_kv_heads ≤ num_heads KV heads, broadcast to
  the query heads at attention time (`common.repeat_kv`), which divides KV
  cache HBM traffic by the group size — the decode bottleneck at scale;
- SwiGLU MLP (gate ⊙ silu(up) — HF order: down(silu(gate) * up));
- untied lm_head (HF `tie_word_embeddings=False` default).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import quant
from .common import (
    KVCache,
    attend,
    attend_quant,
    causal_window_mask,
    dense,
    layer_rows,
    merge_heads,
    quantize_kv,
    repeat_kv,
    rms_norm,
    split_heads,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    max_position_embeddings: int = 8192
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 14336
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # int8 KV cache with per-slot scales (common.quantize_kv); same
    # contract as GPT2Config.quant_kv.
    quant_kv: bool = False
    # Mesh with an `sp` axis > 1: full-sequence attention runs as ring
    # attention, sequence-sharded (same contract as GPT2Config.ring_mesh).
    ring_mesh: Any = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test-size config (fast CPU golden tests vs HF)."""
        kw.setdefault("vocab_size", 384)
        kw.setdefault("max_position_embeddings", 64)
        kw.setdefault("rope_theta", 10000.0)
        return cls(
            hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
            intermediate_size=64, **kw,
        )


def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    d, l, m = cfg.hidden_size, cfg.num_layers, cfg.intermediate_size
    kvd = cfg.num_kv_heads * cfg.head_dim
    keys = jax.random.split(rng, 9)
    std = 0.02
    pd = cfg.param_dtype

    def norm(key, shape):
        return (std * jax.random.normal(key, shape)).astype(pd)

    return {
        "embed": norm(keys[0], (cfg.vocab_size, d)),
        "blocks": {
            "ln1": {"scale": jnp.ones((l, d), pd)},
            "attn": {
                "wq": norm(keys[1], (l, d, d)),
                "wk": norm(keys[2], (l, d, kvd)),
                "wv": norm(keys[3], (l, d, kvd)),
                "wo": norm(keys[4], (l, d, d)),
            },
            "ln2": {"scale": jnp.ones((l, d), pd)},
            "mlp": {
                "wg": norm(keys[5], (l, d, m)),
                "wu": norm(keys[6], (l, d, m)),
                "wd": norm(keys[7], (l, m, d)),
            },
        },
        "lnf": {"scale": jnp.ones((d,), pd)},
        "lm_head": norm(keys[8], (cfg.vocab_size, d)),
    }


def init_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=None,
               groups=None) -> KVCache:
    # `groups` (models/registry.py): llama's planes are not folded.
    return KVCache.create(
        cfg.num_layers, batch, cfg.num_kv_heads, max_len, cfg.head_dim,
        dtype or cfg.dtype, quantized=cfg.quant_kv,
    )


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq=None, table_scale: float = 1.0) -> jax.Array:
    """Rotary embedding, HF rotate_half convention.

    x: [B, H, T, Dh]; positions: [B, T] absolute positions. `inv_freq`
    [Dh/2] replaces the base's own frequencies (a scaled rotary embedding:
    models/mla.py `yarn_inv_freq`), and `table_scale` multiplies cos and
    sin where the scaling asks for it.
    """
    dh = x.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    freqs = positions[:, None, :, None].astype(jnp.float32) * inv_freq  # [B,1,T,Dh/2]
    cos = jnp.concatenate([jnp.cos(freqs)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(freqs)] * 2, axis=-1)
    if table_scale != 1.0:
        cos, sin = cos * table_scale, sin * table_scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * cos + rotated * sin).astype(x.dtype)


def forward(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jax.Array,
    cache: Optional[KVCache] = None,
    positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    rows: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Run the decoder; returns (logits [B, T, V] float32, updated cache).

    Same contract as gpt2.forward (shared by engine.generate): positions are
    absolute (drive RoPE and nothing else — there is no position table),
    cache slots are written at offset `cache.length`, `kv_mask` marks valid
    key slots, `rows` names the cache rows a ragged batch addresses. Same
    overflow precondition as gpt2.forward applies.
    """
    b, t = input_ids.shape
    eps = cfg.rms_norm_eps
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    groups = nh // nkv
    default_positions = positions is None

    offset = jnp.zeros((), jnp.int32) if cache is None else cache.length
    off_row = offset[:, None] if offset.ndim else offset[None, None]
    q_slots = off_row + jnp.arange(t, dtype=jnp.int32)[None, :]
    q_slots = jnp.broadcast_to(q_slots, (b, t))
    if positions is None:
        positions = q_slots

    x = quant.embed_lookup(params["embed"], input_ids).astype(cfg.dtype)

    num_keys = t if cache is None else cache.k.shape[3]
    mask = causal_window_mask(q_slots, num_keys)
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, None, :]

    def block(x, lp, attend_fn):
        h = rms_norm(x, lp["ln1"]["scale"], eps)
        q = split_heads(dense(h, lp["attn"]["wq"]), nh)
        k = split_heads(dense(h, lp["attn"]["wk"]), nkv)
        v = split_heads(dense(h, lp["attn"]["wv"]), nkv)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        a = attend_fn(q, k, v)
        x = x + dense(merge_heads(a), lp["attn"]["wo"])
        h2 = rms_norm(x, lp["ln2"]["scale"], eps)
        g = dense(h2, lp["mlp"]["wg"])
        u = dense(h2, lp["mlp"]["wu"])
        x = x + dense(jax.nn.silu(g) * u, lp["mlp"]["wd"])
        return x

    def full_attend(q, k_att, v_att):
        return attend(
            q,
            repeat_kv(k_att.astype(q.dtype), groups),
            repeat_kv(v_att.astype(q.dtype), groups),
            mask,
        )

    if cache is None:
        ring = (
            cfg.ring_mesh is not None
            and cfg.ring_mesh.shape.get("sp", 1) > 1
        )
        if ring:
            if kv_mask is not None or not default_positions:
                raise ValueError(
                    "ring attention (cfg.ring_mesh) supports full causal "
                    "sequences only: no kv_mask, default positions"
                )
            from ..parallel.ring import ring_attention

            def attend_ring(q, k_att, v_att):
                # GQA: broadcast the shared KV heads before the ring so
                # every block rotation carries [B, H, T/sp, Dh].
                return ring_attention(
                    q,
                    repeat_kv(k_att.astype(q.dtype), groups),
                    repeat_kv(v_att.astype(q.dtype), groups),
                    cfg.ring_mesh,
                )

            attend_full = attend_ring
        else:
            attend_full = full_attend

        def body(carry, lp):
            return block(carry, lp, attend_full), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        new_cache = None
    else:
        zero = jnp.zeros((), jnp.int32)
        if rows is not None and offset.ndim != 1:
            raise ValueError(
                "rows names the cache rows of a ragged batch (per-row "
                "cache.length)"
            )
        quant_kv = cfg.quant_kv

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
                cks2, cvs2 = cks, cvs
                if offset.ndim == 1:
                    # Ragged slots: scatter each row's T new tokens at its
                    # own offset (T=1 for paged decode; T=k+1 for the
                    # speculative verify window — engine.spec). Same layout
                    # as gpt2.forward.
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
                        cks2 = cks.at[layer, at_rows, :, slots].set(
                            k_s.transpose(0, 2, 1)
                        )
                        cvs2 = cvs.at[layer, at_rows, :, slots].set(
                            v_s.transpose(0, 2, 1)
                        )
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
                k_att = layer_rows(ck2, layer, rows)
                v_att = layer_rows(cv2, layer, rows)
                if quant_kv:
                    ks_att = layer_rows(cks2, layer, rows)
                    vs_att = layer_rows(cvs2, layer, rows)
                    return attend_quant(
                        q,
                        repeat_kv(k_att, groups),
                        jnp.repeat(ks_att, groups, axis=1),
                        repeat_kv(v_att, groups),
                        jnp.repeat(vs_att, groups, axis=1),
                        mask,
                    )
                return full_attend(q, k_att, v_att)

            y = block(x, lp, attend_fn)
            return (y, updated["k"], updated["v"], updated["ks"],
                    updated["vs"]), None

        layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, new_k, new_v, new_ks, new_vs), _ = jax.lax.scan(
            body, (x, cache.k, cache.v, cache.ks, cache.vs),
            (params["blocks"], layers),
        )
        new_cache = KVCache(k=new_k, v=new_v, length=cache.length + t,
                            ks=new_ks, vs=new_vs)

    x = rms_norm(x, params["lnf"]["scale"], eps)
    logits = quant.unembed(x, params["lm_head"])
    return logits, new_cache
