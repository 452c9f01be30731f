"""Weight-only int8 quantization for TPU serving.

The decode loop is HBM-bandwidth-bound: every step streams every parameter,
which makes parameter bytes a first-order cost for GPT-2-class models at
tutoring batch sizes. Weight-only int8 halves them: weights store as int8 with a per-output-channel symmetric scale and
dequantize on the fly inside the matmul's operand load (XLA fuses the
convert), so HBM sees int8 while the MXU still computes in bf16/f32.
Activations, norms, biases, and the position table stay full precision —
the standard near-lossless serving recipe (weight-only, per-channel).

Representation: a quantized linear is the dict `{"q": int8 [..., in, out],
"s": f32 [..., out]}` in place of the dense array. `common.dense`,
`quant.embed_lookup`, and `quant.unembed` understand both forms, so model
code is unchanged and the stacked-layer scan carries the pair transparently.

Capability note: the reference serves f32 torch-CPU weights (reference:
GUI_RAFT_LLM_SourceCode/tutoring_server.py:10-12); quantization here is
TPU-headroom work with no reference analogue. Enable per engine via
`EngineConfig.quant="int8"`; quality bound asserted in
tests/test_quant.py (top-1 agreement + logit error on real weights).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

# Leaves to quantize, per family: the big streamed matmul weights. Norm
# scales/biases, wpe (1.5 MB), and biases stay full precision.
_QUANT_LEAVES = {
    "gpt2": {
        ("wte",),
        ("blocks", "attn", "wqkv"),
        ("blocks", "attn", "wo"),
        ("blocks", "mlp", "wi"),
        ("blocks", "mlp", "wo"),
    },
    "llama": {
        ("embed",),
        ("lm_head",),
        ("blocks", "attn", "wq"),
        ("blocks", "attn", "wk"),
        ("blocks", "attn", "wv"),
        ("blocks", "attn", "wo"),
        ("blocks", "mlp", "wg"),
        ("blocks", "mlp", "wu"),
        ("blocks", "mlp", "wd"),
    },
    # MoE: the gpt2-shared trunk leaves plus the expert stacks — the
    # per-out-channel scales for [L, E, D, M] land as [L, E, M] and fold
    # into moe_mlp's batched expert einsums after the dot (expert_dense).
    # The router stays dense (tiny, and softmax-sensitive).
    "gpt2_moe": {
        ("wte",),
        ("blocks", "attn", "wqkv"),
        ("blocks", "attn", "wo"),
        ("blocks", "moe", "wi"),
        ("blocks", "moe", "wo"),
    },
    "bert": {
        ("embeddings", "word"),
        ("blocks", "attn", "wqkv"),
        ("blocks", "attn", "wo"),
        ("blocks", "mlp", "wi"),
        ("blocks", "mlp", "wo"),
    },
}


def quantize_array(w: jax.Array) -> Dict[str, jax.Array]:
    """Symmetric per-output-channel int8: w ≈ q * s, scale over the LAST
    axis (out channels for [in, out] linears, embedding rows for [V, D]
    tables — there the last axis is D, so scales are per-row via axis=-1
    of the TRANSPOSED view; see `quantize_embedding`)."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0  # reduce `in`
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s[..., 0, :].astype(jnp.float32)}


def quantize_embedding(w: jax.Array) -> Dict[str, jax.Array]:
    """Embedding/unembedding table [V, D]: per-row (per-token) scales, so
    the tied unembedding matmul dequantizes per vocab row."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s[..., 0].astype(jnp.float32)}


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def quantize_params(params: Params, family: str) -> Params:
    """Quantize the configured leaves of a model family's param tree."""
    leaves = _QUANT_LEAVES[family]
    emb_leaves = {("wte",), ("embed",), ("lm_head",), ("embeddings", "word")}

    def walk(tree, path=()):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for key, value in tree.items():
            p = path + (key,)
            if p in leaves:
                out[key] = (
                    quantize_embedding(value) if p in emb_leaves
                    else quantize_array(value)
                )
            else:
                out[key] = walk(value, p)
        return out

    return walk(params)


def embed_lookup(table: Any, ids: jax.Array) -> jax.Array:
    """Row lookup supporting both dense [V, D] and quantized tables."""
    if is_quantized(table):
        return table["q"][ids].astype(jnp.float32) * table["s"][ids][..., None]
    return table[ids]


def unembed(x: jax.Array, table: Any) -> jax.Array:
    """Tied unembedding: x [B, T, D] @ table [V, D]^T -> f32 logits.

    For quantized tables the int8 weights feed the MXU directly (the
    convert fuses into the dot's operand load) and the per-row scale
    applies to the f32 accumulator output.
    """
    if is_quantized(table):
        logits = jnp.einsum(
            "btd,vd->btv",
            x,
            table["q"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits * table["s"][None, None, :]
    return jnp.einsum(
        "btd,vd->btv", x, table.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
