"""Ring attention: causal self-attention sharded over the `sp` mesh axis.

Long-context prefill is where attention memory explodes: full [T, T] scores
for a 128k prompt don't fit one chip. Ring attention keeps each device
holding one sequence shard of Q/K/V ([B, H, T/n, Dh]) and rotates the K/V
shards around the ring with `ppermute` (one ICI hop per step) while each
device accumulates its queries' attention with an online-softmax update —
compute overlaps the rotation, no device ever materializes more than a
[T/n, T/n] score block, and the result is EXACTLY dense causal attention
(no approximation; parity-tested against `models.common.attend`).

This is the TPU-native shape of the capability (blockwise/ring attention à
la Liu et al.; public JAX ringattention repos follow the same recipe —
pattern reimplemented here for our [B, H, T, Dh] layout and left-to-right
block causality). The reference CLAMPS context instead (BERT truncates at
512, generation capped at 150 total tokens — reference:
GUI_RAFT_LLM_SourceCode/lms_server.py:98, tutoring_server.py:23), so this
is pure capability headroom: `sp` in `parallel.mesh` stops being a
decorative axis.

Scope: the prefill/training direction (full-sequence attention). Decode
reads a KV cache one token at a time and stays on the tp/dp path.

Production reachability: `GPT2Config.ring_mesh` / `LlamaConfig.ring_mesh`
route the models' full-sequence attention here (models/gpt2.py,
models/llama.py), and `train.make_sharded_train_step` activates it for any
mesh with sp > 1 (the train CLI's --sp flag), sharding the batch's
sequence dim over sp. Parity pinned in tests/test_model_parallel.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _ring_block(q, k, v, q_offset, kv_offset, scale, m, l, o):
    """One online-softmax accumulation of q against a rotated K/V block.

    q [B,H,Tq,Dh]; k/v [B,H,Tk,Dh]; offsets are the blocks' absolute start
    positions (drive the causal mask); m/l/o are the running max, denom,
    and unnormalized output.
    """
    tq, tk = q.shape[2], k.shape[2]
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    q_pos = q_offset + jnp.arange(tq)[:, None]
    k_pos = kv_offset + jnp.arange(tk)[None, :]
    scores = jnp.where((k_pos <= q_pos)[None, None], scores, NEG_INF)

    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    # Fully-masked rows keep m at NEG_INF; exp(NEG_INF - NEG_INF) would be
    # exp(0)=1 and poison the denominator, so clamp the shift.
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(scores - shift)
    correction = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - shift)
    l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * correction + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, o_new


def _ring_attention_shard(q, k, v, *, n: int, axis_name: str, scale: float):
    """Per-device body under shard_map: rotate K/V around the ring."""
    idx = jax.lax.axis_index(axis_name)
    tq = q.shape[2]
    q_offset = idx * tq
    perm = [(i, (i + 1) % n) for i in range(n)]

    # The accumulators must carry the same varying-axes type as q under the
    # shard_map type system (they are per-shard values over every sharded
    # mesh axis, not just the ring axis) — deriving them from q inherits it.
    zero = (q * 0).astype(jnp.float32)
    m = zero[..., :1] + NEG_INF
    l = zero[..., :1]
    o = zero

    def body(step, carry):
        k_blk, v_blk, m, l, o = carry
        # After `step` rotations this device holds the block that started
        # on device (idx - step) mod n.
        owner = (idx - step) % n
        m, l, o = _ring_block(
            q, k_blk, v_blk, q_offset, owner * tq, scale, m, l, o
        )
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, o

    # n-1 rotate-and-accumulate steps, then the final block with no
    # trailing ppermute — the last rotation's K+V shard transfer would be
    # pure discarded ICI traffic.
    k, v, m, l, o = jax.lax.fori_loop(0, n - 1, body, (k, v, m, l, o))
    m, l, o = _ring_block(
        q, k, v, q_offset, ((idx - (n - 1)) % n) * tq, scale, m, l, o
    )
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    spec: Optional[P] = None,
) -> jax.Array:
    """Causal multi-head attention with the sequence sharded over `axis_name`.

    q, k, v: [B, H, T, Dh] with T divisible by the axis size; returns
    [B, H, T, Dh] identical (up to float error) to dense causal `attend`.
    Other mesh axes pass through untouched (compose with dp/tp specs via
    `spec`, default [B over dp, H over tp, T over sp]).
    """
    scale = 1.0 / (q.shape[-1] ** 0.5)
    # shard_map in_specs spell every axis of the [B, H, T, Dh] operand
    # explicitly (rank documentation, and these specs never key a jit
    # cache).  # lint: disable-next=canonical-pspec
    spec = spec or P("dp", "tp", axis_name, None)
    fn = shard_map(
        functools.partial(
            _ring_attention_shard, n=mesh.shape[axis_name],
            axis_name=axis_name, scale=scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
