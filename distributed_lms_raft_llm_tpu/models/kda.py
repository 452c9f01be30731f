"""The Kimi Delta Attention mixer (KDA; Kimi Linear, Moonshot 2025) for any
family, in the two forms a served model needs, which must agree:

    q, k, v = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
              (one causal depthwise convolution over the 3 H K channels)
    q, k   <- q / |q| * K^-0.5, k / |k|              a head
    g_t  = -exp(A_log[h]) softplus(x Wf_a Wf_b + dt_bias)   [H, K], <= 0
    beta_t = sigmoid(x Wb)                                   [H]
    S <- diag(exp(g_t)) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T
    o_t = S^T q_t                                    S [K, V] float32 a head
    out = (RMSNorm_head(o_t) * sigmoid(x Wg_a Wg_b)) Wo

A gated delta rule: the state is a MATRIX a head, corrected towards the
value by a rank-one term and forgotten by a decay of its own for every
channel of the key.

- **the step** (T = 1 over every row of the cache: a decode step): the
  state update is `ops/kda.py` `kda_step`, on the TPU one Pallas kernel a
  layer that reads each slot's state once and writes it where it lies
  (`jax.lax.platform_dependent` picks it; plain `jax.numpy` elsewhere);
- **the chunk** (a prefill chunk for the rows `rows` names, a whole
  bucket, a forward without a cache): starts from the rows' state and
  leaves the state of the LAST LIVE position. Within sub-chunks of
  `SUB_CHUNK` positions the corrections `u` are the solution of a unit
  lower-triangular system, `(I + diag(beta) A) U = diag(beta) (V - (K *
  exp(G)) S_0)` with `A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])`
  for s < t and G the cumulative sum of g; between sub-chunks the state is
  carried. Float32 at `highest`; every decay is taken as `exp` of a
  DIFFERENCE of cumulative sums, G_t - G_s with s <= t, which is never
  positive: no `exp(-G)` is formed, whatever a channel's decay.

What a row carries between calls is `KVCache.ssm` [Lk, B, H, K, V] float32
and `KVCache.conv` [Lk, B, conv-1, 3 H K], the convolution's last inputs
(`models/mamba2.py`'s planes and its convolution, here over three
projections). A position that is not `live` (a right-pad position of a
chunk, an idle or staged lane of a decode step) moves neither: g is 0
there (decay 1) and beta 0 (no correction), its q, k and v enter no window,
and the window kept is the one that ends at the last live position.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import kda as kda_ops
from .common import dense, layer_rows
from .mamba2 import causal_conv

Params = Dict[str, Any]

# Positions a sub-chunk of the chunk form holds: the triangular system is
# [Q, Q] a head and its decays [Q, Q, K] (a served prefill chunk is one).
SUB_CHUNK = 32
_HI = jax.lax.Precision.HIGHEST
_NORM_EPS = 1e-6


def sizes(cfg) -> Tuple[int, int, int, int]:
    """(heads, key dim, value dim, channels the convolution runs over)."""
    h, k = cfg.kda_num_heads, cfg.kda_head_dim
    return h, k, k, 3 * h * k


def init_params(key, cfg, norm, ones) -> Params:
    """One mixer's tree; `norm(key, *shape)` and `ones(*shape)` are the
    family's draws. `a_log` and `dt_bias` are float32 whatever the
    parameter dtype: A in [1, 16], dt in [1e-3, 1e-1] log-uniform."""
    h, k, v, conv_dim = sizes(cfg)
    d, r = cfg.hidden_size, cfg.kda_head_dim
    ks = jax.random.split(key, 10)
    dt = jnp.exp(jax.random.uniform(ks[8], (h * k,), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    return {
        "w_qkv": norm(ks[0], d, conv_dim),
        "conv_w": norm(ks[1], cfg.kda_conv_kernel, conv_dim),
        "w_fa": norm(ks[2], d, r), "w_fb": norm(ks[3], r, h * k),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus's inverse
        "a_log": jnp.log(jax.random.uniform(ks[9], (h,), jnp.float32,
                                            1.0, 16.0)),
        "w_b": norm(ks[4], d, h),
        "w_ga": norm(ks[5], d, r), "w_gb": norm(ks[6], r, h * v),
        "norm": {"scale": ones(v)},
        "w_out": norm(ks[7], h * v, d),
    }


def init_state(cfg, layers: int, batch: int, dtype) -> Tuple[jax.Array,
                                                             jax.Array]:
    """Zeroed (`ssm`, `conv`) planes of `layers` mixers and `batch` rows."""
    h, k, v, conv_dim = sizes(cfg)
    return (jnp.zeros((layers, batch, h, k, v), jnp.float32),
            jnp.zeros((layers, batch, cfg.kda_conv_kernel - 1, conv_dim),
                      dtype))


def _l2(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + _NORM_EPS)


def _chunk_scan(q, k, v, g, beta, state):
    """The delta rule over T positions from `state`, in the chunked form:
    q, k, g [B, T, H, K] (g <= 0, and 0 where not live), v [B, T, H, V],
    beta [B, T, H] (0 where not live), state [B, H, K, V]; all float32 ->
    (o [B, T, H, V], the state after position T-1)."""
    b, t, h, kd = q.shape
    n = min(t, SUB_CHUNK)
    pad = -t % n
    if pad:  # g = 0 and beta = 0 move nothing
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    nq = (t + pad) // n
    seen = jnp.tril(jnp.ones((n, n), bool))           # s <= t
    before = jnp.tril(jnp.ones((n, n), bool), -1)     # s < t
    eye = jnp.eye(n, dtype=jnp.float32)

    def sub(state, part):
        q, k, v, g, beta = part                       # [B, Q, H, ...]
        cs = jnp.cumsum(g, axis=1)                    # [B, Q, H, K], <= 0
        # exp(G_t - G_s) for s <= t alone: the differences are <= 0.
        diff = cs[:, :, None] - cs[:, None, :]        # [B, t, s, H, K]
        decay = jnp.exp(jnp.where(seen[None, :, :, None, None], diff,
                                  -jnp.inf))
        kk = jnp.einsum("bthc,bshc,btshc->bhts", k, k, decay, precision=_HI)
        qk = jnp.einsum("bthc,bshc,btshc->bhts", q, k, decay, precision=_HI)
        into = jnp.exp(cs)                            # from the chunk's start
        bt = beta.transpose(0, 2, 1)[..., None]       # [B, H, Q, 1]
        rhs = bt * (v.transpose(0, 2, 1, 3) - jnp.einsum(
            "bthc,bhcv->bhtv", k * into, state, precision=_HI))
        system = eye + bt * jnp.where(before, kk, 0.0)
        u = jax.lax.linalg.triangular_solve(
            system, rhs, left_side=True, lower=True, unit_diagonal=True)
        o = (jnp.einsum("bthc,bhcv->bthv", q * into, state, precision=_HI)
             + jnp.einsum("bhts,bhsv->bthv", qk, u, precision=_HI))
        # The state carried out: diag(exp(G_Q)) S + sum_s diag(exp(G_Q -
        # G_s)) k_s u_s^T.
        out = jnp.exp(cs[:, -1:] - cs) * k            # [B, Q, H, K]
        state = (jnp.exp(cs[:, -1])[..., None] * state
                 + jnp.einsum("bshc,bhsv->bhcv", out, u, precision=_HI))
        return state, o

    parts = tuple(x.reshape(b, nq, n, *x.shape[2:]).swapaxes(0, 1)
                  for x in (q, k, v, g, beta))
    if nq == 1:
        state, o = sub(state, tuple(x[0] for x in parts))
        return o[:, :t], state
    state, os_ = jax.lax.scan(sub, state, parts)
    return os_.swapaxes(0, 1).reshape(b, nq * n, h, -1)[:, :t], state


def mixer(x: jax.Array, mp: Params, cfg, live: jax.Array,
          planes: Optional[Tuple[jax.Array, jax.Array]] = None,
          layer: int = 0, rows: Optional[jax.Array] = None):
    """One mixer over x [B, T, D] -> (out [B, T, D], planes).

    `planes` = (`ssm`, `conv`) are the cache's stacked planes (module
    docstring) and `layer` this mixer's index in them; batch element i
    owns row i, or row `rows[i]` where `rows` ([B]) is given. None: every
    sequence starts from zeros and nothing is kept. `live` [B, T] bool.
    T = 1 over every row of the planes is the step form, all else the
    chunk form."""
    b, t, _ = x.shape
    h, kd, vd, conv_dim = sizes(cfg)
    f32 = jnp.float32
    with jax.named_scope("kda.proj"):
        qkv = dense(x, mp["w_qkv"])
        g = dense(dense(x, mp["w_fa"]), mp["w_fb"]).astype(f32)
        beta = jax.nn.sigmoid(dense(x, mp["w_b"]).astype(f32))   # [B, T, H]
        gate = dense(dense(x, mp["w_ga"]), mp["w_gb"])
    if planes is None:
        ssm, conv = init_state(cfg, 1, b, x.dtype)
        layer, rows = 0, None
    else:
        ssm, conv = planes
    # Batch element i's row of layer `layer`: row i, or the row named.
    at = layer if rows is None else (layer, rows)
    with jax.named_scope("kda.conv"):
        qkv, window = causal_conv(qkv, layer_rows(conv, layer, rows), mp,
                                  live)
        conv = conv.at[at].set(window)
    # The activations are the served dtype's values (the convolution's
    # output is rounded to it, as the published kernels take it); the
    # state and everything that multiplies it stay float32.
    qkv = qkv.astype(x.dtype).astype(f32)
    q, k, v = (qkv[..., i * h * kd:(i + 1) * h * kd].reshape(b, t, h, kd)
               for i in range(3))
    q, k = _l2(q) * kd ** -0.5, _l2(k)
    g = -jnp.exp(mp["a_log"].astype(f32))[:, None] * jax.nn.softplus(
        g + mp["dt_bias"]).reshape(b, t, h, kd)
    g = jnp.where(live[..., None, None], g, 0.0)
    beta = jnp.where(live[..., None], beta, 0.0)
    with jax.named_scope("kda.scan"):
        if planes is not None and t == 1 and rows is None:
            step = (q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]), beta[:, 0])
            ssm, o = jax.lax.platform_dependent(
                ssm, *step,
                tpu=lambda s, *ops: kda_ops.kda_step(s, layer, *ops),
                default=lambda s, *ops: kda_ops.kda_step_reference(
                    s, layer, *ops))
            o = o[:, None]
        else:
            o, state = _chunk_scan(q, k, v, g, beta,
                                   layer_rows(ssm, layer, rows))
            ssm = ssm.at[at].set(state)
    with jax.named_scope("kda.out"):
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        o = o * mp["norm"]["scale"].astype(f32) * jax.nn.sigmoid(
            gate.astype(f32).reshape(b, t, h, vd))
        out = dense(o.reshape(b, t, h * vd).astype(x.dtype), mp["w_out"])
    return out, (None if planes is None else (ssm, conv))
