"""The Mamba-2 mixer (state-space duality, Dao & Gu 2024) for any family,
in the two forms a served model needs, which must agree:

    [z | xBC | dt] = u W_in                      d_inner + conv_dim + H
    xBC = silu(conv1d_causal_depthwise(xBC, K) + b_conv)
    xBC -> x [H, P], B [G, N], C [G, N]          (H / G heads a group)
    dt  = softplus(dt + dt_bias)   A = -exp(A_log)
    S_h <- exp(dt_h A_h) S_h + dt_h x_h B_g^T    S_h [P, N] float32
    y_h  = S_h C_g + D_h x_h
    out = RMSNorm_grouped(y * silu(z)) W_out     (gate BEFORE the norm)

- **the step** (T = 1 over every row of the cache: a decode step): the
  state update is `ops/ssm.py` `ssm_step`, on the TPU one Pallas kernel a
  layer that reads each slot's state once and writes it where it lies
  (`jax.lax.platform_dependent` picks it; plain `jax.numpy` elsewhere);
- **the chunk** (a prefill chunk for the rows `rows` names, a whole
  bucket, a forward without a cache): starts from the rows' state and
  leaves the state of the LAST LIVE position, computed as the quadratic
  form within sub-chunks of `SUB_CHUNK` positions and the recurrence
  between them (the SSD decomposition; float32, `highest`).

What a row carries between calls is `KVCache.ssm` [Lm, B, H, P, N] float32
and `KVCache.conv` [Lm, B, K-1, C], the convolution's last K-1 inputs
(window-major, so the C = 6,144 channels lie on the lanes; a [C, K-1] plane
would be tiled to 128 lanes for its 3 columns). A position that is not
`live` (a right-pad position of a chunk, an idle or staged lane of a decode
step, a left-pad position of a bucketed prompt) moves neither: `dt` is 0
there (decay 1, input 0), its `xBC` enters no window, and the window kept is
the one that ends at the last live position.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import ssm as ssm_ops
from .common import dense, layer_rows

Params = Dict[str, Any]

# Positions a sub-chunk of the chunk form holds: the quadratic form's
# [Q, Q] decay matrix a head, the recurrence between sub-chunks.
SUB_CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def sizes(cfg) -> Tuple[int, int, int, int, int]:
    """(heads, head dim, groups, state size, channels the conv runs over)."""
    h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n = cfg.n_groups, cfg.ssm_state_size
    return h, p, g, n, h * p + 2 * g * n


def init_params(key, cfg, norm, ones) -> Params:
    """One mixer's tree; `norm(key, *shape)` and `ones(*shape)` are the
    family's draws. `a_log`, `dt_bias` and `d` are float32 whatever the
    parameter dtype: A in [1, 16], dt in [1e-3, 1e-1] log-uniform."""
    h, p, _, _, conv_dim = sizes(cfg)
    d = cfg.hidden_size
    ks = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(ks[3], (h,), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    return {
        "w_in": norm(ks[0], d, h * p + conv_dim + h),
        "conv_w": norm(ks[1], cfg.conv_kernel, conv_dim),
        "conv_b": jnp.zeros((conv_dim,), cfg.param_dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus's inverse
        "a_log": jnp.log(jax.random.uniform(ks[4], (h,), jnp.float32,
                                            1.0, 16.0)),
        "d": jnp.ones((h,), jnp.float32),
        "norm": {"scale": ones(h * p)},
        "w_out": norm(ks[2], h * p, d),
    }


def init_state(cfg, layers: int, batch: int, dtype) -> Tuple[jax.Array,
                                                             jax.Array]:
    """Zeroed (`ssm`, `conv`) planes of `layers` mixers and `batch` rows."""
    h, p, _, n, conv_dim = sizes(cfg)
    return (jnp.zeros((layers, batch, h, p, n), jnp.float32),
            jnp.zeros((layers, batch, cfg.conv_kernel - 1, conv_dim), dtype))


def gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array, groups: int,
               eps: float) -> jax.Array:
    """RMSNorm over groups of the inner width, the gate applied first:
    y, z [..., d_inner] -> float32."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(*y.shape[:-1], groups, -1)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
                            + eps)
    return yg.reshape(y.shape) * scale.astype(jnp.float32)


def causal_conv(xbc: jax.Array, window: jax.Array, mp: Params,
                live: jax.Array, act=jax.nn.silu):
    """The causal depthwise convolution of a chunk that continues a
    window: xbc [B, T, C], window [B, K-1, C] (the inputs before it), live
    [B, T] -> (act(conv + bias) [B, T, C] float32, the window that ends
    at each row's last live position [B, K-1, C]). A tree without `conv_b`
    has no bias (models/kda.py); `act` None is no activation
    (models/lfm2.py: the gates around the convolution are the caller's)."""
    b, t, _ = xbc.shape
    k1 = window.shape[1]
    xbc = jnp.where(live[..., None], xbc, jnp.zeros((), xbc.dtype))
    seq = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=1)
    w = mp["conv_w"].astype(jnp.float32)                        # [K, C]
    out = sum(seq[:, j:j + t].astype(jnp.float32) * w[j]
              for j in range(k1 + 1))
    if "conv_b" in mp:
        out = out + mp["conv_b"].astype(jnp.float32)
    if act is not None:
        out = act(out)
    # seq[last + 1 .. last + K-1] are the K-1 inputs up to live position
    # `last` (-1: none was live, and the window stands).
    ends = jnp.max(jnp.where(live, jnp.arange(1, t + 1), 0), axis=1)
    kept = jax.vmap(lambda s, e: jax.lax.dynamic_slice_in_dim(s, e, k1, 0))(
        seq, ends)
    return out, kept.astype(window.dtype)


def _chunk_scan(x, dt, a, bm, cm, state):
    """The state-space recurrence over T positions from `state`, in the
    chunked (SSD) form: x [B, T, H, P], dt [B, T, H] (0 where not live),
    a [H], bm / cm [B, T, G, N], state [B, H, P, N]; all float32 ->
    (y [B, T, H, P] without the D term, the state after position T-1)."""
    b, t, h, p = x.shape
    g = bm.shape[2]
    q = min(t, SUB_CHUNK)
    pad = -t % q
    if pad:  # dt = 0 moves nothing
        x, dt, bm, cm = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                         for v in (x, dt, bm, cm))
    nq = (t + pad) // q
    tri = jnp.tril(jnp.ones((q, q), bool))

    def sub(state, part):
        x, dt, bm, cm = part                      # [B, Q, ...]
        cs = jnp.cumsum(dt * a, axis=1)           # [B, Q, H], <= 0
        bh = jnp.repeat(bm, h // g, axis=2)       # [B, Q, H, N]
        ch = jnp.repeat(cm, h // g, axis=2)
        # Within the sub-chunk: y_t += sum_{s<=t} exp(cs_t - cs_s) dt_s
        # (C_t . B_s) x_s.
        diff = cs[:, :, None, :] - cs[:, None, :, :]            # [B,t,s,H]
        decay = jnp.exp(jnp.where(tri[None, :, :, None], diff, -jnp.inf))
        cb = jnp.einsum("bthn,bshn->btsh", ch, bh, precision=_HI)
        m = decay * cb * dt[:, None, :, :]
        y = jnp.einsum("btsh,bshp->bthp", m, x, precision=_HI)
        # From the state carried in: y_t += exp(cs_t) S C_t.
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "bthn,bhpn->bthp", ch, state, precision=_HI)
        # The state carried out: exp(cs_Q) S + sum_s exp(cs_Q - cs_s) dt_s
        # x_s B_s^T.
        w = jnp.exp(cs[:, -1:, :] - cs) * dt                    # [B, Q, H]
        state = (jnp.exp(cs[:, -1])[..., None, None] * state
                 + jnp.einsum("bsh,bshp,bshn->bhpn", w, x, bh,
                              precision=_HI))
        return state, y

    parts = tuple(v.reshape(b, nq, q, *v.shape[2:]).swapaxes(0, 1)
                  for v in (x, dt, bm, cm))
    if nq == 1:
        state, y = sub(state, tuple(v[0] for v in parts))
        return y[:, :t], state
    state, ys = jax.lax.scan(sub, state, parts)
    return ys.swapaxes(0, 1).reshape(b, nq * q, h, p)[:, :t], state


def mixer(u: jax.Array, mp: Params, cfg, live: jax.Array,
          planes: Optional[Tuple[jax.Array, jax.Array]] = None,
          layer: int = 0, rows: Optional[jax.Array] = None):
    """One mixer over u [B, T, D] -> (out [B, T, D], planes).

    `planes` = (`ssm`, `conv`) are the cache's stacked planes (module
    docstring) and `layer` this mixer's index in them; batch element i
    owns row i, or row `rows[i]` where `rows` ([B]) is given. None: every
    sequence starts from zeros and nothing is kept. `live` [B, T] bool.
    T = 1 over every row of the planes is the step form, all else the
    chunk form."""
    b, t, _ = u.shape
    h, p, g, n, conv_dim = sizes(cfg)
    di = h * p
    with jax.named_scope("ssm.in_proj"):
        zxbcdt = dense(u, mp["w_in"])
        z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + conv_dim],
                      zxbcdt[..., di + conv_dim:])
    if planes is None:
        ssm, conv = init_state(cfg, 1, b, u.dtype)
        layer, rows = 0, None
    else:
        ssm, conv = planes
    # Batch element i's row of layer `layer`: row i, or the row named.
    at = layer if rows is None else (layer, rows)
    with jax.named_scope("ssm.conv"):
        xbc, window = causal_conv(xbc, layer_rows(conv, layer, rows), mp,
                                  live)
        conv = conv.at[at].set(window)
    x = xbc[..., :di].reshape(b, t, h, p)
    bm = xbc[..., di:di + g * n].reshape(b, t, g, n)
    cm = xbc[..., di + g * n:].reshape(b, t, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + mp["dt_bias"])
    dt = jnp.where(live[..., None], dt, 0.0)                    # [B, T, H]
    a = -jnp.exp(mp["a_log"].astype(jnp.float32))
    # The activations are the served dtype's values (the convolution's
    # output is rounded to it, as the published kernels take it); the
    # state and everything that multiplies it stay float32.
    x, bm, cm = (v.astype(u.dtype).astype(jnp.float32) for v in (x, bm, cm))
    with jax.named_scope("ssm.scan"):
        if planes is not None and t == 1 and rows is None:
            step = (dt[:, 0, :, None] * x[:, 0], jnp.exp(dt[:, 0] * a),
                    bm[:, 0], cm[:, 0])
            ssm, y = jax.lax.platform_dependent(
                ssm, *step,
                tpu=lambda s, *ops: ssm_ops.ssm_step(s, layer, *ops),
                default=lambda s, *ops: ssm_ops.ssm_step_reference(
                    s, layer, *ops))
            y = y[:, None]
        else:
            y, state = _chunk_scan(x, dt, a, bm, cm,
                                   layer_rows(ssm, layer, rows))
            ssm = ssm.at[at].set(state)
        y = y + mp["d"].astype(jnp.float32)[:, None] * x
    with jax.named_scope("ssm.out"):
        y = gated_norm(y.reshape(b, t, di), z, mp["norm"]["scale"], g,
                       cfg.rms_norm_eps)
        out = dense(y.astype(u.dtype), mp["w_out"])
    return out, (None if planes is None else (ssm, conv))
