"""Plain afmoe forward pass: the benchmark's reference for Trinity-Mini.

`jax.numpy`, float32, products at `highest` precision, one sequence at once:
no cache, no batching, no kernel, and no import from the program. Weights
come a layer at a time (`weights.Seeded.layer(l)` when the loop reaches l):
the 4.24 B parameters of the cut do not fit in float32 beside the program's
copy. From the published `config.json`, and, where its keys do not say,
from `transformers`' `modeling_afmoe.py` as ISSUE 30 wrote it down (each
such item is under `assumed` in the configuration file):

- `h = E[ids] * sqrt(hidden)` (`mup_enabled`);
- `h += N2(Attn(N1(h)))`, `h += N4(Mlp(N3(h)))`: four RMSNorms a layer;
- attention: `q`, `k` [heads x head_dim], RMS-normalised per head; rotary
  embedding (rotate-half, theta, all of head_dim) on `q` and `k` in
  `sliding_attention` layers ONLY, no position signal in `full_attention`
  layers; scores `q k / sqrt(head_dim)`, causal, and in a sliding layer a
  query at p sees keys p - window + 1 .. p; softmax; the heads' output
  times `sigmoid(Wg x)`, then `Wo`. No biases;
- dense layer: `Wd (silu(Wg x) * Wu x)`;
- expert layer: `s = sigmoid(Wr x)` over all experts; the chosen are the
  top-k of `s + b` (the bias takes part in the choice only); weights
  `s_i / (sum of the chosen s + 1e-20) * route_scale`; `y = sum w_i E_i(x)
  + Shared(x)`, no capacity, no dropped pick. Experts by a plain loop:
  every expert on every token, times the weight the routing gives it;
- final RMSNorm, untied head.

Departures from the published description: none in the mathematics; linears
are stored [in, out] and a layer's experts stacked (`weights.py`). Returned
are the logits of the LAST `check.logit_positions` positions only (at
2,336 x 200,192 the whole matrix is 1.9 GB a side), the keys and values of
every layer and position as a cache would hold them, and the routing.

`CONTROLS`: the same reference with ONE stated precision a step lower
(`int8_weights`: every matrix in 8 bits, one scale per output channel, per
row for embedding and head; `int8_kv`: keys and values in 8 bits, one scale
per head and token; `fp8_activations`: the input of every product through
float8_e4m3fn), and one control of the mathematics, `no_window`: every
layer sees every key.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

CONTROLS = ("int8_weights", "int8_kv", "fp8_activations", "no_window")
SLIDING = "sliding_attention"


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def _round_to_bits(x, axis, bits):
    top = float(2 ** (bits - 1) - 1)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top, 1e-8)
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _through_fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _rotate(x, theta):
    """Rotary embedding, rotate-half, on [H, T, Dh] at positions 0..T-1."""
    _, t, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _swiglu(x, wg, wu, wd, act):
    return act(jax.nn.silu(act(x) @ wg) * (act(x) @ wu)) @ wd


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "dh", "eps", "theta", "window", "rotary",
    "kv_bits", "fp8"))
def _attention(x, lw, *, heads, kv_heads, dh, eps, theta, window, rotary,
               kv_bits=None, fp8=False):
    """x + N2(Attn(N1(x))), and the layer's keys and values [Hkv, T, Dh]."""
    act = _through_fp8 if fp8 else (lambda a: a)
    t = x.shape[0]
    h = act(_rms_norm(x, lw["input_layernorm.weight"], eps))

    def proj(name, n):
        return (h @ lw[f"self_attn.{name}_proj.weight"]).reshape(
            t, n, dh).transpose(1, 0, 2)

    q, k, v = proj("q", heads), proj("k", kv_heads), proj("v", kv_heads)
    q = _rms_norm(q, lw["self_attn.q_norm.weight"], eps)
    k = _rms_norm(k, lw["self_attn.k_norm.weight"], eps)
    if rotary:
        q, k = _rotate(q, theta), _rotate(k, theta)
    if kv_bits:
        k, v = _round_to_bits(k, -1, kv_bits), _round_to_bits(v, -1, kv_bits)
    group = heads // kv_heads
    qg = q.reshape(kv_heads, group, t, dh)
    scores = jnp.einsum("kgqd,ksd->kgqs", act(qg), act(k)) / math.sqrt(dh)
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen = seen & (pos[None, :] > pos[:, None] - window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("kgqs,ksd->kgqd", act(probs), act(v))
    a = a.reshape(heads, t, dh).transpose(1, 0, 2).reshape(t, heads * dh)
    a = a * jax.nn.sigmoid(h @ lw["self_attn.gate_proj.weight"])
    out = act(a) @ lw["self_attn.o_proj.weight"]
    return (x + _rms_norm(out, lw["post_attention_layernorm.weight"], eps),
            k, v)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _dense_mlp(x, lw, *, eps, fp8=False):
    act = _through_fp8 if fp8 else (lambda a: a)
    h = _rms_norm(x, lw["pre_mlp_layernorm.weight"], eps)
    y = _swiglu(h, lw["mlp.gate_proj.weight"], lw["mlp.up_proj.weight"],
                lw["mlp.down_proj.weight"], act)
    return x + _rms_norm(y, lw["post_mlp_layernorm.weight"], eps)


@functools.partial(jax.jit, static_argnames=(
    "eps", "k", "norm", "scale", "fp8"))
def _expert_mlp(x, lw, *, eps, k, norm, scale, fp8=False):
    """x + N4(routed + shared), and which experts each token chose
    [T, E] bool."""
    act = _through_fp8 if fp8 else (lambda a: a)
    h = _rms_norm(x, lw["pre_mlp_layernorm.weight"], eps)
    s = jax.nn.sigmoid(act(h) @ lw["mlp.router.gate.weight"])
    _, picks = jax.lax.top_k(s + lw["mlp.expert_bias"], k)
    chosen = jnp.sum(jax.nn.one_hot(picks, s.shape[-1]), axis=1)   # [T, E]
    w = s * chosen
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * scale

    def one(y, e):
        out = _swiglu(h, lw["mlp.experts.gate_proj"][e],
                      lw["mlp.experts.up_proj"][e],
                      lw["mlp.experts.down_proj"][e], act)
        return y + out * w[:, e, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(s.shape[-1]))
    y = y + _swiglu(h, lw["mlp.shared_experts.gate_proj.weight"],
                    lw["mlp.shared_experts.up_proj.weight"],
                    lw["mlp.shared_experts.down_proj.weight"], act)
    return (x + _rms_norm(y, lw["post_mlp_layernorm.weight"], eps),
            chosen > 0)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, gain, head, *, eps, fp8=False):
    h = _rms_norm(x, gain, eps)
    return (_through_fp8(h) if fp8 else h) @ head.T


def _int8(lw: dict) -> dict:
    """Every matrix of a layer in 8 bits, one scale per output channel, in
    place (a second float32 copy of an expert layer is 3.2 GB)."""
    for name in list(lw):
        if lw[name].ndim >= 2:
            lw[name] = _round_to_bits(lw[name], -2, 8)
    return lw


def forward(w, ids, config: dict, control=None):
    """(logits [P, V] of the last P = `check.logit_positions` positions,
    keys [L, Hkv, T, Dh], values [L, Hkv, T, Dh], routing [Le, T, E]
    bool), float32, for one sequence of token ids [T]. `w` is a
    `weights.Seeded` in float32; every size is the configuration file's."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control is called {control!r}: {CONTROLS}")
    eps = float(config["rms_norm_eps"])
    fp8 = control == "fp8_activations"
    attn = dict(
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        dh=int(config["head_dim"]), eps=eps,
        theta=float(config["rope_theta"]), fp8=fp8,
        kv_bits=8 if control == "int8_kv" else None)
    routed = dict(eps=eps, k=int(config["num_experts_per_tok"]),
                  norm=bool(config["route_norm"]),
                  scale=float(config["route_scale"]), fp8=fp8)
    rows = int(config["check"]["logit_positions"])
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        embed = w.embed()
        if control == "int8_weights":
            embed = _round_to_bits(embed, -1, 8)
        x = embed[ids]
        del embed
        if config["mup_enabled"]:
            x = x * math.sqrt(int(config["hidden_size"]))
        keys, values, routing = [], [], []
        for layer, kind in enumerate(config["layer_types"]):
            lw = w.layer(layer)
            if control == "int8_weights":
                lw = _int8(lw)
            sliding = kind == SLIDING
            x, k, v = _attention(
                x, lw, rotary=sliding,
                window=(int(config["sliding_window"])
                        if sliding and control != "no_window" else None),
                **attn)
            keys.append(k)
            values.append(v)
            if layer < int(config["num_dense_layers"]):
                x = _dense_mlp(x, lw, eps=eps, fp8=fp8)
            else:
                x, chosen = _expert_mlp(x, lw, **routed)
                routing.append(chosen)
            del lw
        head = w.head()
        if control == "int8_weights":
            head = _round_to_bits(head, -1, 8)
        logits = _head(x[-rows:], w.norm(), head, eps=eps, fp8=fp8)
        return (logits, jnp.stack(keys), jnp.stack(values),
                jnp.stack(routing))
