"""Plain lfm2_moe forward pass: the benchmark's reference for LFM2-8B-A1B.

`jax.numpy`, float32, products at `highest` precision, one sequence at once:
no cache, no batching, no kernel, no chunked form and no import from the
program. Weights come a layer at a time (`weights.Seeded.layer(l)` when the
loop reaches l), a routed layer's experts in blocks of
`weights.EXPERT_BLOCK` (a layer's 32 experts are 1.4 GB in float32 beside
the served copy). The layers are ISSUE 57's (`families/lfm2_moe/README.md`
has the equations; each item the published `config.json` does not settle is
under `assumed` in the configuration file):

- `h += Op(RMSNorm(h))`, `h += FF(RMSNorm(h))`; final RMSNorm
  (`embedding_norm`), the head the embedding;
- `conv`: `[B | C | z] = x W_in`; `u = B * z`; THE CONVOLUTION TOKEN BY
  TOKEN from a zero window: `c_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t`,
  the window then shifted; `y = C * c`; `y W_out`. No activation, no bias;
- `full_attention`: 32 query heads on 8 key/value heads of 64, EXPANDED (a
  key head repeated for its 4 query heads); q and k RMS-normalised per head
  with a learned weight BEFORE the rotation; the rotation over the whole
  head, halves rotated, base `rope_theta`; causal, scale 64^-0.5, softmax
  in float32;
- dense SwiGLU `w2(silu(w1 x) * w3 x)` in the layers whose published index
  is below `published.num_dense_layers`; after them `s = sigmoid(x W_r)`
  over all 32 experts, the chosen the top-4 of `s + expert_bias`, weights
  the chosen `s` over their sum + 1e-6 times `routed_scaling_factor`, and
  EVERY TOKEN'S FOUR EXPERTS ONE BY ONE: a plain loop over the experts,
  each applied to every token and weighted (zero where it was not chosen).

Departures from the published modelling code, each deliberate: the linears
are stored [in, out] and the convolution [K, C] (a transposition of the
checkpoint); the published cache keeps `conv_L_cache` = 3 columns and rolls
them, the window here is the K-1 = 2 inputs before the token, which carry
the same information; a stage of 13 of the 24 layers is the
configuration's cut, not the model's.

Returned for one sequence of T ids: the logits of the LAST
`check.logit_positions` positions, the attention layers' keys and values
[La, Hkv, T, Dh] as a cache would hold them (keys rotated), every conv
layer's window after the last token [Lc, K-1, D], the routing [Le, T, E]
over all E experts, what the attention layers' projections were given
[La, T, D], the attention layers' key and value projections and key norms
({"wk", "wv": [La, D, Hkv * Dh], "kn": [La, Dh], "eps", "theta"}:
`compare.readings` holds a side's keys and values to what these make of
that side's OWN input), the routed layers' experts from position
`check.restore_at` on, what they gave and what the SOUND experts give for
the same input, picks and weights ([2, Le, T - restore_at, D]; one array
twice where no control is on), and 0.0: a reference has no idle rows.

`CONTROLS`: the same reference with ONE stated precision a step lower
(`int8_weights`: every matrix in 8 bits, one scale per output channel, per
row for the embedding; `int8_kv`: keys and values in 8 bits, one scale per
token and head; `fp8_activations`: the input of every product through
float8_e4m3fn; `fp8_window`: the window's inputs `u` through float8_e4m3fn,
the bfloat16 window plane a step lower), and two controls of the family's
own mathematics: `no_router_bias`, the experts chosen by their scores alone
(the bias left out of the choice), and `window_zero_at_hit`, every conv
layer's window zeros at position `check.restore_at`, as a prefix hit that
restored no snapshot would start.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CONTROLS = ("int8_weights", "int8_kv", "fp8_activations", "fp8_window",
            "no_router_bias", "window_zero_at_hit")
HEADS_AT_ONCE = 16
ROUTE_EPS = 1e-6


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def _round_to_bits(x, axis, bits):
    top = float(2 ** (bits - 1) - 1)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top, 1e-8)
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _through_fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _same(x):
    return x


def rotate(x, theta):
    """x [H, T, Dh] rotated by its position, halves rotated (Llama's)."""
    dh, t = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@functools.partial(jax.jit, static_argnames=(
    "eps", "fp8", "fp8_window", "zero_at"))
def _conv(x, lw, *, eps, fp8=False, fp8_window=False, zero_at=0):
    """x + Conv(N(x)) for x [T, D], and the window a cache holds of the
    layer after the last token [K-1, D]."""
    act = _through_fp8 if fp8 else _same
    d = x.shape[1]
    h = act(rms_norm(x, lw["operator_norm.weight"], eps))
    bcz = h @ lw["conv.in_proj.weight"]
    u = bcz[:, :d] * bcz[:, 2 * d:]
    if fp8_window:
        # `reduce_precision`, not a cast there and back: the TPU's
        # compiler is allowed excess precision and drops such a pair here
        # (the first chip reading of this control was 0 on every number).
        u = jax.lax.reduce_precision(u, exponent_bits=4, mantissa_bits=3)
    w = lw["conv.conv.weight"]                                  # [K, D]
    k = w.shape[0]

    def token(window, at):
        u_t, pos = at
        # A hit that restored nothing starts from zeros.
        if zero_at:
            window = jnp.where(pos == zero_at, 0.0, window)
        seq = jnp.concatenate([window, u_t[None]])              # [K, D]
        return seq[1:], jnp.sum(seq * w, axis=0)

    window, c = jax.lax.scan(token, jnp.zeros((k - 1, d)),
                             (u, jnp.arange(x.shape[0])))
    y = bcz[:, d:2 * d] * c
    return x + act(y) @ lw["conv.out_proj.weight"], window


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "dh", "eps", "theta", "kv_bits", "fp8"))
def _attention(x, lw, *, heads, kv_heads, dh, eps, theta, kv_bits=None,
               fp8=False):
    """x + Attn(N(x)), the keys and values a cache holds of the layer
    [Hkv, T, Dh], and what the projections were given [T, D]."""
    act = _through_fp8 if fp8 else _same
    t = x.shape[0]
    h = act(rms_norm(x, lw["operator_norm.weight"], eps))

    def split(name, n):
        return (h @ lw[f"self_attn.{name}_proj.weight"]).reshape(
            t, n, dh).transpose(1, 0, 2)

    q = rotate(rms_norm(split("q", heads),
                          lw["self_attn.q_layernorm.weight"], eps), theta)
    k = rotate(rms_norm(split("k", kv_heads),
                          lw["self_attn.k_layernorm.weight"], eps), theta)
    v = split("v", kv_heads)
    if kv_bits:
        k = _round_to_bits(k, -1, kv_bits)
        v = _round_to_bits(v, -1, kv_bits)
    rep = heads // kv_heads
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]

    def some_heads(part):
        qq, kk, vv = part
        scores = jnp.einsum("hqd,hsd->hqs", act(qq), act(kk)) * dh ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,hsd->hqd", act(probs), act(vv))

    blocks = max(1, heads // HEADS_AT_ONCE)
    o = jax.lax.map(some_heads, tuple(
        a.reshape(blocks, heads // blocks, t, dh)
        for a in (q, jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0))))
    o = o.reshape(heads, t, dh).transpose(1, 0, 2).reshape(t, heads * dh)
    return x + act(o) @ lw["self_attn.out_proj.weight"], k, v, h


def _swiglu(h, w1, w3, w2, act):
    return act(jax.nn.silu(act(h) @ w1) * (act(h) @ w3)) @ w2


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _dense(x, lw, *, eps, fp8=False):
    act = _through_fp8 if fp8 else _same
    h = rms_norm(x, lw["ffn_norm.weight"], eps)
    return x + _swiglu(h, lw["feed_forward.w1.weight"],
                       lw["feed_forward.w3.weight"],
                       lw["feed_forward.w2.weight"], act)


@functools.partial(jax.jit, static_argnames=(
    "eps", "k", "norm", "scale", "fp8", "bias_in_choice"))
def _route(x, lw, *, eps, k, norm, scale, fp8=False, bias_in_choice=True):
    """(the normalised input [T, D], every expert's weight for every token
    [T, E], zero where it was not chosen, which were chosen [T, E] bool)."""
    act = _through_fp8 if fp8 else _same
    h = rms_norm(x, lw["ffn_norm.weight"], eps)
    s = jax.nn.sigmoid(act(h) @ lw["feed_forward.gate.weight"])
    choice = s + lw["feed_forward.expert_bias"] if bias_in_choice else s
    _, picks = jax.lax.top_k(choice, k)
    chosen = jnp.sum(jax.nn.one_hot(picks, s.shape[-1]), axis=1)   # [T, E]
    w = s * chosen
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    return h, w * scale, chosen > 0


@functools.partial(jax.jit, static_argnames=("fp8",))
def _some_experts(y, h, w, block, *, fp8=False):
    """y + the experts of `block` (their stacks [n, ...]), one by one, each
    over every token and weighted by its column of `w` [T, n]."""
    act = _through_fp8 if fp8 else _same

    def one(y, e):
        out = _swiglu(h, block["feed_forward.experts.w1"][e],
                      block["feed_forward.experts.w3"][e],
                      block["feed_forward.experts.w2"][e], act)
        return y + out * w[:, e, None], None

    return jax.lax.scan(one, y, jnp.arange(w.shape[1]))[0]


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, gain, embed, *, eps, fp8=False):
    h = rms_norm(x, gain, eps)
    return (_through_fp8(h) if fp8 else h) @ embed.T


def _int8(lw: dict) -> dict:
    """Every matrix of a layer in 8 bits, one scale per output channel, in
    place (the convolution's taps are a matrix [K, C] too)."""
    for name in list(lw):
        if lw[name].ndim >= 2:
            lw[name] = _round_to_bits(lw[name], -2, 8)
    return lw


def forward(w, ids, config: dict, control=None):
    """(logits [P, V] of the last P = `check.logit_positions` positions,
    keys [La, Hkv, T, Dh], values, conv windows [Lc, K-1, D], routing
    [Le, T, E] bool, the attention layers' input [La, T, D], their key
    and value projections and key norms, the experts' output from
    `check.restore_at` on beside the sound experts' on the same input
    [2, Le, T', D], 0.0: a reference has no idle rows), float32, for one
    sequence of token ids [T]. `w` is a `weights.Seeded` in float32; every size is the
    configuration file's."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control is called {control!r}: {CONTROLS}")
    from benchmarks.families.lfm2_moe.weights import EXPERT_BLOCK

    eps = float(config["norm_eps"])
    fp8 = control == "fp8_activations"
    int8 = control == "int8_weights"
    conv = dict(eps=eps, fp8=fp8, fp8_window=control == "fp8_window",
                zero_at=(int(config["check"]["restore_at"])
                         if control == "window_zero_at_hit" else 0))
    attn = dict(heads=int(config["num_attention_heads"]),
                kv_heads=int(config["num_key_value_heads"]),
                dh=int(config["head_dim"]), eps=eps,
                theta=float(config["rope_theta"]), fp8=fp8,
                kv_bits=8 if control == "int8_kv" else None)
    route = dict(eps=eps, k=int(config["num_experts_per_tok"]),
                 norm=bool(config["norm_topk_prob"]),
                 scale=float(config["routed_scaling_factor"]), fp8=fp8,
                 bias_in_choice=control != "no_router_bias")
    experts = int(config["num_experts"])
    rows = int(config["check"]["logit_positions"])
    tail = int(config["check"]["restore_at"])
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        embed = w.embed()
        if int8:
            embed = _round_to_bits(embed, -1, 8)
        x = embed[ids]
        keys, values, windows, routing, fed = [], [], [], [], []
        own = {"wk": [], "wv": [], "kn": []}
        gave, sound = [], []
        for layer, kind in enumerate(config["layer_types"]):
            lw = w.layer(layer, experts=False)
            if int8:
                lw = _int8(lw)
            if kind == "conv":
                x, window = _conv(x, lw, **conv)
                windows.append(window)
            else:
                x, k, v, h = _attention(x, lw, **attn)
                keys.append(k)
                values.append(v)
                fed.append(h)
                own["wk"].append(lw["self_attn.k_proj.weight"])
                own["wv"].append(lw["self_attn.v_proj.weight"])
                own["kn"].append(lw["self_attn.k_layernorm.weight"])
            if not _routed(config, layer):
                x = _dense(x, lw, eps=eps, fp8=fp8)
                continue
            h, weights, chosen = _route(x, lw, **route)
            routing.append(chosen)
            y = jnp.zeros_like(x)
            y_sound = jnp.zeros_like(x[tail:])
            for first in range(0, experts, EXPERT_BLOCK):
                count = min(EXPERT_BLOCK, experts - first)
                block = w.layer(layer, experts=(first, count))
                if control:
                    # The experts as they are, for this side's input,
                    # picks and weights.
                    y_sound = _some_experts(
                        y_sound, h[tail:],
                        weights[tail:, first:first + count], block)
                if int8:
                    block = _int8(block)
                y = _some_experts(y, h, weights[:, first:first + count],
                                  block, fp8=fp8)
                del block
            gave.append(y[tail:])
            sound.append(y_sound if control else y[tail:])
            x = x + y
            del lw
        logits = _head(x[-rows:], w.norm(), embed, eps=eps, fp8=fp8)
        return (logits, jnp.stack(keys), jnp.stack(values),
                jnp.stack(windows), jnp.stack(routing), jnp.stack(fed),
                dict({name: jnp.stack(parts) for name, parts in own.items()},
                     eps=eps, theta=attn["theta"]),
                jnp.stack([jnp.stack(gave), jnp.stack(sound)]), 0.0)


def _routed(config: dict, layer: int) -> bool:
    """Whether held layer `layer` has routed experts: its PUBLISHED index
    is at or past the published count of leading dense layers."""
    return (int(config["layers_kept"]["first"]) + layer
            >= int(config["published"]["num_dense_layers"]))
