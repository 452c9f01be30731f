"""Plain nemotron_h forward pass: the benchmark's reference for
Nemotron-3-Nano.

`jax.numpy`, float32, products at `highest` precision, one sequence at once:
no cache, no batching, no kernel, no chunked form and no import from the
program. Weights come a block at a time (`weights.Seeded.layer(l)` when the
loop reaches l). The blocks are ISSUE 40's (`families/nemotron_h/README.md`
has the equations; each item the published `config.json` does not settle is
under `assumed` in the configuration file):

- `h += mixer(RMSNorm(h))`, one norm a block, the mixer by the block's
  letter in `hybrid_override_pattern`; final RMSNorm, untied head;
- `M`: `[z | xBC | dt] = x W_in`; `xBC = silu(conv(xBC) + b)`, a causal
  depthwise convolution over the last `conv_kernel` inputs (zeros before
  the sequence); `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`; THE
  RECURRENCE, TOKEN BY TOKEN: `S <- exp(dt A) S + dt x B^T`, `y = S C +
  D x` per head (8 heads share a group's B and C); `y = RMSNorm over
  groups of 512 (y * silu(z))`; `y W_out`;
- `E`: `s = sigmoid(x W_r)` over ALL `published.n_routed_experts` experts;
  the chosen are the top-k of `s + e_score_correction_bias` (no group
  limit); weights the chosen `s` over their sum (+1e-20) times
  `routed_scaling_factor`; `y = sum over the chosen experts HELD of
  w_i W_down relu(W_up x)^2 + Shared(x)`, by a plain loop over the held
  experts. What the absent experts would add is left out, as the program
  leaves it out;
- `*`: 32 query heads on 2 key/value heads of 128, causal, scale
  128^-0.5, no position signal, softmax in float32.

Departures from the published modelling code, each deliberate: the linears
are stored [in, out] and the convolution [K, C] (a transposition of the
checkpoint); the chunked SSD scan of `mamba_chunk_scan_combined` is the
recurrence it computes, written as the recurrence; `dt` is not clamped
(`time_step_limit` is (0, inf) there); a chip's share of the experts and of
the vocabulary is the configuration's cut, not the model's.

Returned for one sequence of T ids: the logits of the LAST
`check.logit_positions` positions, the attention blocks' keys and values
[La, Hkv, T, Dh] as a cache would hold them, every Mamba block's state
after the last token (`ssm` [Lm, H, P, N], `conv` [Lm, K-1, C]: the last
K-1 inputs of the convolution), the routing [Le, T, E] over all E experts,
its columns of the experts held, every Mamba head's time scale in
tokens [Lm, H], `1 / (A dt)` at `dt = softplus(dt_bias)`: how many tokens
back a head's state still holds (`compare.readings` reads the state through
the heads that hold the long context), and what the attention blocks'
projections were given with the key and value projections themselves
(`compare.readings` holds a side's keys and values to the float32 products
of that side's own input).

`CONTROLS`: the same reference with ONE stated precision a step lower
(`int8_weights`: every matrix in 8 bits, one scale per output channel, per
row for embedding and head; `bf16_state`: the recurrent state rounded to
bfloat16 after every token; `int8_kv`: keys and values in 8 bits, one scale
per token and head; `fp8_activations`: the input of every product through
float8_e4m3fn), and two controls of the mathematics that touches the
carry: at every boundary of a served prefill chunk
(`serving.prefill_chunk_tokens`) the convolution starts from zeros
(`conv_window_dropped`) or the state does (`state_dropped`), as a chunk form
that failed to carry its window, or its state, would.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CONTROLS = ("int8_weights", "bf16_state", "int8_kv", "fp8_activations",
            "conv_window_dropped", "state_dropped")
HEADS_AT_ONCE = 16


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def _round_to_bits(x, axis, bits):
    top = float(2 ** (bits - 1) - 1)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top, 1e-8)
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _through_fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _relu2(x, wu, wd, act):
    return act(jnp.square(jax.nn.relu(act(x) @ wu))) @ wd


@functools.partial(jax.jit, static_argnames=(
    "heads", "p", "groups", "n", "eps", "fp8", "bf16_state", "drop_every",
    "drop_state_every"))
def _mamba(x, lw, *, heads, p, groups, n, eps, fp8=False, bf16_state=False,
           drop_every=0, drop_state_every=0):
    """x + Mamba2(N(x)) for x [T, D], what a cache holds of the block
    after the last token (the state [H, P, N] and the convolution's last
    K-1 inputs [K-1, C]) and the heads' time scales in tokens [H]."""
    act = _through_fp8 if fp8 else (lambda a: a)
    t = x.shape[0]
    di, gn = heads * p, groups * n
    u = act(_rms_norm(x, lw["norm.weight"], eps))
    zxbcdt = u @ lw["mixer.in_proj.weight"]
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * gn],
                  zxbcdt[:, 2 * di + 2 * gn:])
    w = lw["mixer.conv1d.weight"]                               # [K, C]
    k = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    pos = jnp.arange(t)
    conv = lw["mixer.conv1d.bias"]
    for j in range(k):
        tap = padded[j:j + t] * w[j]        # the input k-1-j positions back
        if drop_every:
            # A chunk that lost its window sees zeros before its start.
            tap = jnp.where((pos - (k - 1 - j) >= pos // drop_every
                             * drop_every)[:, None], tap, 0.0)
        conv = conv + tap
    conv = jax.nn.silu(conv)
    xs = act(conv[:, :di]).reshape(t, heads, p)
    bs = act(conv[:, di:di + gn]).reshape(t, groups, n)
    cs = act(conv[:, di + gn:]).reshape(t, groups, n)
    dt = jax.nn.softplus(dt + lw["mixer.dt_bias"])              # [T, H]
    a = -jnp.exp(lw["mixer.A_log"])

    def token(state, at):
        x_t, b_t, c_t, dt_t, first = at
        # A chunk that lost its state starts from zeros.
        state = jnp.where(first, 0.0, state)
        b_h = jnp.repeat(b_t, heads // groups, axis=0)          # [H, N]
        c_h = jnp.repeat(c_t, heads // groups, axis=0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        if bf16_state:
            # `reduce_precision`, not a cast there and back: the TPU's
            # compiler is allowed excess precision and drops such a pair.
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        y = jnp.sum(state * c_h[:, None, :], axis=-1)           # [H, P]
        return state, y + lw["mixer.D"][:, None] * x_t

    lost = (pos % drop_state_every == 0 if drop_state_every
            else jnp.zeros((t,), bool))
    state, y = jax.lax.scan(token, jnp.zeros((heads, p, n)),
                            (xs, bs, cs, dt, lost))
    y = y.reshape(t, di) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(t, groups, di // groups), 1.0, eps).reshape(
        t, di) * lw["mixer.norm.weight"]
    return (x + act(y) @ lw["mixer.out_proj.weight"], state,
            padded[t:t + k - 1],
            -1.0 / (a * jax.nn.softplus(lw["mixer.dt_bias"])))


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "dh", "eps", "kv_bits", "fp8"))
def _attention(x, lw, *, heads, kv_heads, dh, eps, kv_bits=None, fp8=False):
    """x + Attn(N(x)), the keys and values a cache holds of the block
    [Hkv, T, Dh], and what the projections were given [T, D]."""
    act = _through_fp8 if fp8 else (lambda a: a)
    t = x.shape[0]
    h = act(_rms_norm(x, lw["norm.weight"], eps))
    q = (h @ lw["mixer.q_proj.weight"]).reshape(t, heads, dh).transpose(
        1, 0, 2)
    k = (h @ lw["mixer.k_proj.weight"]).reshape(t, kv_heads, dh).transpose(
        1, 0, 2)
    v = (h @ lw["mixer.v_proj.weight"]).reshape(t, kv_heads, dh).transpose(
        1, 0, 2)
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
    return x + act(o) @ lw["mixer.o_proj.weight"], k, v, h


@functools.partial(jax.jit, static_argnames=(
    "eps", "k", "norm", "scale", "first", "fp8"))
def _experts(x, lw, *, eps, k, norm, scale, first, fp8=False):
    """x + (the held experts' part + shared), and which of ALL experts
    each token chose [T, E] bool. The stacks hold experts `first` ..
    `first + held - 1` of the router's E."""
    act = _through_fp8 if fp8 else (lambda a: a)
    h = _rms_norm(x, lw["norm.weight"], eps)
    s = jax.nn.sigmoid(act(h) @ lw["mixer.gate.weight"])
    _, picks = jax.lax.top_k(s + lw["mixer.gate.e_score_correction_bias"], k)
    chosen = jnp.sum(jax.nn.one_hot(picks, s.shape[-1]), axis=1)   # [T, E]
    w = s * chosen
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * scale

    def one(y, e):
        out = _relu2(h, lw["mixer.experts.up_proj"][e],
                     lw["mixer.experts.down_proj"][e], act)
        return y + out * w[:, first + e, None], None

    held = lw["mixer.experts.up_proj"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    y = y + _relu2(h, lw["mixer.shared_experts.up_proj.weight"],
                   lw["mixer.shared_experts.down_proj.weight"], act)
    return x + y, chosen > 0


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, gain, head, *, eps, fp8=False):
    h = _rms_norm(x, gain, eps)
    return (_through_fp8(h) if fp8 else h) @ head.T


def _int8(lw: dict) -> dict:
    """Every matrix of a block in 8 bits, one scale per output channel, in
    place (the convolution's taps are a matrix [K, C] too)."""
    for name in list(lw):
        if lw[name].ndim >= 2:
            lw[name] = _round_to_bits(lw[name], -2, 8)
    return lw


def forward(w, ids, config: dict, control=None):
    """(logits [P, V] of the last P = `check.logit_positions` positions,
    keys [La, Hkv, T, Dh], values, ssm [Lm, H, P, N], conv [Lm, K-1, C],
    routing [Le, T, E] bool, the same of the experts held [Le, T, held],
    the Mamba heads' time scales [Lm, H], the attention blocks' input
    [La, T, D], their key and value projections ([La, D, Hkv * Dh] each),
    0.0: a reference has no idle rows), float32, for one sequence of token
    ids [T]. `w` is a `weights.Seeded` in float32; every size is the
    configuration file's."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control is called {control!r}: {CONTROLS}")
    eps = float(config["layer_norm_epsilon"])
    fp8 = control == "fp8_activations"
    chunk = int(config["serving"]["prefill_chunk_tokens"])
    mamba = dict(
        heads=int(config["mamba_num_heads"]), p=int(config["mamba_head_dim"]),
        groups=int(config["n_groups"]), n=int(config["ssm_state_size"]),
        eps=eps, fp8=fp8, bf16_state=control == "bf16_state",
        drop_every=(chunk if control == "conv_window_dropped" else 0),
        drop_state_every=(chunk if control == "state_dropped" else 0))
    attn = dict(heads=int(config["num_attention_heads"]),
                kv_heads=int(config["num_key_value_heads"]),
                dh=int(config["head_dim"]), eps=eps, fp8=fp8,
                kv_bits=8 if control == "int8_kv" else None)
    routed = dict(eps=eps, k=int(config["num_experts_per_tok"]),
                  norm=bool(config["norm_topk_prob"]),
                  scale=float(config["routed_scaling_factor"]),
                  first=int(config["experts_held"]["first"]), fp8=fp8)
    rows = int(config["check"]["logit_positions"])
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        embed = w.embed()
        if control == "int8_weights":
            embed = _round_to_bits(embed, -1, 8)
        x = embed[ids]
        del embed
        keys, values, ssm, conv, scales, routing = [], [], [], [], [], []
        fed, w_keys, w_values = [], [], []
        for layer, kind in enumerate(config["hybrid_override_pattern"]):
            lw = w.layer(layer)
            if control == "int8_weights":
                lw = _int8(lw)
            if kind == "M":
                x, state, window, scale = _mamba(x, lw, **mamba)
                ssm.append(state)
                conv.append(window)
                scales.append(scale)
            elif kind == "*":
                x, k, v, h = _attention(x, lw, **attn)
                keys.append(k)
                values.append(v)
                fed.append(h)
                w_keys.append(lw["mixer.k_proj.weight"])
                w_values.append(lw["mixer.v_proj.weight"])
            else:
                x, chosen = _experts(x, lw, **routed)
                routing.append(chosen)
            del lw
        head = w.head()
        if control == "int8_weights":
            head = _round_to_bits(head, -1, 8)
        logits = _head(x[-rows:], w.norm(), head, eps=eps, fp8=fp8)
        routing = jnp.stack(routing)
        first, held = routed["first"], int(config["n_routed_experts"])
        return (logits, jnp.stack(keys), jnp.stack(values), jnp.stack(ssm),
                jnp.stack(conv), routing, routing[..., first:first + held],
                jnp.stack(scales), jnp.stack(fed),
                (jnp.stack(w_keys), jnp.stack(w_values)), 0.0)
