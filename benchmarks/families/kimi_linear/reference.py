"""Plain kimi_linear forward pass: the benchmark's reference for
Kimi-Linear-48B-A3B.

`jax.numpy`, float32, products at `highest` precision, one sequence at once:
no cache, no batching, no kernel, no chunked form, no absorbed products and
no import from the program. Weights come a layer at a time
(`weights.Seeded.layer(l)` when the loop reaches l). The layers are ISSUE
44's (`families/kimi_linear/README.md` has the equations; each item the
published `config.json` does not settle is under `assumed` in the
configuration file):

- `h += mixer(N1(h))`, `h += mlp(N2(h))`; final RMSNorm, untied head;
- a KDA layer (`linear_attn_config.kda_layers`, numbered from 1): `q, k, v =
  silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))`, each a causal
  depthwise convolution over the last `short_conv_kernel_size` inputs (zeros
  before the sequence); q and k L2-normalised a head, q times `K ** -0.5`;
  `g = -exp(A_log[h]) softplus(x Wf_a Wf_b + dt_bias)` a head and channel;
  `beta = sigmoid(x Wb)`; THE RECURRENCE, TOKEN BY TOKEN from zeros:
  `S <- diag(exp(g)) S`, `u = beta (v - S^T k)`, `S <- S + k u^T`,
  `o = S^T q` per head; `Wo (RMSNorm_head(o) * sigmoid(x Wg_a Wg_b))`;
- an MLA layer (`full_attn_layers`) in its EXPANDED form: `q = x Wq` to
  heads of (128 | 64); `[c_kv | k_p] = x Wkva`; `c_kv = RMSNorm(c_kv)`; a
  head's keys are `[c_kv Wuk | k_p]` and its values `c_kv Wuv`; causal
  softmax at `(128 + 64) ** -0.5`; NO rotation; `Wo`;
- MLP: dense SwiGLU in the first `first_k_dense_replace` layers, then
  `s = sigmoid(x W_r)` over ALL `published.num_experts` experts; the chosen
  are the top-k of `s + e_score_correction_bias` (one group); weights the
  chosen `s` over their sum (+1e-20) times `routed_scaling_factor`;
  `y = sum over the chosen experts HELD of w_i SwiGLU_i(x) + Shared(x)`, by
  a plain loop over the held experts. What the absent experts would add is
  left out, as the program leaves it out.

Departures from the published modelling code, each deliberate: linears are
stored [in, out] and a convolution [K, C]; the chunked kernel `chunk_kda` is
the recurrence it computes, written as the recurrence; a chip's share of the
experts and of the vocabulary (the configuration file's `deployment`).

`forward` returns what `compare.readings` reads, a tuple: (logits [P, V] of
the last `check.logit_positions` positions, the latent cache [La, T, 576],
ssm [Lk, H, K, V], conv [Lk, conv-1, 3 H K], routing [Le, T, E] bool, the
same of the experts held, the KDA heads' time scales in tokens [Lk, H]
(`1 / (A dt)` at the channels' median `dt = softplus(dt_bias)`), what the
MLA layers' projections were given [La, T, D], (their `Wkva`, the gain of
the latent's norm, its epsilon), 0.0: a reference has no idle rows).

`CONTROLS`: the same reference with ONE stated precision a step lower
(`int8_weights`, `bf16_state`, `int8_latent`, `fp8_activations`), two
controls of the carry (`conv_window_dropped`, `state_dropped`: at every
boundary of a served prefill chunk the convolution, or the state, starts
from zeros) and two of this architecture's own mathematics:
`scalar_decay` (the decay made one number a head, the mean of `g` over its
channels: a gated delta rule without KDA's fine-grained gate) and
`no_correction` (`S^T k` left out of `u`: a gated linear attention without
the delta rule).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CONTROLS = ("int8_weights", "bf16_state", "int8_latent", "fp8_activations",
            "conv_window_dropped", "state_dropped", "scalar_decay",
            "no_correction")
HEADS_AT_ONCE = 8
L2_EPS = 1e-6


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def _round_to_bits(x, axis, bits):
    top = float(2 ** (bits - 1) - 1)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top, 1e-8)
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _through_fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _swiglu(x, wg, wu, wd, act):
    return act(jax.nn.silu(act(x) @ wg) * (act(x) @ wu)) @ wd


def _conv(x, w, pos, drop_every):
    """silu of the causal depthwise convolution of x [T, C] with taps w
    [K, C], zeros before the sequence, and the last K-1 inputs."""
    t, k = x.shape[0], w.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1])), x])
    out = 0.0
    for j in range(k):
        tap = padded[j:j + t] * w[j]        # the input k-1-j positions back
        if drop_every:
            # A chunk that lost its window sees zeros before its start.
            tap = jnp.where((pos - (k - 1 - j) >= pos // drop_every
                             * drop_every)[:, None], tap, 0.0)
        out = out + tap
    return jax.nn.silu(out), padded[t:t + k - 1]


@functools.partial(jax.jit, static_argnames=(
    "heads", "dk", "eps", "fp8", "bf16_state", "drop_every",
    "drop_state_every", "scalar_decay", "no_correction"))
def _kda(x, lw, *, heads, dk, eps, fp8=False, bf16_state=False,
         drop_every=0, drop_state_every=0, scalar_decay=False,
         no_correction=False):
    """x + KDA(N1(x)) for x [T, D], what a cache holds of the layer after
    the last token (the state [H, K, V] and the convolutions' last inputs
    [conv-1, 3 H K]) and the heads' time scales in tokens [H]."""
    act = _through_fp8 if fp8 else (lambda a: a)
    t = x.shape[0]
    pos = jnp.arange(t)
    h = act(_rms_norm(x, lw["input_layernorm.weight"], eps))
    parts, windows = [], []
    for n in "qkv":
        y, window = _conv(h @ lw[f"self_attn.{n}_proj.weight"],
                          lw[f"self_attn.{n}_conv1d.weight"], pos, drop_every)
        parts.append(act(y).reshape(t, heads, dk))
        windows.append(window)
    q, k, v = parts

    def unit(a):
        return a / jnp.sqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True)
                            + L2_EPS)

    q, k = unit(q) * dk ** -0.5, unit(k)
    a = jnp.exp(lw["self_attn.A_log"])                          # [H]
    dt = jax.nn.softplus(
        act(h @ lw["self_attn.f_a_proj.weight"])
        @ lw["self_attn.f_b_proj.weight"] + lw["self_attn.dt_bias"])
    g = -a[:, None] * dt.reshape(t, heads, dk)                  # [T, H, K]
    if scalar_decay:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(h @ lw["self_attn.b_proj.weight"])    # [T, H]

    def token(state, at):
        q_t, k_t, v_t, g_t, b_t, first = at
        # A chunk that lost its state starts from zeros.
        state = jnp.where(first, 0.0, state)
        state = jnp.exp(g_t)[:, :, None] * state                # [H, K, V]
        seen = (0.0 if no_correction
                else jnp.sum(state * k_t[:, :, None], axis=1))
        u = b_t[:, None] * (v_t - seen)                         # [H, V]
        state = state + k_t[:, :, None] * u[:, None, :]
        if bf16_state:
            # `reduce_precision`, not a cast there and back: the TPU's
            # compiler is allowed excess precision and drops such a pair.
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    lost = (pos % drop_state_every == 0 if drop_state_every
            else jnp.zeros((t,), bool))
    state, o = jax.lax.scan(token, jnp.zeros((heads, dk, dk)),
                            (q, k, v, g, beta, lost))
    gate = jax.nn.sigmoid(act(h @ lw["self_attn.g_a_proj.weight"])
                          @ lw["self_attn.g_b_proj.weight"])
    o = _rms_norm(o, lw["self_attn.o_norm.weight"], eps).reshape(t, -1) * gate
    median_dt = jnp.median(jax.nn.softplus(
        lw["self_attn.dt_bias"]).reshape(heads, dk), axis=-1)
    return (x + act(o) @ lw["self_attn.o_proj.weight"], state,
            jnp.concatenate(windows, axis=-1), 1.0 / (a * median_dt))


@functools.partial(jax.jit, static_argnames=(
    "heads", "dn", "dr", "dv", "kr", "eps", "latent_bits", "fp8"))
def _mla(x, lw, *, heads, dn, dr, dv, kr, eps, latent_bits=None, fp8=False):
    """x + MLA(N1(x)), what a cache holds of the layer, `[c_kv | k_p]`
    [T, kr + dr], and what the projections were given [T, D]."""
    act = _through_fp8 if fp8 else (lambda a: a)
    t = x.shape[0]
    h = act(_rms_norm(x, lw["input_layernorm.weight"], eps))
    q = (h @ lw["self_attn.q_proj.weight"]).reshape(
        t, heads, dn + dr).transpose(1, 0, 2)                   # [H, T, .]
    kva = h @ lw["self_attn.kv_a_proj_with_mqa.weight"]
    c_kv = _rms_norm(kva[:, :kr], lw["self_attn.kv_a_layernorm.weight"], eps)
    k_p = kva[:, kr:]
    if latent_bits:
        c_kv = _round_to_bits(c_kv, -1, latent_bits)
        k_p = _round_to_bits(k_p, -1, latent_bits)
    kv = (act(c_kv) @ lw["self_attn.kv_b_proj.weight"]).reshape(
        t, heads, dn + dv).transpose(1, 0, 2)
    keys = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_p, (heads, t, dr))], axis=-1)
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]
    scale = (dn + dr) ** -0.5

    def some_heads(part):
        qq, kk, vv = part
        scores = jnp.einsum("hqd,hsd->hqs", act(qq), act(kk)) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,hsd->hqd", act(probs), act(vv))

    blocks = max(1, heads // HEADS_AT_ONCE)
    o = jax.lax.map(some_heads, tuple(
        a.reshape(blocks, heads // blocks, *a.shape[1:])
        for a in (q, keys, kv[..., dn:])))
    o = o.reshape(heads, t, dv).transpose(1, 0, 2).reshape(t, heads * dv)
    return (x + act(o) @ lw["self_attn.o_proj.weight"],
            jnp.concatenate([c_kv, k_p], axis=-1), h)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _dense_mlp(x, lw, *, eps, fp8=False):
    act = _through_fp8 if fp8 else (lambda a: a)
    h = _rms_norm(x, lw["post_attention_layernorm.weight"], eps)
    return x + _swiglu(h, lw["mlp.gate_proj.weight"],
                       lw["mlp.up_proj.weight"], lw["mlp.down_proj.weight"],
                       act)


@functools.partial(jax.jit, static_argnames=(
    "eps", "k", "norm", "scale", "first", "fp8"))
def _experts(x, lw, *, eps, k, norm, scale, first, fp8=False):
    """x + (the held experts' part + shared), and which of ALL experts
    each token chose [T, E] bool. The stacks hold experts `first` ..
    `first + held - 1` of the router's E."""
    act = _through_fp8 if fp8 else (lambda a: a)
    m = "block_sparse_moe."
    h = _rms_norm(x, lw["post_attention_layernorm.weight"], eps)
    s = jax.nn.sigmoid(act(h) @ lw[m + "gate.weight"])
    _, picks = jax.lax.top_k(s + lw[m + "gate.e_score_correction_bias"], k)
    chosen = jnp.sum(jax.nn.one_hot(picks, s.shape[-1]), axis=1)   # [T, E]
    w = s * chosen
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * scale

    def one(y, e):
        out = _swiglu(h, lw[m + "experts.w1"][e], lw[m + "experts.w3"][e],
                      lw[m + "experts.w2"][e], act)
        return y + out * w[:, first + e, None], None

    held = lw[m + "experts.w1"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    y = y + _swiglu(h, lw[m + "shared_experts.gate_proj.weight"],
                    lw[m + "shared_experts.up_proj.weight"],
                    lw[m + "shared_experts.down_proj.weight"], act)
    return x + y, chosen > 0


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, gain, head, *, eps, fp8=False):
    h = _rms_norm(x, gain, eps)
    return (_through_fp8(h) if fp8 else h) @ head.T


def _int8(lw: dict) -> dict:
    """Every matrix of a layer in 8 bits, one scale per output channel, in
    place (a convolution's taps are a matrix [K, C] too)."""
    for name in list(lw):
        if lw[name].ndim >= 2:
            lw[name] = _round_to_bits(lw[name], -2, 8)
    return lw


def layer_kinds(config: dict) -> list:
    """True for a KDA layer, False for an MLA layer, by the layer's place
    from 0."""
    lin = config["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    n = int(config["num_hidden_layers"])
    if kda | full != set(range(1, n + 1)) or kda & full:
        raise ValueError(f"kda_layers {sorted(kda)} and full_attn_layers "
                         f"{sorted(full)} do not part layers 1 to {n}")
    return [i + 1 in kda for i in range(n)]


def mixer_args(config: dict, control=None) -> tuple:
    """The keyword arguments of `_kda`, `_mla` and `_experts` for a
    configuration file (and a control)."""
    eps = float(config["rms_norm_eps"])
    fp8 = control == "fp8_activations"
    chunk = int(config["serving"]["prefill_chunk_tokens"])
    lin = config["linear_attn_config"]
    kda = dict(
        heads=int(lin["num_heads"]), dk=int(lin["head_dim"]), eps=eps,
        fp8=fp8, bf16_state=control == "bf16_state",
        drop_every=(chunk if control == "conv_window_dropped" else 0),
        drop_state_every=(chunk if control == "state_dropped" else 0),
        scalar_decay=control == "scalar_decay",
        no_correction=control == "no_correction")
    mla = dict(heads=int(config["num_attention_heads"]),
               dn=int(config["qk_nope_head_dim"]),
               dr=int(config["qk_rope_head_dim"]),
               dv=int(config["v_head_dim"]), kr=int(config["kv_lora_rank"]),
               eps=eps, fp8=fp8,
               latent_bits=8 if control == "int8_latent" else None)
    routed = dict(eps=eps, k=int(config["num_experts_per_token"]),
                  norm=bool(config["moe_renormalize"]),
                  scale=float(config["routed_scaling_factor"]),
                  first=int(config["experts_held"]["first"]), fp8=fp8)
    return kda, mla, routed


def forward(w, ids, config: dict, control=None):
    """The tuple the module's head lists, float32, for one sequence of
    token ids [T]. `w` is a `weights.Seeded` in float32; every size is the
    configuration file's."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control is called {control!r}: {CONTROLS}")
    kda, mla, routed = mixer_args(config, control)
    eps, fp8 = kda["eps"], kda["fp8"]
    dense_layers = int(config["first_k_dense_replace"])
    rows = int(config["check"]["logit_positions"])
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        embed = w.embed()
        if control == "int8_weights":
            embed = _round_to_bits(embed, -1, 8)
        x = embed[ids]
        del embed
        latent, ssm, conv, scales, routing = [], [], [], [], []
        fed, w_kva, gains = [], [], []
        for layer, is_kda in enumerate(layer_kinds(config)):
            lw = w.layer(layer)
            if control == "int8_weights":
                lw = _int8(lw)
            if is_kda:
                x, state, window, scale = _kda(x, lw, **kda)
                ssm.append(state)
                conv.append(window)
                scales.append(scale)
            else:
                x, held, h = _mla(x, lw, **mla)
                latent.append(held)
                fed.append(h)
                w_kva.append(lw["self_attn.kv_a_proj_with_mqa.weight"])
                gains.append(lw["self_attn.kv_a_layernorm.weight"])
            if layer < dense_layers:
                x = _dense_mlp(x, lw, eps=eps, fp8=fp8)
            else:
                x, chosen = _experts(x, lw, **routed)
                routing.append(chosen)
            del lw
        head = w.head()
        if control == "int8_weights":
            head = _round_to_bits(head, -1, 8)
        logits = _head(x[-rows:], w.norm(), head, eps=eps, fp8=fp8)
        routing = jnp.stack(routing)
        first, held = routed["first"], int(config["num_experts"])
        return (logits, jnp.stack(latent), jnp.stack(ssm), jnp.stack(conv),
                routing, routing[..., first:first + held],
                jnp.stack(scales), jnp.stack(fed),
                (jnp.stack(w_kva), jnp.stack(gains), eps), 0.0)
