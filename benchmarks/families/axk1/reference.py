"""Plain axk1 forward pass: the benchmark's reference for A.X-K1.

`jax.numpy`, float32, products at `highest` precision, one sequence at once:
no cache, no batching, no kernel, no absorbed product and no import from the
program. Weights come a layer at a time (`weights.Seeded.layer(l)` when the
loop reaches l). The layers are ISSUE 34's, in the PUBLISHED (expanded)
form, keys and values of every head made from the latent
(`families/axk1/README.md` has the equations; each item the published
`config.json` does not settle is under `assumed` in the configuration file):

- `h += Attn(N1(h))`, `h += Mlp(N2(h))`, RMSNorm eps from the file; final
  RMSNorm, untied head;
- attention: `c_q = Nq(x Wqa)`, `q = c_q Wqb` -> heads x (nope | rope);
  `[c_kv | k_r] = x Wkva`, `c_kv = Nkv(c_kv)`; YaRN rotary embedding
  (rotate-half) on `q_r` and on the one `k_r` all heads share;
  `[k_nope | v] = c_kv Wkvb` -> heads x (nope + v); `scores = (q_nope .
  k_nope + q_r . k_r) * s`, `s = (nope + rope)^-0.5 * m^2`,
  `m = 0.1 mscale_all_dim ln(factor) + 1`; causal, softmax; `o Wo`;
- dense layer: `Wd (silu(Wg x) * Wu x)`;
- expert layer: `sc = sigmoid(x Wr)` over ALL `published.n_routed_experts`
  experts; the chosen are the top-k of `sc` (no bias, no group limit);
  weights `sc_i / (sum of the chosen + 1e-20) * routed_scaling_factor`;
  `y = sum over the chosen experts HELD of w_i E_i(x) + Shared(x)`: the
  experts `experts_held.first` .. `+ n_routed_experts - 1`, by a plain
  loop (every held expert on every token, times the weight the routing
  gives it, 0 where it was not chosen). What the absent experts would add
  is left out, as the program leaves it out: that partial result is what
  goes on to the next layer.

Returned are the logits of the LAST `check.logit_positions` positions, the
latent cache's contents as a cache would hold them (`c_kv` after its norm
[L, T, kv_lora_rank], `k_r` after its rotation [L, T, rope]), the routing
[Le, T, E] over all E experts and its columns of the experts held.

`CONTROLS`: the same reference with ONE stated precision a step lower
(`int8_weights`: every matrix in 8 bits, one scale per output channel, per
row for embedding and head; `int8_latent`: `c_kv` and `k_r` in 8 bits, one
scale per token and plane; `fp8_activations`: the input of every product
through float8_e4m3fn), and one control of the mathematics,
`no_rope_term`: `q_r . k_r` left out of the scores.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CONTROLS = ("int8_weights", "int8_latent", "fp8_activations", "no_rope_term")
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


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_tables(t: int, dim: int, theta: float, scaling: dict):
    """cos and sin [T, dim] of YaRN at positions 0..T-1 (DeepSeek-V3's
    `DeepseekV3YarnRotaryEmbedding`): a dimension that turns more than
    `beta_fast` times over the original context keeps the base's frequency,
    one that turns fewer than `beta_slow` times has it divided by `factor`,
    a linear ramp between; the tables are multiplied by
    `mscale / mscale_all_dim` (as functions of `factor`)."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_to_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_to_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(turns_to_dim(float(scaling["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * base)) * ramp + (1.0 / base) * (1.0 - ramp)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    own = _mscale(factor, float(scaling["mscale"])) / _mscale(
        factor, float(scaling["mscale_all_dim"]))
    cos = np.concatenate([np.cos(ang)] * 2, axis=-1) * own
    sin = np.concatenate([np.sin(ang)] * 2, axis=-1) * own
    return jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def softmax_scale(config: dict) -> float:
    s = config["rope_scaling"]
    m = _mscale(float(s["factor"]), float(s["mscale_all_dim"]))
    return (int(config["qk_nope_head_dim"])
            + int(config["qk_rope_head_dim"])) ** -0.5 * m * m


def _rotate(x, cos, sin):
    """Rotate-half on [..., T, dim]."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _swiglu(x, wg, wu, wd, act):
    return act(jax.nn.silu(act(x) @ wg) * (act(x) @ wu)) @ wd


@functools.partial(jax.jit, static_argnames=(
    "heads", "dn", "dr", "dv", "kr", "eps", "scale", "latent_bits", "fp8",
    "rope_term"))
def _attention(x, lw, cos, sin, *, heads, dn, dr, dv, kr, eps, scale,
               latent_bits=None, fp8=False, rope_term=True):
    """x + Attn(N1(x)), and what a cache holds of the layer: c_kv [T, kr]
    and k_r [T, dr]."""
    act = _through_fp8 if fp8 else (lambda a: a)
    t = x.shape[0]
    h = act(_rms_norm(x, lw["input_layernorm.weight"], eps))
    c_q = _rms_norm(h @ lw["self_attn.q_a_proj.weight"],
                    lw["self_attn.q_a_layernorm.weight"], eps)
    q = (act(c_q) @ lw["self_attn.q_b_proj.weight"]).reshape(
        t, heads, dn + dr).transpose(1, 0, 2)                 # [H, T, .]
    q_nope, q_r = q[..., :dn], _rotate(q[..., dn:], cos, sin)
    kva = h @ lw["self_attn.kv_a_proj_with_mqa.weight"]
    c_kv = _rms_norm(kva[:, :kr], lw["self_attn.kv_a_layernorm.weight"], eps)
    k_r = _rotate(kva[:, kr:], cos, sin)
    if latent_bits:
        c_kv = _round_to_bits(c_kv, -1, latent_bits)
        k_r = _round_to_bits(k_r, -1, latent_bits)
    kv = (act(c_kv) @ lw["self_attn.kv_b_proj.weight"]).reshape(
        t, heads, dn + dv).transpose(1, 0, 2)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]

    def some_heads(part):
        """A block of heads at once: 64 heads' scores of 2,336 positions
        are 1.4 GB in float32, and softmax holds three of them."""
        qn, qr, kn, vv = part
        scores = jnp.einsum("hqd,hsd->hqs", act(qn), act(kn))
        if rope_term:
            scores = scores + jnp.einsum("hqd,sd->hqs", act(qr), act(k_r))
        probs = jax.nn.softmax(jnp.where(seen, scores * scale, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hqs,hsd->hqd", act(probs), act(vv))

    blocks = max(1, heads // HEADS_AT_ONCE)
    o = jax.lax.map(some_heads, tuple(
        a.reshape(blocks, heads // blocks, *a.shape[1:])
        for a in (q_nope, q_r, k_nope, v)))
    o = o.reshape(heads, t, dv).transpose(1, 0, 2).reshape(t, heads * dv)
    return x + act(o) @ lw["self_attn.o_proj.weight"], c_kv, k_r


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _dense_mlp(x, lw, *, eps, fp8=False):
    act = _through_fp8 if fp8 else (lambda a: a)
    h = _rms_norm(x, lw["post_attention_layernorm.weight"], eps)
    return x + _swiglu(h, lw["mlp.gate_proj.weight"],
                       lw["mlp.up_proj.weight"], lw["mlp.down_proj.weight"],
                       act)


@functools.partial(jax.jit, static_argnames=(
    "eps", "k", "norm", "scale", "first", "fp8"))
def _expert_mlp(x, lw, *, eps, k, norm, scale, first, fp8=False):
    """x + (the held experts' part + shared), and which of ALL experts
    each token chose [T, E] bool. The stacks hold experts `first` ..
    `first + held - 1` of the router's E."""
    act = _through_fp8 if fp8 else (lambda a: a)
    h = _rms_norm(x, lw["post_attention_layernorm.weight"], eps)
    s = jax.nn.sigmoid(act(h) @ lw["mlp.gate.weight"])
    _, picks = jax.lax.top_k(s, k)
    chosen = jnp.sum(jax.nn.one_hot(picks, s.shape[-1]), axis=1)   # [T, E]
    w = s * chosen
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * scale

    def one(y, e):
        out = _swiglu(h, lw["mlp.experts.gate_proj"][e],
                      lw["mlp.experts.up_proj"][e],
                      lw["mlp.experts.down_proj"][e], act)
        return y + out * w[:, first + e, None], None

    held = lw["mlp.experts.gate_proj"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    y = y + _swiglu(h, lw["mlp.shared_experts.gate_proj.weight"],
                    lw["mlp.shared_experts.up_proj.weight"],
                    lw["mlp.shared_experts.down_proj.weight"], act)
    return x + y, chosen > 0


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, gain, head, *, eps, fp8=False):
    h = _rms_norm(x, gain, eps)
    return (_through_fp8(h) if fp8 else h) @ head.T


def _int8(lw: dict) -> dict:
    """Every matrix of a layer in 8 bits, one scale per output channel, in
    place."""
    for name in list(lw):
        if lw[name].ndim >= 2:
            lw[name] = _round_to_bits(lw[name], -2, 8)
    return lw


def forward(w, ids, config: dict, control=None):
    """(logits [P, V] of the last P = `check.logit_positions` positions,
    c_kv [L, T, kv_lora_rank], k_r [L, T, rope], routing [Le, T, E] bool,
    the same of the experts held [Le, T, held]), float32, for one sequence
    of token ids [T]. `w` is a `weights.Seeded`
    in float32; every size is the configuration file's."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control is called {control!r}: {CONTROLS}")
    eps = float(config["rms_norm_eps"])
    fp8 = control == "fp8_activations"
    dr = int(config["qk_rope_head_dim"])
    attn = dict(
        heads=int(config["num_attention_heads"]),
        dn=int(config["qk_nope_head_dim"]), dr=dr,
        dv=int(config["v_head_dim"]), kr=int(config["kv_lora_rank"]),
        eps=eps, scale=softmax_scale(config), fp8=fp8,
        latent_bits=8 if control == "int8_latent" else None,
        rope_term=control != "no_rope_term")
    routed = dict(eps=eps, k=int(config["num_experts_per_tok"]),
                  norm=bool(config["norm_topk_prob"]),
                  scale=float(config["routed_scaling_factor"]),
                  first=int(config["experts_held"]["first"]), fp8=fp8)
    rows = int(config["check"]["logit_positions"])
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        cos, sin = yarn_tables(ids.shape[0], dr, float(config["rope_theta"]),
                               config["rope_scaling"])
        embed = w.embed()
        if control == "int8_weights":
            embed = _round_to_bits(embed, -1, 8)
        x = embed[ids]
        del embed
        latents, rotary, routing = [], [], []
        for layer in range(int(config["num_hidden_layers"])):
            lw = w.layer(layer)
            if control == "int8_weights":
                lw = _int8(lw)
            x, c_kv, k_r = _attention(x, lw, cos, sin, **attn)
            latents.append(c_kv)
            rotary.append(k_r)
            if layer < int(config["first_k_dense_replace"]):
                x = _dense_mlp(x, lw, eps=eps, fp8=fp8)
            else:
                x, chosen = _expert_mlp(x, lw, **routed)
                routing.append(chosen)
            del lw
        head = w.head()
        if control == "int8_weights":
            head = _round_to_bits(head, -1, 8)
        logits = _head(x[-rows:], w.norm(), head, eps=eps, fp8=fp8)
        routing = jnp.stack(routing)
        first, held = routed["first"], int(config["n_routed_experts"])
        return (logits, jnp.stack(latents), jnp.stack(rotary), routing,
                routing[..., first:first + held])
