"""Plain GPT-2 forward pass: the benchmark's reference.

`jax.numpy`, float32, matmuls at `highest` precision, the whole sequence at
once: no cache, no batching, no quantisation, and no import from the
program. It follows the published model (Radford et al. 2019; the
`GPT2LMHeadModel` layout): learned position embeddings, pre-LayerNorm
blocks, one fused QKV projection, causal softmax attention, tanh-GELU MLP,
final LayerNorm, output head tied to the token embedding. Weights are the
stacked published-name dictionary `weights.py` beside this file draws from
the seed.

`CONTROLS` are the controls of the `correct` comparison: the same reference
with ONE stated precision taken to the nearest step below it, everything
else in float32 so that nothing else differs. The configurations serve int8
weights, int8 K and V and bfloat16 activations, so: `int4_weights` (every
matrix in 4 bits per output channel), `int4_kv` (K and V in 4 bits, one
scale per head and token, the cache's own granularity) and
`fp8_activations` (the input of every matrix product through
float8_e4m3fn).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MATRICES = ("attn.c_attn.weight", "attn.c_proj.weight", "mlp.c_fc.weight",
            "mlp.c_proj.weight")
CONTROLS = ("int4_weights", "int4_kv", "fp8_activations")


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _round_to_bits(x, axis, bits):
    top = float(2 ** (bits - 1) - 1)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top, 1e-8)
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _through_fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "kv_bits", "fp8"))
def _block(x, lw, *, n_head, eps, kv_bits=None, fp8=False):
    act = _through_fp8 if fp8 else (lambda a: a)
    t, d = x.shape
    h = _layer_norm(x, lw["ln_1.weight"], lw["ln_1.bias"], eps)
    qkv = act(h) @ lw["attn.c_attn.weight"] + lw["attn.c_attn.bias"]
    q, k, v = (a.reshape(t, n_head, d // n_head).transpose(1, 0, 2)
               for a in jnp.split(qkv, 3, axis=-1))
    if kv_bits:
        k, v = _round_to_bits(k, -1, kv_bits), _round_to_bits(v, -1, kv_bits)
    scores = q @ k.transpose(0, 2, 1) / jnp.sqrt(float(d // n_head))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = (probs @ v).transpose(1, 0, 2).reshape(t, d)
    x = x + act(a) @ lw["attn.c_proj.weight"] + lw["attn.c_proj.bias"]
    h = _layer_norm(x, lw["ln_2.weight"], lw["ln_2.bias"], eps)
    m = _gelu_new(act(h) @ lw["mlp.c_fc.weight"] + lw["mlp.c_fc.bias"])
    x = x + act(m) @ lw["mlp.c_proj.weight"] + lw["mlp.c_proj.bias"]
    return x, k, v


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, gain, bias, wte, *, eps, fp8=False):
    h = _layer_norm(x, gain, bias, eps)
    return (_through_fp8(h) if fp8 else h) @ wte.T


def forward(w: dict, ids, config: dict, control=None):
    """(logits [T, V], keys [L, H, T, Dh], values [L, H, T, Dh]), float32,
    for one sequence of token ids [T]: the keys and values every layer
    attends over, as a cache would hold them. The head count and the
    LayerNorm epsilon are the configuration file's. `control` names one of
    `CONTROLS` (`int4_weights` rounds `w` in place: a second float32 copy
    of gpt2-xl does not fit beside the first)."""
    n_head, eps = int(config["n_head"]), float(config["layer_norm_epsilon"])
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control is called {control!r}: {CONTROLS}")
    if control == "int4_weights":
        w = int4_weights(w)
    kv_bits = 4 if control == "int4_kv" else None
    fp8 = control == "fp8_activations"
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = w["wte"][ids] + w["wpe"][jnp.arange(ids.shape[0])]
        per_layer = sorted(k for k in w if k.split(".")[0] in
                           ("ln_1", "ln_2", "attn", "mlp"))
        keys, values = [], []
        for layer in range(w["ln_1.weight"].shape[0]):
            lw = {k: w[k][layer] for k in per_layer}
            x, k, v = _block(x, lw, n_head=n_head, eps=eps, kv_bits=kv_bits,
                             fp8=fp8)
            keys.append(k)
            values.append(v)
        logits = _head(x, w["ln_f.weight"], w["ln_f.bias"], w["wte"],
                       eps=eps, fp8=fp8)
        return logits, jnp.stack(keys), jnp.stack(values)


def int4_weights(w: dict) -> dict:
    """Every matrix in 4 bits, one scale per output channel (per row for the
    tied embedding), as float32 values, in place."""
    for name in MATRICES:
        w[name] = _round_to_bits(w[name], -2, 4)
    w["wte"] = _round_to_bits(w["wte"], -1, 4)
    return w
