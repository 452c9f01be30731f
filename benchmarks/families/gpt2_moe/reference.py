"""Plain forward pass of the GPT-2 trunk with routed experts: the
benchmark's reference for the `gpt2_moe` family.

`jax.numpy`, float32, matmuls at `highest` precision, the whole sequence at
once, no cache, no quantisation, and no import from the program. The trunk
is GPT-2's (learned positions, pre-LayerNorm blocks, fused QKV, causal
softmax attention, final LayerNorm, tied head), and its LayerNorm, GELU,
head and roundings are the GPT-2 reference's own functions. In place of the MLP, the
routing `models/moe.py` documents:

- a token's router scores are the softmax, over ALL experts, of its
  normalised hidden state times the router matrix, in float32;
- it is sent to the `experts_per_token` experts that score highest, and
  their scores, renormalised to sum to 1, weigh the experts' outputs;
- an expert is the trunk's MLP (tanh-GELU) with its own matrices;
- **capacity.** The tokens of one forward pass share buffers: an expert
  seats at most C = max(1, ceil(capacity_factor x S x experts_per_token /
  num_experts)) picks of a pass of S tokens, every token's first choice
  before any token's second, earlier tokens first. A pick without a seat is
  dropped: its weight is lost, the other pick keeps its own, and the token
  rides the residual. So the answer depends on which tokens share a pass,
  and the reference is told: the first `check.prompt_tokens` tokens are one
  pass (the prefill of a prompt that fills its bucket), every later token a
  pass of its own (a decode step of this one sequence, where both picks
  always find a seat). Padding in a bucket and the other slots of a decode
  step would take seats too; the comparison is shaped to have neither.

It returns, beside logits, keys and values, the routing itself: the weight
each token gives each expert in each layer, 0 where it sends nothing.

`CONTROLS`: the same reference with ONE stated precision a step lower
(int8 weights, int8 K and V, bfloat16 activations are served): the
GPT-2 family's three, with the experts' matrices among the int4 weights
and the router's input among the fp8 products.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.families.gpt2.reference import (
    _gelu_new,
    _head,
    _layer_norm,
    _round_to_bits,
    _through_fp8,
)

MATRICES = ("attn.c_attn.weight", "attn.c_proj.weight",
            "moe.experts.c_fc.weight", "moe.experts.c_proj.weight")
CONTROLS = ("int4_weights", "int4_kv", "fp8_activations")


def capacity(tokens: int, k: int, experts: int, factor: float) -> int:
    """Seats an expert has for the picks of a pass of `tokens` tokens."""
    return max(1, math.ceil(factor * tokens * k / experts))


def _seated(picks, experts: int, seats: int):
    """Which of a pass's picks [S, k] find a seat: first choices before
    second, earlier tokens first, `seats` to an expert."""
    s, k = picks.shape
    chosen = jax.nn.one_hot(picks.T.reshape(-1), experts, dtype=jnp.int32)
    before = jnp.sum((jnp.cumsum(chosen, axis=0) - chosen) * chosen, axis=-1)
    return (before < seats).reshape(k, s).T


def _route(h, router, passes, *, k, factor, act):
    """The weight every token gives every expert, [T, E]: renormalised
    scores of the picks that found a seat in their pass, 0 elsewhere."""
    scores = jax.nn.softmax(act(h) @ router, axis=-1)
    top, picks = jax.lax.top_k(scores, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    experts = router.shape[-1]
    kept = jnp.concatenate([
        _seated(picks[a:b], experts, capacity(b - a, k, experts, factor))
        for a, b in passes])
    return jnp.sum(jax.nn.one_hot(picks, experts) * (top * kept)[..., None],
                   axis=1)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "eps", "k", "factor", "passes", "kv_bits", "fp8"))
def _block(x, lw, *, n_head, eps, k, factor, passes, kv_bits=None,
           fp8=False):
    act = _through_fp8 if fp8 else (lambda a: a)
    t, d = x.shape
    h = _layer_norm(x, lw["ln_1.weight"], lw["ln_1.bias"], eps)
    qkv = act(h) @ lw["attn.c_attn.weight"] + lw["attn.c_attn.bias"]
    q, key, v = (a.reshape(t, n_head, d // n_head).transpose(1, 0, 2)
                 for a in jnp.split(qkv, 3, axis=-1))
    if kv_bits:
        key = _round_to_bits(key, -1, kv_bits)
        v = _round_to_bits(v, -1, kv_bits)
    scores = q @ key.transpose(0, 2, 1) / jnp.sqrt(float(d // n_head))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = (probs @ v).transpose(1, 0, 2).reshape(t, d)
    x = x + act(a) @ lw["attn.c_proj.weight"] + lw["attn.c_proj.bias"]
    h = _layer_norm(x, lw["ln_2.weight"], lw["ln_2.bias"], eps)
    routing = _route(h, lw["moe.router.weight"], passes, k=k, factor=factor,
                     act=act)
    # Every expert on every token, weighed by the routing: plain, and at
    # this size cheap.
    mid = _gelu_new(jnp.einsum("td,edm->etm", act(h),
                               lw["moe.experts.c_fc.weight"])
                    + lw["moe.experts.c_fc.bias"][:, None, :])
    out = (jnp.einsum("etm,emd->etd", act(mid),
                      lw["moe.experts.c_proj.weight"])
           + lw["moe.experts.c_proj.bias"][:, None, :])
    return x + jnp.einsum("te,etd->td", routing, out), key, v, routing


def forward(w: dict, ids, config: dict, control=None):
    """(logits [T, V], keys [L, H, T, Dh], values [L, H, T, Dh], routing
    [L, T, E]), float32, for one sequence of token ids [T]. Sizes, the
    routing's parameters and the split into passes (`check.prompt_tokens`)
    are the configuration file's. `control` names one of `CONTROLS`
    (`int4_weights` rounds `w` in place)."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control is called {control!r}: {CONTROLS}")
    if control == "int4_weights":
        w = int4_weights(w)
    ids = jnp.asarray(ids, jnp.int32)
    n = min(int(config["check"]["prompt_tokens"]), ids.shape[0])
    passes = ((0, n),) + tuple((t, t + 1) for t in range(n, ids.shape[0]))
    shared = dict(
        n_head=int(config["n_head"]), eps=float(config["layer_norm_epsilon"]),
        k=int(config["experts_per_token"]),
        factor=float(config["capacity_factor"]), passes=passes,
        kv_bits=4 if control == "int4_kv" else None,
        fp8=control == "fp8_activations")
    with jax.default_matmul_precision("highest"):
        x = w["wte"][ids] + w["wpe"][jnp.arange(ids.shape[0])]
        per_layer = sorted(k for k in w if k.split(".")[0] in
                           ("ln_1", "ln_2", "attn", "moe"))
        keys, values, routing = [], [], []
        for layer in range(w["ln_1.weight"].shape[0]):
            lw = {k: w[k][layer] for k in per_layer}
            x, k, v, r = _block(x, lw, **shared)
            keys.append(k)
            values.append(v)
            routing.append(r)
        logits = _head(x, w["ln_f.weight"], w["ln_f.bias"], w["wte"],
                       eps=shared["eps"], fp8=shared["fp8"])
        return (logits, jnp.stack(keys), jnp.stack(values),
                jnp.stack(routing))


def int4_weights(w: dict) -> dict:
    """Every matrix in 4 bits, one scale per output channel (per row for the
    tied embedding), as float32 values, in place. The router stays as it
    is: the program serves it unquantised, so it has no step below."""
    for name in MATRICES:
        w[name] = _round_to_bits(w[name], -2, 4)
    w["wte"] = _round_to_bits(w["wte"], -1, 4)
    return w
