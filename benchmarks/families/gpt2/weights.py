"""Seeded GPT-2 weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the plain reference and
the served engine both start from what this file draws from `--seed`, so the
reference compares the program with nothing the program has made. Names and
shapes are the published checkpoint's (`h.<i>.attn.c_attn.weight` ...), with
the per-layer tensors stacked on a leading layer axis.

Scales (listed under `assumed` in each configuration file): 0.02 for every
matrix and embedding as in GPT-2's initialisation, `c_proj` scaled by
1/sqrt(2 L), `c_attn` at 0.04 so that attention is not uniform, biases 0.02,
LayerNorm gains 1 + 0.1 n. The embedding rows of the tokens that are not a
whole UTF-8 text alone (`quiet_ids`) are drawn like the others and then scaled
by `QUIET`: with the tied head their logits stay near 0, under the top-k of
every step, so the seeded model, like a trained one, does not answer in broken
characters (PERF.md section 2 has what they did to the stream).
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

# Sizes the generator needs, by the published config.json's own keys.
SIZE_KEYS = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head")


BENCH = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
VOCAB = os.path.join(BENCH, "vocab", "vocab.json")
QUIET = 0.01


def _byte_of_char() -> dict:
    """GPT-2's printable stand-in of every byte, inverted (the published
    `bytes_to_unicode`)."""
    keep = (list(range(ord("!"), ord("~") + 1))
            + list(range(0xA1, 0xAC + 1)) + list(range(0xAE, 0xFF + 1)))
    chars, n = list(keep), 0
    for b in range(256):
        if b not in keep:
            keep.append(b)
            chars.append(256 + n)
            n += 1
    return {chr(c): b for b, c in zip(keep, chars)}


@functools.lru_cache(maxsize=None)
def _quiet_ids(tokenizer: str, vocab_size: int) -> tuple:
    if tokenizer == "bytes":  # the program's byte fallback: id = byte
        return tuple(range(128, min(256, vocab_size)))
    with open(VOCAB, encoding="utf-8") as fh:
        vocab = json.load(fh)
    byte_of = _byte_of_char()
    ids = []
    for token, i in vocab.items():
        try:
            bytes(byte_of[c] for c in token).decode("utf-8")
        except UnicodeDecodeError:
            ids.append(i)
        except KeyError:  # a special token: text of its own, or none
            pass
    return tuple(sorted(i for i in ids if i < vocab_size))


def quiet_ids(config: dict) -> tuple:
    """The ids of the configuration's tokenizer whose bytes are not a whole
    UTF-8 text alone: a lead byte without its continuation, or the reverse."""
    return _quiet_ids(config.get("tokenizer", "bpe"),
                      int(config["vocab_size"]))


def sizes_of(config: dict) -> tuple:
    """The configuration file's published sizes as a hashable tuple."""
    return tuple(int(config[k]) for k in SIZE_KEYS)


def _spec(sizes: tuple) -> dict:
    v, p, d, l, _ = sizes
    std, proj = 0.02, 0.02 / (2.0 * l) ** 0.5
    return {
        "wte": ((v, d), std, 0.0),
        "wpe": ((p, d), std, 0.0),
        "ln_1.weight": ((l, d), 0.1, 1.0),
        "ln_1.bias": ((l, d), std, 0.0),
        "attn.c_attn.weight": ((l, d, 3 * d), 0.04, 0.0),
        "attn.c_attn.bias": ((l, 3 * d), std, 0.0),
        "attn.c_proj.weight": ((l, d, d), proj, 0.0),
        "attn.c_proj.bias": ((l, d), std, 0.0),
        "ln_2.weight": ((l, d), 0.1, 1.0),
        "ln_2.bias": ((l, d), std, 0.0),
        "mlp.c_fc.weight": ((l, d, 4 * d), std, 0.0),
        "mlp.c_fc.bias": ((l, 4 * d), std, 0.0),
        "mlp.c_proj.weight": ((l, 4 * d, d), proj, 0.0),
        "mlp.c_proj.bias": ((l, d), std, 0.0),
        "ln_f.weight": ((d,), 0.1, 1.0),
        "ln_f.bias": ((d,), std, 0.0),
    }


@functools.partial(jax.jit, static_argnames=("spec", "sizes", "dtype"))
def _make(lo, hi, wte_scale, *, spec, sizes, dtype):
    key = jax.random.fold_in(jax.random.key(lo), hi)
    spec = spec(sizes)
    keys = jax.random.split(key, len(spec))
    out = {}
    for k, (name, (shape, std, mean)) in zip(keys, sorted(spec.items())):
        x = mean + std * jax.random.normal(k, shape, jnp.float32)
        if name == "wte":
            x = x * wte_scale[:, None]
        out[name] = x.astype(dtype)
    return out


def make(seed: int, sizes: tuple, dtype=jnp.float32, quiet: tuple = (),
         spec=_spec) -> dict:
    """The checkpoint for `seed`: float32 for the reference, or cast (after
    the same float32 draw) to the type the program loads it in. `quiet` are
    the token ids whose embedding rows are scaled by `QUIET`. `spec` maps
    `sizes` to every tensor's (shape, scale, mean): GPT-2's, or that of a
    family on the same trunk, whose `sizes` start with GPT-2's."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    lo = jnp.asarray(seed & 0x7FFFFFFF, jnp.int32)
    hi = jnp.asarray(seed >> 31, jnp.int32)
    wte_scale = np.ones((sizes[0],), np.float32)
    wte_scale[list(quiet)] = QUIET
    return _make(lo, hi, wte_scale, spec=spec, sizes=sizes,
                 dtype=jnp.dtype(dtype))


def of_config(seed: int, config: dict, dtype=jnp.float32) -> dict:
    """The checkpoint every side of a run starts from: the configuration's
    sizes, its tokenizer's `quiet_ids`."""
    return make(seed, sizes_of(config), dtype, quiet_ids(config))


def program_tree(w: dict) -> dict:
    """The checkpoint in the parameter tree the program's GPT-2 family loads
    (the tree `models/convert.gpt2_params_from_hf` builds from the same
    names), without a trip through the host."""
    return {
        "wte": w["wte"],
        "wpe": w["wpe"],
        "blocks": {
            "ln1": {"scale": w["ln_1.weight"], "bias": w["ln_1.bias"]},
            "attn": {
                "wqkv": w["attn.c_attn.weight"],
                "bqkv": w["attn.c_attn.bias"],
                "wo": w["attn.c_proj.weight"],
                "bo": w["attn.c_proj.bias"],
            },
            "ln2": {"scale": w["ln_2.weight"], "bias": w["ln_2.bias"]},
            "mlp": {
                "wi": w["mlp.c_fc.weight"],
                "bi": w["mlp.c_fc.bias"],
                "wo": w["mlp.c_proj.weight"],
                "bo": w["mlp.c_proj.bias"],
            },
        },
        "lnf": {"scale": w["ln_f.weight"], "bias": w["ln_f.bias"]},
    }
