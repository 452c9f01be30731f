"""Seeded weights of the GPT-2 trunk with routed experts, made on the
device in one jitted call (the GPT-2 family's, over this file's spec).

The trunk's names and shapes are the published GPT-2 checkpoint's, per-layer
tensors stacked on a leading layer axis; in place of `mlp.*` each layer has
`moe.router.weight` [L, D, E] and the experts' stacks `moe.experts.c_fc.*`
[L, E, D, M] / [L, E, M] and `moe.experts.c_proj.*` [L, E, M, D] / [L, E, D]
(there is no published layout: the names follow the trunk's).

Scales: the trunk's are the GPT-2 family's (`families/gpt2/weights.py`); the
experts' are their MLP's; the router is drawn at 0.2, so that a token's
experts differ by about one in the logit and a rounding does not decide
them. The quiet embedding rows are the GPT-2 family's (same tokenizers).
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.families.gpt2 import weights as trunk

SIZE_KEYS = trunk.SIZE_KEYS + ("n_inner", "num_experts")
ROUTER_STD = 0.2


def sizes_of(config: dict) -> tuple:
    return tuple(int(config[k]) for k in SIZE_KEYS)


def _spec(sizes: tuple) -> dict:
    v, p, d, l, _, m, e = sizes
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
        "moe.router.weight": ((l, d, e), ROUTER_STD, 0.0),
        "moe.experts.c_fc.weight": ((l, e, d, m), std, 0.0),
        "moe.experts.c_fc.bias": ((l, e, m), std, 0.0),
        "moe.experts.c_proj.weight": ((l, e, m, d), proj, 0.0),
        "moe.experts.c_proj.bias": ((l, e, d), std, 0.0),
        "ln_f.weight": ((d,), 0.1, 1.0),
        "ln_f.bias": ((d,), std, 0.0),
    }


def of_config(seed: int, config: dict, dtype=jnp.float32) -> dict:
    """The checkpoint every side of a run starts from: float32 for the
    reference, or cast (after the same float32 draw) to the type the
    program loads it in; the trunk's one jitted draw, over this spec."""
    return trunk.make(seed, sizes_of(config), dtype, trunk.quiet_ids(config),
                      spec=_spec)


def program_tree(w: dict) -> dict:
    """The checkpoint in the parameter tree `models/moe.init_params` builds:
    the GPT-2 tree with a `moe` subtree where `mlp` was."""
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
            "moe": {
                "wr": w["moe.router.weight"],
                "wi": w["moe.experts.c_fc.weight"],
                "bi": w["moe.experts.c_fc.bias"],
                "wo": w["moe.experts.c_proj.weight"],
                "bo": w["moe.experts.c_proj.bias"],
            },
        },
        "lnf": {"scale": w["ln_f.weight"], "bias": w["ln_f.bias"]},
    }
