"""Seeded axk1 weights, drawn on the device one leaf at a time.

The cut of A.X-K1 holds 3.49 B parameters: 7.0 GB in bfloat16, 14 GB in
float32, on a chip of 16 GB that also holds the program's copy. So, as the
afmoe family does, `of_config` returns a `Seeded` (seed, sizes, dtype) from
which any leaf can be drawn when it is needed: every leaf has its own key
(seed, layer, index of its name), is drawn in float32 and cast after, so
the reference (float32, a layer at a time) and the program (`program_tree`,
every leaf in the served dtype) start from the same draws.

Names are the published checkpoint's (DeepSeek-V3's; `model.layers.<i>.`
left off), every linear stored [in, out], and of a layer's experts the
share HELD here stacked on a leading axis (`mlp.experts.gate_proj`
[held, D, M]: `n_routed_experts` of the configuration file counts the
experts held, `published.n_routed_experts` the router's width).

Scales (`assumed` in the configuration file): every matrix is drawn at
`fan_in ** -0.5`, so a product keeps the size of what goes in, and the
embedding at 1: the residual stream starts at 1 and every sublayer adds a
part of that order (the SwiGLUs 0.6 each, a token's held experts as the
router weighs them). `o_proj` is drawn at half that: seeded q and k give
scores of about N(0, 1.8), so a query averages a tenth of its keys, and a
prompt's positions average much the same keys into much the same vector; at
full gain that shared vector is a tenth of the stream's variance at 2,300
keys and a third at 200, and it decides which experts every token of a
batch prefers, by seed (PERF.md section 6, PR 30, found the same in afmoe).
Norm gains are 1 + 0.1 n. The head's rows of the tokens that are not a
whole UTF-8 text alone are scaled by 0.01 (`families/gpt2/weights.py`).

A.X-K1 has no balancing bias under this reading (`topk_method` `none`), so
what is stratified, to give EVERY SEED'S HELD EXPERTS THE SAME WORK, is the
router itself: each of its 192 columns is scaled to the same length, 1
(a column's length is its expert's popularity: independent draws differ
by 0.8% at 7,168 rows, and by 12% at a test's 32). What a seed still
decides is how the 12 held columns lie to whatever direction a batch's
tokens share, which `o_proj`'s gain keeps small; `moe_held_picks_share`
over the seeds shows what is left (PERF.md section 6).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.gpt2 import weights as gpt2_weights

SIZE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
             "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "intermediate_size", "moe_intermediate_size",
             "n_routed_experts", "n_shared_experts")
GAIN_STD, OUT_GAIN = 0.1, 0.5
QUIET = gpt2_weights.QUIET
ROUTER = "mlp.gate.weight"


def sizes_of(config: dict) -> tuple:
    """The configuration file's sizes as a hashable tuple, and after them
    the router's width (the published count of experts)."""
    return tuple(int(config[k]) for k in SIZE_KEYS) + (
        int(config["published"]["n_routed_experts"]),)


def layer_spec(sizes: tuple, layer: int) -> dict:
    """name -> (shape, scale, mean) of every tensor of one layer."""
    (_, d, _, nd, h, qr, kr, dn, dr, dv, inter, m, held, ns, e) = sizes

    def mat(*shape, gain=1.0):
        return (shape, gain * shape[-2] ** -0.5, 0.0)

    def gain(n):
        return ((n,), GAIN_STD, 1.0)

    spec = {
        "input_layernorm.weight": gain(d),
        "post_attention_layernorm.weight": gain(d),
        "self_attn.q_a_proj.weight": mat(d, qr),
        "self_attn.q_a_layernorm.weight": gain(qr),
        "self_attn.q_b_proj.weight": mat(qr, h * (dn + dr)),
        "self_attn.kv_a_proj_with_mqa.weight": mat(d, kr + dr),
        "self_attn.kv_a_layernorm.weight": gain(kr),
        "self_attn.kv_b_proj.weight": mat(kr, h * (dn + dv)),
        "self_attn.o_proj.weight": mat(h * dv, d, gain=OUT_GAIN),
    }
    if layer < nd:
        spec.update({
            "mlp.gate_proj.weight": mat(d, inter),
            "mlp.up_proj.weight": mat(d, inter),
            "mlp.down_proj.weight": mat(inter, d),
        })
    else:
        spec.update({
            ROUTER: mat(d, e),
            "mlp.experts.gate_proj": mat(held, d, m),
            "mlp.experts.up_proj": mat(held, d, m),
            "mlp.experts.down_proj": mat(held, m, d),
            "mlp.shared_experts.gate_proj.weight": mat(d, m * ns),
            "mlp.shared_experts.up_proj.weight": mat(d, m * ns),
            "mlp.shared_experts.down_proj.weight": mat(m * ns, d),
        })
    return spec


@functools.partial(jax.jit, static_argnames=(
    "shape", "std", "mean", "dtype", "router"))
def _draw(lo, hi, group, index, rows, *, shape, std, mean, dtype,
          router=False):
    key = jax.random.fold_in(jax.random.key(lo), hi)
    key = jax.random.fold_in(jax.random.fold_in(key, group), index)
    x = jax.random.normal(key, shape, jnp.float32)
    if router:
        # Every column as long as its neighbour (the file's head).
        x = x / jnp.linalg.norm(x, axis=0, keepdims=True) * shape[0] ** 0.5
    x = mean + std * x
    if rows is not None:
        x = x * rows[:, None]
    return x.astype(dtype)


@dataclasses.dataclass(frozen=True)
class Seeded:
    """A checkpoint that is drawn when asked for: `layer(i)`, `embed()`,
    `head()`, `norm()`, each a fresh array of `dtype`."""

    seed: int
    sizes: tuple
    dtype: object
    quiet: tuple

    @property
    def layers(self) -> int:
        return self.sizes[2]

    def _leaf(self, group, index, shape, std, mean, rows=None, router=False):
        return _draw(jnp.asarray(self.seed & 0x7FFFFFFF, jnp.int32),
                     jnp.asarray(self.seed >> 31, jnp.int32),
                     jnp.asarray(group, jnp.int32),
                     jnp.asarray(index, jnp.int32), rows, shape=shape,
                     std=std, mean=mean, dtype=jnp.dtype(self.dtype),
                     router=router)

    def layer(self, i: int) -> dict:
        """Layer i's tensors by their published names."""
        spec = layer_spec(self.sizes, i)
        return {name: self._leaf(i + 1, j, *spec[name],
                                 router=name == ROUTER)
                for j, name in enumerate(sorted(spec))}

    def embed(self):
        return self._leaf(0, 0, (self.sizes[0], self.sizes[1]), 1.0, 0.0)

    def head(self):
        rows = np.ones((self.sizes[0],), np.float32)
        rows[list(self.quiet)] = QUIET
        return self._leaf(0, 1, (self.sizes[0], self.sizes[1]),
                          self.sizes[1] ** -0.5, 0.0, rows=rows)

    def norm(self):
        return self._leaf(0, 2, (self.sizes[1],), GAIN_STD, 1.0)


def of_config(seed: int, config: dict, dtype=jnp.float32) -> Seeded:
    """The checkpoint every side of a run starts from, not yet drawn."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return Seeded(seed, sizes_of(config), jnp.dtype(dtype),
                  gpt2_weights.quiet_ids(config))


def program_layer(lw: dict, sizes: tuple) -> dict:
    """One layer in the tree `models/axk1.init_params` builds: `kv_b_proj`
    in its two halves, `wuk` and `wuv` [kv_lora_rank, heads, nope | v]."""
    h, kr, dn, dv = sizes[4], sizes[6], sizes[7], sizes[9]

    def mlp(prefix, suffix=".weight"):
        return {"wg": lw[f"{prefix}.gate_proj{suffix}"],
                "wu": lw[f"{prefix}.up_proj{suffix}"],
                "wd": lw[f"{prefix}.down_proj{suffix}"]}

    kv_b = lw["self_attn.kv_b_proj.weight"].reshape(kr, h, dn + dv)
    out = {
        "ln1": {"scale": lw["input_layernorm.weight"]},
        "ln2": {"scale": lw["post_attention_layernorm.weight"]},
        "attn": {
            "wqa": lw["self_attn.q_a_proj.weight"],
            "qn": {"scale": lw["self_attn.q_a_layernorm.weight"]},
            "wqb": lw["self_attn.q_b_proj.weight"],
            "wkva": lw["self_attn.kv_a_proj_with_mqa.weight"],
            "kvn": {"scale": lw["self_attn.kv_a_layernorm.weight"]},
            "wuk": kv_b[..., :dn], "wuv": kv_b[..., dn:],
            "wo": lw["self_attn.o_proj.weight"],
        },
    }
    if ROUTER in lw:
        out["moe"] = {"wr": lw[ROUTER], **mlp("mlp.experts", ""),
                      "shared": mlp("mlp.shared_experts")}
    else:
        out["mlp"] = mlp("mlp")
    return out


def program_tree(w: Seeded) -> dict:
    """The checkpoint in the program's tree, every leaf drawn in `w.dtype`
    (float32 draw, cast, the float32 freed before the next leaf)."""
    return {
        "embed": w.embed(),
        "layers": [program_layer(w.layer(i), w.sizes)
                   for i in range(w.layers)],
        "lnf": {"scale": w.norm()},
        "lm_head": w.head(),
    }
