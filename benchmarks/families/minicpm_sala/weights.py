"""Seeded minicpm_sala weights, drawn on the device one leaf at a time.

The cut of MiniCPM-SALA holds 2.82 B parameters: 5.64 GB in bfloat16, 11.3
GB in float32, beside a cache of 4 GB on a chip of 16. So, as the other
large families do, `of_config` returns a `Seeded` (seed, sizes, dtype) from
which any leaf can be drawn when it is needed: every leaf has its own key
(seed, layer, index of its name), is drawn in float32 and cast after, so the
reference (float32, a layer at a time) and the program (`program_tree`,
every leaf in the served dtype) start from the same draws. Nothing of the
program is imported.

Names are the published checkpoint's as far as `config.json` gives them
(`model.layers.<i>.` left off; `self_attn.o_gate` is the output gate of
`attn_use_output_gate` / `use_output_gate`, `self_attn.o_norm` the
Lightning layers' `use_output_norm`), every linear stored [in, out].

Scales (`assumed` in the configuration file), by the rules the earlier
families paid for: every matrix is drawn at `fan_in ** -0.5`, so a product
keeps the size of what goes in; both mixers' `o_proj` at half that. The
model is muP-scaled, and the draws are placed so that its three scalars do
what they do in the published model and drown nothing: the embedding at `1 /
scale_emb` (the stream starts at 1 after the multiplication by 12), the head
at `(hidden_size / dim_model_base) * hidden ** -0.5` (logits of order 1
after the division by 16), and every residual branch is added at
`scale_depth / sqrt(32)` = 0.247, so the 16 branches of the 8 layers kept
add up to about the size of the stream they join. Norm gains are 1 + 0.1 n,
but the sparse layers' `q_norm` and `k_norm`, which are 2 + 0.1 n: with unit
gains two normalised random vectors score N(0, 1) and a softmax over 33 k
such keys is an average of 33 k values, which the residual stream does not
feel; a query that reads every key then gives the logits of one that reads
64 blocks to 0.24% (my chip run, PR 54: the control `dense_past_dense_len`
read UNDER the served path's own bfloat16 distance), and the selection is
not tested at all. At gains of 2 the scores are N(0, 16), a query's
attention rests on a few keys as a trained model's does, and which blocks it
reads decides what it says.
The head's rows of the tokens that are not a whole UTF-8 text alone are
scaled by 0.01 (`families/gpt2/weights.py`).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.gpt2 import weights as gpt2_weights

SIZE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "intermediate_size", "lightning_nh", "lightning_head_dim")
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
GAIN_STD, OUT_GAIN = 0.1, 0.5
# The mean gain of a sparse layer's q and k norms (module docstring).
SPARSE_QK_GAIN = 2.0
QUIET = gpt2_weights.QUIET


def sizes_of(config: dict) -> tuple:
    """The configuration file's sizes as a hashable tuple, and after them
    the mixers of the layers kept, the published depth, the published index
    of the first layer kept, and the muP scalars (`scale_emb`,
    `hidden_size / dim_model_base`)."""
    mixers = tuple(config["mixer_types"])
    if (len(mixers) != int(config["num_hidden_layers"])
            or set(mixers) - {SPARSE, LIGHTNING}
            or int(config["lightning_nkv"]) != int(config["lightning_nh"])):
        raise ValueError(f"mixer_types {mixers} do not spell "
                         f"{config['num_hidden_layers']} layers")
    return tuple(int(config[k]) for k in SIZE_KEYS) + (
        mixers, int(config["published"]["num_hidden_layers"]),
        int(config["layers_kept"]["first"]), float(config["scale_emb"]),
        float(config["hidden_size"]) / float(config["dim_model_base"]))


def layer_spec(sizes: tuple, layer: int) -> dict:
    """name -> (shape, std, mean) of every tensor of one layer (from 0)."""
    _, d, _, nh, nkv, dh, ff, lh, ld, mixers, *_ = sizes

    def mat(*shape, gain=1.0):
        return (shape, gain * shape[0] ** -0.5, 0.0)

    def gain(n, mean=1.0):
        return ((n,), GAIN_STD, mean)

    a = "self_attn."
    if mixers[layer] == SPARSE:
        hq, hk, hd, sharp = nh, nkv, dh, SPARSE_QK_GAIN
    else:
        hq = hk = lh
        hd, sharp = ld, 1.0
    spec = {
        "input_layernorm.weight": gain(d),
        "post_attention_layernorm.weight": gain(d),
        a + "q_proj.weight": mat(d, hq * hd),
        a + "k_proj.weight": mat(d, hk * hd),
        a + "v_proj.weight": mat(d, hk * hd),
        a + "o_gate.weight": mat(d, hq * hd),
        a + "o_proj.weight": mat(hq * hd, d, gain=OUT_GAIN),
        a + "q_norm.weight": gain(hd, sharp),
        a + "k_norm.weight": gain(hd, sharp),
        "mlp.gate_proj.weight": mat(d, ff),
        "mlp.up_proj.weight": mat(d, ff),
        "mlp.down_proj.weight": mat(ff, d),
    }
    if mixers[layer] == LIGHTNING:
        spec[a + "o_norm.weight"] = gain(hd)
    return spec


@functools.partial(jax.jit, static_argnames=("shape", "std", "mean", "dtype"))
def _draw(lo, hi, group, index, rows, *, shape, std, mean, dtype):
    key = jax.random.fold_in(jax.random.key(lo), hi)
    key = jax.random.fold_in(jax.random.fold_in(key, group), index)
    x = mean + std * jax.random.normal(key, shape, jnp.float32)
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

    def _leaf(self, group, index, shape, std, mean, rows=None):
        return _draw(jnp.asarray(self.seed & 0x7FFFFFFF, jnp.int32),
                     jnp.asarray(self.seed >> 31, jnp.int32),
                     jnp.asarray(group, jnp.int32),
                     jnp.asarray(index, jnp.int32), rows, shape=shape,
                     std=std, mean=mean, dtype=jnp.dtype(self.dtype))

    def layer(self, i: int) -> dict:
        """Layer i's tensors by their published names."""
        spec = layer_spec(self.sizes, i)
        return {name: self._leaf(i + 1, j, *spec[name])
                for j, name in enumerate(sorted(spec))}

    def embed(self):
        return self._leaf(0, 0, (self.sizes[0], self.sizes[1]),
                          1.0 / self.sizes[-2], 0.0)

    def head(self):
        rows = np.ones((self.sizes[0],), np.float32)
        rows[list(self.quiet)] = QUIET
        return self._leaf(0, 1, (self.sizes[0], self.sizes[1]),
                          self.sizes[-1] * self.sizes[1] ** -0.5, 0.0,
                          rows=rows)

    def norm(self):
        return self._leaf(0, 2, (self.sizes[1],), GAIN_STD, 1.0)


def of_config(seed: int, config: dict, dtype=jnp.float32) -> Seeded:
    """The checkpoint every side of a run starts from, not yet drawn."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return Seeded(seed, sizes_of(config), jnp.dtype(dtype),
                  gpt2_weights.quiet_ids(config))


def program_layer(lw: dict) -> dict:
    """One layer in the tree `models/minicpm_sala.init_params` builds."""
    a = "self_attn."
    attn = {"wq": lw[a + "q_proj.weight"], "wk": lw[a + "k_proj.weight"],
            "wv": lw[a + "v_proj.weight"], "wg": lw[a + "o_gate.weight"],
            "wo": lw[a + "o_proj.weight"],
            "qn": {"scale": lw[a + "q_norm.weight"]},
            "kn": {"scale": lw[a + "k_norm.weight"]}}
    if a + "o_norm.weight" in lw:
        attn["on"] = {"scale": lw[a + "o_norm.weight"]}
    return {"ln1": {"scale": lw["input_layernorm.weight"]},
            "ln2": {"scale": lw["post_attention_layernorm.weight"]},
            "attn": attn,
            "mlp": {"wg": lw["mlp.gate_proj.weight"],
                    "wu": lw["mlp.up_proj.weight"],
                    "wd": lw["mlp.down_proj.weight"]}}


def program_tree(w: Seeded) -> dict:
    """The checkpoint in the program's tree, every leaf drawn in `w.dtype`
    (float32 draw, cast, the float32 freed before the next leaf)."""
    return {
        "embed": w.embed(),
        "layers": [program_layer(w.layer(i)) for i in range(w.layers)],
        "lnf": {"scale": w.norm()},
        "lm_head": w.head(),
    }
