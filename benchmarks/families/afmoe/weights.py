"""Seeded afmoe weights, drawn on the device one leaf at a time.

Trinity-Mini's cut holds 4.24 B parameters: 8.5 GB in bfloat16, 17 GB in
float32, on a chip of 16 GB that also holds the program's copy. So nothing
here ever makes the whole tree in float32. `of_config` returns a `Seeded`:
the seed, the sizes and a dtype, from which any leaf can be drawn when it is
needed. Every leaf has its own key (seed, layer, index of its name), is
drawn in float32 and cast after drawing, so the reference (float32, a layer
at a time, `reference.forward` asks for layer l when it reaches it) and the
program (`program_tree`, every leaf in the served dtype) start from the same
draws.

Names are the published checkpoint's (`model.layers.<i>.` left off), with
the experts of a layer stacked on a leading expert axis
(`mlp.experts.gate_proj` [E, D, M]) and every linear stored [in, out], as
the program's families store them (the checkpoint has [out, in]).

Scales (`assumed` in the configuration file): matrices and embeddings 0.02;
norm gains 1 + 0.1 n. With `mup_enabled` the embedding is multiplied by
sqrt(hidden), so the residual stream starts at 0.9. The router's columns are
drawn at 0.02 (its logits are then about N(0, 0.9) on a normalised input).
The head's rows of the tokens that are not a whole UTF-8 text alone are
scaled by 0.01, as the GPT-2 family quietens its tied embedding's
(`families/gpt2/weights.py`).

Two draws are made so that EVERY SEED GIVES THE ROUTED LAYERS THE SAME WORK
(PERF.md section 6, PR 30: with every norm's gain at 1 and a balancing bias
of 128 independent draws at 0.05, a seed's weights decided how many experts
a decode step reached, 44 to 45.5 of 128 a layer, and with that 3% of
`out_tok_s`):

- The gain of `post_attention_layernorm` is 0.25 (1 + 0.1 n): the attention
  sublayer adds a sixteenth of what the MLP sublayer adds, in variance.
  Seeded q and k give scores of about N(0, 1), so every query averages
  nearly all its keys, and that average is much the same vector for every
  position of a prompt; at a gain of 1 the norm scales it back to unit size
  and a third of the residual stream is one vector that all positions of a
  prompt share: the router then sends most tokens of a prompt to the same
  few experts (up to 80% of them to one), and which experts, how many, is
  the seed's. (Sharper scores, gains of 2 on q and k, cure that too, but
  the served bfloat16 path then stands 7% from float32, further than any
  control: call M, PERF.md section 6.)
- The balancing bias is stratified: the 128 quantile midpoints of
  N(0, 0.02), in an order the seed draws. Some experts are favoured (by
  the scores' spread an expert's share of the picks is 0.75 to 1.3 times
  the mean for two experts in three, and 0.45 to 2 times at the ends),
  none starves, and the favour's distribution is the same for every seed:
  128 independent draws would differ by seed in how many experts are
  favoured how much, and the experts a step reaches with them.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.gpt2 import weights as gpt2_weights

SIZE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
             "num_dense_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "intermediate_size",
             "moe_intermediate_size", "num_experts", "num_shared_experts")
STD, GAIN_STD, BIAS_STD, ATTN_GAIN = 0.02, 0.1, 0.02, 0.25
QUIET = gpt2_weights.QUIET


def sizes_of(config: dict) -> tuple:
    """The configuration file's published sizes as a hashable tuple."""
    return tuple(int(config[k]) for k in SIZE_KEYS)


def layer_spec(sizes: tuple, layer: int) -> dict:
    """name -> (shape, scale, mean) of every tensor of one layer."""
    _, d, _, nd, h, hkv, dh, inter, m, e, ns = sizes
    gain, mat = (GAIN_STD, 1.0), (STD, 0.0)
    spec = {
        "input_layernorm.weight": ((d,), *gain),
        "post_attention_layernorm.weight": (
            (d,), GAIN_STD * ATTN_GAIN, ATTN_GAIN),
        "pre_mlp_layernorm.weight": ((d,), *gain),
        "post_mlp_layernorm.weight": ((d,), *gain),
        "self_attn.q_proj.weight": ((d, h * dh), *mat),
        "self_attn.k_proj.weight": ((d, hkv * dh), *mat),
        "self_attn.v_proj.weight": ((d, hkv * dh), *mat),
        "self_attn.gate_proj.weight": ((d, h * dh), *mat),
        "self_attn.o_proj.weight": ((h * dh, d), *mat),
        "self_attn.q_norm.weight": ((dh,), *gain),
        "self_attn.k_norm.weight": ((dh,), *gain),
    }
    if layer < nd:
        spec.update({
            "mlp.gate_proj.weight": ((d, inter), *mat),
            "mlp.up_proj.weight": ((d, inter), *mat),
            "mlp.down_proj.weight": ((inter, d), *mat),
        })
    else:
        spec.update({
            "mlp.router.gate.weight": ((d, e), *mat),
            "mlp.expert_bias": ((e,), BIAS_STD, 0.0),
            "mlp.experts.gate_proj": ((e, d, m), *mat),
            "mlp.experts.up_proj": ((e, d, m), *mat),
            "mlp.experts.down_proj": ((e, m, d), *mat),
            "mlp.shared_experts.gate_proj.weight": ((d, m * ns), *mat),
            "mlp.shared_experts.up_proj.weight": ((d, m * ns), *mat),
            "mlp.shared_experts.down_proj.weight": ((m * ns, d), *mat),
        })
    return spec


@functools.partial(jax.jit, static_argnames=(
    "shape", "std", "mean", "dtype", "stratified"))
def _draw(lo, hi, group, index, rows, *, shape, std, mean, dtype,
          stratified=False):
    key = jax.random.fold_in(jax.random.key(lo), hi)
    key = jax.random.fold_in(jax.random.fold_in(key, group), index)
    x = jax.random.normal(key, shape, jnp.float32)
    if stratified:
        # The quantile midpoints of the distribution, in the draw's order.
        ranks = jnp.argsort(jnp.argsort(x))
        x = jax.scipy.special.ndtri((ranks + 0.5) / x.shape[0])
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

    def _leaf(self, group, index, shape, std, mean, rows=None, dtype=None,
              stratified=False):
        return _draw(jnp.asarray(self.seed & 0x7FFFFFFF, jnp.int32),
                     jnp.asarray(self.seed >> 31, jnp.int32),
                     jnp.asarray(group, jnp.int32),
                     jnp.asarray(index, jnp.int32), rows, shape=shape,
                     std=std, mean=mean, dtype=jnp.dtype(dtype or self.dtype),
                     stratified=stratified)

    def layer(self, i: int) -> dict:
        """Layer i's tensors by their published names; the balancing bias
        is stratified, and float32 whatever the dtype, as the program
        holds it."""
        spec = layer_spec(self.sizes, i)
        bias = "mlp.expert_bias"
        return {name: self._leaf(
            i + 1, j, *spec[name], stratified=name == bias,
            dtype=jnp.float32 if name == bias else None)
            for j, name in enumerate(sorted(spec))}

    def embed(self):
        return self._leaf(0, 0, (self.sizes[0], self.sizes[1]), STD, 0.0)

    def head(self):
        rows = np.ones((self.sizes[0],), np.float32)
        rows[list(self.quiet)] = QUIET
        return self._leaf(0, 1, (self.sizes[0], self.sizes[1]), STD, 0.0,
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
    """One layer in the tree `models/afmoe.init_params` builds."""
    def mlp(prefix, suffix=".weight"):
        return {"wg": lw[f"{prefix}.gate_proj{suffix}"],
                "wu": lw[f"{prefix}.up_proj{suffix}"],
                "wd": lw[f"{prefix}.down_proj{suffix}"]}

    out = {
        "ln1": {"scale": lw["input_layernorm.weight"]},
        "ln1p": {"scale": lw["post_attention_layernorm.weight"]},
        "ln2": {"scale": lw["pre_mlp_layernorm.weight"]},
        "ln2p": {"scale": lw["post_mlp_layernorm.weight"]},
        "attn": {
            "wq": lw["self_attn.q_proj.weight"],
            "wk": lw["self_attn.k_proj.weight"],
            "wv": lw["self_attn.v_proj.weight"],
            "wg": lw["self_attn.gate_proj.weight"],
            "wo": lw["self_attn.o_proj.weight"],
            "qn": {"scale": lw["self_attn.q_norm.weight"]},
            "kn": {"scale": lw["self_attn.k_norm.weight"]},
        },
    }
    if "mlp.router.gate.weight" in lw:
        out["moe"] = {"wr": lw["mlp.router.gate.weight"],
                      "br": lw["mlp.expert_bias"],
                      **mlp("mlp.experts", ""),
                      "shared": mlp("mlp.shared_experts")}
    else:
        out["mlp"] = mlp("mlp")
    return out


def program_tree(w: Seeded) -> dict:
    """The checkpoint in the program's tree, every leaf drawn in `w.dtype`
    (float32 draw, cast, the float32 freed before the next leaf)."""
    return {
        "embed": w.embed(),
        "layers": [program_layer(w.layer(i)) for i in range(w.layers)],
        "lnf": {"scale": w.norm()},
        "lm_head": w.head(),
    }
