"""Seeded lfm2_moe weights, drawn on the device one leaf at a time.

The cut of LFM2-8B-A1B holds 4.6 B parameters: 9.2 GB in bfloat16, 18 GB in
float32, on a chip of 16 GB that also holds the program's copy. So, as the
other routed families do, `of_config` returns a `Seeded` (seed, sizes,
dtype) from which any leaf can be drawn when it is needed: every leaf has
its own key (seed, layer, index of its name), is drawn in float32 and cast
after, so the reference (float32, a layer at a time, a routed layer's
experts in blocks of `EXPERT_BLOCK`) and the program (`program_tree`, every
leaf in the served dtype) start from the same draws.

Names are the published checkpoint's (`model.layers.<i>.` left off, `i` the
PUBLISHED index: the cut's first layer is layer 1), every linear stored
[in, out], the convolution [K, C], and a layer's experts stacked on a
leading expert axis (`feed_forward.experts.w1` [E, D, M]).

Scales (`assumed` in the configuration file), by the rules PRs 30, 34 and
40 paid for: every matrix is drawn at `fan_in ** -0.5`, so a product keeps
the size of what goes in; the attention layers' `out_proj` at half that (a
prompt's positions average much the same keys into much the same vector,
and at full gain that shared vector decides which experts every token of a
batch prefers, by seed; a conv operator sees three positions and averages
nothing, so its `out_proj` is at full gain). Norm gains are 1 + 0.1 n. The
convolution's taps are drawn at K ** -0.5. The embedding IS the head
(`tie_word_embeddings`): it is drawn at `hidden ** -0.5`, so the logits of
a normalised state are about N(0, 1) as the other families' heads give
them, and the residual stream starts small and takes its size from the
first layer's operator; its rows of the tokens that are not a whole UTF-8
text alone are scaled by 0.01 (`families/gpt2/weights.py`: with a tied head
their logits stay under the top-k of every step).

The router's 32 columns are each scaled to the same length, 1 (a column's
length is its expert's popularity), and `expert_bias` is NOT zero: the 32
quantile midpoints of N(0, `BIAS_STD`), in an order the seed draws
(`families/afmoe/weights.py`'s stratified bias): some experts are favoured,
none starves, the favour's distribution is the same for every seed, and a
program that left the bias out of the choice routes otherwise (the
reference's control `no_router_bias`).
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
             "intermediate_size", "moe_intermediate_size", "num_experts",
             "conv_L_cache")
GAIN_STD, OUT_GAIN, BIAS_STD = 0.1, 0.5, 0.03
QUIET = gpt2_weights.QUIET
ROUTER, BIAS = "feed_forward.gate.weight", "feed_forward.expert_bias"
# Experts of a routed layer the reference holds in float32 at once.
EXPERT_BLOCK = 8


def layers_kept(config: dict) -> tuple:
    """The published indices of the layers the configuration keeps."""
    first = int(config["layers_kept"]["first"])
    return tuple(range(first, first + int(config["num_hidden_layers"])))


def sizes_of(config: dict) -> tuple:
    """The configuration file's sizes as a hashable tuple, and after them
    the kept layers' types, their published indices and the published
    count of leading dense layers."""
    return tuple(int(config[k]) for k in SIZE_KEYS) + (
        tuple(config["layer_types"]), layers_kept(config),
        int(config["published"]["num_dense_layers"]))


def layer_spec(sizes: tuple, layer: int) -> dict:
    """name -> (shape, scale, mean, how it is drawn) of every tensor of
    held layer `layer` (0 is the cut's first)."""
    (_, d, _, h, hkv, dh, inter, m, e, k, types, kept, dense) = sizes

    def mat(*shape, gain=1.0):
        return (shape, gain * shape[-2] ** -0.5, 0.0, "normal")

    def gain(n):
        return ((n,), GAIN_STD, 1.0, "normal")

    spec = {"operator_norm.weight": gain(d), "ffn_norm.weight": gain(d)}
    if types[layer] == "full_attention":
        spec.update({
            "self_attn.q_proj.weight": mat(d, h * dh),
            "self_attn.k_proj.weight": mat(d, hkv * dh),
            "self_attn.v_proj.weight": mat(d, hkv * dh),
            "self_attn.out_proj.weight": mat(h * dh, d, gain=OUT_GAIN),
            "self_attn.q_layernorm.weight": gain(dh),
            "self_attn.k_layernorm.weight": gain(dh),
        })
    elif types[layer] == "conv":
        spec.update({
            "conv.in_proj.weight": mat(d, 3 * d),
            "conv.conv.weight": mat(k, d),
            "conv.out_proj.weight": mat(d, d),
        })
    else:
        raise ValueError(f"layer {kept[layer]} is {types[layer]!r}: "
                         f"neither conv nor full_attention")
    if kept[layer] < dense:
        spec.update({
            "feed_forward.w1.weight": mat(d, inter),
            "feed_forward.w3.weight": mat(d, inter),
            "feed_forward.w2.weight": mat(inter, d),
        })
    else:
        spec.update({
            ROUTER: ((d, e), d ** -0.5, 0.0, "router"),
            BIAS: ((e,), BIAS_STD, 0.0, "stratified"),
            "feed_forward.experts.w1": mat(e, d, m),
            "feed_forward.experts.w3": mat(e, d, m),
            "feed_forward.experts.w2": mat(e, m, d),
        })
    return spec


@functools.partial(jax.jit, static_argnames=(
    "shape", "std", "mean", "dtype", "how", "part"))
def _draw(lo, hi, group, index, rows, *, shape, std, mean, dtype,
          how="normal", part=None):
    key = jax.random.fold_in(jax.random.key(lo), hi)
    key = jax.random.fold_in(jax.random.fold_in(key, group), index)
    if part is not None:
        # Experts `part[0]` .. `part[0] + part[1] - 1` of a stack, each
        # expert under a key of its own: a block is the whole's slice.
        keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(
            part[0] + jnp.arange(part[1]))
        x = jax.vmap(lambda k: jax.random.normal(k, shape[1:], jnp.float32))(
            keys)
        return (mean + std * x).astype(dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if how == "router":
        # Every column as long as its neighbour (the file's head).
        x = x / jnp.linalg.norm(x, axis=0, keepdims=True) * shape[0] ** 0.5
    elif how == "stratified":
        # The quantile midpoints of the distribution, in the draw's order.
        ranks = jnp.argsort(jnp.argsort(x))
        x = jax.scipy.special.ndtri((ranks + 0.5) / x.shape[0])
    x = mean + std * x
    if rows is not None:
        x = x * rows[:, None]
    return x.astype(dtype)


@dataclasses.dataclass(frozen=True)
class Seeded:
    """A checkpoint that is drawn when asked for: `layer(i)` (`i` counts
    the held layers from 0), `embed()`, `norm()`, each a fresh array of
    `dtype` (`expert_bias` stays float32, as the program holds it).
    `layer(i, experts=(first, count))` draws that block of a routed
    layer's stacks alone, `experts=False` everything but the stacks."""

    seed: int
    sizes: tuple
    dtype: object
    quiet: tuple

    @property
    def layers(self) -> int:
        return self.sizes[2]

    def _leaf(self, group, index, shape, std, mean, how="normal", rows=None,
              dtype=None, part=None):
        return _draw(jnp.asarray(self.seed & 0x7FFFFFFF, jnp.int32),
                     jnp.asarray(self.seed >> 31, jnp.int32),
                     jnp.asarray(group, jnp.int32),
                     jnp.asarray(index, jnp.int32), rows, shape=shape,
                     std=std, mean=mean,
                     dtype=jnp.dtype(dtype or self.dtype), how=how,
                     part=part)

    def layer(self, i: int, experts=None) -> dict:
        spec = layer_spec(self.sizes, i)
        out = {}
        for j, name in enumerate(sorted(spec)):
            stack = name.startswith("feed_forward.experts.")
            if (experts is not None) and stack == (experts is False):
                continue
            part = None
            if stack:
                part = experts or (0, spec[name][0][0])
            out[name] = self._leaf(
                i + 1, j, *spec[name], part=part,
                dtype=jnp.float32 if name == BIAS else None)
        return out

    def embed(self):
        """The embedding, which is the head too: the quiet rows scaled."""
        rows = np.ones((self.sizes[0],), np.float32)
        rows[list(self.quiet)] = QUIET
        return self._leaf(0, 0, (self.sizes[0], self.sizes[1]),
                          self.sizes[1] ** -0.5, 0.0, rows=rows)

    def norm(self):
        return self._leaf(0, 2, (self.sizes[1],), GAIN_STD, 1.0)


def of_config(seed: int, config: dict, dtype=jnp.float32) -> Seeded:
    """The checkpoint every side of a run starts from, not yet drawn."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    sizes = sizes_of(config)
    if len(sizes[10]) != sizes[2]:
        raise ValueError(f"{sizes[2]} layers but {len(sizes[10])} "
                         f"layer_types")
    return Seeded(seed, sizes, jnp.dtype(dtype),
                  gpt2_weights.quiet_ids(config))


def program_layer(lw: dict) -> dict:
    """One layer in the tree `models/lfm2.init_params` builds."""
    from distributed_lms_raft_llm_tpu.models.lfm2 import pad_experts

    out = {"ln1": {"scale": lw["operator_norm.weight"]},
           "ln2": {"scale": lw["ffn_norm.weight"]}}
    if "conv.in_proj.weight" in lw:
        out["conv"] = {"w_in": lw["conv.in_proj.weight"],
                       "conv_w": lw["conv.conv.weight"],
                       "w_out": lw["conv.out_proj.weight"]}
    else:
        a = "self_attn."
        out["attn"] = {
            "wq": lw[a + "q_proj.weight"], "wk": lw[a + "k_proj.weight"],
            "wv": lw[a + "v_proj.weight"], "wo": lw[a + "out_proj.weight"],
            "qn": {"scale": lw[a + "q_layernorm.weight"]},
            "kn": {"scale": lw[a + "k_layernorm.weight"]}}
    if ROUTER in lw:
        # The inner width in whole tiles, as the program holds the stacks
        # (the expert computed is the same; the reference takes the draws
        # as they are).
        wg, wu, wd = pad_experts(*(lw["feed_forward.experts." + n]
                                   for n in ("w1", "w3", "w2")))
        out["moe"] = {"wr": lw[ROUTER], "br": lw[BIAS],
                      "wg": wg, "wu": wu, "wd": wd}
    else:
        out["mlp"] = {"wg": lw["feed_forward.w1.weight"],
                      "wu": lw["feed_forward.w3.weight"],
                      "wd": lw["feed_forward.w2.weight"]}
    return out


def program_tree(w: Seeded) -> dict:
    """The checkpoint in the program's tree, every leaf drawn in `w.dtype`
    (float32 draw, cast, the float32 freed before the next leaf); no
    `lm_head`: the head is the embedding."""
    return {
        "embed": w.embed(),
        "layers": [program_layer(w.layer(i)) for i in range(w.layers)],
        "lnf": {"scale": w.norm()},
    }
