"""Seeded kimi_linear weights, drawn on the device one leaf at a time.

The cut of Kimi-Linear holds 4.27 B parameters: 8.5 GB in bfloat16, 17 GB in
float32, on a chip of 16 GB. So, as the other routed families do,
`of_config` returns a `Seeded` (seed, sizes, dtype) from which any leaf can
be drawn when it is needed: every leaf has its own key (seed, layer, index
of its name), is drawn in float32 and cast after, so the reference
(float32, a layer at a time) and the program (`program_tree`, every leaf in
the served dtype) start from the same draws.

Names are the published checkpoint's (`model.layers.<i>.` left off), every
linear stored [in, out], a convolution [K, C], and of a layer's experts the
share HELD here stacked on a leading axis (`block_sparse_moe.experts.w1`
[held, D, M]: `num_experts` of the configuration file counts the experts
held, `published.num_experts` the router's width).

Scales (`assumed` in the configuration file), by the rules PRs 30, 34 and 40
paid for: every matrix is drawn at `fan_in ** -0.5`, so a product keeps the
size of what goes in, and the embedding at 1: the residual stream starts at
1 and every sublayer adds a part of that order. Both mixers' `o_proj` are
drawn at half that: a prompt's positions average much the same keys (and,
in a KDA layer, much the same slow state) into much the same vector, and at
full gain that shared vector decides which experts every token of a batch
prefers, by seed. Norm gains are 1 + 0.1 n. The router's 256 columns are
each scaled to the same length, 1 (a column's length is its expert's
popularity), and `e_score_correction_bias` is CALIBRATED, as the published
model's is trained to be (DeepSeek-V3's auxiliary-loss-free balancing):
`balancing_biases` runs `BALANCE_TOKENS` random tokens through the layers a
layer at a time (the reference's, float32) and at every expert layer moves
the bias by 0.05 x (an expert's picks over the mean - 1), 8 times, until
all 256 experts are picked alike (`families/nemotron_h/weights.py` has the
step, `_balance`, and what a cell's steadiness owes to it). The head's rows of the tokens that are not a whole UTF-8 text alone are
scaled by 0.01 (`families/gpt2/weights.py`).

The KDA layers' own parameters are float32 whatever the dtype asked for
(the program keeps them so): `A_log = log(A)`, A uniform in [1, 16], one a
head; `dt_bias` the inverse softplus of a log-uniform draw in [0.001, 0.1],
one a head and channel (time scales of 0.6 to 1,000 tokens: a state that
neither dies nor saturates over 2,400 tokens); the convolutions' taps at
K ** -0.5, no bias.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.gpt2 import weights as gpt2_weights
from benchmarks.families.nemotron_h.weights import (
    BALANCE_TOKENS,
    _balance,
)
from distributed_lms_raft_llm_tpu.models.kimi_linear import pad_experts

SIZE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
             "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "intermediate_size",
             "moe_intermediate_size", "num_experts", "first_k_dense_replace")
GAIN_STD, OUT_GAIN = 0.1, 0.5
A_RANGE, DT_RANGE = (1.0, 16.0), (0.001, 0.1)
QUIET = gpt2_weights.QUIET
ROUTER = "block_sparse_moe.gate.weight"
BIAS = "block_sparse_moe.gate.e_score_correction_bias"
# Leaves that stay float32 whatever dtype is asked for.
FLOAT32 = ("self_attn.A_log", "self_attn.dt_bias", BIAS)


def sizes_of(config: dict) -> tuple:
    """The configuration file's sizes as a hashable tuple, and after them
    the KDA layers' (heads, head dim, kernel), the two lists of layers
    (numbered from 1) and the router's width (the published count of
    experts)."""
    lin = config["linear_attn_config"]
    return tuple(int(config[k]) for k in SIZE_KEYS) + (
        (int(lin["num_heads"]), int(lin["head_dim"]),
         int(lin["short_conv_kernel_size"])),
        tuple(int(i) for i in lin["kda_layers"]),
        tuple(int(i) for i in lin["full_attn_layers"]),
        int(config["published"]["num_experts"]))


def layer_spec(sizes: tuple, layer: int) -> dict:
    """name -> (shape, scale, mean, how it is drawn) of every tensor of one
    layer (from 0)."""
    (_, d, _, h, kr, dn, dr, dv, ff, m, held, dense, (kh, kd, kk), kda, full,
     e) = sizes

    def mat(*shape, gain=1.0):
        return (shape, gain * shape[-2] ** -0.5, 0.0, "normal")

    def gain(n):
        return ((n,), GAIN_STD, 1.0, "normal")

    spec = {"input_layernorm.weight": gain(d),
            "post_attention_layernorm.weight": gain(d)}
    a = "self_attn."
    if layer + 1 in kda:
        for n in "qkv":
            spec[f"{a}{n}_proj.weight"] = mat(d, kh * kd)
            spec[f"{a}{n}_conv1d.weight"] = mat(kk, kh * kd)
        spec.update({
            a + "f_a_proj.weight": mat(d, kd),
            a + "f_b_proj.weight": mat(kd, kh * kd),
            a + "dt_bias": ((kh * kd,), 0.0, 0.0, ("dt_bias",) + DT_RANGE),
            a + "A_log": ((kh,), 0.0, 0.0, ("a_log",) + A_RANGE),
            a + "b_proj.weight": mat(d, kh),
            a + "g_a_proj.weight": mat(d, kd),
            a + "g_b_proj.weight": mat(kd, kh * kd),
            a + "o_norm.weight": gain(kd),
            a + "o_proj.weight": mat(kh * kd, d, gain=OUT_GAIN),
        })
    elif layer + 1 in full:
        spec.update({
            a + "q_proj.weight": mat(d, h * (dn + dr)),
            a + "kv_a_proj_with_mqa.weight": mat(d, kr + dr),
            a + "kv_a_layernorm.weight": gain(kr),
            a + "kv_b_proj.weight": mat(kr, h * (dn + dv)),
            a + "o_proj.weight": mat(h * dv, d, gain=OUT_GAIN),
        })
    else:
        raise ValueError(f"layer {layer + 1} is in neither list of "
                         f"linear_attn_config")
    if layer < dense:
        spec.update({"mlp.gate_proj.weight": mat(d, ff),
                     "mlp.up_proj.weight": mat(d, ff),
                     "mlp.down_proj.weight": mat(ff, d)})
    else:
        b = "block_sparse_moe."
        spec.update({
            ROUTER: ((d, e), d ** -0.5, 0.0, "router"),
            BIAS: ((e,), 0.0, 0.0, "normal"),
            b + "experts.w1": mat(held, d, m),
            b + "experts.w3": mat(held, d, m),
            b + "experts.w2": mat(held, m, d),
            b + "shared_experts.gate_proj.weight": mat(d, m),
            b + "shared_experts.up_proj.weight": mat(d, m),
            b + "shared_experts.down_proj.weight": mat(m, d),
        })
    return spec


@functools.partial(jax.jit, static_argnames=(
    "shape", "std", "mean", "dtype", "how"))
def _draw(lo, hi, group, index, rows, *, shape, std, mean, dtype,
          how="normal"):
    key = jax.random.fold_in(jax.random.key(lo), hi)
    key = jax.random.fold_in(jax.random.fold_in(key, group), index)
    if how[0] == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          how[1], how[2])).astype(dtype)
    if how[0] == "dt_bias":
        lo_t, hi_t = how[1:]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (np.log(hi_t) - np.log(lo_t)) + np.log(lo_t))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if how == "router":
        # Every column as long as its neighbour (the file's head).
        x = x / jnp.linalg.norm(x, axis=0, keepdims=True) * shape[0] ** 0.5
    x = mean + std * x
    if rows is not None:
        x = x * rows[:, None]
    return x.astype(dtype)


@dataclasses.dataclass(frozen=True)
class Seeded:
    """A checkpoint that is drawn when asked for: `layer(i)`, `embed()`,
    `head()`, `norm()`, each a fresh array of `dtype` (`FLOAT32` leaves
    stay float32). `routing` is what the balancing bias's calibration
    needs of the configuration beside the sizes: (experts a token, whether
    their weights are normalised, their scale, the first expert held, the
    norms' epsilon)."""

    seed: int
    sizes: tuple
    dtype: object
    quiet: tuple
    routing: tuple

    @property
    def layers(self) -> int:
        return self.sizes[2]

    def _leaf(self, group, index, shape, std, mean, how="normal", rows=None,
              dtype=None):
        how = how if isinstance(how, str) else tuple(how)
        return _draw(jnp.asarray(self.seed & 0x7FFFFFFF, jnp.int32),
                     jnp.asarray(self.seed >> 31, jnp.int32),
                     jnp.asarray(group, jnp.int32),
                     jnp.asarray(index, jnp.int32), rows, shape=shape,
                     std=std, mean=mean,
                     dtype=jnp.dtype(dtype or self.dtype), how=how)

    def drawn(self, i: int) -> dict:
        """Layer i's draws by their published names; an expert layer's
        balancing bias as drawn, at zero."""
        spec = layer_spec(self.sizes, i)
        return {name: self._leaf(
            i + 1, j, *spec[name],
            dtype=jnp.float32 if name in FLOAT32 else None)
            for j, name in enumerate(sorted(spec))}

    def layer(self, i: int) -> dict:
        """Layer i's tensors by their published names, an expert layer's
        balancing bias calibrated (`balancing_biases`)."""
        lw = self.drawn(i)
        if BIAS in lw:
            lw[BIAS] = jnp.asarray(balancing_biases(
                self.seed, self.sizes, self.quiet,
                self.routing)[i - self.sizes[11]])
        return lw

    def embed(self):
        return self._leaf(0, 0, (self.sizes[0], self.sizes[1]), 1.0, 0.0)

    def head(self):
        rows = np.ones((self.sizes[0],), np.float32)
        rows[list(self.quiet)] = QUIET
        return self._leaf(0, 1, (self.sizes[0], self.sizes[1]),
                          self.sizes[1] ** -0.5, 0.0, rows=rows)

    def norm(self):
        return self._leaf(0, 2, (self.sizes[1],), GAIN_STD, 1.0)


@functools.lru_cache(maxsize=4)
def balancing_biases(seed: int, sizes: tuple, quiet: tuple,
                     routing: tuple) -> tuple:
    """Every expert layer's `e_score_correction_bias` [E] float32, in the
    layers' order (the file's head says why): `BALANCE_TOKENS` random
    tokens, one sequence, through the reference's layers in float32, a
    layer's weights drawn when the loop reaches it; at an expert layer the
    bias is calibrated on the layer's own input to its MLP and the layer
    then run with it. A function of the seed and the configuration alone,
    computed once a process: the program's tree and the reference get the
    same arrays."""
    from benchmarks.families.kimi_linear import reference as ref

    (vocab, _, layers, h, kr, dn, dr, dv, _, _, _, dense, (kh, kd, _), kda,
     _, _) = sizes
    k, norm, scale, first, eps = routing
    w = Seeded(seed, sizes, jnp.dtype(jnp.float32), quiet, routing)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31), layers + 1)
    ids = jax.random.randint(key, (BALANCE_TOKENS,), 0, vocab)
    out = []
    with jax.default_matmul_precision("highest"):
        x = w.embed()[ids]
        for i in range(layers):
            lw = w.drawn(i)
            if i + 1 in kda:
                x = ref._kda(x, lw, heads=kh, dk=kd, eps=eps)[0]
            else:
                x = ref._mla(x, lw, heads=h, dn=dn, dr=dr, dv=dv, kr=kr,
                             eps=eps)[0]
            if i < dense:
                x = ref._dense_mlp(x, lw, eps=eps)
            else:
                lw[BIAS] = _balance(
                    x, lw["post_attention_layernorm.weight"], lw[ROUTER],
                    k=k, eps=eps)
                out.append(np.asarray(lw[BIAS]))
                if i + 1 < layers:
                    x = ref._experts(x, lw, eps=eps, k=k, norm=norm,
                                     scale=scale, first=first)[0]
            del lw
    return tuple(out)


def of_config(seed: int, config: dict, dtype=jnp.float32) -> Seeded:
    """The checkpoint every side of a run starts from, not yet drawn."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return Seeded(
        seed, sizes_of(config), jnp.dtype(dtype),
        gpt2_weights.quiet_ids(config),
        (int(config["num_experts_per_token"]),
         bool(config["moe_renormalize"]),
         float(config["routed_scaling_factor"]),
         int(config["experts_held"]["first"]),
         float(config["rms_norm_eps"])))


def program_layer(lw: dict, sizes: tuple) -> dict:
    """One layer in the tree `models/kimi_linear.init_params` builds."""
    (_, _, _, h, kr, dn, _, dv, *_rest) = sizes
    a = "self_attn."
    out = {"ln1": {"scale": lw["input_layernorm.weight"]},
           "ln2": {"scale": lw["post_attention_layernorm.weight"]}}
    if a + "A_log" in lw:
        out["attn"] = {
            # The three projections, and their convolutions, side by side.
            "w_qkv": jnp.concatenate(
                [lw[f"{a}{n}_proj.weight"] for n in "qkv"], axis=1),
            "conv_w": jnp.concatenate(
                [lw[f"{a}{n}_conv1d.weight"] for n in "qkv"], axis=1),
            "w_fa": lw[a + "f_a_proj.weight"],
            "w_fb": lw[a + "f_b_proj.weight"],
            "dt_bias": lw[a + "dt_bias"], "a_log": lw[a + "A_log"],
            "w_b": lw[a + "b_proj.weight"],
            "w_ga": lw[a + "g_a_proj.weight"],
            "w_gb": lw[a + "g_b_proj.weight"],
            "norm": {"scale": lw[a + "o_norm.weight"]},
            "w_out": lw[a + "o_proj.weight"]}
    else:
        # `kv_b_proj` in the two halves the program holds apart
        # (models/mla.py `split_kv_b`).
        kvb = lw[a + "kv_b_proj.weight"].reshape(kr, h, dn + dv)
        out["attn"] = {
            "wq": lw[a + "q_proj.weight"],
            "wkva": lw[a + "kv_a_proj_with_mqa.weight"],
            "kvn": {"scale": lw[a + "kv_a_layernorm.weight"]},
            "wuk": kvb[..., :dn], "wuv": kvb[..., dn:],
            "wo": lw[a + "o_proj.weight"]}
    if ROUTER in lw:
        b = "block_sparse_moe."
        # The hidden width padded with zeros to whole tiles, as the program
        # holds the stacks ([64, 2304, 1024] -> [64, 2560, 1024]; the expert
        # computed is the same, and the reference takes the draws unpadded).
        wg, wu, wd = pad_experts(lw[b + "experts.w1"], lw[b + "experts.w3"],
                                 lw[b + "experts.w2"])
        out["moe"] = {
            "wr": lw[ROUTER], "br": lw[BIAS], "wg": wg, "wu": wu, "wd": wd,
            "shared": {"wg": lw[b + "shared_experts.gate_proj.weight"],
                       "wu": lw[b + "shared_experts.up_proj.weight"],
                       "wd": lw[b + "shared_experts.down_proj.weight"]}}
    else:
        out["mlp"] = {"wg": lw["mlp.gate_proj.weight"],
                      "wu": lw["mlp.up_proj.weight"],
                      "wd": lw["mlp.down_proj.weight"]}
    return out


def program_tree(w: Seeded) -> dict:
    """The checkpoint in the program's tree, every leaf drawn in `w.dtype`
    (float32 draw, cast, the float32 freed before the next leaf). The
    balancing biases are calibrated BEFORE the first leaf is drawn: the
    calibration holds a layer in float32 (1.8 GB at the published widths)
    and lets it go, so every leaf of the tree is placed in memory the
    calibration has left again."""
    balancing_biases(w.seed, w.sizes, w.quiet, w.routing)
    return {
        "embed": w.embed(),
        "layers": [program_layer(w.layer(i), w.sizes)
                   for i in range(w.layers)],
        "lnf": {"scale": w.norm()},
        "lm_head": w.head(),
    }
