"""Seeded nemotron_h weights, drawn on the device one leaf at a time.

The cut of Nemotron-3-Nano holds 3.17 B parameters: 6.3 GB in bfloat16,
12.7 GB in float32, on a chip of 16 GB that also holds the program's copy.
So, as the afmoe and axk1 families do, `of_config` returns a `Seeded` (seed,
sizes, dtype) from which any leaf can be drawn when it is needed: every leaf
has its own key (seed, layer, index of its name), is drawn in float32 and
cast after, so the reference (float32, a layer at a time) and the program
(`program_tree`, every leaf in the served dtype) start from the same draws.

Names are the published checkpoint's (`backbone.layers.<i>.` left off),
every linear stored [in, out], the convolution [K, C], and of a layer's
experts the share HELD here stacked on a leading axis
(`mixer.experts.up_proj` [held, D, M]: `n_routed_experts` of the
configuration file counts the experts held, `published.n_routed_experts`
the router's width).

Scales (`assumed` in the configuration file), by the rules PRs 30 and 34
paid for: every matrix is drawn at `fan_in ** -0.5`, so a product keeps the
size of what goes in, and the embedding at 1: the residual stream starts at
1 and every block adds a part of that order. `o_proj` and the Mamba
`out_proj` are drawn at half that: a prompt's positions average much the
same keys into much the same vector (and, in a Mamba block, much the same
slow state), and at full gain that shared vector decides which experts every
token of a batch prefers, by seed. Norm gains are 1 + 0.1 n. The router's
128 columns are each scaled to the same length, 1 (a column's length is its
expert's popularity). `e_score_correction_bias` is CALIBRATED, as the
published model's is trained to be (DeepSeek-V3's auxiliary-loss-free
balancing: the bias of an expert picked more than its share goes down):
`balancing_biases` runs `BALANCE_TOKENS` random tokens through the blocks a
block at a time (the reference's, float32) and at every expert block moves
the bias by `BALANCE_STEP` x (an expert's picks over the mean - 1),
`BALANCE_ROUNDS` times, until all 128 experts are picked alike. Without
it (the bias at zero, this family's first weights) an expert's share of the
picks had a coefficient of variation of 0.6 to 0.9 in every expert block
after the first: relu^2 experts add a vector of positive mean, which every
token then shares, and how the 128 columns lie to it is the seed's. A row's
14 lanes then reached 26 to 31.5 of a block's 64 held experts by seed
where uniform picks reach 30.9, the experts' bytes are two thirds of a row,
and a seed's `out_tok_s` stood 2.7% under to 2.5% over the others' (11
seeds on the chip, PR 40: PERF.md section 6); `moe_held_picks_share`
did not see it (49.6 to 52.7). Calibrated, two such seeds reach 124.2 and
124.8 experts a row's four blocks on the CPU where they reached 113.6 and
121.6.
The head's rows of the tokens that
are not a whole UTF-8 text alone are scaled by 0.01 (`families/gpt2/
weights.py`).

The Mamba-2 blocks' own parameters are float32 whatever the dtype asked for
(the program keeps them so) and drawn as the published modelling code
initialises them: `A_log = log(A)`, A uniform in [1, 16]; `dt_bias` the
inverse softplus of a log-uniform draw in [`time_step_min`,
`time_step_max`] floored at `time_step_floor` (time scales of 0.6 to 1,000
tokens a head: a state that neither dies nor saturates over 2,400 tokens);
`D` = 1; the convolution's taps at K ** -0.5 and its bias at 0.1.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.gpt2 import weights as gpt2_weights
from distributed_lms_raft_llm_tpu.models.nemotron_h import pad_experts

SIZE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
             "mamba_num_heads", "mamba_head_dim", "n_groups",
             "ssm_state_size", "conv_kernel", "num_attention_heads",
             "num_key_value_heads", "head_dim", "moe_intermediate_size",
             "moe_shared_expert_intermediate_size", "n_routed_experts")
GAIN_STD, OUT_GAIN, CONV_BIAS_STD = 0.1, 0.5, 0.1
A_RANGE = (1.0, 16.0)
QUIET = gpt2_weights.QUIET
ROUTER = "mixer.gate.weight"
BIAS = "mixer.gate.e_score_correction_bias"
# The balancing bias's calibration (the file's head).
BALANCE_TOKENS, BALANCE_ROUNDS, BALANCE_STEP = 2048, 8, 0.05
# Leaves that stay float32 whatever dtype is asked for.
FLOAT32 = ("mixer.A_log", "mixer.dt_bias", "mixer.D", BIAS)


def sizes_of(config: dict) -> tuple:
    """The configuration file's sizes as a hashable tuple, and after them
    the blocks' letters, the router's width (the published count of
    experts) and the range `dt` is drawn in."""
    return tuple(int(config[k]) for k in SIZE_KEYS) + (
        str(config["hybrid_override_pattern"]),
        int(config["published"]["n_routed_experts"]),
        (float(config["time_step_min"]), float(config["time_step_max"]),
         float(config["time_step_floor"])))


def layer_spec(sizes: tuple, layer: int) -> dict:
    """name -> (shape, scale, mean, how it is drawn) of every tensor of one
    block."""
    (_, d, _, mh, mp, g, n, k, h, hkv, dh, m, ms, held, pattern, e,
     dt_range) = sizes

    def mat(*shape, gain=1.0):
        return (shape, gain * shape[-2] ** -0.5, 0.0, "normal")

    spec = {"norm.weight": ((d,), GAIN_STD, 1.0, "normal")}
    kind = pattern[layer]
    if kind == "M":
        di, conv_dim = mh * mp, mh * mp + 2 * g * n
        spec.update({
            "mixer.in_proj.weight": mat(d, di + conv_dim + mh),
            "mixer.conv1d.weight": mat(k, conv_dim),
            "mixer.conv1d.bias": ((conv_dim,), CONV_BIAS_STD, 0.0, "normal"),
            "mixer.dt_bias": ((mh,), 0.0, 0.0, ("dt_bias",) + dt_range),
            "mixer.A_log": ((mh,), 0.0, 0.0, ("a_log",) + A_RANGE),
            "mixer.D": ((mh,), 0.0, 1.0, "normal"),
            "mixer.norm.weight": ((di,), GAIN_STD, 1.0, "normal"),
            "mixer.out_proj.weight": mat(di, d, gain=OUT_GAIN),
        })
    elif kind == "*":
        spec.update({
            "mixer.q_proj.weight": mat(d, h * dh),
            "mixer.k_proj.weight": mat(d, hkv * dh),
            "mixer.v_proj.weight": mat(d, hkv * dh),
            "mixer.o_proj.weight": mat(h * dh, d, gain=OUT_GAIN),
        })
    elif kind == "E":
        spec.update({
            ROUTER: ((d, e), d ** -0.5, 0.0, "router"),
            BIAS: ((e,), 0.0, 0.0, "normal"),
            "mixer.experts.up_proj": mat(held, d, m),
            "mixer.experts.down_proj": mat(held, m, d),
            "mixer.shared_experts.up_proj.weight": mat(d, ms),
            "mixer.shared_experts.down_proj.weight": mat(ms, d),
        })
    else:
        raise ValueError(f"block {layer} of {pattern!r} is no M, E or *")
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
        lo_t, hi_t, floor = how[1:]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (np.log(hi_t) - np.log(lo_t)) + np.log(lo_t))
        dt = jnp.maximum(dt, floor)
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
        """Block i's draws by their published names; an expert block's
        balancing bias as drawn, at zero."""
        spec = layer_spec(self.sizes, i)
        return {name: self._leaf(
            i + 1, j, *spec[name],
            dtype=jnp.float32 if name in FLOAT32 else None)
            for j, name in enumerate(sorted(spec))}

    def layer(self, i: int) -> dict:
        """Block i's tensors by their published names, an expert block's
        balancing bias calibrated (`balancing_biases`)."""
        lw = self.drawn(i)
        if BIAS in lw:
            pattern = self.sizes[14]
            lw[BIAS] = jnp.asarray(balancing_biases(
                self.seed, self.sizes, self.quiet,
                self.routing)[pattern[:i].count("E")])
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


@functools.partial(jax.jit, static_argnames=("k", "eps"))
def _balance(x, gain, router, *, k, eps):
    """The bias [E] under which the tokens x [T, D] pick the router's E
    experts alike: `BALANCE_ROUNDS` steps of DeepSeek-V3's rule on the
    block's own scores."""
    h = x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                     + eps) * gain
    scores = jax.nn.sigmoid(h @ router)
    e = scores.shape[-1]

    def step(bias, _):
        _, picks = jax.lax.top_k(scores + bias, k)
        load = jnp.zeros((e,), jnp.float32).at[picks.reshape(-1)].add(1.0)
        return bias - BALANCE_STEP * (load / jnp.mean(load) - 1.0), None

    return jax.lax.scan(step, jnp.zeros((e,), jnp.float32), None,
                        length=BALANCE_ROUNDS)[0]


@functools.lru_cache(maxsize=4)
def balancing_biases(seed: int, sizes: tuple, quiet: tuple,
                     routing: tuple) -> tuple:
    """Every expert block's `e_score_correction_bias` [E] float32, in the
    blocks' order (the file's head says why): `BALANCE_TOKENS` random
    tokens, one sequence, through the reference's blocks in float32, a
    block's weights drawn when the loop reaches it; at an expert block the
    bias is calibrated on the block's own input and the block then run with
    it. A function of the seed and the configuration alone, computed once a
    process: the program's tree and the reference get the same arrays."""
    from benchmarks.families.nemotron_h import reference as ref

    (vocab, _, _, mh, mp, g, n, _, h, hkv, dh, *_rest) = sizes
    pattern = sizes[14]
    k, norm, scale, first, eps = routing
    w = Seeded(seed, sizes, jnp.dtype(jnp.float32), quiet, routing)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31), len(pattern) + 1)
    ids = jax.random.randint(key, (BALANCE_TOKENS,), 0, vocab)
    out = []
    with jax.default_matmul_precision("highest"):
        x = w.embed()[ids]
        for i, kind in enumerate(pattern):
            lw = w.drawn(i)
            if kind == "M":
                x = ref._mamba(x, lw, heads=mh, p=mp, groups=g, n=n,
                               eps=eps)[0]
            elif kind == "*":
                x = ref._attention(x, lw, heads=h, kv_heads=hkv, dh=dh,
                                   eps=eps)[0]
            else:
                lw[BIAS] = _balance(x, lw["norm.weight"], lw[ROUTER], k=k,
                                    eps=eps)
                out.append(np.asarray(lw[BIAS]))
                if "E" in pattern[i + 1:]:
                    x = ref._experts(x, lw, eps=eps, k=k, norm=norm,
                                     scale=scale, first=first)[0]
            del lw
    return tuple(out)


def of_config(seed: int, config: dict, dtype=jnp.float32) -> Seeded:
    """The checkpoint every side of a run starts from, not yet drawn."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    sizes = sizes_of(config)
    if len(sizes[14]) != sizes[2]:
        raise ValueError(f"{sizes[2]} layers but the pattern {sizes[14]!r}")
    return Seeded(
        seed, sizes, jnp.dtype(dtype), gpt2_weights.quiet_ids(config),
        (int(config["num_experts_per_tok"]), bool(config["norm_topk_prob"]),
         float(config["routed_scaling_factor"]),
         int(config["experts_held"]["first"]),
         float(config["layer_norm_epsilon"])))


def program_layer(lw: dict) -> dict:
    """One block in the tree `models/nemotron_h.init_params` builds."""
    out = {"ln": {"scale": lw["norm.weight"]}}
    if "mixer.in_proj.weight" in lw:
        out["mamba"] = {
            "w_in": lw["mixer.in_proj.weight"],
            "conv_w": lw["mixer.conv1d.weight"],
            "conv_b": lw["mixer.conv1d.bias"],
            "dt_bias": lw["mixer.dt_bias"], "a_log": lw["mixer.A_log"],
            "d": lw["mixer.D"],
            "norm": {"scale": lw["mixer.norm.weight"]},
            "w_out": lw["mixer.out_proj.weight"]}
    elif ROUTER in lw:
        # Both of an expert's widths padded with zeros to whole tiles, as
        # the program holds them ([64, 2688, 1856] -> [64, 3072, 2048]; the
        # expert computed is the same, and the reference takes the draws
        # unpadded).
        wu, wd = pad_experts(lw["mixer.experts.up_proj"],
                             lw["mixer.experts.down_proj"])
        out["moe"] = {
            "wr": lw[ROUTER],
            "br": lw[BIAS],
            "wu": wu, "wd": wd,
            "shared": {"wu": lw["mixer.shared_experts.up_proj.weight"],
                       "wd": lw["mixer.shared_experts.down_proj.weight"]}}
    else:
        out["attn"] = {"wq": lw["mixer.q_proj.weight"],
                       "wk": lw["mixer.k_proj.weight"],
                       "wv": lw["mixer.v_proj.weight"],
                       "wo": lw["mixer.o_proj.weight"]}
    return out


def program_tree(w: Seeded) -> dict:
    """The checkpoint in the program's tree, every leaf drawn in `w.dtype`
    (float32 draw, cast, the float32 freed before the next leaf). The
    balancing biases are calibrated BEFORE the first leaf is drawn: the
    calibration holds a block in float32 (5.2 GB at the published widths)
    and lets it go, so every leaf of the tree is placed in memory the
    calibration has left again (PERF.md section 6, PR 40, call C)."""
    balancing_biases(w.seed, w.sizes, w.quiet, w.routing)
    return {
        "embed": w.embed(),
        "layers": [program_layer(w.layer(i)) for i in range(w.layers)],
        "lnf": {"scale": w.norm()},
        "lm_head": w.head(),
    }
