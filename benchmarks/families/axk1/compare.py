"""The axk1 family's side of the comparison that decides `correct`: the
program's model step, called as the engine's programs call it.

`program` runs `family.forward` the way the paged engine does: one
right-padded prompt bucket into a prompt-sized latent cache (positions
clamped at the last real one, the pad tail not live), the cache widened to
the serving width, then one token at a time at a per-row offset through the
cache, teacher-forced with the sequence's own next token. Every call runs
the ABSORBED products over the latent cache (`models/mla.py` has no other
form); the reference has the published, expanded form, so the comparison
holds the one to the other. The family hands out its routing on request
(`aux=True`), so nothing is probed.

Five numbers are compared per sequence (`readings`).
`routing_disagreement` is the share of picks on which the two sides differ,
over ALL the router's experts (`afmoe`'s number: the router decides between
the 8th and the 9th of 192 scores by a difference the served precision's
rounding of the hidden state can exceed). A token that one side sends to a
HELD expert and the other does not comes out another token; a pick that
differs among the absent experts changes this chip's result only through
the weights' common divisor. So the distances (`benchmarks/check.py`'s) are
taken over the positions whose picks among the experts held are the same on
both sides in every layer: the logits at the last `check.logit_positions`
positions (the prompt's tail and every decoded token), and the latent
cache's contents, `c_kv` after its norm and `k_r` after its rotation, over
every position of the sequence (`latent_cache_distance`). The fifth,
`first_layer_latent_cache_distance`, is the same over the first layer's
planes alone at every position: four roundings from the embedding, no
attention and no routing before them, it is the number that tells the
cache's own precision from the depth's (PERF.md section 2: afmoe's fifth
number, for the same reason).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check
from benchmarks.families.afmoe.compare import routing_disagreement
from benchmarks.families.axk1 import weights as weights_lib


@functools.partial(jax.jit, static_argnames=(
    "family", "cfg", "n_prompt", "bucket", "width", "rows"))
def _program(params, ids, *, family, cfg, n_prompt, bucket, width, rows):
    prompt = jnp.zeros((bucket,), jnp.int32).at[:n_prompt].set(ids[:n_prompt])
    cache = family.init_cache(cfg, 1, bucket, dtype=cfg.dtype)
    real = (jnp.arange(bucket) < n_prompt)[None, :]
    positions = jnp.minimum(jnp.arange(bucket, dtype=jnp.int32),
                            n_prompt - 1)[None, :]
    pre, cache, aux = family.forward(
        params, cfg, prompt[None], cache=cache, positions=positions,
        kv_mask=real, live=real, aux=True)
    pad = [(0, 0)] * 5
    pad[3] = (0, width - bucket)
    cache = cache._replace(k=jnp.pad(cache.k, pad),
                           length=jnp.full((1,), n_prompt, jnp.int32))

    def step(cache, tok):
        offs = cache.length
        kv_mask = jnp.arange(width)[None, :] <= offs[:, None]
        logits, cache, aux = family.forward(
            params, cfg, tok[None, None], cache=cache, kv_mask=kv_mask,
            live=jnp.ones((1,), bool), aux=True)
        return (cache._replace(length=offs + 1),
                (logits[0, 0], aux["routing"][:, 0, 0]))

    cache, (dec, dec_picks) = jax.lax.scan(step, cache, ids[n_prompt:])
    n = ids.shape[0]
    tail = rows - (n - n_prompt)          # rows taken from the prefill
    picks = jnp.concatenate([aux["routing"][:, 0, :n_prompt],
                             dec_picks.transpose(1, 0, 2)], axis=1)
    chosen = jnp.any(jax.nn.one_hot(picks, cfg.num_experts, dtype=bool),
                     axis=2)                                  # [Le, T, E]

    # The one plane [L, 1, 1, width, kr + rope] as the reference's two.
    latent = cache.k[:, 0, 0, :n].astype(jnp.float32)
    lo, count = cfg.experts_held or (0, cfg.num_experts)
    return (jnp.concatenate([pre[0, n_prompt - tail:n_prompt], dec], axis=0),
            latent[..., :cfg.kv_lora_rank], latent[..., cfg.kv_lora_rank:],
            chosen, chosen[..., lo:lo + count])


def program(family, cfg, params, ids, shape: dict):
    """The program's (logits [P, V], c_kv [L, T, kv_lora_rank], k_r
    [L, T, rope], routing [Le, T, E] bool, the same of the experts held
    [Le, T, held]) for one sequence at the configuration's `check`
    shape."""
    n, bucket = int(shape["prompt_tokens"]), int(shape["bucket"])
    width, rows = int(shape["width"]), int(shape["logit_positions"])
    if not 0 < n <= bucket or len(ids) > width or not (
            len(ids) - n <= rows <= len(ids)):
        raise ValueError(f"{n} prompt tokens of {len(ids)} and {rows} logit "
                         f"rows do not fit bucket {bucket}, width {width}")
    return _program(params, jnp.asarray(ids, jnp.int32), family=family,
                    cfg=cfg, n_prompt=n, bucket=bucket, width=width,
                    rows=rows)


def check_sizes(config: dict, cfg) -> None:
    """The program's preset must have the file's sizes, share and
    routing."""
    got = (cfg.vocab_size, cfg.hidden_size, cfg.num_layers,
           cfg.num_dense_layers, cfg.num_heads, cfg.q_lora_rank,
           cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
           cfg.v_head_dim, cfg.intermediate_size, cfg.moe_intermediate_size,
           cfg.num_experts_held, cfg.num_shared_experts, cfg.num_experts)
    rest = (cfg.experts_held, cfg.num_experts_per_tok, cfg.route_norm,
            cfg.route_scale, cfg.rope_theta, cfg.rms_norm_eps,
            dataclass_dict(cfg.rope_scaling))
    held = config["experts_held"]
    scaling = {k: float(v) for k, v in config["rope_scaling"].items()
               if k != "type"}
    stated = ((int(held["first"]), int(config["n_routed_experts"])),
              int(config["num_experts_per_tok"]),
              bool(config["norm_topk_prob"]),
              float(config["routed_scaling_factor"]),
              float(config["rope_theta"]), float(config["rms_norm_eps"]),
              scaling)
    if (got != weights_lib.sizes_of(config) or rest != stated
            or config["rope_scaling"]["type"] != "yarn"
            or config["topk_method"] != "none"
            or config["scoring_func"] != "sigmoid"):
        raise ValueError(
            f"registry preset has {got} and {rest}, the configuration file "
            f"{weights_lib.sizes_of(config)} and {stated}")


def dataclass_dict(x) -> dict:
    return {k: float(v) for k, v in vars(x).items()}


def readings(got, want) -> dict:
    """The five numbers compared, for one sequence: `got` and `want` are
    (logits [P, V], c_kv, k_r, routing [Le, T, E], held routing
    [Le, T, held]) of the side judged and of the reference. The distances
    are over the positions whose held picks agree in every layer; the
    logits' rows are the sequence's last P positions."""
    alike = np.all(np.asarray(got[4]) == np.asarray(want[4]), axis=(0, 2))
    rows = got[0].shape[0]
    at_rows = np.flatnonzero(alike[-rows:])
    at = np.flatnonzero(alike)
    # A side that routes no compared position as the reference does has
    # no distance to show: infinite, which is outside every limit.
    whole = row = latent = float("inf")
    if len(at_rows):
        whole, row = check.distances(got[0][at_rows], want[0][at_rows])
    if len(at):
        latent = check.kv_distance(got[1][:, at], got[2][:, at],
                                   want[1][:, at], want[2][:, at])
    return {
        "logits_distance": float(whole),
        "logits_worst_position_distance": float(row),
        "latent_cache_distance": float(latent),
        "first_layer_latent_cache_distance": float(check.kv_distance(
            got[1][:1], got[2][:1], want[1][:1], want[2][:1])),
        "routing_disagreement": routing_disagreement(got[3], want[3]),
    }
