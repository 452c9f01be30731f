"""The afmoe family's side of the comparison that decides `correct`: the
program's model step, called as the engine's programs call it.

`program` runs `family.forward` the way the paged engine's
`_prefill_program` and `_step_program` do: one right-padded prompt bucket
into a prompt-sized cache (positions clamped at the last real one, the pad
tail not live), the cache spliced into a slot of the serving width, then
one token at a time at a per-row offset through the cache, teacher-forced
with the sequence's own next token. The family hands out its routing on
request (`aux=True`), so nothing is probed.

Five numbers are compared per sequence (`readings`). `routing_disagreement`
is the share of picks on which the two sides differ: over expert layers and
tokens, the experts one side sends a token to and the other does not, over
the 2 x `num_experts_per_tok` there could be. The router decides between the
8th and the 9th of 128 scores by a difference that the served precision's
rounding of the hidden state can exceed, and a token that goes to another
expert comes out another token (PERF.md section 6, PR 29). So the three
distances (`benchmarks/check.py`'s) are taken over the positions on which
both sides sent the token to the same experts in every layer: the logits at
the last `check.logit_positions` positions (the prompt's tail and every
decoded token; the whole matrix would be 1.9 GB a side), the keys and values
over every position of the sequence, which holds the window's layers to the
reference over the whole prompt. The fifth,
`first_layer_keys_and_values_distance`, is the same distance over the first
layer's keys and values alone, at every position (no routing comes before
them). It is there for the cache's own precision: by the last layer the
served path's bfloat16 activations have been rounded some eighty times in
series and stand 1% from float32, which is also what int8 keys and values
alone cost, so no number taken at depth tells the two apart; the first
layer's keys and values are four or five roundings from the embedding
(families/afmoe/README.md has the readings).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check
from benchmarks.families.afmoe import weights as weights_lib


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
    cache = cache._replace(k=jnp.pad(cache.k, pad), v=jnp.pad(cache.v, pad),
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

    def held(x):  # [L, 1, Hkv, width, Dh] -> [L, Hkv, T, Dh] float32
        return x[:, 0, :, :n].astype(jnp.float32)

    return (jnp.concatenate([pre[0, n_prompt - tail:n_prompt], dec], axis=0),
            held(cache.k), held(cache.v), chosen)


def program(family, cfg, params, ids, shape: dict):
    """The program's (logits [P, V], keys, values [L, Hkv, T, Dh], routing
    [Le, T, E] bool) for one sequence at the configuration's `check` shape."""
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
    """The program's preset must have the file's sizes, pattern and
    routing."""
    got = (cfg.vocab_size, cfg.hidden_size, cfg.num_layers,
           cfg.num_dense_layers, cfg.num_heads, cfg.num_kv_heads,
           cfg.head_dim, cfg.intermediate_size, cfg.moe_intermediate_size,
           cfg.num_experts, cfg.num_shared_experts)
    rest = (tuple(cfg.types), cfg.sliding_window, cfg.num_experts_per_tok,
            cfg.route_norm, cfg.route_scale, cfg.mup_enabled, cfg.rope_theta,
            cfg.rms_norm_eps)
    stated = (tuple(config["layer_types"]), int(config["sliding_window"]),
              int(config["num_experts_per_tok"]), bool(config["route_norm"]),
              float(config["route_scale"]), bool(config["mup_enabled"]),
              float(config["rope_theta"]), float(config["rms_norm_eps"]))
    if got != weights_lib.sizes_of(config) or rest != stated:
        raise ValueError(
            f"registry preset has {got} and {rest}, the configuration file "
            f"{weights_lib.sizes_of(config)} and {stated}")


def routing_disagreement(got, want) -> float:
    """Experts one side sends a token to and the other does not, over the
    2 x experts-per-token a token could differ by; both [Le, T, E] bool."""
    got, want = np.asarray(got), np.asarray(want)
    k = max(1, int(want.sum(axis=-1).max()))
    return float(np.sum(got != want) / (2.0 * k * want.shape[0]
                                        * want.shape[1]))


def readings(got, want) -> dict:
    """The five numbers compared, for one sequence: `got` and `want` are
    (logits [P, V], keys, values, routing) of the side judged and of the
    reference. The distances are over the positions routed alike in every
    layer; the logits' rows are the sequence's last P positions."""
    alike = np.all(np.asarray(got[3]) == np.asarray(want[3]), axis=(0, 2))
    rows = got[0].shape[0]
    at_rows = np.flatnonzero(alike[-rows:])
    at = np.flatnonzero(alike)
    # A side that routes no compared position as the reference does has
    # no distance to show: infinite, which is outside every limit.
    whole = row = kv = float("inf")
    if len(at_rows):
        whole, row = check.distances(got[0][at_rows], want[0][at_rows])
    if len(at):
        kv = check.kv_distance(got[1][:, :, at], got[2][:, :, at],
                               want[1][:, :, at], want[2][:, :, at])
    return {
        "logits_distance": float(whole),
        "logits_worst_position_distance": float(row),
        "keys_and_values_distance": float(kv),
        "first_layer_keys_and_values_distance": float(check.kv_distance(
            got[1][:1], got[2][:1], want[1][:1], want[2][:1])),
        "routing_disagreement": routing_disagreement(got[3], want[3]),
    }
