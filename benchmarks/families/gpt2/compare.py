"""GPT-2's side of the comparison that decides `correct`: the program's
model step, called as the engine's programs call it.

`program` runs `family.forward` the way the paged engine's
`_prefill_program` and `_step_program` do: one right-padded prompt bucket
into a prompt-sized cache, the cache spliced into a slot of the serving
width, then one token at a time at a per-row offset through the int8 KV
cache, teacher-forced with the sequence's own next token. The engine's
programs sample on the device and return tokens only, so the comparison
cannot go through them (PERF.md, Open questions).

Three numbers are compared per sequence (`readings`). Two on the logits,
centred over the vocabulary row by row: the distance between the two
matrices [T, V] as a share of the reference's norm, and the largest such
share of any single row. One on the cache: the distance between the keys
and values the program's cache holds after the last token (dequantised)
and the reference's. With seeded weights attention is diffuse and averages
the cache's rounding away before it reaches the logits, so the precision of
K and V shows only there. The distance functions are `benchmarks/check.py`'s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import check
from benchmarks.families.gpt2 import weights as weights_lib


@functools.partial(
    jax.jit, static_argnames=("family", "cfg", "n_prompt", "bucket", "width")
)
def _program_logits(params, ids, *, family, cfg, n_prompt, bucket, width):
    prompt = jnp.zeros((bucket,), jnp.int32).at[:n_prompt].set(ids[:n_prompt])
    cache = family.init_cache(cfg, 1, bucket, dtype=cfg.dtype)
    kv_mask = (jnp.arange(bucket) < n_prompt)[None, :]
    positions = jnp.minimum(jnp.arange(bucket, dtype=jnp.int32),
                            n_prompt - 1)[None, :]
    pre, cache = family.forward(params, cfg, prompt[None], cache=cache,
                                positions=positions, kv_mask=kv_mask)

    def widen(x):  # the prompt-sized cache into a slot of the serving width
        if x is None:
            return None
        pad = [(0, 0)] * x.ndim
        pad[3] = (0, width - bucket)
        return jnp.pad(x, pad)

    cache = cache._replace(k=widen(cache.k), v=widen(cache.v),
                           ks=widen(cache.ks), vs=widen(cache.vs),
                           length=jnp.full((1,), n_prompt, jnp.int32))

    def step(cache, tok):
        offs = cache.length
        kv_mask = jnp.arange(width)[None, :] <= offs[:, None]
        logits, cache = family.forward(params, cfg, tok[None, None],
                                       cache=cache, kv_mask=kv_mask)
        return cache._replace(length=offs + 1), logits[0, 0]

    cache, dec = jax.lax.scan(step, cache, ids[n_prompt:])

    def held(x, scale):  # [L, 1, H, width, Dh] -> [L, H, T, Dh] float32
        x = x[:, 0, :, :ids.shape[0]].astype(jnp.float32)
        if scale is not None:
            x = x * scale[:, 0, :, :ids.shape[0], None]
        return x

    return (jnp.concatenate([pre[0, :n_prompt], dec], axis=0),
            held(cache.k, cache.ks), held(cache.v, cache.vs))


def program_logits(family, cfg, params, ids, n_prompt: int, bucket: int,
                   width: int):
    """(logits [T, V], keys, values [L, H, T, Dh]) of the program's model
    step for one sequence: logit rows below `n_prompt` from the prefill of
    the prompt bucket, the rest decoded one token at a time through the
    cache at the serving width; keys and values as the cache holds them
    after the last token, dequantised."""
    if not 0 < n_prompt <= bucket or len(ids) > width:
        raise ValueError(f"{n_prompt} prompt tokens of {len(ids)} do not fit "
                         f"bucket {bucket} and width {width}")
    return _program_logits(params, jnp.asarray(ids, jnp.int32), family=family,
                           cfg=cfg, n_prompt=n_prompt, bucket=bucket,
                           width=width)


def program(family, cfg, params, ids, shape: dict):
    """The program's (logits, keys, values) for one sequence at the
    configuration's `check` shape."""
    return program_logits(family, cfg, params, ids,
                          int(shape["prompt_tokens"]), int(shape["bucket"]),
                          int(shape["width"]))


def check_sizes(config: dict, cfg) -> None:
    """The program's preset must have the file's published sizes."""
    got = (cfg.vocab_size, cfg.max_position_embeddings, cfg.hidden_size,
           cfg.num_layers, cfg.num_heads)
    if got != weights_lib.sizes_of(config):
        raise ValueError(f"registry preset has sizes {got}, the "
                         f"configuration file {weights_lib.sizes_of(config)}")


def readings(got, want) -> dict:
    """The three numbers compared, for one sequence: `got` and `want` are
    (logits, keys, values) of the side judged and of the reference."""
    whole, row = check.distances(got[0], want[0])
    return {"logits_distance": float(whole),
            "logits_worst_position_distance": float(row),
            "keys_and_values_distance": float(
                check.kv_distance(got[1], got[2], want[1], want[2]))}
