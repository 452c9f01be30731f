"""The comparison that decides `correct`: the program's model step against
the plain reference, logits at every position.

The program side runs `family.forward` the way the paged engine's
`_prefill_program` and `_step_program` call it: one right-padded prompt
bucket into a prompt-sized cache, the cache spliced into a slot of the
serving width, then one token at a time at a per-row offset through the
int8 KV cache, teacher-forced with the sequence's own next token. The
engine's programs sample on the device and return tokens only, so the
comparison cannot go through them (PERF.md, Open questions).

Three numbers are compared per sequence. Two on the logits, centred over
the vocabulary row by row (softmax does not see a row's mean): the L2
distance between the two matrices [T, V] as a share of the reference's L2
norm (an aggregate over T x V values, steady from seed to seed), and the
largest such share of any single row (an error confined to a few positions
shows there and is diluted in the aggregate). One on the cache: the L2
distance between the keys and values the program's cache holds after the
last token (dequantised) and the reference's, as a share of the reference's
norm. With seeded weights attention is diffuse and averages the cache's
rounding away before it reaches the logits, so the precision of K and V
shows only here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights as weights_lib
from benchmarks.reference import gpt2 as reference

# Each above the largest a sound run gave and below the smallest any control
# gave (int4 weights, int4 K and V, fp8 activations: `reference.CONTROLS`);
# the readings and the arithmetic are in PERF.md section 2.
LIMIT = 0.057
LIMIT_POSITION = 0.064
LIMIT_KV = 0.057


def sequences(seed: int, n: int, length: int, vocab: int) -> np.ndarray:
    """`n` seeded sequences of `length` token ids below `vocab`."""
    rng = np.random.default_rng([int(seed), 0xC0DE])
    return rng.integers(0, vocab, size=(n, length), dtype=np.int32)


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


@jax.jit
def distances(got, want):
    """(||centred(got) - centred(want)|| / ||centred(want)|| over the whole
    matrix, the largest such share of one row), float32."""
    got = got.astype(jnp.float32)
    got = got - jnp.mean(got, axis=-1, keepdims=True)
    want = want - jnp.mean(want, axis=-1, keepdims=True)
    rows = (jnp.linalg.norm(got - want, axis=-1)
            / jnp.linalg.norm(want, axis=-1))
    return jnp.linalg.norm(got - want) / jnp.linalg.norm(want), jnp.max(rows)


def distance(got, want) -> float:
    return float(distances(got, want)[0])


@jax.jit
def kv_distance(got_k, got_v, want_k, want_v):
    """||got - want|| / ||want|| over keys and values together, float32."""
    num = jnp.sum(jnp.square(got_k - want_k)) + jnp.sum(
        jnp.square(got_v - want_v))
    den = jnp.sum(jnp.square(want_k)) + jnp.sum(jnp.square(want_v))
    return jnp.sqrt(num / den)


def reference_logits(config: dict, seed: int, seqs, control=None) -> list:
    """The reference's (logits, keys, values) for each sequence, from the
    seed alone; with `control`, the reference in that lower precision
    (reference.CONTROLS)."""
    w = weights_lib.of_config(seed, config, jnp.float32)
    eps = float(config["layer_norm_epsilon"])
    return [reference.forward(w, s, n_head=int(config["n_head"]), eps=eps,
                              control=control)
            for s in seqs]


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
    whole, row = distances(got[0], want[0])
    return {"whole": float(whole), "position": float(row),
            "kv": float(kv_distance(got[1], got[2], want[1], want[2]))}


def compare(family, cfg, params, config: dict, seed: int, shape: dict) -> dict:
    """Run the comparison for `seed`: `shape` gives `sequences`,
    `prompt_tokens`, `decode_tokens`, `bucket` and `width`. Returns the
    distances and the verdict; prints nothing."""
    check_sizes(config, cfg)
    n, t0, t1 = (int(shape[k]) for k in
                 ("sequences", "prompt_tokens", "decode_tokens"))
    seqs = sequences(seed, n, t0 + t1, int(config["vocab_size"]))
    want = reference_logits(config, seed, seqs)
    read = [readings(program_logits(family, cfg, params, s, t0,
                                    int(shape["bucket"]),
                                    int(shape["width"])), ref)
            for s, ref in zip(seqs, want)]
    limits = {"whole": LIMIT, "position": LIMIT_POSITION, "kv": LIMIT_KV}
    worst = {k: max(r[k] for r in read) for k in limits}
    return {"readings": read, "worst": worst, "limits": limits,
            "ok": all(bool(np.isfinite(worst[k]) and worst[k] <= limits[k])
                      for k in limits)}
