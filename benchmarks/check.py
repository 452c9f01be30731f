"""The comparison that decides `correct`: the program's model step against
the family's plain reference, on seeded sequences.

What is family-blind stays here: the seeded sequences, the distance
functions, the loop over the sequences and the verdict. Everything else is
the family's (`benchmarks/families/<family>/`, named by the configuration
file): `weights` draws the checkpoint both sides start from, `reference`
is the plain float32 forward, `compare` calls the program's forward as the
engine's programs call it and says which numbers are compared
(`readings`). Each number's limit is the configuration file's, under
`check.limits`, by the name `readings` gives it: a reading without a limit
and a limit without a reading are both errors.

The distances: on logits, centred over the vocabulary row by row (softmax
does not see a row's mean), the L2 distance between two matrices [T, V] as
a share of the reference's L2 norm (an aggregate over T x V values, steady
from seed to seed) and the largest such share of any single row (an error
confined to a few positions shows there and is diluted in the aggregate);
on a cache, the L2 distance between the keys and values held and the
reference's, as a share of the reference's norm.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import families


def sequences(seed: int, n: int, length: int, vocab: int) -> np.ndarray:
    """`n` seeded sequences of `length` token ids below `vocab`."""
    rng = np.random.default_rng([int(seed), 0xC0DE])
    return rng.integers(0, vocab, size=(n, length), dtype=np.int32)


def sequences_of(config: dict, seed: int) -> np.ndarray:
    """The sequences the configuration's `check` shape asks for."""
    shape = config["check"]
    return sequences(
        seed, int(shape["sequences"]),
        int(shape["prompt_tokens"]) + int(shape["decode_tokens"]),
        int(config["vocab_size"]))


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


def reference_side(config: dict, seed: int, seqs, control=None) -> list:
    """What the family's reference gives for each sequence, from the seed
    alone; with `control`, the reference in that lower precision (one of
    the family's `reference.CONTROLS`)."""
    fam = families.of_config(config)
    w = fam.weights.of_config(seed, config, jnp.float32)
    return [fam.reference.forward(w, s, config, control=control)
            for s in seqs]


def verdict(read: list, limits: dict) -> dict:
    """The worst of each number over the sequences' readings beside its
    limit, and whether every one is finite and inside."""
    names = set(limits)
    for r in read:
        if set(r) != names:
            raise ValueError(
                f"the family reads {sorted(r)}, the configuration's "
                f"check.limits holds {sorted(names)}")
    worst = {k: max(r[k] for r in read) for k in limits}
    return {"readings": read, "worst": worst, "limits": dict(limits),
            "ok": all(bool(np.isfinite(worst[k]) and worst[k] <= limits[k])
                      for k in limits)}


def compare(family, cfg, params, config: dict, seed: int) -> dict:
    """Run the comparison for `seed` at the configuration's `check` shape
    (`sequences`, `prompt_tokens`, `decode_tokens`, `bucket`, `width`,
    `limits`): `family`, `cfg` and `params` are the program's. Returns the
    readings and the verdict; prints nothing."""
    fam = families.of_config(config)
    fam.compare.check_sizes(config, cfg)
    seqs = sequences_of(config, seed)
    want = reference_side(config, seed, seqs)
    read = [fam.compare.readings(
        fam.compare.program(family, cfg, params, s, config["check"]), ref)
        for s, ref in zip(seqs, want)]
    return verdict(read, config["check"]["limits"])
