"""The minicpm_sala family's side of the comparison that decides `correct`:
the program's model step, called as the engine's programs call it.

`program` takes one sequence through `family.forward` the way ONE ADMISSION
of the paged engine goes (`engine/paged.py`; `families/kimi_linear/
compare.py` is the pattern), on a cache of `check.slots` rows of which one
is live at a time:

- the first `restore_at` prompt tokens (the course reader) are prefilled
  into row `FIRST_ROW` from zeros, in chunks of `prefill_chunk_tokens`
  through `rows=[row]` (`_admission_chunk`'s call: the Lightning layers'
  CHUNK form, the state carried from chunk to chunk; the sparse layers'
  general form, every query choosing its blocks through the pooled plane
  the chunks before it wrote);
- the state that prefill left is taken as a snapshot and the row's keys,
  values AND pooled entries as blocks, and all are put into row
  `SERVED_ROW`, over a previous tenant's state and pages
  (`_export_state_program`, `_restore_state_program` and the block splice of
  a prefix hit: an entry of the pooled plane travels with the positions of
  its own group);
- the rest of the prompt (the question) is prefilled into that row, the last
  chunk right-padded (the pad tail not live: it must not move the state);
- then one token at a time over ALL the rows at per-row offsets, the served
  row alone live, teacher-forced with the sequence's own next token, through
  the STEP form (on the TPU the kernels `sparse_select`, `sparse_decode` and
  `lightning_step`), as the megastep's body calls it.

Every other row holds a state, keys and pooled entries that are not zero
from the start, and no phase has it live. The reference has the recurrence
token by token and the selection per query with the attention expanded, so
the comparison holds both forms of both mixers, the carry from chunk to
chunk, the snapshot, the pooled plane across the splice and the lanes that
are not live to it.

Eight numbers are compared per sequence (`readings`).
`selection_disagreement` is the share of (sparse layer, key head, position
at or past `dense_len`) whose set of chosen blocks differs between the two
sides. A query that reads another block comes out another token at that
position, so the logits' distances (`benchmarks/check.py`'s, at the last
`check.logit_positions` positions) and `kv_cache_distance` (keys and values
at every position) are taken over the positions SELECTED ALIKE in every
sparse layer and key head, as `routing_disagreement`'s families do for
experts. `pooled_keys_distance` is over every pooled key (each averages 32
positions; none is wholly alike). A state sums over every position:
`recurrent_state_distance` is the root mean square over the Lightning layers
of the state's distance as a share of the reference's, after the last token;
`first_lightning_state_distance` the same of the first Lightning layer alone
(one sparse layer and one MLP before it: the state's own precision, apart
from the depth's). `idle_rows_state_change` is the share of the values in
the state plane of the rows that were not live that are not bit-equal at the
end to what they were: its limit is 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check
from benchmarks.families.minicpm_sala import reference as reference_lib
from benchmarks.families.minicpm_sala import weights as weights_lib

# The rows of the cache a sequence is taken through (module docstring).
FIRST_ROW, SERVED_ROW = 1, 2


@functools.partial(jax.jit, static_argnames=("family", "cfg"),
                   donate_argnames=("cache",))
def _chunk(params, cache, ids, start, n_prompt, row, *, family, cfg):
    """One prefill chunk into `row`, as `_admission_chunk` forwards it: the
    positions at and past `n_prompt` are the pad tail."""
    at = start + jnp.arange(ids.shape[0], dtype=jnp.int32)
    logits, new, aux = family.forward(
        params, cfg, ids[None], cache=cache._replace(length=start[None]),
        rows=row[None], positions=jnp.minimum(at, n_prompt - 1)[None],
        live=(at < n_prompt)[None], aux=True)
    return (new._replace(length=cache.length), logits[0],
            aux["selection"][:, 0])


@functools.partial(jax.jit, static_argnames=("family", "cfg"),
                   donate_argnames=("cache",))
def _step(params, cache, toks, live, *, family, cfg):
    """One decode step over every row, as the megastep's body forwards it:
    `cache.length` [S] is each row's offset, `live` [S] its lane."""
    offs = cache.length
    kv_mask = jnp.arange(cache.k.shape[3])[None, :] <= offs[:, None]
    logits, new, aux = family.forward(
        params, cfg, toks[:, None], cache=cache, kv_mask=kv_mask, live=live,
        aux=True)
    return (new._replace(length=jnp.where(live, offs + 1, offs)),
            logits[:, 0], aux["selection"])


@functools.partial(jax.jit, donate_argnames=("cache",))
def _tenants(cache, seed):
    """The cache with every row holding a previous tenant's state (nothing
    is zero, nothing is alike from row to row), keys, values and pooled
    entries (1.5 everywhere: the planes are 3.3 GB each at the timed sizes,
    filled where they lie), but `FIRST_ROW`'s state: a staged slot starts
    from zeros."""
    fresh = (jnp.arange(cache.ssm.shape[1]) == FIRST_ROW)
    ssm = 1.0 + jax.random.uniform(jax.random.key(seed), cache.ssm.shape,
                                   cache.ssm.dtype)
    return cache._replace(
        ssm=jnp.where(fresh[None, :, None, None, None], 0.0, ssm),
        k=cache.k + 1.5, v=cache.v + 1.5, pool=cache.pool + 1.5)


@functools.partial(jax.jit, static_argnames=("restore", "entries"),
                   donate_argnames=("cache",))
def _hit(cache, *, restore: int, entries: int):
    """A prefix hit: the first row's state as a snapshot, and its first
    `restore` positions' keys and values with the pooled entries of their
    own groups as blocks, into the served row, where the planes lie."""
    def spliced(plane, n):
        return plane.at[:, SERVED_ROW, :, :n].set(plane[:, FIRST_ROW, :, :n])

    return cache._replace(
        ssm=cache.ssm.at[:, SERVED_ROW].set(cache.ssm[:, FIRST_ROW]),
        k=spliced(cache.k, restore), v=spliced(cache.v, restore),
        pool=spliced(cache.pool, entries))


def program(family, cfg, params, ids, shape: dict):
    """The program's side of `reference.forward`'s tuple for one sequence at
    the configuration's `check` shape; its last entry is the idle rows'
    share of changed state."""
    n, width = int(shape["prompt_tokens"]), int(shape["width"])
    rows, slots = int(shape["logit_positions"]), int(shape["slots"])
    c, restore = int(shape["prefill_chunk_tokens"]), int(shape["restore_at"])
    total = len(ids)
    stride = cfg.kernel_stride
    if (not 0 < restore < n <= int(shape["bucket"]) or restore % c
            or restore % stride or total > width
            or not total - n <= rows <= total
            or slots <= max(FIRST_ROW, SERVED_ROW)):
        raise ValueError(
            f"{n} prompt tokens of {total}, {rows} logit rows, a snapshot "
            f"at {restore} and {slots} rows do not fit chunks of {c}, "
            f"bucket {shape['bucket']} and width {width}")
    run = dict(family=family, cfg=cfg)
    chunks = -(-n // c)
    prompt = np.zeros((chunks * c,), np.int32)
    prompt[:n] = ids[:n]
    cache = _tenants(family.init_cache(cfg, slots, width, dtype=cfg.dtype),
                     np.int32(ids[0]))
    cache = cache._replace(length=jnp.zeros((slots,), jnp.int32))
    was = np.array(cache.ssm)
    logits, picks = [], []

    def prefill(cache, row, first, last):
        for i in range(first, last):
            cache, out, chose = _chunk(
                params, cache, prompt[i * c:(i + 1) * c], np.int32(i * c),
                np.int32(n), np.int32(row), **run)
            real = min(c, n - i * c)
            logits.append(out[:real] if (i + 1) * c > n - rows else None)
            picks.append(chose[:, :, :real])
        return cache

    cache = prefill(cache, FIRST_ROW, 0, restore // c)
    # A prefix hit: the snapshot, and the blocks of the first row with the
    # pooled entries of their own positions, into the served row.
    was[:, FIRST_ROW] = np.asarray(cache.ssm[:, FIRST_ROW])
    cache = _hit(cache, restore=restore, entries=restore // stride)
    cache = prefill(cache, SERVED_ROW, restore // c, chunks)
    served = np.arange(slots) == SERVED_ROW
    cache = cache._replace(length=jnp.asarray(
        np.where(served, n, np.where(np.arange(slots) == FIRST_ROW,
                                     restore, 0)), jnp.int32))
    for tok in np.asarray(ids[n:], np.int32):
        cache, out, chose = _step(
            params, cache, np.where(served, tok, 0).astype(np.int32), served,
            **run)
        logits.append(out[SERVED_ROW][None])
        picks.append(chose[:, SERVED_ROW])
    entries = max((total - 2 * stride) // stride + 1, 0)
    held = cache.pool[:, SERVED_ROW, :, :entries + 1].astype(jnp.float32)
    return (jnp.concatenate([x for x in logits if x is not None])[-rows:],
            cache.k[:, SERVED_ROW, :, :total].astype(
                jnp.float32).transpose(0, 2, 1, 3),
            cache.v[:, SERVED_ROW, :, :total].astype(
                jnp.float32).transpose(0, 2, 1, 3),
            # Entry b of the plane is the mean of the positions' group b: the
            # pooled key c_j is the mean of entries j and j + 1.
            0.5 * (held[..., :-1, :] + held[..., 1:, :]).transpose(0, 2, 1, 3),
            cache.ssm[:, SERVED_ROW],
            np.concatenate([np.asarray(p) for p in picks], axis=2),
            cfg.dense_len, _changed(cache.ssm, was, ~served))


def _changed(now, was, idle) -> float:
    """The share of the idle rows' values that are not what they were."""
    differ = np.asarray(now != was)[:, idle]
    return float(differ.sum()) / differ.size


def check_sizes(config: dict, cfg) -> None:
    """The program's preset must have the file's sizes, layers, scalars and
    sparse sizes."""
    got = (cfg.vocab_size, cfg.hidden_size, cfg.num_layers, cfg.num_heads,
           cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
           cfg.lightning_heads, cfg.lightning_head_dim,
           tuple(cfg.mixer_types), cfg.published_layers, cfg.layer_offset,
           float(cfg.scale_emb), cfg.hidden_size / cfg.dim_model_base)
    rest = ((cfg.kernel_size, cfg.kernel_stride, cfg.block_size,
             cfg.init_blocks, cfg.window_size, cfg.topk, cfg.dense_len),
            float(cfg.scale_depth), float(cfg.rms_norm_eps),
            float(cfg.rope_theta))
    stated = (reference_lib.sparse_sizes(config),
              float(config["scale_depth"]), float(config["rms_norm_eps"]),
              float(config["rope_theta"]))
    want = weights_lib.sizes_of(config)
    if (got != want or rest != stated or config["attn_use_rope"]
            or not config["lightning_use_rope"] or not config["qk_norm"]
            or int(config["mup_denominator"]) != cfg.published_layers):
        raise ValueError(
            f"registry preset has {got} and {rest}, the configuration file "
            f"{want} and {stated}")


def _share(got, want) -> float:
    return float(np.linalg.norm(np.asarray(got) - np.asarray(want))
                 / np.linalg.norm(np.asarray(want)))


def alike(got, want, dense_len: int):
    """(positions [T] bool whose chosen blocks are the reference's in every
    sparse layer and key head, or that lie below `dense_len`; the share of
    (layer, key head, position at or past `dense_len`) that differ)."""
    a, b = np.asarray(got[5]), np.asarray(want[5])
    nb = min(a.shape[-1], b.shape[-1])
    same = np.all(a[..., :nb] == b[..., :nb], axis=-1)        # [La, Hkv, T]
    past = np.arange(same.shape[-1]) >= dense_len
    differ = float(np.mean(~same[..., past])) if past.any() else 0.0
    return np.all(same, axis=(0, 1)) | ~past, differ


def state_distance(got, want, layers) -> float:
    """Root mean square over `layers` (indices into the Lightning layers) of
    the state's distance as a share of the reference's."""
    return float(np.sqrt(np.mean(np.square(
        [_share(got[4][b], want[4][b]) for b in layers]))))


def readings(got, want) -> dict:
    """The eight numbers compared, for one sequence: `got` and `want` are
    `reference.forward`'s tuple of the side judged and of the reference."""
    clean, differ = alike(got, want, int(want[6]))
    rows = got[0].shape[0]
    at_rows = np.flatnonzero(clean[-rows:])
    at = np.flatnonzero(clean)
    # A side that selects no compared position as the reference does has no
    # distance to show: infinite, which is outside every limit.
    whole = row = kv = float("inf")
    if len(at_rows):
        whole, row = check.distances(got[0][at_rows], want[0][at_rows])
    if len(at):
        kv = float(check.kv_distance(
            *(jnp.asarray(np.asarray(x)[:, at]) for x in
              (got[1], got[2], want[1], want[2]))))
    return {
        "logits_distance": float(whole),
        "logits_worst_position_distance": float(row),
        "kv_cache_distance": kv,
        "pooled_keys_distance": _share(got[3], want[3]),
        "recurrent_state_distance": state_distance(
            got, want, range(np.asarray(want[4]).shape[0])),
        "first_lightning_state_distance": state_distance(got, want, [0]),
        "selection_disagreement": differ,
        "idle_rows_state_change": float(got[7]),
    }
