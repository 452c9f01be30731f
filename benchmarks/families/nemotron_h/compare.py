"""The nemotron_h family's side of the comparison that decides `correct`:
the program's model step, called as the engine's programs call it.

`program` takes one sequence through `family.forward` the way ONE ADMISSION
of the paged engine goes (`engine/paged.py`), on a cache of `check.slots`
rows of which one is live at a time:

- the first `restore_at` prompt tokens are prefilled into row `FIRST_ROW`
  from zeros, in chunks of `prefill_chunk_tokens` through `rows=[row]`,
  each chunk starting from the state and the convolution's window the one
  before left in the row (`_admission_chunk`'s call, the Mamba blocks'
  CHUNK form);
- the state that prefill left is exported as a snapshot and the row's keys
  and values as blocks, and both are put into row `SERVED_ROW`, over a
  previous tenant's state (`_export_state_program`, `_restore_state_program`
  and the block splice of a prefix hit);
- the rest of the prompt is prefilled into that row, the last chunk
  right-padded (the pad tail not live: it must move neither the state nor
  the window, and routes nowhere);
- then one token at a time over ALL the rows at per-row offsets, the served
  row alone live, teacher-forced with the sequence's own next token, through
  the STEP form (on the TPU the kernel `ssm_step`), as `_decode_chunk`'s
  call.

Every other row holds a state that is not zero from the start, and no phase
has it live. The reference has the recurrence, token by token from zeros,
so the comparison holds both forms, the carry from chunk to chunk, the
snapshot and the lanes that are not live to it. The family hands out its
routing and the attention block's input on request (`aux=True`), so
nothing is probed.

Eight numbers are compared per sequence (`readings`).
`routing_disagreement` is the share of picks on which the two sides differ,
over ALL the router's experts (`afmoe`'s number). A token that one side
sends to a HELD expert and the other does not comes out another token
(its logits stand 24 to 73% from the reference's on the chip), and here it
does not stay at its own position: the convolution of every later Mamba
block reaches `conv_kernel - 1` positions back, and the state carries it on.
So the logits' distances (`benchmarks/check.py`'s, at the last
`check.logit_positions` positions) and `keys_and_values_distance` (the
attention block's cache at every position) are taken over the positions
whose picks among the experts held are the same on both sides in every
layer AT THAT POSITION AND AT THE `conv_kernel - 1` BEFORE IT, the
convolution's window (on the chip the worst compared position reads
0.035-0.115 without the window's positions and 0.012-0.029 with them;
PERF.md section 2).

`own_input_keys_and_values_distance` is the cache's own precision: the
keys and values a side holds against the REFERENCE's float32 projections of
that side's OWN input to the attention block, at every position. The
attention block is the sixth, so against the reference's keys and values a
side's cache carries five blocks of bfloat16 activations (1.0%), more than
8-bit keys and values cost (0.65%); against its own input it carries the
projection's roundings alone, and neither a routed pick nor a state reaches
this number.

A state sums over every position, so no position can be left out of it, and
about a quarter of the positions carry a pick that differs: in the blocks
after the first routed one the heads that forget within a few tokens stand
wherever the last such token left them (2 to 36% from the reference's, by
where it fell). The state is therefore read through the heads that hold the
long context, those whose time scale `1 / (A dt)` is `SLOW_TOKENS` or more
(at least a block's slowest head): many tokens average in them, and it is
there that a carry lost at a chunk's boundary and a state kept in too few
bits show (a bfloat16 state drops what a token adds once the state is 256
times larger, which is these heads' case). `recurrent_state_distance` is the
root mean square, over the Mamba blocks, of two shares a block: the slow
heads' `ssm` after the last token as a share of the reference's, and the
`conv` window's; `first_layer_recurrent_state_distance` the same of the
first block alone: one norm and one projection from the embedding, no
routing before it, it tells the state's own precision from the depth's.

`idle_rows_state_change` is the share of the values in the state planes of
the rows that were not live (the previous tenants', and the first row's
once its snapshot was taken) that are not bit-equal at the end to what they
were: its limit is 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check
from benchmarks.families.afmoe.compare import routing_disagreement
from benchmarks.families.nemotron_h import weights as weights_lib

# A head whose state still holds a token this many positions back is read
# as one that holds the long context (two served prefill chunks).
SLOW_TOKENS = 64.0
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
            aux["routing"][:, 0], aux["attn_in"][:, 0])


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
            logits[:, 0], aux["routing"][:, :, 0], aux["attn_in"][:, :, 0])


def _tenants(cache, seed: int):
    """The cache with every row's state planes holding a previous tenant's
    values (nothing is zero, nothing is alike from row to row), but
    `FIRST_ROW`'s: a staged slot starts from zeros."""
    k1, k2 = jax.random.split(jax.random.key(seed))
    fresh = (jnp.arange(cache.ssm.shape[1]) == FIRST_ROW)
    ssm = 1.0 + jax.random.uniform(k1, cache.ssm.shape, cache.ssm.dtype)
    conv = (1.0 + jax.random.uniform(k2, cache.conv.shape, jnp.float32)
            ).astype(cache.conv.dtype)
    return cache._replace(
        ssm=jnp.where(fresh[None, :, None, None, None], 0.0, ssm),
        conv=jnp.where(fresh[None, :, None, None], 0.0, conv))


def _changed(planes, were, idle) -> float:
    """The share of the idle rows' values that are not what they were."""
    moved = total = 0
    for now, was in zip(planes, were):
        differ = np.asarray(now != was)[:, idle]
        moved, total = moved + int(differ.sum()), total + differ.size
    return moved / total


def program(family, cfg, params, ids, shape: dict):
    """The program's (logits [P, V], keys [La, Hkv, T, Dh], values, ssm
    [Lm, H, P, N], conv [Lm, K-1, C], routing [Le, T, E] bool, the same of
    the experts held, the Mamba heads' time scales [Lm, H], the attention
    blocks' input [La, T, D], their key and value projections ([La, D,
    Hkv * Dh] each), the idle rows' share of changed state) for one
    sequence at the configuration's `check` shape."""
    n, width = int(shape["prompt_tokens"]), int(shape["width"])
    rows, slots = int(shape["logit_positions"]), int(shape["slots"])
    c, restore = int(shape["prefill_chunk_tokens"]), int(shape["restore_at"])
    total = len(ids)
    if (not 0 < restore < n <= int(shape["bucket"]) or restore % c
            or total > width or not total - n <= rows <= total
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
                     int(ids[0]))
    cache = cache._replace(length=jnp.zeros((slots,), jnp.int32))
    were = [np.array(cache.ssm), np.array(cache.conv)]
    logits, picks, attn_in = [], [], []

    def prefill(cache, row, first, last):
        for i in range(first, last):
            cache, out, routed, fed = _chunk(
                params, cache, prompt[i * c:(i + 1) * c], np.int32(i * c),
                np.int32(n), np.int32(row), **run)
            real = min(c, n - i * c)
            logits.append(out[:real] if (i + 1) * c > n - rows else None)
            picks.append(routed[:, :real])
            attn_in.append(fed[:, :real])
        return cache

    cache = prefill(cache, FIRST_ROW, 0, restore // c)
    # A prefix hit: the snapshot and the blocks of the first row, into the
    # served row.
    snap = (cache.ssm[:, FIRST_ROW], cache.conv[:, FIRST_ROW])
    for plane, was in zip(snap, were):
        was[:, FIRST_ROW] = np.asarray(plane)
    cache = cache._replace(
        ssm=cache.ssm.at[:, SERVED_ROW].set(snap[0]),
        conv=cache.conv.at[:, SERVED_ROW].set(snap[1]),
        k=cache.k.at[:, SERVED_ROW, :, :restore].set(
            cache.k[:, FIRST_ROW, :, :restore]),
        v=cache.v.at[:, SERVED_ROW, :, :restore].set(
            cache.v[:, FIRST_ROW, :, :restore]))
    cache = prefill(cache, SERVED_ROW, restore // c, chunks)
    served = np.arange(slots) == SERVED_ROW
    cache = cache._replace(length=jnp.asarray(
        np.where(served, n, np.where(np.arange(slots) == FIRST_ROW,
                                     restore, 0)), jnp.int32))
    for tok in np.asarray(ids[n:], np.int32):
        cache, out, routed, fed = _step(
            params, cache, np.where(served, tok, 0).astype(np.int32), served,
            **run)
        logits.append(out[SERVED_ROW][None])
        picks.append(routed[:, SERVED_ROW][:, None])
        attn_in.append(fed[:, SERVED_ROW][:, None])
    picks = jnp.concatenate(picks, axis=1)                       # [Le, T, k]
    chosen = jnp.any(jax.nn.one_hot(picks, cfg.num_experts, dtype=bool),
                     axis=2)                                     # [Le, T, E]
    lo, count = cfg.experts_held or (0, cfg.num_experts)
    mamba = [lp["mamba"] for lp in params["layers"] if "mamba" in lp]
    attn = [lp["attn"] for lp in params["layers"] if "attn" in lp]
    scales = jnp.stack([
        1.0 / (jnp.exp(mp["a_log"]) * jax.nn.softplus(mp["dt_bias"]))
        for mp in mamba])
    return (jnp.concatenate([x for x in logits if x is not None])[-rows:],
            cache.k[:, SERVED_ROW, :, :total].astype(jnp.float32),
            cache.v[:, SERVED_ROW, :, :total].astype(jnp.float32),
            cache.ssm[:, SERVED_ROW],
            cache.conv[:, SERVED_ROW].astype(jnp.float32),
            chosen, chosen[..., lo:lo + count], scales,
            jnp.concatenate(attn_in, axis=1).astype(jnp.float32),
            tuple(jnp.stack([ap[name].astype(jnp.float32) for ap in attn])
                  for name in ("wk", "wv")),
            _changed((cache.ssm, cache.conv), were, ~served))


def check_sizes(config: dict, cfg) -> None:
    """The program's preset must have the file's sizes, blocks, share and
    routing."""
    got = (cfg.vocab_size, cfg.hidden_size, cfg.num_layers,
           cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
           cfg.ssm_state_size, cfg.conv_kernel, cfg.num_heads,
           cfg.num_kv_heads, cfg.head_dim, cfg.moe_intermediate_size,
           cfg.shared_intermediate_size, cfg.num_experts_held, cfg.pattern,
           cfg.num_experts)
    rest = (cfg.experts_held, cfg.num_experts_per_tok, cfg.route_norm,
            cfg.route_scale, cfg.rms_norm_eps)
    held = config["experts_held"]
    stated = ((int(held["first"]), int(config["n_routed_experts"])),
              int(config["num_experts_per_tok"]),
              bool(config["norm_topk_prob"]),
              float(config["routed_scaling_factor"]),
              float(config["layer_norm_epsilon"]))
    want = weights_lib.sizes_of(config)[:-1]
    if (got != want or rest != stated
            or config["mlp_hidden_act"] != "relu2"
            or int(config["n_group"]) != 1 or int(config["topk_group"]) != 1):
        raise ValueError(
            f"registry preset has {got} and {rest}, the configuration file "
            f"{want} and {stated}")


def _share(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def state_distance(got, want, blocks) -> float:
    """Root mean square over `blocks` (indices into the Mamba blocks) of
    two shares a block: the slow heads' `ssm` and the `conv` window, each
    as a share of the reference's (module docstring). `got` and `want` are
    the sides' tuples; the reference's time scales choose the heads."""
    shares = []
    for b in blocks:
        scale = np.asarray(want[7][b])
        slow = scale >= min(SLOW_TOKENS, scale.max())
        shares += [_share(np.asarray(got[3][b])[slow],
                          np.asarray(want[3][b])[slow]),
                   _share(np.asarray(got[4][b]), np.asarray(want[4][b]))]
    return float(np.sqrt(np.mean(np.square(shares))))


def own_input_kv_distance(got, want) -> float:
    """`got`'s keys and values against the float32 products of `got`'s own
    input to the attention blocks with `want`'s projections, at every
    position (module docstring)."""
    fed = np.asarray(got[8], np.float32)                        # [La, T, D]
    heads, dh = np.asarray(got[1]).shape[1], np.asarray(got[1]).shape[3]

    def project(w):
        out = np.einsum("ltd,lde->lte", fed, np.asarray(w, np.float32))
        return out.reshape(*out.shape[:2], heads, dh).transpose(0, 2, 1, 3)

    return float(check.kv_distance(got[1], got[2], project(want[9][0]),
                                   project(want[9][1])))


def readings(got, want) -> dict:
    """The eight numbers compared, for one sequence: `got` and `want` are
    (logits [P, V], keys, values, ssm, conv, routing [Le, T, E], held
    routing, time scales, the attention blocks' input, their key and value
    projections, the idle rows' share of changed state) of the side judged
    and of the reference."""
    alike = np.all(np.asarray(got[6]) == np.asarray(want[6]), axis=(0, 2))
    # ... at the position and at every one the convolution's window holds.
    window = np.asarray(want[4]).shape[1]
    clean = alike.copy()
    for back in range(1, window + 1):
        clean[back:] &= alike[:-back]
    rows = got[0].shape[0]
    at_rows = np.flatnonzero(clean[-rows:])
    at = np.flatnonzero(clean)
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
        "recurrent_state_distance": state_distance(
            got, want, range(np.asarray(want[3]).shape[0])),
        "first_layer_recurrent_state_distance": state_distance(
            got, want, [0]),
        "keys_and_values_distance": float(kv),
        "own_input_keys_and_values_distance": own_input_kv_distance(
            got, want),
        "routing_disagreement": routing_disagreement(got[5], want[5]),
        "idle_rows_state_change": float(got[10]),
    }
