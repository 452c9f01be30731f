"""The lfm2_moe family's side of the comparison that decides `correct`: the
program's model step, called as the engine's programs call it.

`program` takes one sequence through `family.forward` the way ONE ADMISSION
of the paged engine goes (`engine/paged.py`), on a cache of `check.slots`
rows of which one is live at a time (`families/nemotron_h/compare.py`'s
way):

- the first `restore_at` prompt tokens are prefilled into row `FIRST_ROW`
  from zero windows, in chunks of `prefill_chunk_tokens` through
  `rows=[row]`, each chunk starting from the windows the one before left in
  the row (`_admission_chunk`'s call, the conv operators' CHUNK form);
- the windows that prefill left and the row's keys and values up to there
  are copied into row `SERVED_ROW`, over a previous tenant's windows: what
  a prefix hit's snapshot and block splice leave there, copied BY HAND
  (`.at[].set`), not through the engine's `_export_state_program` and
  `_restore_state_program` (`tests/test_lfm2.py` holds those to this, a
  snapshot restored over a previous tenant with `ssm` None);
- the rest of the prompt is prefilled into that row, the last chunk
  right-padded (the pad tail not live: it must move no window, and routes
  nowhere);
- then one token at a time over ALL the rows at per-row offsets, the served
  row alone live, teacher-forced with the sequence's own next token, through
  the STEP form (on the TPU the kernel `shortconv_step`) and the grouped
  products at the decode pass's own size, as `_decode_chunk`'s call.

Every other row holds windows that are not zero from the start, and no
phase has it live. The reference has the convolution token by token from
zeros, so the comparison holds both forms, the carry from chunk to chunk,
the snapshot and the lanes that are not live to it.

Eleven numbers are compared per sequence (`readings`), and what they have
to live with is that THIS STACK IS CHAOTIC IN BFLOAT16: twelve routed layers
in thirteen, each a hard top-4 of 32 near-tied scores on seeded weights. The
served path stands 1.0% from float32 after the cut's first layer (one conv
operator and the dense SwiGLU: two gated products of rounded factors), which
moves a token's picks in the first routed layer at 3 to 4 positions in a
hundred, and a token whose picks moved is another token to every layer
after: by the twelfth routed layer 56 to 60 positions in a hundred have a
pick that differs, 8.6 to 9.5 in a hundred have none in any layer, and the
logits over ALL positions stand 25 to 31% from the reference's (my chip
runs, PR 57, `scratch_tools/diag57.py`: 8-bit weights 42 to 46%, fp8
activations 58 to 63%). So:

- `routing_disagreement` is the share of picks on which the two sides
  differ (`afmoe`'s number), over all layers: the number that says how far
  apart the two sides' tokens are;
- the logits' distances (`benchmarks/check.py`'s, at the last
  `check.logit_positions` = 1,024 positions, of which some 90 remain) and
  `keys_and_values_distance` (the attention layers' cache at every
  position) are taken over the positions whose picks are the same on both
  sides in EVERY routed layer. The `conv_L_cache - 1` positions before a
  position, which its conv layers also see, are NOT asked to agree: that
  would leave one position in a thousand;
- two numbers are taken where nothing has been routed yet, so that no
  position is left out and the served precision shows without the other
  expert of a token: `first_layer_worst_position_distance`, what the first
  attention layer's projections were given (one norm on from the output of
  the cut's first layer), as the largest share of the reference's at any
  one position: it is the number that sees a window lost at the hit (with
  K = 3 such a fault reaches the two positions after the hit and no other,
  their experts change with it, and every number taken over the positions
  routed alike leaves them out); `conv_window_distance`, the FIRST conv
  layer's window after the last token as a share of the reference's (the
  decode step's shifted window, one norm and one projection from the
  embedding: the window plane's own precision, and a step that shifted it
  wrongly; the deeper layers' windows carry a token's other experts,
  1.5 to 45% by depth, and are held through the decoded positions'
  logits);
- `own_input_experts_distance` holds EVERY routed layer's grouped
  products to the side's OWN input and picks, so that depth and the other
  expert of a token cost it nothing: what a layer's experts gave at the
  positions from `restore_at` on (the question's chunk, 160 rows of the
  grouped product, and the decoded tokens, 288 rows of which the served
  row's four are read) against what the same stacks give in float32, one
  expert at a time, for what the layer was given and the picks it made,
  the worst of the twelve layers. The reference's side is its own experts
  against themselves, 0, and a control's its lowered experts against the
  sound ones;
- three numbers hold the attention layers' cache to the side's OWN input
  in the same way: a layer's keys and values as the cache holds them
  against what the reference's projections (for the keys its per-head norm
  and rotation after) make in float32 of what THAT SIDE's projections were
  given, at every position: the projections, the per-head norm before the
  rotation, the rotation by position and the cache's own rounding, and
  nothing upstream of them. `first_layer_own_input_keys_distance` and
  `first_layer_own_input_values_distance` are the first attention layer's
  (published 2): where keys and values kept in 8 bits show (values 0.59%
  against the served 0.23%; against the reference's own cache a side
  carries a layer of activations, 1.0%, which is more than 8 bits cost).
  `own_input_keys_and_values_distance` is the worst of the three layers
  (2, 6 and 10), keys and values together: the same rounding at every
  depth (values 0.234% in each of the three), so 8 bits, or a fault, in a
  deep layer's cache shows as it does in the first. That holds because
  `forward(aux=True)` hands out what the products consumed through an
  `optimization_barrier`: without it the TPU's compiler made the handed
  copy in a fusion of its own, and these numbers read 0.23, 0.44 and 0.60%
  by depth, past what 8 bits cost (PERF.md section 6, PR 57).

`idle_rows_state_change` is the share of the values in the window planes of
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
from benchmarks.families.lfm2_moe import reference
from benchmarks.families.lfm2_moe import weights as weights_lib

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
            aux["routing"][:, 0], aux["attn_in"][:, 0],
            jnp.stack([aux["moe_in"][:, 0], aux["moe_out"][:, 0]]))


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
            logits[:, 0], aux["routing"][:, :, 0], aux["attn_in"][:, :, 0],
            jnp.stack([aux["moe_in"][:, :, 0], aux["moe_out"][:, :, 0]]))


@functools.partial(jax.jit, static_argnames=("norm", "scale", "eps"))
def _sound_experts(h, picks, mp, *, norm, scale, eps):
    """What a routed layer's experts give in float32 at `highest`, one by
    one, for the input h [T, D] and the picks [T, k] the program made,
    from the stacks the program holds (`mp`, the layer's "moe" subtree):
    the chosen experts' scores over their sum for weights."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        h = h.astype(f32)
        s = jax.nn.sigmoid(h @ mp["wr"].astype(f32))
        w = s * jnp.sum(jax.nn.one_hot(picks, s.shape[-1], dtype=f32), axis=1)
        if norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)

        def one(y, e):
            wg, wu, wd = (mp[n][e].astype(f32) for n in ("wg", "wu", "wd"))
            out = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
            return y + out * (scale * w[:, e, None]), None

        return jax.lax.scan(one, jnp.zeros_like(h),
                            jnp.arange(s.shape[-1]))[0]


def _tenants(cache, seed: int):
    """The cache with every row's windows holding a previous tenant's
    values (nothing is zero, nothing is alike from row to row), but
    `FIRST_ROW`'s: a staged slot starts from zeros."""
    fresh = jnp.arange(cache.conv.shape[1]) == FIRST_ROW
    conv = (1.0 + jax.random.uniform(jax.random.key(seed), cache.conv.shape,
                                     jnp.float32)).astype(cache.conv.dtype)
    return cache._replace(
        conv=jnp.where(fresh[None, :, None, None], 0.0, conv))


def program(family, cfg, params, ids, shape: dict):
    """The program's (logits [P, V], keys [La, Hkv, T, Dh], values, conv
    windows [Lc, K-1, D], routing [Le, T, E] bool, the attention layers'
    input [La, T, D], None where the reference has its projections, what
    the routed layers' experts gave from `restore_at` on beside what they
    give in float32 for the same input and picks [2, Le, T', D], the idle
    rows' share of changed windows) for one sequence at the configuration's
    `check` shape."""
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
    if cache.ssm is not None:
        raise ValueError("lfm2's whole state is its windows: the cache "
                         "declares an `ssm` plane")
    cache = cache._replace(length=jnp.zeros((slots,), jnp.int32))
    were = np.array(cache.conv)
    logits, picks, attn_in, moe = [], [], [], []

    def prefill(cache, row, first, last):
        for i in range(first, last):
            cache, out, routed, fed, ffn = _chunk(
                params, cache, prompt[i * c:(i + 1) * c], np.int32(i * c),
                np.int32(n), np.int32(row), **run)
            real = min(c, n - i * c)
            logits.append(out[:real] if (i + 1) * c > n - rows else None)
            picks.append(routed[:, :real])
            attn_in.append(fed[:, :real])
            if i * c >= restore:
                moe.append(ffn[:, :, :real])
        return cache

    cache = prefill(cache, FIRST_ROW, 0, restore // c)
    # A prefix hit: the snapshot and the blocks of the first row, into the
    # served row.
    snap = cache.conv[:, FIRST_ROW]
    were[:, FIRST_ROW] = np.asarray(snap)
    cache = cache._replace(
        conv=cache.conv.at[:, SERVED_ROW].set(snap),
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
        cache, out, routed, fed, ffn = _step(
            params, cache, np.where(served, tok, 0).astype(np.int32), served,
            **run)
        moe.append(ffn[:, :, SERVED_ROW][:, :, None])
        logits.append(out[SERVED_ROW][None])
        picks.append(routed[:, SERVED_ROW][:, None])
        attn_in.append(fed[:, SERVED_ROW][:, None])
    picks = jnp.concatenate(picks, axis=1)                       # [Le, T, k]
    chosen = jnp.any(jax.nn.one_hot(picks, cfg.num_experts, dtype=bool),
                     axis=2)                                     # [Le, T, E]
    differ = np.asarray(cache.conv != were)[:, ~served]
    given, gave = jnp.concatenate(moe, axis=2)                # [Le, T', D]
    sound = jnp.stack([
        _sound_experts(given[i], picks[i, restore:], lp["moe"],
                       norm=cfg.route_norm, scale=cfg.route_scale,
                       eps=cfg.route_eps)
        for i, lp in enumerate(lp for lp in params["layers"] if "moe" in lp)])

    def heads(plane):
        """The served row of a folded plane [La, S, 1, T, Hkv * Dh] in the
        reference's layout, [La, Hkv, T, Dh]."""
        row = plane[:, SERVED_ROW, 0, :total].astype(jnp.float32)
        return row.reshape(*row.shape[:2], cfg.num_kv_heads, -1).transpose(
            0, 2, 1, 3)

    return (jnp.concatenate([x for x in logits if x is not None])[-rows:],
            heads(cache.k), heads(cache.v),
            cache.conv[:, SERVED_ROW].astype(jnp.float32),
            chosen,
            jnp.concatenate(attn_in, axis=1).astype(jnp.float32), None,
            jnp.stack([gave.astype(jnp.float32), sound]),
            int(differ.sum()) / differ.size)


def check_sizes(config: dict, cfg) -> None:
    """The program's preset must have the file's sizes, layers by their
    published index, and routing."""
    got = (cfg.vocab_size, cfg.hidden_size, cfg.num_layers, cfg.num_heads,
           cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
           cfg.moe_intermediate_size, cfg.num_experts, cfg.conv_kernel,
           tuple(cfg.layer_types),
           tuple(range(cfg.layer_offset, cfg.layer_offset + cfg.num_layers)),
           cfg.num_dense_layers)
    rest = (cfg.experts_held, cfg.num_experts_per_tok, cfg.route_norm,
            cfg.route_scale, cfg.rms_norm_eps, cfg.rope_theta,
            sum(cfg.is_dense(i) for i in range(cfg.num_layers)))
    stated = (None, int(config["num_experts_per_tok"]),
              bool(config["norm_topk_prob"]),
              float(config["routed_scaling_factor"]),
              float(config["norm_eps"]), float(config["rope_theta"]),
              int(config["num_dense_layers"]))
    want = weights_lib.sizes_of(config)
    held = config["experts_held"]
    if (got != want or rest != stated or not config["use_expert_bias"]
            or not int(held["count"]) == int(held["of"]) == cfg.num_experts):
        raise ValueError(
            f"registry preset has {got} and {rest}, the configuration file "
            f"{want} and {stated}")


def _share(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _own(fed, made) -> tuple:
    """(keys, values) [La, Hkv, T, Dh] that the reference's projections
    `made` (`reference.forward`'s seventh value) give for the attention
    layers' input `fed` [La, T, D] in float32: keys normalised per head,
    then rotated by position."""
    fed = np.asarray(fed, np.float32)
    norms = np.asarray(made["kn"], np.float32)
    dh = norms.shape[-1]

    def heads(x):
        return x.reshape(*x.shape[:2], -1, dh).transpose(0, 2, 1, 3)

    k = heads(fed @ np.asarray(made["wk"], np.float32))
    v = heads(fed @ np.asarray(made["wv"], np.float32))
    k = reference.rms_norm(k, norms[:, None, None, :], made["eps"])
    return (np.stack([np.asarray(reference.rotate(kl, made["theta"]))
                      for kl in k]), v)


def readings(got, want) -> dict:
    """The eleven numbers compared, for one sequence: `got` and `want` are
    (logits [P, V], keys, values, conv windows, routing [Le, T, E], the
    attention layers' input, the reference's key and value projections,
    the experts' outputs beside the sound experts', the idle rows' share
    of changed windows) of the side judged and of the reference."""
    alike = np.all(np.asarray(got[4]) == np.asarray(want[4]), axis=(0, 2))
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
    first_got, first_want = np.asarray(got[5][0]), np.asarray(want[5][0])
    # The side's own input through the reference's projections.
    own_k, own_v = _own(got[5], want[6])
    return {
        "logits_distance": float(whole),
        "logits_worst_position_distance": float(row),
        "keys_and_values_distance": float(kv),
        "first_layer_own_input_keys_distance": _share(
            np.asarray(got[1][0], np.float32), own_k[0]),
        "first_layer_own_input_values_distance": _share(
            np.asarray(got[2][0], np.float32), own_v[0]),
        "own_input_keys_and_values_distance": max(
            float(check.kv_distance(k, v, ok, ov))
            for k, v, ok, ov in zip(got[1], got[2], own_k, own_v)),
        "own_input_experts_distance": max(
            _share(np.asarray(y), np.asarray(o)) for y, o in zip(*got[7])),
        "conv_window_distance": _share(np.asarray(got[3][0]),
                                       np.asarray(want[3][0])),
        "first_layer_worst_position_distance": float(np.max(
            np.linalg.norm(first_got - first_want, axis=-1)
            / np.linalg.norm(first_want, axis=-1))),
        "routing_disagreement": routing_disagreement(got[4], want[4]),
        "idle_rows_state_change": float(got[8]),
    }
