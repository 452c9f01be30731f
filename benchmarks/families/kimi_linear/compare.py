"""The kimi_linear family's side of the comparison that decides `correct`:
the program's model step, called as the engine's programs call it.

`program` takes one sequence through `family.forward` the way ONE ADMISSION
of the paged engine goes (`engine/paged.py`; `families/nemotron_h/
compare.py` is the pattern), on a cache of `check.slots` rows of which one
is live at a time:

- the first `restore_at` prompt tokens are prefilled into row `FIRST_ROW`
  from zeros, in chunks of `prefill_chunk_tokens` through `rows=[row]`,
  each chunk starting from the state and the convolution's window the one
  before left in the row (`_admission_chunk`'s call, the KDA layers' CHUNK
  form; the MLA layers' absorbed products over the row's latent);
- the state that prefill left is exported as a snapshot and the row's
  latent as blocks, and both are put into row `SERVED_ROW`, over a previous
  tenant's state (`_export_state_program`, `_restore_state_program` and the
  block splice of a prefix hit);
- the rest of the prompt is prefilled into that row, the last chunk
  right-padded (the pad tail not live: it must move neither the state nor
  the window, and routes nowhere);
- then one token at a time over ALL the rows at per-row offsets, the served
  row alone live, teacher-forced with the sequence's own next token, through
  the STEP form (on the TPU the kernels `kda_step` and `mla_decode`), as
  `_decode_chunk`'s call.

Every other row holds a state that is not zero from the start, and no phase
has it live. The reference has the recurrence, token by token from zeros,
and MLA in its expanded form, so the comparison holds both forms of both
mixers, the carry from chunk to chunk, the snapshot and the lanes that are
not live to it. The family hands out its routing and the MLA layers' input
on request (`aux=True`), so nothing is probed.

Eight numbers are compared per sequence (`readings`).
`routing_disagreement` is the share of picks on which the two sides differ,
over ALL the router's experts (`afmoe`'s number). A token that one side
sends to a HELD expert and the other does not comes out another token, and
it does not stay at its own position: the convolution of every later KDA
layer reaches `short_conv_kernel_size - 1` positions back, and the state
carries it on. So the logits' distances (`benchmarks/check.py`'s, at the
last `check.logit_positions` positions) and `latent_cache_distance` (the MLA
layers' cache at every position) are taken over the positions whose picks
among the experts held are the same on both sides in every layer AT THAT
POSITION AND AT THE WINDOW'S POSITIONS BEFORE IT.

`own_input_latent_cache_distance` is the cache's own precision: the latent
a side holds against the float32 projection, with the REFERENCE's weights,
of that side's OWN input to the MLA layers, at every position. The first MLA
layer is the fourth, so against the reference's latent a side's cache
carries three layers of bfloat16 activations; against its own input it
carries the projection's roundings alone, and neither a routed pick nor a
state reaches this number: it is the one that tells an 8-bit latent from
the served path.

A state sums over every position, so no position can be left out of it. As
`nemotron_h`'s, it is read through the heads that hold the long context,
those whose time scale `1 / (A dt)` (at the median `dt` of a head's
channels) is `SLOW_TOKENS` or more (at least a layer's slowest head).
`recurrent_state_distance` is the root mean square, over the KDA layers, of
two shares a layer: the slow heads' `ssm` after the last token as a share of
the reference's, and the `conv` window's;
`first_layer_recurrent_state_distance` the same of the first layer alone:
one norm and the projections from the embedding, no routing before it, it
tells the state's own precision from the depth's.

`idle_rows_state_change` is the share of the values in the state planes of
the rows that were not live (the previous tenants', and the first row's
once its snapshot was taken) that are not bit-equal at the end to what they
were: its limit is 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check
from benchmarks.families.afmoe.compare import routing_disagreement
from benchmarks.families.kimi_linear import weights as weights_lib
# One admission's calls are family-blind (`forward` with `rows`, `live` and
# `aux`; a cache with `ssm` and `conv` planes): the prefill chunk, the
# decode step, the previous tenants' states, the idle rows' share of changed
# values, the two rows and the heads read as slow are nemotron_h's.
from benchmarks.families.nemotron_h.compare import (
    FIRST_ROW,
    SERVED_ROW,
    SLOW_TOKENS,
    _changed,
    _chunk,
    _step,
    _tenants,
)


def program(family, cfg, params, ids, shape: dict):
    """The program's side of `reference.forward`'s tuple for one sequence at
    the configuration's `check` shape; its last entry is the idle rows'
    share of changed state."""
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
    # A prefix hit: the snapshot and the latent blocks of the first row,
    # into the served row.
    snap = (cache.ssm[:, FIRST_ROW], cache.conv[:, FIRST_ROW])
    for plane, was in zip(snap, were):
        was[:, FIRST_ROW] = np.asarray(plane)
    cache = cache._replace(
        ssm=cache.ssm.at[:, SERVED_ROW].set(snap[0]),
        conv=cache.conv.at[:, SERVED_ROW].set(snap[1]),
        k=cache.k.at[:, SERVED_ROW, :, :restore].set(
            cache.k[:, FIRST_ROW, :, :restore]))
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
    trees = [lp["attn"] for lp in params["layers"]]
    kda = [ap for ap in trees if "a_log" in ap]
    mla = [ap for ap in trees if "wkva" in ap]
    kd = cfg.kda_head_dim
    scales = jnp.stack([
        1.0 / (jnp.exp(ap["a_log"]) * jnp.median(
            jax.nn.softplus(ap["dt_bias"]).reshape(-1, kd), axis=-1))
        for ap in kda])
    return (jnp.concatenate([x for x in logits if x is not None])[-rows:],
            cache.k[:, SERVED_ROW, 0, :total].astype(jnp.float32),
            cache.ssm[:, SERVED_ROW],
            cache.conv[:, SERVED_ROW].astype(jnp.float32),
            chosen, chosen[..., lo:lo + count], scales,
            jnp.concatenate(attn_in, axis=1).astype(jnp.float32),
            (jnp.stack([ap["wkva"].astype(jnp.float32) for ap in mla]),
             jnp.stack([ap["kvn"]["scale"].astype(jnp.float32)
                        for ap in mla]), cfg.rms_norm_eps),
            _changed((cache.ssm, cache.conv), were, ~served))


def check_sizes(config: dict, cfg) -> None:
    """The program's preset must have the file's sizes, layers, share and
    routing."""
    got = (cfg.vocab_size, cfg.hidden_size, cfg.num_layers, cfg.num_heads,
           cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
           cfg.v_head_dim, cfg.intermediate_size, cfg.moe_intermediate_size,
           cfg.num_experts_held, cfg.num_dense_layers,
           (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_kernel),
           tuple(cfg.kda_layers), tuple(cfg.full_attn_layers),
           cfg.num_experts)
    rest = (cfg.experts_held, cfg.num_experts_per_tok, cfg.route_norm,
            cfg.route_scale, cfg.rms_norm_eps, cfg.q_lora_rank,
            cfg.mla_use_nope, cfg.rope_scaling, cfg.num_shared_experts)
    held = config["experts_held"]
    stated = ((int(held["first"]), int(config["num_experts"])),
              int(config["num_experts_per_token"]),
              bool(config["moe_renormalize"]),
              float(config["routed_scaling_factor"]),
              float(config["rms_norm_eps"]), config["q_lora_rank"],
              bool(config["mla_use_nope"]), config["rope_scaling"],
              int(config["num_shared_experts"]))
    want = weights_lib.sizes_of(config)
    if (got != want or rest != stated
            or config["moe_router_activation_func"] != "sigmoid"
            or int(config["num_expert_group"]) != 1
            or int(config["topk_group"]) != 1):
        raise ValueError(
            f"registry preset has {got} and {rest}, the configuration file "
            f"{want} and {stated}")


def _share(got, want) -> float:
    return float(np.linalg.norm(np.asarray(got) - np.asarray(want))
                 / np.linalg.norm(np.asarray(want)))


def state_distance(got, want, layers) -> float:
    """Root mean square over `layers` (indices into the KDA layers) of two
    shares a layer: the slow heads' `ssm` and the `conv` window, each as a
    share of the reference's (module docstring). `got` and `want` are the
    sides' tuples; the reference's time scales choose the heads."""
    shares = []
    for b in layers:
        scale = np.asarray(want[6][b])
        slow = scale >= min(SLOW_TOKENS, scale.max())
        shares += [_share(np.asarray(got[2][b])[slow],
                          np.asarray(want[2][b])[slow]),
                   _share(got[3][b], want[3][b])]
    return float(np.sqrt(np.mean(np.square(shares))))


def own_input_latent_distance(got, want) -> float:
    """`got`'s latent against the float32 projection of `got`'s own input
    to the MLA layers with `want`'s `Wkva` and norm, at every position
    (module docstring)."""
    fed = np.asarray(got[7], np.float32)                        # [La, T, D]
    wkva, gain, eps = want[8]
    wkva, gain = np.asarray(wkva, np.float32), np.asarray(gain, np.float32)
    kva = np.einsum("ltd,lde->lte", fed, wkva)
    kr = gain.shape[-1]
    c_kv = kva[..., :kr]
    c_kv = c_kv / np.sqrt(np.mean(np.square(c_kv), axis=-1, keepdims=True)
                          + eps) * gain[:, None]
    return _share(got[1], np.concatenate([c_kv, kva[..., kr:]], axis=-1))


def readings(got, want) -> dict:
    """The eight numbers compared, for one sequence: `got` and `want` are
    `reference.forward`'s tuple of the side judged and of the reference."""
    alike = np.all(np.asarray(got[5]) == np.asarray(want[5]), axis=(0, 2))
    # ... at the position and at every one the convolution's window holds.
    window = np.asarray(want[3]).shape[1]
    clean = alike.copy()
    for back in range(1, window + 1):
        clean[back:] &= alike[:-back]
    rows = got[0].shape[0]
    at_rows = np.flatnonzero(clean[-rows:])
    at = np.flatnonzero(clean)
    # A side that routes no compared position as the reference does has
    # no distance to show: infinite, which is outside every limit.
    whole = row = latent = float("inf")
    if len(at_rows):
        whole, row = check.distances(got[0][at_rows], want[0][at_rows])
    if len(at):
        latent = _share(np.asarray(got[1])[:, at], np.asarray(want[1])[:, at])
    return {
        "logits_distance": float(whole),
        "logits_worst_position_distance": float(row),
        "recurrent_state_distance": state_distance(
            got, want, range(np.asarray(want[2]).shape[0])),
        "first_layer_recurrent_state_distance": state_distance(
            got, want, [0]),
        "latent_cache_distance": float(latent),
        "own_input_latent_cache_distance": own_input_latent_distance(
            got, want),
        "routing_disagreement": routing_disagreement(got[4], want[4]),
        "idle_rows_state_change": float(got[9]),
    }
