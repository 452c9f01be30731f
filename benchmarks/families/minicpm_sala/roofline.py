"""Bytes and operations a decode step of a minicpm_sala model needs, from
the configuration's shapes. JAX-free: the load-generating parent reads it.

Counted per decode step of the served model (bfloat16 weights, keys, values,
pooled keys and activations, a float32 Lightning state; no quantisation), of
the pipeline stage the configuration file keeps:

- weights, once a step whatever the batch: every layer's mixer (q, k, v,
  gate and o projections, the head norms), SwiGLU and two norms, the final
  norm and the head (the embedding's rows of the step's tokens are not
  counted);
- the Lightning state of the LIVE lanes, read once and written once a
  Lightning layer and step (heads x key dim x value dim, float32). Never
  what a kernel chose to read: a lane that is not live costs the floor
  nothing;
- in the sparse layers, what the LIVE lanes ATTENDED and not what their rows
  hold: the keys and values of the chosen blocks at or behind the query
  (every key below `dense_len`), from the program's own counter
  `engine_sparse_keys_attended` (summed over sparse layers on the device),
  and the pooled keys a selecting lane scores, one a `kernel_stride`
  positions of its context;
- operations, per active slot: two per weight of the projections, the SwiGLU
  and the head; four per attended key, query head and head dimension (the
  scores and the weighted sum) and two per pooled key; 6 per state element
  (decay, the rank-one product and its sum, the product with q and its sum).

`sparse_decode_cost`: the sparse layers' decode attention alone, both its
kernels' work (the floor of `sparse_decode_roofline`: the chosen blocks and
the pooled keys, read once). `lightning_step_cost`: the decode step's state
update alone (the floor of `lightning_step_roofline`). What the kernels read
besides (every slot's state, live or not; a dense lane's padded slots) is
handed on as `bytes_read` where it is known, and a kernel that skips it
cannot pass 100%.

The steps are the program's own counter of scan iterations over the span
(`engine_scan_iterations`). Intermediates (logits, the sampling sort, the
block scores and their top-k) are not counted: the least time is a floor,
and the share it gives errs low.
"""

from __future__ import annotations

STEPS_COUNTER = "engine_scan_iterations"
ATTENDED_COUNTER = "engine_sparse_keys_attended"
CONTEXT_COUNTER = "engine_sparse_keys_in_context"
SPARSE_STEPS_COUNTER = "engine_sparse_lane_steps"
BYTES = 2        # bfloat16
STATE_BYTES = 4  # float32
STATE_OPS = 6    # per state element and step
SPARSE = "minicpm4"
HERE = "benchmarks/families/minicpm_sala/roofline.py"


def _s(config: dict) -> dict:
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "lightning_nh", "lightning_head_dim", "vocab_size")
    s = {k: int(config[k]) for k in keys}
    s["sparse_layers"] = sum(m == SPARSE for m in config["mixer_types"])
    s["lightning_layers"] = s["num_hidden_layers"] - s["sparse_layers"]
    s.update({k: int(v) for k, v in config["sparse_config"].items()})
    return s


def sparse_mixer_params(config: dict) -> int:
    """One `minicpm4` mixer: q, gate and o over all heads, k and v over the
    key heads, the two head norms."""
    s = _s(config)
    d, dh = s["hidden_size"], s["head_dim"]
    return (3 * d * s["num_attention_heads"] * dh
            + 2 * d * s["num_key_value_heads"] * dh + 2 * dh)


def lightning_mixer_params(config: dict) -> int:
    """One Lightning mixer: q, k, v, gate and o, the three head norms."""
    s = _s(config)
    return (5 * s["hidden_size"] * s["lightning_nh"]
            * s["lightning_head_dim"] + 3 * s["lightning_head_dim"])


def mlp_params(config: dict) -> int:
    s = _s(config)
    return 3 * s["hidden_size"] * s["intermediate_size"]


def trunk_params(config: dict) -> int:
    """Parameters a step streams whatever its batch: everything but the
    embedding."""
    s = _s(config)
    d = s["hidden_size"]
    return (s["sparse_layers"] * sparse_mixer_params(config)
            + s["lightning_layers"] * lightning_mixer_params(config)
            + s["num_hidden_layers"] * (mlp_params(config) + 2 * d)
            + d + s["vocab_size"] * d)


def parameters(config: dict) -> int:
    """Every parameter the chip holds (`hbm_bytes_worked_out`)."""
    s = _s(config)
    return trunk_params(config) + s["vocab_size"] * s["hidden_size"]


def kv_bytes_per_key(config: dict) -> int:
    """Bytes of one position's key and value in ONE sparse layer."""
    s = _s(config)
    return 2 * s["num_key_value_heads"] * s["head_dim"] * BYTES


def pooled_bytes_per_entry(config: dict) -> int:
    """Bytes of one pooled key in ONE sparse layer."""
    s = _s(config)
    return s["num_key_value_heads"] * s["head_dim"] * BYTES


def state_bytes_per_slot(config: dict) -> int:
    """Bytes of ONE Lightning layer's state of one slot (float32)."""
    s = _s(config)
    return s["lightning_nh"] * s["lightning_head_dim"] ** 2 * STATE_BYTES


def attended(config: dict, trace: dict, slot_steps: float,
             mean_context: float):
    """(keys attended, pooled keys scored, both summed over sparse layers and
    lane-steps of the span; where the numbers come from). From the program's
    counters where it counts them: a selecting lane-step attends `topk`
    blocks of which its own is half full on average, so what the counter
    holds beyond that is the dense lanes' contexts, and the rest of the
    contexts' keys is the selecting lanes', of which they score one pooled
    key a `kernel_stride`. Else as if every lane stood at the mean context."""
    s = _s(config)
    counters = trace.get("span_counters") or {}
    chosen = s["topk"] * s["block_size"] - (s["block_size"] - 1) / 2.0
    if counters.get(ATTENDED_COUNTER):
        keys = float(counters[ATTENDED_COUNTER])
        selecting = float(counters.get(SPARSE_STEPS_COUNTER, 0))
        dense_context = max(keys - selecting * chosen, 0.0)
        scored = max(float(counters.get(CONTEXT_COUNTER, 0)) - dense_context,
                     0.0) / s["kernel_stride"]
        return keys, scored, (f"growth of the counters {ATTENDED_COUNTER}, "
                              f"{SPARSE_STEPS_COUNTER} and {CONTEXT_COUNTER} "
                              f"over the span")
    lane_layers = slot_steps * s["sparse_layers"]
    if mean_context < s["dense_len"]:
        return lane_layers * mean_context, 0.0, "every lane at the mean context"
    return (lane_layers * chosen,
            lane_layers * mean_context / s["kernel_stride"],
            "every lane at the mean context")


def attention_ops(config: dict, keys: float, scored: float) -> float:
    """Operations of the sparse layers' attention over `keys` attended keys
    and `scored` pooled keys."""
    s = _s(config)
    return 2.0 * s["num_attention_heads"] * s["head_dim"] * (
        2.0 * keys + scored)


def sparse_decode_cost(config: dict, trace: dict, slot_steps: float,
                       mean_context: float):
    """Bytes and operations of the sparse layers' decode attention alone
    over the span (the module's head says what is counted); nothing where
    the counter of steps did not grow."""
    steps = (trace.get("span_counters") or {}).get(STEPS_COUNTER)
    if not steps:
        return None
    keys, scored, how = attended(config, trace, slot_steps, mean_context)
    return {
        "bytes": (keys * kv_bytes_per_key(config)
                  + scored * pooled_bytes_per_entry(config)),
        "ops": attention_ops(config, keys, scored),
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "keys_attended": keys,
        "pooled_keys_scored": scored,
        "keys_are": how,
        "counted_by": HERE + " sparse_decode_cost",
    }


def lightning_step_cost(config: dict, trace: dict, slot_steps: float,
                        mean_context: float):
    """Bytes and operations of the decode steps' state update alone over
    the span; nothing where the counter of steps did not grow."""
    steps = (trace.get("span_counters") or {}).get(STEPS_COUNTER)
    if not steps:
        return None
    s = _s(config)
    per_lane = 2 * s["lightning_layers"] * state_bytes_per_slot(config)
    return {
        "bytes": slot_steps * per_lane,
        "ops": (slot_steps * s["lightning_layers"] * STATE_OPS
                * s["lightning_nh"] * s["lightning_head_dim"] ** 2),
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "bytes_read": float(steps) * int(config["serving"]["slots"])
        * per_lane,
        "counted_by": HERE + " lightning_step_cost",
    }


def cost(config: dict, trace: dict, slot_steps: float, mean_context: float):
    """Bytes and operations of the span's decode steps, which advanced
    `slot_steps` slot-tokens at a mean context of `mean_context` tokens:
    weights once a step, the live lanes' state twice, their chosen blocks
    and pooled keys once; nothing where the counter of steps did not grow."""
    steps = (trace.get("span_counters") or {}).get(STEPS_COUNTER)
    if not steps:
        return None
    s = _s(config)
    sparse = sparse_decode_cost(config, trace, slot_steps, mean_context)
    state = lightning_step_cost(config, trace, slot_steps, mean_context)
    loops = trace.get("loops") or []
    by_loop = max(n for _, n in loops) if loops else None
    return {
        "bytes": (steps * trunk_params(config) * BYTES + sparse["bytes"]
                  + state["bytes"]),
        "ops": (2.0 * trunk_params(config) * slot_steps + sparse["ops"]
                + state["ops"]),
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "steps_are": f"growth of the counter {STEPS_COUNTER} over the span",
        "steps_by_loop": by_loop,
        "keys_attended": sparse["keys_attended"],
        "pooled_keys_scored": sparse["pooled_keys_scored"],
        "keys_are": sparse["keys_are"],
        "counted_by": HERE,
    }
