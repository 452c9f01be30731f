"""Bytes and operations a decode step of an afmoe model needs, from the
configuration's shapes. JAX-free: the load-generating parent reads it.

Counted per decode step of the served model (bfloat16 weights, cache and
activations; no quantisation):

- weights outside the routed experts, once a step whatever the batch: every
  layer's five attention matrices and six norm vectors, the dense layers'
  SwiGLU, every expert layer's router, bias and shared expert, the final
  norm and the head (the embedding's rows of the step's tokens are not
  counted: 16 rows);
- of the routed experts, those that were reached: the growth of the
  program's counter `moe_experts_reached` over the traced span (summed on
  the device over expert layers and forward passes, the in-scan prefill's
  too) where the program counts it, else the number expected from the live
  lanes, E (1 - (1 - 1/E)^(k x lanes)) a layer and step;
- keys and values of the tokens live in the step, never the cache's padded
  width: a full layer reads a slot's whole context, a sliding layer at most
  `sliding_window` of it;
- operations, per active slot: two per weight of the attention matrices,
  the dense and shared SwiGLUs, the router, k experts and the head, and
  four per live key, head and head_dim for the attention dots.

The steps are the program's own counter of scan iterations over the span
(`engine_scan_iterations`, counted when the host reaps them). Intermediates
(logits, the sampling sort, the sort of the picks) are not counted: the
least time is a floor, and the share it gives errs low.
"""

from __future__ import annotations

STEPS_COUNTER = "engine_scan_iterations"
REACHED_COUNTER = "moe_experts_reached"
BYTES = 2  # bfloat16


def _s(config: dict) -> dict:
    keys = ("hidden_size", "num_hidden_layers", "num_dense_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok", "num_shared_experts", "vocab_size",
            "sliding_window")
    s = {k: int(config[k]) for k in keys}
    s["sliding_layers"] = sum(
        t == "sliding_attention" for t in config["layer_types"])
    s["expert_layers"] = s["num_hidden_layers"] - s["num_dense_layers"]
    return s


def trunk_params(config: dict) -> int:
    """Parameters a step streams whatever its batch: everything but the
    routed experts and the embedding."""
    s = _s(config)
    d, dh = s["hidden_size"], s["head_dim"]
    attention = (3 * d * s["num_attention_heads"] * dh
                 + 2 * d * s["num_key_value_heads"] * dh + 4 * d + 2 * dh)
    dense = 3 * d * s["intermediate_size"]
    routed_rest = (d * s["num_experts"] + s["num_experts"]
                   + 3 * d * s["moe_intermediate_size"]
                   * s["num_shared_experts"])
    return (s["num_hidden_layers"] * attention
            + s["num_dense_layers"] * dense
            + s["expert_layers"] * routed_rest + d + s["vocab_size"] * d)


def expert_params(config: dict) -> int:
    """Parameters of ONE routed expert of one layer."""
    s = _s(config)
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def expected_reached(config: dict, lanes: float) -> float:
    """Experts of one layer that `lanes` live tokens are expected to reach,
    were the routing uniform."""
    s = _s(config)
    e = s["num_experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** (s["num_experts_per_tok"] * lanes))


def kv_bytes_per_slot(config: dict, context: float) -> float:
    """Bytes of K and V a slot with `context` live tokens reads a step."""
    s = _s(config)
    per_layer = 2 * s["num_key_value_heads"] * s["head_dim"] * BYTES
    full = s["num_hidden_layers"] - s["sliding_layers"]
    return per_layer * (full * context + s["sliding_layers"]
                        * min(context, float(s["sliding_window"])))


def slot_ops(config: dict, context: float) -> float:
    """Operations of one slot's token at `context` live tokens."""
    s = _s(config)
    weights = (trunk_params(config) + s["expert_layers"]
               * s["num_experts_per_tok"] * expert_params(config))
    keys = kv_bytes_per_slot(config, context) / (
        2 * s["num_key_value_heads"] * s["head_dim"] * BYTES)
    return (2.0 * weights
            + 4.0 * s["num_attention_heads"] * s["head_dim"] * keys)


def experts_reached(config: dict, trace: dict, slot_steps: float):
    """(experts read over the span, summed over layers and passes; where
    the number comes from)."""
    counters = trace.get("span_counters") or {}
    if counters.get(REACHED_COUNTER):
        return (float(counters[REACHED_COUNTER]),
                f"growth of the counter {REACHED_COUNTER} over the span")
    steps = counters.get(STEPS_COUNTER)
    if not steps:
        return None, None
    return (steps * _s(config)["expert_layers"]
            * expected_reached(config, slot_steps / steps),
            "expected from the live lanes a step, uniform routing")


def experts_cost(config: dict, trace: dict, slot_steps: float,
                 mean_context: float):
    """Bytes and operations of the grouped expert products alone over the
    span: the experts reached, and two operations per weight of the k
    experts of every live token (a floor: the prefill's picks are in the
    counter's bytes, not in the operations)."""
    reached, how = experts_reached(config, trace, slot_steps)
    if not reached:
        return None
    s = _s(config)
    return {
        "bytes": reached * expert_params(config) * BYTES,
        "ops": (2.0 * slot_steps * s["expert_layers"]
                * s["num_experts_per_tok"] * expert_params(config)),
        "ops_peak": "bf16_flops_per_s",
        "experts_reached": reached,
        "experts_reached_are": how,
        "counted_by": "benchmarks/families/afmoe/roofline.py experts_cost",
    }


def cost(config: dict, trace: dict, slot_steps: float, mean_context: float):
    """Bytes and operations of the span's decode steps, which advanced
    `slot_steps` slot-tokens at a mean context of `mean_context` tokens;
    nothing where the counter of steps did not grow."""
    steps = (trace.get("span_counters") or {}).get(STEPS_COUNTER)
    if not steps:
        return None
    experts = experts_cost(config, trace, slot_steps, mean_context)
    if not experts:
        return None
    loops = trace.get("loops") or []
    by_loop = max(n for _, n in loops) if loops else None
    return {
        "bytes": (steps * trunk_params(config) * BYTES + experts["bytes"]
                  + slot_steps * kv_bytes_per_slot(config, mean_context)),
        "ops": slot_ops(config, mean_context) * slot_steps,
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "steps_are": f"growth of the counter {STEPS_COUNTER} over the span",
        "steps_by_loop": by_loop,
        "experts_reached": experts["experts_reached"],
        "experts_reached_are": experts["experts_reached_are"],
        "experts_reached_per_layer_and_step": (
            experts["experts_reached"]
            / (steps * _s(config)["expert_layers"])),
        "counted_by": "benchmarks/families/afmoe/roofline.py",
    }
