"""Bytes and operations a decode step of an lfm2_moe model needs, from the
configuration's shapes. JAX-free: the load-generating parent reads it.

Counted per decode step of the served model (bfloat16 weights, keys, values,
windows and activations; no quantisation), of the layers the configuration
keeps:

- weights outside the routed experts, once a step whatever the batch: every
  conv operator's two projections and taps, the attention layers' four
  projections and two per-head norms, the dense layers' SwiGLU, every routed
  layer's router and bias, two norms a layer, the final norm and the head,
  which is the embedding (counted once: the embedding's rows of the step's
  tokens are not);
- of the routed experts, those that were reached: the growth of the
  program's counter `moe_experts_reached` over the traced span where the
  program counts it, else the number expected from the live lanes were the
  routing uniform. An expert is three projections (SwiGLU) at the PUBLISHED
  inner width: the zeros the program pads its stacks with
  (`models/lfm2.py` `pad_experts`) cost the floor nothing;
- the keys and values of the tokens live in the step, in the attention
  layers alone;
- the windows of the LIVE lanes, read once and written once a conv layer
  and step (`conv_L_cache` - 1 inputs of `hidden_size` channels, bfloat16),
  in the whole step's count (`cost`). Never what a kernel chose to read: a
  lane that is not live costs the floor nothing;
- operations, per active slot: two per weight of the projections, the
  router, the picks' experts and the head; the attention's 4 per head, head
  dimension and live key; the convolution's 2 per tap and channel and its
  two gates.

`experts_cost`: the grouped products of the experts alone (the floor of
`moe_experts_roofline` in this family's cells). The conv operator's decode
step, the kernel `shortconv_step`, has NO floor of its own here: the
compiled megastep lays the window plane, the projection's `B | C | z` and
`y`, every operand and result of each of the kernel's ten calls, in the
chip's on-chip memory (`S(1)` in its text for a described v5e), so none of
the 2.1 MB a call moves crosses the memory `peaks.json` has a bandwidth
for, and a share of that bandwidth read 113.5% in a traced span (my chip
run, PR 57). The kernel is held by its time alone
(`shortconv_step_dev_us_per_tok`).

The steps are the program's own counter of scan iterations over the span
(`engine_scan_iterations`). Intermediates (logits, the sampling sort, the
sort of the picks) are not counted: the least time is a floor, and the
share it gives errs low.
"""

from __future__ import annotations

STEPS_COUNTER = "engine_scan_iterations"
REACHED_COUNTER = "moe_experts_reached"
BYTES = 2        # bfloat16
CONV_OPS = 2     # a tap: one product, one sum


def _s(config: dict) -> dict:
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "moe_intermediate_size", "num_experts", "num_experts_per_tok",
            "num_dense_layers", "conv_L_cache", "vocab_size")
    s = {k: int(config[k]) for k in keys}
    types = list(config["layer_types"])
    s["conv_layers"] = types.count("conv")
    s["attention_layers"] = types.count("full_attention")
    s["routed_layers"] = s["num_hidden_layers"] - s["num_dense_layers"]
    return s


def conv_params(config: dict) -> int:
    """One conv operator: in and out projections and the taps."""
    s = _s(config)
    d = s["hidden_size"]
    return d * 3 * d + d * d + s["conv_L_cache"] * d


def attention_params(config: dict) -> int:
    """One attention operator: four projections and two per-head norms."""
    s = _s(config)
    d, dh = s["hidden_size"], s["head_dim"]
    return (d * dh * (s["num_attention_heads"]
                      + 2 * s["num_key_value_heads"])
            + s["num_attention_heads"] * dh * d + 2 * dh)


def dense_params(config: dict) -> int:
    s = _s(config)
    return 3 * s["hidden_size"] * s["intermediate_size"]


def expert_params(config: dict) -> int:
    """Parameters of ONE routed expert of one layer: three projections."""
    s = _s(config)
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def router_params(config: dict) -> int:
    """A routed layer outside its experts: the router and its bias."""
    s = _s(config)
    return s["hidden_size"] * s["num_experts"] + s["num_experts"]


def trunk_params(config: dict) -> int:
    """Parameters a step streams whatever its batch: everything but the
    routed experts, the tied embedding counted once, as the head."""
    s = _s(config)
    d = s["hidden_size"]
    return (s["conv_layers"] * conv_params(config)
            + s["attention_layers"] * attention_params(config)
            + s["num_dense_layers"] * dense_params(config)
            + s["routed_layers"] * router_params(config)
            + s["num_hidden_layers"] * 2 * d + d + s["vocab_size"] * d)


def parameters(config: dict) -> int:
    """Every parameter the chip holds (`hbm_bytes_worked_out`)."""
    s = _s(config)
    return (trunk_params(config) + s["routed_layers"] * s["num_experts"]
            * expert_params(config))


def expected_reached(config: dict, lanes: float) -> float:
    """Experts of one layer that `lanes` live tokens are expected to
    reach, were the routing uniform."""
    s = _s(config)
    return s["num_experts"] * (1.0 - (1.0 - 1.0 / s["num_experts"]) ** (
        s["num_experts_per_tok"] * lanes))


def kv_bytes_per_token(config: dict) -> int:
    """Bytes of keys and values a token holds, over the attention layers."""
    s = _s(config)
    return (s["attention_layers"] * 2 * s["num_key_value_heads"]
            * s["head_dim"] * BYTES)


def window_bytes_per_slot(config: dict) -> int:
    """Bytes of ONE conv layer's window of one slot (bfloat16)."""
    s = _s(config)
    return (s["conv_L_cache"] - 1) * s["hidden_size"] * BYTES


def slot_ops(config: dict, context: float) -> float:
    """Operations of one slot's token at `context` live tokens."""
    s = _s(config)
    weights = (trunk_params(config) + s["routed_layers"]
               * s["num_experts_per_tok"] * expert_params(config))
    return (2.0 * weights
            + s["attention_layers"] * 4.0 * s["num_attention_heads"]
            * s["head_dim"] * context
            + s["conv_layers"] * s["hidden_size"]
            * (CONV_OPS * s["conv_L_cache"] + 2))


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
    return (steps * _s(config)["routed_layers"]
            * expected_reached(config, slot_steps / steps),
            "expected from the live lanes a step, uniform routing")


def experts_cost(config: dict, trace: dict, slot_steps: float,
                 mean_context: float):
    """Bytes and operations of the experts' grouped products alone over the
    span: the experts reached (three projections each, at the published
    width), and two operations per weight of the live tokens' picks (a
    floor: the prefill's picks are in the counter's bytes, not in the
    operations)."""
    reached, how = experts_reached(config, trace, slot_steps)
    if not reached:
        return None
    s = _s(config)
    return {
        "bytes": reached * expert_params(config) * BYTES,
        "ops": (2.0 * slot_steps * s["routed_layers"]
                * s["num_experts_per_tok"] * expert_params(config)),
        "ops_peak": "bf16_flops_per_s",
        "experts_reached": reached,
        "experts_reached_are": how,
        "counted_by": "benchmarks/families/lfm2_moe/roofline.py "
                      "experts_cost",
    }


def cost(config: dict, trace: dict, slot_steps: float, mean_context: float):
    """Bytes and operations of the span's decode steps, which advanced
    `slot_steps` slot-tokens at a mean context of `mean_context` tokens:
    the trunk once a step, the experts reached, the live tokens' keys and
    values, the live lanes' windows twice; nothing where the counter of
    steps did not grow."""
    steps = (trace.get("span_counters") or {}).get(STEPS_COUNTER)
    if not steps:
        return None
    experts = experts_cost(config, trace, slot_steps, mean_context)
    if not experts:
        return None
    s = _s(config)
    loops = trace.get("loops") or []
    by_loop = max(n for _, n in loops) if loops else None
    return {
        "bytes": (steps * trunk_params(config) * BYTES + experts["bytes"]
                  + slot_steps * (mean_context * kv_bytes_per_token(config)
                                  + 2 * s["conv_layers"]
                                  * window_bytes_per_slot(config))),
        "ops": slot_ops(config, mean_context) * slot_steps,
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "steps_are": f"growth of the counter {STEPS_COUNTER} over the span",
        "steps_by_loop": by_loop,
        "experts_reached": experts["experts_reached"],
        "experts_reached_are": experts["experts_reached_are"],
        "experts_reached_per_layer_and_step": (
            experts["experts_reached"] / (steps * s["routed_layers"])),
        "counted_by": "benchmarks/families/lfm2_moe/roofline.py",
    }
