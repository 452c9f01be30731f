"""Bytes and operations a decode step of an axk1 model needs, from the
configuration's shapes. JAX-free: the load-generating parent reads it.

Counted per decode step of the served model (bfloat16 weights, latent cache
and activations; no quantisation), of this chip's part of the deployment
(the configuration file: the layers kept, the experts held, the slice of the
vocabulary):

- weights outside the routed experts, once a step whatever the batch: every
  layer's MLA projections (`q_a`, `q_b`, `kv_a`, `kv_b`, `o`) and norms, the
  dense layers' SwiGLU, every expert layer's router (all 192 columns) and
  shared expert, the final norm and the head over the slice (the
  embedding's rows of the step's tokens are not counted);
- of the routed experts HELD, those that were reached: the growth of the
  program's counter `moe_experts_reached` over the traced span (summed on
  the device over expert layers and forward passes, the in-scan prefill's
  too) where the program counts it, else the number expected from the live
  lanes were the routing uniform over all the router's experts;
- the latent cache of the tokens live in the step, 2 x (`kv_lora_rank` +
  `qk_rope_head_dim`) bytes a token and layer, never the padded width
  (`cost` and `mla_decode_cost` alike: padding a program chose to read is
  its own cost, not work the chip was asked for);
- operations, per active slot: two per weight of the projections, the dense
  and shared SwiGLUs, the router, the picks expected on the experts held
  (k x held / experts) and the head, and the absorbed attention's: the two
  halves of `kv_b` once a head (`q_nope` into the latent, the output out of
  it) and, per live key, 2 x (kv_lora_rank + rope) for the scores and
  2 x kv_lora_rank for the output, per head.

`experts_cost`: the grouped products of the experts held alone (the floor of
`moe_experts_roofline` in this family's cells). `mla_decode_cost`: the
decode attention's products over the latent cache alone (the floor of
`mla_decode_roofline`): the BYTES are the live tokens' latent, once a layer
and step, the OPERATIONS the absorbed products' for the lanes live at their
live contexts. What the program's kernel reads instead, every slot's whole
padded row at the cache's width, is handed on beside (`bytes_read`,
`width_read`): the share of the floor falls by that factor, and a kernel
that reads live tokens alone cannot pass 100%.

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
    keys = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts", "vocab_size")
    s = {k: int(config[k]) for k in keys}
    s["experts"] = int(config["published"]["n_routed_experts"])
    s["expert_layers"] = s["num_hidden_layers"] - s["first_k_dense_replace"]
    s["latent"] = s["kv_lora_rank"] + s["qk_rope_head_dim"]
    return s


def attention_params(config: dict) -> int:
    """One layer's MLA projections and its two latent norms."""
    s = _s(config)
    d, h = s["hidden_size"], s["num_attention_heads"]
    return (d * s["q_lora_rank"] + s["q_lora_rank"]
            + s["q_lora_rank"] * h * (s["qk_nope_head_dim"]
                                      + s["qk_rope_head_dim"])
            + d * s["latent"] + s["kv_lora_rank"]
            + s["kv_lora_rank"] * h * (s["qk_nope_head_dim"]
                                       + s["v_head_dim"])
            + h * s["v_head_dim"] * d)


def trunk_params(config: dict) -> int:
    """Parameters a step streams whatever its batch: everything but the
    routed experts and the embedding."""
    s = _s(config)
    d = s["hidden_size"]
    dense = 3 * d * s["intermediate_size"]
    routed_rest = (d * s["experts"]
                   + 3 * d * s["moe_intermediate_size"]
                   * s["n_shared_experts"])
    return (s["num_hidden_layers"] * (attention_params(config) + 2 * d)
            + s["first_k_dense_replace"] * dense
            + s["expert_layers"] * routed_rest + d + s["vocab_size"] * d)


def expert_params(config: dict) -> int:
    """Parameters of ONE routed expert of one layer."""
    s = _s(config)
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def held_picks_per_token(config: dict) -> float:
    """Picks a token is expected to land on the experts held, a layer."""
    s = _s(config)
    return s["num_experts_per_tok"] * s["n_routed_experts"] / s["experts"]


def expected_reached(config: dict, lanes: float) -> float:
    """Held experts of one layer that `lanes` live tokens are expected to
    reach, were the routing uniform over all the router's experts."""
    s = _s(config)
    return s["n_routed_experts"] * (1.0 - (1.0 - 1.0 / s["experts"]) ** (
        s["num_experts_per_tok"] * lanes))


def latent_bytes_per_token(config: dict) -> int:
    """Bytes the latent cache holds a token, over all layers kept."""
    s = _s(config)
    return s["num_hidden_layers"] * s["latent"] * BYTES


def attention_ops_per_lane(config: dict, context: float) -> float:
    """Operations of one lane's absorbed decode attention over `context`
    live keys, one layer: q_nope into the latent and the output out of it
    (the two halves of kv_b, once a head), the scores over latent + rope
    and the output over the latent, per key and head."""
    s = _s(config)
    h, kr = s["num_attention_heads"], s["kv_lora_rank"]
    return 2.0 * h * (kr * (s["qk_nope_head_dim"] + s["v_head_dim"])
                      + context * (s["latent"] + kr))


def slot_ops(config: dict, context: float) -> float:
    """Operations of one slot's token at `context` live tokens."""
    s = _s(config)
    weights = (trunk_params(config) + s["expert_layers"]
               * held_picks_per_token(config) * expert_params(config))
    # kv_b's weights are in the trunk's count and in the absorbed products'
    # both; they are counted once, with the products.
    kv_b = s["kv_lora_rank"] * s["num_attention_heads"] * (
        s["qk_nope_head_dim"] + s["v_head_dim"])
    return (2.0 * (weights - s["num_hidden_layers"] * kv_b)
            + s["num_hidden_layers"] * attention_ops_per_lane(config, context))


def experts_reached(config: dict, trace: dict, slot_steps: float):
    """(held experts read over the span, summed over layers and passes;
    where the number comes from)."""
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
    """Bytes and operations of the held experts' grouped products alone
    over the span: the experts reached, and two operations per weight of
    the picks the live tokens are expected to land on the share held (a
    floor: the prefill's picks are in the counter's bytes, not in the
    operations)."""
    reached, how = experts_reached(config, trace, slot_steps)
    if not reached:
        return None
    s = _s(config)
    return {
        "bytes": reached * expert_params(config) * BYTES,
        "ops": (2.0 * slot_steps * s["expert_layers"]
                * held_picks_per_token(config) * expert_params(config)),
        "ops_peak": "bf16_flops_per_s",
        "experts_reached": reached,
        "experts_reached_are": how,
        "counted_by": "benchmarks/families/axk1/roofline.py experts_cost",
    }


def mla_decode_cost(config: dict, trace: dict, slot_steps: float,
                    mean_context: float):
    """Bytes and operations of the decode attention's products over the
    latent cache alone, over the span (the module's head says what is
    counted); nothing where the counter of steps did not grow."""
    steps = (trace.get("span_counters") or {}).get(STEPS_COUNTER)
    if not steps:
        return None
    s, serving = _s(config), config["serving"]
    width = max(serving["length_buckets"]) + int(
        serving["sampling"]["max_new_tokens"])
    per_token = latent_bytes_per_token(config)
    return {
        "bytes": slot_steps * mean_context * per_token,
        "ops": (slot_steps * s["num_hidden_layers"]
                * attention_ops_per_lane(config, mean_context)),
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "width_read": width,
        "bytes_read": float(steps) * int(serving["slots"]) * width * per_token,
        "counted_by": "benchmarks/families/axk1/roofline.py mla_decode_cost",
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
                  + slot_steps * mean_context
                  * latent_bytes_per_token(config)),
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
        "counted_by": "benchmarks/families/axk1/roofline.py",
    }
