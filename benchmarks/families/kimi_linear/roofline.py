"""Bytes and operations a decode step of a kimi_linear model needs, from the
configuration's shapes. JAX-free: the load-generating parent reads it.

Counted per decode step of the served model (bfloat16 weights, latent cache
and activations, a float32 recurrent state; no quantisation), of this
chip's part of the deployment (the configuration file: the layers kept, the
experts held, the slice of the vocabulary):

- weights outside the routed experts, once a step whatever the batch: every
  KDA layer's projections, convolutions, norm and per-head parameters, the
  MLA layers' projections (`q`, `kv_a`, `kv_b`, `o`) and latent norm, the
  dense layers' SwiGLU, every expert layer's router (all 256 columns), bias
  and shared expert, every layer's two norms, the final norm and the head
  over the slice (the embedding's rows of the step's tokens are not
  counted);
- of the routed experts HELD, those that were reached: the growth of the
  program's counter `moe_experts_reached` over the traced span where the
  program counts it, else the number expected from the live lanes were the
  routing uniform over all the router's experts;
- the latent cache of the tokens live in the step, in the MLA layers alone,
  2 x (`kv_lora_rank` + `qk_rope_head_dim`) bytes a token and layer, never
  the padded width;
- the recurrent state of the LIVE lanes, read once and written once a KDA
  layer and step: `ssm` (heads x key dim x value dim, float32) and `conv`
  (kernel - 1 inputs of the 3 H K channels, bfloat16). Never what a kernel
  chose to read: a lane that is not live costs the floor nothing;
- operations, per active slot: two per weight of the projections, the
  router, the dense and shared SwiGLUs, the picks expected on the experts
  held (k x held / experts) and the head; the absorbed attention's (as
  `families/axk1/roofline.py`); the state's update and read-out, 8 per
  state element (decay, the product with k and its sum, the rank-one
  product and its sum, the product with q and its sum).

`experts_cost`: the grouped products of the experts held alone (the floor of
`moe_experts_roofline` in this family's cells). `mla_decode_cost`: the
decode attention's products over the latent cache alone (the floor of
`mla_decode_roofline`). `kda_step_cost`: the decode step's state update
alone (the floor of `kda_step_roofline`): the live lanes' `ssm` read once
and written once a KDA layer; what the kernels read besides (every slot's
state or whole padded row, live or not) is handed on as `bytes_read`, and a
kernel that skips dead lanes cannot pass 100%.

The steps are the program's own counter of scan iterations over the span
(`engine_scan_iterations`). Intermediates (logits, the sampling sort, the
sort of the picks) are not counted: the least time is a floor, and the
share it gives errs low.
"""

from __future__ import annotations

STEPS_COUNTER = "engine_scan_iterations"
REACHED_COUNTER = "moe_experts_reached"
BYTES = 2        # bfloat16
STATE_BYTES = 4  # float32
STATE_OPS = 8    # per state element and step
HERE = "benchmarks/families/kimi_linear/roofline.py"


def _s(config: dict) -> dict:
    keys = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "intermediate_size",
            "moe_intermediate_size", "num_experts", "num_experts_per_token",
            "num_shared_experts", "vocab_size")
    s = {k: int(config[k]) for k in keys}
    lin = config["linear_attn_config"]
    s["experts"] = int(config["published"]["num_experts"])
    s["expert_layers"] = s["num_hidden_layers"] - s["first_k_dense_replace"]
    s["kda_layers"] = len(lin["kda_layers"])
    s["mla_layers"] = len(lin["full_attn_layers"])
    s["kda_heads"], s["kda_dim"] = int(lin["num_heads"]), int(lin["head_dim"])
    s["conv_kernel"] = int(lin["short_conv_kernel_size"])
    s["inner"] = s["kda_heads"] * s["kda_dim"]
    s["latent"] = s["kv_lora_rank"] + s["qk_rope_head_dim"]
    return s


def kda_params(config: dict) -> int:
    """One KDA mixer: q, k, v and o projections, the three convolutions,
    the decay's and the gate's low-rank pairs, A_log, dt_bias, the beta
    projection and the head norm."""
    s = _s(config)
    d, inner, kd = s["hidden_size"], s["inner"], s["kda_dim"]
    return (4 * d * inner + 3 * inner * s["conv_kernel"]
            + 2 * (d * kd + kd * inner) + s["kda_heads"] + inner
            + d * s["kda_heads"] + kd)


def mla_params(config: dict) -> int:
    """One MLA mixer: one query projection, `kv_a`, the latent norm, `kv_b`
    and the output projection."""
    s = _s(config)
    d, h = s["hidden_size"], s["num_attention_heads"]
    return (d * h * (s["qk_nope_head_dim"] + s["qk_rope_head_dim"])
            + d * s["latent"] + s["kv_lora_rank"]
            + s["kv_lora_rank"] * h * (s["qk_nope_head_dim"]
                                       + s["v_head_dim"])
            + h * s["v_head_dim"] * d)


def expert_params(config: dict) -> int:
    """Parameters of ONE routed expert of one layer."""
    s = _s(config)
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def routed_rest_params(config: dict) -> int:
    """An expert layer's MLP outside its routed experts: the router, its
    bias and the shared expert."""
    s = _s(config)
    d = s["hidden_size"]
    return (d * s["experts"] + s["experts"]
            + 3 * d * s["moe_intermediate_size"] * s["num_shared_experts"])


def trunk_params(config: dict) -> int:
    """Parameters a step streams whatever its batch: everything but the
    routed experts and the embedding."""
    s = _s(config)
    d = s["hidden_size"]
    return (s["kda_layers"] * kda_params(config)
            + s["mla_layers"] * mla_params(config)
            + s["first_k_dense_replace"] * 3 * d * s["intermediate_size"]
            + s["expert_layers"] * routed_rest_params(config)
            + s["num_hidden_layers"] * 2 * d + d + s["vocab_size"] * d)


def parameters(config: dict) -> int:
    """Every parameter the chip holds (`hbm_bytes_worked_out`)."""
    s = _s(config)
    return (trunk_params(config) + s["vocab_size"] * s["hidden_size"]
            + s["expert_layers"] * s["num_experts"] * expert_params(config))


def held_picks_per_token(config: dict) -> float:
    """Picks a token is expected to land on the experts held, a layer."""
    s = _s(config)
    return s["num_experts_per_token"] * s["num_experts"] / s["experts"]


def expected_reached(config: dict, lanes: float) -> float:
    """Held experts of one layer that `lanes` live tokens are expected to
    reach, were the routing uniform over all the router's experts."""
    s = _s(config)
    return s["num_experts"] * (1.0 - (1.0 - 1.0 / s["experts"]) ** (
        s["num_experts_per_token"] * lanes))


def latent_bytes_per_token(config: dict) -> int:
    """Bytes the latent cache holds a token, over the MLA layers kept."""
    s = _s(config)
    return s["mla_layers"] * s["latent"] * BYTES


def ssm_bytes_per_slot(config: dict) -> int:
    """Bytes of ONE KDA layer's `ssm` state of one slot (float32)."""
    s = _s(config)
    return s["inner"] * s["kda_dim"] * STATE_BYTES


def conv_bytes_per_slot(config: dict) -> int:
    """Bytes of ONE KDA layer's `conv` window of one slot (bfloat16)."""
    s = _s(config)
    return (s["conv_kernel"] - 1) * 3 * s["inner"] * BYTES


def state_bytes_per_lane_step(config: dict) -> int:
    """Bytes a live lane's state costs a step: read once and written once
    in every KDA layer."""
    return 2 * _s(config)["kda_layers"] * (
        ssm_bytes_per_slot(config) + conv_bytes_per_slot(config))


def attention_ops_per_lane(config: dict, context: float) -> float:
    """Operations of one lane's absorbed decode attention over `context`
    live keys, one MLA layer (`families/axk1/roofline.py`)."""
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
    return (2.0 * (weights - s["mla_layers"] * kv_b)
            + s["mla_layers"] * attention_ops_per_lane(config, context)
            + s["kda_layers"] * STATE_OPS * s["inner"] * s["kda_dim"])


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
        "counted_by": HERE + " experts_cost",
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
        "ops": (slot_steps * s["mla_layers"]
                * attention_ops_per_lane(config, mean_context)),
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "width_read": width,
        "bytes_read": float(steps) * int(serving["slots"]) * width * per_token,
        "counted_by": HERE + " mla_decode_cost",
    }


def kda_step_cost(config: dict, trace: dict, slot_steps: float,
                  mean_context: float):
    """Bytes and operations of the decode steps' state update alone over
    the span (the module's head says what is counted); nothing where the
    counter of steps did not grow."""
    steps = (trace.get("span_counters") or {}).get(STEPS_COUNTER)
    if not steps:
        return None
    s = _s(config)
    per_lane = 2 * s["kda_layers"] * ssm_bytes_per_slot(config)
    return {
        "bytes": slot_steps * per_lane,
        "ops": (slot_steps * s["kda_layers"] * STATE_OPS * s["inner"]
                * s["kda_dim"]),
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "bytes_read": float(steps) * int(config["serving"]["slots"])
        * per_lane,
        "counted_by": HERE + " kda_step_cost",
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
                  + slot_steps * (mean_context
                                  * latent_bytes_per_token(config)
                                  + state_bytes_per_lane_step(config))),
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
        "counted_by": HERE,
    }
