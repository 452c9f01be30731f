"""Bytes and operations a decode step of a nemotron_h model needs, from the
configuration's shapes. JAX-free: the load-generating parent reads it.

Counted per decode step of the served model (bfloat16 weights, keys, values
and activations, a float32 recurrent state; no quantisation), of this
chip's part of the deployment (the configuration file: the blocks kept, the
experts held, the slice of the vocabulary):

- weights outside the routed experts, once a step whatever the batch: every
  Mamba block's projections, convolution, norms and per-head parameters,
  the attention blocks' four projections, every expert block's router (all
  128 columns), bias and shared expert, every block's norm, the final norm
  and the head over the slice (the embedding's rows of the step's tokens
  are not counted);
- of the routed experts HELD, those that were reached: the growth of the
  program's counter `moe_experts_reached` over the traced span where the
  program counts it, else the number expected from the live lanes were the
  routing uniform over all the router's experts. An expert is TWO
  projections, `W_up` and `W_down` (relu^2, not SwiGLU's three);
- the keys and values of the tokens live in the step, in the attention
  blocks alone;
- the recurrent state of the LIVE lanes, read once and written once a Mamba
  block and step: `ssm` (heads x head_dim x state, float32) and `conv`
  (`conv_kernel` - 1 inputs of the channels, bfloat16). Never what a
  kernel chose to read: a lane that is not live costs the floor nothing;
- operations, per active slot: two per weight of the projections, the
  router, the shared expert, the picks expected on the experts held
  (k x held / experts) and the head; the attention's 4 per head, head
  dimension and live key; the state's update and read-out, 5 per state
  element (decay, outer product, sum, product with C, sum).

`experts_cost`: the grouped products of the experts held alone (the floor of
`moe_experts_roofline` in this family's cells). `ssm_step_cost`: the decode
step's state update alone (the floor of `ssm_step_roofline`): the live
lanes' `ssm` read once and written once a Mamba block; what the kernel
reads besides (every slot's state, live or not) is handed on as
`bytes_read`, and a kernel that skips dead lanes cannot pass 100%.

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
STATE_OPS = 5    # per state element and step


def _s(config: dict) -> dict:
    keys = ("hidden_size", "num_hidden_layers", "mamba_num_heads",
            "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "n_routed_experts", "num_experts_per_tok", "vocab_size")
    s = {k: int(config[k]) for k in keys}
    pattern = str(config["hybrid_override_pattern"])
    s["experts"] = int(config["published"]["n_routed_experts"])
    s["mamba_layers"] = pattern.count("M")
    s["expert_layers"] = pattern.count("E")
    s["attention_layers"] = pattern.count("*")
    s["inner"] = s["mamba_num_heads"] * s["mamba_head_dim"]
    s["conv_dim"] = s["inner"] + 2 * s["n_groups"] * s["ssm_state_size"]
    return s


def mamba_params(config: dict) -> int:
    """One Mamba block: in and out projections, the convolution and its
    bias, dt_bias, A_log, D and the gated norm (its block norm is counted
    with the blocks)."""
    s = _s(config)
    d, h = s["hidden_size"], s["mamba_num_heads"]
    return (d * (s["inner"] + s["conv_dim"] + h) + s["inner"] * d
            + s["conv_dim"] * (s["conv_kernel"] + 1) + 3 * h + s["inner"])


def attention_params(config: dict) -> int:
    s = _s(config)
    d, dh = s["hidden_size"], s["head_dim"]
    return (d * dh * (s["num_attention_heads"]
                      + 2 * s["num_key_value_heads"])
            + s["num_attention_heads"] * dh * d)


def expert_params(config: dict) -> int:
    """Parameters of ONE routed expert of one layer: two projections."""
    s = _s(config)
    return 2 * s["hidden_size"] * s["moe_intermediate_size"]


def routed_rest_params(config: dict) -> int:
    """An expert block outside its routed experts: the router, its bias
    and the shared expert."""
    s = _s(config)
    d = s["hidden_size"]
    return (d * s["experts"] + s["experts"]
            + 2 * d * s["moe_shared_expert_intermediate_size"])


def trunk_params(config: dict) -> int:
    """Parameters a step streams whatever its batch: everything but the
    routed experts and the embedding."""
    s = _s(config)
    d = s["hidden_size"]
    return (s["mamba_layers"] * mamba_params(config)
            + s["attention_layers"] * attention_params(config)
            + s["expert_layers"] * routed_rest_params(config)
            + s["num_hidden_layers"] * d + d + s["vocab_size"] * d)


def parameters(config: dict) -> int:
    """Every parameter the chip holds (`hbm_bytes_worked_out`)."""
    s = _s(config)
    return (trunk_params(config) + s["vocab_size"] * s["hidden_size"]
            + s["expert_layers"] * s["n_routed_experts"]
            * expert_params(config))


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


def kv_bytes_per_token(config: dict) -> int:
    """Bytes of keys and values a token holds, over the attention blocks."""
    s = _s(config)
    return (s["attention_layers"] * 2 * s["num_key_value_heads"]
            * s["head_dim"] * BYTES)


def ssm_bytes_per_slot(config: dict) -> int:
    """Bytes of ONE Mamba block's `ssm` state of one slot (float32)."""
    s = _s(config)
    return s["inner"] * s["ssm_state_size"] * STATE_BYTES


def conv_bytes_per_slot(config: dict) -> int:
    """Bytes of ONE Mamba block's `conv` window of one slot (bfloat16)."""
    s = _s(config)
    return (s["conv_kernel"] - 1) * s["conv_dim"] * BYTES


def state_bytes_per_lane_step(config: dict) -> int:
    """Bytes a live lane's state costs a step: read once and written once
    in every Mamba block."""
    return 2 * _s(config)["mamba_layers"] * (
        ssm_bytes_per_slot(config) + conv_bytes_per_slot(config))


def slot_ops(config: dict, context: float) -> float:
    """Operations of one slot's token at `context` live tokens."""
    s = _s(config)
    weights = (trunk_params(config) + s["expert_layers"]
               * held_picks_per_token(config) * expert_params(config))
    return (2.0 * weights
            + s["attention_layers"] * 4.0 * s["num_attention_heads"]
            * s["head_dim"] * context
            + s["mamba_layers"] * STATE_OPS * s["inner"]
            * s["ssm_state_size"])


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
    over the span: the experts reached (two projections each), and two
    operations per weight of the picks the live tokens are expected to
    land on the share held (a floor: the prefill's picks are in the
    counter's bytes, not in the operations)."""
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
        "counted_by": "benchmarks/families/nemotron_h/roofline.py "
                      "experts_cost",
    }


def ssm_step_cost(config: dict, trace: dict, slot_steps: float,
                  mean_context: float):
    """Bytes and operations of the decode steps' state update alone over
    the span (the module's head says what is counted); nothing where the
    counter of steps did not grow."""
    steps = (trace.get("span_counters") or {}).get(STEPS_COUNTER)
    if not steps:
        return None
    s = _s(config)
    per_lane = 2 * s["mamba_layers"] * ssm_bytes_per_slot(config)
    return {
        "bytes": slot_steps * per_lane,
        "ops": (slot_steps * s["mamba_layers"] * STATE_OPS * s["inner"]
                * s["ssm_state_size"]),
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "bytes_read": float(steps) * int(config["serving"]["slots"])
        * per_lane,
        "counted_by": "benchmarks/families/nemotron_h/roofline.py "
                      "ssm_step_cost",
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
                  + slot_steps * (mean_context * kv_bytes_per_token(config)
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
        "counted_by": "benchmarks/families/nemotron_h/roofline.py",
    }
