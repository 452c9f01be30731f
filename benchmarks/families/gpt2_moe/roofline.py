"""Bytes and operations a decode step of the GPT-2 trunk with routed
experts needs, from the configuration's shapes. JAX-free.

Counted as the GPT-2 family counts (int8 matrices with float32 scales,
bfloat16 vectors, int8 K and V with float32 scales, live tokens only), with
the experts in place of the MLP:

- weights, once a step: every layer's attention matrices, its router
  (bfloat16: served unquantised) and the tied head; of its experts ONLY
  those a step can reach: `experts_per_token` for every active slot, and
  never more than there are. A step of one slot streams two experts of
  four, not four;
- operations, per active slot: two per weight of the attention matrices,
  the router, the head and of `experts_per_token` experts, and four per
  live token, layer and hidden unit for the attention dots. The program
  computes every seat of every expert's buffer, taken or not
  (`models/moe.py`): not counted, a floor asks what the step needs.

The steps are the program's own counter of scan iterations over the traced
span (`engine_scan_iterations`, counted when the host reaps them), which a
trace without a device plane has too; the loop entered most often stands
beside it on the `notes` line.
"""

from __future__ import annotations

STEPS_COUNTER = "engine_scan_iterations"


def _sizes(config: dict) -> tuple:
    return tuple(int(config[k]) for k in (
        "n_layer", "n_embd", "n_head", "vocab_size", "n_inner",
        "num_experts", "experts_per_token"))


def trunk_bytes(config: dict) -> int:
    """Bytes a step streams whatever its batch: attention, router, head."""
    l, d, _, v, _, e, _ = _sizes(config)
    matrices = l * 4 * d * d + v * d             # int8
    scales = 4 * (l * 4 * d + v)                 # float32, one per channel
    vectors = 2 * (l * 8 * d + 2 * d)            # bf16 biases and LayerNorms
    router = 2 * l * d * e                       # bf16
    return matrices + scales + vectors + router


def expert_bytes(config: dict) -> int:
    """Bytes of ONE expert of one layer: two int8 matrices, their scales,
    its two bf16 biases."""
    _, d, _, _, m, _, _ = _sizes(config)
    return 2 * d * m + 4 * (m + d) + 2 * (m + d)


def experts_reached(config: dict, active: float) -> float:
    """Experts of a layer a step with `active` slots can reach."""
    _, _, _, _, _, e, k = _sizes(config)
    return min(float(e), active * k)


def kv_bytes_per_token(config: dict) -> int:
    """Bytes of int8 K and V, with scales, that one live token holds."""
    l, d, h = _sizes(config)[:3]
    return l * 2 * d + 4 * l * 2 * h


def slot_ops(config: dict, context: float) -> float:
    """Operations of one slot's token at `context` live tokens."""
    l, d, _, v, m, e, k = _sizes(config)
    weights = l * (4 * d * d + d * e + k * 2 * d * m) + v * d
    return 2.0 * weights + 4.0 * l * d * context


def cost(config: dict, trace: dict, slot_steps: float, mean_context: float):
    """Bytes and operations of the span's decode steps, which advanced
    `slot_steps` slot-tokens at a mean context of `mean_context` tokens;
    nothing where the counter did not grow."""
    steps = (trace.get("span_counters") or {}).get(STEPS_COUNTER)
    if not steps:
        return None
    loops = trace.get("loops") or []
    by_loop = max(n for _, n in loops) if loops else None
    l = _sizes(config)[0]
    reached = experts_reached(config, slot_steps / steps)
    return {
        "bytes": (steps * (trunk_bytes(config)
                           + l * reached * expert_bytes(config))
                  + slot_steps * mean_context * kv_bytes_per_token(config)),
        "ops": slot_ops(config, mean_context) * slot_steps,
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "steps_are": f"growth of the counter {STEPS_COUNTER} over the span",
        "steps_by_loop": by_loop,
        "steps_less_loop": None if by_loop is None else steps - by_loop,
        "experts_reached_per_layer": reached,
        "counted_by": "benchmarks/families/gpt2_moe/roofline.py",
    }
