"""Bytes and operations a GPT-2 decode step needs, from the configuration's
shapes. JAX-free: the load-generating parent reads it.

Counted per decode step of the served model (int8 matrices with float32
scales, bfloat16 vectors, int8 K and V with float32 scales):

- weights, once a step whatever the batch: every block's four matrices and
  the tied output head in int8, their scales, the biases and LayerNorm
  vectors;
- K and V of the tokens actually live in the step (prompt so far plus
  generated so far, per active slot), with their scales: never the cache's
  padded width;
- operations: two per weight per active slot for the matrices and the
  head, four per live token, layer and hidden unit for the attention dots.

Intermediates (logits, the sampling sort, the repetition mask) are not
counted: the least time is a floor, and the share it gives errs low.
"""

from __future__ import annotations

STEPS_COUNTER = "engine_scan_iterations"


def weight_bytes(config: dict) -> int:
    """Bytes of weights one decode step streams."""
    l, d, v = (int(config[k]) for k in ("n_layer", "n_embd", "vocab_size"))
    matrices = l * 12 * d * d + v * d            # int8
    scales = 4 * (l * 9 * d + v)                 # float32, one per channel
    vectors = 2 * (l * 13 * d + 2 * d)           # bf16 biases and LayerNorms
    return matrices + scales + vectors


def kv_bytes_per_token(config: dict) -> int:
    """Bytes of int8 K and V, with scales, that one live token holds."""
    l, d, h = (int(config[k]) for k in ("n_layer", "n_embd", "n_head"))
    return l * 2 * d + 4 * l * 2 * h


def decode_ops(config: dict, active: float, live_tokens: float) -> float:
    """Operations of one decode step with `active` slots holding
    `live_tokens` tokens of context between them."""
    l, d, v = (int(config[k]) for k in ("n_layer", "n_embd", "vocab_size"))
    return 2.0 * (l * 12 * d * d + v * d) * active + 4.0 * l * d * live_tokens


def decode_steps(trace: dict):
    """The decode steps of the traced span: the loop entered most often is
    the scan over the layers, once per decode step."""
    loops = trace.get("loops") or []
    return max(n for _, n in loops) if loops else None


def cost(config: dict, trace: dict, slot_steps: float, mean_context: float):
    """Bytes and operations of the span's decode steps, which advanced
    `slot_steps` slot-tokens at a mean context of `mean_context` tokens;
    nothing where the trace counted no loop. Activations are bfloat16, so
    the int8 weights are multiplied at the chip's bf16 peak. Beside them,
    for the `notes` line: the steps by the program's own counter over the
    span, which counts an iteration when the host reaps it, and by how
    much the two differ."""
    steps = decode_steps(trace)
    if not steps:
        return None
    by_counter = (trace.get("span_counters") or {}).get(STEPS_COUNTER)
    return {
        "bytes": (steps * weight_bytes(config)
                  + slot_steps * mean_context * kv_bytes_per_token(config)),
        "ops": decode_ops(config, 1.0, mean_context) * slot_steps,
        "ops_peak": "bf16_flops_per_s",
        "steps": steps,
        "steps_are": "entries of the loop entered most often",
        "steps_by_counter": by_counter,
        "steps_less_counter": (None if by_counter is None
                               else steps - by_counter),
    }
