"""Bytes and operations a GPT-2 decode step needs, from the configuration's
shapes, and the least time a chip could take for them.

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

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The table's row for `device_kind`; a kind not in it is an error."""
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: "
                       f"benchmarks/peaks.json has {sorted(table)}")
    return table[device_kind]


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


def decode_least_seconds(config: dict, device_kind: str, steps: float,
                         slot_steps: float, mean_context: float) -> dict:
    """The least time for `steps` decode steps that advanced `slot_steps`
    slot-tokens at a mean context of `mean_context` tokens, and which bound
    it is."""
    pk = peaks(device_kind)
    nbytes = (steps * weight_bytes(config)
              + slot_steps * mean_context * kv_bytes_per_token(config))
    ops = (decode_ops(config, 1.0, mean_context) * slot_steps)
    by_bytes = nbytes / pk["hbm_bytes_per_s"]
    # Activations are bfloat16, so the int8 weights are multiplied in bf16.
    by_ops = ops / pk["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_ops), "bytes": nbytes, "ops": ops,
            "bound": "memory" if by_bytes >= by_ops else "compute"}
