"""Training checkpoint save/restore (safetensors + sidecar metadata).

Round-trips the FULL train state — params, optimizer moments, step — via
the same safetensors writer the serving path uses (models/convert.py), so
a fine-tuned model is immediately servable: `export_model()` writes the
params alone in HF layout for `EngineConfig(checkpoint=...)`.

Layout: one `.safetensors` holding every state leaf under its tree path
(`params/blocks/attn/wqkv`, `opt_state/1/0/mu/...`), plus `<path>.json`
with the step and leaf manifest. Restore maps leaves back into a freshly
built state template (shapes validated), then device_puts through the
caller's shardings — works for both single-chip and pjit-sharded resumes.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

import jax
import numpy as np

from ..models import convert


def _flatten(state: Any) -> Dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    out = {}
    for keypath, leaf in flat:
        key = "/".join(_key_str(k) for k in keypath)
        out[key] = np.asarray(jax.device_get(leaf))
    return out


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    if hasattr(k, "name"):
        return str(k.name)
    return str(k)


def save_train_state(path: str, state: Any) -> None:
    """Write the whole train state to `path` (.safetensors) + `path`.json."""
    flat = _flatten(state)
    convert.save_safetensors(path, flat)
    meta = {
        "step": int(np.asarray(jax.device_get(state["step"]))),
        "leaves": sorted(flat),
    }
    tmp = path + ".json.tmp"
    with open(tmp, "w") as fh:
        json.dump(meta, fh)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path + ".json")


def restore_train_state(
    path: str, template: Any, shardings: Optional[Any] = None
) -> Any:
    """Load a checkpoint back into `template`'s structure.

    `template` is a freshly-built train state (init_train_state) providing
    the pytree structure and expected shapes; `shardings` (optional, same
    structure) device_puts each restored leaf — pass the pjit shardings to
    resume a sharded run.
    """
    tensors = convert.load_safetensors(path)
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for keypath, leaf in flat:
        key = "/".join(_key_str(k) for k in keypath)
        if key not in tensors:
            raise ValueError(f"checkpoint {path} missing leaf {key!r}")
        value = tensors[key]
        if tuple(value.shape) != tuple(np.shape(leaf)):
            raise ValueError(
                f"checkpoint leaf {key!r} has shape {value.shape}, "
                f"expected {np.shape(leaf)}"
            )
        leaves.append(value.astype(np.asarray(leaf).dtype))
    state = jax.tree_util.tree_unflatten(treedef, leaves)
    if shardings is not None:
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, s), state, shardings
        )
    return state


def export_model(path: str, state: Any) -> None:
    """Write just the fine-tuned parameters in HF GPT-2 layout (the inverse
    of the import mapping), so `EngineConfig(checkpoint=path)` serves the
    fine-tuned model through the standard checkpoint path. MoE params have
    no HF counterpart layout; they export in the native tree layout
    (slash-joined paths), which `models.moe.params_from_hf` reads back."""
    params = jax.device_get(state["params"])
    if "moe" in params.get("blocks", {}):
        convert.save_safetensors(path, _flatten(params))
        return
    convert.save_safetensors(path, convert.gpt2_params_to_hf(params))


def latest_step(path: str) -> Optional[int]:
    """Step recorded in `path`'s sidecar, or None if no checkpoint."""
    if not os.path.exists(path + ".json"):
        return None
    with open(path + ".json") as fh:
        return int(json.load(fh)["step"])
