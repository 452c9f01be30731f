"""The `gpt2_moe` family's side of the comparison: the program's model step
called as the engine's programs call it, and the routing read out of the
program's own expert layer.

The trunk is the GPT-2 family's, so `program` is its prefill-then-decode
(`families/gpt2/compare.py` `program_logits`) with the program's MoE family
in it, and the first three readings are the GPT-2 family's. The fourth is
the routing: `family.forward` hands out logits and the cache and nothing of
what its router chose, so while the program is traced its expert layer
(`models/moe.moe_mlp`) is wrapped: beside the layer's own call, the layer is
called once more on the same hidden state with probe experts in place of
the real ones, every matrix zero and expert e's output bias the e-th unit
vector. What comes back is, per token, the weight the program's own
dispatch and combine give each expert: its top-k, its renormalisation and
its capacity drops, whatever the code that makes them. The probe's answers
leave the program in program order through an ordered `jax.debug.callback`.

`routing_disagreement` is the share of picks on which the two sides differ:
over layers and tokens, the experts one side sends a token to and the other
does not, over the 2 x `experts_per_token` there could be. A router decides
between two experts by a difference of scores that can be smaller than the
served precision's rounding, and a token that goes to another expert comes
out another token: at its position the logits differ by a third where they
differ by a hundredth elsewhere (PERF.md section 6, PR 29). That is the
routing's reading to hold, and its limit says how many such tokens there
may be; the three distances are taken over the positions on which the two
sides sent the token to the same experts in every layer, so that they stay
what they are for GPT-2, a measure of the arithmetic.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check
from benchmarks.families.gpt2 import compare as trunk
from benchmarks.families.gpt2_moe import weights as weights_lib

# What the probe said during the last `program` call, in program order: one
# [S, E] array per expert layer executed.
_heard = []


@contextlib.contextmanager
def _probed():
    """While open, a trace of the program's forward carries the probe."""
    from distributed_lms_raft_llm_tpu.models import moe

    inner = moe.moe_mlp

    def probed(h, mp, cfg, return_aux=False):
        e, d = cfg.num_experts, h.shape[-1]
        probe = jax.tree.map(jnp.zeros_like, mp)
        probe["wr"] = mp["wr"]
        probe["bo"] = jnp.eye(e, d, dtype=mp["bo"].dtype)
        said = inner(h, probe, cfg).reshape(-1, d)[:, :e]
        jax.debug.callback(lambda a: _heard.append(np.asarray(a, np.float32)),
                           said, ordered=True)
        return inner(h, mp, cfg, return_aux)

    moe.moe_mlp = probed
    try:
        yield
    finally:
        moe.moe_mlp = inner


def program(family, cfg, params, ids, shape: dict):
    """The program's (logits, keys, values, routing [L, T, E]) for one
    sequence at the configuration's `check` shape: the prompt fills its
    bucket (a pad token would take an expert's seat, and the reference
    knows of none), the rest is decoded a token at a time."""
    n, bucket = int(shape["prompt_tokens"]), int(shape["bucket"])
    if n != bucket:
        raise ValueError(f"the prompt ({n}) has to fill its bucket ({bucket})")
    # An ordered callback runs on one device: where the engine has laid its
    # parameters over several (a host of many replicates them), the check's
    # one sequence takes a copy on the first.
    params = jax.device_put(params, jax.local_devices()[0])
    del _heard[:]
    with _probed():
        logits, keys, values = trunk.program_logits(
            family, cfg, params, ids, n, bucket, int(shape["width"]))
    jax.block_until_ready(logits)
    jax.effects_barrier()
    layers, passes = cfg.num_layers, 1 + len(ids) - n
    if len(_heard) != layers * passes:
        raise RuntimeError(
            f"the probe heard {len(_heard)} expert layers, not {layers} x "
            f"{passes}: the program was traced without it")
    routing = np.stack([
        np.concatenate([_heard[p * layers + layer][:(n if p == 0 else 1)]
                        for p in range(passes)])
        for layer in range(layers)])
    return logits, keys, values, routing


def check_sizes(config: dict, cfg) -> None:
    """The program's preset must have the file's sizes, the routing's too."""
    got = (cfg.vocab_size, cfg.max_position_embeddings, cfg.hidden_size,
           cfg.num_layers, cfg.num_heads, cfg.mlp_dim, cfg.num_experts)
    want = weights_lib.sizes_of(config)
    routing = (cfg.experts_per_token, cfg.capacity_factor)
    stated = (int(config["experts_per_token"]),
              float(config["capacity_factor"]))
    if got != want or routing != stated:
        raise ValueError(f"registry preset has sizes {got} and routing "
                         f"{routing}, the configuration file {want} and "
                         f"{stated}")


def routing_disagreement(got, want) -> float:
    """Experts one side sends a token to and the other does not, over the
    2 x experts-per-token a token could differ by; `got` and `want` are
    weights [L, T, E], 0 where nothing is sent."""
    got, want = np.asarray(got) > 0, np.asarray(want) > 0
    k = max(1, int(want.sum(axis=-1).max()))
    return float(np.sum(got != want) / (2.0 * k * want.shape[0]
                                        * want.shape[1]))


def readings(got, want) -> dict:
    """The four numbers compared, for one sequence: `got` and `want` are
    (logits, keys, values, routing) of the side judged and the reference.
    The distances are over the positions routed alike in every layer."""
    alike = np.all((np.asarray(got[3]) > 0) == (np.asarray(want[3]) > 0),
                   axis=(0, 2))
    if not alike.any():
        raise ValueError("no position is routed alike on both sides")
    at = np.flatnonzero(alike)
    return dict(
        trunk.readings((got[0][at], got[1][:, :, at], got[2][:, :, at]),
                       (want[0][at], want[1][:, :, at], want[2][:, :, at])),
        routing_disagreement=routing_disagreement(got[3], want[3]))
