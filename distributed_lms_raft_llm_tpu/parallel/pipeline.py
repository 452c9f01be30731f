"""Pipeline parallelism: the stacked layer trunk sharded over the `pp` axis.

The models' TPU-first layout (every per-layer weight stacked on a leading
[L, ...] axis, models/gpt2.py) makes pipeline sharding a PartitionSpec: put
`P("pp", ...)` on the layer axis and each device holds L/pp contiguous
layers. This module supplies the schedule: a GPipe-style loop under
`shard_map` where activations hop stage-to-stage over `ppermute` while
microbatches keep every stage busy (pipeline fill/drain is the usual
(pp-1)/(n_micro+pp-1) bubble).

The result is EXACTLY the sequential `lax.scan` over all L layers
(parity-tested on the virtual mesh); the win is memory — each device
stores 1/pp of the trunk parameters — which is what pipeline parallelism
is for. The reference has no analogue of any of this (single-process torch
inference, reference: GUI_RAFT_LLM_SourceCode/tutoring_server.py:10-31);
SURVEY §2.2 lists PP as the optional later axis, and this makes `pp` in
`parallel.mesh` a real capability like `sp` (ring attention) rather than a
decorative mesh dimension.

Production reachability: `gpt2.forward_pipelined` runs the real GPT-2
trunk through this schedule, and `train.make_sharded_train_step` uses it
for any mesh with pp > 1 (the train CLI's --pp/--pp-micro flags), with the
stacked layer weights and their optimizer moments stage-sharded
(train_state_shardings). Loss parity vs the sequential trunk is pinned in
tests/test_model_parallel.py.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

LayerFn = Callable[[jax.Array, jax.Array], jax.Array]  # (layer_params, x) -> x


def _pipeline_shard(stacked, x, *, layer_fn: LayerFn, n_stages: int,
                    n_micro: int, axis_name: str):
    """Per-stage body: run local layers on the current microbatch, pass the
    activation to the next stage, inject/collect at the ends.

    stacked: this stage's [L/pp, ...] slice of the layer parameters.
    x:       the full [n_micro, Bm, ...] microbatched input (replicated).
    """
    idx = jax.lax.axis_index(axis_name)
    is_first = idx == 0
    is_last = idx == n_stages - 1
    # Stage i receives from i-1; no wraparound (the ends inject/collect).
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def local_layers(h):
        def body(h, lp):
            return layer_fn(lp, h), None

        h, _ = jax.lax.scan(body, h, stacked)
        return h

    # Seed the carries with a value that VARIES over the pp axis (derived
    # from this stage's param slice) — the loop body's ppermute/update
    # results are pp-varying, and the shard_map type system rejects a
    # replicated initial carry meeting a varying loop output.
    vzero = (
        jnp.sum(jax.tree_util.tree_leaves(stacked)[0]) * 0
    ).astype(x.dtype)
    zero_like = x[0] * 0 + vzero
    out0 = x * 0 + vzero

    def tick(t, carry):
        received, outputs = carry
        # Stage 0's input for this tick is microbatch t (clamped; ticks
        # past n_micro-1 are drain ticks whose stage-0 output is ignored).
        mb = jax.lax.dynamic_index_in_dim(
            x, jnp.minimum(t, n_micro - 1), 0, keepdims=False
        )
        h = jnp.where(is_first, mb, received)
        y = local_layers(h)
        # The last stage finishes microbatch t-(pp-1) at tick t.
        done_idx = t - (n_stages - 1)
        updated = jax.lax.dynamic_update_index_in_dim(
            outputs, y, jnp.maximum(done_idx, 0), 0
        )
        outputs = jnp.where(is_last & (done_idx >= 0), updated, outputs)
        received = jax.lax.ppermute(y, axis_name, perm)
        return received, outputs

    _, outputs = jax.lax.fori_loop(
        0, n_micro + n_stages - 1, tick, (zero_like, out0)
    )
    # Only the last stage holds the results; psum broadcasts them to every
    # stage so the caller gets a replicated tensor (the loss/unembed can
    # then run anywhere).
    outputs = jnp.where(is_last, outputs, jnp.zeros_like(outputs))
    return jax.lax.psum(outputs, axis_name)


def pipeline_trunk(
    layer_fn: LayerFn,
    stacked_params,
    x: jax.Array,
    mesh: Mesh,
    *,
    n_micro: int,
    axis_name: str = "pp",
    param_spec: P = None,
    batch_spec: P = None,
) -> jax.Array:
    """Apply L stacked layers to x [B, ...] with the layer axis sharded over
    `axis_name` and the batch split into `n_micro` microbatches.

    `layer_fn(layer_params, h) -> h` is one layer (e.g. a transformer
    block); `stacked_params` is any pytree whose leaves lead with the layer
    axis L (L divisible by the pp size, B divisible by n_micro). Returns
    exactly `lax.scan(layer_fn, x, stacked_params)`'s result.

    `batch_spec` is the spec of the microbatched activations
    [n_micro, B/n_micro, ...] — pass e.g. P(None, "dp") to keep the batch
    data-parallel inside the stages (the pp psum at the end leaves other
    axes untouched); default fully replicated.
    """
    n_stages = mesh.shape[axis_name]
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
    layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if layers % n_stages:
        raise ValueError(
            f"{layers} stacked layers not divisible by the {axis_name} "
            f"axis size {n_stages}"
        )
    param_spec = param_spec or P(axis_name)
    batch_spec = batch_spec or P()
    xm = x.reshape((n_micro, b // n_micro) + x.shape[1:])

    specs_params = jax.tree.map(lambda _: param_spec, stacked_params)
    fn = shard_map(
        functools.partial(
            _pipeline_shard, layer_fn=layer_fn, n_stages=n_stages,
            n_micro=n_micro, axis_name=axis_name,
        ),
        mesh=mesh,
        in_specs=(specs_params, batch_spec),
        out_specs=batch_spec,
    )
    out = fn(stacked_params, xm)
    return out.reshape(x.shape)
