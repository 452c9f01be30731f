"""Sharded training step: LM loss + optax optimizer under pjit.

The reference has no training at all (SURVEY.md §2.2) — models come frozen
from the HF hub. A TPU-native framework needs the training path anyway
(fine-tuning the tutoring model on course data is the obvious extension),
and the multi-chip dry-run validates it: parameters/optimizer state shard
per `parallel.partition` rules (tp), the batch shards over dp, gradients
reduce across dp implicitly via jit's sharding propagation, and activations
can be rematerialized (`jax.checkpoint`) to trade FLOPs for HBM.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import gpt2, moe
from ..parallel import partition


@dataclasses.dataclass
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.01
    warmup_steps: int = 100
    decay_steps: int = 10_000  # cosine horizon; set to the planned run length
    max_grad_norm: float = 1.0
    remat: bool = True  # rematerialize block activations (HBM for FLOPs)
    # GPipe microbatches per step when the mesh has a pp axis > 1 (the
    # stacked trunk pipelines via parallel.pipeline.pipeline_trunk; bubble
    # fraction (pp-1)/(pp_micro+pp-1)).
    pp_micro: int = 2
    # MoE: weight of the Switch load-balance aux loss (models/moe.py,
    # applies only to GPT2MoEConfig models — keeps the router from
    # collapsing onto a few experts).
    moe_aux_weight: float = 0.01


def _is_moe(model_cfg) -> bool:
    return isinstance(model_cfg, moe.GPT2MoEConfig)


def _init_params_for(model_cfg):
    return moe.init_params if _is_moe(model_cfg) else gpt2.init_params


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.learning_rate,
        warmup_steps=cfg.warmup_steps,
        decay_steps=cfg.decay_steps,
    )
    return optax.chain(
        optax.clip_by_global_norm(cfg.max_grad_norm),
        optax.adamw(schedule, weight_decay=cfg.weight_decay),
    )


def lm_loss(
    logits: jax.Array, targets: jax.Array, mask: jax.Array
) -> jax.Array:
    """Token-mean cross entropy; logits [B,T,V] f32, targets/mask [B,T]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = mask.astype(jnp.float32)
    return -jnp.sum(picked * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def init_train_state(
    rng: jax.Array, model_cfg: gpt2.GPT2Config, optimizer
) -> Dict[str, Any]:
    params = _init_params_for(model_cfg)(rng, model_cfg)
    return {
        "params": params,
        "opt_state": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def train_state_shardings(state, mesh: Mesh):
    """NamedShardings for the whole train state: params + optimizer moments
    follow the model partition rules (adam mu/nu mirror param shapes);
    scalars replicate. A pp axis > 1 additionally shards every stacked
    block leaf's leading layer axis over pp — each pipeline stage stores
    only its own L/pp layers (and their optimizer moments). MoE states are
    recognized by their param structure and use the gpt2_moe rules
    (experts over ep)."""

    is_moe_state = "moe" in state["params"].get("blocks", {})
    param_specs = partition.match_partition_rules(
        partition.RULES_FOR["gpt2_moe"] if is_moe_state
        else partition.GPT2_RULES,
        state["params"], mesh,
    )
    if mesh.shape.get("pp", 1) > 1:
        param_specs["blocks"] = jax.tree.map(
            lambda s: P("pp", *tuple(s)[1:]),
            param_specs["blocks"],
            is_leaf=lambda s: isinstance(s, P),
        )

    # Optimizer leaves that mirror a parameter (same shape) reuse its spec;
    # everything else (counts, scalars) replicates.
    flat_params, _ = jax.tree_util.tree_flatten(state["params"])
    flat_specs, _ = jax.tree_util.tree_flatten(
        param_specs, is_leaf=lambda x: isinstance(x, P)
    )
    shape_to_spec = {}
    for leaf, spec in zip(flat_params, flat_specs):
        shape_to_spec.setdefault(leaf.shape, spec)

    def leaf_spec(leaf):
        if getattr(leaf, "ndim", 0) == 0:
            return P()
        return shape_to_spec.get(leaf.shape, P())

    specs = {
        "params": param_specs,
        "opt_state": jax.tree.map(leaf_spec, state["opt_state"]),
        "step": P(),
    }
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def make_train_step(
    model_cfg: gpt2.GPT2Config,
    optimizer,
    remat: bool = True,
    mesh: Optional[Mesh] = None,
    pp_micro: int = 2,
    moe_aux_weight: float = 0.01,
) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics); jit it with the
    shardings from `train_state_shardings` + batch over dp.

    Parallel axes beyond dp/tp activate from the mesh shape:
    - sp > 1: the model's full-sequence attention runs as ring attention
      (gpt2.GPT2Config.ring_mesh), the batch's sequence dim sharded over sp;
    - pp > 1: the stacked trunk runs as a GPipe pipeline
      (gpt2.forward_pipelined) with `pp_micro` microbatches, layer weights
      stage-sharded per `train_state_shardings`.
    """
    is_moe = _is_moe(model_cfg)
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        # Composes with MoE too: forward_with_aux IS gpt2.forward, whose
        # ring path carries the aux channel (parity-tested in test_moe).
        model_cfg = dataclasses.replace(model_cfg, ring_mesh=mesh)
    pipelined = mesh is not None and mesh.shape.get("pp", 1) > 1
    if pipelined and is_moe:
        raise ValueError(
            "pp and MoE cannot combine yet: the pipeline stage body has "
            "no aux-loss channel; use ep x tp x dp"
        )

    if pipelined:
        # Combinations the pipeline schedule does not implement yet — fail
        # loudly rather than silently degrade:
        # - sp: trunk_layer uses dense full-sequence attention, so ring
        #   attention (the whole point of --sp) would be dropped;
        # - tp: the shard_map stage body has no tp collectives, so sharded
        #   weight in_specs would compute wrong partials (and replicated
        #   ones would all-gather tp-sharded weights every step).
        if mesh.shape.get("sp", 1) > 1:
            raise ValueError(
                "pp and sp cannot combine: the pipeline stage body uses "
                "dense attention (ring attention unreachable under pp)"
            )
        if mesh.shape.get("tp", 1) > 1:
            raise ValueError(
                "pp and tp cannot combine: the pipeline stage body has no "
                "tensor-parallel collectives; use pp x dp"
            )

        def forward(params, _cfg, input_ids):
            logits = gpt2.forward_pipelined(
                params, model_cfg, input_ids, mesh, n_micro=pp_micro,
                batch_spec=P(None, "dp"), remat=remat,
            )
            return logits, None
    else:
        forward = moe.forward_with_aux if is_moe else gpt2.forward
        if remat:
            forward = jax.checkpoint(partial(forward), static_argnums=(1,))

    def loss_fn(params, input_ids, loss_mask):
        if is_moe:
            logits, aux = forward(params, model_cfg, input_ids)
        else:
            logits, _ = forward(params, model_cfg, input_ids)
            aux = 0.0
        # next-token prediction: shift by one
        loss = lm_loss(logits[:, :-1], input_ids[:, 1:], loss_mask[:, 1:])
        return loss + moe_aux_weight * aux, aux

    def train_step(state, batch):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch["input_ids"], batch["loss_mask"]
        )
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        params = optax.apply_updates(state["params"], updates)
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        gnorm = optax.global_norm(grads)
        metrics = {"loss": loss, "grad_norm": gnorm}
        if is_moe:
            metrics["moe_balance"] = aux
        return new_state, metrics

    return train_step


def make_sharded_train_step(
    mesh: Mesh, model_cfg: gpt2.GPT2Config, train_cfg: TrainConfig, rng
):
    """Everything wired: returns (jitted_step, sharded_state, batch_sharding).

    The batch shards over dp; XLA derives the gradient all-reduce over dp
    and the tensor-parallel collectives over tp from the argument shardings
    alone — no hand-written collectives (SURVEY.md §2.2 TPU-native plan).
    """
    optimizer = make_optimizer(train_cfg)
    with jax.default_device(jax.devices()[0]):
        state = init_train_state(rng, model_cfg, optimizer)
    state_shardings = train_state_shardings(state, mesh)
    state = jax.tree.map(
        lambda x, s: jax.device_put(x, s), state, state_shardings
    )
    # sp > 1: the sequence dim shards too (ring attention consumes it).
    seq_axis = "sp" if mesh.shape.get("sp", 1) > 1 else None
    batch_sharding = {
        "input_ids": NamedSharding(mesh, P("dp", seq_axis)),
        "loss_mask": NamedSharding(mesh, P("dp", seq_axis)),
    }
    step = jax.jit(
        make_train_step(model_cfg, optimizer, remat=train_cfg.remat,
                        mesh=mesh, pp_micro=train_cfg.pp_micro,
                        moe_aux_weight=train_cfg.moe_aux_weight),
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )
    return step, state, batch_sharding


# ------------------------------------------------------------------ driver


def fit(
    mesh: Mesh,
    model_cfg: gpt2.GPT2Config,
    train_cfg: TrainConfig,
    dataset,                      # train.data.PackedDataset
    *,
    epochs: int = 1,
    checkpoint_path: Optional[str] = None,
    checkpoint_every: int = 50,
    seed: int = 0,
    log_every: int = 10,
) -> Dict[str, Any]:
    """Fine-tune on course data with periodic checkpointing and resume.

    If `checkpoint_path` exists, training RESUMES from it: the full state
    (params, optimizer moments, step) restores through the run's shardings
    and the data order continues from the recorded step, so an interrupted
    run and an uninterrupted one walk the same step sequence.
    Returns the final (host-fetched) metrics + state handle.
    """
    import logging

    from . import checkpoint as ckpt_lib

    log = logging.getLogger("train")
    step_fn, state, batch_sharding = make_sharded_train_step(
        mesh, model_cfg, train_cfg, jax.random.key(seed)
    )
    if checkpoint_path and ckpt_lib.latest_step(checkpoint_path) is not None:
        template = jax.tree.map(np.asarray, jax.device_get(state))
        state = ckpt_lib.restore_train_state(
            checkpoint_path, template,
            shardings=train_state_shardings(template, mesh),
        )
        log.info("resumed from %s at step %d", checkpoint_path,
                 int(jax.device_get(state["step"])))

    start_step = int(jax.device_get(state["step"]))
    steps_per_epoch = dataset.steps_per_epoch()
    metrics_host: Dict[str, float] = {}
    step_no = start_step
    for epoch in range(epochs):
        for i, batch in enumerate(dataset.batches(epoch)):
            # Resume: skip batches the restored run already consumed.
            if epoch * steps_per_epoch + i < start_step:
                continue
            batch = {
                k: jax.device_put(v, batch_sharding[k])
                for k, v in batch.items()
            }
            state, metrics = step_fn(state, batch)
            step_no += 1
            if step_no % log_every == 0 or step_no == start_step + 1:
                metrics_host = {
                    k: float(jax.device_get(v)) for k, v in metrics.items()
                }
                log.info("step %d loss %.4f gnorm %.3f", step_no,
                         metrics_host["loss"], metrics_host["grad_norm"])
            if checkpoint_path and step_no % checkpoint_every == 0:
                ckpt_lib.save_train_state(checkpoint_path, state)
    if checkpoint_path:
        ckpt_lib.save_train_state(checkpoint_path, state)
    if not metrics_host:
        metrics_host = {"loss": float("nan"), "grad_norm": float("nan")}
    return {"state": state, "metrics": metrics_host, "step": step_no}


def main(argv=None) -> None:
    """CLI: fine-tune the tutoring model on course materials.

    python -m distributed_lms_raft_llm_tpu.train.train \
        --data lms_data/node1/uploads --vocab data/gpt2-local/vocab.json \
        --merges data/gpt2-local/merges.txt --model tiny \
        --checkpoint ckpt/train_state.safetensors --epochs 2
    """
    import argparse
    import logging

    from ..models import registry
    from ..parallel import mesh as mesh_lib
    from ..utils import tokenizer as tok_lib
    from . import checkpoint as ckpt_lib
    from .data import DataConfig, PackedDataset

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--data", nargs="+", required=True,
                        help="course-text files/dirs (.txt/.md/.pdf)")
    parser.add_argument("--model", default="gpt2")
    parser.add_argument("--vocab", default=None)
    parser.add_argument("--merges", default=None)
    parser.add_argument("--checkpoint", default=None,
                        help="train-state .safetensors (resume if present)")
    parser.add_argument("--export", default=None,
                        help="write fine-tuned params here when done")
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--sp", type=int, default=1,
                        help="sequence-parallel ways: full-sequence "
                        "attention runs as ring attention over sp shards "
                        "(long-context training)")
    parser.add_argument("--pp", type=int, default=1,
                        help="pipeline stages: the stacked trunk shards "
                        "L/pp layers per device (GPipe microbatching)")
    parser.add_argument("--pp-micro", type=int, default=2,
                        help="microbatches per step when --pp > 1")
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel ways (MoE presets: expert "
                        "stacks shard over ep; aux load-balance loss is "
                        "applied automatically)")
    parser.add_argument("--checkpoint-every", type=int, default=50)
    args = parser.parse_args(argv)
    logging.basicConfig(level=logging.INFO)

    _, model_cfg = registry.resolve(args.model, jnp.bfloat16, jnp.float32)
    if args.ep > 1 and not _is_moe(model_cfg):
        # Before the (potentially minutes-long) corpus tokenization.
        parser.error(
            f"--ep {args.ep} requires an MoE model preset; {args.model!r} "
            f"has no expert axis — the ep chips would silently replicate"
        )
    tokenizer = tok_lib.load_gpt2_tokenizer(args.vocab, args.merges, None)
    dataset = PackedDataset.from_paths(
        args.data, tokenizer,
        DataConfig(batch_size=args.batch_size, seq_len=args.seq_len),
    )
    mesh = mesh_lib.make_mesh(
        {"pp": args.pp, "ep": args.ep, "sp": args.sp, "tp": args.tp,
         "dp": -1}
    )
    steps = args.epochs * dataset.steps_per_epoch()
    train_cfg = TrainConfig(
        learning_rate=args.lr,
        warmup_steps=max(1, steps // 20),
        decay_steps=max(2, steps),
        pp_micro=args.pp_micro,
    )
    result = fit(
        mesh, model_cfg, train_cfg, dataset, epochs=args.epochs,
        checkpoint_path=args.checkpoint,
        checkpoint_every=args.checkpoint_every,
    )
    if args.export:
        ckpt_lib.export_model(args.export, result["state"])
    print(f"trained to step {result['step']}: {result['metrics']}")


if __name__ == "__main__":
    main()
