"""Device-mesh construction for SPMD sharding.

The reference has no compute parallelism (SURVEY.md §2.2) — its distribution
is Raft replication over gRPC. Here the TPU compute plane scales the JAX way:
a `jax.sharding.Mesh` over the local chips with named axes, `NamedSharding`
partition specs on parameter/cache pytrees, and XLA-inserted collectives over
ICI. Axes used across the framework:

- ``dp`` — data parallel (batch of concurrent student queries)
- ``tp`` — tensor parallel (weight shards; the BASELINE GPT-2-large/8-chip
  and Llama-3-8B/16-chip configs)
- ``sp`` — sequence/context parallel (ring attention for long context)
- ``pp`` — pipeline stages (train-time; optional)
- ``ep`` — expert parallel (MoE expert shards; models/moe.py)
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def make_mesh(
    axis_sizes: Optional[dict] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_order: Tuple[str, ...] = ("dp", "pp", "ep", "sp", "tp"),
) -> Mesh:
    """Build a mesh over the given (default: all local) devices.

    axis_sizes maps axis name -> size; at most one axis may be -1 (inferred).
    Axes not mentioned get size 1. `tp` is placed innermost (fastest-varying)
    so tensor-parallel collectives ride the shortest ICI hops.

    >>> make_mesh({"dp": 2, "tp": 4})  # 8 devices: 2-way data, 4-way tensor
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = dict(axis_sizes or {})
    unknown = [a for a in sizes if a not in axis_order]
    if unknown:
        raise ValueError(f"unknown mesh axes {unknown}; expected {axis_order}")
    infer = [a for a, s in sizes.items() if s == -1]
    if len(infer) > 1:
        raise ValueError("at most one axis size may be -1")
    known = math.prod(s for s in sizes.values() if s != -1)
    if infer:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[infer[0]] = n // known
    elif known != n:
        # Default: put the remainder on dp if unset, else require exact fit.
        if "dp" not in sizes and n % known == 0:
            sizes["dp"] = n // known
        else:
            raise ValueError(f"axis sizes {sizes} do not multiply to {n} devices")
    shape = [sizes.get(a, 1) for a in axis_order]
    mesh_devices = np.asarray(devices).reshape(shape)
    return Mesh(mesh_devices, axis_order)


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join a multi-host JAX cluster; no-op for single-process runs.

    Multi-host is the scale-out story the reference reaches with one gRPC
    process per machine (SURVEY.md §2.2 — no collective backend at all):
    here each host runs one process, `jax.distributed.initialize` wires the
    cross-host runtime, and `jax.devices()` becomes the GLOBAL device set so
    the same `make_mesh`/`make_hybrid_mesh` + NamedSharding code drives
    1 chip or a pod slice. Arguments fall back to JAX's standard environment
    (JAX_COORDINATOR_ADDRESS / ..NUM_PROCESSES / ..PROCESS_ID, or the TPU
    metadata on Cloud TPU VMs). Returns True if distributed mode was
    initialized.
    """
    import os

    configured = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if not configured and (num_processes in (None, 1)):
        return False  # single-process: local devices only
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def make_hybrid_mesh(
    ici_axis_sizes: dict,
    dcn_axis_sizes: Optional[dict] = None,
    *,
    axis_order: Tuple[str, ...] = ("dp", "pp", "ep", "sp", "tp"),
) -> Mesh:
    """DCN × ICI hybrid mesh for multi-host topologies.

    `dcn_axis_sizes` are the axes that SPAN HOSTS (usually just dp: the
    gradient all-reduce and request batch tolerate DCN latency), and
    `ici_axis_sizes` the within-host axes (tp/sp/pp want ICI bandwidth).
    Device order comes from `mesh_utils.create_hybrid_device_mesh`, which
    keeps each host's chips contiguous on the ICI axes. With a single
    process (all dcn sizes 1) this degrades to `make_mesh` semantics, so
    the code path is exercised by the CPU test mesh too.
    """
    from jax.experimental import mesh_utils

    dcn_axis_sizes = dict(dcn_axis_sizes or {})
    unknown = [
        a for a in (*ici_axis_sizes, *dcn_axis_sizes) if a not in axis_order
    ]
    if unknown:
        raise ValueError(f"unknown mesh axes {unknown}; expected {axis_order}")
    ici = [ici_axis_sizes.get(a, 1) for a in axis_order]
    dcn = [dcn_axis_sizes.get(a, 1) for a in axis_order]
    if math.prod(dcn) == 1:
        # Single-granule: identical to a flat local mesh.
        sizes = {
            a: ici_axis_sizes.get(a, 1) * dcn_axis_sizes.get(a, 1)
            for a in axis_order
        }
        return make_mesh(sizes, axis_order=axis_order)
    devices = mesh_utils.create_hybrid_device_mesh(
        ici, dcn, devices=jax.devices()
    )
    return Mesh(devices, axis_order)


def device_info() -> dict:
    """What this process computes on, as JAX reports it (initializes the
    backend): the fields every entry point logs at start-up and the
    serving /healthz repeats, so no run is ever silently a CPU run."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def device_memory() -> dict:
    """Live allocator figures of the first local device (bytes), or {}
    where the backend reports none (the CPU)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return {k: stats[k] for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}


def single_device_mesh() -> Mesh:
    """Trivial mesh (1 chip) — lets the same pjit code path serve everywhere."""
    return make_mesh({})


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))
