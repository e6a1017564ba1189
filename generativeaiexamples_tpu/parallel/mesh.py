"""Device-mesh construction for the TPU engine.

The reference expresses multi-accelerator scale as a container count
(INFERENCE_GPU_COUNT handed to NIM, reference: deploy/compose/
docker-compose-nim-ms.yaml:20) with NCCL hidden inside. Here the mesh is
explicit: axes ``data`` (batch/DP, DCN-friendly), ``seq`` (sequence/context
parallelism for long inputs) and ``model`` (tensor parallelism over ICI).
XLA lowers collectives onto ICI links from shardings alone.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"


def create_mesh(
    tensor_parallelism: int = -1,
    data_parallelism: int = 1,
    seq_parallelism: int = 1,
    pipe_parallelism: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (pipe, data, seq, model) mesh from the available devices.

    ``tensor_parallelism=-1`` takes every device not consumed by the other
    axes — the TPU analogue of NIM's INFERENCE_GPU_COUNT=all. ``model`` is
    the innermost axis so TP collectives ride adjacent ICI links; ``pipe``
    is outermost (stage hops are point-to-point, DCN-tolerant — the
    Megatron ordering the reference inherits via NeMo's
    pipeline_model_parallel, SURVEY §2.6).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    other = data_parallelism * seq_parallelism * pipe_parallelism
    if tensor_parallelism == -1:
        if n % other:
            raise ValueError(
                f"{n} devices not divisible by pipe={pipe_parallelism} * "
                f"data={data_parallelism} * seq={seq_parallelism}"
            )
        tensor_parallelism = n // other
    total = other * tensor_parallelism
    if total > n:
        raise ValueError(f"Mesh wants {total} devices; only {n} available")
    grid = np.array(devices[:total]).reshape(
        pipe_parallelism, data_parallelism, seq_parallelism, tensor_parallelism
    )
    return Mesh(grid, (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS))


def single_device_mesh() -> Mesh:
    return create_mesh(tensor_parallelism=1)


def tier_submeshes(mesh: Mesh) -> tuple:
    """(prefill, decode) tier meshes for P/D disaggregation
    (engine/scheduler/disagg.py, docs/scheduler.md).

    A single-device mesh — the CPU-testable topology — returns the
    serving mesh twice: both tiers share the device, and with it the
    KV page pool, which is exactly what makes the same-host handoff a
    zero-copy ownership transfer. A multi-device mesh splits the
    device list in half along the flattened order (prefill tier first,
    decode tier second), preserving the axis names with the inner axes
    collapsed — the TOPOLOGY PLAN the disagg policy records and
    reports. Executing the tiers on disjoint devices additionally
    needs the cross-pool page transport (ROADMAP item 3's KV fabric);
    until that lands, dispatch runs on the serving mesh and the split
    is advisory placement metadata.
    """
    if mesh.size < 2:
        return mesh, mesh
    flat = mesh.devices.reshape(-1)
    half = mesh.size // 2
    names = mesh.axis_names
    shape = (1,) * (len(names) - 1) + (half,)
    prefill = Mesh(np.array(flat[:half]).reshape(shape), names)
    decode = Mesh(np.array(flat[half:2 * half]).reshape(shape), names)
    return prefill, decode


def shard_map(f, mesh: Mesh, in_specs, out_specs, check_vma=None):
    """``jax.shard_map`` with the mesh passed by keyword and the
    replication check left at jax's default unless stated."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


def mesh_context(mesh: Mesh):
    """Mesh scope for sharded construction and dispatch."""
    return jax.set_mesh(mesh)
