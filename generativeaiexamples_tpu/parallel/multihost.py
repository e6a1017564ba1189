"""Multi-host (multi-slice / multi-process) mesh construction.

The reference's multi-accelerator story is NCCL hidden inside the NIM
container plus a load balancer across replicas (SURVEY §2.6). The TPU
equivalent is explicit: within a slice, collectives ride ICI; across
hosts/slices they ride DCN. This module owns that boundary:

- ``initialize_distributed()`` brings up the JAX coordination service
  from env vars (the standard GKE/TPU-VM contract:
  ``COORDINATOR_ADDRESS``, ``NUM_PROCESSES``, ``PROCESS_ID``) so every
  host sees the global device set;
- ``create_hybrid_mesh()`` builds a (pipe, data, seq, model) mesh where
  the DCN-spanning axes are outermost (data/pipe — infrequent, large
  messages tolerate DCN latency) and the ICI axes innermost (model/seq —
  latency-critical allreduce/allgather), via
  ``mesh_utils.create_hybrid_device_mesh``;
- single-process fallbacks so every entry point works unchanged on one
  host (the common dev loop) — distribution is configuration, not code.

Serving (engine/llm_engine.py) and training (models/train.py,
tools/finetune.py) accept any mesh these helpers return.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np
from jax.sharding import Mesh

from generativeaiexamples_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
)
from generativeaiexamples_tpu.utils import get_logger

logger = get_logger(__name__)

_AXES = (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS)


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Start the JAX distributed runtime if configured; returns whether
    multi-process mode is active.

    Reads the standard env contract when args are omitted:
    COORDINATOR_ADDRESS (host:port), NUM_PROCESSES, PROCESS_ID. With no
    configuration it's a no-op (single-process), so the same entry point
    serves laptops and pods.
    """
    import jax

    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if coordinator_address is None:
        return False
    num_processes = int(num_processes or os.environ.get("NUM_PROCESSES", "1"))
    process_id = int(process_id if process_id is not None else os.environ.get("PROCESS_ID", "0"))
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    logger.info(
        "Distributed runtime up: process %d/%d, %d global devices",
        process_id, num_processes, jax.device_count(),
    )
    return num_processes > 1


def create_hybrid_mesh(
    dcn_data_parallelism: int = -1,
    dcn_pipe_parallelism: int = 1,
    ici_tensor_parallelism: int = -1,
    ici_seq_parallelism: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """(pipe, data, seq, model) mesh with DCN axes outer, ICI axes inner.

    ``dcn_data_parallelism=-1`` uses one data replica per slice (process
    granule); ``ici_tensor_parallelism=-1`` consumes each slice's
    remaining chips. On a single host this degrades to the plain local
    mesh, keeping every caller host-count agnostic.
    """
    import jax
    from jax.experimental import mesh_utils

    devices = list(devices if devices is not None else jax.devices())
    num_slices = getattr(devices[0], "num_slices", None) or max(
        1, jax.process_count() if devices is jax.devices() else 1
    )
    # Fall back to process count as the DCN granule.
    num_granules = max(1, jax.process_count())
    per_granule = len(devices) // num_granules

    if dcn_data_parallelism == -1:
        dcn_data_parallelism = num_granules // dcn_pipe_parallelism
    if ici_tensor_parallelism == -1:
        ici_tensor_parallelism = per_granule // ici_seq_parallelism

    dcn_shape = (dcn_pipe_parallelism, dcn_data_parallelism, 1, 1)
    ici_shape = (1, 1, ici_seq_parallelism, ici_tensor_parallelism)

    if num_granules == 1:
        # single host: no DCN dimension; plain device mesh
        grid = mesh_utils.create_device_mesh(
            [a * b for a, b in zip(dcn_shape, ici_shape)], devices=devices
        )
    else:
        grid = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices
        )
    return Mesh(np.asarray(grid), _AXES)


def local_batch_slice(global_batch: int, mesh: Mesh) -> int:
    """Per-process batch share for data loading (DCN data sharding)."""
    import jax

    data = mesh.shape[DATA_AXIS] * mesh.shape[PIPE_AXIS]
    if global_batch % data:
        raise ValueError(f"global batch {global_batch} not divisible by {data}")
    return global_batch // max(1, jax.process_count())
