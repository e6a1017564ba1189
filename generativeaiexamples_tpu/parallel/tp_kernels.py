"""Pallas serving kernels under tensor-parallel meshes, via shard_map.

The reference's inference plane keeps its optimized kernels at ANY gpu
count — INFERENCE_GPU_COUNT merely widens TRT-LLM's tensor parallelism
(reference: deploy/compose/docker-compose-nim-ms.yaml:20). A pallas_call
is opaque to the GSPMD partitioner, so on a sharded mesh plain jit either
replicates the kernel's operands or (as rounds 1-2 did) falls back to XLA
paths, losing the int8 weight-streaming, flash-prefill, and page-attention
wins exactly on the flagship v5e-8 topology.

This module closes that gap the shard_map way: every kernel runs
per-device on its local Megatron tile, with an explicit ``psum`` over the
``model`` axis where the layout contracts across shards (row-parallel
wo/w_down). The weight tiles come from ops/quant.py's per-shard pack
layout (tp_shards > 1), so each device's NamedSharding slice is itself a
self-contained kernel operand.

Layout contracts (axis names from parallel/mesh.py):
- column-parallel matmul (wq/wk/wv/w_gate/w_up/lm_head): x replicated,
  q/scale sharded on the output axis -> output sharded on the output
  axis; no collective.
- row-parallel matmul (wo/w_down): x sharded on its last (contraction)
  axis, q sharded on rows, scale replicated -> partial products psum'd
  over ``model`` in f32; output replicated.
- flash prefill attention: q/k/v sharded on the head axis; attention is
  head-local under GQA as long as shards divide both head counts.
- ragged page attention: pools sharded on the KV-head axis, queries on
  the query-head axis; page tables and positions replicated.

Only PURE tensor-parallel meshes are served (mesh.size == model axis
size — the serving engine's topology); hybrid data/seq meshes keep the
GSPMD fallback paths. ``TPContext.interpret`` runs the kernels in Pallas
interpret mode so the virtual 8-device CPU mesh (tests, dryrun) executes
the same shard_map code paths as real hardware.
"""
from __future__ import annotations

import dataclasses
import math

import jax
from jax.sharding import Mesh, PartitionSpec as P

from generativeaiexamples_tpu.ops import (
    flash_attention,
    int8_matmul,
    page_attention,
)
from generativeaiexamples_tpu.parallel.mesh import MODEL_AXIS, shard_map


@dataclasses.dataclass(frozen=True)
class TPContext:
    """Everything the model functions need to run kernels under TP."""

    mesh: Mesh
    shards: int  # size of the model axis
    interpret: bool = False  # CPU/virtual meshes: Pallas interpret mode


def supports_model_config(cfg, shards: int) -> bool:
    """Whether every sharded projection axis divides evenly: the head
    counts (column packs align shards with heads), the MLP width, and
    the vocab (lm_head columns)."""
    return (
        shards > 1
        and cfg.num_heads % shards == 0
        and cfg.num_kv_heads % shards == 0
        and cfg.intermediate_size % shards == 0
        and cfg.vocab_size % shards == 0
    )


def _local_packed_matmul(x, q, scale, interpret: bool, w8a8: bool = False):
    """Per-device tile matmul: Pallas kernel for decode-shaped calls,
    local XLA dequant otherwise (prefill is compute-bound; the kernel's
    win is weight streaming). Shapes here are LOCAL (one shard's tile),
    so the same M/geometry policy as ops/int8_matmul.packed_matmul
    applies per device. ``w8a8`` routes to the int8-MXU kernels with
    per-token activation quant — the same dispatch the single-device
    packed_matmul makes for quantization='w8a8' (the configured mode
    previously fell back silently to weight-only semantics under TP)."""
    M = math.prod(x.shape[:-1])
    use_kernel = (
        (interpret or jax.default_backend() == "tpu")
        and M <= int8_matmul.M_MAX
        and int8_matmul.kernel_supported(q)
    )
    if use_kernel:
        if w8a8:
            return int8_matmul.int8_w8a8_matmul(x, q, scale, interpret=interpret)
        return int8_matmul.int8_matmul(x, q, scale, interpret=interpret)
    if w8a8:
        return int8_matmul.int8_matmul_xla_w8a8(x, q, scale)
    return int8_matmul.int8_matmul_xla(x, q, scale)


def packed_matmul_tp(x, packed, tp: TPContext, kind: str, w8a8: bool = False):
    """x @ per-shard-packed int8 weight over the model axis.

    ``kind`` is the Megatron role of this projection (ops/quant.py
    PACK_KINDS): "column" shards the output features, "row" shards the
    contraction axis and reduces with an f32 psum (matching the f32
    accumulation inside the kernel/XLA dot, so TP=1 vs TP=N differ only
    by the one bf16 rounding at the reduce). ``w8a8`` selects the
    dequant-free int8-MXU local tiles (engine quantization='w8a8') —
    note the TP=1-vs-TP=N equivalence above does NOT hold for w8a8
    row-kind: per-token activation absmax is computed on each shard's
    local K-slice, so outputs differ from TP=1 by activation-quant
    error, not just the reduce rounding.
    """
    q, scale = packed["q"], packed["scale"]
    nd = x.ndim
    if kind == "column":
        in_specs = (
            P(*([None] * nd)),
            P(None, MODEL_AXIS),
            P(None, MODEL_AXIS),
        )
        out_specs = P(*([None] * (nd - 1)), MODEL_AXIS)

        def body(xl, ql, sl):
            return _local_packed_matmul(xl, ql, sl, tp.interpret, w8a8)

    elif kind == "row":
        in_specs = (
            P(*([None] * (nd - 1)), MODEL_AXIS),
            P(MODEL_AXIS, None),
            P(None, None),
        )
        out_specs = P(*([None] * nd))

        def body(xl, ql, sl):
            y = _local_packed_matmul(xl, ql, sl, tp.interpret, w8a8)
            return jax.lax.psum(y.astype(jax.numpy.float32), MODEL_AXIS).astype(
                y.dtype
            )

    else:
        raise ValueError(f"kind must be 'column' or 'row', got {kind!r}")
    return shard_map(
        body, mesh=tp.mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )(x, q, scale)


def flash_supported(cfg, shards: int, T: int) -> bool:
    """Whether the flash prefill kernel can run head-sharded: shards
    divide both head counts (GQA stays local) and the kernel's own
    tiling accepts the shape."""
    return (
        cfg.num_heads % shards == 0
        and cfg.num_kv_heads % shards == 0
        and flash_attention.supported(T, cfg.head_dim)
    )


def flash_attention_tp(q, k, v, tp: TPContext):
    """Causal flash prefill with the head axis sharded over ``model``.

    q [B, T, Hq, D], k/v [B, T, Hkv, D] — each device runs the kernel on
    its Hq/shards query heads against its Hkv/shards KV heads; GQA
    grouping is preserved because column-parallel QKV shards align with
    head boundaries (ops/quant.py pack layout). No collective: attention
    mixes only the sequence axis, which stays local.
    """
    spec = P(None, None, MODEL_AXIS, None)

    def body(ql, kl, vl):
        return flash_attention.flash_attention_causal(
            ql, kl, vl, interpret=tp.interpret
        )

    return shard_map(
        body, mesh=tp.mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def paged_attention_tp(
    q, k, v, tables, positions, k_scale=None, v_scale=None,
    *, tp: TPContext, interpret: bool = False, work=None,
):
    """Ragged page-attention with the head axis sharded over ``model``.

    q [B, T, Hq, Dh]; pools token-major [P, page, Hkv, Dh] (bf16/int8;
    uint8 [P, page, Hkv, Dh//2] for packed int4) with optional
    page-granular scales, TOKEN-MAJOR [P, page, Hkv] (a head-sharded
    pool never stores them lane-dense) — exactly the axes
    parallel/sharding.kv_pool_specs pins to ``model``, so each device's
    NamedSharding slice is a self-contained pool for its own KV heads.
    Page tables and positions replicate (scalar-prefetched inside the
    kernel); attention is head-local under GQA, so no collective. The
    engine gates this path through
    ``page_attention.supports_geometry(..., shards=tp.shards)`` — each
    device runs the ordinary kernel on its local head tile.

    ``interpret`` is threaded separately from ``tp.interpret`` so the
    engine's ``paged_kernel=interpret`` override reaches the kernel the
    same way it does on a single device. ``work`` is the caller's
    ``page_attention.page_work_list`` (built here when absent).
    """
    hspec = P(None, None, MODEL_AXIS, None)
    sspec = P(None, None, MODEL_AXIS)
    run_interpret = interpret or tp.interpret
    if work is None:
        work = page_attention.page_work_list(
            tables, positions, q.shape[1], k.shape[1],
            page_attention.pages_per_step(k, k_scale, q.shape[1]),
        )
    # the work list replicates with the tables: every device walks the
    # same (row, page) items over its own heads
    rep = page_attention.PageWork(P(None), P(None), P(None), P(None))
    scales = () if k_scale is None else (k_scale, v_scale)

    def body(ql, kl, vl, tbl, posl, wl, *sl):
        return page_attention.paged_attention(
            ql, kl, vl, tbl, posl, *sl, interpret=run_interpret, work=wl
        )

    return shard_map(
        body, mesh=tp.mesh,
        in_specs=(hspec, hspec, hspec, P(None, None), P(None), rep)
        + (sspec,) * len(scales),
        out_specs=hspec, check_vma=False,
    )(q, k, v, tables, positions, work, *scales)
