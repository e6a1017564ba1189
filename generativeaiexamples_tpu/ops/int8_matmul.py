"""Pallas TPU kernel: bf16 activations x int8 weights, weight-streaming.

Decode throughput on TPU is bound by streaming the weights from HBM every
step (the MXU is idle most of the time at serving batch sizes). Plain XLA
cannot exploit int8 storage for a bf16 matmul — it materializes the
converted bf16 matrix in HBM first, so the traffic halving is lost (the
reference gets the same effect from TRT-LLM's int8 weight-only CUDA
kernels; SURVEY §2.5). This kernel converts int8 -> bf16 in VMEM, inside
the HBM->MXU pipeline, so weight bytes over HBM are actually halved:

    y[M, F] = (x[M, K] @ convert_bf16(q[K, F])) * scale[1, F]

Scope: the DECODE shape class only (M <= M_MAX = 128 rows — every
serving slot count; rows pad to the next 32-sublane block). Large-M
calls (prefill) are compute-bound, not weight-streaming-bound, and go
through the XLA dequant path — which also avoids VMEM pressure from big
activation tiles. Large K (llama-8b w_down is 14336, 70B is 28672) is
handled by a K-blocked accumulation grid so the VMEM working set stays
at ~2 x (K_BLK x F_BLK) int8 regardless of model size.

Grid: (F tiles, K tiles) with K innermost — each weight block streams
exactly once per call; the single <=128-row activation tile stays
resident (at M=128, K_BLK=8192 the x tile is 2 MB bf16).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# F tile: multiple of the 128-lane dim. A weight tile's DMA burst length
# is F_BLK bytes (int8 rows of a [K, F] array are strided by F), so
# larger tiles read longer contiguous spans per row; env-tunable for
# on-chip A/B (quant.py pads packs to this value, same process-wide
# constant).
F_BLK = int(os.environ.get("GENAI_TPU_INT8_F_BLK", "512"))
if F_BLK <= 0 or F_BLK % 128:
    raise ValueError(
        f"GENAI_TPU_INT8_F_BLK must be a positive multiple of 128, got {F_BLK}"
    )
# K is padded (at pack time) to a multiple of 128 so a K-blocking factor
# with 32-aligned blocks always exists for common model dims.
K_ALIGN = 128
# Largest K block held in VMEM, derived from a ~4 MB weight-tile budget
# (x2 double buffering + the x tile stays inside v5e's ~16 MB VMEM).
# Hard-capped at 8192 regardless of F_BLK: the x tile scales with the K
# block (M=128 rows x K_BLK bf16 = 2 MB at 8192) and would blow VMEM if
# a small F tile let the K block grow. F_BLK=512 -> 8192 (tuned default).
MAX_K_BLK = min(8192, max(128, (4 * 1024 * 1024 // F_BLK) // 128 * 128))
# The kernel serves decode batches only; M is padded up to the next
# multiple of the int8/bf16-safe 32-row sublane block. 128 covers every
# serving slot count in use (the engine decodes all slots each step);
# measured on v5e: the kernel beats the XLA fused-dequant path at M=64
# (+3% engine throughput) and M=96 (BASELINE.md round 2).
try:
    M_MAX = int(os.environ.get("GENAI_TPU_INT8_M_MAX", "128"))
except ValueError:
    raise ValueError(
        "GENAI_TPU_INT8_M_MAX must be an integer (number of activation "
        f"rows), got {os.environ['GENAI_TPU_INT8_M_MAX']!r}"
    ) from None
if M_MAX <= 0:
    # Any positive value works — M_MAX is only the kernel-vs-XLA dispatch
    # threshold; rows pad to the 32-row sublane block per call regardless.
    raise ValueError(f"GENAI_TPU_INT8_M_MAX must be positive, got {M_MAX}")
_M_PAD = 32


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    w = q_ref[:].astype(jnp.bfloat16)  # int8 -> bf16 in VMEM
    acc_ref[:] += jnp.dot(x_ref[:], w, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        o_ref[:] = (acc_ref[:] * s_ref[:]).astype(o_ref.dtype)


def _k_block(k_pad: int) -> int:
    """A blocking of k_pad under MAX_K_BLK (0 = impossible).

    Blocks must be multiples of 128: a K block is the LAST axis of the x
    tile (lane dim, %128) as well as the sublane axis of the int8 w tile
    (%32) — Mosaic rejects anything smaller unless it equals the full
    array dim."""
    if k_pad <= MAX_K_BLK:
        return k_pad
    for n in range(2, 129):
        blk, rem = divmod(k_pad, n)
        if rem == 0 and blk % 128 == 0 and blk <= MAX_K_BLK:
            return blk
    return 0


def _mm_compiler_params():
    """F tiles are independent ("parallel"); K accumulates ("arbitrary").
    Declaring this lets Mosaic overlap the next tile's DMA with the
    current tile's MXU work across the whole grid (the flash kernel
    already does; env-gated for on-chip A/B)."""
    if os.environ.get("GENAI_TPU_INT8_NO_SEMANTICS", "").lower() in ("1", "true"):
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("out_features", "interpret"))
def _call(x, q, scale, out_features: int, interpret: bool):
    M, K_pad = x.shape
    Fp = q.shape[1]
    k_blk = _k_block(K_pad)
    grid = (Fp // F_BLK, K_pad // k_blk)
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((M, Fp), jnp.bfloat16),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((M, k_blk), lambda j, k: (0, k), memory_space=pltpu.VMEM),
                pl.BlockSpec((k_blk, F_BLK), lambda j, k: (k, j), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, F_BLK), lambda j, k: (0, j), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (M, F_BLK), lambda j, k: (0, j), memory_space=pltpu.VMEM
            ),
            scratch_shapes=[pltpu.VMEM((M, F_BLK), jnp.float32)],
        ),
        compiler_params=_mm_compiler_params(),
        interpret=interpret,
    )(x, q, scale)
    return out[:, :out_features]


def int8_matmul(
    x: jax.Array,  # [..., K] bf16 activations, M = prod(leading) <= M_MAX
    q: jax.Array,  # [K_pad, F_pad] int8 weights (pre-padded at pack time)
    scale: jax.Array,  # [1, F] float32 per-output-channel scales (logical F)
    interpret: bool = False,
) -> jax.Array:
    """y = (x @ dequant(q))[..., :F]; leading dims preserved."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    F = scale.shape[-1]
    Fp = q.shape[1]
    x2 = x.reshape(-1, K).astype(jnp.bfloat16)
    M = x2.shape[0]
    if M > M_MAX:
        raise ValueError(
            f"int8_matmul serves decode-shaped calls only (M={M} > {M_MAX}); "
            "use int8_matmul_xla (or packed_matmul, which auto-falls back)."
        )
    K_pad = q.shape[0]
    pad_k = K_pad - K
    # pad rows only to the next sublane block, not all the way to M_MAX —
    # padding 33 rows to 128 would 4x the row compute for nothing
    m_pad_to = ((M + _M_PAD - 1) // _M_PAD) * _M_PAD
    pad_m = m_pad_to - M
    if pad_k or pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, pad_k)))
    s = scale if Fp == F else jnp.pad(scale, ((0, 0), (0, Fp - F)))
    y = _call(x2, q, s.astype(jnp.float32), F, interpret)[:M]
    return y.reshape(*lead, F)


def _kernel_w8a8(x_ref, q_ref, s_ref, sx_ref, o_ref, acc_ref):
    """int8 x int8 -> int32 accumulate; scales fold at the last K block.

    The v5e MXU runs int8 at 2x the bf16 rate (394 TOPS vs 197 TFLOPS),
    and at serving batch sizes the packed decode matmuls are jointly
    compute- and bandwidth-bound (BASELINE.md round 3) — int8 issue
    halves the compute half of that bound. Activations arrive already
    quantized per-token (absmax rows, scales in sx)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(
        x_ref[:], q_ref[:], preferred_element_type=jnp.int32
    )

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        o_ref[:] = (
            acc_ref[:].astype(jnp.float32) * sx_ref[:] * s_ref[:]
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_features", "interpret"))
def _call_w8a8(x_q, x_s, q, scale, out_features: int, interpret: bool):
    M, K_pad = x_q.shape
    Fp = q.shape[1]
    k_blk = _k_block(K_pad)
    grid = (Fp // F_BLK, K_pad // k_blk)
    out = pl.pallas_call(
        _kernel_w8a8,
        out_shape=jax.ShapeDtypeStruct((M, Fp), jnp.bfloat16),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((M, k_blk), lambda j, k: (0, k), memory_space=pltpu.VMEM),
                pl.BlockSpec((k_blk, F_BLK), lambda j, k: (k, j), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, F_BLK), lambda j, k: (0, j), memory_space=pltpu.VMEM),
                pl.BlockSpec((M, 1), lambda j, k: (0, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (M, F_BLK), lambda j, k: (0, j), memory_space=pltpu.VMEM
            ),
            scratch_shapes=[pltpu.VMEM((M, F_BLK), jnp.int32)],
        ),
        compiler_params=_mm_compiler_params(),
        interpret=interpret,
    )(x_q, q, scale, x_s)
    return out[:, :out_features]


def quantize_rows(x: jax.Array):
    """Per-row (per-token) symmetric absmax int8: [..., K] ->
    (int8 [..., K], f32 scales [..., 1])."""
    x32 = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x32 / s), -127, 127).astype(jnp.int8)
    return q, s


def int8_w8a8_matmul(
    x: jax.Array,  # [..., K] bf16 activations, quantized per row inside
    q: jax.Array,  # [K_pad, F_pad] int8 weights
    scale: jax.Array,  # [1, F] f32 per-output-channel weight scales
    interpret: bool = False,
) -> jax.Array:
    """y ~= (x @ dequant(q))[..., :F] with int8 MXU issue; leading dims
    preserved. Dynamic per-token activation quantization (the standard
    W8A8 serving recipe) — approximate where the weight-only kernel is
    near-exact; opt-in via EngineConfig.quantization='w8a8'."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    F = scale.shape[-1]
    Fp = q.shape[1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    if M > M_MAX:
        raise ValueError(
            f"int8_w8a8_matmul serves decode-shaped calls only (M={M} > {M_MAX})"
        )
    x_q, x_s = quantize_rows(x2)
    K_pad = q.shape[0]
    m_pad_to = ((M + _M_PAD - 1) // _M_PAD) * _M_PAD
    pad_m, pad_k = m_pad_to - M, K_pad - K
    if pad_k or pad_m:
        x_q = jnp.pad(x_q, ((0, pad_m), (0, pad_k)))
    if pad_m:
        x_s = jnp.pad(x_s, ((0, pad_m), (0, 0)), constant_values=1.0)
    s = scale if Fp == F else jnp.pad(scale, ((0, 0), (0, Fp - F)))
    y = _call_w8a8(x_q, x_s, q, s.astype(jnp.float32), F, interpret)[:M]
    return y.reshape(*lead, F)


def int8_matmul_xla(x, q, scale) -> jax.Array:
    """XLA path (prefill / CPU / tensor-parallel meshes): dequantize to
    bf16 and matmul. No bandwidth win, identical numerics contract."""
    K = x.shape[-1]
    F = scale.shape[-1]
    w = (q[:K, :F].astype(jnp.float32) * scale).astype(jnp.bfloat16)
    return x @ w


def int8_matmul_xla_w8a8(x, q, scale) -> jax.Array:
    """Dequant-FREE XLA path: per-token int8 activation quant + a native
    int8 x int8 -> int32 dot (TPU MXU runs int8 at 2x the bf16 rate).

    Why it exists: the dequant path above materializes the full bf16
    weight matrix in HBM per call — for an 8B prefill WAVE that is ~15 GB
    written and re-read on top of the 7.5 GB int8 read, a mostly-fixed
    multi-second cost that dominated e2e TTFT (BASELINE.md round 3).
    This path reads only the int8 weights. Approximate (per-token
    activation quant), so it serves quantization='w8a8' only.
    """
    K = x.shape[-1]
    F = scale.shape[-1]
    xq, xs = quantize_rows(x)
    M = 1
    for d in x.shape[:-1]:
        M *= d
    # Chunk the output axis so the int32 accumulator never materializes
    # more than ~256 MB at once: a 5x3072-token 8B gate|up wave would
    # otherwise hold a [15360, 28672] i32 temp (1.76 GB) and push a
    # ~90%-occupied serving chip over HBM at compile time (observed:
    # "exceeded hbm capacity by 98.98M" mid-e2e).
    max_elems = 64 * 1024 * 1024
    chunk = max(512, (max_elems // max(M, 1)) // 512 * 512)
    if F <= chunk:
        acc = jax.lax.dot_general(
            xq,
            q[:K, :F],
            (((xq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return (acc.astype(jnp.float32) * xs * scale).astype(jnp.bfloat16)
    outs = []
    for f0 in range(0, F, chunk):
        f1 = min(f0 + chunk, F)
        acc = jax.lax.dot_general(
            xq,
            q[:K, f0:f1],
            (((xq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        outs.append(
            (acc.astype(jnp.float32) * xs * scale[..., f0:f1]).astype(jnp.bfloat16)
        )
    return jnp.concatenate(outs, axis=-1)


def kernel_supported(q: jax.Array) -> bool:
    """Whether the Pallas kernel can serve this packed weight's shapes."""
    return q.shape[1] % F_BLK == 0 and _k_block(q.shape[0]) > 0


def packed_matmul(x, packed, use_pallas: bool | str | None = None) -> jax.Array:
    """Dispatch x @ packed int8 weight to the Pallas kernel or XLA path.

    ``use_pallas``: pass False under tensor-parallel meshes — a
    pallas_call is opaque to the GSPMD partitioner, which would
    replicate the full weight to every device (the engine threads the
    right value per-instance; see llm_engine.__init__). None = auto:
    Pallas only on a single-device TPU backend, where GSPMD has nothing
    to partition, and only for decode-shaped (M <= M_MAX) calls.
    ``"w8a8"``: the int8-MXU kernel with per-token activation
    quantization for decode-shaped calls (weight-only kernel semantics
    for everything else). ``"w8a8_xla"``: w8a8 semantics with the
    Pallas kernel disabled — every call takes int8_matmul_xla_w8a8, so
    quantization='w8a8' keeps its numerics contract on backends with no
    Pallas path (CPU tests, interpret-free debugging) instead of
    silently downgrading to weight-only.
    """
    if use_pallas == "w8a8_xla":
        return int8_matmul_xla_w8a8(x, packed["q"], packed["scale"])
    M = 1
    for d in x.shape[:-1]:
        M *= d
    w8a8 = use_pallas == "w8a8"
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and jax.device_count() == 1
    if use_pallas and M <= M_MAX and kernel_supported(packed["q"]):
        if w8a8:
            return int8_w8a8_matmul(x, packed["q"], packed["scale"])
        return int8_matmul(x, packed["q"], packed["scale"])
    if w8a8:
        # prefill-shaped w8a8: the dequant-free int8-dot XLA path
        return int8_matmul_xla_w8a8(x, packed["q"], packed["scale"])
    return int8_matmul_xla(x, packed["q"], packed["scale"])
