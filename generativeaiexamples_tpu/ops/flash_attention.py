"""Pallas TPU flash attention (causal, GQA) for the prefill hot path.

The reference delegates attention to the external TRT-LLM/NIM container
(reference: deploy/compose/docker-compose-nim-ms.yaml:2-22); here the
prefill attention runs as an in-repo Pallas kernel so the T×T score
matrix never materializes in HBM:

- grid (batch, q_heads, q_blocks, k_blocks), k innermost ("arbitrary"
  semantics) with the classic flash running max/sum rescaling held in
  f32 VMEM scratch across k iterations;
- GQA without materializing repeated K/V: the k/v BlockSpec index map
  sends query head ``h`` to kv head ``h // group``;
- causal masking from global block indices (prefill positions are
  ``arange``), so no position operands; k blocks entirely above the
  diagonal skip their compute via ``pl.when``;
- scores/accumulator in float32 (MXU with ``preferred_element_type``),
  inputs/outputs bfloat16.

Falls back to the einsum path (models/llama.py:_attention) for shapes the
MXU tiling doesn't like (head_dim not a lane multiple) or on CPU, where
``interpret=True`` keeps tests runnable.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANE = 128
_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, block_q, block_k, nk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Blocks fully above the causal diagonal contribute nothing.
    @pl.when(ik * block_k <= iq * block_q + (block_q - 1))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [Bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [Bk, D]
        v = v_ref[0, 0].astype(jnp.float32)  # [Bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [Bq, Bk]

        q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)

        m_prev = m_ref[:, :1]  # [Bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)  # [Bq, 1]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked (padding) rows
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def flash_attention_causal(
    q: jax.Array,  # [B, T, Hq, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Causal self-attention over T new tokens; returns [B, T, Hq, D]."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    block_q = min(block_q, _ceil_to(T, 8))
    block_k = min(block_k, _ceil_to(T, 8))
    # Both block sizes must divide the padded length or the grid silently
    # drops trailing blocks.
    Tp = _ceil_to(T, math.lcm(block_q, block_k))

    # [B, H, T, D] layout so the last two dims tile (sublane, lane).
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if Tp != T:
        pad = ((0, 0), (0, 0), (0, Tp - T), (0, 0))
        qt, kt, vt = jnp.pad(qt, pad), jnp.pad(kt, pad), jnp.pad(vt, pad)

    nq, nk = Tp // block_q, Tp // block_k
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_q=block_q, block_k=block_k, nk=nk
        ),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, D), q.dtype),
        scratch_shapes=[
            _vmem((block_q, _LANE)),
            _vmem((block_q, _LANE)),
            _vmem((block_q, D)),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out[:, :, :T, :], 1, 2)


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
    )


def supported(T: int, D: int) -> bool:
    """True when the kernel's tiling applies (lane-sized head_dim)."""
    return D % _LANE == 0 and T >= 2


def preferred(T: int, D: int) -> bool:
    """Whether the flash kernel should serve this prefill shape: capable
    AND profitable. Short prompts favor the einsum path — the T x T
    score matrix stays small while the kernel pays (batch x heads)
    grid-step overhead ([96,128] waves measure ~13% slower under flash);
    the kernel earns its keep once T*T scores would spill to HBM.
    Single policy site for models/llama.py's prefill paths. Pallas calls
    are opaque to GSPMD: callers running under a sharded mesh must pass
    use_flash=False explicitly (the engine does, from its mesh size —
    a single-device mesh on a multi-chip host keeps the kernel).
    ``GENAI_TPU_FLASH_MIN_T`` overrides the crossover for tuning."""
    import os

    min_t = int(os.environ.get("GENAI_TPU_FLASH_MIN_T", "512"))
    return (
        jax.default_backend() == "tpu" and supported(T, D) and T >= min_t
    )
