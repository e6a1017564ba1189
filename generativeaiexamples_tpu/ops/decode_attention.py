"""Pallas TPU kernel: decode-step GQA attention over an int8 KV cache.

The reference's decode attention lives inside the external TRT-LLM/NIM
container (reference: deploy/compose/docker-compose-nim-ms.yaml:2-22,
SURVEY §2.5 "optimized kernels"); here it is an in-repo kernel built for
what actually bounds TPU decode: HBM bandwidth spent re-reading the KV
cache every step. Two levers, both invisible to plain XLA:

- **int8 KV storage.** K/V rows are quantized at write time (symmetric
  per-token-per-head absmax, helpers in models/llama.py) and dequantized
  in VMEM inside the HBM->MXU pipeline, halving cache bytes. XLA cannot
  do this: a dequantize-then-einsum graph materializes the converted
  cache in HBM first (measured slower than the bf16 einsum).
- **per-slot cache windows.** Continuous batching leaves slots at very
  different sequence lengths. The kernel takes each slot's current
  position as a scalar-prefetch operand and clamps its DMA grid to the
  blocks that slot actually occupies — Mosaic skips the re-fetch when
  the clamped block index repeats — so cache traffic tracks each
  sequence's true length instead of the longest one (the einsum path's
  power-of-two window bucket covers the whole batch).

Layout scope: both entry points here read the FIXED per-slot cache
layout (``[B, Hkv, S, Dh]`` dense strips, one per decode slot). The
paged layout (``kv_layout=paged``, docs/paged_kv.md) has its own ragged
kernel — ``ops/page_attention.py``, this module's per-slot clamp made
page-granular: it walks a scalar-prefetched work list of each row's
live PAGES only, with the XLA dequant gather in
models/llama.py ``decode_layers_paged`` as the every-geometry fallback.

Layouts (head-major so each slot streams contiguous rows):
  q   [B, Hkv, G, Dh] bf16      G = query heads per KV head (GQA group)
  k,v [B, Hkv, S, Dh] int8      S = cache capacity, multiple of block_s
  k_scale, v_scale [B, Hkv, 1, S] f32  (unit axis: Mosaic wants the
                                sublane block dim to be %8 or equal to
                                the array dim)
  positions [B] int32           query's absolute position per slot;
                                rows at s <= position are live
Scales fold into the score/prob matrices after the int8->bf16 dots
(score_s = (q . k_s) * k_scale_s; out = sum_s p_s * v_scale_s * v_s), so
the MXU sees bf16 operands (int8 converts exactly) and accumulates f32.

Grid: (B, S blocks) — ALL KV heads of one slot are processed per grid
step (an unrolled loop inside the kernel). A (B, Hkv, blocks) grid with
one head per step measures ~6x slower: its 32 KB blocks and [G, Dh]
dots leave each step latency-bound; fusing the head loop amortizes the
per-step cost over 8x the DMA bytes. Softmax running max/sum carried in
VMEM scratch across the innermost (arbitrary) S dimension, as in
ops/flash_attention.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
_NEG_INF = -1e30
# int8 VMEM tiles are (32, 128): S blocks sit on the sublane axis in
# multiples of 32. 256 keeps k+v double-buffered blocks at ~1 MB for
# Hkv=8 while still letting short sequences skip most of the cache.
BLOCK_S = 256


def _kernel(
    pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, block_s: int, ns: int, hkv: int, g: int,
):
    b = pl.program_id(0)
    s = pl.program_id(1)
    p = pos_ref[b]

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Blocks wholly past this slot's position have no live rows. Their DMA
    # was already elided by the clamped index maps; skip their compute.
    @pl.when(s * block_s <= p)
    def _compute():
        hq = hkv * g
        dh = q_ref.shape[-1]
        idx = s * block_s + lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
        live = idx <= p
        # TWO wide MXU dots instead of 2*Hkv skinny per-head dots. The
        # skinny [G, Dh] x [Dh, block_s] dots leave the kernel bound by
        # MXU issue latency (measured ~5x slower); one [Hq, Dh] x
        # [Dh, Hkv*block_s] dot computes every (q head, kv head) pair —
        # Hkv-fold redundant FLOPs, but the MXU is ~99% idle here — and
        # each row's own-head chunk is then selected with cheap
        # lane-masked adds. Same trick for the output: the prob matrix
        # is scattered into a head-block-diagonal [Hq, Hkv*block_s] so
        # ONE dot against the stacked V computes all heads.
        q = q_ref[0].reshape(hq, dh)  # [Hq, Dh] bf16 (leading-dim merge)
        k_cat = kq_ref[0].reshape(hkv * block_s, dh).astype(jnp.bfloat16)
        sc_wide = lax.dot_general(
            q, k_cat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Hq, Hkv*block_s]
        rowhead = lax.broadcasted_iota(jnp.int32, (hq, 1), 0) // g  # [Hq,1]
        sc = jnp.zeros((hq, block_s), jnp.float32)
        for h in range(hkv):
            chunk = sc_wide[:, h * block_s:(h + 1) * block_s]
            sc += jnp.where(rowhead == h, chunk * (ks_ref[0, h] * scale), 0.0)
        sc = jnp.where(live, sc, _NEG_INF)

        m_prev = m_ref[:, :1]  # [Hq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        prob = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(prob, axis=1, keepdims=True),
            l_ref.shape,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv_wide = jnp.concatenate(
            [
                jnp.where(rowhead == h, prob * vs_ref[0, h], 0.0)
                for h in range(hkv)
            ],
            axis=1,
        ).astype(jnp.bfloat16)  # [Hq, Hkv*block_s], block-diagonal by head
        v_cat = vq_ref[0].reshape(hkv * block_s, dh).astype(jnp.bfloat16)
        out = lax.dot_general(
            pv_wide, v_cat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Hq, Dh]
        acc_ref[...] = acc_ref[...] * alpha + out

    @pl.when(s == ns - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # dead slot: all rows masked
        o_ref[0] = (acc_ref[...] / l).reshape(o_ref.shape[1:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(
    q: jax.Array,  # [B, Hq, Dh] bf16 — one query token per slot
    k_q: jax.Array,  # [B, Hkv, S, Dh] int8
    k_s: jax.Array,  # [B, Hkv, 1, S] f32
    v_q: jax.Array,  # [B, Hkv, S, Dh] int8
    v_s: jax.Array,  # [B, Hkv, 1, S] f32
    positions: jax.Array,  # [B] int32
    *,
    block_s: int = BLOCK_S,
    interpret: bool = False,
) -> jax.Array:
    """Attention output [B, Hq, Dh] for one decode step per slot."""
    B, Hq, Dh = q.shape
    Hkv, S = k_q.shape[1], k_q.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    block_s = min(block_s, S)
    ns = S // block_s
    assert S % block_s == 0, (S, block_s)
    scale = 1.0 / math.sqrt(Dh)

    # Query head h attends through KV head h // G (same grouping as the
    # einsum path's reshape in models/llama.py:_attention).
    qg = q.reshape(B, Hkv, G, Dh)
    pos = positions.astype(jnp.int32)

    def last_blk(pos_ref, b):
        # Clamp: dead slots may carry position 0 or stale values.
        return jnp.minimum(pos_ref[b], S - 1) // block_s

    def kv_spec():
        return pl.BlockSpec(
            (1, Hkv, block_s, Dh),
            lambda b, s, p: (b, 0, jnp.minimum(s, last_blk(p, b)), 0),
        )

    def scale_spec():
        return pl.BlockSpec(
            (1, Hkv, 1, block_s),
            lambda b, s, p: (b, 0, 0, jnp.minimum(s, last_blk(p, b))),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, ns),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), lambda b, s, p: (b, 0, 0, 0)),
            kv_spec(),
            scale_spec(),
            kv_spec(),
            scale_spec(),
        ],
        out_specs=pl.BlockSpec((1, Hq, Dh), lambda b, s, p: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, _LANE), jnp.float32),
            pltpu.VMEM((Hq, _LANE), jnp.float32),
            pltpu.VMEM((Hq, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_s=block_s, ns=ns, hkv=Hkv, g=G
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(pos, qg, k_q, k_s, v_q, v_s)
    return out


def decode_attention_xla(
    q: jax.Array,  # [B, T, Hq, Dh]
    k_q: jax.Array,  # [B, Hkv, S, Dh] int8
    k_s: jax.Array,  # [B, Hkv, 1, S] f32
    v_q: jax.Array,
    v_s: jax.Array,
    positions: jax.Array,  # [B, T] int32
    window: int | None = None,
) -> jax.Array:
    """XLA path over the same int8 head-major cache (CPU tests, TP meshes,
    T > 1 chunked decode). Dequantizes through registers — no bandwidth
    win, identical numerics contract to the kernel.

    Contract: ``window`` (when given) MUST cover ``max(positions) + 1`` —
    attention reads only the first W cache rows, so an undersized window
    silently drops the newest context rather than erroring (the engine
    guarantees this by bucketing windows up from the max live position;
    tests assert it on concrete values).
    """
    B, T, Hq, Dh = q.shape
    Hkv, S = k_q.shape[1], k_q.shape[2]
    G = Hq // Hkv
    W = min(window or S, S)
    k = k_q[:, :, :W].astype(jnp.float32) * k_s[:, :, 0, :W, None]  # [B,Hkv,W,Dh]
    v = v_q[:, :, :W].astype(jnp.float32) * v_s[:, :, 0, :W, None]
    qg = q.reshape(B, T, Hkv, G, Dh).astype(jnp.float32)
    sc = jnp.einsum("btkgd,bksd->bkgts", qg, k) / math.sqrt(Dh)
    mask = jnp.arange(W, dtype=jnp.int32)[None, None, :] <= positions[:, :, None]
    sc = jnp.where(mask[:, None, None], sc, _NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgts,bksd->btkgd", p, v)
    return out.reshape(B, T, Hq, Dh).astype(q.dtype)


def supported(S: int, head_dim: int, num_heads: int, num_kv_heads: int) -> bool:
    """Whether the Pallas kernel's tiling fits this cache geometry."""
    return (
        head_dim % _LANE == 0
        and S % min(BLOCK_S, S) == 0
        and S % 32 == 0
        and num_heads % num_kv_heads == 0
        # scratch/reshapes assume an [Hq, 128] sublane layout; head counts
        # off the 8-sublane grid would lean on untested Mosaic padding —
        # fall back to the XLA path instead.
        and num_heads % 8 == 0
    )
