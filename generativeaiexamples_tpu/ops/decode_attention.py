"""XLA decode attention over an int8 head-major KV cache.

The dense per-slot int8 layout (``[B, Hkv, S, Dh]`` strips with
``[B, Hkv, 1, S]`` per-token-per-head scales, models/llama.py
``init_kv_cache_layers(quantized=True)``) is the resident DRAFT model's
private cache (engine/spec_draft.py); the serving engine's own K/V live
in the page pool and are read by ``ops/page_attention.py`` or the XLA
gather in models/llama.py. This plain function is also the reference
the page kernel is checked against (chip_smoke.py, tests).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def decode_attention_xla(
    q: jax.Array,  # [B, T, Hq, Dh]
    k_q: jax.Array,  # [B, Hkv, S, Dh] int8
    k_s: jax.Array,  # [B, Hkv, 1, S] f32
    v_q: jax.Array,
    v_s: jax.Array,
    positions: jax.Array,  # [B, T] int32
    window: int | None = None,
) -> jax.Array:
    """Attention of T query rows a slot over an int8 head-major cache,
    dequantized through registers (scales fold in after the int8 ->
    float converts, which are exact).

    Contract: ``window`` (when given) MUST cover ``max(positions) + 1`` —
    attention reads only the first W cache rows, so an undersized window
    silently drops the newest context rather than erroring (callers
    bucket windows up from the max live position).
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
