"""Pallas TPU kernel: decode attention over a head-less latent page pool.

A latent-attention layer served absorbed caches ONE row a token, the
compressed latent ``c`` (``[P, page, R]``: no head axis), and every
query head reads that same row twice: as its key (``qlat_h . c_s``) and
as its value (``sum_s p_s c_s``). So a page is fetched ONCE a step and
feeds both products for all heads: multi-query attention whose K and V
are the same bytes.

Which cached tokens a query may read is decided outside (a learned
indexer's top-k groups plus the open tail, models/glm5next.py) and
arrives as an additive float32 ``bias`` ``[B * Pmax, 1, page]`` (0 or
-1e30; causality is folded in). The walk itself is
``ops/page_attention.py``'s: the flat work list of live (row, page)
pairs (``page_work_list``), running softmax state in VMEM scratch,
reset at a row's first page and normalised into the row's output at its
last. At the contexts one chip serves (<= 8k) reading every live page
and masking costs less than a gather of two thousand 4 KB slabs a row:
the selection saves score columns here, not page fetches (PERF.md).

``dense_latent_attention`` is the second entry point over the same walk,
for a latent layer that carries a decoupled RoPE key and selects nothing
(models/gigachat35.py): the cached row is ``[c | k_rope | padding]``,
WIDER than the value, which is its first ``value_dim`` columns; every
cached token up to the query's own position is read, so causality comes
from the positions the kernel already prefetches and no bias is built.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.ops.page_attention import PageWork, page_work_list

_LANE = 128
_NEG_INF = -1e30


def _kernel(row_ref, page_ref, phys_ref, pos_ref, q_ref, c_ref, b_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, page: int):
    del phys_ref
    i = pl.program_id(0)
    j = page_ref[i]
    last_tok = pos_ref[row_ref[i]]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[0]  # [page, R]: key and value
    sc = lax.dot_general(q_ref[0], c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    sc = sc * scale + b_ref[0]  # [H, page] + [1, page]
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    # a page with nothing selected leaves m at -1e30: exp(sc - m) would be 1
    prob = jnp.where(sc > 0.5 * _NEG_INF, jnp.exp(sc - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(alpha * l_ref[:, :1] + jnp.sum(prob, axis=1, keepdims=True), l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(prob.astype(c.dtype), c, preferred_element_type=jnp.float32)

    @pl.when((j + 1) * page > last_tok)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_attention(q, pool, bias, tables, positions, *, scale: float, interpret: bool = False,
                     work: Optional[PageWork] = None):
    """q [B, H, R] (absorbed queries, the pool's dtype), pool [P, page, R],
    bias [B, Pmax * page] float32, tables [B, Pmax], positions [B]
    (the query's own position; its row is already in the pool).
    Returns sum_s softmax_s(q . c_s * scale + bias_s) c_s: [B, H, R] float32."""
    B, H, R = q.shape
    P, page, _ = pool.shape
    Pmax = tables.shape[1]
    pos = positions.astype(jnp.int32)
    if work is None:
        work = page_work_list(tables, pos, 1, page)
    bias3 = bias.reshape(B * Pmax, 1, page)
    row_spec = pl.BlockSpec((1, H, R), lambda i, row, pg, phys, pos: (row[i], 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, page=page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(work.n_work[0],),
            in_specs=[
                row_spec,
                pl.BlockSpec((1, page, R), lambda i, row, pg, phys, pos: (phys[i], 0, 0)),
                pl.BlockSpec((1, 1, page), lambda i, row, pg, phys, pos: (row[i] * Pmax + pg[i], 0, 0)),
            ],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((H, _LANE), jnp.float32),
                pltpu.VMEM((H, _LANE), jnp.float32),
                pltpu.VMEM((H, R), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, R), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_attention",
    )(work.row, work.page, work.phys, pos, q, pool, bias3)


def _dense_kernel(row_ref, page_ref, phys_ref, pos_ref, q_ref, c_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, page: int, value_dim: int):
    del phys_ref
    i = pl.program_id(0)
    j = page_ref[i]
    last_tok = pos_ref[row_ref[i]]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[0]  # [page, W]: the key; its first value_dim columns are the value
    sc = lax.dot_general(q_ref[0], c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    ok = j * page + lax.broadcasted_iota(jnp.int32, sc.shape, 1) <= last_tok
    sc = jnp.where(ok, sc, _NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    prob = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(alpha * l_ref[:, :1] + jnp.sum(prob, axis=1, keepdims=True), l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        prob.astype(c.dtype), c[:, :value_dim], preferred_element_type=jnp.float32)

    @pl.when((j + 1) * page > last_tok)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("value_dim", "scale", "interpret"))
def dense_latent_attention(q, pool, tables, positions, *, value_dim: int, scale: float,
                           interpret: bool = False, work: Optional[PageWork] = None):
    """q [B, H, W] (absorbed queries ``[W_uk^T q_nope | q_rope | 0]``, the
    pool's dtype), pool [P, page, W] (rows ``[c | k_rope | 0]``), tables
    [B, Pmax], positions [B] (the query's own position; its row is already
    in the pool). Returns ``sum_s softmax_s(q . row_s * scale) c_s`` over
    every s <= position, c_s the row's first ``value_dim`` columns:
    [B, H, value_dim] float32."""
    B, H, W = q.shape
    P, page, _ = pool.shape
    pos = positions.astype(jnp.int32)
    if work is None:
        work = page_work_list(tables, pos, 1, page)
    return pl.pallas_call(
        functools.partial(_dense_kernel, scale=scale, page=page, value_dim=value_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(work.n_work[0],),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda i, row, pg, phys, pos: (row[i], 0, 0)),
                pl.BlockSpec((1, page, W), lambda i, row, pg, phys, pos: (phys[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, value_dim), lambda i, row, pg, phys, pos: (row[i], 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, _LANE), jnp.float32),
                pltpu.VMEM((H, _LANE), jnp.float32),
                pltpu.VMEM((H, value_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_attention_dense",
    )(work.row, work.page, work.phys, pos, q, pool)
