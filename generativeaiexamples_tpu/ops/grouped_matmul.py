"""Pallas TPU kernel: the grouped (ragged) matmul of an expert layer.

An expert layer routes each token to a few experts; an expert-parallel
chip HOLDS some of the experts and computes only the (token, expert)
pairs routed to those. At decode a step holds a pair or two an expert,
so the product is bound by streaming each HIT expert's matrices once;
an expert no token reached must cost nothing.

**Layout of the work** (``plan``): the held pairs are ordered by expert
and each expert's group is padded up to whole row tiles of ``tm`` rows,
so that every tile belongs to ONE expert. The kernel's grid is
``(column blocks, tiles)`` with the tile's expert handed over by scalar
prefetch: the weight block index is ``(expert[tile], 0, column block)``,
so consecutive tiles of one expert reuse the block in VMEM and an
expert without a tile is never fetched. The number of tiles is static
(``ceil(pairs / tm) + experts``, the worst case); tiles past the used
ones repeat the last used tile's expert (no DMA) and skip their
compute. Bytes streamed: the hit experts' matrices, once.

Two products make the expert MLP:

- ``gate_up``: ``x [M, D] x W_gu [E, D, 2F]`` with the SwiGLU fused in
  the epilogue, ``silu(min(g, limit)) * clip(u, -limit, limit)``: the
  gate and up column blocks are two views of the same array;
- ``down``: ``a [M, F] x W_d [E, F, D]``, float32 out.

``grouped_mlp`` is the whole thing for a walk: plan, gather, the two
kernels, and the gate-weighted combine back to tokens. With
``kernel=None`` it computes the same sum densely over the held experts
in ``jax.numpy`` (CPU tests, the fallback).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class GroupPlan(NamedTuple):
    """Where each held pair's row lives, and which expert owns each tile."""

    row_token: jax.Array  # [M] int32: token of each row; N for a padding row
    dest: jax.Array  # [N, k] int32: row of pair (token, j); M where not held
    tile_expert: jax.Array  # [T] int32
    tiles_used: jax.Array  # [1] int32
    sizes: jax.Array  # [E] int32: pairs held per expert


def row_tile(pairs: int) -> int:
    """Rows a tile: 16 (one bfloat16 sublane tile) where a step holds a
    pair or two an expert, 64 where a prefill chunk holds a dozen."""
    return 16 if pairs <= 512 else 64


def plan(local_expert: jax.Array, num_experts: int, tm: int) -> GroupPlan:
    """``local_expert`` [N, k] int32: the held expert of each pair, or
    ``num_experts`` where the pair's expert is not held."""
    N, k = local_expert.shape
    P, E = N * k, num_experts
    T = -(-P // tm) + E
    M = T * tm
    flat = local_expert.reshape(P)
    onehot = (flat[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    sizes = jnp.sum(onehot, axis=0)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)  # rank inside its expert
    padded = -(-sizes // tm) * tm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    held = flat < E
    dest = jnp.where(held, starts[jnp.minimum(flat, E - 1)] + rank, M).astype(jnp.int32)
    row_token = jnp.full((M,), N, jnp.int32).at[dest].set(
        jnp.arange(P, dtype=jnp.int32) // k, mode="drop")
    used = ends[-1] // tm
    tile = jnp.minimum(jnp.arange(T, dtype=jnp.int32), jnp.maximum(used - 1, 0))
    tile_expert = jnp.minimum(
        jnp.sum((tile[:, None] * tm >= ends[None, :]).astype(jnp.int32), axis=1), E - 1)
    return GroupPlan(row_token, dest.reshape(N, k), tile_expert.astype(jnp.int32),
                     used.reshape(1).astype(jnp.int32), sizes)


def _gate_up_kernel(expert_ref, used_ref, x_ref, wg_ref, wu_ref, o_ref, *, limit: float):
    del expert_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        g = jnp.minimum(g, limit)
        o_ref[...] = (g * jax.nn.sigmoid(g) * jnp.clip(u, -limit, limit)).astype(o_ref.dtype)

    @pl.when(pl.program_id(1) >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _down_kernel(expert_ref, used_ref, a_ref, w_ref, o_ref):
    del expert_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jnp.dot(a_ref[...], w_ref[0], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


_LANES = 128


def _col_block(width: int, want: int) -> int:
    """Columns a weight block holds: the largest multiple of a lane tile
    that divides ``width`` and is at most ``want`` (256 of 1280, where
    512 does not divide ten lane tiles); the whole width where no lane
    multiple divides it (the tiny widths of the CPU tests)."""
    for tn in range(min(want, width) // _LANES * _LANES, 0, -_LANES):
        if width % tn == 0:
            return tn
    return width


_VMEM_LIMIT = 64 * 1024 * 1024


@functools.partial(jax.jit, static_argnames=("tm", "limit", "interpret"))
def grouped_gate_up(x, w_gu, tile_expert, tiles_used, *, tm: int, limit: float, interpret: bool = False):
    """x [M, D] (rows in plan order) x w_gu [E, D, 2F] -> the SwiGLU
    activation [M, F] in x's dtype."""
    M, D = x.shape
    E, _, F2 = w_gu.shape
    F = F2 // 2
    tn = _col_block(F, 512)
    nb = F // tn
    T = M // tm
    return pl.pallas_call(
        functools.partial(_gate_up_kernel, limit=limit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb, T),
            in_specs=[
                pl.BlockSpec((tm, D), lambda n, t, ex, used: (t, 0)),
                pl.BlockSpec((1, D, tn), lambda n, t, ex, used: (ex[t], 0, n)),
                pl.BlockSpec((1, D, tn), lambda n, t, ex, used: (ex[t], 0, n + nb)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, t, ex, used: (t, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul_gate_up",
    )(tile_expert, tiles_used, x, w_gu, w_gu)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_down(a, w_d, tile_expert, tiles_used, *, tm: int, interpret: bool = False):
    """a [M, F] x w_d [E, F, D] -> [M, D] float32."""
    M, F = a.shape
    E, _, D = w_d.shape
    tn = _col_block(D, 1024)
    T = M // tm
    return pl.pallas_call(
        _down_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(D // tn, T),
            in_specs=[
                pl.BlockSpec((tm, F), lambda n, t, ex, used: (t, 0)),
                pl.BlockSpec((1, F, tn), lambda n, t, ex, used: (ex[t], 0, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, t, ex, used: (t, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul_down",
    )(tile_expert, tiles_used, a, w_d)


def swiglu(g, u, limit: float):
    g = jnp.minimum(g, limit)
    return g * jax.nn.sigmoid(g) * jnp.clip(u, -limit, limit)


def grouped_mlp(x, local_expert, gates, w_gu, w_d, *, limit: float, kernel: Optional[str] = None):
    """sum over a token's HELD pairs of ``gate * Expert(x)``.

    x [N, D]; local_expert [N, k] (``E`` where not held); gates [N, k]
    float32; w_gu [E, D, 2F]; w_d [E, F, D]. Returns ([N, D] float32,
    sizes [E] int32: the pairs each held expert got)."""
    N, D = x.shape
    E = w_gu.shape[0]
    if kernel is None:
        onehot = local_expert[:, :, None] == jnp.arange(E, dtype=jnp.int32)[None, None, :]
        dense_gate = jnp.sum(jnp.where(onehot, gates[:, :, None], 0.0), axis=1)  # [N, E]
        gu = jnp.einsum("nd,edf->enf", x, w_gu, preferred_element_type=jnp.float32)
        F = gu.shape[-1] // 2
        a = swiglu(gu[..., :F], gu[..., F:], limit).astype(x.dtype)
        y = jnp.einsum("enf,efd->end", a, w_d, preferred_element_type=jnp.float32)
        out = jnp.einsum("ne,end->nd", dense_gate, y)
        return out, jnp.sum(onehot, axis=(0, 1)).astype(jnp.int32)
    interpret = kernel == "interpret"
    tm = row_tile(N * local_expert.shape[1])
    p = plan(local_expert, E, tm)
    rows = jnp.take(x, p.row_token, axis=0, mode="fill", fill_value=0)
    a = grouped_gate_up(rows, w_gu, p.tile_expert, p.tiles_used, tm=tm, limit=float(limit), interpret=interpret)
    y = grouped_down(a, w_d, p.tile_expert, p.tiles_used, tm=tm, interpret=interpret)
    M = y.shape[0]
    picked = jnp.take(y, jnp.minimum(p.dest, M - 1), axis=0)  # [N, k, D]
    g = jnp.where(p.dest < M, gates, 0.0)
    return jnp.sum(picked * g[:, :, None], axis=1), p.sizes
