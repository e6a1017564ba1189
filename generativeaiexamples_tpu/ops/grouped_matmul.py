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
expert without a tile is never fetched. The PLAN's number of tiles is
static (``ceil(pairs / tm) + experts``: the worst case, every pair held
and a part tile an expert), and an expert-parallel chip holds a few of a
dispatch's pairs, so the GRID's tile axis is dynamic: it ends at
``max(tiles_used, 1)``, a number the plan computes on the device. The
tiles past it take no grid step and move no byte; the rows of ``a`` and
``y`` they would have written are UNINITIALISED, and ``grouped_mlp``'s
combine reads a row only for a held pair. Tile 0 always runs (over zero
rows where nothing is held). Bytes streamed: the hit experts' matrices,
once, and the used tiles' rows.

Two products make the expert MLP:

- ``gate_up``: ``x [M, D] x W_gu [E, D, 2F]`` with the SwiGLU fused in
  the epilogue, ``silu(min(g, limit)) * clip(u, -limit, limit)``: the
  gate and up column blocks are two views of the same array. With
  ``oai_alpha`` (a static number beside ``limit``) the epilogue is
  gpt-oss's ``swigluoai``: ``g' sigmoid(alpha g') (u' + 1)`` over the
  same clamps ``g' = min(g, limit)``, ``u' = clip(u, -limit, limit)``;
  None keeps the SiLU form, its kernel and its bits;
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


def tile_counts(sizes: jax.Array, pairs: int):
    """(row tiles ``plan`` lays out for held groups of ``sizes`` [E]: its
    ``tiles_used``, [] int32; row tiles it plans for ``pairs`` pairs, all
    held: a Python int) of the dispatch ``grouped_mlp`` would make."""
    tm = row_tile(pairs)
    return jnp.sum(-(-sizes // tm)).astype(jnp.int32), -(-pairs // tm) + sizes.shape[0]


def _gate_up_kernel(expert_ref, x_ref, wg_ref, wu_ref, o_ref, *, limit: float, oai_alpha: Optional[float] = None):
    del expert_ref
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    o_ref[...] = swiglu(g, u, limit, oai_alpha).astype(o_ref.dtype)


def _down_kernel(expert_ref, a_ref, w_ref, o_ref):
    del expert_ref
    o_ref[...] = jnp.dot(a_ref[...], w_ref[0], preferred_element_type=jnp.float32)


def _tiles_run(tiles_used):
    """The grid's dynamic tile bound: tile 0 runs even where nothing is
    held, so row 0 of the output is always written."""
    return jnp.maximum(tiles_used[0], 1)


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


@functools.partial(jax.jit, static_argnames=("tm", "limit", "interpret", "oai_alpha"))
def grouped_gate_up(x, w_gu, tile_expert, tiles_used, *, tm: int, limit: float, interpret: bool = False,
                    oai_alpha: Optional[float] = None):
    """x [M, D] (rows in plan order) x w_gu [E, D, 2F] -> the SwiGLU
    activation [M, F] in x's dtype (``oai_alpha``: ``swiglu``'s); rows
    past ``max(tiles_used, 1) * tm`` are not written."""
    M, D = x.shape
    E, _, F2 = w_gu.shape
    F = F2 // 2
    tn = _col_block(F, 512)
    nb = F // tn
    return pl.pallas_call(
        functools.partial(_gate_up_kernel, limit=limit, oai_alpha=oai_alpha),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, _tiles_run(tiles_used)),
            in_specs=[
                pl.BlockSpec((tm, D), lambda n, t, ex: (t, 0)),
                pl.BlockSpec((1, D, tn), lambda n, t, ex: (ex[t], 0, n)),
                pl.BlockSpec((1, D, tn), lambda n, t, ex: (ex[t], 0, n + nb)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, t, ex: (t, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul_gate_up",
    )(tile_expert, x, w_gu, w_gu)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_down(a, w_d, tile_expert, tiles_used, *, tm: int, interpret: bool = False):
    """a [M, F] x w_d [E, F, D] -> [M, D] float32; rows past
    ``max(tiles_used, 1) * tm`` are not written."""
    M, F = a.shape
    E, _, D = w_d.shape
    tn = _col_block(D, 1024)
    return pl.pallas_call(
        _down_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(D // tn, _tiles_run(tiles_used)),
            in_specs=[
                pl.BlockSpec((tm, F), lambda n, t, ex: (t, 0)),
                pl.BlockSpec((1, F, tn), lambda n, t, ex: (ex[t], 0, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, t, ex: (t, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul_down",
    )(tile_expert, a, w_d)


def swiglu(g, u, limit: float, oai_alpha: Optional[float] = None):
    """``silu(g') u'`` with ``g' = min(g, limit)``, ``u' = clip(u, -limit,
    limit)``; with ``oai_alpha`` gpt-oss's ``g' sigmoid(alpha g') (u' + 1)``."""
    g = jnp.minimum(g, limit)
    if oai_alpha is not None:
        return g * jax.nn.sigmoid(oai_alpha * g) * (jnp.clip(u, -limit, limit) + 1.0)
    return g * jax.nn.sigmoid(g) * jnp.clip(u, -limit, limit)


def grouped_mlp(x, local_expert, gates, w_gu, w_d, *, limit: float, kernel: Optional[str] = None,
                oai_alpha: Optional[float] = None):
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
        a = swiglu(gu[..., :F], gu[..., F:], limit, oai_alpha).astype(x.dtype)
        y = jnp.einsum("enf,efd->end", a, w_d, preferred_element_type=jnp.float32)
        out = jnp.einsum("ne,end->nd", dense_gate, y)
        return out, jnp.sum(onehot, axis=(0, 1)).astype(jnp.int32)
    interpret = kernel == "interpret"
    tm = row_tile(N * local_expert.shape[1])
    p = plan(local_expert, E, tm)
    rows = jnp.take(x, p.row_token, axis=0, mode="fill", fill_value=0)
    a = grouped_gate_up(rows, w_gu, p.tile_expert, p.tiles_used, tm=tm, limit=float(limit), interpret=interpret,
                        oai_alpha=oai_alpha)
    y = grouped_down(a, w_d, p.tile_expert, p.tiles_used, tm=tm, interpret=interpret)
    # y's rows past the used tiles are uninitialised: an absent pair reads row 0, which tile 0 always writes,
    # and adds 0.0 whatever that row holds (garbage times a zero gate could be NaN)
    held = p.dest < y.shape[0]
    picked = jnp.where(held[:, :, None], jnp.take(y, jnp.where(held, p.dest, 0), axis=0), 0.0)  # [N, k, D]
    g = jnp.where(held, gates, 0.0)
    return jnp.sum(picked * g[:, :, None], axis=1), p.sizes
