"""``ops/grouped_matmul.py``: the grid's tile axis ends at the tiles a
dispatch USES (PR 55), and the result is the static grid's, bit for bit.

``parent_grouped_mlp`` below is the static-grid call as it stood before
PR 55, kept as the reference: every one of the plan's worst-case tiles
takes a grid step, a tile past ``tiles_used`` writes zeros, and an absent
pair reads the last row. The new call must equal it exactly
(``np.array_equal``): a changed tile order or sum order would re-roll
which compared positions flip a router in the benchmark's comparison.
Both run eagerly here, as every other test calls ``grouped_mlp``: the
kernels are jitted, the combine is the same primitives in the same order.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.ops import grouped_matmul as gm

D, F = 256, 128
LIMIT = 10.0


def _parent_gate_up_kernel(expert_ref, used_ref, x_ref, wg_ref, wu_ref, o_ref):
    del expert_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        o_ref[...] = gm.swiglu(g, u, LIMIT).astype(o_ref.dtype)

    @pl.when(pl.program_id(1) >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _parent_down_kernel(expert_ref, used_ref, a_ref, w_ref, o_ref):
    del expert_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jnp.dot(a_ref[...], w_ref[0], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("tm",))
def _parent_kernels(rows, w_gu, w_d, tile_expert, tiles_used, *, tm):
    M = rows.shape[0]
    T = M // tm
    tn = gm._col_block(F, 512)
    nb = F // tn
    a = pl.pallas_call(
        _parent_gate_up_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nb, T),
            in_specs=[pl.BlockSpec((tm, D), lambda n, t, ex, used: (t, 0)),
                      pl.BlockSpec((1, D, tn), lambda n, t, ex, used: (ex[t], 0, n)),
                      pl.BlockSpec((1, D, tn), lambda n, t, ex, used: (ex[t], 0, n + nb))],
            out_specs=pl.BlockSpec((tm, tn), lambda n, t, ex, used: (t, n))),
        out_shape=jax.ShapeDtypeStruct((M, F), rows.dtype), interpret=True)(tile_expert, tiles_used, rows, w_gu, w_gu)
    td = gm._col_block(D, 1024)
    return pl.pallas_call(
        _parent_down_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(D // td, T),
            in_specs=[pl.BlockSpec((tm, F), lambda n, t, ex, used: (t, 0)),
                      pl.BlockSpec((1, F, td), lambda n, t, ex, used: (ex[t], 0, n))],
            out_specs=pl.BlockSpec((tm, td), lambda n, t, ex, used: (t, n))),
        out_shape=jax.ShapeDtypeStruct((M, D), jnp.float32), interpret=True)(tile_expert, tiles_used, a, w_d)


def parent_grouped_mlp(x, local_expert, gates, w_gu, w_d):
    tm = gm.row_tile(local_expert.size)
    p = gm.plan(local_expert, w_gu.shape[0], tm)
    rows = jnp.take(x, p.row_token, axis=0, mode="fill", fill_value=0)
    y = _parent_kernels(rows, w_gu, w_d, p.tile_expert, p.tiles_used, tm=tm)
    M = y.shape[0]
    picked = jnp.take(y, jnp.minimum(p.dest, M - 1), axis=0)
    g = jnp.where(p.dest < M, gates, 0.0)
    return jnp.sum(picked * g[:, :, None], axis=1), p


def _routing(rng, tokens, k, held, routed):
    """Every token's ``k`` distinct experts of ``routed``; the chip holds
    the first ``held``."""
    top = np.stack([rng.choice(routed, size=k, replace=False) for _ in range(tokens)])
    return np.where(top < held, top, held).astype(np.int32)


def _nothing_held(rng, tokens, k, held, routed):
    return np.full((tokens, k), held, np.int32)


def _all_to_the_same_experts(rng, tokens, k, held, routed):
    return np.broadcast_to(np.arange(k, dtype=np.int32), (tokens, k)).copy()


def _poisoned(kernel):
    """The kernel with every row no tile wrote set to NaN: on the chip
    those rows hold whatever the buffer held."""
    def call(x, w, tile_expert, tiles_used, *, tm, **kw):
        out = kernel(x, w, tile_expert, tiles_used, tm=tm, **kw)
        written = jnp.arange(out.shape[0])[:, None] < jnp.maximum(tiles_used[0], 1) * tm
        return jnp.where(written, out, jnp.nan)
    return call


CASES = [
    # (tokens, k, E held, E routed): Kimi-K2.5's decode step and chunk, MiniMax-M3's step, Trinity-Mini's chunk
    ("kimi-step", 32, 8, 12, 384, _routing),
    ("kimi-chunk", 512, 8, 12, 384, _routing),
    ("minimax-step", 16, 4, 16, 128, _routing),
    ("all-held-chunk", 512, 8, 128, 128, _routing),
    ("no-pair-held", 32, 8, 12, 384, _nothing_held),
    # the worst case the static tile count exists for: every pair held, in whole tiles
    ("every-pair-held-whole-tiles", 64, 8, 12, 384, _all_to_the_same_experts),
]


@pytest.mark.parametrize("poison", [False, True], ids=["", "unwritten-rows-poisoned"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name,tokens,k,held,routed,route", CASES, ids=[c[0] for c in CASES])
def test_grouped_mlp_is_bit_equal_to_the_static_grid(monkeypatch, name, tokens, k, held, routed, route, dtype, poison):
    rng = np.random.default_rng(tokens * k + held)
    x = jnp.asarray(rng.normal(size=(tokens, D)), dtype)
    w_gu = jnp.asarray(rng.normal(size=(held, D, 2 * F)) / np.sqrt(D), dtype)
    w_d = jnp.asarray(rng.normal(size=(held, F, D)) / np.sqrt(F), dtype)
    local = jnp.asarray(route(rng, tokens, k, held, routed))
    gates = jnp.asarray(rng.uniform(0.1, 1.0, size=(tokens, k)), jnp.float32)
    want, plan = parent_grouped_mlp(x, local, gates, w_gu, w_d)
    if poison:
        monkeypatch.setattr(gm, "grouped_gate_up", _poisoned(gm.grouped_gate_up))
        monkeypatch.setattr(gm, "grouped_down", _poisoned(gm.grouped_down))
    got, sizes = gm.grouped_mlp(x, local, gates, w_gu, w_d, limit=LIMIT, kernel="interpret")
    assert np.all(np.isfinite(np.asarray(got))) and np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(sizes), np.asarray(plan.sizes))
    used, planned = gm.tile_counts(sizes, tokens * k)
    assert int(used) == int(plan.tiles_used[0]) <= planned == plan.tile_expert.shape[0]
    if route is _nothing_held:
        assert int(used) == 0 and not np.any(np.asarray(got))
    if route is _all_to_the_same_experts:
        assert int(used) == tokens * k // gm.row_tile(tokens * k)  # no part tile: the grid runs every row it was given
