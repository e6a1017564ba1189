"""The own-head fold of the page kernel (ops/page_attention.py): the
softmax runs over ``score_rows`` rows, a query group's own KV head, in
interpret mode on the CPU. A file of its own beside
tests/test_page_attention.py, whose helpers it borrows: under the
driver's ``--dist loadfile`` a file is one worker process, and that
file's ~300 interpreted kernels already fill one (a 36-case longer file
aborted inside XLA's CPU compile, PERF.md §7 Opened by PR 47 (d))."""
import zlib

import numpy as np
import pytest

import jax.numpy as jnp

from generativeaiexamples_tpu.ops import page_attention as pa
from tests.test_page_attention import (
    Dh,
    _bf16_pool,
    _group_tables,
    _head_major,
    _int4_pool,
    _int8_pool,
    _lane_dense,
    _reference,
    _unpack_pool,
    _walk,
)


FOLD_PAGE = 128  # the served page: a head's columns tile the 128 lanes

SERVED = {
    # name: (Hq, Hkv, pool kind, head_major)
    "mistral-int8-lane-dense": (32, 8, "int8-lane-dense", False),
    "mistral-int8-token-major": (32, 8, "int8", False),
    "mistral-int4": (32, 8, "int4", False),
    "phi4flash-40-10": (40, 10, "bf16", True),
    "trinity-32-4": (32, 4, "bf16", True),
    "solaropen2-64-8": (64, 8, "bf16", True),
}


def _pool_of(rng, kind, pages, page, hkv):
    """``(kernel pool, reference pool)`` of ``pages`` token-major pages."""
    if kind == "bf16":
        pool = _bf16_pool(rng, pages, hkv, page)
        return pool, pool
    if kind == "int4":
        kq, vq, ks, vs = _int4_pool(rng, pages, hkv, page)
        return (kq, vq, ks, vs), (_unpack_pool(kq), _unpack_pool(vq), ks, vs)
    pool = _int8_pool(rng, pages, hkv, page)
    return (_lane_dense(pool) if kind.endswith("lane-dense") else pool), pool


def _runs_folded(hq, hkv, t, page, head_major=False):
    return pa.score_rows(hq, hkv, t) < t * hq and pa._state_lanes(page, hkv, head_major) > 0


@pytest.mark.parametrize(
    "geometry,t",
    [(name, 1) for name in sorted(SERVED)]
    + [("mistral-int8-lane-dense", 5), ("mistral-int8-lane-dense", 16)],
    ids=lambda v: v if isinstance(v, str) else f"T{v}",
)
def test_folded_kernel_matches_the_float32_reference_at_the_served_geometries(geometry, t):
    """The six served reads at T = 1, and Mistral's T = 5 verify read
    and folded 16-query extend read: the folded kernel (the softmax over
    ``score_rows`` rows, a group's own KV head) against the plain
    float32 reference over ragged tables: a dead row, rows ending in the
    middle of their first, second and third page, at both pages a step."""
    hq, hkv, kind, head_major = SERVED[geometry]
    assert _runs_folded(hq, hkv, t, FOLD_PAGE, head_major)
    rng = np.random.default_rng(zlib.crc32(geometry.encode()) + t)
    if t == 16:  # one cache row read as sub-rows of 16 queries, each at its own first position
        first = [FOLD_PAGE - 24, FOLD_PAGE - 8, FOLD_PAGE + 8, 2 * FOLD_PAGE - 4]
        tables = jnp.asarray(np.tile(1 + np.arange(3), (len(first), 1)), jnp.int32)
        used = 4
    else:
        first = [0, 5, FOLD_PAGE + 70, 3 * FOLD_PAGE - t - 9]
        tables, used = _group_tables([1, 1, 2, 3], pmax=3)
    pool, ref_pool = _pool_of(rng, kind, used, FOLD_PAGE, hkv)
    q = jnp.asarray(2 * rng.standard_normal((len(first), t, hq, Dh)), jnp.bfloat16)
    if head_major:
        pool = _head_major(pool)
    rows = np.asarray([True] * len(first) if t == 16 else [p > 0 for p in first])
    ref = np.asarray(_reference(q, *ref_pool[:2], tables, jnp.asarray(first, jnp.int32), *ref_pool[2:]))
    one = _walk(q, pool, tables, first, 1, head_major=head_major)
    np.testing.assert_allclose(one[rows], ref[rows], atol=0.02)
    np.testing.assert_array_equal(_walk(q, pool, tables, first, 2, head_major=head_major)[rows], one[rows])


@pytest.mark.parametrize(
    "hq,hkv",
    [(8, 1), (16, 16), (16, 8), (32, 8), (16, 2), (32, 2), (24, 8), (4, 2)],
    ids=["Hkv1", "G1", "G2", "G4", "G8", "G16", "G3-wide", "Hq4-wide"],
)
@pytest.mark.parametrize("t", [1, 3])
def test_every_group_width_matches_the_reference_on_either_body(hq, hkv, t):
    """One KV head (nothing to fold), groups of 1, 2, 4, 8 and 16 query
    heads, and two geometries whose slabs do not tile a vreg: whichever
    body the static rule sends a geometry to, the read is the
    reference's, at one query a row and at three."""
    folded = _runs_folded(hq, hkv, t, FOLD_PAGE)
    assert folded == (hkv > 1 and hq % 8 == 0 and (hq // hkv) in (1, 2, 4, 8, 16))
    rng = np.random.default_rng(hq * 100 + hkv + t)
    first = [0, FOLD_PAGE - 2, 2 * FOLD_PAGE + 17]
    tables, used = _group_tables([1, 2, 3], pmax=3)
    pool, _ = _pool_of(rng, "int8", used, FOLD_PAGE, hkv)
    q = jnp.asarray(2 * rng.standard_normal((len(first), t, hq, Dh)), jnp.bfloat16)
    got = _walk(q, pool, tables, first, 1)
    ref = np.asarray(_reference(q, *pool[:2], tables, jnp.asarray(first, jnp.int32), *pool[2:]))
    np.testing.assert_allclose(got[1:], ref[1:], atol=0.02)


@pytest.mark.parametrize(
    "heads,t,expect",
    [
        ((32, 8), 1, 8),  # Mistral decode: two groups of 4 share a vreg (the wide body ran 32)
        ((32, 8), 5, 40),  # ... spec verify (160 wide)
        ((32, 8), 16, 128),  # ... the folded extend read (512 wide)
        ((40, 10), 1, 8),  # Phi-4-flash's pair layout (40 wide)
        ((32, 4), 1, 8),  # Trinity-Mini (32 wide)
        ((64, 8), 1, 8),  # Solar-Open2 (64 wide)
        ((8, 1), 1, 8),  # one KV head: nothing to fold
        ((8, 1), 4, 32),
        ((8, 2), 1, 8),  # the fold would hold no fewer registers: wide
        ((24, 8), 1, 24),  # groups of 3 do not tile a vreg: wide
        ((4, 2), 1, 4),  # the debug model: its heads do not fill a vreg
    ],
)
def test_score_rows_of_the_served_geometries(heads, t, expect):
    assert pa.score_rows(*heads, t) == expect
    assert pa.score_rows(*heads, t) <= t * heads[0]


def test_state_lanes_follow_the_column_order_of_a_page():
    """Token-major a head's columns are the lanes congruent to it: one
    class-replicated lane tile, which needs a power-of-two count of
    tokens a tile; head-major one lane-replicated tile a head."""
    assert pa._state_lanes(128, 8, False) == 128
    assert pa._state_lanes(8, 8, False) == 64  # a page narrower than the lanes
    assert pa._state_lanes(128, 10, False) == 0  # ten heads do not divide the lanes
    assert pa._state_lanes(128, 10, True) == 1280
    assert pa._state_lanes(256, 4, True) == 512
    assert pa._state_lanes(8, 10, True) == 0  # more heads than a tile has lanes

