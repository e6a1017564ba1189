"""Ragged Pallas page-attention kernel (ops/page_attention.py), gated on
CPU via interpret mode: operand math against a pure-jnp reference over
ragged page tables (dead rows, scratch page 0, one-page rows, full
rows, multi-query causal chunks), plus the geometry-predicate matrix —
so the kernel's logic is tier-1-tested without TPU hardware (the
compiled path's tiling is what ``supports_geometry`` guards)."""
import math
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.ops import page_attention as pa

B, Hq, Hkv, Dh = 3, 4, 2, 16
PAGE, PMAX, POOL = 8, 8, 24
S = PMAX * PAGE


def _ragged_tables(rng):
    """Row 0: one live page; row 1: four; row 2: the full table. Unused
    entries stay at the scratch page (0), as the engine pads them."""
    tables = np.zeros((B, PMAX), np.int32)
    tables[0, :1] = [1]
    tables[1, :4] = [2, 3, 4, 5]
    tables[2, :] = np.arange(6, 6 + PMAX)
    return jnp.asarray(tables)


def _bf16_pool(rng, pool=POOL, hkv=Hkv, page=PAGE):
    k = jnp.asarray(rng.standard_normal((pool, page, hkv, Dh)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((pool, page, hkv, Dh)), jnp.bfloat16)
    return k, v


def _int8_pool(rng, pool=POOL, hkv=Hkv, page=PAGE):
    kq = jnp.asarray(rng.integers(-127, 128, (pool, page, hkv, Dh)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (pool, page, hkv, Dh)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (pool, page, hkv)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (pool, page, hkv)), jnp.float32)
    return kq, vq, ks, vs


# KV heads at which the tests' 8-token pages tile 128 lanes (two rows a page)
DENSE_HKV = 32


def _lane_dense(pool):
    """The same pool with its scale planes as ``init_kv_pool`` stores
    them where a page's scales tile the lanes: [P, page * Hkv / 128, 128]
    (the same values in the same flat order ``t * Hkv + h``)."""
    k, v, ks, vs = pool
    plane = (ks.shape[0],) + llama.kv_scale_plane_shape(k.shape[1], k.shape[2])
    assert plane[2] == 128 and plane != ks.shape, plane
    return k, v, ks.reshape(plane), vs.reshape(plane)


def _reference(q, k, v, tables, pos, ks=None, vs=None):
    """Pure-jnp gather-all-pages + position mask — the same semantics
    models/llama.py's paged XLA paths compute (f32 softmax over the
    full gathered window). Geometry is read from the shapes."""
    nb, t, hq, dh = q.shape
    hkv = k.shape[2]
    s_max = tables.shape[1] * k.shape[1]
    g = k[tables].reshape(nb, s_max, hkv, dh).astype(jnp.float32)
    gv = v[tables].reshape(nb, s_max, hkv, dh).astype(jnp.float32)
    if ks is not None:
        g = g * ks[tables].reshape(nb, s_max, hkv)[..., None]
        gv = gv * vs[tables].reshape(nb, s_max, hkv)[..., None]
    qg = q.reshape(nb, t, hkv, hq // hkv, dh).astype(jnp.float32)
    sc = jnp.einsum("btkgd,bskd->bkgts", qg, g) / math.sqrt(dh)
    qpos = jnp.minimum(pos[:, None] + jnp.arange(t)[None, :], s_max - 1)
    mask = jnp.arange(s_max)[None, None, :] <= qpos[:, :, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, None], sc, -1e30), axis=-1)
    return jnp.einsum("bkgts,bskd->btkgd", p, gv).reshape(nb, t, hq, dh)


def _assert_close(out, ref, atol=0.02):
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol
    )


def test_bf16_matches_reference_over_ragged_tables():
    rng = np.random.default_rng(0)
    tables = _ragged_tables(rng)
    k, v = _bf16_pool(rng)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, Dh)), jnp.bfloat16)
    # one-page row, mid-length row, full-capacity row
    pos = jnp.asarray([3, 25, S - 1], jnp.int32)
    out = pa.paged_attention(q, k, v, tables, pos, interpret=True)
    _assert_close(out, _reference(q, k, v, tables, pos))


def test_int8_scales_fold_after_the_dots():
    rng = np.random.default_rng(1)
    tables = _ragged_tables(rng)
    kq, vq, ks, vs = _int8_pool(rng)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, Dh)), jnp.bfloat16)
    pos = jnp.asarray([0, 17, 42], jnp.int32)
    out = pa.paged_attention(q, kq, vq, tables, pos, ks, vs, interpret=True)
    _assert_close(out, _reference(q, kq, vq, tables, pos, ks, vs))


def test_dead_pages_beyond_live_length_never_contribute():
    """Poisoning every pool page a row's live range does NOT cover —
    including the scratch page its padding table entries point at —
    must not change that row's output: the DMA clamp + position mask
    make dead pages unreachable."""
    rng = np.random.default_rng(2)
    tables = _ragged_tables(rng)
    k, v = _bf16_pool(rng)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, Dh)), jnp.bfloat16)
    pos = jnp.asarray([5, 20, 30], jnp.int32)
    out = pa.paged_attention(q, k, v, tables, pos, interpret=True)
    # live pages per row: ceil((pos+1)/PAGE) table entries
    live = {
        int(tables[b, j])
        for b in range(B)
        for j in range(int(pos[b]) // PAGE + 1)
    }
    poison = jnp.full_like(k, 1e4)
    k2 = jnp.where(
        jnp.isin(jnp.arange(POOL), jnp.asarray(sorted(live)))[
            :, None, None, None
        ],
        k, poison,
    )
    v2 = jnp.where(
        jnp.isin(jnp.arange(POOL), jnp.asarray(sorted(live)))[
            :, None, None, None
        ],
        v, poison,
    )
    out2 = pa.paged_attention(q, k2, v2, tables, pos, interpret=True)
    _assert_close(out2, out, atol=0.0)


def test_partial_page_rows_mask_to_exact_position():
    """A row whose position sits mid-page attends exactly pos+1 tokens:
    mutating the SAME page's rows past the position changes nothing."""
    rng = np.random.default_rng(3)
    tables = _ragged_tables(rng)
    k, v = _bf16_pool(rng)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, Dh)), jnp.bfloat16)
    pos = jnp.asarray([3, 20, 30], jnp.int32)  # row 0 lives in page 1 rows 0..3
    out = pa.paged_attention(q, k, v, tables, pos, interpret=True)
    k2 = k.at[1, 4:].set(99.0)  # page 1 rows past position 3
    v2 = v.at[1, 4:].set(99.0)
    out2 = pa.paged_attention(q, k2, v2, tables, pos, interpret=True)
    _assert_close(out2[0], out[0], atol=0.0)


def test_multi_query_causal_chunk():
    """T>1 rows (the spec-verify shape): query t attends <= pos + t,
    per row — matches the reference's per-token mask exactly."""
    rng = np.random.default_rng(4)
    tables = _ragged_tables(rng)
    k, v = _bf16_pool(rng)
    kq, vq, ks, vs = _int8_pool(rng)
    T = 3
    q = jnp.asarray(rng.standard_normal((B, T, Hq, Dh)), jnp.bfloat16)
    pos = jnp.asarray([0, 10, 40], jnp.int32)
    out = pa.paged_attention(q, k, v, tables, pos, interpret=True)
    _assert_close(out, _reference(q, k, v, tables, pos))
    out8 = pa.paged_attention(q, kq, vq, tables, pos, ks, vs, interpret=True)
    _assert_close(out8, _reference(q, kq, vq, tables, pos, ks, vs))


def test_dead_row_output_is_finite_garbage():
    """A dead slot (position 0, table full of scratch entries) computes
    finite output the engine discards — never NaN/inf (the fixed
    kernel's contract)."""
    rng = np.random.default_rng(5)
    tables = jnp.zeros((1, PMAX), jnp.int32)  # all scratch
    k, v = _bf16_pool(rng)
    q = jnp.asarray(rng.standard_normal((1, 1, Hq, Dh)), jnp.bfloat16)
    out = pa.paged_attention(
        q, k, v, tables, jnp.zeros((1,), jnp.int32), interpret=True
    )
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


# ------------------------------------------------------------------ //
# the ragged walk: a one-dimensional grid over live (row, page) items
# (page_work_list) instead of (B, Pmax); every edge of the list against
# the XLA gather


# first-query positions of four rows; a row at 0 is dead (its table is
# all scratch), every other row owns ceil-many distinct pool pages
WALK_CASES = {
    "dead_row_between_live_rows": [11, 0, 37, 5],
    "page_boundary_last_and_first_token": [PAGE - 1, PAGE, 3 * PAGE - 1, 3 * PAGE],
    "row_at_capacity": [S - 1, 2, S - 2, 20],
    "all_rows_dead": [0, 0, 0, 0],
}
WALK_POOL = 40
# pages a step of the three served geometries (ops/page_attention.pages_per_step)
RULE = {"mistral": 2, "trinity": 2, "phi4flash": 2}


def _walk_tables(pos, t):
    tables = np.zeros((len(pos), PMAX), np.int32)
    nxt = 1
    for b, p in enumerate(pos):
        if p == 0:
            continue  # dead: scratch entries only
        n = min(p + t - 1, S - 1) // PAGE + 1
        tables[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    assert nxt <= WALK_POOL
    return jnp.asarray(tables)


def _walk_pool(rng, kind, hkv):
    """(kernel operands, reference operands) of one pool dtype."""
    if kind == "bf16":
        pool = _bf16_pool(rng, WALK_POOL, hkv)
        return pool, pool
    if kind == "int8":
        pool = _int8_pool(rng, WALK_POOL, hkv)
        return pool, pool
    kq, vq, ks, vs = _int4_pool(rng, WALK_POOL, hkv)
    return (kq, vq, ks, vs), (_unpack_pool(kq), _unpack_pool(vq), ks, vs)


@pytest.mark.parametrize("heads", [(32, 8), (8, 8)], ids=["gqa32-8", "mha8-8"])
@pytest.mark.parametrize("pool", ["int8", "int4", "bf16"])
@pytest.mark.parametrize("t", [1, 4], ids=["decode", "verify4"])
@pytest.mark.parametrize("case", sorted(WALK_CASES))
@pytest.mark.parametrize("n", [1, 2, 4], ids=["1page", "2pages", "4pages"])
def test_ragged_walk_matches_gather(n, case, t, pool, heads):
    hq, hkv = heads
    pos = WALK_CASES[case]
    rng = np.random.default_rng(zlib.crc32(repr((case, t, pool, heads)).encode()))
    tables = _walk_tables(pos, t)
    kernel_pool, ref_pool = _walk_pool(rng, pool, hkv)
    q = jnp.asarray(rng.standard_normal((len(pos), t, hq, Dh)), jnp.bfloat16)
    posj = jnp.asarray(pos, jnp.int32)
    k, v, *scales = kernel_pool
    out = pa.paged_attention(
        q, k, v, tables, posj, *scales, interpret=True, group=n
    )
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    rk, rv, *rscales = ref_pool
    _assert_close(out, _reference(q, rk, rv, tables, posj, *rscales))


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_page_work_list_holds_live_pages_only(case, t):
    """The list alone (pure jnp): as many items as live pages, a dead
    row keeps one, rows ascend, a row's pages ascend from 0 to its last
    live page — so each row starts and finishes exactly once — and
    every item names the pool page its table entry holds."""
    pos = np.asarray(WALK_CASES[case])
    tables = _walk_tables(list(pos), t)
    work = pa.page_work_list(tables, jnp.asarray(pos, jnp.int32), t, PAGE)
    live = np.minimum(pos + t - 1, S - 1) // PAGE + 1
    n = int(work.n_work[0])
    assert n == live.sum()
    assert work.row.shape == work.page.shape == work.phys.shape == (len(pos) * PMAX,)
    if case != "row_at_capacity":
        assert n < len(pos) * PMAX  # never the dense grid for short rows
    row, page = np.asarray(work.row)[:n], np.asarray(work.page)[:n]
    want_row = np.repeat(np.arange(len(pos)), live)
    want_page = np.concatenate([np.arange(m) for m in live])
    np.testing.assert_array_equal(row, want_row)
    np.testing.assert_array_equal(page, want_page)
    for b, m in enumerate(live):
        assert (page[row == b] == 0).sum() == 1  # _init fires once
        assert (page[row == b] == m - 1).sum() == 1  # _finish fires once
    np.testing.assert_array_equal(
        np.asarray(work.phys)[:n], np.asarray(tables)[row, page]
    )
    # the padding past n_work stays inside the tables
    assert int(work.row.max()) < len(pos) and int(work.page.max()) < PMAX


def test_shared_work_list_equals_the_one_built_inside():
    """A caller's pre-built list (one per step, shared by the layers)
    gives the bits the kernel gets when it builds its own."""
    rng = np.random.default_rng(30)
    pos = jnp.asarray(WALK_CASES["dead_row_between_live_rows"], jnp.int32)
    tables = _walk_tables(WALK_CASES["dead_row_between_live_rows"], 1)
    (k, v, ks, vs), _ = _walk_pool(rng, "int8", Hkv)
    q = jnp.asarray(rng.standard_normal((4, 1, Hq, Dh)), jnp.bfloat16)
    work = pa.page_work_list(tables, pos, 1, PAGE)
    got = pa.paged_attention(q, k, v, tables, pos, ks, vs, interpret=True, work=work)
    want = pa.paged_attention(q, k, v, tables, pos, ks, vs, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


# several pages of a row a grid step (PageWork at N > 1)

GROUP_PMAX = 12


def _group_rows(n):
    """First-query positions of rows holding 1, N-1, N, N+1 and 2N+1
    live pages (T = 1), each ending in the middle of its last page."""
    live = sorted({1, max(1, n - 1), n, n + 1, 2 * n + 1})
    return [(m - 1) * PAGE + 3 for m in live], live


def _group_tables(live, pmax=GROUP_PMAX):
    tables = np.zeros((len(live), pmax), np.int32)
    nxt = 1
    for b, m in enumerate(live):
        tables[b, :m] = np.arange(nxt, nxt + m)
        nxt += m
    return jnp.asarray(tables), nxt


def _walk(q, pool, tables, pos, n, **kw):
    k, v, *scales = pool
    return np.asarray(pa.paged_attention(
        q, k, v, tables, jnp.asarray(pos, jnp.int32), *scales,
        interpret=True, group=n, **kw,
    ), np.float32)


@pytest.mark.parametrize("pool", ["int8", "int4", "bf16", "int8-lane-dense", "int4-lane-dense"])
@pytest.mark.parametrize("n", [2, 4])
def test_group_edges_are_bit_equal_to_one_page_a_step(n, pool):
    """Rows of 1, N-1, N, N+1 and 2N+1 live pages: a group walks its
    pages in ascending order through the one-page arithmetic, so every
    row keeps the bits of the one-page-a-step walk (and the gather's
    values). Over LANE-DENSE scale planes (the row whose last group has
    a dead place among them) the bits are those of the token-major
    planes of the same values."""
    pos, live = _group_rows(n)
    tables, used = _group_tables(live)
    rng = np.random.default_rng(n * 31 + len(pool))
    kind, _, dense = pool.partition("-")
    hkv = DENSE_HKV if dense else 8
    if kind == "bf16":
        kernel_pool = ref_pool = _bf16_pool(rng, used, hkv)
    elif kind == "int8":
        kernel_pool = ref_pool = _int8_pool(rng, used, hkv)
    else:
        kq, vq, ks, vs = _int4_pool(rng, used, hkv)
        kernel_pool, ref_pool = (kq, vq, ks, vs), (_unpack_pool(kq), _unpack_pool(vq), ks, vs)
    q = jnp.asarray(rng.standard_normal((len(pos), 1, 32, Dh)), jnp.bfloat16)
    one = _walk(q, kernel_pool, tables, pos, 1)
    if dense:
        token_major, kernel_pool = kernel_pool, _lane_dense(kernel_pool)
        np.testing.assert_array_equal(_walk(q, kernel_pool, tables, pos, 1), one)
        np.testing.assert_array_equal(_walk(q, kernel_pool, tables, pos, n), _walk(q, token_major, tables, pos, n))
    got = _walk(q, kernel_pool, tables, pos, n)
    np.testing.assert_array_equal(got, one)
    rk, rv, *rs = ref_pool
    _assert_close(got, _reference(q, rk, rv, tables, jnp.asarray(pos, jnp.int32), *rs))


@pytest.mark.parametrize("planes", ["token_major", "lane_dense"])
@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize(
    "t,first,folded",
    [
        (4, [2 * PAGE + 3, PAGE - 2, PAGE - 1, 5 * PAGE + 6], False),  # a verify chunk
        (8, [3, 11, 19, 27], True),  # one cache row folded into sub-rows of 8 queries
        (8, [PAGE + 5, PAGE + 13, 2 * PAGE + 5, 0], True),  # ... from mid-page, a dead sub-row
    ],
    ids=["verify4", "fold8", "fold8-midpage"],
)
def test_chunk_with_a_first_position_inside_a_page(n, t, first, folded, planes):
    """A verify chunk and the folded extend read with first positions
    in the middle of a page: query ``i`` of a row sees tokens up to
    ``first + i`` and no further, on the page that holds ``first`` and on
    every page after it. A token mask left off a page that needed it
    lets a query read its successors' keys, which the gather's values
    (and the one-page walk's bits) refuse."""
    rng = np.random.default_rng(t * 7 + first[0])
    if folded:  # every sub-row reads the SAME cache row through its own table copy
        live = [(max(first) + t - 1) // PAGE + 1] * len(first)
        tables = jnp.asarray(np.tile(1 + np.arange(GROUP_PMAX), (len(first), 1)), jnp.int32)
        used = GROUP_PMAX + 1
    else:
        live = [(p + t - 1) // PAGE + 1 for p in first]
        tables, used = _group_tables(live)
    pool = _int8_pool(rng, used, DENSE_HKV if planes == "lane_dense" else 8)
    k, v, ks, vs = token_major = pool
    if planes == "lane_dense":
        pool = _lane_dense(pool)
    # keys of distinct sizes, so a leaked future token moves the output
    q = jnp.asarray(4 * rng.standard_normal((len(first), t, 32, Dh)), jnp.bfloat16)
    got = _walk(q, pool, tables, first, n)
    ref = np.asarray(_reference(q, k, v, tables, jnp.asarray(first, jnp.int32), ks, vs))
    rows = np.asarray(first) > 0
    np.testing.assert_allclose(got[rows], ref[rows], atol=0.02)
    np.testing.assert_array_equal(got[rows], _walk(q, pool, tables, first, 1)[rows])
    # the bits do not depend on how the pool stores a page's scales
    np.testing.assert_array_equal(got[rows], _walk(q, token_major, tables, first, n)[rows])
    # the control: the same read one position too far IS told apart
    late = _walk(q, pool, tables, [p + 1 if p else 0 for p in first], n)
    assert np.abs(late[rows] - ref[rows]).max() > 0.05


def _head_major(pool):
    return tuple(jnp.swapaxes(a, 1, 2) for a in pool)


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("heads", [(32, 4), (40, 10)], ids=["32-4", "40-10"])
def test_head_major_pool_over_pages_a_step(heads, n):
    """The head-major pool ``[P, Hkv, page, Dh]`` (column order ``h *
    page + t``) at Trinity-Mini's 4 and Phi-4-flash's 10 KV heads: the
    gather's values at every N, the one-page walk's bits."""
    hq, hkv = heads
    pos, live = _group_rows(4)
    tables, used = _group_tables(live)
    rng = np.random.default_rng(hq + n)
    k, v = _bf16_pool(rng, used, hkv)
    q = jnp.asarray(rng.standard_normal((len(pos), 1, hq, Dh)), jnp.bfloat16)
    got = _walk(q, _head_major((k, v)), tables, pos, n, head_major=True)
    _assert_close(got, _reference(q, k, v, tables, jnp.asarray(pos, jnp.int32)))
    np.testing.assert_array_equal(
        got, _walk(q, _head_major((k, v)), tables, pos, 1, head_major=True)
    )


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_grouped_work_list(case, t, n):
    """The list at N pages a step: ``sum ceil(n_b / N)`` steps for the
    same live pages; a row's groups start at 0, N, 2N ..; place ``k`` of
    a step names the table entry of page ``first + k`` where that page is
    live, and where it is not (the row's last group) the pool page the
    same place held one step earlier, so nothing is fetched for it."""
    pos = np.asarray(WALK_CASES[case])
    tables = _walk_tables(list(pos), t)
    work = pa.page_work_list(tables, jnp.asarray(pos, jnp.int32), t, PAGE, n)
    live = np.minimum(pos + t - 1, S - 1) // PAGE + 1
    groups = -(-live // n)
    steps = int(work.n_work[0])
    assert steps == groups.sum()
    length = len(pos) * (-(-PMAX // n))
    assert work.row.shape == work.page.shape == (length,)
    assert work.phys.shape == (length * n,)
    row, first = np.asarray(work.row)[:steps], np.asarray(work.page)[:steps]
    np.testing.assert_array_equal(row, np.repeat(np.arange(len(pos)), groups))
    np.testing.assert_array_equal(first, np.concatenate([n * np.arange(m) for m in groups]))
    phys = np.asarray(work.phys).reshape(length, n)
    tab = np.asarray(tables)
    for i in range(steps):
        for k in range(n):
            if first[i] + k < live[row[i]]:  # ascending inside the group
                assert phys[i, k] == tab[row[i], first[i] + k]
            elif i and any(first[h] + k < live[row[h]] for h in range(i)):
                assert phys[i, k] == phys[i - 1, k]  # unchanged block index: no DMA
            else:
                assert phys[i, k] == phys[i, 0]
    assert int(work.row.max()) < len(pos) and int(work.phys.max()) < WALK_POOL


@pytest.mark.parametrize("planes", ["token_major", "lane_dense"])
@pytest.mark.parametrize("n", [2, 4])
def test_dead_places_of_a_group_are_never_read(n, planes):
    """Point every dead place of the list at a pool page of NaNs: the
    output does not change by a bit."""
    pos, live = _group_rows(n)
    tables, used = _group_tables(live)
    rng = np.random.default_rng(5 + n)
    k, v, ks, vs = _int8_pool(rng, used + 1, DENSE_HKV if planes == "lane_dense" else 8)
    ks, vs = ks.at[used].set(jnp.nan), vs.at[used].set(jnp.nan)
    if planes == "lane_dense":
        k, v, ks, vs = _lane_dense((k, v, ks, vs))
    q = jnp.asarray(rng.standard_normal((len(pos), 1, 32, Dh)), jnp.bfloat16)
    posj = jnp.asarray(pos, jnp.int32)
    work = pa.page_work_list(tables, posj, 1, PAGE, n)
    want = pa.paged_attention(q, k, v, tables, posj, ks, vs, interpret=True, work=work)
    place = np.asarray(work.page)[:, None] + np.arange(n)[None, :]
    dead = place >= np.asarray(live)[np.asarray(work.row)][:, None]
    assert dead[: int(work.n_work[0])].any()
    poisoned = work._replace(
        phys=jnp.where(jnp.asarray(dead).reshape(-1), used, work.phys)
    )
    got = pa.paged_attention(q, k, v, tables, posj, ks, vs, interpret=True, work=poisoned)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_the_list_built_without_naming_n_is_one_page_an_item_and_a_latent_read_names_its_own():
    """A list built without naming N is the list of PR 27, one page an
    item, three maps of one length; ``ops/latent_attention.py`` builds
    the same function's list at the N its own rule names
    (``decode_work_list``, PR 51)."""
    from generativeaiexamples_tpu.ops import latent_attention

    pos = jnp.asarray(WALK_CASES["dead_row_between_live_rows"], jnp.int32)
    tables = _walk_tables(WALK_CASES["dead_row_between_live_rows"], 1)
    work = pa.page_work_list(tables, pos, 1, PAGE)
    assert latent_attention.page_work_list is pa.page_work_list
    assert work.row.shape == work.page.shape == work.phys.shape == (4 * PMAX,)
    n = int(work.n_work[0])
    np.testing.assert_array_equal(
        np.asarray(work.phys)[:n],
        np.asarray(tables)[np.asarray(work.row)[:n], np.asarray(work.page)[:n]],
    )
    for a, b in zip(work, pa.page_work_list(tables, pos, 1, PAGE, group=1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pool = jnp.zeros((2, PAGE, 128), jnp.bfloat16)
    n = latent_attention.latent_pages_per_step(PAGE, 128, pool.dtype, PMAX)
    for a, b in zip(latent_attention.decode_work_list(pool, tables, pos), pa.page_work_list(tables, pos, 1, PAGE, n)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "shape,dtype,scales,expect",
    [
        ((128, 8, 128), "int8", (8, 128), RULE["mistral"]),  # lane-dense scales: 4 KB a block, pairs
        ((128, 8, 128), "int8", (128, 8), 1),  # token-major scales (a head-sharded pool): padded blocks, walks alone
        ((4, 128, 128), "bfloat16", None, RULE["trinity"]),  # 262,144 B
        ((10, 128, 128), "bfloat16", None, RULE["phi4flash"]),  # 655,360 B
        ((128, 8, 64), "uint8", (8, 128), 2),  # the packed int4 pool, lane-dense
        ((128, 8, 64), "uint8", (128, 8), 1),  # ... token-major
        ((128, 32, 128), "bfloat16", None, 1),  # 2 MB a pair: bound by its bytes, walks alone
        ((8, 2, 16), "bfloat16", None, 2),  # the tests' tiny pages
    ],
)
def test_pages_a_step_follow_the_bytes_of_a_page(shape, dtype, scales, expect):
    """N is a function of what is static alone: the pool's dtype, the
    bytes a page moves, how the pool stores a page's scales and the
    queries a row holds (a page with padded scale blocks and a
    multi-query page keep their own step)."""
    k = jax.ShapeDtypeStruct((7,) + shape, dtype)
    s = jax.ShapeDtypeStruct((7,) + scales, jnp.float32) if scales else None
    assert pa.pages_per_step(k, s) == expect
    assert pa.pages_per_step(k, s, query_len=5) == 1
    if shape[0] == 128:  # token-major: the engine's host-side form agrees
        page, hkv, dh = shape
        dh = dh * 2 if dtype == "uint8" else dh
        assert pa.pool_pages_per_step(page, hkv, dh, dtype, scales) == expect


@pytest.mark.parametrize(
    "kw,expect",
    [
        # the serving shape: 128-token pages, 128-lane heads, 8 KV heads
        (dict(page_size=128, head_dim=128, num_heads=32, num_kv_heads=8), True),
        # head_dim off the lane grid
        (dict(page_size=128, head_dim=96, num_heads=32, num_kv_heads=8), False),
        # merged sublane (page * Hkv) off the int8 tile grid
        (dict(page_size=8, head_dim=128, num_heads=32, num_kv_heads=1), False),
        # GQA mismatch is structural — refused even in interpret
        (dict(page_size=128, head_dim=128, num_heads=30, num_kv_heads=8), False),
        # head count off the 8-sublane grid
        (dict(page_size=128, head_dim=128, num_heads=4, num_kv_heads=2), False),
        # prefill-length chunks exceed the query-row cap
        (
            dict(page_size=128, head_dim=128, num_heads=32, num_kv_heads=8,
                 query_len=512),
            False,
        ),
        # spec-verify widths fit
        (
            dict(page_size=128, head_dim=128, num_heads=32, num_kv_heads=8,
                 query_len=5),
            True,
        ),
    ],
)
def test_supports_geometry_matrix(kw, expect):
    assert pa.supports_geometry(**kw) is expect


def test_supports_geometry_interpret_relaxes_tiling_only():
    # tiling constraints waived (CPU debug engines)...
    assert pa.supports_geometry(
        8, 16, 4, 2, interpret=True
    )
    # ...but structure (GQA divisibility, row cap) still binds
    assert not pa.supports_geometry(8, 16, 30, 8, interpret=True)
    assert not pa.supports_geometry(
        8, 16, 4, 2, query_len=1000, interpret=True
    )


# ------------------------------------------------------------------ //
# packed int4 pools (two values per byte, split-halves codec)


def _int4_pool(rng, pool=POOL, hkv=Hkv, page=PAGE):
    """Quantize a random f32 pool through the engine codec: packed
    uint8 [pool, page, hkv, Dh//2] + page-granular f32 scales."""
    kf = rng.standard_normal((pool, page, hkv, Dh)).astype(np.float32)
    vf = rng.standard_normal((pool, page, hkv, Dh)).astype(np.float32)
    kq, ks = llama.quantize_kv_int4(jnp.asarray(kf))
    vq, vs = llama.quantize_kv_int4(jnp.asarray(vf))
    return kq, vq, ks, vs


def _unpack_pool(packed):
    """Widen a packed pool back to its int values for the reference."""
    return llama.unpack_int4(packed)


def test_int4_codec_round_trips_exactly():
    """quantize_kv_int4 -> unpack_int4 reproduces the clipped int rows
    bit-for-bit, never emits -8, and dequant is exact through f32."""
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.standard_normal((5, 7, Dh)).astype(np.float32))
    packed, s = llama.quantize_kv_int4(x)
    assert packed.dtype == jnp.uint8 and packed.shape[-1] == Dh // 2
    q = np.asarray(llama.unpack_int4(packed))
    assert q.min() >= -7 and q.max() <= 7
    want = np.clip(
        np.round(np.asarray(x) / np.asarray(s)[..., None]), -7, 7
    ).astype(np.int8)
    np.testing.assert_array_equal(q, want)


def test_int4_kernel_matches_reference_over_ragged_tables():
    rng = np.random.default_rng(11)
    tables = _ragged_tables(rng)
    kq, vq, ks, vs = _int4_pool(rng)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, Dh)), jnp.bfloat16)
    pos = jnp.asarray([3, 25, S - 1], jnp.int32)
    out = pa.paged_attention(q, kq, vq, tables, pos, ks, vs, interpret=True)
    ref = _reference(
        q, _unpack_pool(kq), _unpack_pool(vq), tables, pos, ks, vs
    )
    _assert_close(out, ref)


def test_int4_multi_query_causal_chunk():
    """T>1 (spec-verify widths) over the packed pool: per-token causal
    mask agrees with the dequantized reference."""
    rng = np.random.default_rng(12)
    tables = _ragged_tables(rng)
    kq, vq, ks, vs = _int4_pool(rng)
    T = 3
    q = jnp.asarray(rng.standard_normal((B, T, Hq, Dh)), jnp.bfloat16)
    pos = jnp.asarray([0, 10, 40], jnp.int32)
    out = pa.paged_attention(q, kq, vq, tables, pos, ks, vs, interpret=True)
    ref = _reference(
        q, _unpack_pool(kq), _unpack_pool(vq), tables, pos, ks, vs
    )
    _assert_close(out, ref)


def test_int4_dead_pages_never_contribute():
    """Poisoning every non-live packed page (0xFF bytes = -1/-1 nibbles,
    huge scales) leaves the output bit-identical — the position mask and
    DMA clamp hold for the packed layout too."""
    rng = np.random.default_rng(13)
    tables = _ragged_tables(rng)
    kq, vq, ks, vs = _int4_pool(rng)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, Dh)), jnp.bfloat16)
    pos = jnp.asarray([5, 20, 30], jnp.int32)
    out = pa.paged_attention(q, kq, vq, tables, pos, ks, vs, interpret=True)
    live = {
        int(tables[b, j])
        for b in range(B)
        for j in range(int(pos[b]) // PAGE + 1)
    }
    live_mask = jnp.isin(jnp.arange(POOL), jnp.asarray(sorted(live)))
    kq2 = jnp.where(live_mask[:, None, None, None], kq, jnp.uint8(0xFF))
    vq2 = jnp.where(live_mask[:, None, None, None], vq, jnp.uint8(0xFF))
    ks2 = jnp.where(live_mask[:, None, None], ks, 1e6)
    vs2 = jnp.where(live_mask[:, None, None], vs, 1e6)
    out2 = pa.paged_attention(q, kq2, vq2, tables, pos, ks2, vs2, interpret=True)
    _assert_close(out2, out, atol=0.0)


def test_int4_partial_page_rows_mask_to_exact_position():
    rng = np.random.default_rng(14)
    tables = _ragged_tables(rng)
    kq, vq, ks, vs = _int4_pool(rng)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, Dh)), jnp.bfloat16)
    pos = jnp.asarray([3, 20, 30], jnp.int32)  # row 0 lives in page 1 rows 0..3
    out = pa.paged_attention(q, kq, vq, tables, pos, ks, vs, interpret=True)
    kq2 = kq.at[1, 4:].set(jnp.uint8(0xFF))
    vq2 = vq.at[1, 4:].set(jnp.uint8(0xFF))
    out2 = pa.paged_attention(q, kq2, vq2, tables, pos, ks, vs, interpret=True)
    _assert_close(out2[0], out[0], atol=0.0)


@pytest.mark.parametrize(
    "kw,expect",
    [
        # stored dim 128 lanes: head_dim 256 packs to 128 -> accepted
        (dict(page_size=128, head_dim=256, num_heads=32, num_kv_heads=8,
              kv_dtype="int4"), True),
        # head_dim 128 packs to 64 -> off the lane grid in compiled mode
        (dict(page_size=128, head_dim=128, num_heads=32, num_kv_heads=8,
              kv_dtype="int4"), False),
        # odd head_dim cannot pack at all — structural, even in interpret
        (dict(page_size=8, head_dim=17, num_heads=4, num_kv_heads=2,
              kv_dtype="int4", interpret=True), False),
        # interpret waives the lane tiling for the packed dim too
        (dict(page_size=8, head_dim=16, num_heads=4, num_kv_heads=2,
              kv_dtype="int4", interpret=True), True),
    ],
)
def test_supports_geometry_int4_matrix(kw, expect):
    assert pa.supports_geometry(**kw) is expect


@pytest.mark.parametrize(
    "kw,expect",
    [
        # per-shard tile (8 q heads, 2 kv heads) still passes every check
        (dict(page_size=128, head_dim=128, num_heads=32, num_kv_heads=8,
              shards=4), True),
        # 8-way shard leaves 4 q heads/device — off the 8-sublane grid
        (dict(page_size=128, head_dim=128, num_heads=32, num_kv_heads=8,
              shards=8), False),
        # head counts must divide by the shard count
        (dict(page_size=128, head_dim=128, num_heads=32, num_kv_heads=8,
              shards=3), False),
        # per-shard kv head count falls off the sublane grid
        (dict(page_size=8, head_dim=128, num_heads=32, num_kv_heads=16,
              shards=16), False),
        # interpret: structural checks still bind on the per-shard tile
        (dict(page_size=8, head_dim=16, num_heads=8, num_kv_heads=2,
              shards=2, interpret=True), True),
        (dict(page_size=8, head_dim=16, num_heads=8, num_kv_heads=2,
              shards=4, interpret=True), False),
    ],
)
def test_supports_geometry_shards_matrix(kw, expect):
    assert pa.supports_geometry(**kw) is expect


# ------------------------------------------------------------------ //
# shard_map TP wrapper: heads shard over the model axis, tables
# replicate — per-device outputs concatenate to the single-device result


TP_Hq, TP_Hkv = 16, 8  # divisible by the 8-device virtual mesh


@pytest.fixture(scope="module")
def tp_ctx():
    from generativeaiexamples_tpu.parallel import tp_kernels
    from generativeaiexamples_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(tensor_parallelism=8)
    return tp_kernels, tp_kernels.TPContext(mesh, 8, interpret=True)


def _tp_tables():
    tables = np.zeros((B, PMAX), np.int32)
    tables[0, :2] = [1, 2]
    tables[1, :5] = [3, 4, 5, 6, 7]
    tables[2, :] = np.arange(8, 8 + PMAX)
    return jnp.asarray(tables)


def test_paged_attention_tp_bf16_matches_single_device(tp_ctx):
    tp_kernels, tp = tp_ctx
    rng = np.random.default_rng(20)
    tables = _tp_tables()
    k = jnp.asarray(rng.standard_normal((POOL, PAGE, TP_Hkv, Dh)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((POOL, PAGE, TP_Hkv, Dh)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, 1, TP_Hq, Dh)), jnp.bfloat16)
    pos = jnp.asarray([9, 33, S - 1], jnp.int32)
    got = tp_kernels.paged_attention_tp(q, k, v, tables, pos, tp=tp)
    want = pa.paged_attention(q, k, v, tables, pos, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


def test_paged_attention_tp_int4_matches_single_device(tp_ctx):
    """The packed pool shards over its head axis the same way — each
    device unpacks only its own heads' nibbles. Bit parity with the
    single-device kernel, multi-query chunk included."""
    tp_kernels, tp = tp_ctx
    rng = np.random.default_rng(21)
    tables = _tp_tables()
    kf = rng.standard_normal((POOL, PAGE, TP_Hkv, Dh)).astype(np.float32)
    vf = rng.standard_normal((POOL, PAGE, TP_Hkv, Dh)).astype(np.float32)
    kq, ks = llama.quantize_kv_int4(jnp.asarray(kf))
    vq, vs = llama.quantize_kv_int4(jnp.asarray(vf))
    T = 3
    q = jnp.asarray(rng.standard_normal((B, T, TP_Hq, Dh)), jnp.bfloat16)
    pos = jnp.asarray([4, 21, 40], jnp.int32)
    got = tp_kernels.paged_attention_tp(
        q, kq, vq, tables, pos, ks, vs, tp=tp
    )
    want = pa.paged_attention(q, kq, vq, tables, pos, ks, vs, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


# ------------------------------------------------------------------ //
# the counter the walk brings: decode spans of the dispatch timeline


@pytest.mark.parametrize("pool", ["bfloat16", "int8-lane-dense"])
def test_decode_span_carries_pages_walked_and_the_dense_grid(pool):
    """A served decode dispatch records what the kernel walks at its
    first step (live rows' pages up to the query position, one scratch
    page per empty slot — page_work_list's count, from the host's
    position shadow) beside the slots x Pmax grid it replaced, and the
    grid steps that carry those pages (``kv_page_steps``): two pages of
    a row a step for the bfloat16 pool and for the int8 pool whose scale
    planes are lane-dense (the debug model's 2 KV heads at 64-token
    pages), as the engine's ``resolved kernel paths:`` line names them."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine import dispatch_timeline as dtl
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

    page, seq, n_prompt, kv = (8, 64, 20, {}) if pool == "bfloat16" else (64, 256, 160, {"kv_cache_dtype": "int8"})
    pmax = seq // page
    dtl.reset()
    dtl.configure(enable=True)
    eng = LLMEngine(EngineConfig(
        model_config_name="debug-1k", max_batch_size=3, max_seq_len=seq,
        prefill_chunk=2 * page, decode_block=4, decode_runahead=1,
        tensor_parallelism=1, page_size=page,
        paged_kernel="interpret", watchdog_stall_s=0.0, **kv,
    ))
    try:
        assert eng._paged_kernel == "interpret"
        assert eng._kv_scale_layout() == (None if pool == "bfloat16" else "lane_dense")
        prompt = [5 + i % 200 for i in range(n_prompt)]  # first decode query at n_prompt
        params = SamplingParams(temperature=0.0, max_tokens=9, seed=1)
        assert len(list(eng.iter_ids(prompt, params, timeout=300))) == 9
        spans, _ = dtl.spans_since(0)
        decode = [v for v in spans if v["kind"] == "decode"]
        assert decode and all(v["path"] == "kernel" for v in decode)
        for v in decode:
            assert v["kv_pages_grid"] == 3 * pmax
            assert 3 <= v["kv_pages_walked"] <= v["kv_pages_grid"]
            # the rows a page's softmax runs over: the debug model's 4
            # query heads do not fill a vreg, so the wide body's
            assert v["kv_score_rows"] == pa.score_rows(eng._kv_shape.num_heads, eng._kv_shape.num_kv_heads)
        # one live row at position n_prompt (3 pages) + two empty slots;
        # the next block starts 4 positions on (bfloat16: in the fourth page)
        live = [(n_prompt + 4 * i) // page + 1 for i in range(2)]
        assert live == ([3, 4] if pool == "bfloat16" else [3, 3])
        assert [v["kv_pages_walked"] for v in decode[:2]] == [m + 2 for m in live]
        tables = jnp.zeros((3, pmax), jnp.int32)
        n = eng._kv_pages_a_step
        assert n == pa.pages_per_step(eng._cache[0]["k"], eng._cache[0].get("ks")) == 2
        for pos0, v in zip((n_prompt, n_prompt + 4), decode):
            work = pa.page_work_list(tables, jnp.asarray([pos0, 0, 0]), 1, page)
            assert int(work.n_work[0]) == v["kv_pages_walked"]
            # ... and the grid steps that carry them, n pages of a row a step
            steps = pa.page_work_list(tables, jnp.asarray([pos0, 0, 0]), 1, page, n)
            assert int(steps.n_work[0]) == v["kv_page_steps"] == 4  # 3 or 4 pages: 2 steps, + 2 empty slots
            assert v["kv_page_steps"] <= v["kv_pages_walked"]
        # other kinds of span carry no such field
        assert all("kv_pages_walked" not in v for v in spans if v["kind"] != "decode")
    finally:
        eng.shutdown()
        dtl.reset()


# --------------------------------------------------------------------- //
# A chunk wider than the query-row cap reads as sub-rows (the narrow
# rung of chunked prefill, models/llama.py _chunk_layers_paged)


@pytest.mark.parametrize(
    "query_len,heads,expect",
    [
        (128, 32, 16),  # the benchmark's tail: 8 sub-rows of 16 x 32 = 512 rows
        (128, 8, 64),  # four-way sharded heads
        (5, 32, 5),  # spec verify fits whole
        (1, 32, 1),  # decode
        (16, 4, 16),
        (12, 100, 4),  # the largest DIVISOR under the cap (5 does not divide 12)
        (8, 1024, 0),  # the heads alone pass the cap
    ],
)
def test_query_fold(query_len, heads, expect):
    fold = pa.query_fold(query_len, heads)
    assert fold == expect
    if fold:
        assert query_len % fold == 0 and fold * heads <= pa.MAX_QUERY_ROWS


@pytest.mark.parametrize("kv", ["float32", "int8"])
@pytest.mark.parametrize("valid", [[16, 5, 0], [1, 9, 12]])
def test_folded_extend_read_matches_the_gather(monkeypatch, kv, valid):
    """``extend_layers_paged`` of a 16-wide chunk with the kernel's row
    cap lowered to 16 (4 heads: sub-rows of 4 queries) against the same
    walk on the gather: hidden states of the valid tokens' last
    position and every pool byte written."""
    monkeypatch.setattr(pa, "MAX_QUERY_ROWS", 16)
    cfg = llama.PRESETS["debug"]
    params = llama.consume_split_params_layers(llama.init_params_fast(cfg, 0, jnp.float32))
    page, pmax, rows = 8, 8, 3
    assert pa.query_fold(16, cfg.num_heads) == 4
    rng = np.random.default_rng(7)
    tables = jnp.asarray(1 + np.arange(rows * pmax).reshape(rows, pmax), jnp.int32)
    slots = jnp.arange(rows, dtype=jnp.int32)
    first = jnp.asarray(rng.integers(1, cfg.vocab_size, (rows, 16)), jnp.int32)
    tail = jnp.asarray(rng.integers(1, cfg.vocab_size, (rows, 16)), jnp.int32)
    full = jnp.full((rows,), 16, jnp.int32)
    outs = {}
    for kernel in (None, "interpret"):
        caches = llama.init_kv_pool(cfg, 1 + rows * pmax, page, jnp.float32, quantized=kv == "int8")
        _, caches = llama.extend_layers_paged(
            params, cfg, first, jnp.zeros((rows,), jnp.int32), full, slots, tables, caches, 64, page,
        )
        outs[kernel] = llama.extend_layers_paged(
            params, cfg, tail, full, jnp.asarray(valid, jnp.int32), slots, tables, caches, 64, page,
            page_kernel=kernel,
        )
    (h_g, c_g), (h_k, c_k) = outs[None], outs["interpret"]
    live = np.asarray(valid) > 0
    # layer 0's K/V does not depend on the read; layer 1's does, through the hidden state
    np.testing.assert_allclose(np.asarray(h_k)[live], np.asarray(h_g)[live], atol=2e-2, rtol=2e-2)
    for a, b in zip(jax.tree.leaves(c_g[0]), jax.tree.leaves(c_k[0])):
        assert bool(jnp.all(a[1:] == b[1:]))
