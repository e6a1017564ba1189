"""``ops/selected_chunk_read.py`` (the chunk walk's block-sparse read:
scores, masks and probabilities in VMEM) in interpret mode against the
XLA loop it replaces (``models/minimaxm3.py`` ``_attend_selected_blocks``),
the work list it walks and what a layer's selection adds to it, and the
rule of shapes that decides which of the two the family serves with.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import minimaxm3 as m
from generativeaiexamples_tpu.ops import selected_chunk_read as scr

# widths that tile the chip, at head counts and a context a CPU walks in a moment
HQ, HK, DH, PAGE, PMAX = 4, 2, 128, 128, 12
CFG = dataclasses.replace(m.PRESETS["minimaxm3-debug"], num_heads=HQ, num_kv_heads=HK, head_dim=DH, msa_block=PAGE,
                          msa_topk=2, max_seq_len=PAGE * PMAX)
BP = scr.block_pages(PAGE, PMAX)
BLOCK = BP * PAGE

# name -> (T, offsets, valid, msa_topk, what the selection's scores are)
CASES = {
    # one tile; six candidates for two places: top-k bites
    "one_tile_topk_bites": (128, [8 * PAGE], [128], 2, "random"),
    # two tiles of 256 at an offset that is no multiple of the page, the second wholly past the valid tokens
    "wide_chunk_offset_off_the_page": (512, [5 * PAGE + 37], [200], 2, "random"),
    # more places than candidates: every complete page is read, the walk is dense
    "topk_does_not_bite": (256, [4 * PAGE + 5], [256], 16, "random"),
    # rows of different depth in one call, one of them dead
    "two_depths_and_a_dead_row": (256, [0, 7 * PAGE + 52, 300], [256, 130, 0], 2, "random"),
    # three tiles of 128; the chunk writes the last tokens of pages 2 and 3, its later queries score them highest
    "a_page_the_chunk_completes_is_selected": (384, [3 * PAGE - 20], [384], 2, "prefer_new"),
    # every query keeps pages 1 and 2: at 8+ pages deep the second block of four holds nothing a first-tile
    # query selected (its local and open pages lie in the third): the list's item is skipped
    "a_block_no_query_of_the_tile_selects": (128, [9 * PAGE], [128], 2, "prefer_old"),
}


def operands(case, dtype=jnp.float32, seed=0, poison=None):
    T, offsets, valid, topk, kind = CASES[case]
    N = len(offsets)
    ks = jax.random.split(jax.random.key(seed), 4)
    P = 1 + N * PMAX
    k = jax.random.normal(ks[0], (P, HK, PAGE, DH), jnp.float32)
    v = jax.random.normal(ks[1], (P, HK, PAGE, DH), jnp.float32)
    q = jax.random.normal(ks[2], (N, T, HQ, DH), jnp.float32) * 0.3
    tables = jnp.asarray(1 + np.random.default_rng(seed).permutation(N * PMAX).reshape(N, PMAX), jnp.int32)
    offsets, valid = jnp.asarray(offsets, jnp.int32), jnp.asarray(valid, jnp.int32)
    positions = jnp.minimum(offsets[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :], PMAX * PAGE - 1)
    n_tokens = jnp.where(valid > 0, offsets + valid, 0)
    page = jnp.arange(PMAX, dtype=jnp.float32)
    scores = {"random": jax.random.normal(ks[3], (N, T, HK, PMAX)),
              "prefer_new": jnp.broadcast_to(page, (N, T, HK, PMAX)),
              "prefer_old": jnp.broadcast_to(-page, (N, T, HK, PMAX))}[kind]
    sel_pages, sel_valid = m.select_pages(scores, positions, dataclasses.replace(CFG, msa_topk=topk))
    if poison is not None:
        # every cached row at or past a row's n_tokens, and the scratch page: another tenant's values
        s = jnp.arange(PMAX * PAGE, dtype=jnp.int32).reshape(PMAX, 1, PAGE, 1)
        for n in range(N):
            dead = s >= n_tokens[n]
            k = k.at[tables[n]].set(jnp.where(dead, poison, k[tables[n]]))
            v = v.at[tables[n]].set(jnp.where(dead, poison, v[tables[n]]))
        k, v = k.at[0].set(poison), v.at[0].set(poison)
    return dict(q=q.astype(dtype), pool={"k": k.astype(dtype), "v": v.astype(dtype)}, tables=tables,
                positions=positions, n_tokens=n_tokens, sel_pages=sel_pages, sel_valid=sel_valid, valid=valid)


def xla(a):
    return np.asarray(m._attend_selected_blocks(a["q"], a["pool"], a["tables"], a["positions"], a["n_tokens"],
                                                a["sel_pages"], a["sel_valid"]))


def kernel(a):
    P = a["pool"]["k"].shape[0]
    work = scr.chunk_work_list(a["tables"], a["positions"], a["n_tokens"], PAGE, P)
    src, n_read = scr.chunk_live_steps(work, a["sel_pages"], a["sel_valid"])
    out = scr.selected_chunk_read(a["q"], a["pool"]["k"], a["pool"]["v"], a["positions"], a["sel_pages"],
                                  a["sel_valid"], work, src, interpret=True)
    return np.asarray(out), work, np.asarray(src).reshape(HK, -1), int(n_read)


def live_queries(a):
    """[N, T] bool: the queries whose output a walk keeps."""
    return np.arange(a["q"].shape[1])[None, :] < np.asarray(a["valid"])[:, None]


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_agrees_with_the_xla_loop(case):
    """float32 operands, products at full precision: what is left is the
    order of the sums (the kernel's tiles walk no further than they
    reach, the loop walks every row as far as the deepest)."""
    with jax.default_matmul_precision("highest"):
        a = operands(case)
        ref = xla(a)
        out, work, src, n_read = kernel(a)
    N, T = a["positions"].shape
    assert out.shape == ref.shape == (N, T, HQ, DH) and out.dtype == np.float32
    assert np.isfinite(out).all()  # a dead row and a padded query too
    keep = live_queries(a)
    assert np.max(np.abs(out - ref)[keep]) < 1e-5 * max(1.0, float(np.abs(ref[keep]).max()))
    # the list: every (row, tile) has an item, no tile walks past what its last position and n_tokens reach
    n = int(work.n_work[0])
    tq = scr.query_tile(T)
    row, tile, block = (np.asarray(x)[:n] for x in (work.row, work.tile, work.block))
    assert sorted(set(zip(row.tolist(), tile.tolist()))) == [(r, t) for r in range(N) for t in range(T // tq)]
    pos, n_tok = np.asarray(a["positions"]), np.asarray(a["n_tokens"])
    for r, t, b in zip(row, tile, block):
        first, last = pos[r, t * tq], pos[r, (t + 1) * tq - 1]
        reach = min(last + 1, n_tok[r]) if first < n_tok[r] else 0
        assert b == 0 or b * BLOCK < reach
    assert n_read <= HK * n
    if case == "topk_does_not_bite":
        assert n_read == HK * n and bool(np.all(np.asarray(a["sel_valid"]).sum(-1)[keep] >= 3))
    if case == "one_tile_topk_bites":
        assert int(np.asarray(a["sel_valid"]).sum(-1).max()) == 5  # first, two of six candidates, local, open
    if case == "a_page_the_chunk_completes_is_selected":
        # the last query (block 5) keeps pages 2 and 3, whose last tokens this chunk wrote
        assert set(np.asarray(a["sel_pages"])[0, -1, 0, 1:3].tolist()) == {2, 3}
        first_new = 3 * PAGE - 20
        assert np.asarray(a["positions"])[0, 0] == first_new and first_new // PAGE == 2  # page 2 completed by the chunk
    if case == "a_block_no_query_of_the_tile_selects":
        # three blocks of four pages: the middle one (pages 4-7) holds nothing selected and is left out
        assert n == 3 and n_read == HK * 2 and (src[:, :3] == np.asarray([0, 0, 2])).all()


def test_a_skipped_block_is_neither_read_nor_missed():
    """The block no query selected holds another tenant's values (1e4:
    one product with them would swamp the sum) and the output is the XLA
    loop's all the same; with every item forced live the output is still
    right, so the skip changes time and nothing else."""
    case = "a_block_no_query_of_the_tile_selects"
    with jax.default_matmul_precision("highest"):
        a = operands(case)
        skipped = np.asarray(a["tables"])[0, BP:2 * BP]
        for name in ("k", "v"):
            a["pool"][name] = a["pool"][name].at[skipped].set(1e4)
        ref = xla(a)
        out, work, src, n_read = kernel(a)
        assert n_read == HK * 2 and np.max(np.abs(out - ref)) < 1e-5 * max(1.0, float(np.abs(ref).max()))
        forced = scr.selected_chunk_read(
            a["q"], a["pool"]["k"], a["pool"]["v"], a["positions"], a["sel_pages"], a["sel_valid"], work,
            jnp.tile(jnp.arange(work.row.shape[0], dtype=jnp.int32), HK), interpret=True)
    assert np.max(np.abs(np.asarray(forced) - ref)) < 1e-5 * max(1.0, float(np.abs(ref).max()))


@pytest.mark.parametrize("case", ["wide_chunk_offset_off_the_page", "two_depths_and_a_dead_row"])
def test_bfloat16_operands_and_poisoned_dead_rows(case):
    """The served dtype: bfloat16 pools and queries, every cached row past
    a row's tokens poisoned. Same arithmetic (bfloat16 products, float32
    sums, probabilities rounded once): the two walks differ by where
    their blocks end, a rounding of the probabilities."""
    a = operands(case, jnp.bfloat16, poison=3e4)
    ref = xla(a)
    out, _, _, _ = kernel(a)
    keep = live_queries(a)
    assert np.isfinite(out[keep]).all()
    assert np.max(np.abs(out - ref)[keep]) < 2e-2 * float(np.abs(ref[keep]).max())


def test_the_kind_is_decided_from_shapes_alone():
    full = m.PRESETS["minimax-m3-ep8"]
    assert m.selected_chunk_kind(full, "compiled") == "compiled" and m.selected_chunk_kind(full, None) is None
    for T in (128, 256, 512, 1024):
        assert m.selected_chunk_kind(full, "compiled", T) == "compiled", T
    # a chunk that does not cut into query tiles of whole lane tiles (a query is a lane of the scores)
    for T in (8, 24, 64, 100, 192):
        assert m.selected_chunk_kind(full, "compiled", T) is None, T
    assert m.selected_chunk_kind(CFG, "interpret", 256) == "interpret"
    # a page or a head size that is no lane tile, interpreted or not: a page is a lane tile of the scores
    for change in (dict(msa_block=64), dict(msa_block=8), dict(head_dim=64), dict(head_dim=192), dict(num_heads=3)):
        for kind in ("compiled", "interpret"):
            assert m.selected_chunk_kind(dataclasses.replace(CFG, **change), kind) is None, change
    assert m.selected_chunk_kind(m.PRESETS["minimaxm3-debug"], "interpret") is None
    # a group so wide that one tile's queries, accumulators and output block pass what a step may hold in VMEM
    assert m.selected_chunk_kind(dataclasses.replace(full, num_heads=1024), "compiled", 512) is None
