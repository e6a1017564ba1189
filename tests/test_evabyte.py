"""EvaByte (models/evabyte.py) at a tiny size (hidden 64, 4 heads of 16,
window 32, chunk 4, 2 layers, 40 ids, 2 output heads) and the engine
serving it through the model registry: the plain reference's two
identities with causal attention; the chunk walk and the decode step
through buffer and pages against that reference
(``perfbench/arch/evabyte.py``: an independent implementation) on ALL
output logits, across chunk and window boundaries, slot reuse and
poisoned stale rows; the four wrong forms of the layer failing the limit
the bfloat16 path passes; the stats against a count made by hand.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import evabyte as m
from generativeaiexamples_tpu.models import registry
from generativeaiexamples_tpu.ops import eva_read
from perfbench.arch import evabyte as adapter
from tests.perfbench.test_perfbench_evabyte import TINY


@pytest.fixture(autouse=True, scope="module")
def float32_products():
    """float32 walks are held to a float32 forward: products at full
    precision, for THIS module only."""
    with jax.default_matmul_precision("highest"):
        yield


CFG = m.PRESETS["evabyte-debug"]
FULL = m.PRESETS["evabyte-6.5b-pp4"]
PAGE, SLOTS, PMAX = 8, 3, 16
W, C = CFG.window_size, CFG.chunk_size
V_ALL = CFG.num_pred_heads * CFG.vocab_size
# float32 walks against the float32 reference. Not rounding alone: a summary row is kept in bfloat16 whatever the
# buffer holds (two halves of the heads a 32-bit word, ops/eva_read.py), 2^-9 of a row of size ~1, and the summaries
# carry up to half a query's attention here; logits of size ~3 then move by up to ~2e-3. A misplaced row, a wrong
# mask or a missing summary moves them by 0.05 and more (test_the_limit_separates_the_wrong_forms measures four).
TOL = 4e-3
TABLES = jnp.asarray(1 + np.arange(SLOTS * PMAX).reshape(SLOTS, PMAX), jnp.int32)


@pytest.fixture(scope="module")
def params():
    return m.init_params_fast(CFG, 0, jnp.float32)


def reference_logits(params, toks, cfg=TINY, **kw):
    """The plain reference's logits [T, 2 x V] on this parameter tree, every position."""
    host = lambda tree: jax.tree.map(np.asarray, tree)  # noqa: E731
    final = host((params["final_norm"], params["head"]))
    return adapter.forward([list(toks)], cfg, np.asarray(params["embed"]), lambda l: host(params["layers"][l]),
                           final, positions=len(toks), **kw)[0]


def fresh_cache(dtype=jnp.float32):
    return m.init_paged_cache(CFG, 1 + SLOTS * PMAX, PAGE, SLOTS, dtype)


def extend(params, caches, toks, slot, width, start=0, path=None):
    """Chunked extend of ``toks`` (positions ``start ..``) into ``slot``, the chunk walk's read through ``path``;
    returns (all-head logits of the last position, caches)."""
    slots = jnp.asarray([slot], jnp.int32)
    for s in range(0, len(toks), width):
        n = min(width, len(toks) - s)
        piece = jnp.asarray(np.pad(np.asarray(toks[s:s + n], np.int32), (0, width - n))[None])
        hidden, caches = m.extend_paged(params, CFG, caches, piece, jnp.asarray([start + s], jnp.int32),
                                        jnp.asarray([n], jnp.int32), slots, TABLES, 0, PAGE, eva_read=path)
    return np.asarray(m.head(params, CFG, hidden, all_heads=True))[0], caches


def decode(params, caches, tok, pos, slot, path=None):
    """One decode step of ``slot`` alone (the other rows dead); returns (all-head logits, caches)."""
    tokens = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(tok)
    live = jnp.zeros((SLOTS,), bool).at[slot].set(True)
    positions = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(pos)
    logits, caches = m.decode_paged(params, CFG, caches, tokens, positions, live, TABLES, None, PAGE,
                                    eva_read=path, all_heads=True)
    return np.asarray(logits)[slot], caches


def rel_err(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# --------------------------------------------------------------------- //
# The reference's two identities


def plain_causal_logits(params, toks, window=None):
    """Full causal softmax attention (or causal attention inside windows that do not overlap) in the same layer,
    written here from scratch: no chunk, no summary."""
    cfg, T = TINY, len(toks)
    H, Dh = cfg["num_attention_heads"], cfg["hidden_size"] // cfg["num_attention_heads"]
    pos = np.arange(T)
    seen = pos[None, :] <= pos[:, None]
    if window:
        seen &= pos[None, :] // window == pos[:, None] // window
    x = jnp.asarray(params["embed"])[np.asarray(toks)]
    for lp in params["layers"]:
        a = adapter.rms(x, lp["n1"], 1e-5)
        q, k, v = (y.reshape(T, H, Dh) for y in jnp.split(a @ lp["wqkv"], 3, axis=1))
        q, k = adapter.rope_half(q, pos, 1e5), adapter.rope_half(k, pos, 1e5)
        sc = jnp.where(seen[None], jnp.einsum("thd,shd->hts", q, k) * Dh ** -0.5, -jnp.inf)
        x = x + jnp.einsum("hts,shd->thd", jax.nn.softmax(sc, axis=-1), v).reshape(T, H * Dh) @ lp["wo"]
        x = x + adapter.mlp(adapter.rms(x, lp["n2"], 1e-5), lp)
    return np.asarray(adapter.rms(x, params["final_norm"], 1e-5) @ params["head"])


def test_reference_with_chunks_of_one_token_is_full_causal_attention(params):
    """``C = 1``: both chunk softmaxes are over one element, ``kbar = k`` and ``vbar = v``, so a closed window's
    summaries ARE its tokens and the one softmax is causal attention over the whole sequence."""
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, size=(3 * W + 5,))
    got = reference_logits(params, toks, dict(TINY, chunk_size=1))
    np.testing.assert_allclose(got, plain_causal_logits(params, toks), atol=2e-5)
    # and the chunk of four is NOT that: the summaries compress
    assert rel_err(reference_logits(params, toks), got) > 1e-2


def test_reference_inside_one_window_is_causal_attention(params):
    """``T <= W``: no summary is visible. And past it the exact part is the query's own window alone."""
    toks = np.random.default_rng(4).integers(0, CFG.vocab_size, size=(W,))
    np.testing.assert_allclose(reference_logits(params, toks), plain_causal_logits(params, toks), atol=2e-5)
    longer = np.random.default_rng(5).integers(0, CFG.vocab_size, size=(2 * W + 3,))
    ref = reference_logits(params, longer)
    np.testing.assert_allclose(ref[:W], plain_causal_logits(params, longer[:W]), atol=2e-5)
    assert rel_err(ref[W:], plain_causal_logits(params, longer, window=W)[W:]) > 1e-2  # the summaries carry weight


# --------------------------------------------------------------------- //
# The walks against the reference


@pytest.mark.parametrize("prompt,steps", [(13, 8), (27, 8), (59, 8), (27, 40), (70, 5), (96, 3)],
                         ids=["crosses_chunks", "closes_a_window", "closes_a_second_window", "closes_two_windows",
                              "two_windows_behind", "starts_a_window"])
@pytest.mark.parametrize("path", [None, "interpret"], ids=["xla_read", "kernel_read"])
def test_extend_then_decode_is_the_reference_on_all_logits(params, prompt, steps, path):
    """Chunked extend from offset 0 (chunks of 16: the last one partial, lengths that are no multiple of the
    chunk of 4), then decode steps through buffer and pages: ALL 80 logits of every position are the plain
    reference's whole forward, across a chunk's end, a window's end and two of them."""
    toks = np.random.default_rng(prompt).integers(0, CFG.vocab_size, size=(prompt + steps,))
    ref = reference_logits(params, toks)
    assert ref.shape == (prompt + steps, V_ALL)
    logits, caches = extend(params, fresh_cache(), toks[:prompt], slot=1, width=16, path=path)
    assert rel_err(logits, ref[prompt - 1]) < TOL
    for j in range(steps):
        logits, caches = decode(params, caches, toks[prompt + j], prompt + j, slot=1, path=path)
        assert rel_err(logits, ref[prompt + j]) < TOL, (j, prompt + j)


@pytest.mark.parametrize("width", [8, 16, 32])
@pytest.mark.parametrize("path", [None, "interpret"], ids=["xla_read", "kernel_read"])
def test_extend_in_chunks_of_any_width_equals_extend_whole(params, width, path):
    """The widths an engine can send (whole pages that divide the window), their reads through ``path``, against
    the whole prompt in one program (``prefill_paged``, its reads through XLA), and the caches they leave: the
    same buffer rows and the same summaries."""
    T = 2 * W + 21
    toks = np.random.default_rng(9).integers(0, CFG.vocab_size, size=(T,))
    whole, cache_whole = m.prefill_paged(params, CFG, fresh_cache(), jnp.asarray(toks[None], jnp.int32),
                                         jnp.asarray([T]), jnp.asarray([2]), TABLES, PAGE)
    logits, caches = extend(params, fresh_cache(), toks, slot=2, width=width, path=path)
    np.testing.assert_allclose(logits[: CFG.vocab_size], np.asarray(whole)[0], atol=2e-5)
    for l in range(CFG.num_layers):
        np.testing.assert_allclose(np.asarray(caches["win"][l]["k"][2, : T % W]),
                                   np.asarray(cache_whole["win"][l]["k"][2, : T % W]), atol=2e-5)
        closed = TABLES[2, : 2 * W // PAGE]
        assert np.array_equal(np.asarray(caches["sum"][l]["v"][closed]), np.asarray(cache_whole["sum"][l]["v"][closed]))


def test_a_chunk_that_could_straddle_a_window_is_refused(params):
    with pytest.raises(ValueError, match="straddle"):
        extend(params, fresh_cache(), list(range(24)), slot=0, width=24)
    with pytest.raises(ValueError, match="page_size"):
        m.init_paged_cache(CFG, 9, 6, 2)


@pytest.mark.parametrize("path", [None, "interpret"], ids=["xla_read", "kernel_read"])
def test_stale_buffer_rows_and_the_open_windows_pages_are_never_read(params, path):
    """A row 11 tokens into its third window: the buffer past its valid length (a former tenant's rows), the
    pages of the open window (written as its chunks complete, not visible before it closes) and the pages past
    them are poisoned with large values; the next steps' logits do not move by a bit."""
    T = 2 * W + 11
    toks = np.random.default_rng(11).integers(0, CFG.vocab_size, size=(T + 3,))
    _, caches = extend(params, fresh_cache(), toks[:T], slot=0, width=16)
    poisoned = {"win": [], "sum": [], "stats": caches["stats"]}
    first_open = 2 * W // PAGE
    for l in range(CFG.num_layers):
        poisoned["win"].append({n: caches["win"][l][n].at[0, T % W + 3:].set(1e4) for n in ("k", "v")})
        poison = eva_read.pack_rows(jnp.full((PAGE // C, CFG.num_heads, CFG.head_dim), 1e4))
        poisoned["sum"].append({n: caches["sum"][l][n].at[TABLES[0, first_open + 1:]].set(poison) for n in ("k", "v")})
    for j in range(3):
        clean, caches = decode(params, caches, toks[T + j], T + j, slot=0, path=path)
        dirty, poisoned = decode(params, poisoned, toks[T + j], T + j, slot=0, path=path)
        assert np.array_equal(clean, dirty)


def test_the_kernel_reads_what_the_xla_read_reads():
    """Rows at every stage at once (inside the first window, on a window's last token, several windows deep, a
    dead row at position 0) over random buffers and pages: one softmax, the same to float32 rounding."""
    rng = np.random.default_rng(2)
    H, Dh, B, pmax = 4, 16, 5, 16
    positions = jnp.asarray([5, W - 1, 3 * W + 17, 0, 2 * W], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
    win_k, win_v = (jnp.asarray(rng.normal(size=(B, W, H * Dh)), jnp.float32) for _ in range(2))
    sum_k, sum_v = (eva_read.pack_rows(jnp.asarray(rng.normal(size=(1 + B * pmax, PAGE // C, H, Dh)), jnp.float32))
                    for _ in range(2))
    tables = jnp.asarray(1 + rng.permutation(B * pmax).reshape(B, pmax), jnp.int32)
    want = eva_read.eva_decode_read_xla(q, win_k, win_v, sum_k, sum_v, tables, positions, window=W,
                                        chunks_a_window=W // C)
    work = eva_read.work_list(tables, positions, W, PAGE)
    # 1 + 1 + (1 + 3) + 1 + (1 + 2) steps: a window step a row (a window of 32 is one tile) and one a closed window
    assert int(work.n_work[0]) == 10 and work.valid.tolist() == [6, W, 18, 1, 1]
    got = eva_read.eva_decode_read(q, win_k, win_v, sum_k, sum_v, work, window=W, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # the packing is exact on bfloat16 values
    x = jnp.asarray(rng.normal(size=(3, H, Dh)), jnp.bfloat16)
    assert np.array_equal(np.asarray(eva_read.unpack_rows(eva_read.pack_rows(x), H)), np.asarray(x, np.float32))


# (offsets, valid) of two rows (slots 2 and 0) walked as one chunk of 16 queries
CHUNK_CASES = {
    "at_zero_and_inside_the_first_window": ([0, 16], [16, 16]),
    "at_a_window_boundary_and_three_windows_deep": ([2 * W, 3 * W + 16], [16, 16]),
    "partly_padding": ([W + 8, 4 * W], [11, 3]),
    "one_row_with_nothing_valid": ([3 * W + 8, 8], [0, 16]),
}


@pytest.mark.parametrize("tiles", [(None, None), (8, 8)], ids=["one_tile", "tiles_of_8"])
@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_the_chunk_kernel_reads_what_attend_reads(case, tiles):
    """``eva_chunk_read`` over the buffer AFTER the chunk's keys went into it, against ``_attend`` over buffer,
    chunk and gathered summaries: the same to float32 rounding on every valid query, with the buffer's rows past
    the chunk (a former tenant's) and the pages of the OPEN window and past it poisoned with large values. A
    padding query may read the stale rows: its output feeds nothing and is finite."""
    rng = np.random.default_rng(7)
    H, Dh, T, N = CFG.num_heads, CFG.head_dim, 16, 2
    offsets, valid = (jnp.asarray(x, jnp.int32) for x in CHUNK_CASES[case])
    slots = jnp.asarray([2, 0], jnp.int32)
    base, n_closed = np.asarray(offsets) % W, np.asarray(offsets) // W
    q, k, v = (jnp.asarray(rng.normal(size=(N, T, H, Dh)), jnp.float32) for _ in range(3))
    win = {n: rng.normal(size=(SLOTS, W, H * Dh)).astype(np.float32) for n in ("k", "v")}
    pool = {n: np.array(eva_read.pack_rows(jnp.asarray(rng.normal(size=(1 + SLOTS * PMAX, PAGE // C, H, Dh)),
                                                      jnp.float32))) for n in ("k", "v")}
    poison = np.asarray(eva_read.pack_rows(jnp.full((PAGE // C, H, Dh), 1e4)))
    for r in range(N):
        for n in ("k", "v"):
            win[n][int(slots[r]), base[r] + int(valid[r]):] = 1e4  # stale rows past the chunk's valid tokens
            pool[n][np.asarray(TABLES[int(slots[r]), n_closed[r] * (W // PAGE):])] = poison  # the open window's pages on
    win, pool = jax.tree.map(jnp.asarray, (win, pool))
    # what _chunk_walk hands _attend: the buffer below the chunk, the chunk's own keys, every page of the table
    idx = jnp.arange(T)
    n_sum = PMAX * (PAGE // C)
    seen = jnp.concatenate([
        jnp.broadcast_to((jnp.arange(W)[None, :] < base[:, None])[:, None, :], (N, T, W)),
        jnp.broadcast_to((idx[:, None] >= idx[None, :])[None], (N, T, T)),
        jnp.broadcast_to((jnp.arange(n_sum)[None, :] < (n_closed * (W // C))[:, None])[:, None, :], (N, T, n_sum)),
    ], axis=2)
    gathered = {n: eva_read.unpack_rows(pool[n][TABLES[slots]], H).reshape(N, n_sum, H, Dh) for n in ("k", "v")}
    want = m._attend(q, jnp.concatenate([win["k"][slots].reshape(N, W, H, Dh), k, gathered["k"]], axis=1),
                     jnp.concatenate([win["v"][slots].reshape(N, W, H, Dh), v, gathered["v"]], axis=1), seen)
    # the kernel's side: the chunk's valid tokens written first, as _chunk_walk writes them
    at = jnp.where(idx[None, :] < valid[:, None], jnp.asarray(base)[:, None] + idx[None, :], W)
    lead = jnp.broadcast_to(slots[:, None], at.shape)
    wk = win["k"].at[lead, at].set(k.reshape(N, T, H * Dh), mode="drop")
    wv = win["v"].at[lead, at].set(v.reshape(N, T, H * Dh), mode="drop")
    tq, tw = tiles
    work = eva_read.chunk_work_list(TABLES, slots, offsets, valid, T, W, PAGE, SLOTS, 1 + SLOTS * PMAX,
                                    query_tile=tq, window_tile=tw)
    got = eva_read.eva_chunk_read(q.reshape(N, T, H * Dh), wk, wv, pool["k"], pool["v"], work, num_heads=H,
                                  interpret=True, query_tile=tq, window_tile=tw)
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    for r in range(N):
        np.testing.assert_allclose(got[r, : int(valid[r])], want[r, : int(valid[r])], atol=3e-6)


def test_the_chunk_work_list_is_a_count_made_by_hand():
    """Two rows of 16 queries in query tiles of 8 over buffer tiles of 8 rows. Row 0 (slot 2) at offset 2 W + 8,
    all valid: tile 0 sees buffer rows 0 .. 15 (two buffer tiles), tile 1 rows 0 .. 23 (three; the fourth lies
    wholly above it), each then one step a CLOSED window (two); none for the open window's pages. Row 1 (slot 0)
    at offset 3, 5 valid: tile 0 sees rows 0 .. 7 (one buffer tile), tile 1 has no valid query and walks buffer
    tile 0 alone; no window has closed."""
    work = eva_read.chunk_work_list(TABLES, jnp.asarray([2, 0]), jnp.asarray([2 * W + 8, 3]), jnp.asarray([16, 5]),
                                    16, W, PAGE, SLOTS, 1 + SLOTS * PMAX, query_tile=8, window_tile=8)
    n = int(work.n_work[0])
    assert n == (2 + 2) + (3 + 2) + 1 + 1
    steps = lambda name: getattr(work, name)[:n].tolist()  # noqa: E731
    assert steps("row") == [0] * 9 + [1] * 2 and steps("slot") == [2] * 9 + [0] * 2
    assert steps("qtile") == [0] * 4 + [1] * 5 + [0, 1]
    is_sum = [f // 4 % 2 for f in steps("flags")]
    assert is_sum == [0, 0, 1, 1] + [0, 0, 0, 1, 1] + [0, 0]
    assert [t for t, s in zip(steps("tile"), is_sum) if not s] == [0, 1] + [0, 1, 2] + [0, 0]
    assert [f % 2 for f in steps("flags")] == [1, 0, 0, 0] + [1, 0, 0, 0, 0] + [1, 1]  # a tile's first step
    assert [f // 2 % 2 for f in steps("flags")] == [0, 0, 0, 1] + [0, 0, 0, 0, 1] + [1, 1]  # and its last
    # the causal compare only where a buffer tile's last row lies past the tile's first query's own row: query tile 0
    # of row 0 starts at buffer row 8, so buffer tile 0 is seen whole and tile 1 is not; query tile 1 starts at 16;
    # row 1 starts at buffer row 3 (inside tile 0) and its second query tile at 11 (past it)
    assert [f // 8 for f in steps("flags")] == [0, 1, 0, 0] + [0, 0, 1, 0, 0] + [1, 0]
    # a summary step names the four pages of its closed window; a window step keeps the pages of the step before
    ppw = W // PAGE
    phys = np.asarray(work.phys).reshape(-1, ppw)[:n]
    first, second = np.asarray(TABLES[2, :ppw]), np.asarray(TABLES[2, ppw:2 * ppw])
    assert [p.tolist() for p in phys[2:4]] == [first.tolist(), second.tolist()]
    assert [p.tolist() for p in phys[7:9]] == [first.tolist(), second.tolist()]
    assert (phys[:2] == 0).all() and (phys[4:7] == second).all() and (phys[9:] == second).all()
    open_pages = set(np.asarray(TABLES[2, 2 * ppw:]).tolist()) | set(np.asarray(TABLES[0]).tolist())
    assert not open_pages & set(phys.reshape(-1).tolist())


# --------------------------------------------------------------------- //
# The served precision, and the wrong forms it must tell apart

# The bfloat16 path (bfloat16 weights, buffer and products, float32 residual row, norms and softmaxes) against the
# float32 reference on the same weights at six depths (inside one window, on a window's last token, two and three
# windows deep), measured when this test was written: 0.0025-0.0041. The four wrong forms at their clearest depth:
# ``phi`` ignored 0.022, ``mu`` ignored 0.054, no ``-|k|^2 / 2`` 0.145, two softmaxes 0.225. The limit: 2.2 times
# the path's largest reading, 2.4 times under the least wrong form's. (``phi`` is drawn N(0, 0.1): ``s phi . k``
# moves a chunk's value pooling by ~0.1 in the exponent, the smallest term of the layer.)
TOL_BF16 = 0.009
DEPTHS = (21, W - 1, 2 * W + 2, 2 * W + 13, 3 * W + 5, 3 * W + 30)
WRONG_FORMS = ("no_ksq", "no_mu", "no_phi", "two_softmax")


@pytest.fixture(scope="module")
def served_bf16():
    """(bfloat16 parameters, {prompt length: all-head logits of one decode step after a chunked extend})."""
    params = m.init_params_fast(CFG, 0, jnp.bfloat16)
    rows = {}
    for T in DEPTHS:
        toks = np.random.default_rng(T).integers(0, CFG.vocab_size, size=(T + 1,))
        _, caches = extend(params, fresh_cache(jnp.bfloat16), toks[:T], slot=1, width=16, path="interpret")
        rows[T] = (toks, decode(params, caches, toks[T], T, slot=1, path="interpret")[0])
    return params, rows


def test_the_bfloat16_path_is_the_float32_reference_within_its_limit(served_bf16):
    params, rows = served_bf16
    errs = [rel_err(got, reference_logits(params, toks)[-1]) for toks, got in rows.values()]
    assert max(errs) < TOL_BF16, errs


def fault_errs(served_bf16, fault):
    params, rows = served_bf16
    return {T: rel_err(got, reference_logits(params, toks, fault=fault)[-1]) for T, (toks, got) in rows.items()}


@pytest.mark.parametrize("fault", WRONG_FORMS)
def test_the_limit_separates_the_wrong_forms(served_bf16, fault):
    """Each wrong form of the layer planted in the reference (the value pooling without ``-|k|^2 / 2``; a pooling
    that ignores ``mu``, or ``phi``; two reads each normalised by itself) is further from the served path than
    the limit at some depth past one window, and exactly the reference inside one."""
    errs = fault_errs(served_bf16, fault)
    assert max(errs[T] for T in DEPTHS[2:]) > TOL_BF16, errs
    assert max(errs[T] for T in DEPTHS[:2]) < TOL_BF16, errs  # no summary is visible inside one window


def test_an_int8_summary_pool_is_not_a_wrong_form_at_this_limit(served_bf16):
    """ISSUE 57 listed an int8 pool among the forms the limit must fail. Measured, it does not: summary rows
    rounded to int8 with ONE scale a row (coarser than the int8 KV pool's scale a token and head) read what the
    bfloat16 rows read, 0.003-0.004: a summary is 1 row in 16 and bfloat16 keeps 8 bits of it too. An int8
    summary pool is therefore OPEN WORK (PERF.md section 7), not an error this test can name."""
    assert adapter.FAULTS == WRONG_FORMS + ("int8_summaries",)
    errs = fault_errs(served_bf16, "int8_summaries")
    assert max(errs.values()) < TOL_BF16, errs


# --------------------------------------------------------------------- //
# The registry and the engine


def test_registry_resolves_the_family_and_what_it_declares():
    fam, cfg = registry.resolve("evabyte-debug")
    assert fam.name == "evabyte" and fam.fixed_state and fam.verify_paged is None and cfg is CFG
    assert registry.resolve("evabyte-6.5b-pp4")[1] is FULL and registry.family_of(FULL).name == "evabyte"
    shape = fam.paged_kv_shape(FULL)
    assert (shape.num_layers, shape.num_kv_heads, shape.head_dim, shape.num_heads) == (8, 32, 128, 32)
    assert shape.bytes_per_token == 8 * 1024 and not shape.latent
    assert fam.fixed_state_bytes_per_slot(FULL) == 8 * 33_554_432
    assert fam.span_fields(FULL) == {"eva_layers": 8, "eva_window": 2048, "eva_chunk": 16}
    assert fam.state_row_keys == () and not fam.weight_formats and not fam.kv_formats
    assert not fam.sharded and not fam.snapshot_pages and not fam.extend_reads_window and fam.extend_packed is None
    # every resolved kernel path is a keyword of the walks under the SAME name
    assert fam.resolve_kernels(FULL, "compiled") == {"eva_read": "compiled"}
    assert fam.resolve_kernels(cfg, "compiled") == {"eva_read": None}  # heads of 16 do not tile the chip
    assert fam.resolve_kernels(cfg, "interpret") == {"eva_read": "interpret"}
    assert "eva_read" in inspect.signature(m.decode_paged).parameters
    assert fam.stat_names == m.STAT_NAMES
    with pytest.raises(ValueError, match="bfloat16"):
        fam.init_paged_cache(cfg, 9, 8, 2, jnp.bfloat16, quantized=True)
    # the memory plan of the published stage: ISSUE 57's arithmetic
    assert m.count_logical_params(FULL) == 1_630_932_992
    plan = m.serving_memory_bytes(FULL, 24, 20480)
    assert plan["weights"] == 3_261_865_984 and plan["fixed_state"] == 24 * 8 * 33_554_432
    assert plan["kv_cache"] - plan["fixed_state"] == 24 * 20480 * 8192


BASE = dict(
    model_config_name="evabyte-debug", max_batch_size=3, max_seq_len=256, prefill_chunk=32,
    tensor_parallelism=1, decode_block=4, decode_runahead=1, page_size=8, prefix_cache_enable="off",
    dtype="float32", paged_kernel="interpret",
)


@pytest.fixture(scope="module")
def engine():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    eng = LLMEngine(EngineConfig(**BASE))
    eng.warmup()
    yield eng
    eng.shutdown()


def test_engine_serves_every_depth_as_the_references_argmax(engine):
    """Prompts inside one window, ending on a window's last token, and two and four windows deep, more requests
    than slots one after another (a finished request's slot, its restarted buffer and its pages go to a new
    owner): every served token is the plain reference's argmax on the engine's own weights, through the
    interpreted read. Nothing compiles after warm-up."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    assert engine._family.name == "evabyte" and engine._family_kernels == {"eva_read": "interpret"}
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, CFG.vocab_size, size=n)] for n in (5, 31, 70, 150, 29, 64, 9)]
    greedy = SamplingParams(temperature=0.0, max_tokens=7, ignore_eos=True)
    reqs = [engine.submit(p, greedy) for p in prompts]  # seven requests over three slots
    outs = [[t for t in iter(r.out_queue.get, None)] for r in reqs]
    for p, o in zip(prompts, outs):
        assert len(o) == 7
        ref = reference_logits(engine.params, p + o)[:, : CFG.vocab_size]
        assert max(float(ref[len(p) - 1 + j].max() - ref[len(p) - 1 + j][t]) for j, t in enumerate(o)) < 2e-2
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


def eva_counters():
    """The ``genai_engine_eva_*`` lines of ``/metrics``' registry, by name and labels."""
    from generativeaiexamples_tpu.utils import metrics as metrics_mod

    out = {}
    for line in metrics_mod.get_registry().render().splitlines():
        if line.startswith("genai_engine_eva_") and " " in line:
            k, v = line.rsplit(" ", 1)
            out[k] = float(v)
    return out


def chunk_reads(counts, path):
    return counts.get(f'genai_engine_eva_chunk_reads_total{{path="{path}"}}', 0.0)


def test_engine_reads_the_four_counts_back_and_they_are_a_count_made_by_hand(engine):
    from generativeaiexamples_tpu.engine import dispatch_timeline
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    before, cursor = eva_counters(), dispatch_timeline.cursor()
    # 75 prompt tokens: chunks of 32, 32 and 11 (padded to a chunk of 32 or of 8 + ...), then 25 decode steps:
    # positions 75 .. 99, which close the window at 95
    list(engine.iter_ids(list(range(3, 38)) + list(range(3, 38)) + list(range(3, 8)),
                         SamplingParams(temperature=0.0, max_tokens=26, ignore_eos=True), timeout=600))
    grew = {k: v - before.get(k, 0.0) for k, v in eva_counters().items()}
    L, per_window = CFG.num_layers, W // C
    spans = [s for s in dispatch_timeline.spans_since(cursor)[0] if "eva_window_tokens_read" in s]
    chunks = [s for s in spans if s["kind"] == "prefill_chunk"]
    steps = [s for s in spans if s["kind"] == "decode"]
    for s in spans:
        assert (s["eva_layers"], s["eva_window"], s["eva_chunk"]) == (L, W, C)
    # the chunk walk: a query at position p reads p % W + 1 exact keys and 8 summaries a closed window
    assert [s["eva_window_tokens_read"] for s in chunks] == [L * sum(range(1, 33)), L * sum(range(1, 33)),
                                                           L * sum(range(1, 12))]
    assert [s["eva_summaries_read"] for s in chunks] == [0, L * 32 * per_window, L * 11 * 2 * per_window]
    assert [s["eva_summaries_written"] for s in chunks] == [L * 8, L * 8, L * 2]
    assert [s["eva_windows_closed"] for s in chunks] == [L, L, 0]
    # a decode dispatch reports its LAST step: one row at position p
    for s in steps:
        p = s["eva_window_tokens_read"] // L - 1  # p % W
        assert s["eva_summaries_read"] in (L * 2 * per_window, L * 3 * per_window)
        assert s["eva_summaries_written"] == (L if p % C == C - 1 else 0)
        assert s["eva_windows_closed"] == (L if p == W - 1 else 0)
    assert {s["eva_summaries_read"] for s in steps} == {L * 2 * per_window, L * 3 * per_window}  # 95 closed a window
    assert grew["genai_engine_eva_windows_closed_total"] >= 2 * L
    assert grew["genai_engine_eva_summaries_written_total"] >= 18 * L
    assert grew["genai_engine_eva_window_tokens_read_total"] >= L * (2 * sum(range(1, 33)) + sum(range(1, 12)))
    assert grew["genai_engine_eva_summaries_read_total"] >= L * (32 + 22) * per_window
    # which body read the chunks: under ``interpret`` every layer of every chunk went through the kernel
    # (ops/eva_read.py eva_chunk_read), a decode step through neither
    assert [(s["eva_chunk_kernel_layers"], s["eva_chunk_xla_layers"]) for s in chunks] == [(L, 0)] * 3
    assert all(s["eva_chunk_kernel_layers"] == s["eva_chunk_xla_layers"] == 0 for s in steps)
    after = eva_counters()
    assert chunk_reads(after, "kernel") - chunk_reads(before, "kernel") == 3 * L
    assert chunk_reads(after, "xla") == chunk_reads(before, "xla")
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


def test_with_the_kernel_off_the_chunks_read_through_attend_and_the_counter_says_so():
    """The same family with ``paged_kernel="off"``: ``eva_read`` resolves to None, the chunk walk reads through
    ``_attend``, every layer of a chunk counts under ``path="xla"`` and none under ``path="kernel"``."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine import dispatch_timeline
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

    eng = LLMEngine(EngineConfig(**dict(BASE, paged_kernel="off", max_batch_size=1, max_seq_len=64)))
    try:
        assert eng._family_kernels == {"eva_read": None}
        before, cursor = eva_counters(), dispatch_timeline.cursor()
        out = list(eng.iter_ids(list(range(3, 38)) + [5, 6, 7, 8, 9], SamplingParams(temperature=0.0, max_tokens=2),
                                timeout=600))  # 40 tokens: chunks of 32 and 8
        after = eva_counters()
        chunks = [s for s in dispatch_timeline.spans_since(cursor)[0] if s["kind"] == "prefill_chunk"]
        L = CFG.num_layers
        assert len(out) <= 2 and [(s["eva_chunk_kernel_layers"], s["eva_chunk_xla_layers"]) for s in chunks] == [(0, L)] * 2
        assert chunk_reads(after, "xla") - chunk_reads(before, "xla") == 2 * L
        assert chunk_reads(after, "kernel") == chunk_reads(before, "kernel")
    finally:
        eng.shutdown()


def test_with_ignore_eos_a_sampled_stop_id_is_a_frame_and_the_answer_ends_on_its_budget(engine, monkeypatch):
    """40 ids: whatever the sampler draws is soon a stop id. By default the answer ends there (reason ``eos``,
    the id counted and never delivered); with ``ignore_eos`` the same greedy walk delivers the id and runs to
    ``max_tokens`` (a client's ``length``)."""
    from generativeaiexamples_tpu.engine import llm_engine
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    prompt = list(range(3, 20))
    free = list(engine.iter_ids(prompt, SamplingParams(temperature=0.0, max_tokens=9), timeout=300))
    assert len(free) == 9
    k = max(i for i in range(len(free)) if free[i] not in free[:i])  # the last id not seen before it
    finished = []
    monkeypatch.setattr(llm_engine.flight_recorder, "finish_rid",
                        lambda rid, outcome="finish", **attrs: finished.append(attrs))
    monkeypatch.setattr(engine, "_stop_ids", engine._stop_ids | {free[k]})
    assert list(engine.iter_ids(prompt, SamplingParams(temperature=0.0, max_tokens=9), timeout=300)) == free[:k]
    assert finished[-1] == {"generated": k, "stop": "eos"}
    ignoring = SamplingParams(temperature=0.0, max_tokens=9, ignore_eos=True)
    assert list(engine.iter_ids(prompt, ignoring, timeout=300)) == free
    assert finished[-1] == {"generated": 9, "stop": "max_tokens"}


REFUSED = {
    "tensor_parallel": (dict(tensor_parallelism=2), "sharded mesh"),
    "prefix_cache": (dict(prefix_cache_enable="auto", prefix_cache_slots=2), "prefix-cache reuse"),
    "spec_decode": (dict(spec_decode_enable="on"), "speculative verify"),
    "int8_weights": (dict(quantization="int8"), "quantization='int8'"),
    "int8_kv": (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
    "page_of_no_whole_chunks": (dict(page_size=2), "page_size"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_engine_build_refuses_what_the_family_does_not_declare(feature):
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    overrides, message = REFUSED[feature]
    with pytest.raises(ValueError, match=message):
        LLMEngine(EngineConfig(**dict(BASE, **overrides)))


def test_request_snapshots_are_refused_where_they_are_taken(engine):
    from generativeaiexamples_tpu.engine.request_snapshot import SnapshotError

    with pytest.raises(SnapshotError, match="fixed per-slot state"):
        engine.drain(timeout=1)
    assert not engine.is_draining()
