"""MiniMax-M3 (models/minimaxm3.py) at a tiny size that keeps the dense
layer and two expert layers, a block = page of 8 tokens and top 2, so
that the selection bites at 64 tokens, and the engine serving it through
the model registry as its second pages-only family: the paged walks
against the benchmark's plain float32 reference
(``perfbench/arch/minimaxm3.py``: an independent implementation; logits,
not tokens), the chunk walk's and a decode step's selection at one
position, a turn entered through shared pages (summaries included)
against a cold prefill bit for bit, the selected-page read interpreted
against an XLA gather, the eight shares of an expert layer adding up to
the uncut layer, ``swigluoai`` in the grouped kernel beside the old
activation's bits, and what the engine refuses a family by what it
declares.
"""
import dataclasses
import functools
import inspect
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine import kv_pages
from generativeaiexamples_tpu.models import glm5next, registry
from generativeaiexamples_tpu.models import minimaxm3 as m
from generativeaiexamples_tpu.ops import grouped_matmul, page_attention
from perfbench.arch import minimaxm3 as mm
from tests.expert_stats import assert_one_live_row_tiles
from tests.perfbench.test_perfbench_minimaxm3 import TINY, counters


@pytest.fixture(autouse=True, scope="module")
def float32_products():
    """float32 walks are held to a float32 forward: products at full
    precision, for THIS module only."""
    with jax.default_matmul_precision("highest"):
        yield


CFG = m.PRESETS["minimaxm3-debug"]
FULL = m.PRESETS["minimax-m3-ep8"]
PAGE, SLOTS, PMAX = 8, 3, 16
S = PAGE * PMAX
TOL = 2e-5  # float32 walks against the float32 reference
TABLES = jnp.asarray(1 + np.arange(SLOTS * PMAX).reshape(SLOTS, PMAX), jnp.int32)


@pytest.fixture(scope="module")
def params():
    return m.init_params_fast(CFG, 0, jnp.float32)


def reference_logits(params, toks, cfg=TINY, precision="float32", selections=None):
    """The plain reference's logits [T, V] on this parameter tree."""
    host = lambda tree: jax.tree.map(np.asarray, tree)  # noqa: E731
    layer = lambda l: host({k: v for k, v in params["layers"][l].items() if k not in mm._EXPERT_LEAVES})  # noqa: E731
    experts = lambda l: host(tuple(params["layers"][l].get(k) for k in mm._EXPERT_LEAVES))  # noqa: E731
    final = host((params["final_norm"], params["head"]))
    return mm.forward([list(toks)], cfg, np.asarray(params["embed"]), layer, experts, final,
                      positions=len(toks), precision=precision, selections=selections)[0]


@pytest.fixture(scope="module")
def sequence(params):
    """106 tokens (13 blocks: ten candidates for two places at the end)
    and the reference's logits at every position."""
    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, size=(106,))
    return toks, reference_logits(params, toks)


def dirty_caches():
    """Pools in which every page holds another tenant's rows and summaries."""
    caches = m.init_paged_cache(CFG, 1 + SLOTS * PMAX, PAGE, SLOTS, jnp.float32)
    return jax.tree.map(lambda x: x + 3 if x.dtype == jnp.int32 else x + 3.0, caches)


def rel(a, b):
    b = np.asarray(b)
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))


@functools.lru_cache(maxsize=None)
def _walks(kernel):
    def ext(params, caches, row, off, n, slot):
        kept = {}
        h, caches = m.extend_paged(params, CFG, caches, row, off, n, slot, TABLES, S, PAGE, grouped_matmul=kernel,
                                   capture=kept)
        return h, caches, kept

    def dec(params, caches, tok, pos, live):
        kept = {}
        logits, caches = m.decode_paged(params, CFG, caches, tok, pos, live, TABLES, S, PAGE, grouped_matmul=kernel,
                                        selected_read=kernel, capture=kept)
        return logits, caches, kept

    return jax.jit(ext), jax.jit(dec)


def extend(params, caches, toks, slot, chunk, kernel=None, upto=None, start=0):
    """Chunked extend of ``toks[start:upto]`` on ``slot``; returns (logits, caches, the last chunk's selection)."""
    n_all = len(toks) if upto is None else upto
    for off in range(start, n_all, chunk):
        n = min(chunk, n_all - off)
        row = np.zeros((1, chunk), np.int32)
        row[0, :n] = toks[off:off + n]
        h, caches, kept = _walks(kernel)[0](params, caches, jnp.asarray(row), jnp.asarray([off], jnp.int32),
                                            jnp.asarray([n], jnp.int32), jnp.asarray([slot], jnp.int32))
    return m.head(params, CFG, h)[0], caches, kept


def decode(params, caches, rows, kernel=None):
    tok, pos, live = [0] * SLOTS, [0] * SLOTS, [False] * SLOTS
    for s, (t, p) in rows.items():
        tok[s], pos[s], live[s] = int(t), int(p), True
    return _walks(kernel)[1](params, caches, jnp.asarray(tok, jnp.int32), jnp.asarray(pos, jnp.int32), jnp.asarray(live))


def blocks_of(pages, valid):
    """The selection as a set of blocks a KV head."""
    return [sorted(set(np.asarray(pg)[np.asarray(ok)].tolist())) for pg, ok in zip(pages, valid)]


# --------------------------------------------------------------------------- #
# Each mechanism against its plain form, and the numbers at the published keys


def test_the_share_the_memory_plan_and_the_pools_as_the_plan_counts_them():
    assert FULL.layers == ("dense", "sparse", "sparse", "sparse", "sparse") and FULL.num_layers == 5
    assert m.count_logical_params(FULL) == 4_985_107_456  # ISSUE 52: ~4.99 G
    one = dataclasses.replace(FULL, layers_served=(0,), vocab_size=0)
    assert m.count_logical_params(one) - FULL.hidden_size == 346_051_072
    assert m.count_logical_params(dataclasses.replace(one, layers_served=(3,))) - FULL.hidden_size == 1_082_937_984
    # 2,048 B a token a layer of K and V + 1,024 B a page a layer of pooled keys
    assert m.page_bytes(FULL) == 5 * (128 * 2048 + 1024) == 1_315_840 and m.kv_bytes_per_token(FULL) == 10_280
    assert FULL.experts_held == 16 and FULL.num_local_experts == 128 and FULL.pages_a_read == 19
    assert (FULL.swiglu_limit, FULL.swiglu_alpha, FULL.routed_scaling_factor) == (7.0, 1.702, 2.0)
    assert m.serving_memory_bytes(FULL, 16, 36864)["fixed_state"] == 0
    with pytest.raises(ValueError, match="experts held"):
        m.validate(dataclasses.replace(FULL, experts_first=120))
    with pytest.raises(ValueError, match="layers_served"):
        m.validate(dataclasses.replace(FULL, layers_served=(0, 60)))
    with pytest.raises(ValueError, match="page_size must be 128"):
        m.init_paged_cache(FULL, 9, 64, 4)
    # what the plan counts a page is what the cache pytree allocates; nothing is held a slot
    caches = jax.eval_shape(lambda: m.init_paged_cache(FULL, 9, 128, 4, jnp.bfloat16))
    nbytes = lambda xs: sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(xs))  # noqa: E731
    assert nbytes(caches["kv"]) == 9 * m.page_bytes(FULL)
    shape = registry.resolve("minimax-m3-ep8")[0].paged_kv_shape(FULL)
    plan = kv_pages.cache_plan(9, 128, 4, paged_bytes_per_token=shape.bytes_per_token, fixed_bytes_per_slot=0)
    assert 0 <= plan.paged_bytes - nbytes(caches["kv"]) < 9 * 128 and plan.fixed_bytes == 0  # the summary's share rounds up
    assert set(caches) == {"kv", "stats"} and len(caches["kv"]) == 5
    assert {k: v.shape for k, v in caches["kv"][0].items()} == {
        "k": (9, 4, 128, 128), "v": (9, 4, 128, 128), "kmax": (9, 4, 128)}


def test_parameter_count_matches_the_tree(params):
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) == m.count_logical_params(CFG)


def test_the_selection_keeps_first_local_open_and_the_best_candidates():
    """By hand at a block of 8 and top 2: at position 45 (block 5) the
    candidates are blocks 1..3; the two highest scores win, ties to the
    lower index; under three blocks everything is read."""
    scores = jnp.asarray([9.0, 1.0, 5.0, 5.0, 7.0, 8.0, 0.0, 0.0])[None, None, None, :]
    pages, valid = m.select_pages(scores, jnp.asarray([[45]]), CFG)
    assert blocks_of(pages[0, 0], valid[0, 0]) == [[0, 2, 3, 4, 5]]  # 2 before 3: equal scores
    pages, valid = m.select_pages(scores.at[..., 1].set(6.0), jnp.asarray([[45]]), CFG)
    assert blocks_of(pages[0, 0], valid[0, 0]) == [[0, 1, 2, 4, 5]]
    for pos, want in ((3, [0]), (9, [0, 1]), (23, [0, 1, 2]), (31, [0, 1, 2, 3]), (39, [0, 1, 2, 3, 4])):
        pages, valid = m.select_pages(scores, jnp.asarray([[pos]]), CFG)
        assert blocks_of(pages[0, 0], valid[0, 0]) == [want], pos
    assert bool(valid[0, 0, 0, 0]) and int(pages[0, 0, 0, 0]) == 0  # place 0 is block 0: a token the query sees


@pytest.mark.parametrize("group", [1, 2, 4])
def test_the_selected_page_read_interpreted_equals_a_gather(group, monkeypatch):
    """``selected_page_attention`` walks exactly the strips the list
    names, ``group`` a step, and equals the XLA gather of the same
    strips; two heads of one row read different pages; the list holds
    the selected places and no more."""
    rng = np.random.default_rng(group)
    B, Hq, Hkv, Dh, page, P, Pmax, K = 3, 8, 2, 16, 8, 40, 12, 5
    q, k, v = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((B, Hq, Dh), (P, Hkv, page, Dh), (P, Hkv, page, Dh)))
    tables = jnp.asarray(rng.permutation(P - 1)[:B * Pmax].reshape(B, Pmax) + 1, jnp.int32)
    pos = jnp.asarray([5, 50, 95], jnp.int32)
    pages, valid = np.zeros((B, Hkv, K), np.int32), np.zeros((B, Hkv, K), bool)
    for b in range(B):
        jt = int(pos[b]) // page
        for h in range(Hkv):
            mine = [0] + sorted(rng.choice(np.arange(1, jt - 1), size=min(2, jt - 2), replace=False).tolist() if jt > 2 else [])
            mine += [jt - 1] * (jt - 1 >= 1) + [jt] * (jt >= 1)
            places = [0] + sorted(rng.choice(np.arange(1, K), size=len(mine) - 1, replace=False).tolist())
            pages[b, h, places], valid[b, h, places] = mine, True
    pages, valid = jnp.asarray(pages), jnp.asarray(valid)
    want = page_attention.selected_page_gather(q, k, v, tables, pos, pages, valid)
    monkeypatch.setattr(page_attention, "SELECTED_PAGES_A_STEP", group)
    work = page_attention.selected_work_list(tables, pages, valid)
    got = page_attention.selected_page_attention(q, k, v, pos, work, interpret=True)
    assert rel(got, want) < 1e-5
    counts = np.asarray(valid).sum(-1).reshape(-1)
    assert int(work.n_work[0]) == int(np.sum(-(-counts // group)))
    live = np.asarray(work.page).reshape(-1, group)[: int(work.n_work[0])]
    assert int((live < page_attention._DEAD_PAGE).sum()) == int(counts.sum())  # the selected strips and no others
    assert not np.array_equal(np.asarray(pages[2, 0]), np.asarray(pages[2, 1]))  # heads of one row choose apart
    assert page_attention.supports_selected(128, 128, 64, 4) and not page_attention.supports_selected(8, 16, 4, 2)
    assert page_attention.supports_selected(8, 16, 4, 2, interpret=True)


@pytest.mark.parametrize("rows", [6, 40])
def test_swigluoai_in_the_grouped_kernel_and_the_old_activation_bit_for_bit(rows):
    """The activation arrives as a static argument beside ``limit``: with
    ``oai_alpha`` the kernel computes ``g' sigmoid(alpha g') (u' + 1)``;
    without it every bit of the clamped-SiLU form is what it was."""
    rng = np.random.default_rng(rows)
    E, D, F, k = 4, 32, 16, 2
    x = jnp.asarray(rng.normal(size=(rows, D)) * 3, jnp.float32)
    w_gu = jnp.asarray(rng.normal(size=(E, D, 2 * F)), jnp.float32)
    w_d = jnp.asarray(rng.normal(size=(E, F, D)), jnp.float32)
    local = jnp.asarray(rng.integers(0, E + 1, size=(rows, k)), jnp.int32)
    gates = jnp.asarray(rng.uniform(size=(rows, k)), jnp.float32)
    g, u = jnp.asarray(rng.normal(size=(5, 7)) * 6, jnp.float32), jnp.asarray(rng.normal(size=(5, 7)) * 6, jnp.float32)
    gc, uc = jnp.minimum(g, 7.0), jnp.clip(u, -7.0, 7.0)
    assert np.array_equal(grouped_matmul.swiglu(g, u, 7.0), gc * jax.nn.sigmoid(gc) * uc)  # the form as it was
    np.testing.assert_allclose(grouped_matmul.swiglu(g, u, 7.0, 1.702), gc * jax.nn.sigmoid(1.702 * gc) * (uc + 1), rtol=1e-6)
    for alpha in (None, 1.702):
        dense, sizes = grouped_matmul.grouped_mlp(x, local, gates, w_gu, w_d, limit=7.0, oai_alpha=alpha)
        kern, sizes_k = grouped_matmul.grouped_mlp(x, local, gates, w_gu, w_d, limit=7.0, kernel="interpret", oai_alpha=alpha)
        assert rel(kern, dense) < 1e-5 and np.array_equal(sizes, sizes_k)
    # the old activation through the kernel: the bits of the epilogue written out as it stood before
    tm = grouped_matmul.row_tile(rows * k)
    p = grouped_matmul.plan(local, E, tm)
    xr = jnp.take(x, p.row_token, axis=0, mode="fill", fill_value=0)
    got = grouped_matmul.grouped_gate_up(xr, w_gu, p.tile_expert, p.tiles_used, tm=tm, limit=7.0, interpret=True)
    for t in range(int(p.tiles_used[0])):
        e, rs = int(p.tile_expert[t]), slice(t * tm, (t + 1) * tm)
        gg = jnp.minimum(jnp.dot(xr[rs], w_gu[e][:, :F], preferred_element_type=jnp.float32), 7.0)
        uu = jnp.dot(xr[rs], w_gu[e][:, F:], preferred_element_type=jnp.float32)
        assert np.array_equal(got[rs], gg * jax.nn.sigmoid(gg) * jnp.clip(uu, -7.0, 7.0))
    oai = grouped_matmul.grouped_gate_up(xr, w_gu, p.tile_expert, p.tiles_used, tm=tm, limit=7.0, interpret=True,
                                         oai_alpha=1.702)
    assert not np.array_equal(oai, got)
    assert "oai_alpha" in inspect.signature(glm5next.moe).parameters  # the five families pass none


def test_the_router_weighs_scores_and_the_reference_routes_alike(params):
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (9, CFG.hidden_size))
    top, gates = glm5next.route(x, lp, CFG)
    assert top.shape == (9, 4) and np.allclose(np.asarray(gates).sum(1), 2.0, atol=1e-5)  # normalised, times 2
    rtop, rgates = mm.route(x, {k: np.asarray(v) for k, v in lp.items()}, mm.expert_keys(TINY))
    assert np.array_equal(np.sort(np.asarray(rtop), 1), np.sort(np.asarray(top), 1))
    np.testing.assert_allclose(np.sort(np.asarray(rgates), 1), np.sort(np.asarray(gates), 1), rtol=1e-5)


def test_the_8_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test at the PUBLISHED split: 128 experts, top 4, 16 a
    chip over 8 chips (the widths tiny), with ``swigluoai``. The routed
    parts of all 8 shares (the shared expert, which every chip computes
    alike, counted once) sum to the layer that holds all 128; held +
    absent pairs = rows x 4 on every chip; the router's width and its
    top 4 do not change with the share. The reference, given one share,
    gives that share's part."""
    cfg = dataclasses.replace(CFG, num_local_experts=128, num_experts_per_tok=4, experts_held=16)
    rng = jax.random.PRNGKey(7)
    D, F, E = cfg.hidden_size, cfg.intermediate_size, 128
    lp = dict(m.init_params_fast(dataclasses.replace(cfg, layers_served=(1,)), 3, jnp.float32)["layers"][0])
    x = jax.random.normal(rng, (10, D)) * 2
    whole = dataclasses.replace(cfg, experts_first=0, experts_held=E)
    w_all = {"we_gate_up": jax.random.normal(jax.random.fold_in(rng, 1), (E, D, 2 * F)) * 0.3,
             "we_down": jax.random.normal(jax.random.fold_in(rng, 2), (E, F, D)) * 0.3}
    count = jnp.ones((10,), bool)
    a = cfg.swiglu_alpha
    uncut, stats = glm5next.moe(x, dict(lp, **w_all), whole, count, None, oai_alpha=a)
    assert stats.tolist() == [40, 0, int(stats[2]), 128, int(stats[2]), 3 + 128]  # a 16-row tile an expert hit of ceil(40 / 16) + 128
    shared = glm5next.swiglu_mlp(x, lp["ws_gate_up"], lp["ws_down"], cfg.swiglu_limit, a)
    total, held_pairs = shared, 0
    top_whole, _ = glm5next.route(x, lp, whole)
    for chip in range(8):
        share = dataclasses.replace(cfg, experts_first=16 * chip, experts_held=16)
        mine = {k: v[16 * chip:16 * chip + 16] for k, v in w_all.items()}
        part, st = glm5next.moe(x, dict(lp, **mine), share, count, None, oai_alpha=a)
        total = total + (part - shared)
        held_pairs += int(st[0])
        assert int(st[0]) + int(st[1]) == 10 * 4 and int(st[3]) == 16  # held + absent = rows x 4
        top, _ = glm5next.route(x, lp, share)
        assert top.shape == (10, 4) and np.array_equal(top, top_whole) and lp["router"].shape[1] == 128
        if chip in (0, 5):
            keys = dict(mm.expert_keys(TINY), num_local_experts=128, experts_first=16 * chip, num_local_experts_held=16)
            ref = mm.moe(x, {k: np.asarray(v) for k, v in lp.items()}, keys,
                         lambda e, mine=mine: (mine["we_gate_up"][e], mine["we_down"][e]))
            assert rel(ref, part) < 1e-5
    assert held_pairs == 40 and rel(total, uncut) < 1e-5


# --------------------------------------------------------------------------- #
# The paged walks against the plain reference


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_one_chunk_then_decode_steps_on_dirty_pages(params, sequence, kernel):
    toks, full = sequence
    caches = dirty_caches()
    row = np.zeros((1, 64), np.int32)
    row[0, :50] = toks[:50]
    whole = jax.jit(lambda params, caches, row: m.prefill_paged(
        params, CFG, caches, row, jnp.asarray([50], jnp.int32), jnp.asarray([1], jnp.int32), TABLES, PAGE,
        grouped_matmul=kernel))
    logits, caches = whole(params, caches, jnp.asarray(row))
    assert rel(logits[0], full[49]) < TOL
    stats = dict(zip(m.STAT_NAMES, np.asarray(caches["stats"]).tolist()))
    assert stats["msa_pages_pooled"] == 6 * 3  # six complete pages of 50 tokens, three layers
    for p in range(50, 56):
        logits, caches, _ = decode(params, caches, {1: (toks[p], p)}, kernel)
        assert rel(logits[1], full[p]) < TOL, p
    stats = dict(zip(m.STAT_NAMES, np.asarray(caches["stats"]).tolist()))
    # position 55 is in block 6: first, two of the four candidates, local, open = 5 of 7 pages a head, 2 heads, 3 layers;
    # the step wrote block 6's last token: a summary a layer
    assert stats["msa_pages_selected"] == 5 * 2 * 3 and stats["msa_pages_live"] == 7 * 2 * 3
    assert stats["msa_blocks_scored"] == 4 * 2 * 3 and stats["msa_pages_pooled"] == 3
    assert stats["moe_pairs_held"] + stats["moe_pairs_absent"] == 2 * 4 and stats["moe_experts_held"] == 2 * 2
    assert_one_live_row_tiles(m.STAT_NAMES, stats, CFG, SLOTS)


@pytest.mark.parametrize("chunk,kernel", [(16, None), (32, "interpret"), (64, None)])
def test_chunked_extend_then_decode_through_the_cache(params, sequence, chunk, kernel):
    toks, full = sequence
    logits, caches, _ = extend(params, dirty_caches(), toks, 2, chunk, kernel, upto=100)
    assert rel(logits, full[99]) < TOL
    for p in range(100, 106):
        logits, caches, _ = decode(params, caches, {2: (toks[p], p)}, kernel)
        assert rel(logits[2], full[p]) < TOL, p
        # the step at 103 writes block 12's last token: a summary a layer, and no other step writes one
        assert int(caches["stats"][m.STAT_NAMES.index("msa_pages_pooled")]) == (3 if p == 103 else 0)


def test_the_chunk_walk_and_a_decode_step_select_the_same_blocks(params, sequence):
    """Position 99 entered as the last query of a chunk, and as a decode
    step after a walk of 99 tokens: the same blocks a KV head in the
    last layer, and the reference's. A block the chunk itself completed
    is a candidate of the chunk's later queries."""
    toks, _ = sequence
    _, _, kept = extend(params, dirty_caches(), toks, 0, 64, upto=100)
    from_chunk = blocks_of(kept["pages"][-1, 0, 35], kept["valid"][-1, 0, 35])
    _, caches, _ = extend(params, dirty_caches(), toks, 0, 64, upto=99)
    _, _, kept = decode(params, caches, {0: (toks[99], 99)}, "interpret")
    from_step = blocks_of(kept["pages"][-1, 0], kept["valid"][-1, 0])
    assert from_chunk == from_step and all(len(b) == 5 and b[0] == 0 and b[-2:] == [11, 12] for b in from_step)
    sel = [[]]
    reference_logits(params, toks[:104], selections=sel)
    assert [np.nonzero(row)[0].tolist() for row in sel[0][0][99]] == from_step
    # the chunk from 64 holds positions 64..105 and completes blocks 8..12; at j_t = 12 the candidates
    # are 1..10: over the chunk's last queries and both heads, one of 8, 9, 10 is chosen
    _, _, kept = extend(params, dirty_caches(), toks, 0, 64, upto=106)
    picked = {b for t in range(32, 42) for mine in blocks_of(kept["pages"][-1, 0, t], kept["valid"][-1, 0, t]) for b in mine}
    assert picked & {8, 9, 10}


def test_a_turn_through_shared_pages_equals_a_cold_walk_bit_for_bit(params, sequence):
    """What a stateless entry is: another row's pages mapped into this
    row's table, SUMMARIES WITH THEM (indexed by physical page). Row 0
    walks 64 tokens; row 1's table maps row 0's first eight pages and
    walks only the tail from offset 64. Logits and every page and
    summary the tail wrote equal the cold walk's, bit for bit; the
    shared pages are untouched. Against the reference too."""
    toks, full = sequence
    _, base, _ = extend(params, dirty_caches(), toks, 0, 32, upto=64)
    logits_cold, cold, _ = extend(params, base, toks, 0, 32, upto=100, start=64)
    tables = np.asarray(TABLES).copy()
    tables[1, :8] = tables[0, :8]  # the hit: eight shared pages
    walk = jax.jit(lambda caches, row, off, n: m.extend_paged(
        params, CFG, caches, row, off, n, jnp.asarray([1], jnp.int32), jnp.asarray(tables), S, PAGE))
    caches = base
    for off in (64, 96):
        n = min(32, 100 - off)
        row = np.zeros((1, 32), np.int32)
        row[0, :n] = toks[off:off + n]
        h, caches = walk(caches, jnp.asarray(row), jnp.asarray([off], jnp.int32), jnp.asarray([n], jnp.int32))
    logits_hit = m.head(params, CFG, h)[0]
    assert np.array_equal(np.asarray(logits_hit), np.asarray(logits_cold)) and rel(logits_hit, full[99]) < TOL
    for l in range(CFG.num_layers):
        for leaf in ("k", "v", "kmax"):
            hit_pool, cold_pool, base_pool = (np.asarray(c["kv"][l][leaf]) for c in (caches, cold, base))
            assert np.array_equal(hit_pool[tables[0, :8]], base_pool[tables[0, :8]])  # shared: read, never written
            assert np.array_equal(hit_pool[tables[1, 8:12]], cold_pool[tables[0, 8:12]])  # the tail's: the cold walk's
        # a shared page's summary IS the maximum of its keys as cached
        pool = caches["kv"][l]
        assert np.array_equal(np.asarray(pool["kmax"])[tables[0, :8]], np.asarray(pool["k"])[tables[0, :8]].max(axis=2))


def test_a_row_with_nothing_valid_and_a_dead_row_change_nothing(params):
    caches = dirty_caches()
    _, after, _ = _walks(None)[0](params, caches, jnp.zeros((1, 32), jnp.int32), jnp.asarray([16], jnp.int32),
                                  jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32))
    _, after, _ = decode(params, after, {})
    for a, b in zip(jax.tree.leaves(caches["kv"]), jax.tree.leaves(after["kv"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_rows_decoding_together_and_rows_in_one_chunk_equal_their_solo_runs(params, sequence):
    toks, full = sequence
    _, caches, _ = extend(params, dirty_caches(), toks, 0, 32, upto=40)
    _, caches, _ = extend(params, caches, toks, 2, 32, upto=70)
    for j in range(2):
        logits, caches, _ = decode(params, caches, {0: (toks[40 + j], 40 + j), 2: (toks[70 + j], 70 + j)}, "interpret")
        assert rel(logits[0], full[40 + j]) < TOL and rel(logits[2], full[70 + j]) < TOL
    # a wave of three rows at different offsets and lengths, one dead
    _, caches, _ = extend(params, dirty_caches(), toks, 0, 32, upto=64)
    rows = np.zeros((3, 32), np.int32)
    rows[0, :32], rows[1, :20] = toks[64:96], toks[:20]
    h, _, _ = _walks(None)[0](params, caches, jnp.asarray(rows), jnp.asarray([64, 0, 0], jnp.int32),
                              jnp.asarray([32, 20, 0], jnp.int32), jnp.asarray([0, 1, 2], jnp.int32))
    logits = m.head(params, CFG, h)
    assert rel(logits[0], full[95]) < TOL and rel(logits[1], full[19]) < TOL


def test_the_walks_equal_the_modules_own_whole_sequence_forward(params, sequence):
    toks, full = sequence
    own = np.asarray(jax.jit(lambda params, toks: m.forward_full(params, CFG, toks))(params, jnp.asarray(toks)[None]))[0]
    assert max(rel(own[t], full[t]) for t in (7, 23, 50, 99, 105)) < TOL


def test_an_all_bfloat16_control_fails_the_tolerance(params, sequence):
    """The reference one precision down (nothing in float32) is NOT
    within ``TOLERANCE`` of the float32 reference at its worst position,
    while the float32 walks are within 2e-5 at every one."""
    toks, full = sequence
    low = reference_logits(params, toks, precision="bfloat16")
    errs = [rel(low[t], full[t]) for t in range(len(toks))]
    assert max(errs) > mm.TOLERANCE, max(errs)


def test_registry_resolves_the_family_and_what_it_declares():
    fam, cfg = registry.resolve("minimaxm3-debug")
    assert fam.name == "minimaxm3" and cfg is CFG and registry.family_of(CFG).name == "minimaxm3"
    # pages only, and nothing it does not bring
    assert not fam.fixed_state and fam.state_row_keys == () and fam.verify_paged is None and fam.extend_packed is None
    assert not fam.extend_reads_window and fam.weight_formats == () and fam.kv_formats == ()
    assert not fam.sharded and not fam.snapshot_pages and fam.fixed_state_bytes_per_slot(CFG) == 0
    shape = fam.paged_kv_shape(FULL)
    assert (shape.num_layers, shape.num_kv_heads, shape.head_dim, shape.num_heads, shape.bytes_per_token, shape.latent) == (
        5, 4, 128, 64, 10_280, False)
    fams = registry.families()
    assert [fams[n].paged_kv_shape(fams[n].presets[p]).latent for n, p in (
        ("glm5next", "glm5next-debug"), ("gigachat35", "gigachat35-debug"), ("kimik2", "kimik2-debug"),
        ("afmoe", "afmoe-debug"), ("llama", "debug-1k"))] == [True, True, True, False, False]
    assert fam.span_fields(FULL) == {"kv_readers": 5, "msa_pages_a_read": 19}
    # tiny widths do not tile: the chunk walk's kernel declines them interpreted too (a page is a lane tile of its scores)
    assert fam.resolve_kernels(cfg, "compiled") == {"grouped_matmul": "compiled", "selected_read": None, "selected_chunk": None}
    assert fam.resolve_kernels(cfg, "interpret") == {
        "grouped_matmul": "interpret", "selected_read": "interpret", "selected_chunk": None}
    assert fam.resolve_kernels(FULL, "compiled") == {
        "grouped_matmul": "compiled", "selected_read": "compiled", "selected_chunk": "compiled"}
    for walk in (m.extend_paged, m.decode_paged):
        assert {"grouped_matmul"} <= set(inspect.signature(walk).parameters)
    assert "selected_read" in inspect.signature(m.decode_paged).parameters
    assert "selected_chunk" in inspect.signature(m.extend_paged).parameters
    assert fam.stat_names == m.STAT_NAMES == glm5next.MOE_STAT_NAMES + (
        "msa_pages_selected", "msa_pages_live", "msa_blocks_scored", "msa_pages_pooled",
        "msa_chunk_kernel_layers", "msa_chunk_xla_layers", "msa_chunk_blocks_read", "msa_chunk_blocks_live")
    # the program's model modules name the family; the engine and the server name no model
    import pathlib

    import generativeaiexamples_tpu

    pkg = pathlib.Path(generativeaiexamples_tpu.__file__).parent
    for sub in ("engine", "server"):
        for path in (pkg / sub).rglob("*.py"):
            assert "minimax" not in path.read_text(encoding="utf-8").lower(), path


# --------------------------------------------------------------------------- #
# The engine: served through the registry, pages only

BASE = dict(
    model_config_name="minimaxm3-debug", max_batch_size=3, max_seq_len=256, prefill_chunk=32,
    tensor_parallelism=1, decode_block=4, decode_runahead=1, page_size=8, prefix_cache_enable="auto",
    prefix_cache_slots=4, prefill_wave_tokens=64, dtype="float32", paged_kernel="interpret",
)


def build(**overrides):
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    return LLMEngine(EngineConfig(**dict(BASE, **overrides)))


@pytest.fixture(scope="module")
def engine():
    eng = build()
    eng.warmup()
    yield eng
    eng.shutdown()


def greedy(n):
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    return SamplingParams(temperature=0.0, max_tokens=n)


def test_engine_serves_every_prompt_shape_as_the_references_argmax(engine):
    """One chunk (5, 32), several (150): every served token is the
    plain reference's argmax, through the interpreted kernels, the
    selection biting from 24 tokens on. Nothing compiles after warm-up;
    the wave may hold two rows."""
    assert engine._family.name == "minimaxm3" and engine._paged_kernel == "interpret" and not engine._fixed_state
    assert engine._family_kernels == {"grouped_matmul": "interpret", "selected_read": "interpret", "selected_chunk": None}
    assert engine.shapes.max_wave_rows() == 2  # follows prefill_wave_tokens, like llama's
    assert engine._spec_available is False and engine._state_store_rows == 0 and engine._copy_state_fn is None
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, 250, size=n)] for n in (5, 32, 150)]
    outs = [list(engine.iter_ids(p, greedy(5), timeout=600)) for p in prompts]
    for p, o in zip(prompts, outs):
        assert len(o) == 5
        ref = reference_logits(engine.params, p + o)
        assert max(float(ref[len(p) - 1 + j].max() - ref[len(p) - 1 + j][t]) for j, t in enumerate(o)) < 1e-4
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


def test_a_prefix_hit_maps_pages_with_their_summaries_and_spans_and_counters_say_so(engine):
    """The stateless store over K, V and summary pools: the same
    150-token prompt twice. The second admission maps the entry's 16
    pages (128 tokens), prefills the 22-token tail at that offset under
    the selection, and answers as the first did and as an engine with
    the store off does; decode spans say a step fetched fewer pages than
    were live, and the counters grew."""
    from generativeaiexamples_tpu.engine import dispatch_timeline

    prompt = [int(t) for t in np.random.default_rng(9).integers(3, 250, size=150)]
    before = counters()
    first = list(engine.iter_ids(prompt, greedy(8), timeout=600))
    mid = counters()
    second = list(engine.iter_ids(prompt, greedy(8), timeout=600))
    after = counters()
    grew = lambda a, b, k: b.get(k, 0.0) - a.get(k, 0.0)  # noqa: E731
    assert first == second and len(first) == 8
    assert grew(mid, after, "genai_engine_prefix_cache_hits_total") == 1
    assert grew(mid, after, "genai_engine_prefix_cache_tokens_reused_total") == 128
    assert grew(mid, after, "genai_engine_kv_prefix_pages_mapped_total") == 16
    assert grew(mid, after, "genai_engine_prefill_tokens_total") == 22
    for name in ("genai_engine_prefix_state_saves_total", "genai_engine_prefix_state_restores_total"):
        assert grew(before, after, name) == 0  # pages only: no state row travels
    for name in ("selected", "live", "scored", "pooled"):
        key = {"scored": "blocks_scored"}.get(name, "pages_" + name)
        assert grew(before, after, f"genai_engine_msa_{key}_total") > 0, name
    ref = reference_logits(engine.params, prompt + second)
    assert max(float(ref[149 + j].max() - ref[149 + j][t]) for j, t in enumerate(second)) < 1e-4
    spans = [s for s in dispatch_timeline.recent_spans(256) if s.get("kind") in ("decode", "prefill_chunk")]
    chunks = [s for s in spans if s["kind"] == "prefill_chunk"]
    assert chunks[0]["prefix_depth_tokens"] == 128 and chunks[0]["tokens"] == 22  # newest first: the tail after the hit
    step = [s for s in spans if s["kind"] == "decode"][0]
    assert step["kv_readers"] == 3 and step["msa_pages_a_read"] == 5
    # 150+ tokens = 19-20 live pages a head; a step reads 5 a head: 2 heads, 3 layers
    assert step["msa_pages_selected"] == 5 * 2 * 3 and step["msa_pages_live"] >= 19 * 2 * 3
    assert chunks[0]["msa_pages_selected"] < chunks[0]["msa_pages_live"] and chunks[0]["msa_pages_pooled"] == 2 * 3
    cold = build(prefix_cache_enable="off")
    try:
        assert list(cold.iter_ids(prompt, greedy(8), timeout=600)) == first
    finally:
        cold.shutdown()
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


# widths that tile the chip (a head and a page of one lane tile) at a size a CPU serves: the dense layer and four
# expert layers, five reads a chunk as the served share has
LANES = dataclasses.replace(CFG, layers_served=(0, 1, 2, 3, 4), head_dim=128, rotary_dim=64, msa_block=128, max_seq_len=1536)


@pytest.mark.parametrize("chunk", [128, 256])
def test_the_chunk_walk_through_the_kernel_is_the_xla_walk(chunk):
    """``ops/selected_chunk_read.py`` interpreted inside the family's own
    chunked extend: 1,100 tokens (nine blocks of 128: six candidates for
    two places at the end, an offset off the chunk grid at the tail) give
    the XLA walk's logits and, layer by layer, its selection."""
    assert m.selected_chunk_kind(LANES, "interpret", chunk) == "interpret"
    params = m.init_params_fast(LANES, 0, jnp.float32)
    toks = np.random.default_rng(4).integers(0, LANES.vocab_size, size=(1100,))
    page, pmax = LANES.msa_block, 12
    tables = jnp.asarray(1 + np.arange(pmax).reshape(1, pmax), jnp.int32)
    got = {}
    for path in (None, "interpret"):
        def walk(params, caches, row, off, n, path=path):
            kept = {}
            h, caches = m.extend_paged(params, LANES, caches, row, off, n, jnp.zeros((1,), jnp.int32), tables,
                                       page * pmax, page, capture=kept, selected_chunk=path)
            return h, caches, kept

        walk = jax.jit(walk)
        caches = m.init_paged_cache(LANES, 1 + pmax, page, 1, jnp.float32)
        stats = np.zeros((len(m.STAT_NAMES),), np.int64)
        for off in range(0, len(toks), chunk):
            n = min(chunk, len(toks) - off)
            row = np.zeros((1, chunk), np.int32)
            row[0, :n] = toks[off:off + n]
            h, caches, kept = walk(params, caches, jnp.asarray(row), jnp.asarray([off], jnp.int32), jnp.asarray([n], jnp.int32))
            stats += np.asarray(caches["stats"])
        got[path] = (np.asarray(m.head(params, LANES, h)[0]), jax.tree.map(np.asarray, kept), dict(zip(m.STAT_NAMES, stats)))
    (xla, xla_kept, xla_stats), (ker, ker_kept, ker_stats) = got[None], got["interpret"]
    assert rel(ker, xla) < TOL
    n = len(toks) % chunk  # the last chunk's live queries [L, 1, n, Hk, K]; a padded query's row is nobody's
    assert (ker_kept["valid"][:, :, :n] == xla_kept["valid"][:, :, :n]).all()
    assert (np.where(xla_kept["valid"], ker_kept["pages"] == xla_kept["pages"], True)[:, :, :n]).all()
    assert int(ker_kept["valid"][:, 0, :n].sum(-1).max()) == 5  # the selection bites: two of six candidates
    chunks = -(-len(toks) // chunk)
    assert (xla_stats["msa_chunk_xla_layers"], xla_stats["msa_chunk_kernel_layers"]) == (5 * chunks, 0)
    assert (ker_stats["msa_chunk_kernel_layers"], ker_stats["msa_chunk_xla_layers"]) == (5 * chunks, 0)
    assert xla_stats["msa_chunk_blocks_live"] == 0 and 0 < ker_stats["msa_chunk_blocks_read"] <= ker_stats["msa_chunk_blocks_live"]
    for name in m.STAT_NAMES[:8]:
        assert ker_stats[name] == xla_stats[name], name


def test_an_engine_at_widths_that_tile_reads_its_chunks_through_the_kernel(caplog):
    """The engine's own extend programs with ``paged_kernel="interpret"``
    at widths that tile: the log line names ``selected_chunk=interpret``,
    a 300-token prompt goes in three chunks whose spans say all five
    layers read through the kernel and how many of its blocks were read,
    the counter grows by five a chunk under ``path="kernel"`` and not at
    all under ``path="xla"``, a decode span reports zeros in the four new
    places, and the answer is the one the engine gives with every kernel off."""
    from generativeaiexamples_tpu.engine import dispatch_timeline

    name = "minimaxm3-lanes-test"
    m.PRESETS[name] = LANES
    reads = lambda c, path: c.get(f'genai_engine_msa_chunk_reads_total{{path="{path}"}}', 0.0)  # noqa: E731
    prompt = [int(t) for t in np.random.default_rng(3).integers(3, 250, size=300)]
    shape = dict(model_config_name=name, page_size=128, prefill_chunk=128, prefill_wave_tokens=128, max_seq_len=512,
                 max_batch_size=2, prefix_cache_enable="off")
    try:
        answers = {}
        for kernel in ("interpret", "off"):
            with caplog.at_level(logging.INFO, logger="generativeaiexamples_tpu.engine.llm_engine"):
                caplog.clear()
                eng = build(paged_kernel=kernel, **shape)
            try:
                line = next(r.getMessage() for r in caplog.records if "resolved kernel paths:" in r.getMessage())
                assert f"selected_chunk={'interpret' if kernel == 'interpret' else None}" in line
                before, t0 = counters(), time.time()
                answers[kernel] = list(eng.iter_ids(prompt, greedy(4), timeout=600))
                after = counters()
                spans = [s for s in dispatch_timeline.recent_spans(64) if s["t_wall"] >= t0]
                chunks = [s for s in spans if s["kind"] == "prefill_chunk"]
                served, other = ("kernel", "xla") if kernel == "interpret" else ("xla", "kernel")
                assert len(chunks) == 3
                assert all(s[f"msa_chunk_{served}_layers"] == 5 and s[f"msa_chunk_{other}_layers"] == 0 for s in chunks)
                assert reads(after, served) - reads(before, served) == 15 and reads(after, other) == reads(before, other)
                if kernel == "interpret":
                    assert all(0 < s["msa_chunk_blocks_read"] <= s["msa_chunk_blocks_live"] for s in chunks)
                steps = [s for s in spans if s["kind"] == "decode"]
                assert steps and all(s[n] == 0 for s in steps for n in m.STAT_NAMES[8:])
                assert all(s["msa_pages_selected"] > 0 for s in steps)
                assert eng._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0
            finally:
                eng.shutdown()
        assert answers["interpret"] == answers["off"] and len(answers["off"]) == 4
    finally:
        del m.PRESETS[name]


REFUSED = {
    "tensor_parallel": (dict(tensor_parallelism=2), "sharded mesh.*declares no sharded walk"),
    "spec_decode": (dict(spec_decode_enable="on"), "speculative decoding.*registers no verify walk"),
    "int8_weights": (dict(quantization="int8"), r"quantization='int8'.*reads weight formats \[\]"),
    "int8_kv": (dict(kv_cache_dtype="int8"), r"kv_cache_dtype='int8'.*reads pool formats \[\]"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_engine_build_refuses_the_family_what_it_does_not_declare(feature):
    overrides, message = REFUSED[feature]
    with pytest.raises(ValueError, match=message) as exc:
        build(**overrides)
    assert "minimaxm3 model 'minimaxm3-debug'" in str(exc.value) and "fixed per-slot state" not in str(exc.value)


@pytest.mark.parametrize("call", ["drain", "restore_snapshot"])
def test_request_snapshots_are_refused_where_they_are_taken_with_the_familys_own_reason(engine, call):
    from generativeaiexamples_tpu.engine.request_snapshot import SnapshotError

    with pytest.raises(SnapshotError, match="minimaxm3 family, whose page pools are not the per-layer K and V pages") as exc:
        engine.drain(timeout=1) if call == "drain" else engine.restore_snapshot(None)
    assert "fixed per-slot state" not in str(exc.value) and not engine.is_draining()


def test_a_page_size_that_is_not_the_block_is_refused_at_start_up():
    with pytest.raises(ValueError, match="page_size must be 8"):
        build(page_size=16)
