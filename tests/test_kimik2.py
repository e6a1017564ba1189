"""Kimi-K2.5 (models/kimik2.py) at a tiny size that keeps the dense layer
and two expert layers — latent attention in every one, no state beside
the pages — and the engine serving it through the model registry as its
first family with pages only that is not ``llama``: each mechanism
against its plain form, the paged walks against the benchmark's plain
float32 reference (``perfbench/arch/kimik2.py``: an independent
implementation; logits, not tokens), a prefix hit then the tail against
a cold prefill bit for bit, YaRN's numbers at the published keys, the 32
shares of a layer adding up to the uncut layer, and what the engine
refuses a family by what it declares.
"""
import dataclasses
import functools
import inspect
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine import kv_pages
from generativeaiexamples_tpu.models import gigachat35, glm5next, registry
from generativeaiexamples_tpu.models import kimik2 as m
from perfbench.arch import kimik2 as kimi
from tests.expert_stats import assert_one_live_row_tiles
from tests.perfbench.test_perfbench_kimik2 import CFG as FILE, TINY, counters


@pytest.fixture(autouse=True, scope="module")
def float32_products():
    """float32 walks are held to a float32 forward: products at full
    precision, for THIS module only."""
    with jax.default_matmul_precision("highest"):
        yield


CFG = m.PRESETS["kimik2-debug"]
FULL = m.PRESETS["kimi-k2.5-ep32"]
PAGE, SLOTS, PMAX = 16, 3, 8
S = PAGE * PMAX
TOL = 2e-5  # float32 walks against the float32 reference
TABLES = jnp.asarray(1 + np.arange(SLOTS * PMAX).reshape(SLOTS, PMAX), jnp.int32)


@pytest.fixture(scope="module")
def params():
    return m.init_params_fast(CFG, 0, jnp.float32)


def reference_logits(params, toks, cfg=TINY, precision="float32"):
    """The plain reference's logits [T, V] on this parameter tree."""
    host = lambda tree: jax.tree.map(np.asarray, tree)  # noqa: E731
    layer = lambda l: host({k: v for k, v in params["layers"][l].items() if k not in kimi._EXPERT_LEAVES})  # noqa: E731
    experts = lambda l: host(tuple(params["layers"][l].get(k) for k in kimi._EXPERT_LEAVES))  # noqa: E731
    final = host((params["final_norm"], params["head"]))
    return kimi.forward([list(toks)], cfg, np.asarray(params["embed"]), layer, experts, final,
                        positions=len(toks), precision=precision)[0]


@pytest.fixture(scope="module")
def sequence(params):
    """106 tokens (the RoPE key's original context is 64: YaRN's slowed
    pairs turn) and the reference's logits at every position."""
    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, size=(106,))
    return toks, reference_logits(params, toks)


def dirty_caches():
    """Pools in which every page holds another tenant's rows."""
    caches = m.init_paged_cache(CFG, 1 + SLOTS * PMAX, PAGE, SLOTS, jnp.float32)
    return jax.tree.map(lambda x: x + 3 if x.dtype == jnp.int32 else x + 3.0, caches)


def rel(a, b):
    b = np.asarray(b)
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))


@functools.lru_cache(maxsize=None)
def _walks(kernel):
    ext = jax.jit(lambda params, caches, row, off, n, slot: m.extend_paged(
        params, CFG, caches, row, off, n, slot, TABLES, S, PAGE, grouped_matmul=kernel))
    dec = jax.jit(lambda params, caches, tok, pos, live: m.decode_paged(
        params, CFG, caches, tok, pos, live, TABLES, S, PAGE, page_kernel=kernel, grouped_matmul=kernel))
    return ext, dec


def extend(params, caches, toks, slot, chunk, kernel=None, upto=None, start=0):
    """Chunked extend of ``toks[start:upto]`` on ``slot``; returns (logits, caches)."""
    n_all = len(toks) if upto is None else upto
    for off in range(start, n_all, chunk):
        n = min(chunk, n_all - off)
        row = np.zeros((1, chunk), np.int32)
        row[0, :n] = toks[off:off + n]
        h, caches = _walks(kernel)[0](params, caches, jnp.asarray(row), jnp.asarray([off], jnp.int32),
                                      jnp.asarray([n], jnp.int32), jnp.asarray([slot], jnp.int32))
    return m.head(params, CFG, h)[0], caches


def decode(params, caches, rows, kernel=None):
    tok, pos, live = [0] * SLOTS, [0] * SLOTS, [False] * SLOTS
    for s, (t, p) in rows.items():
        tok[s], pos[s], live[s] = int(t), int(p), True
    return _walks(kernel)[1](params, caches, jnp.asarray(tok, jnp.int32), jnp.asarray(pos, jnp.int32), jnp.asarray(live))


# --------------------------------------------------------------------------- #
# Each mechanism against its plain form, and the numbers at the published keys


def test_the_share_the_memory_plan_and_the_pools_as_the_plan_counts_them():
    assert FULL.layers == ("dense", "sparse", "sparse", "sparse", "sparse") and FULL.num_layers == 5
    assert m.count_logical_params(FULL) == 3_496_763_904  # ISSUE 49: one dense + four expert layers + the vocabulary's eighth
    one = dataclasses.replace(FULL, layers_served=(0,), vocab_size=0)
    assert m.count_logical_params(one) - FULL.hidden_size == 497_500_160
    assert m.count_logical_params(dataclasses.replace(one, layers_served=(1,))) - FULL.hidden_size == 676_413_824
    assert FULL.latent_row == 640 and m.kv_bytes_per_token(FULL) == 5 * 1280  # [c 512 | k_rope 64] padded to five lane tiles
    assert FULL.experts_held == 12 and FULL.n_routed_experts == 384 and FULL.swiglu_limit == math.inf
    assert m.serving_memory_bytes(FULL, 32, 24576)["fixed_state"] == 0
    with pytest.raises(ValueError, match="experts held"):
        m.validate(dataclasses.replace(FULL, experts_first=380))
    with pytest.raises(ValueError, match="layers_served"):
        m.validate(dataclasses.replace(FULL, layers_served=(0, 61)))
    # what CachePlan counts a token is what the cache pytree allocates; nothing is held a slot
    caches = jax.eval_shape(lambda: m.init_paged_cache(FULL, 9, 128, 4, jnp.bfloat16))
    nbytes = lambda xs: sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in xs)  # noqa: E731
    shape = registry.resolve("kimi-k2.5-ep32")[0].paged_kv_shape(FULL)
    plan = kv_pages.cache_plan(9, 128, 4, paged_bytes_per_token=shape.bytes_per_token, fixed_bytes_per_slot=0)
    assert plan.paged_bytes == nbytes(caches["lat"]) == 9 * 128 * 6400 and plan.fixed_bytes == 0
    assert set(caches) == {"lat", "stats"} and len(caches["lat"]) == 5


def test_parameter_count_matches_the_tree(params):
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) == m.count_logical_params(CFG)
    assert "wx" in params["layers"][0] and params["layers"][0]["wx"].shape == (64, 48 + 32 + 8)  # no gate columns


def test_yarn_frequencies_and_the_softmax_scale_at_the_published_keys():
    """DeepSeek-V3's YaRN at theta 50000, factor 64 over 4096, beta 32 / 1,
    by hand: pair i turns ``4096 theta^(-2i/64) / 2 pi`` times inside the
    original context; the ramp runs from the last pair that turns more
    than 32 times to the first that turns less than once."""
    inv = np.asarray(gigachat35.yarn_inv_freq(FULL), np.float64)
    base = 50000.0 ** (-np.arange(32) / 32.0)
    dim = lambda rot: 64 * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(50000.0))  # noqa: E731
    low, high = math.floor(dim(32)), math.ceil(dim(1))
    assert (low, high) == (8, 20)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, base / 64 * ramp + base * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(inv[:9], base[:9], rtol=1e-6)  # the fast pairs keep their frequency
    np.testing.assert_allclose(inv[20:], base[20:] / 64, rtol=1e-6)  # the slow pairs are slowed by the factor
    np.testing.assert_allclose(inv, kimi.yarn_inv_freq(FILE), rtol=1e-6)  # the reference's, from the file's keys
    assert FULL.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2) == pytest.approx(0.144680, abs=1e-6)
    assert kimi.softmax_scale(FILE) == pytest.approx(FULL.softmax_scale)
    # cos and sin are unscaled: mscale == mscale_all_dim
    x = jnp.ones((1, 64), jnp.float32)
    assert float(jnp.linalg.norm(gigachat35.rope(x, jnp.asarray([5000]), FULL))) == pytest.approx(8.0, rel=1e-5)


def test_the_absorbed_read_equals_the_unabsorbed_one(params):
    """``[W_uk^T q_nope | q_rope]`` against the cached row, values through
    ``W_uv`` after the softmax, equals per-head keys and values rebuilt
    from the latent (the reference's form); positions past YaRN's
    original context of 64."""
    lp = params["layers"][1]
    T, H, R, dr, dn = 90, CFG.num_heads, CFG.kv_lora_rank, CFG.qk_rope_head_dim, CFG.qk_nope_head_dim
    x = jax.random.normal(jax.random.PRNGKey(3), (T, CFG.hidden_size))
    q_nope, q_rope, gate, row = gigachat35._mla_project(x, jnp.arange(T), lp, CFG, output_gate=False)
    assert gate is None and row.shape == (T, CFG.latent_row) and not np.asarray(row[:, R + dr:]).any()
    c, k_rope = row[:, :R], row[:, R:R + dr]
    causal = (np.arange(T)[None, :] <= np.arange(T)[:, None])[None]
    k_nope = jnp.einsum("sr,hdr->shd", c, lp["wuk"])
    v = jnp.einsum("sr,hrv->shv", c, lp["wuv"])
    sc = (jnp.einsum("thd,shd->hts", q_nope, k_nope) + jnp.einsum("thd,sd->hts", q_rope, k_rope)) * CFG.softmax_scale
    plain = jnp.einsum("hts,shv->thv", jax.nn.softmax(jnp.where(causal, sc, -1e30), -1), v)
    qlat = gigachat35._absorb(q_nope, q_rope, lp, CFG)
    assert qlat.shape == (T, H, CFG.latent_row)
    p2 = jax.nn.softmax(jnp.where(causal, jnp.einsum("thw,sw->hts", qlat, row) * CFG.softmax_scale, -1e30), -1)
    absorbed = jnp.einsum("thr,hrv->thv", jnp.einsum("hts,sr->thr", p2, c), lp["wuv"])
    assert rel(absorbed, plain) < 1e-5
    # and the reference's mixer, a block of queries at a time, is its own whole-sequence form
    w = {k: np.asarray(v) for k, v in lp.items() if k not in kimi._EXPERT_LEAVES}
    u = jax.random.normal(jax.random.PRNGKey(4), (256, CFG.hidden_size))
    assert rel(kimi.mla_mixer(u, w, TINY, query_block=64), kimi.mla_mixer(u, w, TINY, query_block=256)) < 1e-5


def test_the_router_bias_selects_scores_weigh_and_the_gates_sum_to_the_scale(params):
    lp = dict(params["layers"][1])
    x = jax.random.normal(jax.random.PRNGKey(11), (12, CFG.hidden_size))
    top, gates = glm5next.route(x, lp, CFG)
    s = np.asarray(jax.nn.sigmoid(x @ lp["router"]))
    want = np.argsort(-(s + np.asarray(lp["e_bias"])), axis=1)[:, :CFG.num_experts_per_tok]
    assert np.array_equal(np.sort(np.asarray(top), 1), np.sort(want, 1))
    np.testing.assert_allclose(np.asarray(gates).sum(1), CFG.routed_scaling_factor, rtol=1e-6)  # sum 1 x 2.827
    chosen = np.take_along_axis(s, np.asarray(top), 1)
    np.testing.assert_allclose(np.asarray(gates), 2.827 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    # a large bias on one expert makes every token choose it and changes no chosen score's weight
    lp["e_bias"] = lp["e_bias"].at[5].set(10.0)
    top2, gates2 = glm5next.route(x, lp, CFG)
    assert (np.asarray(top2) == 5).any(axis=1).all()
    at = np.asarray(top2) == 5
    np.testing.assert_allclose(np.asarray(gates2)[at], 2.827 * s[:, 5] / np.take_along_axis(s, np.asarray(top2), 1).sum(1), rtol=1e-5)
    # the reference's router agrees (ties apart: none with random scores)
    rtop, rgates = kimi.route(x, {k: np.asarray(v) for k, v in lp.items()}, kimi.expert_keys(TINY))
    assert np.array_equal(np.sort(np.asarray(rtop), 1), np.sort(np.asarray(top2), 1))
    np.testing.assert_allclose(np.sort(np.asarray(rgates), 1), np.sort(np.asarray(gates2), 1), rtol=1e-5)


def test_the_32_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test at the PUBLISHED split: 384 experts, top 8, 12 a
    chip over 32 chips (the widths tiny). The routed parts of all 32
    shares (the shared expert, which every chip computes alike, counted
    once) sum to the layer that holds all 384; held + absent pairs =
    rows x 8 on every chip; the router's width and its top 8 do not
    change with the share. The reference, given one share, gives that
    share's part."""
    cfg = dataclasses.replace(CFG, n_routed_experts=384, num_experts_per_tok=8, experts_held=12)
    rng = jax.random.PRNGKey(7)
    D, F, E = cfg.hidden_size, cfg.moe_intermediate_size, 384
    lp = {k: v for k, v in m.init_params_fast(dataclasses.replace(cfg, layers_served=(1,)), 3, jnp.float32)["layers"][0].items()}
    x = jax.random.normal(rng, (10, D))
    whole = dataclasses.replace(cfg, experts_first=0, experts_held=E)
    w_all = {"we_gate_up": jax.random.normal(jax.random.fold_in(rng, 1), (E, D, 2 * F)) * 0.1,
             "we_down": jax.random.normal(jax.random.fold_in(rng, 2), (E, F, D)) * 0.1}
    count = jnp.ones((10,), bool)
    uncut, stats = glm5next.moe(x, dict(lp, **w_all), whole, count, None)
    assert stats.tolist() == [80, 0, int(stats[2]), 384, int(stats[2]), 5 + 384]  # a 16-row tile an expert hit of ceil(80 / 16) + 384
    shared = glm5next.swiglu_mlp(x, lp["ws_gate_up"], lp["ws_down"], cfg.swiglu_limit)
    total, held_pairs = shared, 0
    top_whole, _ = glm5next.route(x, lp, whole)
    for chip in range(32):
        share = dataclasses.replace(cfg, experts_first=12 * chip, experts_held=12)
        mine = {k: v[12 * chip:12 * chip + 12] for k, v in w_all.items()}
        part, st = glm5next.moe(x, dict(lp, **mine), share, count, None)
        total = total + (part - shared)
        held_pairs += int(st[0])
        assert int(st[0]) + int(st[1]) == 10 * 8 and int(st[3]) == 12  # held + absent = rows x 8
        top, _ = glm5next.route(x, lp, share)
        assert top.shape == (10, 8) and np.array_equal(top, top_whole) and lp["router"].shape[1] == 384
        if chip in (0, 17):
            keys = dict(kimi.expert_keys(TINY), num_experts_per_tok=8, experts_first=12 * chip, n_routed_experts_held=12)
            ref = kimi.moe(x, {k: np.asarray(v) for k, v in lp.items()}, keys,
                           lambda e, mine=mine: (mine["we_gate_up"][e], mine["we_down"][e]))
            assert rel(ref, part) < 1e-5
    assert held_pairs == 80 and rel(total, uncut) < 1e-5


# --------------------------------------------------------------------------- #
# The paged walks against the plain reference


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_one_chunk_then_decode_steps_on_dirty_pages(params, sequence, kernel):
    toks, full = sequence
    caches = dirty_caches()
    row = np.zeros((1, 64), np.int32)
    row[0, :50] = toks[:50]
    logits, caches = m.prefill_paged(params, CFG, caches, jnp.asarray(row), jnp.asarray([50], jnp.int32),
                                     jnp.asarray([1], jnp.int32), TABLES, PAGE, grouped_matmul=kernel)
    assert rel(logits[0], full[49]) < TOL
    for p in range(50, 60):
        logits, caches = decode(params, caches, {1: (toks[p], p)}, kernel)
        assert rel(logits[1], full[p]) < TOL, p
    stats = dict(zip(m.STAT_NAMES, np.asarray(caches["stats"]).tolist()))
    assert stats["latent_tokens_read"] == 60  # every cached token up to the query's own: ONE layer's read
    assert stats["moe_pairs_held"] + stats["moe_pairs_absent"] == 2 * 4 and stats["moe_experts_held"] == 2 * 2
    assert_one_live_row_tiles(m.STAT_NAMES, stats, CFG, SLOTS)


@pytest.mark.parametrize("chunk,kernel", [(16, None), (32, "interpret"), (64, None)])
def test_chunked_extend_then_decode_through_the_cache(params, sequence, chunk, kernel):
    toks, full = sequence
    logits, caches = extend(params, dirty_caches(), toks, 2, chunk, kernel, upto=100)
    assert rel(logits, full[99]) < TOL
    assert int(caches["stats"][m.STAT_NAMES.index("latent_tokens_read")]) == sum(range(100 - (100 - 1) % chunk, 101))  # the last chunk's queries, each to itself
    for p in range(100, 106):
        logits, caches = decode(params, caches, {2: (toks[p], p)}, kernel)
        assert rel(logits[2], full[p]) < TOL, p


def test_a_prefix_hit_then_the_tail_equals_a_cold_walk_bit_for_bit(params, sequence):
    """What a stateless entry is: another row's pages mapped into this
    row's table. Row 0 walks 64 tokens; row 1's table maps row 0's first
    four pages and walks only the tail from offset 64. Logits and every
    page the tail wrote equal the cold walk's, bit for bit; the shared
    pages are untouched. Against the reference too."""
    toks, full = sequence
    _, base = extend(params, dirty_caches(), toks, 0, 32, upto=64)
    logits_cold, cold = extend(params, base, toks, 0, 32, upto=100, start=64)
    tables = np.asarray(TABLES).copy()
    tables[1, :4] = tables[0, :4]  # the hit: four shared pages
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
        hit_pool, cold_pool, base_pool = (np.asarray(c["lat"][l]) for c in (caches, cold, base))
        assert np.array_equal(hit_pool[tables[0, :4]], base_pool[tables[0, :4]])  # shared pages: read, never written
        assert np.array_equal(hit_pool[tables[1, 4:7]], cold_pool[tables[0, 4:7]])  # the tail's pages: the cold walk's


def test_a_row_with_nothing_valid_and_a_dead_row_change_nothing(params):
    caches = dirty_caches()
    _, after = m.extend_paged(params, CFG, caches, jnp.zeros((1, 16), jnp.int32), jnp.asarray([16], jnp.int32),
                              jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32), TABLES, S, PAGE)
    _, after = decode(params, after, {})
    for a, b in zip(caches["lat"], after["lat"]):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_rows_decoding_together_equal_their_solo_runs(params, sequence):
    toks, full = sequence
    _, caches = extend(params, dirty_caches(), toks, 0, 32, upto=40)
    _, caches = extend(params, caches, toks, 2, 32, upto=70)
    for j in range(4):
        logits, caches = decode(params, caches, {0: (toks[40 + j], 40 + j), 2: (toks[70 + j], 70 + j)}, "interpret")
        assert rel(logits[0], full[40 + j]) < TOL and rel(logits[2], full[70 + j]) < TOL
    assert int(caches["stats"][m.STAT_NAMES.index("latent_tokens_read")]) == 44 + 74


def test_several_live_rows_in_one_chunk_equal_their_solo_runs(params, sequence):
    """A wave of three rows at different offsets and lengths, one dead:
    what ``prefill_wave_tokens`` above one chunk would send this family."""
    toks, full = sequence
    _, caches = extend(params, dirty_caches(), toks, 0, 32, upto=32)
    rows = np.zeros((3, 32), np.int32)
    rows[0, :32], rows[1, :20] = toks[32:64], toks[:20]
    h, _ = m.extend_paged(params, CFG, caches, jnp.asarray(rows), jnp.asarray([32, 0, 0], jnp.int32),
                          jnp.asarray([32, 20, 0], jnp.int32), jnp.asarray([0, 1, 2], jnp.int32), TABLES, S, PAGE)
    logits = m.head(params, CFG, h)
    assert rel(logits[0], full[63]) < TOL and rel(logits[1], full[19]) < TOL


def test_an_all_bfloat16_control_fails_the_tolerance(params, sequence):
    """The reference one precision down (nothing in float32) is NOT
    within ``TOLERANCE`` of the float32 reference at its worst position,
    while the float32 walks are within 2e-5 at every one: as on the chip,
    the control's largest reading is a position where a router's top 4
    flipped (most positions read ~0.03)."""
    toks, full = sequence
    low = reference_logits(params, toks, precision="bfloat16")
    errs = [rel(low[t], full[t]) for t in range(len(toks))]
    assert max(errs) > kimi.TOLERANCE and float(np.median(errs)) < kimi.TOLERANCE, (max(errs), float(np.median(errs)))


def test_registry_resolves_the_family_and_what_it_declares():
    fam, cfg = registry.resolve("kimik2-debug")
    assert fam.name == "kimik2" and cfg is CFG and registry.family_of(CFG).name == "kimik2"
    # pages only, and nothing it does not bring
    assert not fam.fixed_state and fam.state_row_keys == () and fam.verify_paged is None and fam.extend_packed is None
    assert not fam.extend_reads_window and fam.weight_formats == () and fam.kv_formats == ()
    assert not fam.sharded and not fam.snapshot_pages and fam.fixed_state_bytes_per_slot(CFG) == 0
    shape = fam.paged_kv_shape(FULL)
    assert (shape.num_layers, shape.num_kv_heads, shape.head_dim, shape.num_heads, shape.bytes_per_token) == (5, 1, 640, 64, 6400)
    assert fam.span_fields(FULL) == {"latent_layers": 5}
    resolved = fam.resolve_kernels(cfg, "compiled")
    # (the debug preset's widths do not tile the chip: its chunks read through XLA, tests/test_latent_attention_chunk.py)
    assert resolved == {"grouped_matmul": "compiled", "latent_chunk": None}
    assert fam.resolve_kernels(FULL, "compiled") == {"grouped_matmul": "compiled", "latent_chunk": "compiled"}
    for walk in (m.prefill_paged, m.extend_paged, m.decode_paged):
        assert set(resolved) <= set(inspect.signature(walk).parameters)
    assert "page_kernel" in inspect.signature(m.decode_paged).parameters  # the engine's own, which serves the latent read
    assert fam.stat_names == m.STAT_NAMES == glm5next.MOE_STAT_NAMES + (
        "latent_tokens_read", "latent_chunk_kernel_layers", "latent_chunk_xla_layers")
    # llama declares everything the engine had given it; the five fixed-state families nothing new
    fams = registry.families()
    assert fams["llama"].sharded and fams["llama"].snapshot_pages and fams["llama"].weight_formats == ("int8", "w8a8")
    assert fams["llama"].kv_formats == ("int8", "int4")
    for name in ("phi4flash", "glm5next", "gigachat35", "afmoe", "solaropen2"):
        f = fams[name]
        assert f.fixed_state and not f.sharded and not f.snapshot_pages and f.weight_formats == f.kv_formats == ()
    # the program's model modules name the family; the engine and the server name no model
    import pathlib
    import generativeaiexamples_tpu

    pkg = pathlib.Path(generativeaiexamples_tpu.__file__).parent
    for sub in ("engine", "server"):
        for path in (pkg / sub).rglob("*.py"):
            assert "kimi" not in path.read_text(encoding="utf-8").lower(), path


# --------------------------------------------------------------------------- #
# The engine: served through the registry, pages only

BASE = dict(
    model_config_name="kimik2-debug", max_batch_size=3, max_seq_len=256, prefill_chunk=64,
    tensor_parallelism=1, decode_block=4, decode_runahead=1, page_size=16, prefix_cache_enable="auto",
    prefix_cache_slots=4, prefill_wave_tokens=128, dtype="float32", paged_kernel="interpret",
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
    """One chunk (5, 64), several (100: a wide and a narrow chunk; 150),
    more requests than slots one after another: every served token is
    the plain reference's argmax, through the interpreted kernels.
    Nothing compiles after warm-up; the wave may hold two rows."""
    assert engine._family.name == "kimik2" and engine._paged_kernel == "interpret" and not engine._fixed_state
    assert engine._family_kernels == {"grouped_matmul": "interpret", "latent_chunk": None}
    assert engine.shapes.max_wave_rows() == 2  # follows prefill_wave_tokens, like llama's
    assert engine._spec_available is False and engine._state_store_rows == 0 and engine._copy_state_fn is None
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, 250, size=n)] for n in (5, 64, 100, 150, 9)]
    before = engine.metrics
    outs = [list(engine.iter_ids(p, greedy(6), timeout=600)) for p in prompts]
    for p, o in zip(prompts, outs):
        assert len(o) == 6
        ref = reference_logits(engine.params, p + o)
        assert max(float(ref[len(p) - 1 + j].max() - ref[len(p) - 1 + j][t]) for j, t in enumerate(o)) < 1e-4
    assert engine.metrics["paged_attn_kernel_dispatches"] > before["paged_attn_kernel_dispatches"]
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


def test_a_prefix_hit_maps_pages_and_the_answer_is_the_cold_engines(engine):
    """The stateless store over the latent pools: the same 150-token
    prompt twice. The second admission maps the entry's 8 pages (128
    tokens) of every pool, prefills the 22-token tail at that offset,
    and answers as the first did and as an engine with the store off
    does; spans and gauge say what was shared."""
    from generativeaiexamples_tpu.engine import dispatch_timeline

    prompt = [int(t) for t in np.random.default_rng(9).integers(3, 250, size=150)]
    before = counters()
    first = list(engine.iter_ids(prompt, greedy(8), timeout=600))
    mid = counters()
    second = list(engine.iter_ids(prompt, greedy(8), timeout=600))
    after = counters()
    grew = lambda a, b, k: b.get(k, 0.0) - a.get(k, 0.0)  # noqa: E731
    assert first == second and len(first) == 8
    assert grew(before, mid, "genai_engine_prefix_cache_hits_total") == 0
    assert grew(mid, after, "genai_engine_prefix_cache_hits_total") == 1
    assert grew(mid, after, "genai_engine_prefix_cache_tokens_reused_total") == 128
    assert grew(mid, after, "genai_engine_kv_prefix_pages_mapped_total") == 8
    assert grew(mid, after, "genai_engine_prefill_tokens_total") == 22
    for name in ("genai_engine_prefix_state_saves_total", "genai_engine_prefix_state_restores_total"):
        assert grew(before, after, name) == 0  # pages only: no state row travels
    ref = reference_logits(engine.params, prompt + second)
    assert max(float(ref[149 + j].max() - ref[149 + j][t]) for j, t in enumerate(second)) < 1e-4
    spans = [s for s in dispatch_timeline.recent_spans(256) if s.get("kind") in ("decode", "prefill_chunk")]
    chunks = [s for s in spans if s["kind"] == "prefill_chunk"]
    assert chunks[0]["prefix_depth_tokens"] == 128 and chunks[0]["tokens"] == 22  # newest first: the tail after the hit
    assert sum("prefix_depth_tokens" in s for s in chunks[:4]) == 1  # the cold admission's chunks carry none
    for s in spans[:6]:
        assert s["latent_layers"] == 3 and "state_rows" not in s and "moe_experts_hit" in s
    step = [s for s in spans if s["kind"] == "decode"][0]
    assert 150 < step["latent_tokens_read"] <= 158 and step["kv_pages_walked"] >= 10
    assert grew(before, after, "genai_engine_latent_read_tokens_total") > 0
    assert "genai_engine_prefix_shared_pages_in_use" in after
    # while a row that entered through the entry is live, the gauge counts the pages both hold
    stream = engine.iter_ids(prompt, greedy(40), timeout=600)
    next(stream)
    with engine._lock:
        engine._shared_pages_stale = True
        engine._update_occupancy_gauges()
    assert counters()["genai_engine_prefix_shared_pages_in_use"] == 8
    list(stream)
    cold = build(prefix_cache_enable="off")
    try:
        assert list(cold.iter_ids(prompt, greedy(8), timeout=600)) == first
    finally:
        cold.shutdown()
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


# widths that tile the chip (heads and a latent of whole lane tiles) at a size a CPU serves: the dense and one expert layer
LANES = dataclasses.replace(
    CFG, layers_served=(0, 1), num_heads=2, kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)


def test_an_engine_at_widths_that_tile_reads_its_chunks_through_the_kernel():
    """``ops/latent_attention.py`` ``latent_chunk_read`` interpreted, in
    the engine's own extend programs: a 150-token prompt in three chunks
    (the kernel at offsets 0, 64 and 128) is answered as the engine with
    every kernel off answers it and as the reference does; each chunk's
    span says both latent layers read through the kernel and the counter
    grew under ``path="kernel"`` alone, under ``path="xla"`` with it off."""
    from generativeaiexamples_tpu.engine import dispatch_timeline

    name = "kimik2-lanes-test"
    m.PRESETS[name] = LANES
    reads = lambda c, path: c.get(f'genai_engine_latent_chunk_reads_total{{path="{path}"}}', 0.0)  # noqa: E731
    prompt = [int(t) for t in np.random.default_rng(3).integers(3, 250, size=150)]
    try:
        assert registry.resolve(name)[0].resolve_kernels(LANES, "interpret")["latent_chunk"] == "interpret"
        answers = {}
        for kernel in ("interpret", "off"):
            eng = build(model_config_name=name, paged_kernel=kernel, prefix_cache_enable="off")
            try:
                assert eng._family_kernels["latent_chunk"] == (None if kernel == "off" else "interpret")
                before, t0 = counters(), time.time()
                answers[kernel] = list(eng.iter_ids(prompt, greedy(6), timeout=600))
                after = counters()
                chunks = [s for s in dispatch_timeline.recent_spans(64) if s["kind"] == "prefill_chunk" and s["t_wall"] >= t0]
                served, other = ("kernel", "xla") if kernel == "interpret" else ("xla", "kernel")
                assert len(chunks) == 3
                assert all(s[f"latent_chunk_{served}_layers"] == 2 and s[f"latent_chunk_{other}_layers"] == 0 for s in chunks)
                assert reads(after, served) - reads(before, served) == 6 and reads(after, other) == reads(before, other)
                steps = [s for s in dispatch_timeline.recent_spans(64) if s["kind"] == "decode" and s["t_wall"] >= t0]
                assert steps and all(s["latent_chunk_kernel_layers"] == s["latent_chunk_xla_layers"] == 0 for s in steps)
            finally:
                eng.shutdown()
        assert answers["interpret"] == answers["off"] and len(answers["off"]) == 6
        params = m.init_params_fast(LANES, 0, jnp.float32)
        cfg = dict(TINY, layers_served=[0, 1], layers=2, num_attention_heads=2, kv_lora_rank=128, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128)
        ref = reference_logits(params, prompt + answers["interpret"], cfg=cfg)
        assert max(float(ref[149 + j].max() - ref[149 + j][t]) for j, t in enumerate(answers["interpret"])) < 1e-4
    finally:
        del m.PRESETS[name]


REFUSED = {
    "tensor_parallel": (dict(tensor_parallelism=2), "sharded mesh.*declares no sharded walk"),
    "spec_decode": (dict(spec_decode_enable="on"), "speculative decoding.*registers no verify walk"),
    "int8_weights": (dict(quantization="int8"), r"quantization='int8'.*reads weight formats \[\]"),
    "int8_kv": (dict(kv_cache_dtype="int8"), r"kv_cache_dtype='int8'.*reads pool formats \[\]"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_engine_build_refuses_a_pages_only_family_what_it_does_not_declare(feature):
    """One clear error each, naming what the family lacks; none speaks of
    a fixed per-slot state, which this family has none of."""
    overrides, message = REFUSED[feature]
    with pytest.raises(ValueError, match=message) as exc:
        build(**overrides)
    assert "kimik2 model 'kimik2-debug'" in str(exc.value) and "fixed per-slot state" not in str(exc.value)


@pytest.mark.parametrize("call", ["drain", "restore_snapshot"])
def test_request_snapshots_are_refused_where_they_are_taken_with_the_familys_own_reason(engine, call):
    from generativeaiexamples_tpu.engine.request_snapshot import SnapshotError

    with pytest.raises(SnapshotError, match="kimik2 family, whose page pools are not the per-layer K and V pages") as exc:
        engine.drain(timeout=1) if call == "drain" else engine.restore_snapshot(None)
    assert "fixed per-slot state" not in str(exc.value) and not engine.is_draining()


FIXED_STATE_REFUSED = {
    "tensor_parallel": (dict(tensor_parallelism=2), "sharded mesh"),
    "prefix_cache": (dict(prefix_cache_enable="auto", prefix_cache_slots=2), "prefix-cache reuse"),
    "spec_decode": (dict(spec_decode_enable="on"), "speculative verify"),
    "int8_weights": (dict(quantization="int8"), "quantization='int8'"),
    "int8_kv": (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
}


@pytest.mark.parametrize("feature", sorted(FIXED_STATE_REFUSED))
@pytest.mark.parametrize("name", ["phi4flash-debug", "glm5next-debug", "gigachat35-debug", "afmoe-debug", "solaropen2-debug"])
def test_the_five_fixed_state_families_are_refused_exactly_what_they_were(name, feature):
    """The validation asks the family record now; for a family that
    declares fixed per-slot state the refusals and their words are the
    ones it had (the one family that names state rows keeps its store)."""
    overrides, message = FIXED_STATE_REFUSED[feature]
    base = dict(BASE, model_config_name=name, prefix_cache_enable="off", prefix_cache_slots=0, prefill_wave_tokens=64)
    if name == "solaropen2-debug" and feature == "prefix_cache":
        fam = registry.resolve(name)[0]
        assert fam.state_row_keys  # carried by the store: not refused (tests/test_prefix_state.py drives it)
        return
    with pytest.raises(ValueError, match=message) as exc:
        build(**dict(base, **overrides))
    assert "fixed per-slot state" in str(exc.value)
