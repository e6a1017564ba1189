"""GLM-5.3-Flash (models/glm5next.py) at a tiny size that keeps the five
layers of the served share — KDA + dense MLP; sparse latent attention,
KDA, KDA, KDA with experts — and the engine serving it through the model
registry: each mechanism against its plain form, the three paged walks
against the model's own whole-sequence forward (logits, not tokens), a
context past ``index_topk`` where the selection discards keys, slot
reuse, the expert share of eight chips adding up to the uncut layer.
The plain float32 REFERENCE (an independent implementation) is held
against the same model in tests/perfbench/test_perfbench_glm5next.py.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import glm5next as m
from generativeaiexamples_tpu.models import registry
from generativeaiexamples_tpu.ops import grouped_matmul, latent_attention
from tests.expert_stats import assert_one_live_row_tiles


@pytest.fixture(autouse=True, scope="module")
def float32_products():
    """float32 walks are held to float32 forwards: products at full
    precision, for THIS module only (a process-wide setting would reach
    the kernels the next test file compiles)."""
    with jax.default_matmul_precision("highest"):
        yield

CFG = m.PRESETS["glm5next-debug"]
PAGE, SLOTS, PMAX = 16, 3, 8
S = PAGE * PMAX
TOL = 2e-5  # float32 walks against the float32 whole-sequence forward
TABLES = jnp.asarray(1 + np.arange(SLOTS * PMAX).reshape(SLOTS, PMAX), jnp.int32)


@pytest.fixture(scope="module")
def params():
    return m.init_params_fast(CFG, 0, jnp.float32)


@pytest.fixture(scope="module")
def sequence(params):
    """106 tokens (topk covers 32: the selection discards from 36 on)
    and their logits at every position."""
    rng = np.random.default_rng(0)
    toks = rng.integers(0, CFG.vocab_size, size=(1, 106))
    return toks, np.asarray(m.forward_full(params, CFG, jnp.asarray(toks)))[0]


def dirty_caches():
    """Caches in which every state holds another tenant's values."""
    caches = m.init_paged_cache(CFG, 1 + SLOTS * PMAX, PAGE, SLOTS, jnp.float32)
    return jax.tree.map(lambda x: x + 3 if x.dtype == jnp.int32 else x + 3.0, caches)


def rel(a, b):
    b = np.asarray(b)
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))


@functools.lru_cache(maxsize=None)
def _walks(kernel):
    """The extend and decode walks, compiled once a shape and kernel path."""
    ext = jax.jit(lambda params, caches, row, off, n, slot: m.extend_paged(
        params, CFG, caches, row, off, n, slot, TABLES, S, PAGE, grouped_matmul=kernel))
    dec = jax.jit(lambda params, caches, tok, pos, live: m.decode_paged(
        params, CFG, caches, tok, pos, live, TABLES, S, PAGE, page_kernel=kernel, grouped_matmul=kernel,
        delta_step=kernel))
    return ext, dec


def extend(params, caches, toks, slot, chunk, kernel=None, upto=None):
    """Chunked extend of ``toks`` on ``slot``; returns (logits, caches)."""
    n_all = len(toks) if upto is None else upto
    for off in range(0, n_all, chunk):
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
# Each mechanism against its plain form


def test_the_share_and_the_memory_plan():
    full = m.PRESETS["glm-5.3-flash-ep8"]
    assert [mx for mx, _ in full.layers] == ["kda", "dsa", "kda", "kda", "kda"]
    assert [f for _, f in full.layers] == ["dense", "sparse", "sparse", "sparse", "sparse"]
    assert m.count_logical_params(full) == 4_718_313_870
    assert m.kv_bytes_per_token(full) == 1088  # a 512-wide latent row and 64 B of pooled index key
    assert m.fixed_state_bytes_per_slot(full) == 4 * (64 * 128 * 128 * 4 + 3 * 24576 * 2) + 512
    assert full.topk_groups == 512 and full.experts_held == 36 and full.n_routed_experts == 288
    with pytest.raises(ValueError, match="experts held"):
        m.validate(dataclasses.replace(full, experts_first=280))


def test_parameter_count_matches_the_tree(params):
    leaves = jax.tree.leaves(params)
    assert sum(int(np.prod(x.shape)) for x in leaves) == m.count_logical_params(CFG)


@pytest.mark.parametrize("block", [4, 16])
def test_kda_block_wise_equals_token_by_token(block):
    """The WY / UT-transform walk against the delta rule stepped a token
    at a time, with decays down to the lower bound (-5 a token)."""
    N, T, H, Dk = 2, 64, 3, 16
    k0 = jax.random.PRNGKey(1)
    q = jax.random.normal(k0, (N, T, H, Dk))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (N, T, H, Dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(jax.random.fold_in(k0, 2), (N, T, H, Dk))
    beta = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(k0, 3), (N, T, H)))
    g = -jax.random.uniform(jax.random.fold_in(k0, 4), (N, T, H, Dk)) * 5.0
    S0 = jax.random.normal(jax.random.fold_in(k0, 5), (N, H, Dk, Dk))

    def step(Sx, xs):
        o, Sx = m.kda_step(Sx, *xs)
        return Sx, o

    S1, o1 = jax.lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, beta, g)))
    o2, S2 = m.kda_chunk(S0, q, k, v, beta, g, block=block)
    assert rel(o2, jnp.moveaxis(o1, 0, 1)) < 1e-5 and rel(S2, S1) < 1e-5
    # a token with beta = 0 and g = 0 leaves the state as it is
    o3, S3 = m.kda_chunk(S0, q, k, v, beta * 0, g * 0, block=block)
    assert rel(S3, S0) < 1e-6


def test_kda_step_is_the_delta_rule_as_written():
    Dk = 8
    rng = np.random.default_rng(0)
    Sx, q, k, v, g = (rng.standard_normal(s) for s in ((Dk, Dk), (Dk,), (Dk,), (Dk,), (Dk,)))
    g, beta = -np.abs(g), 0.7
    S1 = np.exp(g)[:, None] * Sx
    S1 = S1 + beta * np.outer(k, v - S1.T @ k)
    o, S2 = m.kda_step(jnp.asarray(Sx), jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(beta), jnp.asarray(g))
    assert rel(S2, S1) < 1e-6 and rel(o, S1.T @ q) < 1e-6


def test_sinkhorn_makes_the_mixing_matrix_doubly_stochastic(params):
    X = jax.random.normal(jax.random.PRNGKey(2), (5, CFG.hc_mult, CFG.hidden_size))
    pre, post, res = m.hc_maps(X, params["layers"][0], "mix", CFG)
    assert np.allclose(np.sum(res, axis=-1), 1.0, atol=1e-4) and np.allclose(np.sum(res, axis=-2), 1.0, atol=1e-4)
    assert np.all(np.asarray(res) > 0) and np.all(np.diagonal(np.asarray(res), axis1=-2, axis2=-1) > 0.9)  # near the identity
    assert pre.shape == post.shape == (5, 4) and np.all((post > 0) & (post < 2))
    m4 = jnp.asarray(np.random.default_rng(0).uniform(0.1, 2.0, size=(3, 4, 4)), jnp.float32)
    want = np.asarray(m4)
    for _ in range(20):
        want = want / (want.sum(-1, keepdims=True) + 1e-6)
        want = want / (want.sum(-2, keepdims=True) + 1e-6)
    assert rel(m.sinkhorn(m4, 20, 1e-6), want) < 1e-5


def test_selection_keeps_the_best_groups_and_the_open_tail():
    scores = jnp.asarray([[[0.5, 0.9, 0.1, 0.9, 0.3, 0.7]]])  # one query, six groups
    # five groups complete before the query's own: the best three of THOSE (group 5 is not one)
    sel = np.asarray(m.select_groups(scores, jnp.asarray([[5]]), topk=3))[0, 0]
    assert sel.tolist() == [True, True, False, True, False, False]
    few = np.asarray(m.select_groups(scores, jnp.asarray([[2]]), topk=3))[0, 0]
    assert few.tolist() == [True, True, False, False, False, False]  # fewer complete groups than topk: all
    mask = np.asarray(m.token_mask(jnp.asarray(few)[None, None], jnp.asarray([[9]]), CFG))[0, 0]
    assert mask.tolist() == [True] * 8 + [True, True] + [False] * 14  # groups 0, 1 and the open group up to 9


def test_selection_ranks_by_score_with_ties_to_the_lower_index():
    scores = jnp.asarray([[[0.5, 0.9, 0.1, 0.9, 0.3, 0.7]]])
    sel = np.asarray(m.select_groups(scores, jnp.asarray([[6]]), topk=3))[0, 0]
    assert [i for i, s in enumerate(sel) if s] == [1, 3, 5]
    tie = np.asarray(m.select_groups(jnp.zeros((1, 1, 6)), jnp.asarray([[6]]), topk=2))[0, 0]
    assert [i for i, s in enumerate(tie) if s] == [0, 1]


@pytest.mark.parametrize("held", [(0, 2), (6, 4)])
def test_grouped_matmul_equals_the_dense_sum_over_held_experts(held):
    first, E = held
    N, D, F, K, routed = 24, 64, 32, 4, 16
    k0 = jax.random.PRNGKey(0)
    x = jax.random.normal(k0, (N, D))
    wgu = jax.random.normal(jax.random.fold_in(k0, 1), (E, D, 2 * F)) * 0.1
    wd = jax.random.normal(jax.random.fold_in(k0, 2), (E, F, D)) * 0.1
    top = jax.random.randint(jax.random.fold_in(k0, 3), (N, K), 0, routed)
    local = jnp.where((top >= first) & (top < first + E), top - first, E)
    gates = jax.random.uniform(jax.random.fold_in(k0, 4), (N, K))
    a, s1 = grouped_matmul.grouped_mlp(x, local, gates, wgu, wd, limit=10.0, kernel=None)
    b, s2 = grouped_matmul.grouped_mlp(x, local, gates, wgu, wd, limit=10.0, kernel="interpret")
    assert rel(b, a) < 1e-5 and np.array_equal(s1, s2) and int(s1.sum()) == int((local < E).sum())
    none, _ = grouped_matmul.grouped_mlp(x, jnp.full((N, K), E), gates, wgu, wd, limit=10.0, kernel="interpret")
    assert float(jnp.max(jnp.abs(none))) == 0.0  # no pair held: nothing computed, nothing added
    plan = grouped_matmul.plan(local, E, 16)
    assert int(plan.tiles_used[0]) == int(np.sum(-(-np.asarray(plan.sizes) // 16)))


def test_latent_kernel_equals_masked_attention_over_the_same_rows():
    B, H, R, page, Pmax = 3, 4, 32, 8, 5
    k0 = jax.random.PRNGKey(0)
    pool = jax.random.normal(k0, (1 + B * Pmax, page, R))
    tables = jnp.asarray(1 + np.arange(B * Pmax).reshape(B, Pmax), jnp.int32)
    pos = jnp.asarray([0, 13, 39], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(k0, 9), (B, H, R))
    tok = jnp.arange(Pmax * page)[None, :]
    ok = (tok <= pos[:, None]) & ((jax.random.uniform(jax.random.fold_in(k0, 5), (B, Pmax * page)) > 0.4) | (tok == pos[:, None]))
    bias = jnp.where(ok, 0.0, -1e30)
    out = latent_attention.latent_attention(q, pool, bias, tables, pos, scale=0.25, interpret=True)
    c = pool[tables].reshape(B, Pmax * page, R)
    sc = jnp.einsum("bhr,bsr->bhs", q, c) * 0.25 + bias[:, None, :]
    assert rel(out, jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(sc, -1), c)) < 1e-5


def test_the_absorbed_latent_read_equals_the_unabsorbed_one(params):
    """q_h . (W_uk,h c) = (W_uk,h^T q_h) . c and sum p (W_uv,h c) = W_uv,h (sum p c)."""
    lp = params["layers"][CFG.layers_of("dsa")[0]]
    k0 = jax.random.PRNGKey(3)
    T = 12
    q = jax.random.normal(k0, (T, CFG.num_heads, CFG.qk_head_dim))
    c = jax.random.normal(jax.random.fold_in(k0, 1), (T, CFG.kv_lora_rank))
    mask = jnp.tril(jnp.ones((T, T), bool))
    kh = jnp.einsum("sr,hdr->shd", c, lp["wuk"])
    vh = jnp.einsum("sr,hrv->shv", c, lp["wuv"])
    p = jax.nn.softmax(jnp.where(mask[None], jnp.einsum("thd,shd->hts", q, kh) * 0.25, -1e30), -1)
    plain = jnp.einsum("hts,shv->thv", p, vh)
    qlat = jnp.einsum("thd,hdr->thr", q, lp["wuk"])
    p2 = jax.nn.softmax(jnp.where(mask[None], jnp.einsum("thr,sr->hts", qlat, c) * 0.25, -1e30), -1)
    absorbed = jnp.einsum("thr,hrv->thv", jnp.einsum("hts,sr->thr", p2, c), lp["wuv"])
    assert rel(absorbed, plain) < 1e-5


def test_eight_chips_partial_expert_outputs_add_up_to_the_uncut_layer(params):
    """The share test: with 2 of 16 experts a chip, the routed parts of
    the eight chips (the shared expert counted once) sum to the layer
    that holds all 16; the router's width and its top 4 do not change
    with the share."""
    l = CFG.sparse_layers()[0]
    lp = params["layers"][l]
    rng = jax.random.PRNGKey(7)
    x = jax.random.normal(rng, (10, CFG.hidden_size))
    whole = dataclasses.replace(CFG, experts_first=0, experts_held=CFG.n_routed_experts)
    w_all = {"we_gate_up": jax.random.normal(jax.random.fold_in(rng, 1), (16, CFG.hidden_size, 2 * CFG.moe_intermediate_size)) * 0.1,
             "we_down": jax.random.normal(jax.random.fold_in(rng, 2), (16, CFG.moe_intermediate_size, CFG.hidden_size)) * 0.1}
    count = jnp.ones((10,), bool)
    uncut, stats = m.moe(x, dict(lp, **w_all), whole, count, None)
    assert stats.tolist() == [40, 0, int(stats[2]), 16, int(stats[2]), 3 + 16]  # a 16-row tile an expert hit of ceil(40 / 16) + 16
    shared = m.swiglu_mlp(x, lp["ws_gate_up"], lp["ws_down"], CFG.swiglu_limit)
    total, held_pairs = shared, 0
    top_whole, _ = m.route(x, lp, whole)
    for chip in range(8):
        share = dataclasses.replace(CFG, experts_first=2 * chip, experts_held=2)
        mine = {k: v[2 * chip:2 * chip + 2] for k, v in w_all.items()}
        part, st = m.moe(x, dict(lp, **mine), share, count, None)
        total = total + (part - shared)
        held_pairs += int(st[0])
        top, _ = m.route(x, lp, share)
        assert top.shape == (10, 4) and np.array_equal(top, top_whole) and lp["router"].shape[1] == 16
    assert held_pairs == 40 and rel(total, uncut) < 1e-5


# --------------------------------------------------------------------------- #
# The paged walks against the whole-sequence forward


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_prefill_then_decode_past_the_selection_on_dirty_slots(params, sequence, kernel):
    toks, full = sequence
    caches = dirty_caches()
    row = np.zeros((1, 64), np.int32)
    row[0, :50] = toks[0, :50]
    logits, caches = m.prefill_paged(params, CFG, caches, jnp.asarray(row), jnp.asarray([50], jnp.int32),
                                     jnp.asarray([1], jnp.int32), TABLES, PAGE, grouped_matmul=kernel)
    assert rel(logits[0], full[49]) < TOL
    for p in range(50, 60):
        logits, caches = decode(params, caches, {1: (toks[0, p], p)}, kernel)
        assert rel(logits[1], full[p]) < TOL, p
    stats = dict(zip(m.STAT_NAMES, np.asarray(caches["stats"]).tolist()))
    assert stats["dsa_context_tokens"] == 60 and stats["dsa_tokens_selected"] == 32 + 4  # 8 groups and the open one
    assert stats["moe_pairs_held"] + stats["moe_pairs_absent"] == 4 * 4 and stats["moe_experts_held"] == 4 * 2
    assert_one_live_row_tiles(m.STAT_NAMES, stats, CFG, SLOTS)


@pytest.mark.parametrize("chunk,kernel", [(16, None), (32, "interpret"), (64, None)])
def test_chunked_extend_carries_state_and_pages_from_chunk_to_chunk(params, sequence, chunk, kernel):
    """Narrow and wide rungs: the state, the convolution tails, the open
    group's sum and the pages go from chunk to chunk; 100 tokens are past
    the selection's reach (32), so keys are discarded."""
    toks, full = sequence
    logits, caches = extend(params, dirty_caches(), toks[0], 2, chunk, kernel, upto=100)
    assert rel(logits, full[99]) < TOL
    for p in range(100, 106):
        logits, caches = decode(params, caches, {2: (toks[0, p], p)}, kernel)
        assert rel(logits[2], full[p]) < TOL, p


def test_a_narrow_last_chunk_leaves_what_a_wide_one_leaves(params, sequence):
    toks, full = sequence
    _, wide = extend(params, dirty_caches(), toks[0], 0, 64, upto=70)
    caches = dirty_caches()
    _, caches = extend(params, caches, toks[0], 0, 64, upto=64)
    row = np.zeros((1, 16), np.int32)
    row[0, :6] = toks[0, 64:70]
    h, narrow = m.extend_paged(params, CFG, caches, jnp.asarray(row), jnp.asarray([64], jnp.int32),
                               jnp.asarray([6], jnp.int32), jnp.asarray([0], jnp.int32), TABLES, S, PAGE)
    assert rel(m.head(params, CFG, h)[0], full[69]) < TOL
    for a, b in zip(wide["kda"], narrow["kda"]):
        assert rel(b[0], a[0]) < TOL
    assert rel(narrow["idx_sum"][0][0], wide["idx_sum"][0][0]) < TOL


def test_a_row_with_nothing_valid_and_a_dead_row_change_nothing(params):
    caches = dirty_caches()
    _, after = m.extend_paged(params, CFG, caches, jnp.zeros((1, 16), jnp.int32), jnp.asarray([16], jnp.int32),
                              jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32), TABLES, S, PAGE)
    _, after = decode(params, after, {})
    for name in ("lat", "idx", "idx_sum", "kda", "conv"):
        for a, b in zip(caches[name], after[name]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_a_freed_slot_shows_no_trace_of_its_last_tenant(params, sequence):
    toks, full = sequence
    caches = dirty_caches()
    other = np.random.default_rng(5).integers(0, CFG.vocab_size, size=(90,))
    _, caches = extend(params, caches, other, 1, 32)
    logits, _ = extend(params, caches, toks[0], 1, 32, upto=70)
    assert rel(logits, full[69]) < TOL


def test_rows_decoding_together_equal_their_solo_runs(params, sequence):
    toks, full = sequence
    caches = dirty_caches()
    _, caches = extend(params, caches, toks[0], 0, 32, upto=40)
    _, caches = extend(params, caches, toks[0], 2, 32, upto=70)
    for j in range(4):
        logits, caches = decode(params, caches, {0: (toks[0, 40 + j], 40 + j), 2: (toks[0, 70 + j], 70 + j)}, "interpret")
        assert rel(logits[0], full[40 + j]) < TOL and rel(logits[2], full[70 + j]) < TOL


def test_decode_through_the_step_kernel_equals_the_xla_step_in_logits_and_every_cache_leaf(params, sequence):
    """``delta_step`` on against off, every other path the same: two
    live rows and a dead one on dirty slots, three steps. The kernel
    changes the summation order over Dk and nothing else."""
    toks, _ = sequence
    caches = dirty_caches()
    _, caches = extend(params, caches, toks[0], 0, 32, upto=40)
    _, caches = extend(params, caches, toks[0], 2, 32, upto=70)
    off = on = caches
    walk = jax.jit(lambda caches, tok, pos, live, path: m.decode_paged(
        params, CFG, caches, tok, pos, live, TABLES, S, PAGE, delta_step=path), static_argnums=4)
    live = jnp.asarray([True, False, True])
    for j in range(3):
        tok = jnp.asarray([toks[0, 40 + j], 0, toks[0, 70 + j]], jnp.int32)
        pos = jnp.asarray([40 + j, 0, 70 + j], jnp.int32)
        logits_off, off = walk(off, tok, pos, live, None)
        logits_on, on = walk(on, tok, pos, live, "interpret")
        assert rel(logits_on[live], logits_off[live]) < 1e-5, j
    assert int(on["stats"][-1]) == 2 and int(off["stats"][-1]) == 0 and m.STAT_NAMES[-1] == "state_kernel_rows"
    assert np.array_equal(np.asarray(on["stats"][:-1]), np.asarray(off["stats"][:-1]))
    for name in ("lat", "idx", "idx_sum", "kda", "conv"):
        for a, b in zip(on[name], off[name]):
            assert a.dtype == b.dtype and rel(a, b) < 1e-5, name
    for a, b in zip(on["kda"], caches["kda"]):
        assert a.dtype == jnp.float32 and np.array_equal(np.asarray(a[1]), np.asarray(b[1]))  # the dead row: bit-equal


def test_registry_resolves_the_family_and_what_it_declares():
    fam, cfg = registry.resolve("glm5next-debug")
    assert fam.name == "glm5next" and fam.fixed_state and fam.verify_paged is None and cfg is CFG
    shape = fam.paged_kv_shape(m.PRESETS["glm-5.3-flash-ep8"])
    assert (shape.num_layers, shape.num_kv_heads, shape.head_dim, shape.num_heads, shape.bytes_per_token) == (1, 1, 512, 64, 1088)
    paths = fam.resolve_kernels(cfg, "compiled")
    assert paths == {"grouped_matmul": "compiled", "delta_step": "compiled"}
    # the engine hands a resolved path to the walks as a keyword of the SAME name: a walk that took it
    # under another name would swallow it in **_paths and serve the XLA path (found on the chip, PR 35)
    import inspect

    for walk in (m.prefill_paged, m.extend_paged, m.decode_paged):
        assert set(paths) <= set(inspect.signature(walk).parameters)
    assert fam.stat_names == m.STAT_NAMES and registry.family_of(CFG).name == "glm5next"
    assert m.STAT_NAMES[:6] == m.MOE_STAT_NAMES and m.MOE_STAT_NAMES[3:] == ("moe_experts_held", "moe_tiles_used", "moe_tiles_planned")
    assert registry.resolve("phi4flash-debug")[0].stat_names == () and registry.resolve("debug")[0].resolve_kernels(None, "compiled") == {}


# --------------------------------------------------------------------------- #
# The engine: served through the registry

BASE = dict(
    model_config_name="glm5next-debug", max_batch_size=3, max_seq_len=256, prefill_chunk=64,
    tensor_parallelism=1, decode_block=4, decode_runahead=1, page_size=16, prefix_cache_enable="off",
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


def test_engine_serves_every_prompt_shape_as_the_models_own_argmax(engine):
    """One chunk (5, 64), several (100: a wide and a
    narrow chunk; 150), more requests than slots one after another: every
    served token is the whole-sequence forward's argmax, through the
    interpreted kernels. Nothing compiles after warm-up."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    assert engine._family.name == "glm5next" and engine._paged_kernel == "interpret"
    assert engine._family_kernels == {"grouped_matmul": "interpret", "delta_step": "interpret"}
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, 250, size=n)] for n in (5, 64, 100, 150, 9)]
    before = engine.metrics
    outs = [list(engine.iter_ids(p, SamplingParams(temperature=0.0, max_tokens=6), timeout=600)) for p in prompts]
    full = jax.jit(lambda params, toks: m.forward_full(params, engine.model_config, toks))
    for p, o in zip(prompts, outs):
        assert len(o) == 6
        ref = np.asarray(full(engine.params, jnp.asarray([p + o], jnp.int32)))[0]
        assert max(float(ref[len(p) - 1 + j].max() - ref[len(p) - 1 + j][t]) for j, t in enumerate(o)) < 1e-4
    assert engine.metrics["paged_attn_kernel_dispatches"] > before["paged_attn_kernel_dispatches"]
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


def test_engine_reads_the_familys_counts_back_with_the_tokens(engine):
    from generativeaiexamples_tpu.engine import dispatch_timeline
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams
    from generativeaiexamples_tpu.utils import metrics as metrics_mod

    def read():
        out = {}
        for line in metrics_mod.get_registry().render().splitlines():
            if line.startswith("genai_engine_") and " " in line:
                k, v = line.rsplit(" ", 1)
                out[k] = float(v)
        return out

    before = read()
    list(engine.iter_ids(list(range(3, 103)), SamplingParams(temperature=0.0, max_tokens=9), timeout=600))
    after = read()
    grew = lambda k: after.get(k, 0.0) - before.get(k, 0.0)  # noqa: E731
    assert grew("genai_engine_state_slot_resets_total") == 1
    assert grew("genai_engine_prefill_cross_skipped_tokens_total") == 0  # every layer sees every token
    assert grew('genai_engine_moe_pairs_total{held="true"}') > 0 and grew('genai_engine_moe_pairs_total{held="false"}') > 0
    assert 0 < grew("genai_engine_dsa_selected_tokens_total") < grew("genai_engine_dsa_context_tokens_total")
    assert after["genai_engine_fixed_state_bytes"] == 3 * m.fixed_state_bytes_per_slot(CFG, 2)
    # (the ring is the process's: another family's engine of this worker may have written to it; newest first)
    spans = [s for s in dispatch_timeline.recent_spans(256)
             if s.get("kind") in ("decode", "prefill_chunk") and "dsa_tokens_selected" in s]
    chunk = [s for s in spans if s["kind"] == "prefill_chunk"][0]
    step = [s for s in spans if s["kind"] == "decode"][0]
    for s in (chunk, step):
        assert s["state_rows"] == 1 and s["moe_experts_held"] == 8 and s["moe_experts_hit"] >= 1
        assert s["moe_pairs_held"] >= s["moe_experts_hit"] and "kv_readers" not in s and "cross_skipped_tokens" not in s
    assert step["dsa_tokens_selected"] <= 36 < step["dsa_context_tokens"]
    # the step kernel engaged on every row a decode dispatch advanced, on none of a chunk's
    assert step["state_kernel_rows"] == step["state_rows"] and chunk["state_kernel_rows"] == 0
    assert grew("genai_engine_state_kernel_rows_total") >= 1
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


REFUSED = {
    "tensor_parallel": (dict(tensor_parallelism=2), "sharded mesh"),
    "prefix_cache": (dict(prefix_cache_enable="auto", prefix_cache_slots=2), "prefix-cache reuse"),
    "spec_decode": (dict(spec_decode_enable="on"), "speculative verify"),
    "int8_weights": (dict(quantization="int8"), "quantization='int8'"),
    "int8_kv": (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_engine_build_refuses_what_cannot_carry_a_fixed_state(feature):
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    overrides, message = REFUSED[feature]
    with pytest.raises(ValueError, match=message):
        LLMEngine(EngineConfig(**dict(BASE, **overrides)))
