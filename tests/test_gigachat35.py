"""GigaChat3.5 (models/gigachat35.py) at a tiny size that keeps the five
layers of the served share — Gated DeltaNet + dense MLP; latent
attention, then three Gated DeltaNet layers with experts — and the
engine serving it through the model registry: each mechanism against its
plain form, the three paged walks against the benchmark's plain float32
reference (``perfbench/arch/gigachat35.py``: an independent
implementation; logits, not tokens), YaRN positions past the original
context, slot reuse, the expert shares adding up to the uncut layer.
"""
import dataclasses
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine import kv_pages
from generativeaiexamples_tpu.models import gigachat35 as m
from generativeaiexamples_tpu.models import glm5next, registry
from generativeaiexamples_tpu.ops import latent_attention
from perfbench.arch import gigachat35 as giga
from tests.expert_stats import assert_one_live_row_tiles
from tests.perfbench.test_perfbench_gigachat35 import TINY


@pytest.fixture(autouse=True, scope="module")
def float32_products():
    """float32 walks are held to a float32 forward: products at full
    precision, for THIS module only."""
    with jax.default_matmul_precision("highest"):
        yield


CFG = m.PRESETS["gigachat35-debug"]
FULL = m.PRESETS["gigachat3.5-432b-a28b-ep16"]
PAGE, SLOTS, PMAX = 16, 3, 8
S = PAGE * PMAX
TOL = 2e-5  # float32 walks against the float32 reference
TABLES = jnp.asarray(1 + np.arange(SLOTS * PMAX).reshape(SLOTS, PMAX), jnp.int32)


@pytest.fixture(scope="module")
def params():
    return m.init_params_fast(CFG, 0, jnp.float32)


def reference_logits(params, toks):
    """The plain reference's logits [T, V] on this parameter tree."""
    host = lambda tree: jax.tree.map(np.asarray, tree)  # noqa: E731
    layer = lambda l: host({k: v for k, v in params["layers"][l].items() if k not in giga._EXPERT_LEAVES})  # noqa: E731
    experts = lambda l: host(tuple(params["layers"][l][k] for k in giga._EXPERT_LEAVES))  # noqa: E731
    final = host((params["final_norm_w"], params["final_norm_g"], params["head"]))
    return giga.forward([list(toks)], TINY, np.asarray(params["embed"]), layer, experts, final, positions=len(toks))[0]


@pytest.fixture(scope="module")
def sequence(params):
    """106 tokens (the RoPE key's original context is 64: YaRN's slowed
    pairs turn) and the reference's logits at every position."""
    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, size=(106,))
    return toks, reference_logits(params, toks)


def dirty_caches():
    """Caches in which every state holds another tenant's values."""
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


def test_the_share_the_memory_plan_and_the_pool_as_the_plan_counts_it():
    assert [mx for mx, _ in FULL.layers] == ["gdn", "mla", "gdn", "gdn", "gdn"]
    assert [f for _, f in FULL.layers] == ["dense", "sparse", "sparse", "sparse", "sparse"]
    assert m.count_logical_params(FULL) == 4_731_873_280
    assert FULL.latent_row == 640 and m.kv_bytes_per_token(FULL) == 1280  # [c 512 | k_rope 64] padded to five lane tiles
    assert m.fixed_state_bytes_per_slot(FULL) == 4 * (64 * 128 * 128 * 4 + 3 * 16384 * 2) == 17_170_432
    assert FULL.experts_held == 16 and FULL.n_routed_experts == 256 and FULL.conv_dim == 16384
    assert FULL.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
    with pytest.raises(ValueError, match="experts held"):
        m.validate(dataclasses.replace(FULL, experts_first=250))
    # what CachePlan counts a token and a slot is what the cache pytree allocates
    caches = jax.eval_shape(lambda: m.init_paged_cache(FULL, 9, 128, 4, jnp.bfloat16))
    nbytes = lambda xs: sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in xs)  # noqa: E731
    shape = registry.resolve("gigachat3.5-432b-a28b-ep16")[0].paged_kv_shape(FULL)
    plan = kv_pages.cache_plan(9, 128, 4, paged_bytes_per_token=shape.bytes_per_token,
                               fixed_bytes_per_slot=m.fixed_state_bytes_per_slot(FULL))
    assert plan.paged_bytes == nbytes(caches["lat"]) == 9 * 128 * 1280
    assert plan.fixed_bytes == nbytes(caches["gdn"]) + nbytes(caches["conv"])


def test_parameter_count_matches_the_tree(params):
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) == m.count_logical_params(CFG)
    # every term is drawn away from the value that would hide it
    lp = params["layers"][1]
    for name in ("n_mix_in_w", "n_mix_out_g", "n_mlp_in_g", "n_mlp_out_w", "e_bias"):
        assert float(jnp.max(jnp.abs(lp[name]))) > 0.05, name
    assert float(jnp.max(jnp.abs(params["layers"][0]["o_norm"]))) > 0.05


def _token_by_token(S0, q, k, v, beta, g):
    def step(S, xs):
        o, S = m.gdn_step(S, *xs)
        return S, o

    S1, o = jax.lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, beta, g)))
    return jnp.moveaxis(o, 0, 1), S1


@pytest.mark.parametrize("block", [4, 64])
def test_gdn_block_wise_equals_token_by_token(block):
    """The WY / UT-transform walk against the delta rule stepped a token
    at a time: keys with a common component (as after SiLU), decays from
    nearly none to e^-40 a token (no lower bound exists), a carried state."""
    N, T, H, Dk = 2, 128, 3, 16
    k0 = jax.random.PRNGKey(1)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(k0, (N, T, H, Dk)) + 0.7) * Dk ** -0.5
    k = unit(jax.random.normal(jax.random.fold_in(k0, 1), (N, T, H, Dk)) + 0.7)
    v = jax.random.normal(jax.random.fold_in(k0, 2), (N, T, H, Dk))
    beta = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(k0, 3), (N, T, H)))
    g = -jnp.exp(jax.random.uniform(jax.random.fold_in(k0, 4), (N, T, H), minval=-6.0, maxval=3.7))
    S0 = jax.random.normal(jax.random.fold_in(k0, 5), (N, H, Dk, Dk))
    o_ref, S_ref = _token_by_token(S0, q, k, v, beta, g)
    o, S1 = m.gdn_chunk(S0, q, k, v, beta, g, block=block)
    assert rel(o, o_ref) < 1e-5 and rel(S1, S_ref) < 1e-5 and bool(jnp.all(jnp.isfinite(o)))
    # a token with beta = 0 and g = 0 (padding) leaves the state as it is
    pad = lambda x: jnp.concatenate([x, jnp.zeros_like(x)], axis=1)  # noqa: E731
    _, S2 = m.gdn_chunk(S0, pad(q), pad(k), pad(v), pad(beta), pad(g), block=block)
    assert rel(S2, S_ref) < 1e-5


def test_gdn_step_is_the_delta_rule_as_written():
    k0 = jax.random.PRNGKey(2)
    S0 = jax.random.normal(k0, (3, 8, 8))
    q, k, v = (jax.random.normal(jax.random.fold_in(k0, i), (3, 8)) for i in (1, 2, 3))
    beta, g = jnp.asarray([0.3, 0.9, 0.0]), jnp.asarray([-0.5, -3.0, 0.0])
    o, S1 = m.gdn_step(S0, q, k, v, beta, g)
    Sa = jnp.exp(g)[:, None, None] * S0
    Sb = Sa + beta[:, None, None] * k[:, :, None] * (v - jnp.einsum("hkv,hk->hv", Sa, k))[:, None, :]
    assert rel(S1, Sb) < 1e-6 and rel(o, jnp.einsum("hkv,hk->hv", Sb, q)) < 1e-6
    assert np.array_equal(np.asarray(S1[2]), np.asarray(S0[2]))


def test_the_zero_centred_gated_norm_and_the_sandwich():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    zeros = jnp.zeros((8,))
    plain = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    assert rel(m.gated_norm(x, zeros, zeros, 1e-6, 2.0), plain) < 1e-6  # 1 + 0 and 2 sigmoid(0): the identity
    w, g = jnp.full((8,), 0.5), jnp.full((8,), 1.0)
    assert rel(m.gated_norm(x, w, g, 1e-6, 2.0), plain * 1.5 * 2 / (1 + np.exp(-1.0))) < 1e-6
    lp = {"n_mix_in_w": w, "n_mix_in_g": g, "n_mix_out_w": -w, "n_mix_out_g": -g}
    F = lambda u: u * u  # noqa: E731
    want = x + m.gated_norm(F(m.gated_norm(x, w, g, 1e-6, 2.0)), -w, -g, 1e-6, 2.0)
    assert rel(m.sublayer(x, lp, "mix", CFG, F), want) < 1e-6


def test_dense_latent_kernel_equals_causal_attention_with_a_value_narrower_than_the_key():
    B, H, W, R, page, Pmax = 3, 4, 128, 32, 8, 5
    k0 = jax.random.PRNGKey(0)
    pool = jax.random.normal(k0, (1 + B * Pmax, page, W))
    tables = jnp.asarray(1 + np.arange(B * Pmax).reshape(B, Pmax), jnp.int32)
    pos = jnp.asarray([0, 13, 39], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(k0, 9), (B, H, W))
    out = latent_attention.dense_latent_attention(q, pool, tables, pos, value_dim=R, scale=0.25, interpret=True)
    rows = pool[tables].reshape(B, Pmax * page, W)
    ok = jnp.arange(Pmax * page)[None, :] <= pos[:, None]
    sc = jnp.where(ok[:, None], jnp.einsum("bhw,bsw->bhs", q, rows) * 0.25, -1e30)
    want = jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(sc, -1), rows[..., :R])
    assert out.shape == (B, H, R) and rel(out, want) < 1e-5


def test_the_absorbed_latent_read_equals_the_unabsorbed_one_at_yarn_positions_past_a_chunk(params):
    """``q_nope . (W_uk c) + q_rope . k_rope = [W_uk^T q_nope | q_rope] . [c | k_rope]`` and
    ``sum p (W_uv c) = W_uv (sum p c)``, at positions 100.. (the original context is 64)."""
    lp = params["layers"][CFG.layers_of("mla")[0]]
    T, R, dr = 24, CFG.kv_lora_rank, CFG.qk_rope_head_dim
    x = jax.random.normal(jax.random.PRNGKey(3), (T, CFG.hidden_size))
    pos = 100 + jnp.arange(T)
    q_nope, q_rope, _, row = m._mla_project(x, pos, lp, CFG)
    assert row.shape == (T, CFG.latent_row) and not np.asarray(row[:, R + dr:]).any()
    c, k_rope = row[:, :R], row[:, R:R + dr]
    causal = jnp.tril(jnp.ones((T, T), bool))[None]
    sc = (jnp.einsum("thd,shd->hts", q_nope, jnp.einsum("sr,hdr->shd", c, lp["wuk"]))
          + jnp.einsum("thd,sd->hts", q_rope, k_rope)) * CFG.softmax_scale
    plain = jnp.einsum("hts,shv->thv", jax.nn.softmax(jnp.where(causal, sc, -1e30), -1), jnp.einsum("sr,hrv->shv", c, lp["wuv"]))
    qlat = m._absorb(q_nope, q_rope, lp, CFG)
    p2 = jax.nn.softmax(jnp.where(causal, jnp.einsum("thw,sw->hts", qlat, row) * CFG.softmax_scale, -1e30), -1)
    absorbed = jnp.einsum("thr,hrv->thv", jnp.einsum("hts,sr->thr", p2, c), lp["wuv"])
    assert rel(absorbed, plain) < 1e-5
    # the rotation depends on the position, and YaRN slows all but the fastest pair here
    _, _, _, near = m._mla_project(x, jnp.arange(T), lp, CFG)
    assert rel(near[:, R:R + dr], k_rope) > 0.1 and rel(near[:, :R], c) == 0.0
    inv = np.asarray(m.yarn_inv_freq(CFG))
    plain_inv = CFG.rope_theta ** (-np.arange(0, dr, 2) / dr)
    np.testing.assert_allclose(inv, plain_inv * np.asarray([1, 1 / 8, 1 / 8, 1 / 8]), rtol=1e-6)


def test_the_shares_partial_expert_outputs_add_up_to_the_uncut_layer(params):
    """The share test: with 2 of 16 experts a chip, the routed parts of
    all eight shares (the shared expert, which every chip computes alike,
    counted once) sum to the layer that holds all 16; the router's width
    and its top 4 do not change with the share."""
    lp = params["layers"][1]
    rng = jax.random.PRNGKey(7)
    D, F, E = CFG.hidden_size, CFG.moe_intermediate_size, CFG.n_routed_experts
    x = jax.random.normal(rng, (10, D))
    whole = dataclasses.replace(CFG, experts_first=0, experts_held=E)
    w_all = {"we_gate_up": jax.random.normal(jax.random.fold_in(rng, 1), (E, D, 2 * F)) * 0.1,
             "we_down": jax.random.normal(jax.random.fold_in(rng, 2), (E, F, D)) * 0.1}
    count = jnp.ones((10,), bool)
    uncut, stats = m.moe(x, dict(lp, **w_all), whole, count, None)
    assert stats.tolist() == [40, 0, int(stats[2]), 16, int(stats[2]), 3 + 16]  # a 16-row tile an expert hit of ceil(40 / 16) + 16
    shared = m.swiglu_mlp(x, lp["ws_gate_up"], lp["ws_down"], CFG.swiglu_limit)
    total, held_pairs = shared, 0
    top_whole, _ = glm5next.route(x, lp, whole)
    for chip in range(E // CFG.experts_held):
        share = dataclasses.replace(CFG, experts_first=2 * chip, experts_held=2)
        mine = {k: v[2 * chip:2 * chip + 2] for k, v in w_all.items()}
        part, st = m.moe(x, dict(lp, **mine), share, count, None)
        total = total + (part - shared)
        held_pairs += int(st[0])
        top, _ = glm5next.route(x, lp, share)
        assert top.shape == (10, 4) and np.array_equal(top, top_whole) and lp["router"].shape[1] == 16
    assert held_pairs == 40 and rel(total, uncut) < 1e-5


# --------------------------------------------------------------------------- #
# The paged walks against the plain reference


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_prefill_then_decode_on_dirty_slots(params, sequence, kernel):
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
    assert stats["latent_tokens_read"] == 60  # every cached token up to the query's own, one latent layer
    assert stats["moe_pairs_held"] + stats["moe_pairs_absent"] == 4 * 4 and stats["moe_experts_held"] == 4 * 2
    assert_one_live_row_tiles(m.STAT_NAMES, stats, CFG, SLOTS)


@pytest.mark.parametrize("chunk,kernel", [(16, None), (32, "interpret"), (64, None)])
def test_chunked_extend_carries_state_tails_and_pages_from_chunk_to_chunk(params, sequence, chunk, kernel):
    toks, full = sequence
    logits, caches = extend(params, dirty_caches(), toks, 2, chunk, kernel, upto=100)
    assert rel(logits, full[99]) < TOL
    assert int(caches["stats"][m.STAT_NAMES.index("latent_tokens_read")]) == sum(range(100 - (100 - 1) % chunk, 101))  # the last chunk's queries, each to itself
    for p in range(100, 106):
        logits, caches = decode(params, caches, {2: (toks[p], p)}, kernel)
        assert rel(logits[2], full[p]) < TOL, p


def test_a_narrow_last_chunk_leaves_what_a_wide_one_leaves(params, sequence):
    toks, full = sequence
    _, wide = extend(params, dirty_caches(), toks, 0, 64, upto=70)
    _, caches = extend(params, dirty_caches(), toks, 0, 64, upto=64)
    row = np.zeros((1, 16), np.int32)
    row[0, :6] = toks[64:70]
    h, narrow = m.extend_paged(params, CFG, caches, jnp.asarray(row), jnp.asarray([64], jnp.int32),
                               jnp.asarray([6], jnp.int32), jnp.asarray([0], jnp.int32), TABLES, S, PAGE)
    assert rel(m.head(params, CFG, h)[0], full[69]) < TOL
    for name in ("gdn", "conv"):
        for a, b in zip(wide[name], narrow[name]):
            assert rel(b[0], a[0]) < TOL, name


def test_a_row_with_nothing_valid_and_a_dead_row_change_nothing(params):
    caches = dirty_caches()
    _, after = m.extend_paged(params, CFG, caches, jnp.zeros((1, 16), jnp.int32), jnp.asarray([16], jnp.int32),
                              jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32), TABLES, S, PAGE)
    _, after = decode(params, after, {})
    for name in ("lat", "gdn", "conv"):
        for a, b in zip(caches[name], after[name]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_a_freed_slot_shows_no_trace_of_its_last_tenant(params, sequence):
    toks, full = sequence
    other = np.random.default_rng(5).integers(0, CFG.vocab_size, size=(90,))
    _, caches = extend(params, dirty_caches(), other, 1, 32)
    logits, _ = extend(params, caches, toks, 1, 32, upto=70)
    assert rel(logits, full[69]) < TOL


def test_rows_decoding_together_equal_their_solo_runs(params, sequence):
    toks, full = sequence
    _, caches = extend(params, dirty_caches(), toks, 0, 32, upto=40)
    _, caches = extend(params, caches, toks, 2, 32, upto=70)
    for j in range(4):
        logits, caches = decode(params, caches, {0: (toks[40 + j], 40 + j), 2: (toks[70 + j], 70 + j)}, "interpret")
        assert rel(logits[0], full[40 + j]) < TOL and rel(logits[2], full[70 + j]) < TOL
    assert int(caches["stats"][m.STAT_NAMES.index("latent_tokens_read")]) == 44 + 74


def test_decode_through_the_step_kernel_equals_the_xla_step_in_logits_and_every_cache_leaf(params, sequence):
    """``delta_step`` on against off, every other path the same: two
    live rows and a dead one on dirty slots, three steps. The kernel maps
    the 2 key heads to the 4 value heads itself and changes the summation
    order over Dk, nothing else."""
    toks, _ = sequence
    _, caches = extend(params, dirty_caches(), toks, 0, 32, upto=40)
    _, caches = extend(params, caches, toks, 2, 32, upto=70)
    off = on = caches
    walk = jax.jit(lambda caches, tok, pos, live, path: m.decode_paged(
        params, CFG, caches, tok, pos, live, TABLES, S, PAGE, delta_step=path), static_argnums=4)
    live = jnp.asarray([True, False, True])
    for j in range(3):
        tok = jnp.asarray([toks[40 + j], 0, toks[70 + j]], jnp.int32)
        pos = jnp.asarray([40 + j, 0, 70 + j], jnp.int32)
        logits_off, off = walk(off, tok, pos, live, None)
        logits_on, on = walk(on, tok, pos, live, "interpret")
        assert rel(logits_on[live], logits_off[live]) < 1e-5, j
    assert int(on["stats"][-1]) == 2 and int(off["stats"][-1]) == 0 and m.STAT_NAMES[-1] == "state_kernel_rows"
    assert np.array_equal(np.asarray(on["stats"][:-1]), np.asarray(off["stats"][:-1]))
    for name in ("lat", "gdn", "conv"):
        for a, b in zip(on[name], off[name]):
            assert a.dtype == b.dtype and rel(a, b) < 1e-5, name
    for a, b in zip(on["gdn"], caches["gdn"]):
        assert a.dtype == jnp.float32 and np.array_equal(np.asarray(a[1]), np.asarray(b[1]))  # the dead row: bit-equal


def test_registry_resolves_the_family_and_what_it_declares():
    fam, cfg = registry.resolve("gigachat35-debug")
    assert fam.name == "gigachat35" and fam.fixed_state and fam.verify_paged is None and cfg is CFG
    shape = fam.paged_kv_shape(FULL)
    assert (shape.num_layers, shape.num_kv_heads, shape.head_dim, shape.num_heads, shape.bytes_per_token) == (1, 1, 640, 64, 1280)
    # every resolved kernel path is a keyword of the walks under the SAME name: one that a walk took under
    # another name would vanish in **_paths and the XLA path would serve (found on the chip, PR 35)
    resolved = fam.resolve_kernels(cfg, "compiled")
    # (the debug preset's widths do not tile the chip: its chunks read through XLA, tests/test_latent_attention_chunk.py)
    assert resolved == {"grouped_matmul": "compiled", "delta_step": "compiled", "latent_chunk": None}
    for walk in (m.prefill_paged, m.extend_paged, m.decode_paged):
        assert set(resolved) <= set(inspect.signature(walk).parameters)
    assert "page_kernel" in inspect.signature(m.decode_paged).parameters  # the engine's own, which serves the latent read
    assert fam.stat_names == m.STAT_NAMES and not fam.extend_reads_window and registry.family_of(CFG).name == "gigachat35"
    from generativeaiexamples_tpu.ops import page_attention

    assert page_attention.supports_geometry(128, shape.head_dim, shape.num_heads, shape.num_kv_heads)  # 640: whole lane tiles
    assert not page_attention.supports_geometry(128, 576, 64, 1)  # the unpadded row would be refused


# --------------------------------------------------------------------------- #
# The engine: served through the registry

BASE = dict(
    model_config_name="gigachat35-debug", max_batch_size=3, max_seq_len=256, prefill_chunk=64,
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


def test_engine_serves_every_prompt_shape_as_the_references_argmax(engine):
    """One chunk (5, 64), several (100: a wide and a
    narrow chunk; 150), more requests than slots one after another: every
    served token is the plain reference's argmax, through the interpreted
    kernels. Nothing compiles after warm-up."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    assert engine._family.name == "gigachat35" and engine._paged_kernel == "interpret"
    assert engine._family_kernels == {"grouped_matmul": "interpret", "delta_step": "interpret", "latent_chunk": None}
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, 250, size=n)] for n in (5, 64, 100, 150, 9)]
    before = engine.metrics
    outs = [list(engine.iter_ids(p, SamplingParams(temperature=0.0, max_tokens=6), timeout=600)) for p in prompts]
    for p, o in zip(prompts, outs):
        assert len(o) == 6
        ref = reference_logits(engine.params, p + o)
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
    assert grew('genai_engine_moe_pairs_total{held="true"}') > 0 and grew('genai_engine_moe_pairs_total{held="false"}') > 0
    assert grew("genai_engine_latent_read_tokens_total") >= sum(range(65, 101))  # the second chunk's queries at least
    assert grew("genai_engine_dsa_context_tokens_total") == 0  # nothing is selected here
    assert after["genai_engine_fixed_state_bytes"] == 3 * m.fixed_state_bytes_per_slot(CFG, 2)
    spans = [s for s in dispatch_timeline.recent_spans(256)
             if s.get("kind") in ("decode", "prefill_chunk") and "latent_tokens_read" in s]
    chunk = [s for s in spans if s["kind"] == "prefill_chunk"][0]  # newest first
    step = [s for s in spans if s["kind"] == "decode"][0]
    for s in (chunk, step):
        assert s["state_rows"] == 1 and s["moe_experts_held"] == 8 and s["moe_experts_hit"] >= 1
        assert s["moe_pairs_held"] >= s["moe_experts_hit"] and "dsa_tokens_selected" not in s
    # the two chunks of the prompt: 64 queries from position 0, 36 from 64, each reading up to itself
    read = [s["latent_tokens_read"] for s in spans if s["kind"] == "prefill_chunk"]
    assert sum(range(1, 65)) in read and sum(range(65, 101)) in read and 100 < step["latent_tokens_read"] <= 109
    assert step["kv_pages_walked"] >= 7
    # the step kernel engaged on every row a decode dispatch advanced, on none of a chunk's
    assert step["state_kernel_rows"] == step["state_rows"] and chunk["state_kernel_rows"] == 0
    assert grew("genai_engine_state_kernel_rows_total") >= 1
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


# the latent layer at widths that tile the chip (heads and a latent of whole lane tiles), the rest as the debug preset
LANES = dataclasses.replace(CFG, num_heads=2, kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)


def test_an_engine_at_widths_that_tile_reads_its_chunks_through_the_kernel():
    """``ops/latent_attention.py`` ``latent_chunk_read`` interpreted, in
    the engine's own extend programs, with the output gate applied after
    it: a 150-token prompt in three chunks is answered as the engine with
    every kernel off answers it; each chunk's span says the ONE latent
    layer read through the kernel and the counter grew under
    ``path="kernel"`` alone, under ``path="xla"`` with it off."""
    import time

    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine import dispatch_timeline
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams
    from generativeaiexamples_tpu.utils import metrics as metrics_mod

    def reads(path):
        key = f'genai_engine_latent_chunk_reads_total{{path="{path}"}} '
        lines = [l for l in metrics_mod.get_registry().render().splitlines() if l.startswith(key)]
        return float(lines[0].rsplit(" ", 1)[1]) if lines else 0.0

    name = "gigachat35-lanes-test"
    m.PRESETS[name] = LANES
    prompt = [int(t) for t in np.random.default_rng(3).integers(3, 250, size=150)]
    try:
        assert registry.resolve(name)[0].resolve_kernels(LANES, "interpret")["latent_chunk"] == "interpret"
        answers = {}
        for kernel in ("interpret", "off"):
            eng = LLMEngine(EngineConfig(**dict(BASE, model_config_name=name, paged_kernel=kernel)))
            try:
                assert eng._family_kernels["latent_chunk"] == (None if kernel == "off" else "interpret")
                served, other = ("kernel", "xla") if kernel == "interpret" else ("xla", "kernel")
                before, t0 = (reads(served), reads(other)), time.time()
                answers[kernel] = list(eng.iter_ids(prompt, SamplingParams(temperature=0.0, max_tokens=6), timeout=600))
                chunks = [s for s in dispatch_timeline.recent_spans(64) if s["kind"] == "prefill_chunk" and s["t_wall"] >= t0]
                assert len(chunks) == 3
                assert all(s[f"latent_chunk_{served}_layers"] == 1 and s[f"latent_chunk_{other}_layers"] == 0 for s in chunks)
                assert (reads(served), reads(other)) == (before[0] + 3, before[1])
            finally:
                eng.shutdown()
        assert answers["interpret"] == answers["off"] and len(answers["off"]) == 6
    finally:
        del m.PRESETS[name]


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
