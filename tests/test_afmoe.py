"""Trinity-Mini (models/afmoe.py) at a tiny size that keeps the three
kinds of layer served — sliding + dense MLP, sliding + experts, full +
experts — and the engine serving it through the model registry: the
three paged walks against the benchmark's plain float32 reference
(``perfbench/arch/afmoe.py``: an independent implementation; logits, not
tokens) across a ring wrap, slot reuse, the expert shares adding up to
the uncut layer, the grouped matmul at 128 experts.
"""
import dataclasses
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import afmoe as m
from generativeaiexamples_tpu.models import glm5next, registry
from generativeaiexamples_tpu.ops import grouped_matmul, page_attention
from perfbench.arch import afmoe as adapter
from tests.expert_stats import assert_one_live_row_tiles
from tests.perfbench.test_perfbench_afmoe import TINY


@pytest.fixture(autouse=True, scope="module")
def float32_products():
    """float32 walks are held to a float32 forward: products at full
    precision, for THIS module only."""
    with jax.default_matmul_precision("highest"):
        yield


CFG = m.PRESETS["afmoe-debug"]
FULL = m.PRESETS["trinity-mini"]
PAGE, SLOTS, PMAX = 4, 3, 16
S = PAGE * PMAX
W = CFG.sliding_window
TOL = 2e-5  # float32 walks against the float32 reference
TABLES = jnp.asarray(1 + np.arange(SLOTS * PMAX).reshape(SLOTS, PMAX), jnp.int32)


@pytest.fixture(scope="module")
def params():
    return m.init_params_fast(CFG, 0, jnp.float32)


def reference_logits(params, toks, cfg=TINY):
    """The plain reference's logits [T, V] on this parameter tree."""
    host = lambda tree: jax.tree.map(np.asarray, tree)  # noqa: E731
    layer = lambda l: host({k: v for k, v in params["layers"][l].items() if k not in adapter._EXPERT_LEAVES})  # noqa: E731
    experts = lambda l: host(tuple(params["layers"][l][k] for k in adapter._EXPERT_LEAVES))  # noqa: E731
    final = host((params["final_norm"], params["head"]))
    return adapter.forward([list(toks)], cfg, np.asarray(params["embed"]), layer, experts, final, positions=len(toks))[0]


@pytest.fixture(scope="module")
def sequence(params):
    """45 tokens (more than five windows of 8) and the reference's logits at every position."""
    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, size=(45,))
    return toks, reference_logits(params, toks)


def dirty_caches():
    """Caches in which every ring and page holds another tenant's values."""
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


def extend(params, caches, toks, slot, chunk, kernel=None, upto=None, check=None):
    """Chunked extend of ``toks`` on ``slot``; returns (logits, caches).
    ``check(off + n - 1, logits)`` sees every chunk's last position."""
    n_all = len(toks) if upto is None else upto
    for off in range(0, n_all, chunk):
        n = min(chunk, n_all - off)
        row = np.zeros((1, chunk), np.int32)
        row[0, :n] = toks[off:off + n]
        h, caches = _walks(kernel)[0](params, caches, jnp.asarray(row), jnp.asarray([off], jnp.int32),
                                      jnp.asarray([n], jnp.int32), jnp.asarray([slot], jnp.int32))
        if check is not None:
            check(off + n - 1, m.head(params, CFG, h)[0])
    return m.head(params, CFG, h)[0], caches


def decode(params, caches, rows, kernel=None):
    tok, pos, live = [0] * SLOTS, [0] * SLOTS, [False] * SLOTS
    for s, (t, p) in rows.items():
        tok[s], pos[s], live[s] = int(t), int(p), True
    return _walks(kernel)[1](params, caches, jnp.asarray(tok, jnp.int32), jnp.asarray(pos, jnp.int32), jnp.asarray(live))


# --------------------------------------------------------------------------- #
# The plan and each mechanism against its plain form


def test_the_layers_served_the_memory_plan_and_the_parameter_count():
    assert FULL.layers == (("window", "dense"),) + (("window", "sparse"),) * 3 + (("full", "sparse"),)
    assert CFG.layers == (("window", "dense"), ("window", "sparse"), ("full", "sparse"))
    assert dataclasses.replace(FULL, layers_served=None).num_layers == 32
    assert m.count_logical_params(FULL) == 4_241_534_720  # ISSUE 42's own count
    assert m.fixed_state_bytes_per_slot(FULL) == 4 * 2048 * 2048 == 16_777_216
    assert m.kv_bytes_per_token(FULL) == 2048
    plan = m.serving_memory_bytes(FULL, 64, 8192)
    assert plan["weights"] == 8_483_069_440 and plan["fixed_state"] == 64 * 16_777_216
    assert plan["kv_cache"] - plan["fixed_state"] == 64 * 8192 * 2048
    caches = jax.eval_shape(lambda: m.init_paged_cache(FULL, 4097, 128, 64))
    assert caches["full"][0]["k"].shape == (4097, 4, 128, 128) and len(caches["full"]) == 1
    assert caches["win"][0]["v"].shape == (64, 4, 2048, 128) and len(caches["win"]) == 4
    ring_bytes = sum(x.size * 2 for ring in caches["win"] for x in ring.values())
    assert ring_bytes == 64 * m.fixed_state_bytes_per_slot(FULL)


def test_parameter_count_matches_the_tree(params):
    assert sum(x.size for x in jax.tree.leaves(params)) == m.count_logical_params(CFG)
    assert params["layers"][1]["e_bias"].dtype == jnp.float32 and params["layers"][1]["router"].dtype == jnp.float32


def test_validate_refuses_a_share_outside_the_experts_and_an_unknown_layer_type():
    with pytest.raises(ValueError, match="experts held"):
        m.validate(dataclasses.replace(CFG, experts_first=6, experts_held=4))
    with pytest.raises(ValueError, match="unknown layer type"):
        m.validate(dataclasses.replace(CFG, layer_types=("sliding_attention", "linear")))
    with pytest.raises(ValueError, match="layers_served"):
        m.validate(dataclasses.replace(CFG, layers_served=(0, 3)))


def test_rope_rotates_halves_and_scores_depend_on_the_distance_alone():
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.normal(size=(1, 1, 16)), jnp.float32) for _ in range(2))
    half = 8
    inv = 10000.0 ** (-np.arange(half) / half)
    got = np.asarray(m.rope(q[None], jnp.asarray([[5]]), 10000.0))[0, 0, 0]
    z = (np.asarray(q)[0, 0, :half] + 1j * np.asarray(q)[0, 0, half:]) * np.exp(1j * 5 * inv)
    np.testing.assert_allclose(got, np.concatenate([z.real, z.imag]), rtol=1e-5, atol=1e-6)
    score = lambda a, b: float(jnp.sum(m.rope(q[None], jnp.asarray([[a]]), 1e4) * m.rope(k[None], jnp.asarray([[b]]), 1e4)))  # noqa: E731
    assert score(9, 4) == pytest.approx(score(105, 100), rel=1e-4) and score(9, 4) != pytest.approx(score(9, 5), rel=1e-3)


def test_the_whole_sequence_forward_equals_the_plain_reference(params, sequence):
    toks, ref = sequence
    full = m.forward_full(params, CFG, jnp.asarray(toks[None]))[0]
    assert rel(full, ref) < TOL


def test_a_key_one_past_the_window_is_not_seen_and_the_full_layer_sees_every_key(params):
    """Changing token 0 moves a window layer's output at position W - 1
    (its 8th key back) and not at position W; the full layer's at both."""
    only = lambda mixer: dataclasses.replace(  # noqa: E731
        CFG, layer_types=(mixer,), num_dense_layers=1, layers_served=(0,))
    a = np.random.default_rng(5).integers(0, 256, size=(1, 12))
    b = a.copy()
    b[0, 0] = (a[0, 0] + 1) % 256
    p1 = dict(params, layers=params["layers"][:1])
    for mixer, moved_past in (("sliding_attention", False), ("full_attention", True)):
        fa, fb = (np.asarray(m.forward_full(p1, only(mixer), jnp.asarray(t)))[0] for t in (a, b))
        assert np.abs(fa[W - 1] - fb[W - 1]).max() > 1e-4
        assert (np.abs(fa[W] - fb[W]).max() > 1e-4) == moved_past


def test_the_shares_partial_expert_outputs_add_up_to_the_uncut_layer(params):
    """Guide section 4: two shares of 4 of 8 experts, the shared expert
    counted once, add up to what the uncut reference gives for the layer;
    ``experts_held`` = all equals it outright."""
    lp = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(11, CFG.hidden_size)), jnp.float32)
    count = jnp.ones((11,), bool)
    w = jax.tree.map(np.asarray, {k: v for k, v in lp.items() if k not in adapter._EXPERT_LEAVES})
    whole = np.asarray(adapter.moe(x, w, adapter.expert_keys(TINY),
                                   lambda e: (np.asarray(lp["we_gate_up"][e]), np.asarray(lp["we_down"][e]))))
    shared = np.asarray(glm5next.swiglu_mlp(x, lp["ws_gate_up"], lp["ws_down"], CFG.swiglu_limit))
    parts, held = [], 0
    for first in (0, 4):
        cfg = dataclasses.replace(CFG, experts_first=first, experts_held=4)
        share = dict(lp, we_gate_up=lp["we_gate_up"][first:first + 4], we_down=lp["we_down"][first:first + 4])
        y, stats = glm5next.moe(x, share, cfg, count, None)
        parts.append(np.asarray(y) - shared)
        held += int(stats[0])
        assert int(stats[0]) + int(stats[1]) == 11 * CFG.num_experts_per_tok and int(stats[3]) == 4
    assert held == 11 * CFG.num_experts_per_tok  # every pair is computed on exactly one share
    assert rel(parts[0] + parts[1] + shared, whole) < TOL
    y, stats = glm5next.moe(x, lp, CFG, count, None)
    assert rel(y, whole) < TOL and int(stats[1]) == 0 and int(stats[3]) == 8
    assert float(np.max(np.abs(parts[0]))) > 0.01 and float(np.max(np.abs(parts[1]))) > 0.01


def test_the_experts_multiply_the_rounded_row_and_the_router_scores_the_row_as_it_comes(params):
    """``expert_dtype``: the routing of a float32 row stays float32's."""
    lp = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(4).normal(size=(7, CFG.hidden_size)), jnp.float32)
    count = jnp.ones((7,), bool)
    y32, _ = glm5next.moe(x, lp, CFG, count, None)
    y16, _ = glm5next.moe(x, lp, CFG, count, None, expert_dtype=jnp.bfloat16)
    assert 1e-4 < rel(y16, y32) < 2e-2  # rounded products, the same experts and gates


@pytest.mark.parametrize("tokens,tm", [(64, 16), (512, 64)])
def test_grouped_mlp_at_128_experts_equals_its_dense_fallback_and_a_cold_expert_costs_no_tile(tokens, tm):
    """The plan of a decode step (64 x 8 pairs: 160 tiles of 16) and of a
    chunk (512 x 8: 192 tiles of 64) over 128 experts, interpreted."""
    rng = np.random.default_rng(6)
    E, D, F, k = 128, 32, 16, 8
    x = jnp.asarray(rng.normal(size=(tokens, D)), jnp.float32)
    w_gu = jnp.asarray(rng.normal(size=(E, D, 2 * F)) / np.sqrt(D), jnp.float32)
    w_d = jnp.asarray(rng.normal(size=(E, F, D)) / np.sqrt(F), jnp.float32)
    # experts 100..127 are never routed to
    top = jnp.asarray(np.stack([rng.choice(100, size=k, replace=False) for _ in range(tokens)]), jnp.int32)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, size=(tokens, k)), jnp.float32)
    assert grouped_matmul.row_tile(tokens * k) == tm
    plan = grouped_matmul.plan(top, E, tm)
    sizes = np.asarray(plan.sizes)
    assert plan.tile_expert.shape[0] == -(-tokens * k // tm) + E and sizes[100:].sum() == 0
    used = int(plan.tiles_used[0])
    assert used == int(np.sum(-(-sizes // tm)))  # whole tiles of the experts HIT, none for a cold one
    assert set(np.asarray(plan.tile_expert)[:used]) == set(np.nonzero(sizes)[0])
    dense, s0 = grouped_matmul.grouped_mlp(x, top, gates, w_gu, w_d, limit=np.inf, kernel=None)
    kern, s1 = grouped_matmul.grouped_mlp(x, top, gates, w_gu, w_d, limit=np.inf, kernel="interpret")
    assert rel(kern, dense) < TOL and np.array_equal(np.asarray(s0), np.asarray(s1))


# --------------------------------------------------------------------------- #
# The three walks against the plain reference


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_prefill_then_decode_on_dirty_slots(params, sequence, kernel):
    """A monolithic prefill of 29 tokens (three windows: the ring keeps
    the last 8) on a slot full of another tenant's values, then decode
    steps over the wrapped ring, the page kernel and the grouped matmul."""
    toks, ref = sequence
    T = 29
    row = np.zeros((1, 32), np.int32)
    row[0, :T] = toks[:T]
    logits, caches = jax.jit(lambda p, c, r: m.prefill_paged(
        p, CFG, c, r, jnp.asarray([T]), jnp.asarray([1]), TABLES, PAGE, grouped_matmul=kernel))(
            params, dirty_caches(), jnp.asarray(row))
    tol = TOL if kernel is None else 5e-3  # the page kernel multiplies bfloat16 probabilities
    assert rel(logits[0], ref[T - 1]) < tol
    for t in range(T, T + 10):
        logits, caches = decode(params, caches, {1: (toks[t], t)}, kernel)
        assert rel(logits[1], ref[t]) < tol, t
    stats = dict(zip(m.STAT_NAMES, np.asarray(caches["stats"]).tolist()))
    assert stats["window_tokens_read"] == 2 * W and stats["full_tokens_read"] == T + 10
    assert stats["moe_pairs_held"] == 2 * CFG.num_experts_per_tok and stats["moe_pairs_absent"] == 0
    assert stats["moe_experts_held"] == 16 and 2 <= stats["moe_experts_hit"] <= 4
    assert_one_live_row_tiles(m.STAT_NAMES, stats, CFG, SLOTS)


@pytest.mark.parametrize("chunk,kernel", [(4, None), (8, "interpret"), (16, None), (32, None)])
def test_chunked_extend_across_ring_wraps_agrees_at_every_chunk_end(params, sequence, chunk, kernel):
    """Chunks narrower than the window (4 of 8), as wide, and wider (the
    ring keeps a chunk's last 8): the ring wraps inside the prompt, and a
    chunk reads it as it stood beside its own keys."""
    toks, ref = sequence
    seen = []
    logits, caches = extend(params, dirty_caches(), toks, 2, chunk, kernel,
                            check=lambda t, lg: seen.append(rel(lg, ref[t])))
    assert len(seen) == -(-len(toks) // chunk) and max(seen) < TOL, seen
    logits, caches = decode(params, caches, {2: (toks[-1], len(toks) - 1)}, kernel)  # the last token again, as a step
    del caches
    assert rel(logits[2], ref[-1]) < (TOL if kernel is None else 5e-3)


def test_the_chunk_stats_count_the_keys_each_query_saw(params, sequence):
    toks, _ = sequence
    _, caches = extend(params, dirty_caches(), toks, 0, 16, upto=32)
    stats = dict(zip(m.STAT_NAMES, np.asarray(caches["stats"]).tolist()))
    # the second chunk: 16 queries at positions 16..31, all past the window
    assert stats["window_tokens_read"] == 2 * 16 * W and stats["full_tokens_read"] == sum(range(17, 33))
    assert stats["moe_pairs_held"] == 2 * 16 * CFG.num_experts_per_tok


def test_a_row_with_nothing_valid_and_a_dead_row_change_nothing(params):
    caches = dirty_caches()
    row = jnp.zeros((1, 8), jnp.int32)
    _, after = _walks(None)[0](params, caches, row, jnp.asarray([5]), jnp.asarray([0]), jnp.asarray([1]))
    _, stepped = decode(params, caches, {})
    for new in (after, stepped):
        for a, b in zip(jax.tree.leaves({k: caches[k] for k in ("full", "win")}),
                        jax.tree.leaves({k: new[k] for k in ("full", "win")})):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_freed_slot_shows_no_trace_of_its_last_tenant(params, sequence):
    """No reset at admission: a row below the window masks what it has not written."""
    toks, ref = sequence
    _, caches = extend(params, dirty_caches(), toks[::-1].copy(), 1, 16)  # a tenant that wrapped the ring
    logits, caches = extend(params, caches, toks, 1, 4, upto=6)  # a newcomer still below the window
    assert rel(logits, ref[5]) < TOL
    logits, _ = decode(params, caches, {1: (toks[6], 6)})
    assert rel(logits[1], ref[6]) < TOL


def test_rows_decoding_together_equal_their_solo_runs(params, sequence):
    toks, ref = sequence
    caches = dirty_caches()
    _, caches = extend(params, caches, toks, 0, 16, upto=20)
    _, caches = extend(params, caches, toks, 2, 8, upto=5)
    logits, _ = decode(params, caches, {0: (toks[20], 20), 2: (toks[5], 5)})
    assert rel(logits[0], ref[20]) < TOL and rel(logits[2], ref[5]) < TOL


# --------------------------------------------------------------------------- #
# The registry and the engine


def test_registry_resolves_the_family_and_what_it_declares():
    fam, cfg = registry.resolve("afmoe-debug")
    assert fam.name == "afmoe" and fam.fixed_state and fam.verify_paged is None and cfg is CFG
    assert registry.resolve("trinity-mini")[1] is FULL and registry.family_of(FULL).name == "afmoe"
    shape = fam.paged_kv_shape(FULL)
    assert (shape.num_layers, shape.num_kv_heads, shape.head_dim, shape.num_heads, shape.bytes_per_token) == (1, 4, 128, 32, None)
    assert fam.fixed_state_bytes_per_slot(FULL) == 16_777_216
    assert fam.span_fields(FULL) == {"kv_readers": 1}
    # every resolved kernel path is a keyword of the walks under the SAME name
    resolved = fam.resolve_kernels(cfg, "compiled")
    assert resolved == {"grouped_matmul": "compiled"}
    for walk in (m.prefill_paged, m.extend_paged, m.decode_paged):
        assert set(resolved) <= set(inspect.signature(walk).parameters)
    assert "page_kernel" in inspect.signature(m.decode_paged).parameters
    assert fam.stat_names == m.STAT_NAMES and not fam.extend_reads_window and fam.extend_packed is None
    assert page_attention.supports_geometry(128, shape.head_dim, shape.num_heads, shape.num_kv_heads)
    with pytest.raises(ValueError, match="bfloat16"):
        fam.init_paged_cache(cfg, 9, 4, 2, jnp.bfloat16, quantized=True)


BASE = dict(
    model_config_name="afmoe-debug", max_batch_size=3, max_seq_len=256, prefill_chunk=64,
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
    """One chunk (5, 64), several (100, 150: every chunk
    wider than the window), more requests than slots one after another:
    every served token is the plain reference's argmax, through the
    interpreted kernels. Nothing compiles after warm-up."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    assert engine._family.name == "afmoe" and engine._paged_kernel == "interpret"
    assert engine._family_kernels == {"grouped_matmul": "interpret"}
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, 250, size=n)] for n in (5, 64, 100, 150, 9)]
    before = engine.metrics
    outs = [list(engine.iter_ids(p, SamplingParams(temperature=0.0, max_tokens=6), timeout=600)) for p in prompts]
    for p, o in zip(prompts, outs):
        assert len(o) == 6
        ref = reference_logits(engine.params, p + o)
        assert max(float(ref[len(p) - 1 + j].max() - ref[len(p) - 1 + j][t]) for j, t in enumerate(o)) < 2e-2
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
    cursor = dispatch_timeline.cursor()
    list(engine.iter_ids(list(range(3, 103)), SamplingParams(temperature=0.0, max_tokens=9), timeout=600))
    after = read()
    grew = lambda k: after.get(k, 0.0) - before.get(k, 0.0)  # noqa: E731
    assert grew("genai_engine_state_slot_resets_total") == 1
    assert grew('genai_engine_moe_pairs_total{held="true"}') > 0 and grew('genai_engine_moe_pairs_total{held="false"}') == 0
    # the two chunks of the prompt: 64 queries from position 0, 36 from 64
    chunks = [sum(range(1, 65)), sum(range(65, 101))]
    ring = [2 * sum(min(p + 1, W) for p in range(64)), 2 * 36 * W]
    assert grew("genai_engine_full_read_tokens_total") >= sum(chunks)
    assert grew("genai_engine_window_read_tokens_total") >= sum(ring)
    assert after["genai_engine_fixed_state_bytes"] == 3 * m.fixed_state_bytes_per_slot(CFG, 2)
    spans = [s for s in dispatch_timeline.spans_since(cursor)[0]
             if s.get("kind") in ("decode", "prefill_chunk") and "full_tokens_read" in s]
    chunk = [s for s in spans if s["kind"] == "prefill_chunk"]
    step = [s for s in spans if s["kind"] == "decode"][-1]
    assert [s["full_tokens_read"] for s in chunk] == chunks and [s["window_tokens_read"] for s in chunk] == ring
    for s in chunk + [step]:
        assert s["state_rows"] == 1 and s["kv_readers"] == 1 and s["moe_experts_held"] == 16 and s["moe_pairs_absent"] == 0
        assert s["moe_pairs_held"] >= s["moe_experts_hit"] >= 2
    assert step["window_tokens_read"] == 2 * W and 100 < step["full_tokens_read"] <= 109
    assert step["kv_pages_walked"] >= 7
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


REFUSED = {
    "tensor_parallel": (dict(tensor_parallelism=2), "sharded mesh"),
    "prefix_cache": (dict(prefix_cache_enable="auto", prefix_cache_slots=2), "prefix-cache reuse"),
    "spec_decode": (dict(spec_decode_enable="on"), "speculative verify"),
    "int8_weights": (dict(quantization="int8"), "quantization='int8'"),
    "int8_kv": (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_engine_build_refuses_what_the_ring_store_cannot_carry(feature):
    """A limit of the ring store, not of the model (docs/model_registry.md)."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    overrides, message = REFUSED[feature]
    with pytest.raises(ValueError, match=message):
        LLMEngine(EngineConfig(**dict(BASE, **overrides)))
