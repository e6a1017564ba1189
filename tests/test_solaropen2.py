"""Solar-Open2 (models/solaropen2.py) at a tiny size that keeps the
period served (one softmax layer, three KDA layers, every layer routed)
and the engine serving it through the model registry: the three paged
walks against the benchmark's plain float32 reference
(``perfbench/arch/solaropen2.py``: an independent implementation;
logits, not tokens), the block-wise recurrence under an unbounded decay,
the expert shares adding up to the uncut layer, store rows behind the
slots that a step leaves alone.
"""
import dataclasses
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import glm5next, registry
from generativeaiexamples_tpu.models import solaropen2 as m
from generativeaiexamples_tpu.ops import grouped_matmul, page_attention
from perfbench.arch import solaropen2 as adapter
from tests.expert_stats import assert_one_live_row_tiles
from tests.perfbench.test_perfbench_solaropen2 import TINY


@pytest.fixture(autouse=True, scope="module")
def float32_products():
    """float32 walks are held to a float32 forward: products at full
    precision, for THIS module only."""
    with jax.default_matmul_precision("highest"):
        yield


CFG = m.PRESETS["solaropen2-debug"]
FULL = m.PRESETS["solar-open2-250b-ep8"]
PAGE, SLOTS, STORE, PMAX = 16, 3, 2, 8
S = PAGE * PMAX
TOL = 3e-5  # float32 walks against the float32 reference
TABLES = jnp.asarray(1 + np.arange(SLOTS * PMAX).reshape(SLOTS, PMAX), jnp.int32)


@pytest.fixture(scope="module")
def params():
    return m.init_params_fast(CFG, 0, jnp.float32)


def reference_logits(params, toks, cfg=TINY, **faults):
    """The plain reference's logits [T, V] on this parameter tree."""
    host = lambda tree: jax.tree.map(np.asarray, tree)  # noqa: E731
    layer = lambda l: host({k: v for k, v in params["layers"][l].items() if k not in adapter._EXPERT_LEAVES})  # noqa: E731
    experts = lambda l: host(tuple(params["layers"][l][k] for k in adapter._EXPERT_LEAVES))  # noqa: E731
    final = host((params["final_norm"], params["head"]))
    return adapter.forward([list(toks)], cfg, np.asarray(params["embed"]), layer, experts, final,
                           positions=len(toks), **faults)[0]


@pytest.fixture(scope="module")
def sequence(params):
    """100 tokens and the reference's logits at every position."""
    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, size=(100,))
    return toks, reference_logits(params, toks)


def dirty_caches():
    """Caches (slots and store rows) in which every state and page holds another tenant's values."""
    caches = m.init_paged_cache(CFG, 1 + SLOTS * PMAX, PAGE, SLOTS + STORE, jnp.float32)
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


def extend(params, caches, toks, slot, chunk, kernel=None, start=0, upto=None, check=None):
    """Chunked extend of ``toks[start:upto]`` on ``slot``; returns (logits, caches)."""
    n_all = len(toks) if upto is None else upto
    for off in range(start, n_all, chunk):
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
    """ISSUE 44's arithmetic at the published widths."""
    assert FULL.layers == ("full", "kda", "kda", "kda") and CFG.layers == FULL.layers
    assert m.SolarOpen2Config().layers.count("full") == 12 and len(m.SolarOpen2Config().layers) == 48
    shapes = lambda mixer: {k: int(np.prod(s)) for k, (s, _) in m._shapes(FULL, mixer).items()}  # noqa: E731
    moe_leaves = ("router", "e_bias", "ws_gate_up", "ws_down", "we_gate_up", "we_down", "ln_mix", "ln_mlp")
    mixer = lambda kind: sum(v for k, v in shapes(kind).items() if k not in moe_leaves)  # noqa: E731
    assert mixer("full") == 109_051_904 and mixer("kda") == 137_732_288
    assert sum(shapes("full").values()) == 755_245_376 and sum(shapes("kda").values()) == 783_925_760
    assert m.count_logical_params(FULL) == 3_308_353_344
    assert m.fixed_state_bytes_per_slot(FULL) == 13_025_280 and m.kv_bytes_per_token(FULL) == 4096
    mem = m.serving_memory_bytes(FULL, 64, 8192)
    assert mem["weights"] == 6_616_706_688 and mem["fixed_state"] == 64 * 13_025_280


def test_parameter_count_matches_the_tree(params):
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) == m.count_logical_params(CFG)


def test_validate_refuses_a_share_outside_the_experts_and_a_layer_that_is_not_there():
    with pytest.raises(ValueError, match="experts held"):
        m.validate(dataclasses.replace(CFG, experts_first=15, experts_held=2))
    with pytest.raises(ValueError, match="layers_served"):
        m.validate(dataclasses.replace(CFG, layers_served=(0, 4)))


def test_the_whole_sequence_forward_equals_the_plain_reference(params, sequence):
    toks, ref = sequence
    assert rel(m.forward_full(params, CFG, jnp.asarray(toks)[None])[0], ref) < TOL


def test_beta_reaches_past_one_and_the_decay_has_no_floor(params):
    """``beta = 2 sigmoid``: some transition has a NEGATIVE eigenvalue
    along k; a decay driven far below GLM's clamp of -5 comes through."""
    lp = params["layers"][1]
    u = jax.random.normal(jax.random.key(2), (1, 24, CFG.hidden_size)) * 3
    cat = jnp.pad(glm5next._mm(u, lp["wqkv"]), ((0, 0), (CFG.kda_conv - 1, 0), (0, 0)))
    beta = m._kda(u, cat, lp, CFG)[3]
    assert float(beta.max()) > 1.0 and float(beta.min()) > 0.0 and float(beta.max()) < 2.0
    steep = dict(lp, dt_bias=lp["dt_bias"] + 40.0)
    assert float(m._kda(u, cat, steep, CFG)[4].min()) < -30.0
    clamped = glm5next._kda_inputs(u, cat, steep, CFG.kda, lower_bound=-5.0)
    assert float(clamped[4].min()) == -5.0 and float(clamped[3].max()) < 1.0  # what the older configurations pass


@pytest.mark.parametrize("decay", [0.5, 30.0], ids=["mild", "thirty-a-token"])
def test_the_pairwise_block_form_equals_the_token_form_under_any_decay(decay):
    """At -30 a token ``glm5next.kda_chunk`` (keys divided by their
    cumulative decay: e^480 inside a block of 16) leaves float32; the
    pairwise form does not, and equals the token-by-token recurrence."""
    N, T, H, D = 2, 64, 2, 16
    ks = jax.random.split(jax.random.key(3), 6)
    q, v = jax.random.normal(ks[0], (N, T, H, D)), jax.random.normal(ks[2], (N, T, H, D))
    k = jax.random.normal(ks[1], (N, T, H, D))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[3], (N, T, H)))
    g = -decay * (0.5 + jax.random.uniform(ks[4], (N, T, H, D)))
    S0 = jax.random.normal(ks[5], (N, H, D, D))

    def step(S, xs):
        o, S = glm5next.kda_step(S, *xs)
        return S, o

    S_tok, o_tok = jax.lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, beta, g)))
    o, S1 = m.kda_chunk_pairwise(S0, q, k, v, beta, g)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S1).all())
    assert float(jnp.abs(o - jnp.moveaxis(o_tok, 0, 1)).max()) < 2e-5 and float(jnp.abs(S1 - S_tok).max()) < 2e-5
    o_old, _ = glm5next.kda_chunk(S0, q, k, v, beta, g)
    assert bool(jnp.isfinite(o_old).all()) == (decay < 5.0)


def test_a_padding_token_leaves_the_state_as_it_is():
    N, T, H, D = 1, 32, 2, 16
    ks = jax.random.split(jax.random.key(4), 5)
    q, k, v = (jax.random.normal(ks[i], (N, T, H, D)) for i in range(3))
    S0 = jax.random.normal(ks[3], (N, H, D, D))
    _, S1 = m.kda_chunk_pairwise(S0, q, k, v, jnp.zeros((N, T, H)), jnp.zeros((N, T, H, D)))
    assert float(jnp.abs(S1 - S0).max()) == 0.0


def test_the_shares_partial_expert_outputs_add_up_to_the_uncut_layer(params):
    """Eight chips of 2 experts each, the shared expert counted once,
    make the layer that holds all 16."""
    lp = params["layers"][1]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((12, CFG.hidden_size)), jnp.float32)
    count = jnp.ones((12,), bool)
    whole_cfg = dataclasses.replace(CFG, experts_first=0, experts_held=16)
    full_w = {k: jnp.asarray(rng.standard_normal((16,) + lp[k].shape[1:]), jnp.float32) * 0.1
              for k in ("we_gate_up", "we_down")}
    whole, stats = glm5next.moe(x, dict(lp, **full_w), whole_cfg, count, None)
    assert int(stats[1]) == 0 and int(stats[0]) == 12 * CFG.num_experts_per_tok
    shared = glm5next.swiglu_mlp(x, lp["ws_gate_up"], lp["ws_down"], CFG.swiglu_limit)
    total, pairs = shared, 0
    for chip in range(8):
        cfg = dataclasses.replace(CFG, experts_first=2 * chip, experts_held=2)
        part, st = glm5next.moe(x, dict(lp, **{k: w[2 * chip:2 * chip + 2] for k, w in full_w.items()}), cfg, count, None)
        total, pairs = total + (part - shared), pairs + int(st[0])
    assert pairs == 12 * CFG.num_experts_per_tok and rel(total, whole) < TOL


@pytest.mark.parametrize("width,gate_up,down", [(1280, 256, 640), (1024, 512, 1024), (2048, 512, 1024),
                                                 (4096, 512, 1024), (7168, 512, 1024)])
def test_col_block_is_the_largest_lane_multiple_that_divides_the_width(width, gate_up, down):
    """1280 is ten lane tiles: 512 does not divide it and the block must
    not become the whole width. The accepted cells' widths (expert widths
    2048, 1024; hidden 4096, 7168, 2048 on the way down) keep 512 / 1024."""
    assert grouped_matmul._col_block(width, 512) == gate_up and grouped_matmul._col_block(width, 1024) == down
    assert grouped_matmul._col_block(96, 512) == 96  # the CPU tests' widths: no lane multiple divides them


# --------------------------------------------------------------------------- #
# The paged walks


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_prefill_then_decode_on_dirty_slots(params, sequence, kernel):
    toks, ref = sequence
    tol = TOL if kernel is None else 2e-2
    caches = dirty_caches()
    row = np.zeros((1, 64), np.int32)
    row[0, :40] = toks[:40]
    logits, caches = jax.jit(lambda p, c, r: m.prefill_paged(
        p, CFG, c, r, jnp.asarray([40], jnp.int32), jnp.asarray([1], jnp.int32), TABLES, PAGE,
        grouped_matmul=kernel))(params, caches, jnp.asarray(row))
    assert rel(logits[0], ref[39]) < tol
    for t in range(40, 48):
        logits, caches = decode(params, caches, {1: (toks[t], t)}, kernel)
        assert rel(logits[1], ref[t]) < tol, t
    stats = dict(zip(m.STAT_NAMES, np.asarray(caches["stats"]).tolist()))
    assert stats["moe_pairs_held"] + stats["moe_pairs_absent"] == 4 * CFG.num_experts_per_tok  # one live row, four layers
    assert stats["moe_experts_held"] == 4 * CFG.experts_held and stats["full_tokens_read"] == 48
    assert_one_live_row_tiles(m.STAT_NAMES, stats, CFG, SLOTS)
    assert stats["state_kernel_rows"] == (1 if kernel else 0)


@pytest.mark.parametrize("chunk,kernel", [(16, None), (32, None), (32, "interpret")])
def test_chunked_extend_agrees_at_every_chunk_end(params, sequence, chunk, kernel):
    toks, ref = sequence
    tol = TOL if kernel is None else 2e-2
    seen = []
    extend(params, dirty_caches(), toks, 2, chunk, kernel, check=lambda t, lg: seen.append(rel(lg, ref[t])))
    assert len(seen) == -(-len(toks) // chunk) and max(seen) < tol, seen


def test_a_row_with_nothing_valid_and_a_dead_row_change_nothing(params, sequence):
    toks, _ = sequence
    _, caches = extend(params, dirty_caches(), toks, 0, 32, upto=64)
    keep = lambda c: [np.asarray(x) for k in ("kda", "conv", "full") for x in jax.tree.leaves(c[k])]  # noqa: E731
    before = keep(caches)
    _, after = _walks(None)[0](params, caches, jnp.zeros((1, 32), jnp.int32), jnp.asarray([64], jnp.int32),
                               jnp.asarray([0], jnp.int32), jnp.asarray([0], jnp.int32))
    _, after = decode(params, after, {})
    for a, b in zip(before, keep(after)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_a_step_leaves_the_store_rows_behind_the_slots_alone(params, sequence, kernel):
    """The fixed-state leaves hold SLOTS + STORE rows; a decode step over
    SLOTS rows reads and writes the first SLOTS only."""
    toks, ref = sequence
    _, caches = extend(params, dirty_caches(), toks, 1, 32, kernel, upto=64)
    store = [np.asarray(x[SLOTS:]) for k in m.STATE_ROW_KEYS for x in caches[k]]
    logits, after = decode(params, caches, {1: (toks[64], 64)}, kernel)
    assert rel(logits[1], ref[64]) < (TOL if kernel is None else 2e-2)
    for a, b in zip(store, [np.asarray(x[SLOTS:]) for k in m.STATE_ROW_KEYS for x in after[k]]):
        np.testing.assert_array_equal(a, b)
    assert all(x.shape[0] == SLOTS + STORE for k in m.STATE_ROW_KEYS for x in after[k])


def test_a_state_copied_out_and_back_continues_bit_for_bit(params, sequence):
    """What a prefix hit does, at the walks: the state after 64 tokens
    copied to a store row, the slot given to another tenant, the row
    copied into ANOTHER slot whose table maps the same pages: the next
    chunk's logits are the uninterrupted walk's, bit for bit."""
    toks, ref = sequence
    cold, _ = extend(params, dirty_caches(), toks, 0, 32)
    _, caches = extend(params, dirty_caches(), toks, 0, 32, upto=64)
    copy = lambda c, src, dst: dict(c, **{k: [x.at[dst].set(x[src]) for x in c[k]] for k in m.STATE_ROW_KEYS})  # noqa: E731
    caches = copy(caches, 0, SLOTS + 1)
    _, caches = extend(params, caches, toks[::-1].copy(), 0, 32, upto=32)  # another tenant in the slot (and its pages)
    _, caches = extend(params, caches, toks, 0, 32, upto=64)  # ... whose pages the entry would have kept: rewritten here
    caches = copy(copy(caches, 0, 2), SLOTS + 1, 0)  # a dirty slot 0 again, then the saved row into it
    warm, _ = extend(params, caches, toks, 0, 32, start=64)
    np.testing.assert_array_equal(np.asarray(warm), np.asarray(cold))
    assert rel(warm, ref[-1]) < TOL


def test_rows_decoding_together_equal_their_solo_runs(params, sequence):
    toks, ref = sequence
    caches = dirty_caches()
    for slot, n in ((0, 32), (2, 64)):
        _, caches = extend(params, caches, toks, slot, 32, upto=n)
    logits, _ = decode(params, caches, {0: (toks[32], 32), 2: (toks[64], 64)})
    assert rel(logits[0], ref[32]) < TOL and rel(logits[2], ref[64]) < TOL


def test_registry_resolves_the_family_and_what_it_declares():
    fam, cfg = registry.resolve("solaropen2-debug")
    assert fam.name == "solaropen2" and fam.fixed_state and fam.verify_paged is None and cfg is CFG
    assert registry.resolve("solar-open2-250b-ep8")[1] is FULL and registry.family_of(FULL).name == "solaropen2"
    shape = fam.paged_kv_shape(FULL)
    assert (shape.num_layers, shape.num_kv_heads, shape.head_dim, shape.num_heads, shape.bytes_per_token) == (1, 8, 128, 64, None)
    assert fam.fixed_state_bytes_per_slot(FULL) == 13_025_280
    assert fam.span_fields(FULL) == {"kv_readers": 1}  # each one a span carries (engine _state_counters)
    # the ONE field that lets the prefix store carry this family's state; the four older fixed-state families name none
    assert fam.state_row_keys == ("kda", "conv") == m.STATE_ROW_KEYS
    assert all(not f.state_row_keys for n, f in registry.families().items() if n != "solaropen2")
    caches = fam.init_paged_cache(cfg, 9, 4, 5, jnp.bfloat16)
    assert all(x.shape[0] == 5 for k in fam.state_row_keys for x in jax.tree.leaves(caches[k]))
    resolved = fam.resolve_kernels(cfg, "compiled")
    assert resolved == {"grouped_matmul": "compiled", "delta_step": "compiled"}
    for walk in (m.prefill_paged, m.extend_paged, m.decode_paged):
        assert set(resolved) - {"delta_step"} <= set(inspect.signature(walk).parameters)
    assert {"page_kernel", "delta_step"} <= set(inspect.signature(m.decode_paged).parameters)
    assert fam.stat_names == m.STAT_NAMES and not fam.extend_reads_window and fam.extend_packed is None
    assert page_attention.supports_geometry(128, shape.head_dim, shape.num_heads, shape.num_kv_heads)
    with pytest.raises(ValueError, match="bfloat16"):
        fam.init_paged_cache(cfg, 9, 4, 2, jnp.bfloat16, quantized=True)


# --------------------------------------------------------------------------- #
# The engine


BASE = dict(
    model_config_name="solaropen2-debug", max_batch_size=3, max_seq_len=256, prefill_chunk=64,
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
    """One chunk (5, 64), several (100, 150), more
    requests than slots one after another: every served token is near
    the plain reference's best, through the interpreted kernels. Nothing
    compiles after warm-up."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    assert engine._family.name == "solaropen2" and engine._paged_kernel == "interpret"
    assert engine._family_kernels == {"grouped_matmul": "interpret", "delta_step": "interpret"}
    assert engine._state_store_rows == 0 and engine._copy_state_fn is None  # the store is off
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
    assert grew('genai_engine_moe_pairs_total{held="true"}') > 0 and grew('genai_engine_moe_pairs_total{held="false"}') > 0
    assert grew("genai_engine_full_read_tokens_total") >= sum(range(1, 101))
    assert grew("genai_engine_state_kernel_rows_total") > 0
    assert after["genai_engine_fixed_state_bytes"] == 3 * m.fixed_state_bytes_per_slot(CFG, 2)
    spans = [s for s in dispatch_timeline.spans_since(cursor)[0]
             if s.get("kind") in ("decode", "prefill_chunk") and "full_tokens_read" in s]
    chunk = [s for s in spans if s["kind"] == "prefill_chunk"]
    step = [s for s in spans if s["kind"] == "decode"][-1]
    assert [s["full_tokens_read"] for s in chunk] == [sum(range(1, 65)), sum(range(65, 101))]
    for s in chunk + [step]:
        assert s["state_rows"] == 1 and s["kv_readers"] == 1
        assert s["moe_experts_held"] == 4 * CFG.experts_held and s["moe_pairs_held"] >= s["moe_experts_hit"]
    assert step["state_kernel_rows"] == 1 and chunk[0]["state_kernel_rows"] == 0
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


REFUSED = {
    "tensor_parallel": (dict(tensor_parallelism=2), "sharded mesh"),
    "spec_decode": (dict(spec_decode_enable="on"), "speculative verify"),
    "int8_weights": (dict(quantization="int8"), "quantization='int8'"),
    "int8_kv": (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_engine_build_refuses_what_the_fixed_state_cannot_carry(feature):
    """Speculation, sharding and quantisation stay refused for the
    family; the prefix store alone is carried (tests/test_prefix_state.py)."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    overrides, message = REFUSED[feature]
    with pytest.raises(ValueError, match=message):
        LLMEngine(EngineConfig(**dict(BASE, **overrides)))
