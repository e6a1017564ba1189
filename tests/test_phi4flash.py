"""Phi-4-mini-flash-reasoning (models/phi4flash.py) at a tiny size that
keeps the layer rule — Mamba 0,2,4; window 1,3; full 5; GMU 6; cross 7;
window 8 — and the engine serving it through the model registry: the
three paged walks against the model's own whole-sequence forward
(logits, not tokens), window wrap, chunked extend, slot reuse, rows
admitted at different steps, the prefill shortcut, and every engine
feature a fixed-state model refuses. The plain float32 REFERENCE (an
independent implementation) is held against the same model in
tests/perfbench/test_perfbench_phi4flash.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import phi4flash as m
from generativeaiexamples_tpu.models import registry

CFG = m.PRESETS["phi4flash-debug"]
PAGE, SLOTS, PMAX = 8, 4, 16
TOL = 2e-5  # float32 walks against the float32 whole-sequence forward


@pytest.fixture(scope="module")
def params():
    return m.init_params_fast(CFG, 0, jnp.float32)


@pytest.fixture(scope="module")
def sequences(params):
    """Two token sequences of 45 (five windows and a half) and their
    logits at every position."""
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(2, 45)), jnp.int32)
    return toks, np.asarray(m.forward_full(params, CFG, toks))


def dirty_caches():
    """Caches in which every fixed state holds another tenant's values."""
    caches = m.init_paged_cache(CFG, 1 + SLOTS * PMAX, PAGE, SLOTS, jnp.float32)
    return jax.tree.map(lambda x: x + 3.0, caches)


TABLES = jnp.asarray(1 + np.arange(SLOTS * PMAX).reshape(SLOTS, PMAX), jnp.int32)


def decode(params, caches, rows, page_kernel=None):
    """One step; ``rows`` maps slot -> (token, position)."""
    tok, pos, live = [0] * SLOTS, [0] * SLOTS, [False] * SLOTS
    for s, (t, p) in rows.items():
        tok[s], pos[s], live[s] = int(t), int(p), True
    return m.decode_paged(params, CFG, caches, jnp.asarray(tok, jnp.int32), jnp.asarray(pos, jnp.int32),
                          jnp.asarray(live), TABLES, None, PAGE, page_kernel=page_kernel)


def err(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


# --------------------------------------------------------------------------- #
# The model file


@pytest.mark.parametrize("name,layers", [("phi-4-mini-flash-reasoning", 32), ("phi4flash-debug", 8)])
def test_layer_rule_and_memory_plan(name, layers):
    cfg = m.PRESETS[name]
    half = layers // 2
    assert cfg.layers_of("mamba") == list(range(0, half + 1, 2))
    assert cfg.layers_of("window") == list(range(1, half, 2))
    assert cfg.layers_of("full") == [half + 1]
    assert cfg.layers_of("cross") == list(range(half + 3, layers, 2))
    assert cfg.layers_of("gmu") == list(range(half + 2, layers, 2))
    assert cfg.memory_layer == half
    if layers == 32:
        assert cfg.head_dim == 64 and cfg.d_inner == 5120 and cfg.dt_rank == 160
        assert abs(m.count_logical_params(cfg) - 3.85e9) < 0.01e9
        assert m.kv_bytes_per_token(cfg) == 5120  # K and V of ONE layer
        assert m.fixed_state_bytes_per_slot(cfg) == 8 * 512 * 5120 + 9 * (16 * 5120 * 4 + 3 * 5120 * 2)
        plan = m.serving_memory_bytes(cfg, 64, 4096)
        assert plan["fixed_state"] == 64 * 24_197_120 and plan["total"] == plan["weights"] + plan["kv_cache"]


def test_parameter_count_matches_the_tree(params):
    assert m.count_logical_params(CFG) == sum(int(x.size) for x in jax.tree.leaves(params))


def test_prefill_shortcut_equals_the_full_computation_at_the_last_position(params, sequences):
    toks, full = sequences
    for length in (45, 17):
        short = m.forward_full(params, CFG, toks, jnp.asarray([length, length], jnp.int32))
        assert err(short, full[:, length - 1]) < TOL


@pytest.mark.parametrize("page_kernel", [None, "interpret"])
def test_prefill_then_decode_past_the_windows_wrap_on_dirty_slots(params, sequences, page_kernel):
    """Rows of 20 and 13 tokens prefilled into slots whose state holds a
    former tenant's values, then decoded 25 steps: positions 13..44, over
    three wraps of the 8-token window; logits at every step."""
    toks, full = sequences
    lens, slots = jnp.asarray([20, 13], jnp.int32), jnp.asarray([2, 0], jnp.int32)
    logits, caches = m.prefill_paged(params, CFG, dirty_caches(), toks[:, :24], lens, slots, TABLES, PAGE)
    assert err(logits[0], full[0, 19]) < TOL and err(logits[1], full[1, 12]) < TOL
    pos, row = {2: 20, 0: 13}, {2: 0, 0: 1}
    for _ in range(25):
        lg, caches = decode(params, caches, {s: (toks[row[s], p], p) for s, p in pos.items()}, page_kernel)
        for s in pos:
            # (the page kernel multiplies in bfloat16 whatever the pool holds)
            assert err(lg[s], full[row[s], pos[s]]) < (0.02 if page_kernel else 5 * TOL), (s, pos[s])
            pos[s] += 1


@pytest.mark.parametrize("chunk", [16, 8])
def test_chunked_extend_carries_state_from_chunk_to_chunk(params, sequences, chunk):
    """45 tokens as chunks of 16, 16 and 13 (or of 8, the window itself:
    every chunk replaces the whole ring) into a dirty slot, with a
    decode block of dead rows between the chunks (the slot is not live
    yet: nothing may touch its state)."""
    toks, full = sequences
    caches, slot = dirty_caches(), jnp.asarray([1], jnp.int32)
    for start in range(0, 45, chunk):
        seg = toks[0:1, start:start + chunk]
        valid = seg.shape[1]
        seg = jnp.pad(seg, ((0, 0), (0, chunk - valid)))
        hidden, caches = m.extend_paged(params, CFG, caches, seg, jnp.asarray([start], jnp.int32),
                                        jnp.asarray([valid], jnp.int32), slot, TABLES, 64, PAGE)
        _, caches = decode(params, caches, {})
    assert err(m.head(params, CFG, hidden)[0], full[0, 44]) < TOL
    lg, _ = decode(params, caches, {1: (toks[1, 0], 45)})  # and the state it left decodes on
    again = np.asarray(m.forward_full(params, CFG, jnp.concatenate([toks[0:1], toks[1:2, :1]], axis=1)))
    assert err(lg[1], again[0, 45]) < 5 * TOL


@pytest.mark.parametrize("tail", [1, 5, 7, 8])
def test_a_narrow_last_chunk_leaves_what_the_fixed_width_walk_leaves(params, sequences, tail):
    """The engine runs a prompt's tail at a tail's width (the 583-token
    prompt of the benchmark: a chunk of 512, then 71 tokens in a chunk of
    128 at the capacity window instead of 512 at window 1024). At this
    size: a chunk of 32, then ``tail`` tokens in a chunk of 8 (one
    window, one page). Scan state, conv tails, rings, the slot's pages
    and the logits equal what the fixed-width walk leaves."""
    toks, full = sequences
    n = 32 + tail
    slot, ends = jnp.asarray([2], jnp.int32), {}
    for name, width, window in (("fixed", 32, 64), ("narrow", 8, PAGE * PMAX)):
        caches = dirty_caches()
        _, caches = m.extend_paged(params, CFG, caches, toks[0:1, :32], jnp.asarray([0], jnp.int32),
                                   jnp.asarray([32], jnp.int32), slot, TABLES, 32, PAGE)
        seg = jnp.pad(toks[0:1, 32:n], ((0, 0), (0, width - tail)))
        hidden, caches = m.extend_paged(params, CFG, caches, seg, jnp.asarray([32], jnp.int32),
                                        jnp.asarray([tail], jnp.int32), slot, TABLES, window, PAGE)
        ends[name] = (m.head(params, CFG, hidden)[0], caches)
    (lg_f, c_f), (lg_n, c_n) = ends["fixed"], ends["narrow"]
    assert err(lg_n, full[0, n - 1]) < TOL and err(lg_n, lg_f) < TOL
    mine = np.asarray(TABLES[2])[: -(-n // PAGE)]  # the slot's live pages
    for a, b in zip(jax.tree.leaves(c_f), jax.tree.leaves(c_n)):
        if a.shape[0] == SLOTS:  # scan state, conv tails, rings: every slot
            assert err(a, b) < TOL
        else:  # the pool
            assert err(a[mine], b[mine]) < TOL
    lg, _ = decode(params, c_n, {2: (toks[0, n], n)})  # and decodes on from there
    assert err(lg[2], full[0, n]) < 5 * TOL


def test_a_row_with_nothing_valid_changes_nothing(params):
    before = dirty_caches()
    _, after = m.extend_paged(params, CFG, before, jnp.zeros((2, 16), jnp.int32), jnp.zeros((2,), jnp.int32),
                              jnp.zeros((2,), jnp.int32), jnp.asarray([1, 1], jnp.int32), TABLES, 64, PAGE)
    before = dirty_caches()
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        if a.shape[0] == SLOTS:  # the fixed state, every slot
            assert bool(jnp.all(a == b))
        else:  # the pool: only the scratch page may differ
            assert bool(jnp.all(a[1:] == b[1:]))


def test_a_freed_slot_shows_no_trace_of_its_last_tenant(params, sequences):
    toks, full = sequences
    caches = m.init_paged_cache(CFG, 1 + SLOTS * PMAX, PAGE, SLOTS, jnp.float32)
    one = jnp.asarray([3], jnp.int32)
    _, caches = m.prefill_paged(params, CFG, caches, toks[0:1, :40], jnp.asarray([40], jnp.int32), one, TABLES, PAGE)
    for p in range(40, 45):
        _, caches = decode(params, caches, {3: (toks[0, p], p)})
    # the same slot and pages, a shorter tenant (its window is not full yet)
    logits, caches = m.prefill_paged(params, CFG, caches, toks[1:2, :8], jnp.asarray([5], jnp.int32), one, TABLES, PAGE)
    assert err(logits[0], full[1, 4]) < TOL
    for p in range(5, 12):
        lg, caches = decode(params, caches, {3: (toks[1, p], p)})
        assert err(lg[3], full[1, p]) < 5 * TOL


def test_rows_admitted_at_different_steps_equal_their_solo_runs(params, sequences):
    toks, full = sequences
    caches = dirty_caches()
    _, caches = m.prefill_paged(params, CFG, caches, toks[0:1, :8], jnp.asarray([6], jnp.int32),
                                jnp.asarray([0], jnp.int32), TABLES, PAGE)
    pos = {0: 6}
    for step in range(12):
        if step == 5:  # a neighbour joins while row 0 decodes
            _, caches = m.prefill_paged(params, CFG, caches, toks[1:2, :16], jnp.asarray([11], jnp.int32),
                                        jnp.asarray([2], jnp.int32), TABLES, PAGE)
            pos[2] = 11
        lg, caches = decode(params, caches, {s: (toks[0 if s == 0 else 1, p], p) for s, p in pos.items()})
        for s in pos:
            assert err(lg[s], full[0 if s == 0 else 1, pos[s]]) < 5 * TOL
            pos[s] += 1


def test_a_window_layers_memory_is_its_window_and_the_pool_holds_one_layer():
    cfg = m.PRESETS["phi-4-mini-flash-reasoning"]
    shapes = jax.eval_shape(lambda: m.init_paged_cache(cfg, 2049, 128, 64))
    assert shapes["pool"]["k"].shape == (2049, 10, 128, 128)  # ONE layer, pair layout, head-major pages
    assert len(shapes["win"]) == 8 and shapes["win"][0]["k"].shape == (64, 10, 512, 128)  # 512 tokens a slot, for good
    assert len(shapes["ssm"]) == 9 and shapes["ssm"][0].shape == (64, 16, 5120)
    assert shapes["ssm"][0].dtype == jnp.float32 and shapes["conv"][0].shape == (64, 3, 5120)


# --------------------------------------------------------------------------- #
# The registry


def test_registry_resolves_both_families_and_presets_written_at_run_time():
    from generativeaiexamples_tpu.models import llama

    fam, cfg = registry.resolve("phi4flash-debug")
    assert fam.name == "phi4flash" and fam.fixed_state and fam.verify_paged is None and cfg is CFG
    assert fam.paged_kv_shape(m.PRESETS["phi-4-mini-flash-reasoning"]) == registry.PagedKVShape(1, 10, 128, 40)
    fam, cfg = registry.resolve("debug")
    assert fam.name == "llama" and not fam.fixed_state and cfg is llama.PRESETS["debug"]
    llama.PRESETS["written-late"] = llama.PRESETS["debug"]  # as the benchmark's Mistral adapter does
    try:
        assert registry.resolve("written-late")[0].name == "llama"
    finally:
        del llama.PRESETS["written-late"]
    registry.register_preset("phi4flash", "another-name", dataclasses.replace(CFG, sliding_window=16))
    try:
        assert registry.resolve("another-name")[1].sliding_window == 16
    finally:
        del m.PRESETS["another-name"]
    with pytest.raises(KeyError, match="unknown model_config_name"):
        registry.resolve("no-such-model")
    assert registry.family_of(CFG).name == "phi4flash" and registry.family_of(llama.PRESETS["debug"]).name == "llama"


# --------------------------------------------------------------------------- #
# The engine: served through the registry, continuous batching


BASE = dict(
    model_config_name="phi4flash-debug", max_batch_size=3, max_seq_len=128, prefill_chunk=16,
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


def reference_margins(eng, prompt, out):
    ref = np.asarray(m.forward_full(eng.params, eng.model_config, jnp.asarray([prompt + out], jnp.int32)))[0]
    return [float(ref[len(prompt) - 1 + j].max() - ref[len(prompt) - 1 + j][t]) for j, t in enumerate(out)]


def test_engine_serves_every_prompt_shape_as_the_models_own_argmax(engine):
    """One chunk (5, 16), several (37: three chunks, 50:
    four), decode blocks past several window wraps, more requests than
    slots one after another (slot and page reuse): every served token is
    the whole-sequence forward's argmax. Nothing compiles after warm-up."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    assert engine._family.name == "phi4flash" and engine._paged_kernel == "interpret"
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, 500, size=n)] for n in (5, 16, 37, 50, 9)]
    greedy = SamplingParams(temperature=0.0, max_tokens=20)
    before = engine.metrics
    outs = [list(engine.iter_ids(p, greedy, timeout=300)) for p in prompts]
    for p, o in zip(prompts, outs):
        assert len(o) == 20 and max(reference_margins(engine, p, o)) < 1e-4
    after = engine.metrics
    assert after["paged_attn_kernel_dispatches"] > before["paged_attn_kernel_dispatches"]
    assert after["paged_attn_gather_dispatches"] == before["paged_attn_gather_dispatches"]
    assert engine._compile_watch.snapshot().get("hot_path_compiles_total", 0) == 0


@pytest.fixture(scope="module")
def ladder_engine():
    """A chunk of 32 over pages of 8: the width ladder has two rungs."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    eng = LLMEngine(EngineConfig(**dict(BASE, prefill_chunk=32)))
    eng.warmup()
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("n", [33, 37, 40, 41, 70])
def test_engine_serves_a_narrow_last_chunk_as_the_models_own_argmax(ladder_engine, n):
    """Tails of 1, 5, 8 (a narrow chunk of 8 at the capacity window), 9
    (past the narrow rung: a full-width tail) and 6 after two full
    chunks: every served token is the whole-sequence forward's argmax,
    the family keeps one row a wave and one narrow program, and nothing
    compiles after warm-up."""
    from generativeaiexamples_tpu.engine import dispatch_timeline
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    eng = ladder_engine
    assert eng.shapes.chunk_widths() == [8, 32]
    assert [s for s in eng.shapes.extend_signatures() if s[1] == 8] == [(1, 8, eng.max_seq_len)]
    prompt = [int(t) for t in np.random.default_rng(n).integers(3, 500, size=n)]
    cursor = dispatch_timeline.spans_since(0)[1]
    out = list(eng.iter_ids(prompt, SamplingParams(temperature=0.0, max_tokens=10), timeout=300))
    assert len(out) == 10 and max(reference_margins(eng, prompt, out)) < 1e-4
    chunks = [s for s in dispatch_timeline.spans_since(cursor)[0] if s["kind"] == "prefill_chunk"]
    tail = n % 32
    assert [(s["rows_dispatched"], s["width"]) for s in chunks] == [(1, 32)] * (n // 32) + [(1, 8 if tail <= 8 else 32)]
    assert chunks[-1]["pad_tokens"] == chunks[-1]["width"] - tail
    assert eng._compile_watch.snapshot()["compile_hot_path_total"] == 0


def test_rows_served_together_equal_their_solo_runs(engine):
    import threading

    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(3, 500, size=n)] for n in (7, 30, 12, 21, 40)]
    greedy = SamplingParams(temperature=0.0, max_tokens=14)
    from generativeaiexamples_tpu.engine import dispatch_timeline

    since = dispatch_timeline.cursor()  # the ring is the process's: other engines' waves sit in it
    solo = [list(engine.iter_ids(p, greedy, timeout=300)) for p in prompts]
    got = [None] * len(prompts)

    def run(i):
        got[i] = list(engine.iter_ids(prompts[i], greedy, timeout=300))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == solo
    waves = [s for s in dispatch_timeline.spans_since(since)[0] if s.get("kind") == "prefill_chunk"]
    assert waves and all(s["rows"] <= 1 for s in waves)  # one row a wave: shapes.max_wave_rows


def test_engine_counts_resets_skipped_tokens_and_state_dispatches(engine):
    from generativeaiexamples_tpu.engine import dispatch_timeline
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams
    from generativeaiexamples_tpu.utils import metrics as metrics_mod

    def read():
        text = metrics_mod.get_registry().render()  # Prometheus text
        out = {}
        for line in text.splitlines():
            if line.startswith("genai_engine_") and " " in line:
                k, v = line.rsplit(" ", 1)
                out[k] = float(v)
        return out

    before = read()
    prompt = list(range(3, 40))  # 37 tokens: three chunks of 16
    list(engine.iter_ids(prompt, SamplingParams(temperature=0.0, max_tokens=6), timeout=300))
    after = read()
    grew = lambda k: after.get(k, 0.0) - before.get(k, 0.0)  # noqa: E731
    assert grew("genai_engine_state_slot_resets_total") == 1
    assert grew("genai_engine_prefill_tokens_total") == 37
    assert grew("genai_engine_prefill_cross_skipped_tokens_total") == 37 - 3  # one position a chunk is computed
    assert grew('genai_engine_ssm_dispatches_total{path="scan"}') == 3
    assert grew('genai_engine_ssm_dispatches_total{path="step"}') >= 2
    assert after["genai_engine_fixed_state_bytes"] == 3 * m.fixed_state_bytes_per_slot(CFG, 2)
    spans = [s for s in dispatch_timeline.recent_spans(64) if s.get("kind") in ("decode", "prefill_chunk")]
    assert spans
    for s in spans[-4:]:
        assert s["kv_readers"] == 2 and s["state_rows"] >= 1 and "window_tokens_read" in s
    chunk = [s for s in spans if s["kind"] == "prefill_chunk"][-1]
    assert chunk["cross_skipped_tokens"] == chunk["tokens"] - chunk["rows"]


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
    with pytest.raises(ValueError, match=message) as exc:
        LLMEngine(EngineConfig(**dict(BASE, **overrides)))
    assert "fixed per-slot state" in str(exc.value)


@pytest.mark.parametrize("call", ["drain", "restore_snapshot"])
def test_request_snapshots_are_refused_where_they_are_taken(engine, call):
    from generativeaiexamples_tpu.engine.request_snapshot import SnapshotError

    with pytest.raises(SnapshotError, match="fixed per-slot state"):
        engine.drain(timeout=1) if call == "drain" else engine.restore_snapshot(None)
    assert not engine.is_draining()


def test_an_answer_that_ends_on_a_stop_id_reports_what_it_delivered(engine, monkeypatch):
    """The stop id that ends an answer is sampled but never a frame: the
    ``generated`` of the finish event is the number of ids the stream
    delivered (a load test pairs the two), the stop reason ``eos``."""
    from generativeaiexamples_tpu.engine import llm_engine
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    greedy, prompt = SamplingParams(temperature=0.0, max_tokens=8), [5, 6, 7]
    free = list(engine.iter_ids(prompt, greedy, timeout=300))
    k = max(i for i in range(len(free)) if free[i] not in free[:i])  # the last id not seen before it
    finished = []
    monkeypatch.setattr(llm_engine.flight_recorder, "finish_rid",
                        lambda rid, outcome="finish", **attrs: finished.append(attrs))
    monkeypatch.setattr(engine, "_stop_ids", engine._stop_ids | {free[k]})
    assert list(engine.iter_ids(prompt, greedy, timeout=300)) == free[:k]
    assert finished[-1] == {"generated": k, "stop": "eos"}


def test_a_fixed_state_family_is_sent_one_row_a_prefill_wave(engine):
    """Waves of several rows of the chunk walk hung the chip now and
    then (PERF.md, PR 29; cause not found): whatever
    ``prefill_wave_tokens`` says, no program of more than one extend
    row is built or warmed for a family with fixed state."""
    assert engine.engine_config.prefill_wave_tokens >= 4 * engine.engine_config.prefill_chunk
    assert engine.num_slots > 1
    assert engine.shapes.max_wave_rows() == 1
    assert {n for n, _, _ in engine.shapes.extend_signatures()} == {1}
