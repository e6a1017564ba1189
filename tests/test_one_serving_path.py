"""One serving path (PR 31): an engine always holds per-layer weights and
a page pool. A geometry that cannot page is refused at start-up, the
removed fields and switches are gone, and what the engine serves is the
model's own greedy decode."""
import dataclasses
import logging

import numpy as np
import pytest
from greedy_reference import reference_greedy, reference_params

from generativeaiexamples_tpu.config import EngineConfig

TINY = dict(
    model_config_name="debug",
    max_batch_size=4,
    max_seq_len=128,
    prefill_chunk=16,
    page_size=16,
    decode_block=4,
    dtype="float32",
    tensor_parallelism=1,
    watchdog_stall_s=0.0,
)


# --------------------------------------------------------------------- #
# (a) a geometry `kv_layout=auto` used to serve on fixed strips, behind
# an INFO line, is refused at start-up, naming the fields at fault


@pytest.mark.parametrize(
    "overrides,fields",
    [
        (dict(page_size=128, prefill_chunk=16), ("prefill_chunk", "page_size")),
        (dict(page_size=32, prefill_chunk=48), ("prefill_chunk", "page_size")),
        (dict(page_size=16, max_seq_len=120), ("max_seq_len", "page_size")),
    ],
    ids=["page128_chunk16", "page32_chunk48", "seq120_page16"],
)
def test_a_geometry_that_cannot_page_is_refused_at_start_up(overrides, fields):
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    with pytest.raises(ValueError) as exc:
        LLMEngine(EngineConfig(**dict(TINY, **overrides)))
    for name in fields:
        assert name in str(exc.value)


# --------------------------------------------------------------------- #
# (b) the fields and switches that chose among serving paths are gone


@pytest.mark.parametrize(
    "field", ["kv_layout", "serving_layout", "pipeline_parallelism", "chunked_prefill"]
)
def test_engine_config_has_no_field_that_selects_a_serving_path(field):
    assert field not in {f.name for f in dataclasses.fields(EngineConfig)}
    with pytest.raises(TypeError):
        EngineConfig(**{field: "auto"})


@pytest.fixture(scope="module")
def engine():
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    eng = LLMEngine(EngineConfig(**TINY))
    eng.warmup()
    yield eng
    eng.shutdown()


PROMPTS = {
    "one_chunk": [5 + i for i in range(9)],  # chunk 0 alone
    "three_chunks": [3 + (i * 7) % 200 for i in range(40)],  # chunked extend
    "chunk_boundary": [11 + (i * 5) % 300 for i in range(32)],
}


@pytest.mark.parametrize(
    "switch", ["GENAI_TPU_DECODE_SLAB", "GENAI_TPU_DECODE_UNROLL", "GENAI_TPU_DISABLE_KV_KERNEL"]
)
def test_a_removed_switch_changes_nothing(monkeypatch, switch):
    """Set, a removed switch neither fails start-up nor moves a token:
    same executables, same greedy stream."""
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

    monkeypatch.setenv(switch, "1")
    eng = LLMEngine(EngineConfig(**TINY))
    try:
        assert not any(hasattr(eng, a) for a in ("_slab_decode", "_decode_unrolled", "_kv_kernel"))
        prompt = PROMPTS["three_chunks"]
        got = list(eng.iter_ids(prompt, SamplingParams(temperature=0.0, max_tokens=6), timeout=300))
        assert got == reference_greedy(prompt, 6)
        assert eng.paged_stats()["attn_path"] == "gather"
    finally:
        eng.shutdown()


# --------------------------------------------------------------------- #
# (c) what an engine is: a page allocator, one executable family a step
# kind, and the model's own greedy decode


def test_an_engine_always_has_a_page_allocator_and_one_program_a_step_kind(engine):
    stats = engine.paged_stats()
    assert stats is not None and stats["pages_capacity"] == 4 * (128 // 16) + 4 * (128 // 16)
    for gone in ("_paged", "_layered", "_pp", "_kv_kernel", "_chunked", "_prefix_store"):
        assert not hasattr(engine, gone), gone
    snap = engine._compile_watch.snapshot()
    families = {k[len("compile_executables_"):] for k in snap if k.startswith("compile_executables_")}
    # (the dense family's waves go out packed: ONE prefill program kind, extend, as every family's; and
    # no put_rows, the tiny program that hands a rectangle of fewer rows' hidden states back to its wave)
    # (update_slots: a module-level function, so jit's caches of it are one a process and an engine built
    # after another finds it warm: jit reports no event and the watch, which reads jit's events, no family)
    assert families | {"update_slots"} == {"decode", "extend", "finish", "update_slots", "page_tables"}
    assert engine.shapes.packed and not hasattr(engine, "_prefill_fn")  # (every family: test_one_admission_path.py)
    # the gather serves the CPU: one decode program a window rung, and
    # nothing compiled after warm-up
    assert snap["compile_executables_decode"] == len(engine.shapes.window_rungs())
    assert snap["compile_hot_path_total"] == 0


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_served_greedy_tokens_are_the_cache_free_forwards(engine, name):
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    prompt = PROMPTS[name]
    got = list(engine.iter_ids(prompt, SamplingParams(temperature=0.0, max_tokens=12), timeout=300))
    assert got == reference_greedy(prompt, 12)
    assert engine._compile_watch.snapshot()["compile_hot_path_total"] == 0


def test_a_mixed_wave_serves_each_row_its_own_greedy_tokens(engine):
    """Rows of one admission wave (short and long prompts together: a
    chunked wave with per-row valid masks) each decode as if alone."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    greedy = SamplingParams(temperature=0.0, max_tokens=8)
    with engine.hold_admissions():
        reqs = {name: engine.submit(PROMPTS[name], greedy) for name in sorted(PROMPTS)}
    for name, req in reqs.items():
        got = []
        while (item := req.out_queue.get(timeout=300)) is not None:
            got.append(item)
        assert got == reference_greedy(PROMPTS[name], 8), name


def test_the_kernel_paths_line_names_what_the_harness_reads(caplog):
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    with caplog.at_level(logging.INFO, logger="generativeaiexamples_tpu.engine.llm_engine"):
        eng = LLMEngine(EngineConfig(**dict(TINY, paged_kernel="interpret")))
    try:
        line = next(r.getMessage() for r in caplog.records if "resolved kernel paths:" in r.getMessage())
        fields = dict(kv.split("=", 1) for kv in line.split("resolved kernel paths: ")[1].split(" (")[0].split())
        assert fields["paged_kernel"] == "interpret" and fields["quant_kernel"] == "False"
        assert "kv_kernel" not in fields
        assert eng.paged_stats()["attn_path"] == "kernel"
    finally:
        eng.shutdown()


# --------------------------------------------------------------------- #
# the model-axis width, now that no pipeline axis can absorb devices


class _Arch:
    def __init__(self, heads, kv_heads, mlp=512, vocab=512, hidden=256):
        self.num_heads, self.num_kv_heads = heads, kv_heads
        self.intermediate_size, self.vocab_size, self.hidden_size = mlp, vocab, hidden


@pytest.mark.parametrize(
    "tp,devices,arch,want",
    [
        (2, 8, _Arch(8, 8), 2),  # an explicit width wins
        (-1, 1, _Arch(8, 2), -1),  # one device: nothing to resolve
        (-1, 8, _Arch(8, 8), -1),  # the architecture admits every device
        (-1, 8, _Arch(8, 2), 2),  # KV heads cap the axis: spare devices idle
        (-1, 8, _Arch(6, 6, mlp=510, vocab=510, hidden=252), 2),  # gcd with the device count
    ],
)
def test_resolve_parallelism_caps_the_model_axis(monkeypatch, tp, devices, arch, want):
    import jax

    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    monkeypatch.setattr(jax, "devices", lambda *a: [object()] * devices)
    eng = LLMEngine.__new__(LLMEngine)
    assert eng._resolve_parallelism(EngineConfig(tensor_parallelism=tp), arch) == want


# --------------------------------------------------------------------- #
# the draft model's private cache walks (models/llama.py): what is left
# of the per-slot strips


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16_strips", "int8_strips"])
def test_draft_cache_walks_follow_the_cache_free_forward(quantized):
    """extend_layers over two chunks, then decode_layers steps, against
    ``forward`` over the whole sequence: exact for float strips, inside
    quantisation error for int8 ones."""
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama

    cfg, stacked = llama.PRESETS["debug"], reference_params()
    params = llama.consume_split_params_layers(dict(stacked, layers=dict(stacked["layers"])))
    prompt = PROMPTS["three_chunks"][:24]
    caches = llama.init_kv_cache_layers(cfg, 2, 64, jnp.float32, quantized=quantized)
    slot = jnp.asarray([1], jnp.int32)
    for k in range(0, 24, 16):
        chunk = prompt[k:k + 16]
        tok = jnp.asarray([chunk + [0] * (16 - len(chunk))], jnp.int32)
        _, caches = llama.extend_layers(
            params, cfg, tok, jnp.asarray([k], jnp.int32), jnp.asarray([len(chunk)], jnp.int32),
            slot, caches, 64,
        )
    ids = list(prompt)
    tokens = jnp.asarray([0, 77], jnp.int32)
    positions = jnp.asarray([0, 24], jnp.int32)
    logits, caches = llama.decode_layers(params, cfg, tokens, positions, caches, window=64)
    ids.append(77)
    want, _ = llama.forward(
        stacked, cfg, jnp.asarray([ids], jnp.int32), jnp.arange(25, dtype=jnp.int32)[None]
    )
    err = float(np.max(np.abs(np.asarray(logits[1]) - np.asarray(want[0, -1]))))
    scale = float(np.max(np.abs(np.asarray(want[0, -1]))))
    assert err <= (0.05 if quantized else 1e-4) * scale, (err, scale)
