"""Chunked prefill (VERDICT r3 #4): fixed-shape chunk dispatches replace
per-length-bucket prefill executables, so no prompt length can trigger an
XLA compile inside a request and admission waves mix prompt lengths.

Reference analogue: TRT-LLM chunked context (docs/architecture.md:54-66).
"""
import dataclasses
import functools

import pytest
from greedy_reference import build_engine as build
from greedy_reference import reference_greedy

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

TINY = dict(
    model_config_name="debug",
    max_batch_size=4,
    max_seq_len=128,
    prefill_chunk=16,
    decode_block=2,
    dtype="float32",
    tensor_parallelism=1,
    page_size=16,
)


def _greedy(engine, prompt, n):
    return list(
        engine.iter_ids(
            prompt, SamplingParams(temperature=0.0, max_tokens=n), timeout=300
        )
    )


@pytest.fixture(scope="module")
def golden():
    """Cache-free greedy streams (``llama.forward``) for several prompt
    lengths."""
    prompts = {
        "short": [1, 9, 27],  # < one chunk
        "exact": list(range(2, 18)),  # == one chunk
        "long": [(i * 7) % 250 + 1 for i in range(41)],  # 3 chunks
    }
    return prompts, {k: reference_greedy(p, 6) for k, p in prompts.items()}


def test_chunked_greedy_matches_the_cache_free_forward(golden):
    prompts, ref = golden
    eng = LLMEngine(EngineConfig(**TINY))
    try:
        for name, prompt in prompts.items():
            assert _greedy(eng, prompt, 6) == ref[name], name
    finally:
        eng.shutdown()


def test_chunked_mixed_length_wave(golden):
    """One admission wave carrying different prompt lengths (the
    fragmentation fix): every request still decodes its own reference
    stream."""
    prompts, ref = golden
    eng = LLMEngine(EngineConfig(**TINY))
    try:
        waves0 = eng.metrics.get("admission_waves", 0)
        with eng.hold_admissions():
            reqs = {
                name: eng.submit(
                    prompt, SamplingParams(temperature=0.0, max_tokens=6)
                )
                for name, prompt in prompts.items()
            }
        got = {}
        for name, req in reqs.items():
            toks = []
            while True:
                item = req.out_queue.get(timeout=300)
                if item is None:
                    break
                toks.append(item)
            got[name] = toks
        # the long prompt makes the wave chunked, which admits the short
        # rows alongside: one wave, not three
        assert eng.metrics["admission_waves"] == waves0 + 1
        assert eng.metrics.get("prefill_chunks", 0) >= 3
        for name in prompts:
            assert got[name] == ref[name], name
    finally:
        eng.shutdown()


def test_chunked_int8_kv_chunking_invariant(golden):
    """Chunked scatter/gather through the int8 page pool: greedy tokens
    are EXACTLY invariant to the chunk size (per-row quantization is
    independent of chunking), so a 3-chunk and a 2-chunk prefill of the
    same prompt must agree. (Exact match vs the float forward is not
    required: chunked queries attend dequantized rows — logits differ
    by quantization error.)"""
    prompts, _ = golden
    cfg = dict(TINY)
    streams = {}
    for chunk in (16, 32):
        cfg["prefill_chunk"] = chunk
        eng = LLMEngine(
            EngineConfig(kv_cache_dtype="int8", **cfg)
        )
        try:
            streams[chunk] = _greedy(eng, prompts["long"], 6)
        finally:
            eng.shutdown()
    assert streams[16] == streams[32]
    assert len(streams[16]) == 6


@pytest.mark.parametrize("kind", ["packed", "rect"])
def test_warmup_covers_all_lengths(kind):
    """After warm-up, NO prompt length 1..max_seq_len and no wave size
    adds an executable of any step program — the no-compile-inside-
    request property, read from the compile watch every program
    dispatches through — and warm-up compiled no extend signature the
    shape rule cannot produce. Packed: one program a token rung.
    Neither kind has a prefill program beside its extends."""
    cfg = dict(TINY, max_seq_len=96, prefill_chunk=32, page_size=8)
    eng = build(kind, **cfg)
    try:
        eng.warmup()
        before = eng._compile_watch.snapshot()
        assert before["compile_executables_extend"] > 0 and before["compile_executables_finish"] > 0
        # one executable a signature of the shape rule, by jit's own count
        signatures = set(eng.shapes.extend_signatures())
        assert before["compile_executables_extend"] == len(signatures)
        if kind == "packed":
            assert sorted(w for _, w, _ in signatures) == eng.shapes.packed_rungs() == [8, 16, 24, 32, 48, 64, 96, 128]
            assert {n for n, _, _ in signatures} == {4}  # the carry's rows: the wave cap
            assert before["compile_executables_finish"] == 1
        else:
            assert {w for _, w, _ in signatures} == {8, 32}
        assert not hasattr(eng, "_prefill_fn") and "compile_executables_prefill" not in before
        # what jit itself holds: it keys an executable on more than
        # shapes (a carry that is not committed to the device selects
        # another one than a carry that is), so serving must not add to
        # it either
        jits = {name: getattr(eng, f"_{name}_fn").__wrapped__
                for name in ("extend", "finish", "put_rows", "update_slots", "decode")}
        held = {name: fn._cache_size() for name, fn in jits.items()}
        assert held["extend"] == len(signatures)
        lengths = list(range(1, 95))
        size = 0
        while lengths:
            size = size % 4 + 1  # waves of 1, 2, 3, 4 rows in turn
            with eng.hold_admissions():
                reqs = [
                    eng.submit([(i * 5) % 200 + 1 for i in range(lengths.pop())],
                               SamplingParams(temperature=0.0, max_tokens=2))
                    for _ in range(min(size, len(lengths)))
                ]
            for req in reqs:
                while req.out_queue.get(timeout=300) is not None:
                    pass
        after = eng._compile_watch.snapshot()
        assert after["compile_hot_path_total"] == 0
        assert {name: fn._cache_size() for name, fn in jits.items()} == held
        assert {k: v for k, v in after.items() if k.startswith("compile_executables")} == {
            k: v for k, v in before.items() if k.startswith("compile_executables")
        }
    finally:
        eng.shutdown()


# --------------------------------------------------------------------- //
# The shape rule, on a chunk of 64 over pages of 16 and four slots.
# Packed (llama as registered): a chunk's live tokens on one axis at the
# least rung of {16, 32, 48, 64, 96, 128, 192, 256} that holds them.
# Rectangles (a family without a packed walk): a prompt's tail runs at a
# tail's width, over the rows that have one (widths {16, 64}, rows {1, 4}).

LADDER = dict(TINY, model_config_name="debug-1k", max_seq_len=256, prefill_chunk=64, page_size=16)
TAILS = [1, 15, 16, 17, 63]  # 1, page - 1, page, page + 1, chunk - 1
RUNGS = [16, 32, 48, 64, 96, 128, 192, 256]
KINDS = ["packed", "rect"]


def _rung(n):
    return next(t for t in RUNGS if t >= n)


def _prompt(n, salt):
    return [(i * salt + 3) % 250 + 1 for i in range(n)]


SHORT = {"b": _prompt(40, 11), "c": _prompt(30, 13), "d": _prompt(10, 17)}


@functools.lru_cache(maxsize=None)
def _ref(prompt, preset="debug-1k"):
    return reference_greedy(list(prompt), 4, preset=preset)


def _agrees(eng, toks, ref):
    """The served stream is the reference's, up to where the reference
    samples a stop id (which ends an answer and is never delivered)."""
    return toks == ref[:len(toks)] and (len(toks) == len(ref) or ref[len(toks)] in eng._stop_ids)


def _serve_wave(eng, prompts, hint=None):
    """One admission wave of ``prompts``; the greedy streams."""
    waves0 = eng.metrics.get("admission_waves", 0)
    params = SamplingParams(temperature=0.0, max_tokens=4, prefix_hint=hint)
    with eng.hold_admissions():
        reqs = [eng.submit(p, params) for p in prompts]
    out = []
    for req in reqs:
        toks = []
        while (item := req.out_queue.get(timeout=300)) is not None:
            toks.append(item)
        out.append(toks)
    assert eng.metrics["admission_waves"] == waves0 + 1
    return out


def _waves(tail):
    """name -> prompts of one wave: a 64 + tail prompt alone, with three
    short rows that sit the tail chunk out, with a second tail and two
    short rows (as rectangles: the tail runs on one row, on one row of
    the four, on the wave's four rows, two of them dead)."""
    a, a2 = _prompt(64 + tail, 7), _prompt(64 + max(1, tail - 1), 5)
    return {
        "one_row": [a],
        "tail_on_one_of_four": [SHORT["b"], a, SHORT["c"], SHORT["d"]],
        "tail_on_four": [a, SHORT["b"], a2, SHORT["d"]],
    }


def _computed(kind, wave, tail, prompts):
    """Tokens the wave's two chunk dispatches compute."""
    if kind == "rect":
        # the tail chunk at the narrowest rung that holds it, over the
        # rows that have one: not rows x prefill_chunk again
        rows = {"one_row": 1, "tail_on_one_of_four": 1, "tail_on_four": 4}[wave]
        return len(prompts) * 64 + rows * (16 if tail <= 16 else 64)
    # the live tokens of each chunk, up the one ladder
    return sum(_rung(sum(min(max(len(p) - k * 64, 0), 64) for p in prompts)) for k in (0, 1))


@pytest.fixture(scope="module", params=KINDS)
def ladder_engine(request):
    # (no prefix reuse: the waves share prompts, and the token counts
    # below are those of cold rows)
    eng = build(request.param, prefix_cache_enable="off", **LADDER)
    assert eng.shapes.chunk_widths() == [16, 64] and eng.shapes.packed_rungs() == RUNGS
    yield request.param, eng
    eng.shutdown()


@pytest.mark.parametrize("wave", ["one_row", "tail_on_one_of_four", "tail_on_four"])
@pytest.mark.parametrize("tail", TAILS)
def test_tail_chunks_match_the_cache_free_forward(ladder_engine, tail, wave):
    kind, eng = ladder_engine
    prompts = _waves(tail)[wave]
    computed0, live0 = eng.metrics["extend_tokens_computed"], eng.metrics["prefill_tokens"]
    got = _serve_wave(eng, prompts)
    for p, toks in zip(prompts, got):
        assert _agrees(eng, toks, _ref(tuple(p))), (wave, tail, len(p), toks)
    assert eng.metrics["extend_tokens_computed"] - computed0 == _computed(kind, wave, tail, prompts)
    assert eng.metrics["prefill_tokens"] - live0 == sum(len(p) for p in prompts)


@pytest.fixture(scope="module", params=KINDS)
def prefix_engine(request):
    eng = build(request.param, prefix_cache_enable="auto", **LADDER)
    first = _prompt(64 + 20, 7)
    assert _agrees(eng, _serve_wave(eng, [first], hint="rag:test")[0], _ref(tuple(first)))
    yield request.param, eng, first
    eng.shutdown()


@pytest.mark.parametrize("tail", TAILS)
def test_tail_chunk_of_a_prefix_hit_row(prefix_engine, tail):
    """A row whose first chunk is a prefix hit runs ONLY its tail:
    alone (one short dispatch at offset 64 and nothing else), and
    beside a cold row of two chunks (packed: the cold row's first chunk
    alone on the axis, then both tails on one)."""
    kind, eng, first = prefix_engine
    warm = first[:64] + _prompt(tail, 19)
    cold = _prompt(64 + 5, 23 + tail)
    for wave in ([warm], [warm, cold]):
        hits0, computed0 = eng.metrics["prefix_cache_hits"], eng.metrics["extend_tokens_computed"]
        got = _serve_wave(eng, wave, hint="rag:test")
        assert eng.metrics["prefix_cache_hits"] - hits0 == 1
        for p, toks in zip(wave, got):
            assert _agrees(eng, toks, _ref(tuple(p))), (len(wave), len(p), toks)
        computed = eng.metrics["extend_tokens_computed"] - computed0
        if len(wave) == 1:
            assert computed == (_rung(tail) if kind == "packed" else (16 if tail <= 16 else 64))
        elif kind == "packed":
            assert computed == 64 + _rung(tail + 5)


@pytest.fixture(scope="module")
def int8_streams():
    """int8 KV: per-token quantization is independent of chunking and of
    how a wave is laid out, so the packed engine, a packed engine whose
    ladder starts at the chunk (pages as large as the chunk) and the
    engine of rectangles must serve EXACTLY the same tokens."""
    engines = {
        name: build(kind, kv_cache_dtype="int8", **dict(LADDER, page_size=page))
        for name, kind, page in (("ladder", "packed", 16), ("fixed", "packed", 64), ("rect", "rect", 16))
    }
    assert engines["ladder"].shapes.packed_rungs() == RUNGS and engines["fixed"].shapes.packed_rungs() == [64, 128, 192, 256]
    yield engines
    for eng in engines.values():
        eng.shutdown()


@pytest.mark.parametrize("wave", ["one_row", "tail_on_one_of_four", "tail_on_four"])
@pytest.mark.parametrize("tail", TAILS)
def test_tail_chunks_int8_kv_equal_the_fixed_width_walk(int8_streams, tail, wave):
    prompts = _waves(tail)[wave]
    got = {name: _serve_wave(eng, prompts) for name, eng in int8_streams.items()}
    assert got["ladder"] == got["fixed"] == got["rect"]
    assert any(len(toks) == 4 for toks in got["ladder"])


@pytest.fixture(scope="module", params=KINDS)
def kernel_engine(request):
    """A short dispatch's read through the page kernel (interpreted),
    with the kernel's row cap lowered so that a 16-wide tail of this
    model's four heads folds into sub-rows of four queries, as a
    128-wide tail of 32 heads folds into sub-rows of 16 on the chip."""
    from generativeaiexamples_tpu.ops import page_attention

    cap, page_attention.MAX_QUERY_ROWS = page_attention.MAX_QUERY_ROWS, 16
    eng = build(request.param, prefix_cache_enable="off", paged_kernel="interpret", **LADDER)
    try:
        assert eng._paged_extend_kernel == "interpret"
        # one program a rung under the chunk, whatever the chunk: no window rung
        assert [s for s in eng.shapes.extend_signatures() if s[1] < 64] == (
            [(4, 16, 256), (4, 32, 256), (4, 48, 256)] if request.param == "packed"
            else [(1, 16, 256), (4, 16, 256)]
        )
        yield eng
    finally:
        eng.shutdown()
        page_attention.MAX_QUERY_ROWS = cap


@pytest.mark.parametrize("wave", ["tail_on_one_of_four", "tail_on_four"])
@pytest.mark.parametrize("tail", [1, 16, 17])
def test_tail_chunks_through_the_page_kernel(kernel_engine, tail, wave):
    prompts = _waves(tail)[wave]
    for p, toks in zip(prompts, _serve_wave(kernel_engine, prompts)):
        assert _agrees(kernel_engine, toks, _ref(tuple(p))), (wave, tail, len(p), toks)


# --------------------------------------------------------------------- //
# Packed waves at the benchmark's geometry (chunk 512, pages of 128, four
# rows a wave), on the debug model: every prompt length of the deck and
# around it, in waves of 1-4 rows

LENGTHS = [1, 17, 71, 330, 458, 512, 583, 1100]
BENCH = dict(TINY, model_config_name="debug-2k", max_seq_len=2048, prefill_chunk=512, page_size=128,
             prefill_wave_tokens=2048, prefix_cache_enable="off")


def _wave_of(rows, first):
    """``rows`` prompts, the first of ``first`` tokens, the others the
    next lengths of the list."""
    at = LENGTHS.index(first)
    return [_prompt(LENGTHS[(at + j) % len(LENGTHS)], 7 + 2 * j) for j in range(rows)]


@pytest.fixture(scope="module")
def bench_engine():
    from generativeaiexamples_tpu.models import llama, registry

    registry.register_preset("llama", "debug-2k", dataclasses.replace(llama.PRESETS["debug-1k"], max_seq_len=2048))
    eng = build("packed", **BENCH)
    assert eng.shapes.packed_rungs() == [128, 256, 384, 512, 768, 1024, 1536, 2048]
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("rows", [1, 2, 3, 4])
@pytest.mark.parametrize("first", LENGTHS)
def test_packed_waves_match_the_cache_free_forward(bench_engine, first, rows):
    prompts = _wave_of(rows, first)
    computed0 = bench_engine.metrics["extend_tokens_computed"]
    for p, toks in zip(prompts, _serve_wave(bench_engine, prompts)):
        assert _agrees(bench_engine, toks, _ref(tuple(p), "debug-2k")), (rows, first, len(p), toks)
    # every chunk at the least rung that holds its live tokens
    ladder = bench_engine.shapes.packed_rungs()
    chunks = [sum(min(max(len(p) - k * 512, 0), 512) for p in prompts) for k in range(3)]
    assert bench_engine.metrics["extend_tokens_computed"] - computed0 == sum(
        next(t for t in ladder if t >= n) for n in chunks if n
    )
