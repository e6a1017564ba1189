"""Chunked prefill (VERDICT r3 #4): fixed-shape chunk dispatches replace
per-length-bucket prefill executables, so no prompt length can trigger an
XLA compile inside a request and admission waves mix prompt lengths.

Reference analogue: TRT-LLM chunked context (docs/architecture.md:54-66).
"""
import pytest
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


def test_warmup_covers_all_lengths():
    """After warm-up, serving any longer prompt adds NO new executable
    of any step program — the no-compile-inside-request property, read
    from the compile watch every program dispatches through."""
    eng = LLMEngine(EngineConfig(**TINY))
    try:
        eng.warmup(prompt_lengths=[8])
        before = eng._compile_watch.snapshot()
        assert before["compile_executables_extend"] > 0 and before["compile_executables_finish"] > 0
        _greedy(eng, [(i * 5) % 200 + 1 for i in range(100)], 4)  # 7 chunks
        after = eng._compile_watch.snapshot()
        assert after["compile_hot_path_total"] == 0
        assert {k: v for k, v in after.items() if k.startswith("compile_executables")} == {
            k: v for k, v in before.items() if k.startswith("compile_executables")
        }
    finally:
        eng.shutdown()
