"""Engine-level speculative-decoding tests (ISSUE 3 acceptance).

The contract under test: with ``spec_decode_enable=on``, greedy decode
output is TOKEN-IDENTICAL to ``off`` — including the int8-KV and
prefix-cache-warm paths — while copy-heavy prompts decode in strictly
fewer verify dispatches than the non-spec run's decode dispatches, with
mean emitted tokens/dispatch >= 1.5 (the bench spec pass numbers).
Engine-building tests: slow tier (conftest SLOW_MODULES)."""
import dataclasses

import pytest

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

TINY = dict(
    model_config_name="debug",
    max_batch_size=4,
    max_seq_len=128,
    prefill_chunk=16,
    # block=1: the apples-to-apples dispatch comparison — spec replaces
    # per-token dispatches with multi-token verify dispatches; a blocked
    # engine amortizes dispatches by fusing steps instead (the bench
    # records both counters).
    decode_block=1,
    dtype="float32",
    tensor_parallelism=1,
    page_size=16,
)

# Calibrated copy-heavy prompt: greedy decode of the debug model from
# this ramp settles into self-repetition the output-buffer lookup
# drafts (the random-weight proxy for RAG outputs copying retrieved
# spans verbatim).
COPY_PROMPT = [3 + 10 * i for i in range(16)]
PLAIN_PROMPT = [(i * 7) % 250 + 1 for i in range(24)]


def _greedy(engine, prompt, n=96, spec_decode=None):
    params = SamplingParams(
        temperature=0.0, max_tokens=n, spec_decode=spec_decode
    )
    return list(engine.iter_ids(prompt, params, timeout=300))


@pytest.fixture(scope="module")
def spec_eng():
    eng = LLMEngine(EngineConfig(spec_decode_enable="on", **TINY))
    assert eng._spec_available and eng._spec_enabled
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def ref_eng():
    """Config-gated OFF: the exact prior decode path."""
    eng = LLMEngine(EngineConfig(spec_decode_enable="off", **TINY))
    assert not eng._spec_enabled
    yield eng
    eng.shutdown()


def test_greedy_token_identical_and_fewer_dispatches(spec_eng, ref_eng):
    m0 = spec_eng.metrics
    out_spec = _greedy(spec_eng, COPY_PROMPT)
    m1 = spec_eng.metrics
    out_ref = _greedy(ref_eng, COPY_PROMPT)
    assert out_spec == out_ref
    assert len(out_spec) == 96
    spec_disp = m1["decode_dispatches"] - m0["decode_dispatches"]
    drafted = m1["spec_drafted_tokens"] - m0["spec_drafted_tokens"]
    accepted = m1["spec_accepted_tokens"] - m0["spec_accepted_tokens"]
    assert drafted > 0 and accepted > 0
    # the acceptance bar: >= 1.5 emitted tokens per verify dispatch and
    # strictly fewer dispatches than one-per-token decode
    assert (len(out_spec) - 1) / spec_disp >= 1.5
    assert spec_disp < len(out_spec) - 1


def test_non_copy_prompt_still_token_identical(spec_eng, ref_eng):
    """A prompt with little self-repetition gains nothing — rejected
    drafts and draft-less steps must not change the stream."""
    assert _greedy(spec_eng, PLAIN_PROMPT, n=48) == _greedy(
        ref_eng, PLAIN_PROMPT, n=48
    )


def test_sampled_rows_fall_back_and_match(spec_eng, ref_eng):
    """temperature>0 rows never draft (single-token rows inside the
    verify dispatch) and their seeded stream is identical to the
    non-spec engine's."""
    params = SamplingParams(
        temperature=0.8, top_p=0.9, max_tokens=24, seed=4242
    )
    d0 = spec_eng.metrics["spec_drafted_tokens"]
    out_spec = list(spec_eng.iter_ids(COPY_PROMPT, params, timeout=300))
    assert spec_eng.metrics["spec_drafted_tokens"] == d0  # no drafting
    out_ref = list(ref_eng.iter_ids(COPY_PROMPT, params, timeout=300))
    assert out_spec == out_ref


def test_per_request_opt_out(spec_eng, ref_eng):
    """SamplingParams(spec_decode=False) opts one request out of
    drafting on a spec-enabled engine; the stream stays identical."""
    d0 = spec_eng.metrics["spec_drafted_tokens"]
    out = _greedy(spec_eng, COPY_PROMPT, n=32, spec_decode=False)
    assert spec_eng.metrics["spec_drafted_tokens"] == d0
    assert out == _greedy(ref_eng, COPY_PROMPT, n=32)


def test_draft_capped_at_max_tokens_budget(spec_eng, ref_eng):
    """Draft overrunning max_tokens: a copy-heavy request with a tiny
    budget emits EXACTLY max_tokens tokens, identical to non-spec (the
    cap_draft_len budget clamp + the reader's per-token stop)."""
    for n in (2, 5):
        out_spec = _greedy(spec_eng, COPY_PROMPT, n=n)
        out_ref = _greedy(ref_eng, COPY_PROMPT, n=n)
        assert len(out_spec) == n
        assert out_spec == out_ref


def test_mixed_wave_spec_and_sampled_rows(spec_eng, ref_eng):
    """One held-admission wave mixing a drafting greedy row, a sampled
    row, and an opted-out greedy row: every stream matches its non-spec
    reference."""
    specs = {
        "greedy": SamplingParams(temperature=0.0, max_tokens=48),
        "sampled": SamplingParams(
            temperature=0.7, top_p=0.8, max_tokens=48, seed=99
        ),
        "optout": SamplingParams(
            temperature=0.0, max_tokens=48, spec_decode=False
        ),
    }
    prompts = {
        "greedy": COPY_PROMPT,
        "sampled": PLAIN_PROMPT,
        "optout": COPY_PROMPT + [7],
    }
    with spec_eng.hold_admissions():
        reqs = {
            k: spec_eng.submit(prompts[k], specs[k]) for k in specs
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
    for name in specs:
        ref = list(ref_eng.iter_ids(prompts[name], specs[name], timeout=300))
        assert got[name] == ref, name


def test_sampled_only_traffic_keeps_pipelined_block_path():
    """With spec on but no draft-capable row live (sampled-only load),
    _decode_once must keep the PLAIN fused block path — steps advance
    decode_block per dispatch, nothing drafts, and the stream matches
    the non-spec engine's."""
    cfg = dict(TINY, decode_block=4)
    eng = LLMEngine(EngineConfig(spec_decode_enable="on", **cfg))
    try:
        params = SamplingParams(
            temperature=0.9, top_p=0.85, max_tokens=24, seed=7
        )
        m0 = eng.metrics
        out = list(eng.iter_ids(PLAIN_PROMPT, params, timeout=300))
        m1 = eng.metrics
        steps = m1["decode_steps"] - m0["decode_steps"]
        disp = m1["decode_dispatches"] - m0["decode_dispatches"]
        assert m1["spec_drafted_tokens"] == m0["spec_drafted_tokens"]
        assert steps / disp == 4  # every dispatch ran the fused block
        ref = LLMEngine(EngineConfig(spec_decode_enable="off", **cfg))
        try:
            assert out == list(ref.iter_ids(PLAIN_PROMPT, params, timeout=300))
        finally:
            ref.shutdown()
    finally:
        eng.shutdown()


def test_zero_draft_dispatch_falls_back_to_fused_block():
    """A draft-capable row whose draft length caps to zero (max_tokens
    budget) must dispatch the fused block program, not a 1-token
    verify: steps advance decode_block for that dispatch and the
    truncated stream matches non-spec."""
    cfg = dict(TINY, decode_block=4)
    eng = LLMEngine(EngineConfig(spec_decode_enable="on", **cfg))
    try:
        m0 = eng.metrics
        # budget after the prefill token is 1 -> cap_draft_len == 0 ->
        # the zero-draft fallback runs the block program
        out = _greedy(eng, COPY_PROMPT, n=2)
        m1 = eng.metrics
        steps = m1["decode_steps"] - m0["decode_steps"]
        disp = m1["decode_dispatches"] - m0["decode_dispatches"]
        assert len(out) == 2
        assert m1["spec_drafted_tokens"] == m0["spec_drafted_tokens"]
        assert steps / disp == 4
        ref = LLMEngine(EngineConfig(spec_decode_enable="off", **cfg))
        try:
            assert out == _greedy(ref, COPY_PROMPT, n=2)
        finally:
            ref.shutdown()
    finally:
        eng.shutdown()


def test_warmup_spec_shapes_compiles_without_corrupting_state(spec_eng):
    """Zero-live warmup dispatches are value no-ops: a greedy stream
    after warmup_spec_shapes matches one from before."""
    before = _greedy(spec_eng, COPY_PROMPT, n=24)
    spec_eng.warmup_spec_shapes()
    assert _greedy(spec_eng, COPY_PROMPT, n=24) == before


def test_int8_kv_spec_matches_non_spec():
    """The verify chunk through the head-major int8 cache layout
    (quantize-on-write, dequantized attention) stays token-identical."""
    cfg = dict(TINY)
    eng = LLMEngine(
        EngineConfig(spec_decode_enable="on", kv_cache_dtype="int8", **cfg)
    )
    try:
        assert eng._kv_quant and eng._spec_enabled
        d0 = eng.metrics["spec_drafted_tokens"]
        out_spec = _greedy(eng, COPY_PROMPT, n=64)
        assert eng.metrics["spec_drafted_tokens"] > d0
        ref = LLMEngine(
            EngineConfig(
                spec_decode_enable="off", kv_cache_dtype="int8", **cfg
            )
        )
        try:
            assert out_spec == _greedy(ref, COPY_PROMPT, n=64)
        finally:
            ref.shutdown()
    finally:
        eng.shutdown()


def test_prefix_cache_warm_spec_matches_cold_non_spec():
    """Spec decode on a prefix-cache-WARM request (cached preamble rows
    fetched into the slot, suffix-only prefill, then verify dispatches)
    still matches the cold non-spec stream."""
    pre = [(i * 7) % 250 + 1 for i in range(32)]  # 2 chunks
    tails = {"a": COPY_PROMPT[:5], "b": [9, 10, 11, 12]}
    eng = LLMEngine(
        EngineConfig(spec_decode_enable="on", prefix_cache_slots=2, **TINY)
    )
    try:
        assert eng._prefix is not None
        h0 = eng.metrics["prefix_cache_hits"]
        warm = {}
        for k, t in tails.items():  # 'a' inserts, 'b' hits the radix cache
            warm[k] = _greedy(eng, pre + t, n=48)
        assert eng.metrics["prefix_cache_hits"] - h0 >= 1
        ref = LLMEngine(
            EngineConfig(
                spec_decode_enable="off", prefix_cache_enable="off", **TINY
            )
        )
        try:
            for k, t in tails.items():
                assert warm[k] == _greedy(ref, pre + t, n=48), k
        finally:
            ref.shutdown()
    finally:
        eng.shutdown()


def test_draft_crossing_attention_window_boundary():
    """With capacity 256 the window ladder has two rungs (128, 256): a
    copy-heavy request whose verify chunks straddle position 128 decodes
    across the window recompile boundary token-identically."""
    from generativeaiexamples_tpu.models import llama

    llama.PRESETS.setdefault(
        "debug-256",
        dataclasses.replace(llama.PRESETS["debug"], max_seq_len=256),
    )
    cfg = dict(TINY, model_config_name="debug-256", max_seq_len=256)
    prompt = [3 + (10 * i) % 490 for i in range(100)]
    eng = LLMEngine(EngineConfig(spec_decode_enable="on", **cfg))
    try:
        # positions run ~100 -> ~200: drafts cross the 128-row window rung
        out_spec = _greedy(eng, prompt, n=100)
        assert len(out_spec) == 100
        ref = LLMEngine(EngineConfig(spec_decode_enable="off", **cfg))
        try:
            assert out_spec == _greedy(ref, prompt, n=100)
        finally:
            ref.shutdown()
    finally:
        eng.shutdown()


def test_knob_validation_at_engine_init():
    with pytest.raises(ValueError, match="spec_decode_enable"):
        LLMEngine(EngineConfig(spec_decode_enable="always", **TINY))
    with pytest.raises(ValueError, match="spec_draft_len"):
        LLMEngine(EngineConfig(spec_draft_len=0, **TINY))
    with pytest.raises(ValueError, match="spec_ngram_max"):
        LLMEngine(EngineConfig(spec_ngram_max=-1, **TINY))
