"""Engine-level resident-draft-model speculative-decoding tests
(ISSUE 13 acceptance).

The contract under test, mirroring tests/test_spec_decode.py for the
draft-model proposer: with ``spec_proposer='draft_model'`` (or
``'combined'``), greedy AND seeded-sampled streams are TOKEN-IDENTICAL
to spec-off — including int8 target KV, the paged and fixed layouts,
and prefix-cache-warm admissions — while the whole wave drafts in ONE
batched draft dispatch per spec round and normal (non-copy-heavy)
prompts clear >2 emitted tokens per target dispatch with a calibrated
(shared-weights) tiny draft. Engine-building tests: slow tier
(conftest SLOW_MODULES)."""
import pytest

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

TINY = dict(
    model_config_name="debug",
    max_batch_size=4,
    max_seq_len=128,
    prefill_chunk=16,
    decode_block=1,
    dtype="float32",
    tensor_parallelism=1,
    page_size=16,
)
# "debug-draft" is a genuinely DIFFERENT (1-layer) model: acceptance is
# near zero, so these tests exercise heavy rejection + the frontier
# rewind. The calibrated throughput test pairs "debug" with itself
# (shared random-init weights — the mechanical acceptance ceiling).
DRAFT = dict(
    spec_decode_enable="on",
    spec_proposer="draft_model",
    spec_draft_model="debug-draft",
)

COPY_PROMPT = [3 + 10 * i for i in range(16)]
NORMAL_PROMPT = [(i * 37 + (i * i) % 91) % 199 + 1 for i in range(24)]


def _greedy(engine, prompt, n=64):
    params = SamplingParams(temperature=0.0, max_tokens=n)
    return list(engine.iter_ids(prompt, params, timeout=300))


def _sampled(engine, prompt, n=24, seed=4242):
    params = SamplingParams(
        temperature=0.7, top_p=0.8, max_tokens=n, seed=seed
    )
    return list(engine.iter_ids(prompt, params, timeout=300))


@pytest.fixture(scope="module")
def draft_eng():
    eng = LLMEngine(EngineConfig(**DRAFT, **TINY))
    assert eng._spec_available and eng._spec_enabled
    assert eng._draft is not None
    assert eng._spec_proposer.kind == "draft_model"
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def ref_eng():
    eng = LLMEngine(EngineConfig(spec_decode_enable="off", **TINY))
    yield eng
    eng.shutdown()


def test_greedy_identity_and_batched_draft_dispatches(draft_eng, ref_eng):
    m0 = draft_eng.metrics
    out = _greedy(draft_eng, NORMAL_PROMPT)
    m1 = draft_eng.metrics
    assert out == _greedy(ref_eng, NORMAL_PROMPT)
    assert len(out) == 64
    # the wave drafted through batched draft dispatches (one per spec
    # round), counted on their own family — target dispatches unchanged
    draft_disp = m1["spec_draft_dispatches"] - m0["spec_draft_dispatches"]
    drafted = m1["spec_drafted_tokens"] - m0["spec_drafted_tokens"]
    assert draft_disp > 0
    assert drafted > 0
    # mismatched 1-layer draft: rejections dominate; every rejected
    # round still emitted the bonus token and stayed identical
    assert m1["spec_accepted_tokens"] - m0["spec_accepted_tokens"] <= drafted


def test_sampled_rows_draft_and_stay_identical(draft_eng, ref_eng):
    """The draft-model proposer drafts SAMPLED rows (the verify program
    samples every position with the pure (seed, position) keys), and
    the seeded stream matches the non-spec engine token for token."""
    d0 = draft_eng.metrics["spec_drafted_tokens"]
    out = _sampled(draft_eng, NORMAL_PROMPT)
    assert draft_eng.metrics["spec_drafted_tokens"] > d0  # it DID draft
    assert out == _sampled(ref_eng, NORMAL_PROMPT)


def test_copy_prompt_identity(draft_eng, ref_eng):
    assert _greedy(draft_eng, COPY_PROMPT, n=48) == _greedy(
        ref_eng, COPY_PROMPT, n=48
    )


def test_per_request_opt_out(draft_eng, ref_eng):
    d0 = draft_eng.metrics["spec_drafted_tokens"]
    params = SamplingParams(temperature=0.0, max_tokens=32, spec_decode=False)
    out = list(draft_eng.iter_ids(NORMAL_PROMPT, params, timeout=300))
    assert draft_eng.metrics["spec_drafted_tokens"] == d0
    assert out == _greedy(ref_eng, NORMAL_PROMPT, n=32)


def test_tiny_budget_caps_draft(draft_eng, ref_eng):
    for n in (2, 5):
        out = _greedy(draft_eng, NORMAL_PROMPT, n=n)
        assert len(out) == n
        assert out == _greedy(ref_eng, NORMAL_PROMPT, n=n)


def test_mixed_wave_greedy_sampled_optout(draft_eng, ref_eng):
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
        "greedy": NORMAL_PROMPT,
        "sampled": COPY_PROMPT,
        "optout": NORMAL_PROMPT + [7],
    }
    with draft_eng.hold_admissions():
        reqs = {k: draft_eng.submit(prompts[k], specs[k]) for k in specs}
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
        ref = list(
            ref_eng.iter_ids(prompts[name], specs[name], timeout=300)
        )
        assert got[name] == ref, name


def test_proposer_runtime_toggle_and_off_restores_prior_path(
    draft_eng, ref_eng
):
    """lookup <-> draft_model <-> combined at runtime; spec off keeps
    the exact pipelined block path."""
    ref = _greedy(ref_eng, COPY_PROMPT, n=32)
    try:
        assert draft_eng.set_spec_proposer("lookup") == "lookup"
        assert _greedy(draft_eng, COPY_PROMPT, n=32) == ref
        assert draft_eng.set_spec_proposer("combined") == "combined"
        draft_eng.warmup_spec_shapes()
        assert _greedy(draft_eng, COPY_PROMPT, n=32) == ref
        assert draft_eng.set_spec_decode(False) is False
        assert _greedy(draft_eng, COPY_PROMPT, n=32) == ref
        draft_eng.set_spec_decode(True)
    finally:
        assert draft_eng.set_spec_proposer("draft_model") == "draft_model"
        draft_eng.set_spec_decode(True)


def test_int8_target_kv_identity():
    cfg = dict(TINY)
    eng = LLMEngine(EngineConfig(kv_cache_dtype="int8", **DRAFT, **cfg))
    try:
        assert eng._kv_quant
        d0 = eng.metrics["spec_drafted_tokens"]
        out = _greedy(eng, NORMAL_PROMPT)
        assert eng.metrics["spec_drafted_tokens"] > d0
        ref = LLMEngine(
            EngineConfig(
                spec_decode_enable="off", kv_cache_dtype="int8", **cfg
            )
        )
        try:
            assert out == _greedy(ref, NORMAL_PROMPT)
        finally:
            ref.shutdown()
    finally:
        eng.shutdown()


def test_int8_draft_kv_identity():
    """An int8 DRAFT cache changes only the proposals (the draft's own
    numerics); the emitted stream must still match spec-off exactly."""
    cfg = dict(TINY)
    eng = LLMEngine(
        EngineConfig(spec_draft_kv_dtype="int8", **DRAFT, **cfg)
    )
    try:
        assert eng._draft._kv_quant
        out = _greedy(eng, NORMAL_PROMPT)
        ref = LLMEngine(EngineConfig(spec_decode_enable="off", **cfg))
        try:
            assert out == _greedy(ref, NORMAL_PROMPT)
        finally:
            ref.shutdown()
    finally:
        eng.shutdown()


def test_small_page_target_identity(ref_eng):
    """Draft-model spec over a target pool of another page size (the
    draft's private strips know no pages): greedy + seeded sampled match
    the spec-off engine."""
    eng = LLMEngine(
        EngineConfig(**DRAFT, **dict(TINY, page_size=8))
    )
    try:
        assert _greedy(eng, NORMAL_PROMPT) == _greedy(ref_eng, NORMAL_PROMPT)
        assert _sampled(eng, NORMAL_PROMPT) == _sampled(ref_eng, NORMAL_PROMPT)
    finally:
        eng.shutdown()


def test_prefix_warm_identity():
    pre = [(i * 7) % 250 + 1 for i in range(32)]  # 2 chunks
    tails = {"a": NORMAL_PROMPT[:5], "b": [9, 10, 11, 12]}
    eng = LLMEngine(
        EngineConfig(prefix_cache_slots=2, **DRAFT, **TINY)
    )
    try:
        assert eng._prefix is not None
        h0 = eng.metrics["prefix_cache_hits"]
        warm = {}
        for k, t in tails.items():  # 'a' inserts, 'b' hits
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


def test_draft_model_len_override_serves():
    """spec_draft_model_len widens the EFFECTIVE K past spec_draft_len
    (verify width, caps, and paged funding all follow — the
    test_kv_pages invariant); the stream stays identical."""
    cfg = dict(TINY)
    eng = LLMEngine(
        EngineConfig(
            spec_draft_len=2, spec_draft_model_len=6, **DRAFT, **cfg
        )
    )
    try:
        assert eng._spec_draft == 6
        out = _greedy(eng, NORMAL_PROMPT, n=32)
        ref = LLMEngine(EngineConfig(spec_decode_enable="off", **cfg))
        try:
            assert out == _greedy(ref, NORMAL_PROMPT, n=32)
        finally:
            ref.shutdown()
    finally:
        eng.shutdown()


def test_draft_validates_preset():
    with pytest.raises(ValueError, match="spec_draft_model"):
        LLMEngine(
            EngineConfig(
                spec_decode_enable="on",
                spec_proposer="draft_model",
                spec_draft_model="no-such-preset",
                **TINY,
            )
        )
