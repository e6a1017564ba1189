"""Engine-level prefix KV-cache tests (ISSUE 2 acceptance criteria).

With a shared chunk-aligned preamble: the second request's prefill
dispatches strictly fewer chunk steps than the first (via the
``genai_engine_prefill_chunks_total`` legacy-dict delta), warm greedy
outputs are token-identical to cold runs, disabling
``prefix_cache_enable`` restores the exact pre-PR admission path, and
eviction under a full store never corrupts outputs.
"""
import pytest

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

PRE = [(i * 7) % 250 + 1 for i in range(32)]  # 2 chunks, shared preamble
TAILS = {
    "q1": [3, 4, 5, 6, 7],
    "q2": [9, 10, 11, 12],
    "q3": [30, 31, 32, 33, 34, 35],
}


def _greedy(engine, prompt, n=6, hint=None):
    params = SamplingParams(temperature=0.0, max_tokens=n, prefix_hint=hint)
    return list(engine.iter_ids(prompt, params, timeout=300))


@pytest.fixture(scope="module")
def golden():
    """Cold greedy streams from a prefix-cache-DISABLED engine."""
    eng = LLMEngine(EngineConfig(prefix_cache_enable="off", **TINY))
    try:
        assert eng._prefix is None
        ref = {k: _greedy(eng, PRE + t) for k, t in TAILS.items()}
        # disabled path: identical prompts re-dispatch the full chunk set
        c0 = eng.metrics["prefill_chunks"]
        _greedy(eng, PRE + TAILS["q1"])
        assert eng.metrics["prefill_chunks"] - c0 == 3
        return ref
    finally:
        eng.shutdown()


def test_warm_hit_skips_chunks_and_is_token_identical(golden):
    eng = LLMEngine(EngineConfig(prefix_cache_slots=2, **TINY))
    try:
        assert eng._prefix is not None
        m0 = eng.metrics
        out1 = _greedy(eng, PRE + TAILS["q1"], hint="rag:test")
        m1 = eng.metrics
        # cold: full chunk set, one miss, prefix inserted
        assert m1["prefill_chunks"] - m0["prefill_chunks"] == 3
        assert m1["prefix_cache_misses"] - m0["prefix_cache_misses"] == 1
        assert m1["prefix_cache_hits"] - m0["prefix_cache_hits"] == 0
        assert out1 == golden["q1"]

        out2 = _greedy(eng, PRE + TAILS["q2"], hint="rag:test")
        m2 = eng.metrics
        # warm: strictly fewer chunk dispatches (suffix only), one hit,
        # 32 preamble tokens served from cached rows
        warm_chunks = m2["prefill_chunks"] - m1["prefill_chunks"]
        assert warm_chunks < 3
        assert warm_chunks == 1
        assert m2["prefix_cache_hits"] - m1["prefix_cache_hits"] == 1
        assert (
            m2["prefix_cache_tokens_reused"] - m1["prefix_cache_tokens_reused"]
            == 32
        )
        # the acceptance bar: warm greedy tokens identical to a cold run
        assert out2 == golden["q2"]
        # the session hint registered for submit-time keep-alives
        assert "rag:test" in eng._prefix._hints
    finally:
        eng.shutdown()


def test_repeated_full_prompt_still_prefills_last_chunk(golden):
    """An EXACT repeat of a cached prompt must still run >= 1 real chunk
    (the match caps at len-1) and produce the same greedy stream."""
    eng = LLMEngine(EngineConfig(prefix_cache_slots=2, **TINY))
    try:
        out1 = _greedy(eng, PRE + TAILS["q3"])
        c0 = eng.metrics["prefill_chunks"]
        out2 = _greedy(eng, PRE + TAILS["q3"])
        assert eng.metrics["prefill_chunks"] - c0 >= 1
        assert out1 == out2 == golden["q3"]
    finally:
        eng.shutdown()


def test_eviction_under_full_store_stays_correct(golden):
    """One store slot, three distinct preamble+tail prompts round-robin:
    inserts evict each other, and every stream still matches its cold
    reference — eviction can reclaim rows, never corrupt them."""
    eng = LLMEngine(EngineConfig(prefix_cache_slots=1, **TINY))
    try:
        ev0 = eng.metrics["prefix_cache_evictions"]
        prompts = {
            "a": [(i * 5) % 240 + 1 for i in range(32)] + [1, 2],
            "b": [(i * 9) % 240 + 2 for i in range(32)] + [3, 4],
        }
        cold = {}
        for name, p in prompts.items():  # b's insert evicts a
            cold[name] = _greedy(eng, p)
        warm = {}
        for name, p in prompts.items():  # a misses (evicted), re-inserts
            warm[name] = _greedy(eng, p)
        assert eng.metrics["prefix_cache_evictions"] - ev0 >= 2
        assert warm == cold
        # cross-check against a fresh prefix-off engine
        ref_eng = LLMEngine(EngineConfig(prefix_cache_enable="off", **TINY))
        try:
            for name, p in prompts.items():
                assert _greedy(ref_eng, p) == cold[name], name
        finally:
            ref_eng.shutdown()
    finally:
        eng.shutdown()


def test_mixed_wave_with_partial_hits(golden):
    """A held-admission wave mixing a warm (cached-prefix) row, a cold
    long row, and a short row decodes every stream correctly."""
    eng = LLMEngine(EngineConfig(prefix_cache_slots=2, **TINY))
    try:
        _greedy(eng, PRE + TAILS["q1"])  # populate the cache
        with eng.hold_admissions():
            reqs = {
                "q2": eng.submit(
                    PRE + TAILS["q2"],
                    SamplingParams(temperature=0.0, max_tokens=6),
                ),
                "long": eng.submit(
                    [(i * 3) % 200 + 1 for i in range(41)],
                    SamplingParams(temperature=0.0, max_tokens=6),
                ),
                "short": eng.submit(
                    [1, 9, 27], SamplingParams(temperature=0.0, max_tokens=6)
                ),
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
        assert got["q2"] == golden["q2"]
        # cold references for the other rows from a prefix-off engine
        ref_eng = LLMEngine(EngineConfig(prefix_cache_enable="off", **TINY))
        try:
            assert got["long"] == _greedy(
                ref_eng, [(i * 3) % 200 + 1 for i in range(41)]
            )
            assert got["short"] == _greedy(ref_eng, [1, 9, 27])
        finally:
            ref_eng.shutdown()
    finally:
        eng.shutdown()


def test_int8_kv_warm_matches_cold():
    """Prefix reuse through the int8 page pool (quantized rows and
    scales shared in place): warm greedy == cold greedy."""
    cfg = dict(TINY)
    eng = LLMEngine(
        EngineConfig(prefix_cache_slots=2, kv_cache_dtype="int8", **cfg)
    )
    try:
        assert eng._prefix is not None and eng._kv_quant
        _greedy(eng, PRE + TAILS["q1"])  # populate
        h0 = eng.metrics["prefix_cache_hits"]
        warm = _greedy(eng, PRE + TAILS["q2"])
        assert eng.metrics["prefix_cache_hits"] - h0 == 1
        ref = LLMEngine(
            EngineConfig(prefix_cache_enable="off", kv_cache_dtype="int8", **cfg)
        )
        try:
            assert warm == _greedy(ref, PRE + TAILS["q2"])
        finally:
            ref.shutdown()
    finally:
        eng.shutdown()


def test_admission_failure_unwinds_slots_and_pins(golden):
    """A prefill dispatch failure before _slot_req registration must
    fail the request (error + _END), return its claimed slot, and unpin
    its matched prefix entry — not leak capacity or freeze eviction."""
    eng = LLMEngine(EngineConfig(prefix_cache_slots=2, **TINY))
    try:
        _greedy(eng, PRE + TAILS["q1"])  # populate the radix cache
        boom = RuntimeError("synthetic dispatch failure")
        orig = eng._prefill_chunked
        state = {"fail": True}

        def failing(*args, **kwargs):
            if state["fail"]:
                state["fail"] = False
                raise boom
            return orig(*args, **kwargs)

        eng._prefill_chunked = failing
        req = eng.submit(
            PRE + TAILS["q2"], SamplingParams(temperature=0.0, max_tokens=4)
        )
        assert req.out_queue.get(timeout=120) is None  # failed fast
        assert req.error is boom
        # matched entry unpinned, slot returned, engine still healthy
        with eng._lock:
            assert all(e.refs == 0 for e in eng._prefix._entries)
            assert len(eng._free_slots) == eng.num_slots
        assert _greedy(eng, PRE + TAILS["q2"]) == golden["q2"]
    finally:
        eng.shutdown()
