"""Pipelined spec-verify dispatch (``spec_pipeline_enable``): the
token-identity matrix (ISSUE 17 acceptance).

The contract under test: with the pipeline ON, every stream is
TOKEN-IDENTICAL to the same engine config with the pipeline OFF —
greedy and seeded-sampled, through the int8 KV cache, the paged
layout, a prefix-cache-warm admission, the disagg scheduler, and with
every runahead draft fault-forced into the rollback path
(``utils/faults.py`` site ``engine.spec_pipeline``). Optimism shapes
proposals only; the verify guards emissions, so identity holds
unconditionally. OFF must also be the exact prior dispatch path: the
pipeline counters never move. Engine-building tests: slow tier
(conftest SLOW_MODULES)."""
from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams
from generativeaiexamples_tpu.utils import faults

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

# Calibrated copy-heavy ramp (test_spec_decode.py): greedy decode of
# the debug model settles into self-repetition the lookup proposer
# drafts, so the runahead's full-acceptance optimism confirms often.
COPY_PROMPT = [3 + 10 * i for i in range(16)]
# Little self-repetition: drafts mostly miss, runahead mostly rolls
# back — the identity contract must not care.
PLAIN_PROMPT = [(i * 7) % 250 + 1 for i in range(24)]


def _legs():
    """One greedy and one seeded-sampled leg per prompt class."""
    return [
        ("greedy-copy", COPY_PROMPT,
         SamplingParams(temperature=0.0, max_tokens=64)),
        ("greedy-plain", PLAIN_PROMPT,
         SamplingParams(temperature=0.0, max_tokens=48)),
        ("sampled-copy", COPY_PROMPT,
         SamplingParams(temperature=0.8, top_p=0.9, max_tokens=32,
                        seed=1234)),
    ]


def _stream(engine, prompt, params):
    return list(engine.iter_ids(prompt, params, timeout=300))


def _pair(**overrides):
    """(pipeline-on, pipeline-off) engines sharing every other knob."""
    base = dict(TINY, spec_decode_enable="on")
    base.update(overrides)
    on = LLMEngine(EngineConfig(spec_pipeline_enable="on", **base))
    off = LLMEngine(EngineConfig(spec_pipeline_enable="off", **base))
    assert on._spec_pipeline and not off._spec_pipeline
    return on, off


def _assert_identical(**overrides):
    on, off = _pair(**overrides)
    try:
        for name, prompt, params in _legs():
            got = _stream(on, prompt, params)
            ref = _stream(off, prompt, params)
            assert got == ref, name
            assert got, name
    finally:
        on.shutdown()
        off.shutdown()


def test_identity_baseline_and_pipeline_actually_engages():
    on, off = _pair()
    try:
        m0 = on.metrics
        for name, prompt, params in _legs():
            assert _stream(on, prompt, params) == _stream(
                off, prompt, params
            ), name
        m1 = on.metrics
        # The runahead really ran (reconcile outcomes were recorded)
        # and optimism confirmed at least sometimes on the copy-heavy
        # leg. The confirm/rollback MIX is workload- and model-shaped
        # (the random-weight debug model only settles into clean
        # self-repetition in phases), so only engagement is pinned.
        confirmed = m1["spec_pipeline_confirmed"] - m0["spec_pipeline_confirmed"]
        rollbacks = m1["spec_pipeline_rollbacks"] - m0["spec_pipeline_rollbacks"]
        assert confirmed > 0
        assert confirmed + rollbacks > 0
    finally:
        on.shutdown()
        off.shutdown()


def test_identity_int8_kv():
    _assert_identical(kv_cache_dtype="int8")


def test_identity_small_pages():
    _assert_identical(page_size=8)


def test_identity_disagg_scheduler():
    # Disagg requires a paged-tileable geometry (test_scheduler.py);
    # decode tier runs the fused block like the reference disagg tests.
    _assert_identical(
        scheduler_policy="disagg",
        page_size=16,
        decode_block=4,
        watchdog_stall_s=0.0,
    )


def test_identity_prefix_cache_warm():
    """Insert-then-hit: the second admission lands on a warm prefix
    slot; both the insert and the hit stream must match OFF."""
    pre = [(i * 7) % 250 + 1 for i in range(32)]  # 32 cacheable tokens
    on, off = _pair(prefix_cache_slots=2)
    try:
        params = SamplingParams(temperature=0.0, max_tokens=32)
        for tail in (99, 123):  # first warms the slot, second hits it
            assert _stream(on, pre + [tail], params) == _stream(
                off, pre + [tail], params
            ), tail
    finally:
        on.shutdown()
        off.shutdown()


def test_fault_forced_rollbacks_stay_token_identical():
    """faults site ``engine.spec_pipeline``: every flush invalidates
    its runahead draft, driving the rollback path deterministically.
    The stream is STILL identical to OFF, and the rollback counter
    records the forced misses."""
    on, off = _pair()
    try:
        params = SamplingParams(temperature=0.0, max_tokens=64)
        ref = _stream(off, COPY_PROMPT, params)
        m0 = on.metrics
        faults.configure("engine.spec_pipeline", "error", at=1, count=0)
        try:
            got = _stream(on, COPY_PROMPT, params)
        finally:
            faults.reset()
        m1 = on.metrics
        assert got == ref
        assert (
            m1["spec_pipeline_rollbacks"] - m0["spec_pipeline_rollbacks"] > 0
        )
        # a forced rollback never confirms
        assert (
            m1["spec_pipeline_confirmed"] == m0["spec_pipeline_confirmed"]
        )
        # the engine recovers once the fault clears: optimism confirms
        # again and the stream is unchanged
        m2 = on.metrics
        assert _stream(on, COPY_PROMPT, params) == ref
        assert on.metrics["spec_pipeline_confirmed"] > m2["spec_pipeline_confirmed"]
    finally:
        on.shutdown()
        off.shutdown()


def test_pipeline_off_is_exact_prior_path():
    """OFF restores the synchronous per-round verify: nothing is ever
    left pending and the pipeline counters never move."""
    off = LLMEngine(
        EngineConfig(
            spec_decode_enable="on", spec_pipeline_enable="off", **TINY
        )
    )
    try:
        m0 = off.metrics
        out = _stream(
            off, COPY_PROMPT, SamplingParams(temperature=0.0, max_tokens=48)
        )
        m1 = off.metrics
        assert len(out) == 48
        assert off._spec_pending is None
        assert m1["spec_pipeline_rollbacks"] == m0["spec_pipeline_rollbacks"]
        assert m1["spec_pipeline_confirmed"] == m0["spec_pipeline_confirmed"]
        # spec itself still ran (the prior path, not a silent opt-out)
        assert m1["spec_drafted_tokens"] > m0["spec_drafted_tokens"]
    finally:
        off.shutdown()
