"""The paged KV cache: engine-level contracts (slow tier).

The bar is the model's own greedy decode: what a float32 engine serves
— plain, speculative, prefix-warm, in a mixed wave — equals a
cache-free ``llama.forward`` decode (tests/greedy_reference.py).
Quantised pools and sampled streams, which no float reference can pin,
are held to run-to-run determinism, batch invariance, spec-on ==
spec-off and kernel-against-gather first tokens — plus the zero-copy
contract (a prefix hit maps pages) and exact page accounting
(everything released when the requests drain). Engines are tiny debug
configs on the virtual CPU platform; builds still jit-compile the
serving programs, hence the slow tier.
"""
import pytest
from greedy_reference import reference_greedy

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

BASE = dict(
    model_config_name="debug",
    max_batch_size=3,
    max_seq_len=64,
    prefill_chunk=16,
    tensor_parallelism=1,
    decode_block=4,
    decode_runahead=1,
    prefix_cache_slots=2,
    page_size=8,
)

PREAMBLE = [(i * 7) % 90 + 2 for i in range(33)]  # 33 tokens: 32 cacheable
PROMPTS = [
    PREAMBLE + [99],            # prefix-cache candidate
    list(range(5, 25)),         # one-chunk-plus prompt
    [42, 43, 44],               # short (one chunk)
]


def collect(engine, prompts, params):
    return [list(engine.iter_ids(p, params, timeout=300)) for p in prompts]


def build(**overrides):
    return LLMEngine(EngineConfig(**dict(BASE, **overrides)))


def wave(engine, prompts, params):
    """One held admission wave; each row's stream."""
    with engine.hold_admissions():
        reqs = [engine.submit(p, params) for p in prompts]
    outs = []
    for r in reqs:
        toks = []
        while True:
            item = r.out_queue.get(timeout=300)
            if item is None:
                break
            toks.append(item)
        outs.append(toks)
    return outs


@pytest.fixture(scope="module")
def paged():
    """A float32 engine, gather-served: what the reference can pin."""
    eng = build(dtype="float32")
    yield eng
    eng.shutdown()


def test_greedy_token_identity(paged):
    params = SamplingParams(temperature=0.0, max_tokens=12, seed=5)
    assert collect(paged, PROMPTS, params) == [reference_greedy(p, 12) for p in PROMPTS]


def test_sampled_streams_are_a_function_of_seed_and_position(paged):
    """A seeded sampled stream repeats run to run and does not depend
    on which other rows share its wave or its decode batch."""
    params = SamplingParams(temperature=0.9, top_p=0.8, max_tokens=12, seed=11)
    solo = collect(paged, PROMPTS, params)
    assert collect(paged, PROMPTS, params) == solo
    assert wave(paged, PROMPTS, params) == solo
    other = SamplingParams(temperature=0.9, top_p=0.8, max_tokens=12, seed=12)
    assert collect(paged, PROMPTS[:1], other) != solo[:1]


def test_prefix_warm_zero_copy(paged):
    """A prefix hit maps pages (refcount bump, no device work) and
    streams identically to its own cold pass and to the reference."""
    params = SamplingParams(temperature=0.0, max_tokens=10, seed=3)
    prompt = PREAMBLE + [7]

    m0 = paged.metrics
    cold = list(paged.iter_ids(prompt, params, timeout=300))
    warm = list(paged.iter_ids(prompt, params, timeout=300))
    m1 = paged.metrics
    assert warm == cold == reference_greedy(prompt, 10)
    assert m1["prefix_cache_hits"] - m0["prefix_cache_hits"] >= 1
    assert m1["kv_prefix_pages_mapped"] - m0["kv_prefix_pages_mapped"] >= 1
    assert m1["prefill_chunks"] - m0["prefill_chunks"] < 2 * 3  # the warm pass skipped cached chunks


def test_pages_released_when_drained(paged):
    """After every stream completes, the only pages still held belong to
    prefix-cache entries; live-request accounting returns to zero."""
    params = SamplingParams(temperature=0.0, max_tokens=8, seed=2)
    collect(paged, PROMPTS, params)
    stats = paged.paged_stats()
    assert stats["request_pages_held"] == 0
    assert stats["live_tokens"] == 0
    # entries hold at most capacity-many chunk-aligned prefixes
    assert stats["pages_in_use"] <= stats["pages_capacity"]
    assert stats["pages_in_use"] + stats["pages_free"] == stats["pages_capacity"]


def test_int8_kv_determinism_and_spec_identity():
    """An int8 pool has no float reference (quantisation moves the
    logits): its streams repeat run to run, on a second engine built
    the same way, and with speculation on."""
    first = build(kv_cache_dtype="int8")
    second = build(kv_cache_dtype="int8")
    try:
        params = SamplingParams(temperature=0.0, max_tokens=12, seed=5)
        outs = collect(first, PROMPTS, params)
        assert all(len(o) == 12 for o in outs)
        assert collect(first, PROMPTS, params) == outs
        assert collect(second, PROMPTS, params) == outs
        assert second.set_spec_decode(True)
        assert collect(second, PROMPTS, params) == outs
    finally:
        first.shutdown()
        second.shutdown()


def test_spec_decode_token_identity(paged):
    params = SamplingParams(temperature=0.0, max_tokens=12, seed=5)
    assert paged.set_spec_decode(True)
    try:
        assert collect(paged, PROMPTS, params) == [reference_greedy(p, 12) for p in PROMPTS]
    finally:
        paged.set_spec_decode(False)


def test_mixed_concurrent_wave_identity(paged):
    """A full mixed-length wave submitted at once (held admissions) —
    the page-granular admission path — serves each row its own greedy
    decode."""
    params = SamplingParams(temperature=0.0, max_tokens=10, seed=9)
    prompts = [PREAMBLE + [i] for i in range(2)] + [[42, 43, 44]]
    assert wave(paged, prompts, params) == [reference_greedy(p, 10) for p in prompts]


def test_minimal_pool_self_pin_no_livelock():
    """A request whose own pinned prefix match holds the pages whose
    eviction would fund it must still admit: funding retains the shared
    pages and UNPINS before the evict-and-retry loop (the allocator
    refcount, not the pin, protects shared pages).
    Before that ordering, this shape spun the dispatch loop forever."""
    paged = build(
        max_batch_size=1,
        kv_pool_pages=9,  # 1 scratch + exactly one full-length request
        decode_block=4,
    )
    try:
        params = SamplingParams(temperature=0.0, max_tokens=8, seed=4)
        # Request A caches a 32-token (4-page) prefix entry.
        out_a = list(paged.iter_ids(PREAMBLE + [1], params, timeout=120))
        assert out_a
        # Request B matches only the first chunk (2 shared pages) but
        # needs the full per-slot reservation — fundable only by
        # evicting the entry B itself pinned at match time.
        big = SamplingParams(temperature=0.0, max_tokens=64, seed=4)
        out_b = list(
            paged.iter_ids(PREAMBLE[:17] + [9] * 10, big, timeout=120)
        )
        assert out_b
        stats = paged.paged_stats()
        assert stats["request_pages_held"] == 0
    finally:
        paged.shutdown()


def test_kernel_path_serves_decode_and_verify():
    """The ragged Pallas kernel path (interpret mode on CPU — the same
    kernel logic the TPU compiles) against the gather. The op-level math
    is pinned tier-1 against a jnp reference
    (tests/test_page_attention.py); exact stream identity is the chip's
    to show (chip_smoke.py, the benchmark's reference comparison) — on
    CPU the random-init debug weights sit at argmax-tie flatness where
    the kernel's blockwise (non-bitwise) softmax legitimately flips
    ties. What IS invariant here: greedy determinism, bitwise first
    tokens (prefill never runs the kernel), full budgets, spec-on
    operation, and every decode dispatch charged to the kernel path."""
    gather = build()
    kern = build(paged_kernel="interpret")
    try:
        assert kern._paged_kernel == "interpret"
        assert kern._paged_verify_kernel == "interpret"
        params = SamplingParams(temperature=0.0, max_tokens=12, seed=5)
        fixed_outs = collect(gather, PROMPTS, params)
        m0 = kern.metrics  # the families are process-wide: count after the gather engine is done
        outs = collect(kern, PROMPTS, params)
        # deterministic under greedy decoding
        assert collect(kern, PROMPTS, params) == outs
        # first tokens come from prefill/extend logits the kernel never
        # touches — bitwise-equal to the gather-served engine's
        assert [o[0] for o in outs] == [o[0] for o in fixed_outs]
        assert all(len(o) == 12 for o in outs)
        # spec decode rides the multi-query kernel rows and still runs
        assert kern.set_spec_decode(True)
        try:
            spec_outs = collect(kern, PROMPTS, params)
            assert all(len(o) == 12 for o in spec_outs)
        finally:
            kern.set_spec_decode(False)
        m1 = kern.metrics
        assert (
            m1["paged_attn_kernel_dispatches"]
            > m0["paged_attn_kernel_dispatches"]
        )
        assert (
            m1["paged_attn_gather_dispatches"]
            == m0["paged_attn_gather_dispatches"]
        )
        assert kern.paged_stats()["attn_path"] == "kernel"
    finally:
        kern.shutdown()
        gather.shutdown()


@pytest.mark.parametrize("scales", ["token_major", "lane_dense"])
def test_kernel_path_int8_runs_deterministically(scales):
    """... over both layouts of the pool's scale planes: the debug
    model's 2 KV heads tile 128 lanes at 64-token pages (one row a
    page) and do not at 8. The lane-dense engine pairs its int8 pages
    a grid step, and a gather-served engine of the same geometry
    (writers and gather reader through the same helpers) agrees with it
    on the first tokens, which the kernel never touches."""
    geometry = dict(page_size=64, prefill_chunk=64, max_seq_len=128) if scales == "lane_dense" else {}
    kern = build(kv_cache_dtype="int8", paged_kernel="interpret", **geometry)
    gather = build(kv_cache_dtype="int8", **geometry)
    try:
        assert kern._kv_scale_layout() == gather._kv_scale_layout() == scales
        assert kern._cache[0]["ks"].shape[1:] == ((1, 128) if scales == "lane_dense" else (8, 2))
        assert kern._kv_pages_a_step == (2 if scales == "lane_dense" else 1)
        params = SamplingParams(temperature=0.0, max_tokens=12, seed=5)
        outs = collect(kern, PROMPTS, params)
        assert all(len(o) == 12 for o in outs)
        assert collect(kern, PROMPTS, params) == outs
        assert [o[0] for o in outs] == [o[0] for o in collect(gather, PROMPTS, params)]
    finally:
        kern.shutdown()
        gather.shutdown()


def test_gather_serves_the_cpu():
    """The kernel stays off on CPU with paged_kernel='auto' —
    gather-served, loudly accounted."""
    eng = build()
    try:
        assert eng._paged_kernel is None
        assert eng.paged_stats()["attn_path"] == "gather"
        params = SamplingParams(temperature=0.0, max_tokens=6, seed=1)
        m0 = eng.metrics
        assert list(eng.iter_ids([9, 8, 7], params, timeout=300))
        m1 = eng.metrics
        assert (
            m1["paged_attn_gather_dispatches"]
            > m0["paged_attn_gather_dispatches"]
        )
    finally:
        eng.shutdown()


def test_paged_warmup_compiles():
    """warmup() walks the chunked + window rungs (tables threaded
    through every program) without touching live state."""
    paged = build()
    try:
        paged.warmup()
        params = SamplingParams(temperature=0.0, max_tokens=6, seed=1)
        out = list(paged.iter_ids(list(range(9, 30)), params, timeout=300))
        assert len(out) > 0
    finally:
        paged.shutdown()


# --------------------------------------------------------------------------- #
# int4 packed KV (kv_cache_dtype=int4: two values per pool byte)


def test_int4_kv_deterministic_and_kernel_serves():
    """int4 streams are deterministic run-to-run and the ragged kernel
    (interpret) serves every decode dispatch over the packed pool. The
    op-level kernel-vs-dequant parity is pinned tier-1
    (tests/test_page_attention.py); exact stream identity vs the gather
    is the chip's to show — on CPU the random-init debug
    weights sit at argmax-tie flatness where the kernel's blockwise
    softmax legitimately flips ties (same bar as the bf16/int8 kernel
    tests above). First tokens come from prefill the kernel never
    touches, so those ARE bitwise. (int4 is NOT compared against
    int8/bf16 streams: halving the stored bits changes the numerics.)"""
    params = SamplingParams(temperature=0.0, max_tokens=12, seed=5)
    gather = build(kv_cache_dtype="int4")
    try:
        assert gather._kv_quant and gather._kv_packed
        pool = gather._cache[0]
        dh = gather.model_config.head_dim
        assert str(pool["k"].dtype) == "uint8"
        assert pool["k"].shape[-1] == dh // 2  # two values per byte
        a = collect(gather, PROMPTS, params)
        assert collect(gather, PROMPTS, params) == a
        kern = build(kv_cache_dtype="int4", paged_kernel="interpret")
        try:
            assert kern._paged_kernel == "interpret"
            m0 = kern.metrics
            outs = collect(kern, PROMPTS, params)
            assert collect(kern, PROMPTS, params) == outs
            assert [o[0] for o in outs] == [o[0] for o in a]
            assert all(len(o) == 12 for o in outs)
            m1 = kern.metrics
            assert (
                m1["paged_attn_kernel_dispatches"]
                > m0.get("paged_attn_kernel_dispatches", 0)
            )
            assert (
                m1["paged_attn_gather_dispatches"]
                == m0.get("paged_attn_gather_dispatches", 0)
            )
        finally:
            kern.shutdown()
    finally:
        gather.shutdown()


def test_int4_prefix_warm_zero_copy_and_spec_identity():
    """The page-mapping prefix hit and spec decode both survive the
    packed pool: warm streams match cold, and spec-on matches
    spec-off."""
    paged = build(kv_cache_dtype="int4")
    try:
        params = SamplingParams(temperature=0.0, max_tokens=10, seed=3)
        prompt = PREAMBLE + [7]
        m0 = paged.metrics
        cold = list(paged.iter_ids(prompt, params, timeout=300))
        warm = list(paged.iter_ids(prompt, params, timeout=300))
        m1 = paged.metrics
        assert warm == cold
        assert m1["prefix_cache_hits"] - m0["prefix_cache_hits"] >= 1
        assert m1["kv_prefix_pages_mapped"] - m0["kv_prefix_pages_mapped"] >= 1

        plain = collect(paged, PROMPTS, params)
        assert paged.set_spec_decode(True)
        try:
            assert collect(paged, PROMPTS, params) == plain
        finally:
            paged.set_spec_decode(False)
    finally:
        paged.shutdown()


# --------------------------------------------------------------------------- #
# acceptance-adaptive speculation (spec_adaptive_k=on)


def test_adaptive_k_token_identity_with_fixed_k():
    """On a load whose acceptance never dips below the threshold the
    adaptive engine dispatches every round at k_max — token-identical to
    the fixed-K engine (and to spec-off). The dispatched widths are
    accounted: adaptive rounds equal verify dispatches, and the mean
    picked K stays inside [k_min, k_max]."""
    params = SamplingParams(temperature=0.0, max_tokens=12, seed=5)
    fixed = build(spec_decode_enable="on", spec_draft_len=4)
    try:
        fixed_outs = collect(fixed, PROMPTS, params)
    finally:
        fixed.shutdown()
    adap = build(
        spec_decode_enable="on", spec_draft_len=4,
        spec_adaptive_k="on", spec_adaptive_k_min=1,
    )
    try:
        assert adap._adaptive_k is not None
        assert adap._adaptive_k.ladder == (4, 2, 1)
        m0 = adap.metrics
        assert collect(adap, PROMPTS, params) == fixed_outs
        m1 = adap.metrics
        rounds = m1["spec_adaptive_rounds"] - m0.get("spec_adaptive_rounds", 0)
        ksum = m1["spec_adaptive_k_sum"] - m0.get("spec_adaptive_k_sum", 0)
        assert rounds > 0
        assert 1 <= ksum / rounds <= 4  # every pick is a ladder rung
    finally:
        adap.shutdown()


def test_adaptive_k_warm_ladder_no_hot_compiles():
    """warmup() walks the (window x K-rung) verify grid, so no
    acceptance trajectory can reach an uncompiled verify shape: serving
    with adaptive K after warmup adds zero executables."""
    eng = build(
        spec_decode_enable="on", spec_draft_len=4,
        spec_adaptive_k="on", spec_adaptive_k_min=1,
    )
    try:
        eng.warmup()
        snap = eng.utilization_snapshot()
        assert snap["compile_warmup_done"] == 1.0
        executables = snap["compile_executables"]
        params = SamplingParams(temperature=0.0, max_tokens=10, seed=5)
        collect(eng, PROMPTS, params)
        snap = eng.utilization_snapshot()
        assert snap["compile_hot_path_total"] == 0.0
        assert snap["compile_executables"] == executables
    finally:
        eng.shutdown()


def test_int4_disagg_token_identity():
    """int4 under the disaggregated scheduler: the handoff moves
    packed pages between tiers, and streams stay identical to the
    unified scheduler on the same packed pool."""
    params = SamplingParams(temperature=0.0, max_tokens=10, seed=7)
    uni = build(kv_cache_dtype="int4")
    try:
        want = collect(uni, PROMPTS, params)
    finally:
        uni.shutdown()
    dis = build(kv_cache_dtype="int4", scheduler_policy="disagg")
    try:
        assert collect(dis, PROMPTS, params) == want
    finally:
        dis.shutdown()
