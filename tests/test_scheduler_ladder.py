"""Scheduler shape-ladder property tests (CPU-only, no engine build).

The prefix-cache admission path leans on these invariants: cached
prefixes are chunk-aligned (`_prefill_bucket` alignment), fetch copies
use the `_attention_window` rungs, and warm waves still pad up the
`_wave_sizes` ladder under the `_max_wave_rows` token budget. The
helpers only read scheduler scalars, so a bare instance (no jax, no
weights) exercises them across many configs.
"""
import pytest

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine.llm_engine import LLMEngine


def make_sched(chunk=16, max_seq=128, slots=8, budget=16384):
    eng = LLMEngine.__new__(LLMEngine)  # scheduler helpers only
    eng.engine_config = EngineConfig(
        prefill_chunk=chunk,
        max_seq_len=max_seq,
        max_batch_size=slots,
        prefill_wave_tokens=budget,
    )
    eng.num_slots = slots
    eng.max_seq_len = max_seq
    eng._fixed_state = False
    return eng


GRID = [
    dict(chunk=16, max_seq=128, slots=8),
    dict(chunk=16, max_seq=96, slots=4),   # capacity not chunk-aligned
    dict(chunk=512, max_seq=8192, slots=16),
    dict(chunk=128, max_seq=512, slots=96, budget=16384),
    dict(chunk=32, max_seq=4096, slots=1),
    dict(chunk=512, max_seq=4096, slots=32, budget=4096),
    # the benchmark's two cells (perfbench/configs) and chip_smoke's debug preset
    dict(chunk=512, max_seq=4096, slots=64, budget=2048),
    dict(chunk=512, max_seq=4096, slots=64, budget=512),
    dict(chunk=64, max_seq=256, slots=4),
]


@pytest.mark.parametrize("cfg", GRID)
def test_prefill_bucket_chunk_aligned_and_monotone(cfg):
    eng = make_sched(**cfg)
    chunk, cap = cfg["chunk"], cfg["max_seq"]
    prev = 0
    for n in range(1, cap + 2 * chunk):
        b = eng._prefill_bucket(n)
        assert b % chunk == 0 or b == cap  # chunk-aligned (or clamped)
        assert b <= cap
        if n <= cap:
            assert b >= n  # covers the prompt
            assert b - n < chunk  # padding stays under one chunk
        assert b >= prev  # monotone in prompt length
        prev = b


@pytest.mark.parametrize("cfg", GRID)
def test_wave_sizes_ladder(cfg):
    eng = make_sched(**cfg)
    sizes = eng._wave_sizes()
    slots = cfg["slots"]
    assert sizes[0] == 1 or slots == 1
    assert sizes[-1] == slots
    assert sizes == sorted(set(sizes))  # strictly increasing
    assert all(1 <= s <= slots for s in sizes)
    step = 4  # each rung is a compile of the whole unrolled prefill
    for a, b in zip(sizes, sizes[1:]):
        assert b <= a * step  # padding waste bounded by the rung step


@pytest.mark.parametrize("cfg", GRID)
def test_wave_pad_smallest_covering_rung(cfg):
    eng = make_sched(**cfg)
    sizes = eng._wave_sizes()
    for n in range(1, cfg["slots"] + 1):
        p = eng._wave_pad(n)
        assert p >= n
        assert p in sizes
        # smallest rung >= n
        assert all(s < n for s in sizes if s < p)


@pytest.mark.parametrize("cfg", GRID)
def test_max_wave_rows_budget(cfg):
    eng = make_sched(**cfg)
    budget = cfg.get("budget", 16384)
    prev = None
    for bucket in range(cfg["chunk"], cfg["max_seq"] + 1, cfg["chunk"]):
        r = eng._max_wave_rows(bucket)
        assert 1 <= r <= cfg["slots"]
        assert r * bucket <= budget or r == 1  # bounded activation footprint
        if prev is not None:
            assert r <= prev  # monotone non-increasing in bucket
        prev = r
    if cfg["chunk"] * cfg["slots"] <= budget:
        assert eng._max_wave_rows(cfg["chunk"]) == cfg["slots"]


@pytest.mark.parametrize("cfg", GRID)
def test_attention_window_rungs(cfg):
    eng = make_sched(**cfg)
    cap = cfg["max_seq"]
    prev = 0
    for needed in range(0, cap + 1, max(1, cfg["chunk"] // 2)):
        w = eng._attention_window(needed)
        assert w >= min(needed, cap)  # covers every live position
        assert w <= cap
        # power-of-two rung (or clamped at capacity)
        assert w == cap or (w & (w - 1)) == 0
        assert w >= prev  # monotone
        prev = w
