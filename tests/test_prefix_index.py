"""Prefix-cache radix index semantics (host-only, fast tier).

Covers the index contracts the engine relies on: chunk-aligned match
caps, deepest-match, insert dedup, refcount pinning (eviction can never
recycle rows under a live request), LRU eviction order, session-hint
recency, and the engine's config-knob validation.
"""
import pytest

from generativeaiexamples_tpu.engine.prefix_cache import (
    PrefixCache,
    metrics_snapshot,
)


def ids(n, base=1):
    return [(base + i) % 251 + 1 for i in range(n)]


def test_match_is_chunk_aligned_and_capped():
    cache = PrefixCache(chunk=4, slots=2, max_len=64)
    prompt = ids(11)
    res = cache.insert(prompt)
    assert res is not None
    slot, length = res
    assert length == 8  # largest multiple of 4 <= len-1 = 10

    hit = cache.match(prompt)
    assert hit is not None and hit[1] == 8
    cache.release(hit[0])

    # a 9-token prompt sharing the prefix can still use the full 8 rows
    hit = cache.match(prompt[:9])
    assert hit is not None and hit[1] == 8
    cache.release(hit[0])

    # an 8-token prompt caps at 4 cached tokens — served as a PARTIAL
    # match against the depth-8 entry's first 4 rows (radix semantics:
    # any prefix of a cached prefix is itself cached)
    hit = cache.match(prompt[:8])
    assert hit is not None and hit[1] == 4
    assert hit[0].length == 8  # same entry, shorter usable span
    cache.release(hit[0])

    # a diverging prompt shares no chunk: miss
    assert cache.match([9, 9, 9, 9, 9, 9]) is None


def test_short_prompts_never_counted():
    cache = PrefixCache(chunk=8, slots=1, max_len=64)
    before = metrics_snapshot()
    assert cache.match(ids(8)) is None  # cap = 0: no cacheable chunk
    assert cache.insert(ids(8)) is None
    after = metrics_snapshot()
    assert after == before  # neither hit nor miss recorded


def test_insert_dedup_and_deeper_entries():
    cache = PrefixCache(chunk=4, slots=4, max_len=64)
    prompt = ids(20)
    assert cache.insert(prompt[:9]) is not None  # depth 8
    assert cache.insert(prompt[:9]) is None  # already cached at full cap
    deeper = cache.insert(prompt)  # depth 16 along the same path
    assert deeper is not None and deeper[1] == 16
    hit = cache.match(prompt)
    assert hit[1] == 16  # deepest rows win
    cache.release(hit[0])
    hit = cache.match(prompt[:10])
    assert hit[1] == 8  # capped walk serves the shared 8-row prefix
    cache.release(hit[0])


def test_refcount_pins_entry_against_eviction():
    cache = PrefixCache(chunk=4, slots=1, max_len=64)
    a, b = ids(9, base=1), ids(9, base=100)
    assert cache.insert(a) is not None
    pinned = cache.match(a)
    assert pinned is not None  # request admitted against entry A

    ev0 = metrics_snapshot()["prefix_cache_evictions"]
    assert cache.insert(b) is None  # every slot pinned: insert skips
    assert metrics_snapshot()["prefix_cache_evictions"] == ev0
    hit = cache.match(a)  # A's rows still intact
    assert hit is not None
    cache.release(hit[0])

    cache.release(pinned[0])  # request left its decode slot
    res = cache.insert(b)  # now B may evict A
    assert res is not None
    assert metrics_snapshot()["prefix_cache_evictions"] == ev0 + 1
    assert cache.match(a) is None  # A evicted
    hit = cache.match(b)
    assert hit is not None and hit[0].store_slot == res[0]
    cache.release(hit[0])


def test_lru_eviction_order():
    cache = PrefixCache(chunk=4, slots=2, max_len=64)
    a, b, c = ids(9, base=1), ids(9, base=100), ids(9, base=200)
    assert cache.insert(a) is not None
    assert cache.insert(b) is not None
    hit = cache.match(a)  # A most-recently used
    cache.release(hit[0])
    assert cache.insert(c) is not None  # evicts LRU = B
    assert cache.match(b) is None
    hit = cache.match(a)
    assert hit is not None
    cache.release(hit[0])


def test_hint_touch_protects_session():
    cache = PrefixCache(chunk=4, slots=2, max_len=64)
    a, b, c = ids(9, base=1), ids(9, base=100), ids(9, base=200)
    assert cache.insert(a, hint="session-a") is not None
    assert cache.insert(b) is not None  # B now more recent than A
    cache.touch("session-a")  # submit-time keep-alive for A's session
    assert cache.insert(c) is not None  # evicts B, not the touched A
    hit = cache.match(a, hint="session-a")
    assert hit is not None
    cache.release(hit[0])
    assert cache.match(b) is None


def test_stats_and_utilization():
    cache = PrefixCache(chunk=4, slots=2, max_len=16)
    assert cache.stats()["cached_rows"] == 0
    cache.insert(ids(9))
    s = cache.stats()
    assert s["entries"] == 1
    assert s["cached_rows"] == 8
    assert s["capacity_rows"] == 32
    assert s["free_slots"] == 1


def test_engine_validates_prefix_knobs():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    tiny = dict(
        model_config_name="debug", max_batch_size=2, max_seq_len=64,
        prefill_chunk=16, tensor_parallelism=1,
    )
    with pytest.raises(ValueError, match="prefix_cache_enable"):
        LLMEngine(EngineConfig(prefix_cache_enable="banana", **tiny))
    with pytest.raises(ValueError, match="prefix_cache_slots"):
        LLMEngine(EngineConfig(prefix_cache_slots=-1, **tiny))


def test_deeper_insert_consolidates_subsumed_ancestors():
    """A growing conversation inserts ever-deeper prefixes; unpinned
    ancestor entries along the same path are pure duplication (partial
    matching serves their rows from the deeper entry) and must be
    reclaimed instead of squatting store slots."""
    cache = PrefixCache(chunk=4, slots=4, max_len=64)
    convo = ids(40)
    other = ids(9, base=100)  # another chain's preamble
    assert cache.insert(other) is not None

    ev0 = metrics_snapshot()["prefix_cache_evictions"]
    for turn_len in (9, 17, 25, 33):  # each turn extends the history
        cache.insert(convo[:turn_len])
    # one consolidated conversation entry + the other chain's preamble
    assert cache.stats()["entries"] == 2
    # consolidation is not eviction: nothing became unservable
    assert metrics_snapshot()["prefix_cache_evictions"] == ev0
    hit = cache.match(other)  # preamble survived the conversation
    assert hit is not None
    cache.release(hit[0])
    hit = cache.match(convo[:12])  # early turns served via partial match
    assert hit is not None and hit[1] == 8
    cache.release(hit[0])
    hit = cache.match(convo[:40])
    assert hit is not None and hit[1] == 32
    cache.release(hit[0])


def test_divergent_sibling_tails_not_inserted():
    """Diverging INSIDE a cached branch (shared preamble + one-off
    question tail) must not burn a store slot per request; the shared
    rows stay served by partial matching. Pure extensions still deepen
    (previous test)."""
    cache = PrefixCache(chunk=4, slots=4, max_len=64)
    pre = ids(8)  # shared 2-chunk preamble
    q1 = pre + ids(8, base=50)
    assert cache.insert(q1) is not None  # cold: entry at depth 12
    q2 = pre + ids(8, base=90)  # sibling tail, diverges inside q1's branch
    hit = cache.match(q2)
    assert hit is not None and hit[1] == 8  # preamble served partially
    cache.release(hit[0])
    assert cache.insert(q2) is None  # no slot burned on the one-off tail
    assert cache.stats()["entries"] == 1


def test_engine_order_keeps_one_slot_per_conversation():
    """Engine call order per turn is match -> release (post-fetch) ->
    insert: the previous turn's entry is unpinned by insert time, so
    consolidation holds a growing conversation to ONE store slot."""
    cache = PrefixCache(chunk=4, slots=4, max_len=64)
    convo = ids(40)
    cache.insert(convo[:9])
    for turn_len in (17, 25, 33):
        m = cache.match(convo[:turn_len])
        assert m is not None
        cache.release(m[0])  # engine releases right after the fetch
        assert cache.insert(convo[:turn_len]) is not None
        assert cache.stats()["entries"] == 1
