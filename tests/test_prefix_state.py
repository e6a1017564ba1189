"""The prefix store carrying a fixed state (engine/prefix_cache.py
``stateful``, engine/llm_engine.py ``_insert_prefix_state`` /
``_copy_prefix_state``, models/registry.py ``state_row_keys``): the
index alone (what is matchable, take-over, eviction, pins, discard) and
an engine of the ``solaropen2`` family with the store on, held against
the same engine with the store off and against the plain reference.
"""
import jax
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.prefix_cache import PrefixCache

CHUNK = 4


def ids(n, salt=0):
    return [(7 * i + salt) % 97 for i in range(n)]


def index(slots=3, **kw):
    dropped = []
    return PrefixCache(chunk=CHUNK, slots=slots, max_len=64, on_drop=dropped.append, stateful=True, **kw), dropped


# --------------------------------------------------------------------------- #
# The index


def test_an_entry_is_matchable_only_once_its_copy_is_enqueued():
    cache, _ = index()
    prompt = ids(10)
    ent = cache.insert_entry(prompt)
    assert ent is not None and ent.length == 8 == cache.cacheable_len(len(prompt)) and not ent.ready
    assert cache.match(prompt) is None  # inserted, its state not yet on its way to the row
    cache.mark_ready(ent)
    got, length = cache.match(prompt)
    assert got is ent and length == 8
    cache.release(got)


def test_a_match_returns_only_a_depth_that_has_a_state():
    """A deeper entry's PAGES cover a shallower prefix; its state does
    not. A plain index serves the partial prefix, a stateful one does not."""
    long, short = ids(17), ids(17)[:9] + [1, 2, 3]
    cache, _ = index()
    cache.mark_ready(cache.insert_entry(long))  # depth 16
    assert cache.match(short) is None  # shares 8 tokens with the entry's path: no state stands at 8
    plain = PrefixCache(chunk=CHUNK, slots=3, max_len=64)
    plain.insert_entry(long)
    assert plain.match(short)[1] == 8
    # a state AT 8 is found, and the deepest one on the path wins
    cache.mark_ready(cache.insert_entry(long[:9]))
    got, length = cache.match(short)
    assert length == 8 and got.length == 8
    cache.release(got)
    got, length = cache.match(long + [5])
    assert length == 16
    cache.release(got)


def test_an_insert_takes_over_the_ticket_of_the_entry_its_request_entered_through():
    """A conversation's next turn enters through the last turn's entry
    and inserts a deeper one: the old entry's ticket (its store row) and
    pages pass on; the index holds ONE entry a conversation."""
    cache, dropped = index(slots=2)
    turn1 = ids(9)
    first = cache.insert_entry(turn1)
    cache.mark_ready(first)
    turn2 = turn1 + ids(8, salt=3)
    via, _ = cache.match(turn2)
    cache.release(via)  # the funding step unpins once the pages are retained
    second = cache.insert_entry(turn2, via=via)
    assert second.store_slot == first.store_slot and second.length == 16 and dropped == [first]
    assert cache.stats()["entries"] == 1 and cache.stats()["free_slots"] == 1
    # an entry somebody else still holds, or one off the path, is not taken over
    cache.mark_ready(second)
    other, _ = cache.match(turn2 + [1])
    third = cache.insert_entry(turn2 + ids(8, salt=5), via=second)
    assert third.store_slot != second.store_slot and cache.stats()["entries"] == 2
    cache.release(other)
    stranger = cache.insert_entry(ids(9, salt=50), via=second)  # evicts by LRU instead: second is unpinned now
    assert stranger is not None and cache.stats()["entries"] == 2


def test_eviction_frees_the_row_with_the_pages_and_a_pinned_entry_stays():
    cache, dropped = index(slots=2)
    a, b = cache.insert_entry(ids(9, 1)), cache.insert_entry(ids(9, 2))
    cache.mark_ready(a)
    cache.mark_ready(b)
    pinned, _ = cache.match(ids(9, 1))  # a is pinned, and the more recently used
    c = cache.insert_entry(ids(9, 3))
    assert dropped == [b] and c.store_slot == b.store_slot  # the row went with the entry, to the new one
    assert cache.insert_entry(ids(9, 4)) is not None and dropped == [b, c]  # c unpinned: LRU; a stays
    cache.release(pinned)
    assert cache.evict_lru() and dropped[-1] is a


def test_discard_drops_an_entry_an_admission_could_not_complete():
    cache, dropped = index(slots=1)
    ent = cache.insert_entry(ids(9))
    cache.discard(ent)
    assert dropped == [ent] and cache.stats() == {"entries": 0, "free_slots": 1, "cached_rows": 0, "capacity_rows": 64}
    cache.discard(ent)  # gone already: nothing happens
    assert cache.stats()["free_slots"] == 1 and cache.insert_entry(ids(9)) is not None


# --------------------------------------------------------------------------- #
# The engine


BASE = dict(
    model_config_name="solaropen2-debug", max_batch_size=2, max_seq_len=512, prefill_chunk=64,
    tensor_parallelism=1, decode_block=4, decode_runahead=1, page_size=16, prefix_cache_enable="auto",
    prefix_cache_slots=2, dtype="float32", paged_kernel="off",
)


def build(**overrides):
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    return LLMEngine(EngineConfig(**dict(BASE, **overrides)))


@pytest.fixture(scope="module")
def engine():
    eng = build()
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def cold_engine():
    eng = build(prefix_cache_enable="off")
    yield eng
    eng.shutdown()


def greedy(n=6):
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    return SamplingParams(temperature=0.0, max_tokens=n)


def counters():
    from generativeaiexamples_tpu.utils import metrics as metrics_mod

    out = {}
    for line in metrics_mod.get_registry().render().splitlines():
        if line.startswith("genai_engine_") and " " in line:
            k, v = line.rsplit(" ", 1)
            out[k] = float(v)
    return out


def probe(eng, prompt):
    """The entry ``prompt`` would enter through (None: a miss), left unpinned."""
    m = eng._prefix.match(prompt)
    if m is not None:
        eng._prefix.release(m[0])
    return m[0] if m is not None else None


def state_rows(eng, row):
    """Row ``row`` of every fixed-state leaf, on the host."""
    return [np.asarray(x[row]) for key in eng._family.state_row_keys for x in jax.tree.leaves(eng._cache[key])]


def test_the_store_rows_stand_behind_the_slots_in_the_same_arrays(engine, cold_engine):
    from generativeaiexamples_tpu.models import solaropen2 as m

    assert engine._state_store_rows == 2 and engine._prefix.stateful and engine._copy_state_fn is not None
    assert all(x.shape[0] == 2 + 2 for key in m.STATE_ROW_KEYS for x in engine._cache[key])
    assert cold_engine._state_store_rows == 0 and all(x.shape[0] == 2 for key in m.STATE_ROW_KEYS for x in cold_engine._cache[key])
    assert counters()["genai_engine_fixed_state_bytes"] in (2 * m.fixed_state_bytes_per_slot(m.PRESETS["solaropen2-debug"]),
                                                            4 * m.fixed_state_bytes_per_slot(m.PRESETS["solaropen2-debug"]))


def test_a_hit_gives_the_tokens_and_the_state_of_the_cold_run_bit_for_bit_and_the_references_best(engine, cold_engine):
    """The same prompt three ways: cold on an engine without the store,
    cold on the engine with it (which saves the state at 192 of 200
    tokens, between the third chunk and the last), and through the hit
    (the state restored, 8 tokens prefilled). Tokens equal; the slot's
    recurrent state after the request equal bit for bit; every token
    the plain reference's best. Then a second turn that enters through
    the first's entry and takes its row over."""
    from generativeaiexamples_tpu.engine import dispatch_timeline
    from tests.test_solaropen2 import reference_logits

    rng = np.random.default_rng(7)
    prompt = [int(t) for t in rng.integers(3, 250, size=200)]
    want = list(cold_engine.iter_ids(prompt, greedy(), timeout=600))
    cold_state = state_rows(cold_engine, 0)
    before, cursor = counters(), dispatch_timeline.cursor()
    first = list(engine.iter_ids(prompt, greedy(), timeout=600))
    mid = counters()
    second = list(engine.iter_ids(prompt, greedy(), timeout=600))
    after = counters()
    assert first == want and second == want
    grew = lambda a, b, k: b.get(k, 0.0) - a.get(k, 0.0)  # noqa: E731
    assert grew(before, mid, "genai_engine_prefix_state_saves_total") == 1 and grew(before, mid, "genai_engine_prefix_cache_misses_total") == 1
    assert grew(mid, after, "genai_engine_prefix_state_restores_total") == 1 and grew(mid, after, "genai_engine_prefix_state_saves_total") == 0
    assert grew(mid, after, "genai_engine_prefix_cache_tokens_reused_total") == 192
    assert grew(mid, after, "genai_engine_state_slot_resets_total") == 0  # the restore stands in place of the reset
    row_bytes = engine._state_row_bytes
    assert grew(before, after, "genai_engine_prefix_state_bytes_total") == 2 * row_bytes
    assert after["genai_engine_prefix_state_rows_in_use"] == 1
    for a, b in zip(cold_state, state_rows(engine, 0)):
        np.testing.assert_array_equal(a, b)
    ref = reference_logits(engine.params, prompt + want)
    assert max(float(ref[199 + j].max() - ref[199 + j][t]) for j, t in enumerate(want)) < 1e-3
    spans = [s for s in dispatch_timeline.spans_since(cursor)[0] if s.get("kind", "").startswith("prefix_state_")]
    assert [s["kind"] for s in spans] == ["prefix_state_save", "prefix_state_restore"]
    assert all(s["bytes"] == row_bytes and s["depth_tokens"] == 192 and s["store_row"] == spans[0]["store_row"] for s in spans)
    # the next turn: the first's prompt and answer, then more; it enters at 192 and saves at 320, in the same row
    turn2 = prompt + want + [int(t) for t in rng.integers(3, 250, size=130)]
    want2 = list(cold_engine.iter_ids(turn2, greedy(), timeout=600))
    row = probe(engine, prompt)
    assert list(engine.iter_ids(turn2, greedy(), timeout=600)) == want2
    stats = engine._prefix.stats()
    assert stats["entries"] == 1 and stats["cached_rows"] == 320 and probe(engine, prompt) is None
    deeper = probe(engine, turn2)
    assert deeper.length == 320 and deeper.store_slot == row.store_slot


def test_eviction_releases_the_entrys_pages_and_the_row_serves_the_next(engine):
    """Two tickets: a third conversation evicts the least recently used
    entry; its pages go back to the allocator and its row is the new
    entry's. The evicted prompt then enters cold, with the right answer."""
    rng = np.random.default_rng(8)
    prompts = [[int(t) for t in rng.integers(3, 250, size=140)] for _ in range(3)]
    answers = [list(engine.iter_ids(p, greedy(3), timeout=600)) for p in prompts[:2]]
    held = engine.paged_stats()["pages_in_use"] if "pages_in_use" in engine.paged_stats() else None
    rows = {probe(engine, p).store_slot for p in prompts[:2]}
    evictions = counters()["genai_engine_prefix_cache_evictions_total"]
    list(engine.iter_ids(prompts[2], greedy(3), timeout=600))
    assert counters()["genai_engine_prefix_cache_evictions_total"] >= evictions + 1
    assert engine._prefix.stats()["entries"] == 2 and engine._prefix.stats()["free_slots"] == 0
    newest = probe(engine, prompts[2])
    assert newest.store_slot in rows and len(newest.pages) == 128 // 16
    if held is not None:
        assert engine.paged_stats()["pages_in_use"] == held  # 8 pages dropped, 8 donated
    gone = [p for p in prompts[:2] if probe(engine, p) is None]
    assert len(gone) == 1
    assert list(engine.iter_ids(gone[0], greedy(3), timeout=600)) == answers[prompts.index(gone[0])]


def test_a_failed_admission_leaves_no_entry(engine, monkeypatch):
    """The extend program fails on the chunk AFTER the save: the entry
    the wave inserted is dropped, its row and its pages freed, and the
    request ends with the error."""
    rng = np.random.default_rng(9)
    prompt = [int(t) for t in rng.integers(3, 250, size=200)]
    real, calls = engine._extend_fn, []

    def failing(*args, **kw):
        calls.append(1)
        if len(calls) == 4:  # chunks 0-2 run and the state is saved at 192; the tail's chunk fails
            raise RuntimeError("injected extend failure")
        return real(*args, **kw)

    before = engine._prefix.stats()
    pages = engine._kv_alloc.stats()  # (no request is live: what is in use is the entries')
    saves = counters()["genai_engine_prefix_state_saves_total"]
    monkeypatch.setattr(engine, "_extend_fn", failing)
    with pytest.raises(Exception, match="LLM engine failed|injected extend failure"):
        list(engine.iter_ids(prompt, greedy(3), timeout=600))
    monkeypatch.setattr(engine, "_extend_fn", real)
    assert counters()["genai_engine_prefix_state_saves_total"] == saves + 1  # the copy was enqueued ...
    assert probe(engine, prompt) is None  # ... and the entry taken back
    after = engine._prefix.stats()
    assert after["entries"] <= before["entries"] and after["free_slots"] >= before["free_slots"]
    assert engine._kv_alloc.stats()["pages_in_use"] <= pages["pages_in_use"]  # the slot's and the new entry's all back (an evicted entry's too)
    # the engine serves on
    assert len(list(engine.iter_ids(prompt, greedy(3), timeout=600))) == 3


@pytest.mark.parametrize("name", ["afmoe-debug", "phi4flash-debug", "glm5next-debug", "gigachat35-debug"])
def test_a_family_that_registers_no_state_rows_is_refused_the_store(name):
    """The four older fixed-state families name no ``state_row_keys``
    (a ring's rows are no state at one depth; the two delta-rule
    families' steps walk every row of their arrays): refused as before,
    in words that say what is missing."""
    with pytest.raises(ValueError, match="registers state_row_keys.*this family registers none"):
        build(model_config_name=name, max_seq_len=256)


def test_snapshots_and_speculation_stay_refused_for_the_family(engine):
    from generativeaiexamples_tpu.engine.request_snapshot import SnapshotError

    with pytest.raises(ValueError, match="speculative verify"):
        build(spec_decode_enable="on")
    with pytest.raises(SnapshotError, match="fixed per-slot state"):
        engine.drain()
