"""The schedule builder: the seed shuffles, it does not resize."""
import json
import os
import random

import pytest

from perfbench import loadgen

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "perfbench")
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")) if f.endswith(".json"))
SEEDS = (1, 2_147_483_659)  # the second is above 2**31, as the driver's are


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json"), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_give_the_same_multiset_of_sizes_in_another_order(name):
    t = mix(name)
    a, b = (loadgen.build_deck(t, s) for s in SEEDS)
    key = lambda d: [(x["question_bytes"], x["max_tokens"]) for x in d]  # noqa: E731
    assert sorted(key(a)) == sorted(key(b))
    assert key(a) != key(b)
    assert [x["question"] for x in a] != [x["question"] for x in b]
    assert all(len(x["question"].encode()) == x["question_bytes"] for x in a + b)
    assert len({x["question"] for x in a}) == len(a)  # distinct prompts: no whole-prompt cache hit
    again = loadgen.build_deck(t, SEEDS[0])
    assert [x["question"] for x in again] == [x["question"] for x in a]  # same seed, same inputs


@pytest.mark.parametrize("name", MIXES)
def test_every_pair_of_sizes_has_an_equal_share(name):
    t = mix(name)
    deck = loadgen.build_deck(t, 5)
    counts = {}
    for x in deck:
        counts[(x["question_bytes"], x["max_tokens"])] = counts.get((x["question_bytes"], x["max_tokens"]), 0) + 1
    assert len(counts) == len(t["question_bytes"]) * len(t["max_tokens"])
    assert len(set(counts.values())) == 1


@pytest.mark.parametrize("name", [m for m in MIXES if "corpus" in mix(m)])
def test_corpus_chunks_come_from_the_fixed_multiset(name):
    t = mix(name)
    a, b = (loadgen.build_corpus(t, s) for s in SEEDS)
    assert sorted(len(x[1]) for x in a) == sorted(len(x[1]) for x in b)
    assert [x[1] for x in a] != [x[1] for x in b]
    sizes = t["corpus"]["chunk_bytes"]
    per = t["corpus"]["documents"] // len(sizes)
    for n in sizes:
        assert sum(1 for x in a if len(x[1]) == n) in (per, per + 1)
    # one document is one chunk: words joined by single spaces survive the
    # chain's whitespace splitter byte for byte
    assert all(" ".join(x[1].split()) == x[1] and len(x[1].split()) < 510 for x in a)


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_every_prefix_of_the_deck_carries_the_pairs_in_equal_shares(name, seed):
    """A run consumes only the head of the deck: whatever it takes, no
    pair of sizes is ahead of another by more than one."""
    t = mix(name)
    deck = loadgen.build_deck(t, seed)
    pairs = len(t["question_bytes"]) * len(t["max_tokens"])
    assert len(deck) % pairs == 0
    counts = {}
    for i, x in enumerate(deck, 1):
        key = (x["question_bytes"], x["max_tokens"])
        counts[key] = counts.get(key, 0) + 1
        assert max(counts.values()) - (min(counts.values()) if len(counts) == pairs else 0) <= 1
        if i % pairs == 0:
            assert len(set(counts.values())) == 1 and len(counts) == pairs


def test_open_loop_gaps_are_one_multiset_for_every_seed():
    t = mix("rehearsal_poisson")
    a, b = (loadgen.arrival_times(t, s, 50.0) for s in SEEDS)
    gaps = lambda xs: sorted(round(y - x, 9) for x, y in zip([0.0] + xs, xs))  # noqa: E731
    assert gaps(a) == gaps(b) and a != b and len(a) == int(t["rate_per_s"] * 50.0)
    assert all(x < y for x, y in zip(a, a[1:]))


@pytest.mark.parametrize("n", [1, 7, 64, 333])
def test_text_has_exactly_the_bytes_asked(n):
    text = loadgen.text_of_bytes(random.Random(n), n, "lead in")
    assert len(text.encode()) == n and text == text.strip()


def test_cell_traffic_matches_the_issue():
    chat = mix("chat_decode")
    assert chat["kind"] == "closed" and chat["clients"] == 64
    assert chat["request"] == {"use_knowledge_base": False, "temperature": 0.1, "top_p": 0.1}
    assert chat["max_tokens"] == [256, 384, 512] and chat["question_bytes"] == [128, 256, 384]
    # the ramp spreads the clients over about one mean request and lets the
    # longest first request end before its cap (PERF.md section 6)
    assert chat["ramp"] == {"expected_request_s": 24.0, "cap_s": 60.0}
