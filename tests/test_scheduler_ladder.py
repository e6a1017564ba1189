"""Scheduler shape-ladder property tests (CPU-only, no engine build).

The prefix-cache admission path leans on these invariants: cached
prefixes are chunk-aligned (`prefill_bucket` alignment), fetch copies
use the `attention_window` rungs, and warm waves still pad up the
`wave_sizes` ladder under the `max_wave_rows` token budget. The ladders
are one frozen dataclass of ten scalars (engine/scheduler/shapes.py
``ShapePlan``), so a plain instance (no jax, no weights) exercises them
across many configs.
"""
import dataclasses

import pytest

from generativeaiexamples_tpu.engine.scheduler.shapes import ShapePlan


def make_sched(chunk=16, max_seq=128, slots=8, budget=16384, page=None, packed=False,
               fixed_state=False, extend_reads_window=True):
    return ShapePlan(
        prefill_chunk=chunk,
        page_size=page or min(chunk, 128),
        max_seq_len=max_seq,
        num_slots=slots,
        prefill_wave_tokens=budget,
        decode_block=8,
        fixed_state=fixed_state,
        packed=packed,  # a family with a walk over a packed token axis (llama)
        extend_reads_window=extend_reads_window,
        page_kernel=False,
    )


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
        b = eng.prefill_bucket(n)
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
    sizes = eng.wave_sizes()
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
    sizes = eng.wave_sizes()
    for n in range(1, cfg["slots"] + 1):
        p = eng.wave_pad(n)
        assert p >= n
        assert p in sizes
        # smallest rung >= n
        assert all(s < n for s in sizes if s < p)


@pytest.mark.parametrize("cfg", GRID)
def test_max_wave_rows_budget(cfg):
    eng = make_sched(**cfg)
    budget = cfg.get("budget", 16384)
    prev = None
    for chunk in range(cfg["chunk"], cfg["max_seq"] + 1, cfg["chunk"]):
        r = dataclasses.replace(eng, prefill_chunk=chunk).max_wave_rows()
        assert 1 <= r <= cfg["slots"]
        assert r * chunk <= budget or r == 1  # bounded activation footprint
        if prev is not None:
            assert r <= prev  # monotone non-increasing in the chunk
        prev = r
    if cfg["chunk"] * cfg["slots"] <= budget:
        assert eng.max_wave_rows() == cfg["slots"]
    assert dataclasses.replace(eng, fixed_state=True).max_wave_rows() == 1


@pytest.mark.parametrize("cfg", GRID)
def test_attention_window_rungs(cfg):
    eng = make_sched(**cfg)
    cap = cfg["max_seq"]
    prev = 0
    for needed in range(0, cap + 1, max(1, cfg["chunk"] // 2)):
        w = eng.attention_window(needed)
        assert w >= min(needed, cap)  # covers every live position
        assert w <= cap
        # power-of-two rung (or clamped at capacity)
        assert w == cap or (w & (w - 1)) == 0
        assert w >= prev  # monotone
        prev = w


# --------------------------------------------------------------------- //
# The shape of one extend dispatch, from what the chunk holds
# (chunk_rung). A family with a packed walk: ONE token ladder
# (packed_rungs). Any other: rows x widths, the two ladders. And the
# executable set of each.


@pytest.mark.parametrize(
    "chunk,page,widths",
    [
        (512, 128, [128, 512]),  # both benchmark cells
        (64, 16, [16, 64]),  # chip_smoke's debug preset
        (16, 16, [16]),  # one page a chunk: one rung
        (2048, 128, [128, 512, 2048]),
        (256, 128, [256]),  # a quarter of the chunk would be under a page
        (512, 64, [128, 512]),  # whole pages; powers of four, no finer
    ],
)
def test_width_ladder(chunk, page, widths):
    eng = make_sched(chunk=chunk, max_seq=4096, page=page)
    assert eng.chunk_widths() == widths
    assert all(w % page == 0 for w in widths)


SHAPE_CASES = [
    # (valid of the wave's rows, real rows) -> (live rows, rows dispatched, width)
    ("tail_on_one_row", [0, 71, 0, 0], 4, ([1], 1, 128)),
    ("tail_on_two_rows", [71, 0, 0, 71], 4, ([0, 3], 4, 128)),
    ("full_chunk", [512, 330, 458, 512], 4, ([0, 1, 2, 3], 4, 512)),
    ("tail_past_the_narrow_rung", [0, 129, 0, 0], 4, ([1], 1, 512)),
    ("tail_of_a_page_exactly", [128, 0, 0, 0], 4, ([0], 1, 128)),
    ("one_token", [0, 0, 1, 0], 4, ([2], 1, 128)),
    # a wave of three pads to four: the fourth row is a copy of row 0 and never live
    ("padding_row_is_not_live", [71, 0, 0, 71], 3, ([0], 1, 128)),
    ("padding_rows_only", [0, 0, 0, 71], 3, None),
    ("empty_chunk", [0, 0, 0, 0], 4, None),
    ("one_row_wave", [71], 1, ([0], 1, 128)),
]


@pytest.mark.parametrize("name,valid,n_real,expect", SHAPE_CASES, ids=[c[0] for c in SHAPE_CASES])
def test_chunk_rung_follows_what_the_chunk_holds(name, valid, n_real, expect):
    eng = make_sched(chunk=512, max_seq=4096, slots=64, budget=2048)  # chat_decode_7b's geometry
    assert eng.chunk_rung(valid, n_real) == expect


def test_chunk_rung_of_a_fixed_state_family_is_one_row():
    eng = make_sched(chunk=512, max_seq=4096, slots=64, budget=2048, fixed_state=True)
    assert eng.chunk_rung([71], 1) == ([0], 1, 128)
    assert sorted({n for n, _, _ in eng.extend_signatures()}) == [1]
    assert len(eng.extend_signatures()) == 4 + 1  # four windows at 512, capacity at 128


@pytest.mark.parametrize("cfg", GRID + [dict(chunk=512, max_seq=4096, slots=64, budget=2048, page=128),
                                        dict(chunk=64, max_seq=256, slots=4, page=16)])
def test_extend_signatures_are_exactly_what_the_rule_can_produce(cfg):
    """Every (rows, width, window) the rule gives for any chunk of any
    wave is in the warmed set, and the set holds nothing else."""
    eng = make_sched(**cfg)
    C = cfg["chunk"]
    cap = eng.max_wave_rows()
    reachable = set()
    for k in range(-(-cfg["max_seq"] // C)):
        for need in sorted({1, *eng.chunk_widths(), *(w + 1 for w in eng.chunk_widths() if w < C)}):
            for n_live in range(1, cap + 1):
                live, rows, width = eng.chunk_rung([need] * n_live, n_live)
                assert len(live) == n_live <= rows <= cap and rows in eng.wave_sizes() + [cap]
                assert width >= need and width in eng.chunk_widths()
                reachable.add((rows, width, eng.extend_window(k, width)))
    assert reachable == set(eng.extend_signatures())
    # a narrow chunk has ONE window, capacity; a full one the rung that covers it
    for rows, width, window in reachable:
        assert window == cfg["max_seq"] if width < C else window >= min(C, cfg["max_seq"])


RECT_AT_THE_BENCHMARK_GEOMETRY = [
    (1, 128, 4096), (1, 512, 512), (1, 512, 1024), (1, 512, 2048), (1, 512, 4096),
    (4, 128, 4096), (4, 512, 512), (4, 512, 1024), (4, 512, 2048), (4, 512, 4096),
]


def test_executable_count_at_the_benchmark_geometry():
    """Mistral's cell as rectangles (before the packed axis): rows
    {1, 4} x four windows at 512 and one program a row rung at 128: 10.
    Packed: one program a token rung, 8: fewer, as ISSUE 41 asks, with
    no allowance: no family has a prefill program beside its extends."""
    rect = make_sched(chunk=512, max_seq=4096, slots=64, budget=2048, page=128)
    assert rect.extend_signatures() == RECT_AT_THE_BENCHMARK_GEOMETRY
    eng = make_sched(chunk=512, max_seq=4096, slots=64, budget=2048, page=128, packed=True)
    assert eng.packed_rungs() == [128, 256, 384, 512, 768, 1024, 1536, 2048]
    assert eng.extend_signatures() == [(4, t, 4096) for t in eng.packed_rungs()]
    assert eng.packed_windows() == [512, 1024, 2048, 4096]
    assert len(eng.extend_signatures()) <= len(rect.extend_signatures())


PACKED_GRID = GRID + [dict(chunk=512, max_seq=4096, slots=64, budget=2048, page=128),
                      dict(chunk=64, max_seq=256, slots=4, page=16),
                      dict(chunk=32, max_seq=96, slots=4, page=8)]


@pytest.mark.parametrize("cfg", PACKED_GRID)
def test_packed_rungs_are_the_one_ladder(cfg):
    """Whole pages at 1 and 1.5 times the powers of two, from one page to
    the most a chunk of a wave can hold; so every token count that can
    arise has a rung, and under a third of that rung is padding (but
    for the first page)."""
    eng = make_sched(packed=True, **cfg)
    page, C = eng.page_size, cfg["chunk"]
    top = eng.max_wave_rows() * C
    rungs = eng.packed_rungs()
    assert rungs == sorted(set(rungs)) and rungs[0] == min(page, top) and rungs[-1] == top
    assert all(t % page == 0 or t == top for t in rungs)
    for t in rungs[:-1]:
        m = t // page
        assert m & (m - 1) == 0 or (m % 3 == 0 and (m // 3) & (m // 3 - 1) == 0)
    for a, b in zip(rungs, rungs[1:]):
        assert b <= 2 * a and (b - a - 1) * 3 <= b or b - a <= page  # a need of a + 1 pads under a third of b
    # the count grows with the logarithm of the wave
    assert len(rungs) <= 2 * max(1, (top // page)).bit_length()


@pytest.mark.parametrize("cfg", PACKED_GRID)
def test_packed_signatures_are_exactly_what_the_rule_can_produce(cfg):
    """Every chunk of every wave of a packed family lands on a warmed
    (rows, T, capacity) and names a window the program holds; the set
    holds no other."""
    eng = make_sched(packed=True, **cfg)
    C = cfg["chunk"]
    cap = eng.max_wave_rows()
    windows = eng.packed_windows()
    assert windows == sorted(set(windows)) and windows[-1] == cfg["max_seq"]
    reachable = set()
    for n_live in range(1, cap + 1):
        for need in {1, C // 2 + 1, C}:
            live, rows, width = eng.chunk_rung([need] * n_live + [0] * (cap - n_live), cap)
            assert live == list(range(n_live)) and rows == 1
            assert width >= n_live * need and width in eng.packed_rungs()
            assert all(t < n_live * need for t in eng.packed_rungs() if t < width)  # the least that holds them
            reachable.add((cap, width, cfg["max_seq"]))
    for t in eng.packed_rungs():  # every rung is some wave's: t tokens over the fewest rows
        rows = -(-t // C)
        spread = [t // rows + (1 if i < t % rows else 0) for i in range(rows)]
        assert eng.chunk_rung(spread, rows) == (list(range(rows)), 1, t)
        reachable.add((cap, t, cfg["max_seq"]))
    assert reachable == set(eng.extend_signatures())
    for k in range(-(-cfg["max_seq"] // C)):
        assert eng.extend_window(k, C) in windows
        assert eng.extend_window(k, C) >= min((k + 1) * C, cfg["max_seq"])


PACKED_SHAPE_CASES = [
    # (valid of the wave's rows, real rows) -> (live rows, 1 axis, T)
    ("tail_on_one_row", [0, 71, 0, 0], 4, ([1], 1, 128)),
    ("tail_on_two_rows", [71, 0, 0, 71], 4, ([0, 3], 1, 256)),
    ("tails_on_three_rows", [71, 71, 0, 71], 4, ([0, 1, 3], 1, 256)),
    ("tails_on_four_rows", [71, 71, 71, 71], 4, ([0, 1, 2, 3], 1, 384)),
    ("the_deck_in_one_wave", [512, 330, 458, 512], 4, ([0, 1, 2, 3], 1, 2048)),
    ("one_short_prompt", [330, 0, 0, 0], 4, ([0], 1, 384)),
    ("one_prompt_under_a_chunk", [458, 0, 0, 0], 4, ([0], 1, 512)),
    ("two_short_prompts", [330, 0, 330, 0], 4, ([0, 2], 1, 768)),
    ("three_short_prompts", [330, 330, 330, 0], 4, ([0, 1, 2], 1, 1024)),
    ("a_page_exactly", [128, 0, 0, 0], 4, ([0], 1, 128)),
    ("one_token", [0, 0, 1, 0], 4, ([2], 1, 128)),
    # a wave of three: the fourth row is a copy of row 0 and never live
    ("padding_row_is_not_live", [71, 0, 0, 71], 3, ([0], 1, 128)),
    ("padding_rows_only", [0, 0, 0, 71], 3, None),
    ("empty_chunk", [0, 0, 0, 0], 4, None),
]


@pytest.mark.parametrize("name,valid,n_real,expect", PACKED_SHAPE_CASES, ids=[c[0] for c in PACKED_SHAPE_CASES])
def test_packed_chunk_rung_follows_the_live_tokens(name, valid, n_real, expect):
    eng = make_sched(chunk=512, max_seq=4096, slots=64, budget=2048, packed=True)  # chat_decode_7b's geometry
    assert eng.chunk_rung(valid, n_real) == expect


# What the three fixed-state families' cells are sent, pinned to the
# parent's (443b2dc): no packed walk registered, one row a wave, the
# rectangles of before. (configuration, engine geometry of its cell in
# perfbench/configs) -> extend_signatures()
FIXED_STATE_CELLS = {
    "phi4flash": (dict(chunk=512, max_seq=4096, slots=64, budget=512, page=128),
                  [(1, 128, 4096), (1, 512, 512), (1, 512, 1024), (1, 512, 2048), (1, 512, 4096)]),
    "glm5next": (dict(chunk=512, max_seq=8192, slots=64, budget=512, page=128),
                 [(1, 128, 8192), (1, 512, 8192)]),
    "gigachat35": (dict(chunk=512, max_seq=8192, slots=64, budget=512, page=128),
                   [(1, 128, 8192), (1, 512, 8192)]),
}


@pytest.mark.parametrize("name", sorted(FIXED_STATE_CELLS))
def test_fixed_state_families_keep_their_rectangles(name):
    from generativeaiexamples_tpu.models import registry

    family = registry.families()[name]
    assert family.fixed_state and family.extend_packed is None
    cfg, signatures = FIXED_STATE_CELLS[name]
    # as LLMEngine.__init__ reads them from the registry entry
    eng = make_sched(
        fixed_state=bool(family.fixed_state),
        extend_reads_window=bool(family.extend_reads_window),
        packed=family.extend_packed is not None, **cfg,
    )
    assert eng.extend_signatures() == signatures
    assert eng.chunk_widths() == [128, 512]
    for valid, expect in (([71], ([0], 1, 128)), ([128], ([0], 1, 128)), ([129], ([0], 1, 512)),
                          ([512], ([0], 1, 512)), ([0], None)):
        assert eng.chunk_rung(valid, 1) == expect
    assert eng.max_wave_rows() == 1


def test_only_the_dense_family_registers_a_packed_walk():
    from generativeaiexamples_tpu.models import registry

    assert {n for n, f in registry.families().items() if f.extend_packed is not None} == {"llama"}


@pytest.mark.parametrize("chunk,max_seq", [(512, 8192), (64, 256)])
def test_a_family_that_reads_no_window_has_one_extend_program_a_width(chunk, max_seq):
    """``extend_reads_window=False`` (models/registry.py): every chunk is
    dispatched at capacity, so warm-up builds one program a width rung,
    not one a power-of-two window."""
    eng = make_sched(chunk=chunk, max_seq=max_seq, slots=64, fixed_state=True)
    windows = {w for _, _, w in eng.extend_signatures()}
    assert len(windows) > 1
    eng = dataclasses.replace(eng, extend_reads_window=False)
    assert {w for _, _, w in eng.extend_signatures()} == {max_seq}
    assert len(eng.extend_signatures()) == len(eng.chunk_widths())


@pytest.mark.parametrize("packed", [False, True], ids=["rectangle", "packed"])
@pytest.mark.parametrize("cfg", PACKED_GRID)
def test_every_rung_of_a_one_chunk_wave_is_warmed(cfg, packed):
    """The one admission path: a wave whose prompts all fit one chunk is
    chunk 0 of the walk and no other, and whatever mix of lengths and
    rows it holds, its dispatch is one of ``extend_signatures()``."""
    eng = make_sched(packed=packed, **cfg)
    C, cap = cfg["chunk"], eng.max_wave_rows()
    warmed = set(eng.extend_signatures())
    lengths = sorted({1, eng.page_size, eng.page_size + 1, C // 2 + 1, C} & set(range(1, min(C, cfg["max_seq"]) + 1)))
    for n_real in range(1, cap + 1):
        n_padded = cap if packed else min(eng.wave_pad(n_real), cap)
        for longest in lengths:
            for rest in (1, longest):
                valid = [longest] + [rest] * (n_real - 1) + [longest] * (n_padded - n_real)
                assert eng.prefill_bucket(max(valid)) == min(C, cfg["max_seq"])  # one chunk, offset zero
                live, rows, width = eng.chunk_rung(valid, n_real)
                assert live == list(range(n_real))
                if packed:  # the window is an operand of the one program a token rung
                    assert (cap, width, cfg["max_seq"]) in warmed
                    assert eng.extend_window(0, C) in eng.packed_windows()
                else:
                    assert (rows, width, eng.extend_window(0, width)) in warmed


@pytest.mark.parametrize("cfg", GRID)
def test_decode_window_is_a_warmed_rung(cfg):
    """A decode block's window is a rung warm-up walks: every
    power-of-two rung on the gather, capacity alone under the page
    kernel."""
    eng = make_sched(**cfg)
    rungs = eng.window_rungs()
    assert rungs == sorted(set(rungs)) and rungs[-1] == cfg["max_seq"]
    kernel = dataclasses.replace(eng, page_kernel=True)
    for max_pos in range(0, cfg["max_seq"], max(1, cfg["chunk"] // 2)):
        w = eng.decode_window(max_pos)
        assert w in rungs and w >= min(max_pos + eng.decode_block, cfg["max_seq"])
        assert kernel.decode_window(max_pos) == cfg["max_seq"]
