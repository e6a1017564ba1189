"""``perfbench/gap_readers.py``: the share of one part in the tail of the
gaps between stream hand-offs, over a recorded span list
(``perfbench/fixtures/handoff_gap_spans.json``); the metric file that
names it and the manifest's entry, found by name; and a parent's spans
(no such field) reading nothing."""
import json
import os

import pytest

from perfbench import gap_readers, readers, run
from tests.perfbench.manifest_entries import entries_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
NAME = "gap_tail_extend_share"
CELLS = ["chat_decode_7b", "reason_decode_phi4flash", "doc_reason_glm53flash", "doc_reason_gigachat35",
         "doc_reason_trinitymini", "chat_sessions_solaropen2", "agent_sessions_kimik25", "agent_files_minimaxm3"]

with open(os.path.join(BENCH, "fixtures", "handoff_gap_spans.json"), encoding="utf-8") as _fh:
    RECORDED = json.load(_fh)
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _fh:
    MANIFEST = json.load(_fh)
with open(os.path.join(BENCH, "layer_metrics", NAME + ".json"), encoding="utf-8") as _fh:
    SPEC = json.load(_fh)


def _ctx(block, spans=None):
    return {"spans": RECORDED[f"block{block}"] if spans is None else spans,
            "window": (0.0, 51.0), "config": {"engine": {"decode_block": block}}}


def _read(ctx, **params):
    roots = [os.path.join(ROOT, p) for p in MANIFEST["paths"]]
    return readers.resolve(SPEC["reader"], roots)(ctx, dict(SPEC["params"], **params))


# decode_block -> (the percentile of the gaps between hand-offs that the client's p99.5
# of ALL frame gaps reads, the gap at it, the spans at or above it as (rows, gap, extend))
TAILS = {
    # 100 rows of hand-offs: 80 at 0.150, 12 at 0.200, then 4 + 4: the 96th row is the first of 0.330
    8: (96.0, 0.330, [(4, 0.330, 0.180), (4, 0.400, 0.224)]),
    # 80 rows at 0.020, 8, 6, 3, 2, 1: the 99th row is the second of the two at 0.080
    2: (99.0, 0.080, [(2, 0.080, 0.060), (1, 0.120, 0.088)]),
}


@pytest.mark.parametrize("block", sorted(TAILS))
def test_the_judged_percentile_of_the_gaps_between_handoffs_by_decode_block(block):
    q, cut, tail = TAILS[block]
    params = SPEC["params"]
    assert gap_readers.handoff_percentile(_ctx(block), params) == pytest.approx(q)
    spans = gap_readers.gap_spans(_ctx(block), params)
    assert sum(s["handoff_rows"] for s in spans) == 100
    assert {s["kind"] for s in spans} <= set(params["kinds"])
    got_cut, got_tail = gap_readers.weighted_tail(spans, q)
    assert got_cut == pytest.approx(cut)
    assert gap_readers.gap_tail_ms(_ctx(block), params) == pytest.approx(cut * 1000.0)
    assert sorted((s["handoff_rows"], s["gap_s"], s["gap_extend_s"]) for s in got_tail) == sorted(tail)


@pytest.mark.parametrize("block", sorted(TAILS))
def test_metric_file_reads_the_share_of_the_tail_by_hand_arithmetic(block):
    _, _, tail = TAILS[block]
    want = 100.0 * sum(rows * extend for rows, _, extend in tail) / sum(rows * gap for rows, gap, _ in tail)
    assert _read(_ctx(block)) == pytest.approx(want, rel=1e-9)
    assert 0.0 < want < 100.0
    # the other parts through the same reader (for the next benchmark PR's data files)
    parts = [_read(_ctx(block), part=p) for p in
             ("gap_decode_s", "gap_extend_s", "gap_other_s", "gap_starved_s", "gap_host_s")]
    assert sum(parts) == pytest.approx(100.0)


def test_the_two_block_sizes_read_different_tails_of_the_same_spans():
    """The same window read as decode_block 8 takes the gaps from the 96th
    percentile up, as 2 from the 99th: more of the ordinary gaps in the first."""
    spans = RECORDED["block2"]
    wide, narrow = _read(_ctx(8, spans)), _read(_ctx(2, spans))
    assert wide == pytest.approx(100.0 * (3 * 0.044 + 2 * 0.060 + 0.088) / (3 * 0.060 + 2 * 0.080 + 0.120))
    assert narrow == pytest.approx(100.0 * (2 * 0.060 + 0.088) / (2 * 0.080 + 0.120))


def test_the_traced_stretch_and_the_profilers_start_are_left_out():
    """A traced run's window ends with ``trace.window_s`` traced seconds, and the
    profiler's start holds the host just before them: that gap is the harness's."""
    spans = [dict(s, t_wall=1000.0 + 0.2 * i) for i, s in enumerate(RECORDED["block2"])]  # 20 spans over 3.8 s
    ordinary = _read(_ctx(2, spans))
    stall = dict(spans[-1], gap_s=0.9, gap_extend_s=0.9, gap_host_s=0.0, handoff_rows=8, t_wall=1002.6)
    with_stall = spans[:13] + [stall] + spans[13:]
    assert _read(_ctx(2, with_stall)) > ordinary + 10.0  # an untraced window keeps it
    traced = dict(_ctx(2, with_stall), trace={"window_s": 1.0, "devices": 1})
    kept = gap_readers.gap_spans(traced, SPEC["params"])
    assert stall not in kept and max(s["t_wall"] for s in kept) < 1003.8 - 1.0 - gap_readers.TRACE_START_MARGIN_S
    # what is left is the spans before 1002.3: the reading is theirs alone
    assert _read(traced) == pytest.approx(_read(_ctx(2, [s for s in spans if s["t_wall"] < 1002.3])))
    # no trace, or spans without a clock: every span is kept
    assert gap_readers.gap_spans(dict(_ctx(2, with_stall), trace=None), SPEC["params"]) == \
        gap_readers.gap_spans(_ctx(2, with_stall), SPEC["params"])
    assert len(gap_readers.gap_spans(dict(_ctx(2), trace={"window_s": 1.0}), SPEC["params"])) == 15


@pytest.mark.parametrize("case", ["parent", "empty", "no_rows", "no_decode_block"])
def test_spans_without_the_field_read_nothing(case):
    """The parent of PR 54 records no ``gap_s`` (and ``GENAI_DISPATCH_TIMELINE=off``
    no span): the metric is left out of its line, nothing raises."""
    spans = {
        "parent": [{k: v for k, v in s.items() if not k.startswith("gap_") and k != "handoff_rows"}
                   for s in RECORDED["block8"]],
        "empty": [],
        "no_rows": [dict(s, handoff_rows=0, gap_s=0.0) for s in RECORDED["block8"]],
        "no_decode_block": RECORDED["block8"],
    }[case]
    ctx = _ctx(8, spans)
    if case == "no_decode_block":
        ctx["config"] = {"engine": {}}
    assert _read(ctx) is None and gap_readers.gap_tail_ms(ctx, SPEC["params"]) is None


def test_manifest_entry_found_by_name_with_all_eight_cells():
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert (entry["unit"], entry["better"], entry["source"]) == ("%", "lower", "program_span")
    assert all(NAME in entries_of(MANIFEST, cell) for cell in CELLS)  # a ninth cell joins by appending its name
    # a layer the manifest already names, and an end-to-end metric every one of the cells reports
    assert entry["layer"] in {m["layer"] for m in MANIFEST["per_layer"] if m["name"] != NAME}
    (moved,) = [m for m in MANIFEST["end_to_end"] if m["name"] == entry["moves"]]
    assert moved["name"] == "itl_p995_ms" and set(CELLS) <= set(moved.get("workloads", CELLS))
    assert len(MANIFEST["per_layer"]) <= 128 and len({m["name"] for m in MANIFEST["per_layer"]}) == len(MANIFEST["per_layer"])


def test_the_entrys_file_resolves_under_the_manifests_paths():
    assert SPEC["name"] == NAME and SPEC["params"]["part"] == "gap_extend_s" and SPEC["params"]["frame_q"] == 99.5
    assert run.layer_metric_file(NAME) == os.path.join(BENCH, "layer_metrics", NAME + ".json")
    roots = [os.path.join(ROOT, p) for p in MANIFEST["paths"]]
    assert readers.resolve(SPEC["reader"], roots) is gap_readers.gap_tail_share
    with pytest.raises(ValueError):  # a module of the program is no reader: the yardstick stays under paths
        readers.resolve("generativeaiexamples_tpu.engine.dispatch_timeline:device_clock", roots)
