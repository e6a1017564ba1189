"""``perfbench/span_readers.py``: statistics of the dispatch timeline's
completion stamp over a recorded span list, the six metric files that
name them, and a parent's spans (no such field) reading nothing."""
import json
import os

import pytest

from perfbench import readers, span_readers
from tests.perfbench.manifest_entries import ENTRY_KEYS, entries_of, real

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
NEW = ("decode_step_done_ms", "extend_wide_done_ms", "extend_narrow_done_ms",
       "extend_device_share", "device_starved_share", "device_hold_max_ms")
CELLS = ["chat_decode_7b", "reason_decode_phi4flash", "doc_reason_glm53flash"]


def _span(kind, device_s, starved_s=0.0, **fields):
    return dict({"kind": kind, "category": "dispatch", "steps": 1, "device_s": device_s,
                 "starved_s": starved_s, "queued_s": 0.0}, **fields)


# a window of 10 s: five decode blocks of 8 steps, three wide chunks, two
# narrow ones, a monolithic prefill, and what is no dispatch span at all
SPANS = [
    _span("decode", 0.160, steps=8), _span("decode", 0.176, steps=8), _span("decode", 0.168, steps=8),
    _span("decode", 0.800, steps=8), _span("decode", 0.152, 0.25, steps=8),
    _span("prefill_chunk", 0.110, width=512, rows_dispatched=4),
    _span("prefill_chunk", 0.130, width=512, rows_dispatched=4),
    _span("prefill_chunk", 0.120, 0.05, width=512, rows_dispatched=1),
    _span("prefill_chunk", 0.030, width=128, rows_dispatched=1),
    _span("prefill_chunk", 0.050, width=256, rows_dispatched=4),
    _span("prefill", 0.044),
    {"kind": "device_hold:decode", "category": "stall", "duration_s": 0.8, "device_s": 99.0},
    {"kind": "readback:decode", "category": "readback", "duration_s": 0.2},
]
CTX = {"spans": SPANS, "window": (0.0, 10.0), "config": {"engine": {"prefill_chunk": 512, "decode_block": 8}}}


def _read(name, ctx=CTX):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as fh:
        spec = json.load(fh)
    assert spec["name"] == name
    roots = [BENCH, os.path.join(ROOT, "tests", "perfbench")]
    return readers.resolve(spec["reader"], roots)(ctx, spec.get("params", {}))


EXPECT = {
    "decode_step_done_ms": 21.0,      # median of 20, 22, 21, 100, 19 ms a step
    "extend_wide_done_ms": 120.0,     # median of the three chunks of width 512
    "extend_narrow_done_ms": 40.0,    # median of 30 and 50 ms
    "extend_device_share": 100.0 * 0.484 / 1.940,
    "device_starved_share": 3.0,      # 0.30 s of a 10 s window
    "device_hold_max_ms": 800.0,      # of dispatch spans: the stall span's 99 s is none
}


@pytest.mark.parametrize("name", NEW)
def test_metric_file_reads_the_recorded_spans(name):
    assert _read(name) == pytest.approx(EXPECT[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_parent_spans_without_the_field_read_nothing(name):
    """The parent of PR 40 records ``device_est_s`` and no completion
    field: every new metric is left out of its line, none raises."""
    parent = [
        {k: v for k, v in dict(s, device_est_s=0.001).items()
         if k not in ("device_s", "starved_s", "queued_s", "t_done")}
        for s in SPANS
    ]
    assert _read(name, dict(CTX, spans=parent)) is None
    assert _read(name, dict(CTX, spans=[])) is None


def test_span_stat_where_per_scale_and_statistics():
    stat = lambda **p: span_readers.span_stat(CTX, dict({"field": "device_s"}, **p))  # noqa: E731
    decode = {"kinds": ["decode"], "per": "steps", "scale": 1000.0}
    assert stat(stat="mean", **decode) == pytest.approx((20 + 22 + 21 + 100 + 19) / 5)
    assert stat(stat="max", **decode) == pytest.approx(100.0)
    assert stat(stat="p100", **decode) == pytest.approx(100.0)
    assert stat(stat="sum", kinds=["decode"]) == pytest.approx(1.456)
    assert stat(**decode) == pytest.approx(21.0)  # the default is the median
    # where: equality, a literal beside the engine's setting, and *_below
    chunks = {"kinds": ["prefill_chunk"], "stat": "sum"}
    assert stat(where={"width": 512}, **chunks) == pytest.approx(0.360)
    assert stat(where={"width": "engine:prefill_chunk", "rows_dispatched": 4}, **chunks) == pytest.approx(0.240)
    assert stat(where={"width_below": 256}, **chunks) == pytest.approx(0.030)
    assert stat(where={"width": 64}, **chunks) is None
    # a span without the where field does not match (a decode span has no width)
    assert stat(where={"width_below": 4096}, stat="sum") == pytest.approx(0.440)
    # over_window: a sum as a share of the window's seconds
    assert span_readers.span_stat(CTX, {"field": "starved_s", "stat": "sum", "over_window": True}) == pytest.approx(0.03)


def test_span_share_is_over_every_dispatch_span_that_carries_the_field():
    share = span_readers.span_share(CTX, {"kinds": ["decode"], "field": "device_s"})
    assert share == pytest.approx(100.0 * 1.456 / 1.940)
    everything = span_readers.span_share(CTX, {"field": "device_s"})
    assert everything == pytest.approx(100.0)
    assert span_readers.span_share(CTX, {"kinds": ["spec"], "field": "device_s"}) == 0.0
    assert span_readers.span_share(dict(CTX, spans=[{"kind": "decode", "category": "dispatch"}]),
                                   {"kinds": ["decode"], "field": "device_s"}) is None


def test_manifest_lists_the_six_metrics_last_for_the_three_cells():
    """The six entries are found by NAME (the test keeps the name it came
    with: a cell's entries stand anywhere); the three cells PR 40 named
    still read them, the narrow one where a ladder has two chunk widths."""
    manifest = real()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    end_to_end = {m["name"] for m in manifest["end_to_end"]}
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] not in NEW}
    for cell in CELLS:
        assert set(NEW) - {"extend_narrow_done_ms"} <= set(entries_of(manifest, cell))
    for name in NEW:
        m = by_name[name]
        assert set(m) == ENTRY_KEYS
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] in end_to_end and m["layer"] in layers
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".json"))
