"""``moe_tiles_used_share``: the share of the grouped product's worst-case
row tiles that a chunk's traffic used, from the two span fields the
expert families report since PR 55 (``moe_tiles_used``,
``moe_tiles_planned``), through the generic reader the benchmark has.

The metric is DATA over ``perfbench.arch.glm5next:span_ratio``. PR 55
kept its specification here because the manifest was full; since PR 56
(one entry a metric) it is ``perfbench/layer_metrics/moe_tiles_used_share.json``
and an entry of the six expert cells, and this dictionary pins both.
"""
import os

import pytest

from perfbench import readers
from tests.perfbench.manifest_entries import entries_of, metric_spec, real

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ROOTS = [os.path.join(ROOT, "perfbench"), os.path.join(ROOT, "tests", "perfbench")]
SPEC = {"name": "moe_tiles_used_share", "reader": "perfbench.arch.glm5next:span_ratio",
        "params": {"kind": "prefill_chunk", "num": "moe_tiles_used", "den": "moe_tiles_planned", "scale": 100}}

# Kimi-K2.5's shapes: a 512-token chunk plans 4 x (64 + 12) tiles, a decode block's step 4 x (16 + 12)
CHUNK = {"kind": "prefill_chunk", "category": "dispatch", "rows": 1, "moe_experts_hit": 10, "moe_experts_held": 48,
         "moe_tiles_used": 11, "moe_tiles_planned": 304}
DECODE = {"kind": "decode", "category": "dispatch", "rows": 24, "moe_experts_hit": 6, "moe_experts_held": 48,
          "moe_tiles_used": 6, "moe_tiles_planned": 112}


def _without(span, *fields):
    return {k: v for k, v in span.items() if k not in fields}


@pytest.mark.parametrize("spans,kind,want", [
    ([CHUNK, dict(CHUNK, moe_tiles_used=13), DECODE], "prefill_chunk", 100.0 * 24 / 608),
    ([CHUNK, DECODE, dict(DECODE, moe_tiles_used=8)], "decode", 100.0 * 14 / 224),
    # the parent's spans carry neither field: nothing to read, and no error
    ([_without(CHUNK, "moe_tiles_used", "moe_tiles_planned"), DECODE], "prefill_chunk", None),
    ([_without(CHUNK, "moe_tiles_planned")], "prefill_chunk", None),
    ([DECODE], "prefill_chunk", None),  # a window without a chunk
], ids=["chunks", "decode-steps", "parent-spans", "half-a-pair", "no-chunk"])
def test_the_share_of_planned_tiles_used_reads_through_the_generic_span_ratio(spans, kind, want):
    read = readers.resolve(SPEC["reader"], ROOTS)
    got = read({"spans": spans}, dict(SPEC["params"], kind=kind))
    assert got is None if want is None else got == pytest.approx(want, rel=1e-12)


def test_the_file_and_the_entry_are_as_pr_55_left_them_for_this_pr():
    assert metric_spec(SPEC["name"]) == SPEC
    manifest = real()
    (entry,) = [e for e in manifest["per_layer"] if e["name"] == SPEC["name"]]
    assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} == {
        "unit": "%", "better": "lower", "source": "program_span", "layer": "step programs", "moves": "out_tok_s"}
    # the cells that hold experts: those that read the share of experts hit, and no other
    expert = [w["name"] for w in manifest["workloads"] if "moe_experts_hit_share" in entries_of(manifest, w["name"])]
    assert entry["workloads"] == expert and len(expert) >= 6
