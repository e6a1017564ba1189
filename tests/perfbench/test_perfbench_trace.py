"""The reduction from device-track events to busy time, idle share,
operation self time and name rules — on a hand-made list and on a small
list recorded on the chip (perfbench/fixtures)."""
import json
import os

import pytest

from perfbench import readers, trace_reduce as tr

FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "perfbench", "fixtures", "chip_trace_events.json",
)
P, OPS, MODS = "/device:TPU:0", tr.OPS_LINE, tr.MODULES_LINE

# One device, two programs 100 us apart. The decode program holds a
# `while` whose body ops are on the same line (nesting), then a kernel.
HAND = [
    (P, MODS, "jit_decode_paged(123)", 1_000, 10_000),
    (P, MODS, "jit_extend_batch_paged(9)", 111_000, 4_000),
    (P, OPS, "%while.3 = (s32[]) while(...)", 1_000, 8_000),
    (P, OPS, "%fusion.10 = bf16[64,4096] fusion(...)", 1_000, 3_000),
    (P, OPS, "%paged_attention.7 = bf16[64,1,32,128] custom-call(...)", 4_500, 2_500),
    (P, OPS, "%_call.921 = bf16[64,28672] custom-call(...)", 9_500, 1_500),
    (P, OPS, "%fusion.11 = bf16[4,512] fusion(...)", 111_000, 4_000),
    ("/host:CPU", OPS, "ignored", 0, 10 ** 9),
]


@pytest.mark.parametrize("name,want", [
    ("%fusion.123 = bf16[64,4096]{1,0} fusion(bf16[64] %p)", "fusion"),
    ("%paged_attention.251 = bf16[64,1,32,128]{3,2,1,0:T(8,128)}", "paged_attention"),
    ("%_call.921 = bf16[64,28672] custom-call(...)", "_call"),
    ("jit_decode_paged(11167679330107758230)", "jit_decode_paged"),
    ("%copy-done.148 = f32[1,4096] copy-done(...)", "copy-done"),
    ("%fusion = f32[2] fusion()", "fusion"),
])
def test_base_name_drops_the_instance_number_and_the_shapes(name, want):
    assert tr.base_name(name) == want


def test_union_merges_overlaps_and_nesting():
    assert tr.union_ns([(0, 10), (5, 12), (20, 30), (22, 25)]) == 22
    assert tr.union_ns([]) == 0


def test_self_time_takes_children_out_of_their_parent():
    ops = [(n, s, d) for p, l, n, s, d in HAND if l == OPS and p == P]
    self_ns = tr.self_times_ns(ops)
    assert self_ns == {"while": 8_000 - 3_000 - 2_500, "fusion": 3_000 + 4_000,
                       "paged_attention": 2_500, "_call": 1_500}
    assert sum(self_ns.values()) == tr.union_ns([(s, s + d) for _, s, d in ops])


def test_reduce_hand_made_events():
    s = tr.reduce_events(HAND)  # the host plane's event is left out
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(114e-6)
    assert s["busy_s"] == pytest.approx((8_000 + 1_500 + 4_000) * 1e-9)
    assert s["modules"]["jit_decode_paged"] == {"count": 1.0, "total_s": pytest.approx(10e-6)}
    assert s["idle_gaps_s"] == {"after_jit_decode_paged_before_jit_extend_batch_paged": pytest.approx(100e-6),
                                "between_ops_lt_20us": pytest.approx(0.5e-6)}
    ctx = {"trace": s, "config": {"engine": {"decode_block": 8}}}
    R = readers.READERS
    assert R["device_idle_share"](ctx, {}) == pytest.approx(100 * (1 - 13.5 / 114))
    assert R["device_module_ms"](ctx, {"match": "^jit_decode", "divide_by_engine": "decode_block"}) \
        == pytest.approx(10e-3 / 8)
    assert R["device_op_busy_share"](ctx, {"match": "paged_attention"}) == pytest.approx(100 * 2.5 / 13.5)
    assert R["device_op_busy_share"](ctx, {"match": "^_call$|int8_matmul"}) == pytest.approx(100 * 1.5 / 13.5)
    assert R["device_module_ms"](ctx, {"match": "^jit_nothing"}) is None
    bd = tr.breakdown(s)
    assert bd["device_ops"][0] == ["fusion", pytest.approx(7e-6)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_no_device_plane_reads_as_nothing():
    s = tr.reduce_events([e for e in HAND if not tr.is_device_plane(e[0])])
    assert s == {"devices": 0}
    assert readers.READERS["device_idle_share"]({"trace": s}, {}) is None


def test_reduce_recorded_chip_events_against_a_brute_force_count():
    with open(FIXTURE, encoding="utf-8") as fh:
        events = [tuple(e) for e in json.load(fh)["events"]]
    s = tr.reduce_events(events)
    ops = [(st, st + d) for p, l, n, st, d in events if l == OPS]
    lo, hi = min(a for a, _ in ops), max(b for _, b in ops)
    covered = bytearray(hi - lo)  # one flag per nanosecond: independent of union_ns
    for a, b in ops:
        covered[a - lo:b - lo] = b"\x01" * (b - a)
    assert s["busy_s"] == pytest.approx(sum(covered) * 1e-9)
    assert s["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert sum(s["ops_self_s"].values()) == pytest.approx(s["busy_s"])
    # the weight-streaming int8 matmul is the Pallas call named `_call`;
    # the rule in layer_metrics finds it among these 3 ms
    share = readers.READERS["device_op_busy_share"]({"trace": s}, {"match": "^_call$|int8_matmul"})
    assert 20 < share < 30
    assert set(s["modules"]) >= {"jit_decode_paged", "jit_prefill_batch_paged"}
    assert 0 <= readers.READERS["device_idle_share"]({"trace": s}, {}) < 5
