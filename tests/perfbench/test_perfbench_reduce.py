"""Percentile, window-token and sub-window arithmetic on hand-made logs."""
import pytest

from perfbench import readers, reduce


def req(send, frames, end=None, status="ok", due=None, max_tokens=8, wall=None):
    return {"send_s": send, "due_s": due, "frames_s": frames, "end_s": end, "status": status,
            "max_tokens": max_tokens, "send_wall": send if wall is None else wall}


LOG = [
    req(-1.0, [-0.5, 0.5, 1.5, 2.5], end=2.6),        # first frame before the window
    req(0.2, [1.2, 1.3, 1.4, 9.5], end=9.6),           # ends inside, one gap of 8.1 s
    req(8.0, [9.9, 10.5], end=10.6),                    # last frame after the window
    req(9.0, [], end=None, status="in_flight"),
    req(3.0, [], end=4.0, status="failed"),
]


@pytest.mark.parametrize("values,q,want", [
    ([], 50, None), ([7.0], 99, 7.0), ([1, 2, 3, 4], 50, 2.5), ([1, 2, 3, 4, 5], 90, 4.6),
    ([10, 0], 25, 2.5), (list(range(101)), 99, 99.0),
])
def test_percentile_interpolates_between_order_statistics(values, q, want):
    assert reduce.percentile(values, q) == pytest.approx(want) if want is not None else reduce.percentile(values, q) is None


def test_tokens_are_counted_by_arrival_inside_the_window_not_by_request():
    assert reduce.window_tokens(LOG, 0.0, 10.0) == 3 + 4 + 1
    assert reduce.out_tok_s(LOG, 0.0, 10.0) == pytest.approx(0.8)
    assert reduce.window_tokens(LOG, 0.0, 1.0) == 1


def test_sub_window_rates_sum_to_the_whole_window():
    tenths = reduce.sub_window_rates(LOG, 0.0, 10.0, 10)
    assert tenths == [1, 4, 1, 0, 0, 0, 0, 0, 0, 2]
    assert sum(tenths) / 10 == pytest.approx(reduce.out_tok_s(LOG, 0.0, 10.0))
    assert reduce.percentile(tenths, 50) == 0.0 and reduce.percentile(tenths, 90) == pytest.approx(2.2)


def test_ttft_counts_requests_whose_first_frame_arrived_in_the_window():
    assert sorted(reduce.ttfts_ms(LOG, 0.0, 10.0)) == pytest.approx([1000.0, 1900.0])
    open_loop = [req(2.0, [3.0], end=3.1, due=1.0)]
    assert reduce.ttfts_ms(open_loop, 0.0, 10.0) == pytest.approx([2000.0])  # from the due instant


def test_gaps_are_taken_over_every_stream_by_the_later_frame():
    gaps = sorted(reduce.gaps_ms(LOG, 0.0, 10.0))
    assert gaps == pytest.approx([100.0, 100.0, 1000.0, 1000.0, 1000.0, 8100.0])
    e2e = reduce.end_to_end(LOG, 0.0, 10.0)
    assert e2e["itl_p99_ms"] == pytest.approx(reduce.percentile(gaps, 99))
    assert e2e["itl_p995_ms"] == pytest.approx(reduce.percentile(gaps, 99.5)) and e2e["itl_p995_ms"] > e2e["itl_p99_ms"]


def test_attempted_and_failed_count_requests_that_ended_in_the_window():
    assert reduce.counts(LOG, 0.0, 10.0) == {"attempted": 3, "failed": 1}
    assert reduce.tpots_ms(LOG, 0.0, 10.0) == pytest.approx([1000.0, 8300.0 / 3])


def test_spread_is_the_interquartile_range_over_the_median():
    import statistics

    xs = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q = statistics.quantiles(xs, n=4)
    assert reduce.spread(xs) == pytest.approx((q[2] - q[0]) / statistics.median(xs))
    assert reduce.spread([1.0]) is None


TIMELINE = {"started_at": 100.0, "total_s": 5.0, "timeline": [
    {"t_s": 0.0, "event": "http_request", "path": "/generate"},
    {"t_s": 0.1, "event": "retrieve", "duration_s": 0.4},
    {"t_s": 0.6, "event": "submit", "prompt_tokens": 2000},
    {"t_s": 0.9, "event": "admit", "queue_wait_s": 0.3},
    {"t_s": 2.9, "event": "first_token"},
    {"t_s": 4.8, "event": "decode_leave"},
    {"t_s": 4.9, "event": "engine_finish", "generated": 128, "stop": "max_tokens"},
]}


def test_phases_follow_the_flight_recorder_events():
    ph = readers.phases(TIMELINE)
    assert ph == pytest.approx({"queue_wait": 0.3, "prefill": 2.0, "decode": 2.0, "retrieval": 0.4, "batcher": 0.0})
    assert readers.phases({"timeline": [{"t_s": 0, "event": "http_request"}]}) is None
    assert readers.event_attr(TIMELINE, "engine_finish", "generated") == 128


def test_generic_readers_read_their_sources_and_return_none_on_nothing():
    client = req(99.9, [102.9, 104.7], end=105.2, wall=99.98)
    ctx = {
        "requests": [client], "window": (90.0, 110.0), "flight": [TIMELINE],
        "pairs": readers.join_in_order([client], [TIMELINE]),
        "spans": [{"kind": "decode", "category": "dispatch", "rows": 12, "steps": 8},
                  {"kind": "decode", "category": "dispatch", "rows": 16, "steps": 8},
                  {"kind": "prefill", "category": "dispatch", "rows": 4}],
        "metrics_before": readers.parse_metrics('genai_x_total{path="kernel"} 5\n'),
        "metrics_after": readers.parse_metrics('# HELP\ngenai_x_total{path="kernel"} 9\ngenai_x_total{path="gather"} 1\n'),
        "trace": None, "config": {"engine": {"decode_block": 8}}, "peaks": {},
    }
    R = readers.READERS
    assert len(ctx["pairs"]) == 1
    assert R["flight_phase_percentile"](ctx, {"phase": "retrieval", "q": 50}) == pytest.approx(400.0)
    assert R["client_other_percentile"](ctx, {"q": 50}) == pytest.approx((5.3 - 4.7) * 1000)
    assert R["span_mean"](ctx, {"kind": "decode", "field": "rows"}) == 14
    assert readers.metric_sum(ctx["metrics_after"], "genai_x_total", path="kernel") == 9
    assert R["client_tpot_percentile"](ctx, {"q": 50}) == pytest.approx(1800.0)
    assert R["client_gap_percentile"](ctx, {"q": 99}) == pytest.approx(1800.0)  # one gap: 102.9 -> 104.7
    for name in ("device_module_ms", "device_op_busy_share", "device_idle_share"):
        assert R[name](ctx, {"match": "x"}) is None
    assert R["span_mean"](ctx, {"kind": "spec", "field": "rows"}) is None


def test_join_pairs_sends_with_server_records_in_order_and_drops_strays():
    reqs = [req(0, [1], end=2, wall=10.0), req(0, [1], end=2, wall=10.5), req(0, [1], end=2, wall=50.0)]
    tls = [{"started_at": 10.51}, {"started_at": 10.01}, {"started_at": 30.0}]
    pairs = readers.join_in_order(reqs, tls)
    assert [(r["send_wall"], t["started_at"]) for r, t in pairs] == [(10.0, 10.01), (10.5, 10.51)]


@pytest.mark.parametrize("delivered, generated, lonely", [
    ([256, 384, 512], [512, 256, 384], []),            # same requests, any order
    ([256, 384], [256, 384, 512], []),                 # the server counted one more at the window's edge
    ([256, 384, 512, 512], [256, 384, 512], []),       # the client did
    ([256, 383], [256, 384, 512], [383]),              # a stream lost a token: no partner
    ([256, 384, 512], [256, 384, 500], [512]),               # equal numbers: every count needs its partner
])
def test_delivered_and_generated_counts_pair_up_except_at_the_edges(delivered, generated, lonely):
    assert reduce.unpaired_counts(delivered, generated) == lonely
