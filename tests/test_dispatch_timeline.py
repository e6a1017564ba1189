"""Dispatch-timeline profiler (engine/dispatch_timeline.py): the span
ring's whole-window eviction, the ``?since`` cursor contract on
GET /internal/timeline (parity with /internal/requests: 400 on a
garbage cursor, cursor echoed in every response), the bubble
decomposition summing to 1.0 over engine-active wall, and the Perfetto
export's track structure.
"""
import asyncio
import threading
import time

from generativeaiexamples_tpu.engine import dispatch_timeline as dtl


def _fresh(enable=True, capacity=dtl._DEFAULT_CAPACITY):
    dtl.reset()
    dtl.configure(enable=enable, capacity=capacity)


def _span(kind="decode", *, t_wall=None, lock_wait=0.0, run=0.001, **kw):
    dtl.record_span(
        kind,
        t_wall=time.time() if t_wall is None else t_wall,
        lock_wait_s=lock_wait,
        run_s=run,
        **kw,
    )


def _on_thread(name, fn):
    worker = threading.Thread(target=fn, name=name)
    worker.start()
    worker.join()


# --------------------------------------------------------------------------- #
# Ring semantics


def test_span_view_shape_and_gap_attribution():
    _fresh()
    try:
        now = time.time()
        _span("decode", t_wall=now - 0.5, lock_wait=0.002, run=0.01,
              rows=4, tokens=64, steps=16, path="kernel", rids=[7, 9])
        # next dispatch on the same thread, 0.1s after the first's host
        # return: that 0.1s is queued host gap
        first_end = (now - 0.5) + 0.002 + 0.01
        _span("decode", t_wall=first_end + 0.1, run=0.01)
        views, cur = dtl.spans_since(0)
        assert cur == 2 and [v["seq"] for v in views] == [1, 2]
        head = views[0]
        assert head["kind"] == "decode" and head["category"] == "dispatch"
        assert head["rows"] == 4 and head["tokens"] == 64 and head["steps"] == 16
        assert head["path"] == "kernel" and head["rids"] == [7, 9]
        assert head["lock_wait_s"] == 0.002 and head["device_est_s"] == 0.01
        assert abs(views[1]["gap_s"] - 0.1) < 1e-3
        # unqueued dispatch (no backlog): idle time is nobody's bubble
        _span("decode", queued=False)
        assert dtl.recent_spans(1)[0]["gap_s"] == 0.0
    finally:
        _fresh()


def test_whole_window_eviction_never_splits_a_window():
    cap = 2 * dtl.WINDOW_SPANS
    _fresh(capacity=cap)
    try:
        for _ in range(cap):
            _span("decode")
        views, _ = dtl.spans_since(0, limit=10_000)
        assert len(views) == cap
        # one more span evicts exactly one whole window — never a
        # partial window, so a cursor-tailing reader sees no interior
        # holes in what remains
        _span("decode")
        views, cur = dtl.spans_since(0, limit=10_000)
        assert len(views) == cap - dtl.WINDOW_SPANS + 1
        seqs = [v["seq"] for v in views]
        assert seqs == list(range(dtl.WINDOW_SPANS + 1, cap + 2))
        assert cur == cap + 1
    finally:
        _fresh()


def test_configure_rounds_capacity_up_to_whole_windows():
    _fresh(capacity=dtl.WINDOW_SPANS + 1)
    try:
        assert dtl._CAPACITY == 2 * dtl.WINDOW_SPANS
        # capacity can never shrink below one eviction window
        dtl.configure(capacity=1)
        assert dtl._CAPACITY == dtl.WINDOW_SPANS
    finally:
        _fresh()


def test_spans_since_cursor_and_limit():
    _fresh()
    try:
        for _ in range(5):
            _span("prefill")
        anchor = dtl.cursor()
        assert anchor == 5
        _span("decode")
        tail, cur = dtl.spans_since(anchor)
        assert [v["kind"] for v in tail] == ["decode"] and cur == 6
        capped, cur = dtl.spans_since(0, limit=2)
        assert [v["seq"] for v in capped] == [1, 2] and cur == 6
    finally:
        _fresh()


def test_disabled_recorder_records_nothing():
    _fresh(enable=False)
    try:
        _span("decode")
        dtl.record_stall("handoff_backpressure", 0.5)
        dtl.record_readback("token", 0.01)
        dtl.record_compile("decode_block", 1.0)
        assert dtl.cursor() == 0
        assert dtl.counters_snapshot()["timeline_spans"] == 0
    finally:
        _fresh()


# --------------------------------------------------------------------------- #
# Bubble decomposition


def test_bubble_components_sum_to_one():
    _fresh()
    try:
        now = time.time()
        _span("decode", t_wall=now - 1.0, lock_wait=0.05, run=0.2)
        _span("prefill_chunk", t_wall=now - 0.7, lock_wait=0.0, run=0.3)
        dtl.record_stall("handoff_backpressure", 0.1)
        dtl.record_readback("token", 0.15)
        out = dtl.bubble_snapshot()
        assert out["bubble_spans_in_window"] == 4
        parts = (
            out["bubble_device_ratio"] + out["bubble_lock_ratio"]
            + out["bubble_gap_ratio"] + out["bubble_readback_ratio"]
        )
        assert abs(parts - 1.0) < 5e-3
        assert abs(out["bubble_ratio"] - (1.0 - out["bubble_device_ratio"])) < 5e-3
        # active wall = device + lock + gap + readback seconds; the
        # second dispatch also carries 0.05s of queued host gap since
        # the first's host return on the same thread
        assert abs(out["bubble_window_s"]
                   - (0.2 + 0.3 + 0.05 + 0.05 + 0.1 + 0.15)) < 1e-2
        assert out["bubble_readback_ratio"] > 0 and out["bubble_lock_ratio"] > 0
    finally:
        _fresh()


def test_pipeline_flush_and_rollback_span_kinds():
    """The spec pipeline's two new span kinds land in the right bubble
    categories: pipeline_flush is a readback (the deferred packed sync),
    rollback is a stall (host re-proposal time) — and the components
    still sum to 1.0 with both in the window."""
    _fresh()
    try:
        _span("spec", run=0.2, rows=3)
        dtl.record_pipeline_flush(0.05, rows=3)
        dtl.record_rollback(0.03, rows=2, rids=[1, 4])
        views, _ = dtl.spans_since(0)
        by_kind = {v["kind"]: v for v in views}
        assert by_kind["pipeline_flush"]["category"] == "readback"
        assert by_kind["pipeline_flush"]["rows"] == 3
        assert by_kind["rollback"]["category"] == "stall"
        assert by_kind["rollback"]["rows"] == 2
        assert by_kind["rollback"]["rids"] == [1, 4]
        counters = dtl.counters_snapshot()
        assert abs(counters["timeline_readback_stall_seconds"] - 0.05) < 1e-9
        assert abs(counters["timeline_gap_seconds"] - 0.03) < 1e-9
        out = dtl.bubble_snapshot()
        parts = (
            out["bubble_device_ratio"] + out["bubble_lock_ratio"]
            + out["bubble_gap_ratio"] + out["bubble_readback_ratio"]
        )
        assert abs(parts - 1.0) < 5e-3
        assert out["bubble_readback_ratio"] > 0
        assert out["bubble_gap_ratio"] > 0
    finally:
        _fresh()


def test_per_mode_counter_split_and_bubble_mode_ratios():
    """Every cumulative component is split per dispatch mode (decode /
    spec / prefill / other, derived from the span kind): mode keys are
    always present (zeros included), modes partition the totals, and
    the per-mode bubble ratios of active modes sum to ~1.0."""
    _fresh()
    try:
        now = time.time()
        _span("decode", t_wall=now - 1.0, lock_wait=0.01, run=0.2)
        _span("spec", t_wall=now - 0.7, lock_wait=0.02, run=0.1)
        _span("prefill_chunk", t_wall=now - 0.5, run=0.3)
        dtl.record_pipeline_flush(0.05)  # spec-mode readback
        dtl.record_rollback(0.03)        # spec-mode stall
        dtl.record_stall("handoff_backpressure", 0.07)  # prefill-mode
        dtl.record_readback("decode", 0.04)  # decode-mode (reader slab)
        counters = dtl.counters_snapshot()
        for mode in dtl.MODES:
            for part in ("device_est", "lock_wait", "gap",
                         "readback_stall"):
                assert f"timeline_{mode}_{part}_seconds" in counters
            assert f"timeline_{mode}_dispatches" in counters
        # the mode split partitions the totals exactly
        for part in ("device_est_seconds", "lock_wait_seconds",
                     "gap_seconds", "readback_stall_seconds"):
            total = counters[f"timeline_{part}"]
            split = sum(
                counters[f"timeline_{m}_{part}"] for m in dtl.MODES
            )
            assert abs(total - split) < 1e-6, part
        assert counters["timeline_spec_dispatches"] == 1
        assert abs(
            counters["timeline_spec_readback_stall_seconds"] - 0.05
        ) < 1e-9
        # rollback stall (0.03) plus the spec span's queued host gap
        assert counters["timeline_spec_gap_seconds"] >= 0.03
        # handoff stall (0.07) plus the prefill span's queued host gap
        assert counters["timeline_prefill_gap_seconds"] >= 0.07
        assert abs(
            counters["timeline_decode_readback_stall_seconds"] - 0.04
        ) < 1e-9
        out = dtl.bubble_snapshot()
        mode_sum = sum(
            out[f"bubble_mode_{m}_ratio"] for m in dtl.MODES
            if f"bubble_mode_{m}_ratio" in out
        )
        assert abs(mode_sum - 1.0) < 5e-3
        assert out["bubble_mode_spec_ratio"] > 0
        # 'other' saw no spans: its ratio key is omitted, its counter
        # keys still exist as zeros
        assert "bubble_mode_other_ratio" not in out
        assert counters["timeline_other_device_est_seconds"] == 0.0
    finally:
        _fresh()


def test_readback_kind_prefix_strip_maps_modes():
    """record_readback kinds arrive as the program kind ('token',
    'spec', ...) and mode attribution must survive the readback: prefix
    mapping puts spec fetches on the spec track."""
    _fresh()
    try:
        dtl.record_readback("spec", 0.02)
        dtl.record_readback("spec_block", 0.01)
        counters = dtl.counters_snapshot()
        assert abs(
            counters["timeline_spec_readback_stall_seconds"] - 0.03
        ) < 1e-9
    finally:
        _fresh()


def test_compile_spans_are_overlay_only():
    """Compile time already lands inside its dispatch span's run_s, so
    compile markers must not double-charge the bubble sums."""
    _fresh()
    try:
        _span("decode", run=0.2)
        before = dtl.bubble_snapshot()
        dtl.record_compile("decode_block", 5.0, hot=True)
        after = dtl.bubble_snapshot()
        assert after["bubble_spans_in_window"] == before["bubble_spans_in_window"]
        assert after["bubble_window_s"] == before["bubble_window_s"]
        counters = dtl.counters_snapshot()
        assert counters["timeline_device_est_seconds"] == 0.2
        assert counters["timeline_readback_stall_seconds"] == 0.0
        # but the marker is visible on the ring for the Perfetto overlay
        assert dtl.recent_spans(1)[0]["kind"] == "hot_compile:decode_block"
    finally:
        _fresh()


def test_empty_window_reports_no_components():
    _fresh()
    try:
        assert dtl.bubble_snapshot() == {"bubble_spans_in_window": 0}
    finally:
        _fresh()


# --------------------------------------------------------------------------- #
# Perfetto export


def test_perfetto_trace_tier_tracks_and_lock_children():
    _fresh()
    try:
        _on_thread("llm-prefill-tier",
                   lambda: _span("prefill_chunk", run=0.05))
        _on_thread("llm-decode",
                   lambda: _span("decode", lock_wait=0.01, run=0.02))
        _on_thread("llm-prefill-tier",
                   lambda: dtl.record_stall("handoff_backpressure", 0.1))
        views, _ = dtl.spans_since(0)
        flight = [{
            "request_id": "req-1", "trace_id": "ab" * 16,
            "started_at": time.time() - 1.0, "rids": [3],
            "timeline": [{"event": "submit", "t_s": 0.0},
                         {"event": "first_token", "t_s": 0.4}],
        }]
        trace = dtl.perfetto_trace(views, flight=flight)
        events = trace["traceEvents"]
        tracks = {
            e["args"]["name"] for e in events
            if e.get("ph") == "M" and e.get("name") == "thread_name"
        }
        assert {"llm-prefill-tier", "llm-decode", "requests"} <= tracks
        named = {e["name"] for e in events if e.get("ph") == "X"}
        assert {"prefill_chunk", "decode", "handoff_backpressure",
                "dispatch_lock_wait"} <= named
        # host-return device-estimate track present when no xplane feed
        pids = {e.get("pid") for e in events}
        assert dtl._PID_DEVICE_EST in pids
        # flight overlay: process-scoped instants carrying the trace id
        instants = [e for e in events if e.get("ph") == "i"]
        assert {e["name"] for e in instants} == {"submit", "first_token"}
        assert all(e["args"]["trace_id"] == "ab" * 16 for e in instants)
        assert all(e["s"] == "p" for e in instants)
    finally:
        _fresh()


def test_perfetto_xplane_events_replace_estimate_track():
    _fresh()
    try:
        _span("decode", run=0.02)
        views, _ = dtl.spans_since(0)
        trace = dtl.perfetto_trace(
            views,
            device_events=[{"name": "jit_decode_block", "ts_us": 1.0,
                            "dur_us": 900.0, "tid": 1}],
        )
        events = trace["traceEvents"]
        pids = {e.get("pid") for e in events}
        assert dtl._PID_DEVICE_XPLANE in pids
        assert dtl._PID_DEVICE_EST not in pids
        assert any(
            e.get("name") == "jit_decode_block" and e.get("ph") == "X"
            for e in events
        )
    finally:
        _fresh()


def test_span_counters_show_as_fields_of_the_view():
    """Kind-specific counts (the decode span's kv_pages_walked /
    kv_pages_grid) ride the span and surface as top-level fields;
    a span recorded without them has none."""
    _fresh()
    try:
        _span("decode", rows=2, counters={"kv_pages_walked": 7, "kv_pages_grid": 64})
        _span("prefill", rows=1)
        decode, prefill = dtl.spans_since(0)[0]
        assert decode["kv_pages_walked"] == 7 and decode["kv_pages_grid"] == 64
        assert decode["kv_pages_walked"] <= decode["kv_pages_grid"]
        assert "kv_pages_walked" not in prefill and "counters" not in prefill
    finally:
        _fresh()


# --------------------------------------------------------------------------- #
# GET /internal/timeline


def _timeline_app():
    from aiohttp import web

    from generativeaiexamples_tpu.server.observability import (
        add_observability_routes,
    )

    app = web.Application()
    add_observability_routes(app)
    return app


def test_timeline_endpoint_since_cursor_parity():
    _fresh()
    try:
        for kind in ("prefill", "decode", "decode"):
            _span(kind)

        async def scenario():
            from aiohttp.test_utils import TestClient, TestServer

            async with TestClient(TestServer(_timeline_app())) as client:
                full = await (await client.get("/internal/timeline")).json()
                assert full["enabled"] is True and full["cursor"] == 3
                assert [v["seq"] for v in full["spans"]] == [1, 2, 3]
                assert "bubble" in full
                # incremental tail from the echoed cursor
                tail = await (
                    await client.get("/internal/timeline?since=2")
                ).json()
                assert tail["cursor"] == 3
                assert [v["seq"] for v in tail["spans"]] == [3]
                # caught-up poll still echoes the cursor
                idle = await (
                    await client.get("/internal/timeline?since=3")
                ).json()
                assert idle["spans"] == [] and idle["cursor"] == 3
                # garbage cursor: 400, not a silent full dump
                bad = await client.get("/internal/timeline?since=banana")
                assert bad.status == 400
                detail = (await bad.json())["detail"]
                assert "integer cursor" in detail and "banana" in detail
                # perfetto format carries the cursor too
                pf = await (
                    await client.get("/internal/timeline?format=perfetto")
                ).json()
                assert pf["cursor"] == 3 and "traceEvents" in pf

        asyncio.run(scenario())
    finally:
        _fresh()


# --------------------------------------------------------------------------- #
# Config wiring


def test_validate_config_rejects_bad_knobs():
    import types

    import pytest

    ok = types.SimpleNamespace(
        dispatch_timeline_enable="on",
        dispatch_timeline_capacity=4096,
    )
    dtl.validate_config(ok)
    with pytest.raises(ValueError, match="on|off"):
        dtl.validate_config(types.SimpleNamespace(
            dispatch_timeline_enable="sometimes",
            dispatch_timeline_capacity=4096,
        ))
    with pytest.raises(ValueError, match="whole span window"):
        dtl.validate_config(types.SimpleNamespace(
            dispatch_timeline_enable="on",
            dispatch_timeline_capacity=dtl.WINDOW_SPANS - 1,
        ))


# --------------------------------------------------------------------------- #
# The prefill_chunk span says what its dispatch computed (chunked
# prefill's shape rule: engine/llm_engine.py _chunk_rung)

import pytest  # noqa: E402


@pytest.fixture(scope="module")
def chunk_engine():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    eng = LLMEngine(EngineConfig(
        model_config_name="debug-1k", max_batch_size=4, max_seq_len=256, prefill_chunk=64, page_size=16,
        decode_block=2, dtype="float32", tensor_parallelism=1, prefix_cache_enable="off",
    ))
    yield eng
    eng.shutdown()


def _engine_counters():
    from generativeaiexamples_tpu.utils import metrics as metrics_mod

    out = {}
    for line in metrics_mod.get_registry().render().splitlines():
        if line.startswith("genai_engine_") and " " in line:
            k, v = line.rsplit(" ", 1)
            out[k] = float(v)
    return out


CHUNK_WAVES = {
    # prompt lengths of one wave -> (rows, tokens, rows_dispatched, width, pad_tokens) of each chunk span
    "tail_on_one_of_three": ([69, 40, 10], [(3, 114, 4, 64, 142), (1, 5, 1, 16, 11)]),
    "two_tails": ([69, 40, 90, 64], [(4, 232, 4, 64, 24), (2, 31, 4, 64, 225)]),
    "two_narrow_tails": ([69, 70, 10], [(3, 138, 4, 64, 118), (2, 11, 4, 16, 53)]),
    "one_row_two_full_chunks_and_a_page": ([144], [(1, 64, 1, 64, 0), (1, 64, 1, 64, 0), (1, 16, 1, 16, 0)]),
}


@pytest.mark.parametrize("name", sorted(CHUNK_WAVES))
def test_prefill_chunk_span_carries_rows_width_and_padding(chunk_engine, name):
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    lengths, expect = CHUNK_WAVES[name]
    before, cursor = _engine_counters(), dtl.spans_since(0)[1]
    with chunk_engine.hold_admissions():
        reqs = [
            chunk_engine.submit([(i * 7 + n) % 250 + 1 for i in range(n)],
                                SamplingParams(temperature=0.0, max_tokens=2))
            for n in lengths
        ]
    for req in reqs:
        while req.out_queue.get(timeout=300) is not None:
            pass
    spans = [s for s in dtl.spans_since(cursor)[0] if s["kind"] == "prefill_chunk"]
    got = [(s["rows"], s["tokens"], s["rows_dispatched"], s["width"], s["pad_tokens"]) for s in spans]
    assert got == expect
    for s in spans:
        assert s["pad_tokens"] == s["rows_dispatched"] * s["width"] - s["tokens"] >= 0
    after = _engine_counters()
    grew = lambda k: after.get(k, 0.0) - before.get(k, 0.0)  # noqa: E731
    assert grew("genai_engine_prefill_tokens_total") == sum(lengths)
    assert grew("genai_engine_extend_tokens_computed_total") == sum(e[2] * e[3] for e in expect)
    assert grew("genai_engine_prefill_chunks_total") == len(expect)


def test_monolithic_prefill_counts_its_padded_bucket(chunk_engine):
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    before = _engine_counters()
    list(chunk_engine.iter_ids([5] * 20, SamplingParams(temperature=0.0, max_tokens=2), timeout=300))
    after = _engine_counters()
    assert after["genai_engine_prefill_tokens_total"] - before["genai_engine_prefill_tokens_total"] == 20
    # one row of one 64-token bucket: live share 20 / 64
    assert (after["genai_engine_extend_tokens_computed_total"]
            - before["genai_engine_extend_tokens_computed_total"]) == 64
