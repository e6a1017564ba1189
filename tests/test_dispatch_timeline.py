"""Dispatch timeline (engine/dispatch_timeline.py): the span ring's
whole-window eviction, the ``?since`` cursor contract on
GET /internal/timeline (parity with /internal/requests: 400 on a
garbage cursor, cursor echoed in every response), the completion stamp
(``device_s`` / ``starved_s`` / ``queued_s`` from a fake clock and
handles that become ready at stated times), the record a long hold
leaves, the bubble decomposition over those fields summing to 1.0, and
the Perfetto export's track structure.
"""
import asyncio
import logging
import threading
import time

import pytest

from generativeaiexamples_tpu.engine import dispatch_timeline as dtl


def _fresh(enable=True, capacity=dtl._DEFAULT_CAPACITY):
    # a watcher left by an engine of an earlier file of this worker (or by
    # the watcher test below) would race stamp_pending() for the queue and
    # stamp a fake-clock launch from the wall clock
    dtl.stop_watcher()
    dtl.reset()
    dtl.configure(enable=enable, capacity=capacity)


def _span(kind="decode", *, t_wall=None, lock_wait=0.0, run=0.001, **kw):
    return dtl.record_span(
        kind,
        t_wall=time.time() if t_wall is None else t_wall,
        lock_wait_s=lock_wait,
        run_s=run,
        **kw,
    )


class _Clock:
    """The fake wall clock the stamps are read from."""

    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class _Handle:
    """A launch's output that becomes ready at a stated time: awaiting
    it moves the clock there (never backwards)."""

    def __init__(self, clock, ready_at):
        self.clock, self.ready_at = clock, ready_at

    def block_until_ready(self):
        self.clock.t = max(self.clock.t, self.ready_at)


def _launch(clock, kind, t_enq, ready_at, **kw):
    """A launch whose enqueue call returned at ``t_enq`` (after 1 ms
    inside the lock) and whose output is ready at ``ready_at``."""
    return _span(kind, t_wall=t_enq - 0.001, run=0.001,
                 handle=_Handle(clock, ready_at), **kw)


def _stamped(clock):
    dtl.stamp_pending(clock)
    return [v for v in dtl.spans_since(0, limit=10_000)[0] if v["category"] == "dispatch"]


def _on_thread(name, fn):
    worker = threading.Thread(target=fn, name=name)
    worker.start()
    worker.join()


# --------------------------------------------------------------------------- #
# Ring semantics


def test_span_view_shape_and_true_names():
    _fresh()
    try:
        now = time.time()
        _span("decode", t_wall=now - 0.5, lock_wait=0.002, run=0.01,
              rows=4, tokens=64, steps=16, path="kernel", rids=[7, 9])
        _span("decode", run=0.01)
        views, cur = dtl.spans_since(0)
        assert cur == 2 and [v["seq"] for v in views] == [1, 2]
        head = views[0]
        assert head["kind"] == "decode" and head["category"] == "dispatch"
        assert head["rows"] == 4 and head["tokens"] == 64 and head["steps"] == 16
        assert head["path"] == "kernel" and head["rids"] == [7, 9]
        # the host time inside the lock under its true name, and the
        # wall time at which the enqueue call returned
        assert head["lock_wait_s"] == 0.002 and head["enqueue_s"] == 0.01
        assert abs(head["t_enq"] - (now - 0.5 + 0.002 + 0.01)) < 1e-5
        assert "device_est_s" not in head
        # no output awaited yet: no completion field claims a value
        assert not {"t_done", "device_s", "starved_s", "queued_s"} & set(head)
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


def test_disabled_recorder_records_nothing_and_awaits_nothing():
    _fresh(enable=False)
    try:
        clock = _Clock()
        assert _launch(clock, "decode", 1000.0, 1000.5) is None
        dtl.record_stall("handoff_backpressure", 0.5)
        dtl.record_readback("token", 0.01)
        dtl.record_compile("decode_block", 1.0)
        dtl.note_jit("decode", "compile", 1.0)
        assert dtl.cursor() == 0
        assert dtl.stamp_pending(clock) == 0 and clock.t == 1000.0
        before = dtl._WATCHER  # an engine earlier in this process may have started it
        dtl.start_watcher()
        assert dtl._WATCHER is before  # the one switch turns the stamp off too
        assert dtl.counters_snapshot()["timeline_spans"] == 0
    finally:
        _fresh()


# --------------------------------------------------------------------------- #
# The completion stamp


def test_stamps_in_order_give_device_starved_and_queued_seconds():
    """One device runs launches in enqueue order. A is enqueued at 0.0
    and done at 0.5; B is enqueued at 0.1 (queued 0.4 s behind A) and
    done at 0.8; C is enqueued at 1.0, 0.2 s after the device ran dry,
    and done at 1.3."""
    _fresh()
    try:
        clock = _Clock(1000.0)
        _launch(clock, "decode", 1000.0, 1000.5)
        _launch(clock, "prefill_chunk", 1000.1, 1000.8)
        _launch(clock, "decode", 1001.0, 1001.3)
        a, b, c = _stamped(clock)
        assert a["t_done"] == 1000.5 and b["t_done"] == 1000.8 and c["t_done"] == 1001.3
        assert abs(a["device_s"] - 0.5) < 1e-6 and a["starved_s"] == a["queued_s"] == 0.0
        assert abs(b["device_s"] - 0.3) < 1e-6  # from A's completion, not B's enqueue
        assert abs(b["queued_s"] - 0.4) < 1e-6 and b["starved_s"] == 0.0
        assert abs(c["device_s"] - 0.3) < 1e-6  # from C's enqueue: nothing was queued
        assert abs(c["starved_s"] - 0.2) < 1e-6 and c["queued_s"] == 0.0
        counters = dtl.counters_snapshot()
        assert abs(counters["timeline_device_seconds"] - 1.1) < 1e-6
        assert abs(counters["timeline_gap_seconds"] - 0.2) < 1e-6
        assert abs(counters["timeline_decode_device_seconds"] - 0.8) < 1e-6
        assert abs(counters["timeline_prefill_device_seconds"] - 0.3) < 1e-6
    finally:
        _fresh()


def test_a_late_stamp_moves_time_between_spans_and_conserves_the_sum():
    """The thread that stamps reaches A's output only at 0.7 (it was
    busy, or A was awaited out of order behind B): A's stamp is late, B
    is stamped the moment it is reached, nothing is negative and the sum
    is still the device's 0.8 s."""
    _fresh()
    try:
        clock = _Clock(1000.0)
        _launch(clock, "decode", 1000.0, 1000.5)
        _launch(clock, "decode", 1000.1, 1000.8)
        clock.t = 1000.7  # the stamping thread comes late to A
        a, b = _stamped(clock)
        assert a["t_done"] == 1000.7 and abs(a["device_s"] - 0.7) < 1e-6
        assert b["t_done"] == 1000.8 and abs(b["device_s"] - 0.1) < 1e-6
        assert abs(a["device_s"] + b["device_s"] - 0.8) < 1e-6
        # out of order: the later launch's output awaited FIRST
        _fresh()
        clock = _Clock(2000.0)
        first = _launch(clock, "decode", 2000.0, 2000.5)
        second = _launch(clock, "decode", 2000.1, 2000.8)
        held = [dtl._PENDING.get_nowait(), dtl._PENDING.get_nowait()]
        for item in reversed(held):
            dtl._PENDING.put(item)
        a, b = _stamped(clock)
        assert (a["seq"], b["seq"]) == (first.seq, second.seq)
        assert abs(b["device_s"] - 0.7) < 1e-6  # charged with A's time
        assert a["device_s"] == 0.0 and a["t_done"] == b["t_done"] == 2000.8
        assert a["starved_s"] == 0.0 and abs(a["queued_s"] - 0.8) < 1e-6
    finally:
        _fresh()


def test_an_unspanned_program_is_charged_to_the_next_span():
    """finish / update_slots have no span: enqueued between a chunk and
    the next decode block, their device time (0.05 s here) lands on the
    decode span, and jit work in them is stamped on it too."""
    _fresh()
    try:
        clock = _Clock(1000.0)
        _launch(clock, "prefill_chunk", 1000.0, 1000.2)
        dtl.note_jit("update_slots", "cache_load", 2.5)  # in an unspanned program
        _launch(clock, "decode", 1000.01, 1000.2 + 0.05 + 0.3, steps=4)
        chunk, decode = _stamped(clock)
        assert abs(chunk["device_s"] - 0.2) < 1e-6
        assert abs(decode["device_s"] - 0.35) < 1e-6
        assert decode["jit_what"] == "cache_load" and decode["jit_s"] == 2.5
        assert "jit_what" not in chunk
    finally:
        _fresh()


def _steady_blocks(clock, n, t0, rows_dispatched=4):
    """``n`` decode blocks of one rung, 0.1 s each, back to back."""
    for i in range(n):
        _launch(clock, "decode", t0 + 0.1 * i, t0 + 0.1 * (i + 1), rows=3, steps=2,
                counters={"rows_dispatched": rows_dispatched})
    return t0 + 0.1 * n


def test_a_hold_over_the_threshold_leaves_exactly_one_record(caplog):
    _fresh()
    try:
        clock = _Clock(1000.0)
        t = _steady_blocks(clock, 5, 1000.0)
        dtl.stamp_pending(clock)
        _launch(clock, "prefill_chunk", t, t + 0.02, rows=1,
                counters={"rows_dispatched": 1, "width": 16})
        dtl.note_jit("extend", "cache_load", 0.4)
        # the injected hold: this block's output is ready 2 s late
        held = _launch(clock, "decode", t + 0.02, t + 0.02 + 0.1 + 2.0, rows=3, steps=2,
                       counters={"rows_dispatched": 4})
        with caplog.at_level(logging.WARNING, logger=dtl.logger.name):
            dtl.stamp_pending(clock)
        views = dtl.spans_since(0, limit=10_000)[0]
        holds = [v for v in views if v["kind"].startswith("device_hold:")]
        assert len(holds) == 1
        hold = holds[0]
        assert hold["kind"] == "device_hold:decode" and hold["category"] == "stall"
        assert hold["held"] == {"seq": held.seq, "kind": "decode", "rows_dispatched": 4,
                                "width": 0, "steps": 2, "device_s": 2.1}
        assert abs(hold["duration_s"] - 2.1) < 1e-6
        # the three launches enqueued before it, oldest first, with their rungs
        assert [b["kind"] for b in hold["before"]] == ["decode", "decode", "prefill_chunk"]
        assert hold["before"][-1] == {"seq": held.seq - 1, "kind": "prefill_chunk",
                                      "rows_dispatched": 1, "width": 16, "steps": 1}
        assert hold["jit"] == [] or all(len(e) == 3 for e in hold["jit"])
        for field in ("heartbeat_max_gap_s", "cpu_s", "wall_s", "gc_pauses", "bytes_in_use"):
            assert field in hold
        assert abs(hold["wall_s"] - 2.1) < 1e-6 and hold["cpu_s"] >= 0 and hold["gc_pauses"] >= 0
        lines = [r.getMessage() for r in caplog.records if "DEVICE HOLD" in r.getMessage()]
        assert len(lines) == 1 and "'device_s': 2.1" in lines[0]
        # the hold is no part of the sums: the device's seconds are the spans'
        assert abs(dtl.counters_snapshot()["timeline_device_seconds"] - (0.5 + 0.02 + 2.1)) < 1e-6
    finally:
        _fresh()


@pytest.mark.parametrize("late_s, history", [
    (0.85, 5),  # 0.95 s: over eight medians, under HOLD_MIN_S
    (2.0, 2),   # over both, but its rung has no median to speak of yet
])
def test_no_record_under_the_threshold(late_s, history, caplog):
    _fresh()
    try:
        clock = _Clock(1000.0)
        t = _steady_blocks(clock, history, 1000.0)
        _launch(clock, "decode", t, t + 0.1 + late_s, rows=3, steps=2,
                counters={"rows_dispatched": 4})
        # a block eight times the median of ITS rung: a slow rung is no hold
        with caplog.at_level(logging.WARNING, logger=dtl.logger.name):
            dtl.stamp_pending(clock)
        assert not [v for v in dtl.spans_since(0, limit=10_000)[0] if v["category"] == "stall"]
        assert not [r for r in caplog.records if "DEVICE HOLD" in r.getMessage()]
    finally:
        _fresh()


def test_a_hold_is_judged_against_its_own_rung():
    """A wide extend of 1.2 s is what wide extends take; the same 1.2 s
    on the narrow rung, whose median is 0.05 s, is a hold."""
    _fresh()
    try:
        clock = _Clock(1000.0)
        t = 1000.0
        for width, each in ((512, 1.2), (16, 0.05)):
            for _ in range(4):
                _launch(clock, "prefill_chunk", t, t + each, rows=1,
                        counters={"rows_dispatched": 1, "width": width})
                t += each
        _launch(clock, "prefill_chunk", t, t + 1.2, rows=1,
                counters={"rows_dispatched": 1, "width": 512})
        _launch(clock, "prefill_chunk", t + 1.2, t + 2.4, rows=1,
                counters={"rows_dispatched": 1, "width": 16})
        dtl.stamp_pending(clock)
        holds = [v for v in dtl.spans_since(0, limit=10_000)[0] if v["category"] == "stall"]
        assert [h["held"]["width"] for h in holds] == [16]
    finally:
        _fresh()


def test_the_watcher_thread_stamps_from_the_wall_clock():
    """The real thread: started once, it awaits a handle that becomes
    ready ~50 ms later and stamps the wall time (and the heartbeat and
    gc hooks of a hold's record are in place)."""
    import gc

    _fresh()
    try:
        ready = threading.Event()

        class Slow:
            def block_until_ready(self):
                ready.wait(5)

        t0 = time.time()
        span = _span("decode", t_wall=t0, handle=Slow())
        dtl.start_watcher()
        dtl.start_watcher()  # idempotent
        assert sum(t.name == "llm-dispatch-watcher" for t in threading.enumerate()) == 1
        assert dtl._count_gc in gc.callbacks
        time.sleep(0.05)
        assert span.t_done is None
        ready.set()
        deadline = time.time() + 5
        while span.t_done is None and time.time() < deadline:
            time.sleep(0.005)
        assert span.t_done is not None and 0.04 < span.device_s < 4.0
    finally:
        _fresh()


def test_fresh_stops_a_watcher_an_earlier_engine_left_running():
    """What made two tests below depend on the run: a live watcher takes
    a launch off the queue before ``stamp_pending`` does, now and then,
    and stamps it from the wall clock (0.5 s here) and not the fake one."""
    _fresh()
    try:
        dtl.start_watcher()
        assert any(t.name == "llm-dispatch-watcher" and t.is_alive() for t in threading.enumerate())
        _fresh()
        assert not any(t.name == "llm-dispatch-watcher" and t.is_alive() for t in threading.enumerate())
        for _ in range(50):
            dtl.reset()
            clock = _Clock(time.time() - 0.5)
            span = _launch(clock, "decode", clock.t, clock.t + 0.2)
            assert dtl.stamp_pending(clock) == 1
            assert abs(span.device_s - 0.2) < 1e-6
        dtl.start_watcher()  # an engine built later gets a new one
        assert sum(t.name == "llm-dispatch-watcher" and t.is_alive() for t in threading.enumerate()) == 1
    finally:
        _fresh()


# --------------------------------------------------------------------------- #
# Bubble decomposition: device = sum of device_s, host_gap = sum of
# starved_s, lock contention and readback as measured on the host


def _two_launches_a_stall_and_a_readback():
    """Now-relative: a decode block (lock wait 0.05, device 0.2), then a
    chunk enqueued 0.05 s after the device ran dry (device 0.3)."""
    now = time.time()
    clock = _Clock(now - 1.0)
    _span("decode", t_wall=now - 1.0 - 0.051, lock_wait=0.05, run=0.001,
          handle=_Handle(clock, now - 0.8))
    _launch(clock, "prefill_chunk", now - 0.75, now - 0.45)
    dtl.stamp_pending(clock)
    dtl.record_stall("handoff_backpressure", 0.1)
    dtl.record_readback("token", 0.15)


def test_bubble_components_sum_to_one():
    _fresh()
    try:
        _two_launches_a_stall_and_a_readback()
        out = dtl.bubble_snapshot()
        # a host stall is a span on its thread's track, not a component:
        # what it cost the device is the next launch's starved_s
        assert out["bubble_spans_in_window"] == 3
        parts = (
            out["bubble_device_ratio"] + out["bubble_lock_ratio"]
            + out["bubble_gap_ratio"] + out["bubble_readback_ratio"]
        )
        assert abs(parts - 1.0) < 5e-3
        assert abs(out["bubble_ratio"] - (1.0 - out["bubble_device_ratio"])) < 5e-3
        # active wall = device + lock + starved + readback seconds
        assert abs(out["bubble_window_s"] - (0.2 + 0.3 + 0.05 + 0.05 + 0.15)) < 1e-2
        assert abs(out["bubble_device_ratio"] - 0.5 / 0.75) < 5e-3
        assert abs(out["bubble_gap_ratio"] - 0.05 / 0.75) < 5e-3
        assert abs(out["bubble_gap_p95_s"] - 0.05) < 1e-3
        assert out["bubble_readback_ratio"] > 0 and out["bubble_lock_ratio"] > 0
    finally:
        _fresh()


def test_pipeline_flush_and_rollback_span_kinds():
    """The spec pipeline's two span kinds land in the right categories:
    pipeline_flush is a readback (the deferred packed sync), rollback is
    a stall (host re-proposal time, on the thread's track only) — and
    the components still sum to 1.0 with both in the window."""
    _fresh()
    try:
        clock = _Clock(time.time() - 0.5)
        _launch(clock, "spec", clock.t, clock.t + 0.2, rows=3)
        dtl.stamp_pending(clock)
        dtl.record_pipeline_flush(0.05, rows=3)
        dtl.record_rollback(0.03, rows=2, rids=[1, 4])
        views, _ = dtl.spans_since(0)
        by_kind = {v["kind"]: v for v in views}
        assert by_kind["pipeline_flush"]["category"] == "readback"
        assert by_kind["pipeline_flush"]["rows"] == 3
        assert by_kind["rollback"]["category"] == "stall"
        assert by_kind["rollback"]["rows"] == 2
        assert by_kind["rollback"]["rids"] == [1, 4]
        assert by_kind["rollback"]["duration_s"] == 0.03
        counters = dtl.counters_snapshot()
        assert abs(counters["timeline_readback_stall_seconds"] - 0.05) < 1e-9
        assert abs(counters["timeline_device_seconds"] - 0.2) < 1e-6
        assert counters["timeline_gap_seconds"] == 0.0  # the device was never starved
        out = dtl.bubble_snapshot()
        parts = (
            out["bubble_device_ratio"] + out["bubble_lock_ratio"]
            + out["bubble_gap_ratio"] + out["bubble_readback_ratio"]
        )
        assert abs(parts - 1.0) < 5e-3
        assert out["bubble_readback_ratio"] > 0
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
        clock = _Clock(now - 1.0)
        _span("decode", t_wall=now - 1.011, lock_wait=0.01, run=0.001,
              handle=_Handle(clock, now - 0.8))
        _span("spec", t_wall=now - 0.771, lock_wait=0.02, run=0.001,
              handle=_Handle(clock, now - 0.65))  # starved 0.05, device 0.1
        _launch(clock, "prefill_chunk", now - 0.7, now - 0.35)  # queued, device 0.3
        dtl.stamp_pending(clock)
        dtl.record_pipeline_flush(0.05)  # spec-mode readback
        dtl.record_rollback(0.03)        # spec-mode stall
        dtl.record_stall("handoff_backpressure", 0.07)  # prefill-mode
        dtl.record_readback("decode", 0.04)  # decode-mode (reader slab)
        counters = dtl.counters_snapshot()
        for mode in dtl.MODES:
            for part in ("device", "lock_wait", "gap", "readback_stall"):
                assert f"timeline_{mode}_{part}_seconds" in counters
            assert f"timeline_{mode}_dispatches" in counters
        assert not [k for k in counters if "est" in k.split("_")]
        # the mode split partitions the totals exactly
        for part in ("device_seconds", "lock_wait_seconds",
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
        assert abs(counters["timeline_spec_gap_seconds"] - 0.05) < 1e-6
        assert abs(counters["timeline_spec_device_seconds"] - 0.1) < 1e-6
        assert abs(counters["timeline_prefill_device_seconds"] - 0.3) < 1e-6
        assert counters["timeline_prefill_gap_seconds"] == 0.0
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
        assert counters["timeline_other_device_seconds"] == 0.0
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
    """Compile time already lands inside its dispatch span's enqueue_s,
    so compile markers must not double-charge the bubble sums."""
    _fresh()
    try:
        clock = _Clock(time.time() - 0.5)
        _launch(clock, "decode", clock.t, clock.t + 0.2)
        dtl.stamp_pending(clock)
        before = dtl.bubble_snapshot()
        dtl.record_compile("decode_block", 5.0, hot=True)
        after = dtl.bubble_snapshot()
        assert after["bubble_spans_in_window"] == before["bubble_spans_in_window"]
        assert after["bubble_window_s"] == before["bubble_window_s"]
        counters = dtl.counters_snapshot()
        assert abs(counters["timeline_device_seconds"] - 0.2) < 1e-6
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
        # no launch was stamped: no device track claims a time
        pids = {e.get("pid") for e in events}
        assert dtl._PID_DEVICE not in pids
        # flight overlay: process-scoped instants carrying the trace id
        instants = [e for e in events if e.get("ph") == "i"]
        assert {e["name"] for e in instants} == {"submit", "first_token"}
        assert all(e["args"]["trace_id"] == "ab" * 16 for e in instants)
        assert all(e["s"] == "p" for e in instants)
    finally:
        _fresh()


def test_perfetto_device_track_from_stamps_and_xplane_replaces_it():
    _fresh()
    try:
        clock = _Clock(1000.0)
        _launch(clock, "decode", 1000.0, 1000.25)
        dtl.stamp_pending(clock)
        views, _ = dtl.spans_since(0)
        device = [e for e in dtl.perfetto_trace(views)["traceEvents"]
                  if e.get("pid") == dtl._PID_DEVICE and e.get("ph") == "X"]
        assert len(device) == 1 and device[0]["name"] == "decode"
        assert abs(device[0]["ts"] - 1000.0e6) < 1 and abs(device[0]["dur"] - 0.25e6) < 1
        trace = dtl.perfetto_trace(
            views,
            device_events=[{"name": "jit_decode_block", "ts_us": 1.0,
                            "dur_us": 900.0, "tid": 1}],
        )
        events = trace["traceEvents"]
        pids = {e.get("pid") for e in events}
        assert dtl._PID_DEVICE_XPLANE in pids
        assert dtl._PID_DEVICE not in pids
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
# prefill's shape rule: engine/scheduler/shapes.py chunk_rung)

def _chunk_engine(kind):
    from greedy_reference import build_engine

    return build_engine(
        kind, model_config_name="debug-1k", max_batch_size=4, max_seq_len=256, prefill_chunk=64, page_size=16,
        decode_block=2, dtype="float32", tensor_parallelism=1, prefix_cache_enable="off",
    )


@pytest.fixture(scope="module")
def chunk_engine():
    eng = _chunk_engine("packed")
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def rect_engine():
    """The same engine for a family without a packed walk (rectangles)."""
    eng = _chunk_engine("rect")
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
    # prompt lengths of one wave -> (rows, tokens, rows_dispatched, width, pad_tokens, packed_rows) of each chunk span
    "packed": {
        # one axis a dispatch: width is its token rung of {16, 32, 48, 64, 96, 128, 192, 256}
        "tail_on_one_of_three": ([69, 40, 10], [(3, 114, 1, 128, 14, 3), (1, 5, 1, 16, 11, 1)]),
        "two_tails": ([69, 40, 90, 64], [(4, 232, 1, 256, 24, 4), (2, 31, 1, 32, 1, 2)]),
        "two_narrow_tails": ([69, 70, 10], [(3, 138, 1, 192, 54, 3), (2, 11, 1, 16, 5, 2)]),
        "one_row_two_full_chunks_and_a_page": ([144], [(1, 64, 1, 64, 0, 1), (1, 64, 1, 64, 0, 1), (1, 16, 1, 16, 0, 1)]),
    },
    "rect": {
        "tail_on_one_of_three": ([69, 40, 10], [(3, 114, 4, 64, 142, 1), (1, 5, 1, 16, 11, 1)]),
        "two_tails": ([69, 40, 90, 64], [(4, 232, 4, 64, 24, 1), (2, 31, 4, 64, 225, 1)]),
        "two_narrow_tails": ([69, 70, 10], [(3, 138, 4, 64, 118, 1), (2, 11, 4, 16, 53, 1)]),
        "one_row_two_full_chunks_and_a_page": ([144], [(1, 64, 1, 64, 0, 1), (1, 64, 1, 64, 0, 1), (1, 16, 1, 16, 0, 1)]),
    },
}


@pytest.mark.parametrize("name", sorted(CHUNK_WAVES["packed"]))
@pytest.mark.parametrize("kind", sorted(CHUNK_WAVES))
def test_prefill_chunk_span_carries_rows_width_and_padding(request, kind, name):
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    engine = request.getfixturevalue("chunk_engine" if kind == "packed" else "rect_engine")
    lengths, expect = CHUNK_WAVES[kind][name]
    before, cursor = _engine_counters(), dtl.spans_since(0)[1]
    with engine.hold_admissions():
        reqs = [
            engine.submit([(i * 7 + n) % 250 + 1 for i in range(n)],
                          SamplingParams(temperature=0.0, max_tokens=2))
            for n in lengths
        ]
    for req in reqs:
        while req.out_queue.get(timeout=300) is not None:
            pass
    spans = [s for s in dtl.spans_since(cursor)[0] if s["kind"] == "prefill_chunk"]
    got = [(s["rows"], s["tokens"], s["rows_dispatched"], s["width"], s["pad_tokens"], s["packed_rows"]) for s in spans]
    assert got == expect
    for s in spans:
        assert s["pad_tokens"] == s["rows_dispatched"] * s["width"] - s["tokens"] >= 0
    after = _engine_counters()
    grew = lambda k: after.get(k, 0.0) - before.get(k, 0.0)  # noqa: E731
    assert grew("genai_engine_prefill_tokens_total") == sum(lengths)
    assert grew("genai_engine_extend_tokens_computed_total") == sum(e[2] * e[3] for e in expect)
    assert grew("genai_engine_prefill_chunks_total") == len(expect)


@pytest.mark.parametrize("kind", ["packed", "rect"])
def test_a_short_prompt_counts_what_its_dispatch_computed(request, kind):
    """A prompt under a chunk is one ``prefill_chunk`` span either way:
    packed, its tokens' rung on the one ladder; as a rectangle, one row
    at the width rung that holds it."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    engine = request.getfixturevalue("chunk_engine" if kind == "packed" else "rect_engine")
    before, cursor = _engine_counters(), dtl.spans_since(0)[1]
    list(engine.iter_ids([5] * 20, SamplingParams(temperature=0.0, max_tokens=2), timeout=300))
    after = _engine_counters()
    assert after["genai_engine_prefill_tokens_total"] - before["genai_engine_prefill_tokens_total"] == 20
    # packed: 20 live of the 32-token rung; a rectangle: one row at the 64-token width
    assert (after["genai_engine_extend_tokens_computed_total"]
            - before["genai_engine_extend_tokens_computed_total"]) == (32 if kind == "packed" else 64)
    kinds = [s["kind"] for s in dtl.spans_since(cursor)[0] if s["kind"] in ("prefill", "prefill_chunk")]
    assert kinds == ["prefill_chunk"]


def test_engine_spans_are_stamped_by_the_watcher(chunk_engine):
    """Every launch with a span hands the watcher an output: a chunked
    wave's chunks (``sub_h``) and the decode blocks (the token slab) come
    back with ``t_done`` in enqueue order, nothing negative."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    cursor = dtl.spans_since(0)[1]
    list(chunk_engine.iter_ids([3] * 100, SamplingParams(temperature=0.0, max_tokens=6), timeout=300))
    deadline = time.time() + 10
    while time.time() < deadline:
        spans = [s for s in dtl.spans_since(cursor)[0] if s["category"] == "dispatch"]
        if spans and all("t_done" in s for s in spans):
            break
        time.sleep(0.01)
    kinds = [s["kind"] for s in spans]
    assert kinds.count("prefill_chunk") == 2 and "decode" in kinds
    assert all("t_done" in s for s in spans)
    done = [s["t_done"] for s in spans]
    assert done == sorted(done)
    for s in spans:
        assert s["t_done"] >= s["t_enq"] - 1e-6
        assert s["device_s"] >= 0 and s["starved_s"] >= 0 and s["queued_s"] >= 0
        assert not (s["starved_s"] and s["queued_s"])
