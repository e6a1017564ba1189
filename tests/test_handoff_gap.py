"""What a token waited behind (PR 54): every stream hand-off's gap split,
inside the engine, into the device's time by program kind and the host's
rest (engine/dispatch_timeline.py ``DeviceClock`` / ``HandoffBlock``;
engine/llm_engine.py ``_hand_off`` / ``_emit``; docs/streaming.md).

The split is driven here the way the reader thread drives it, on a stub
engine, with a fake wall clock on both sides (the launches' stamps through
``stamp_pending``, the reader's ``now`` through the engine module's
``time``); then on real tiny engines for the fields, the series on
``/metrics`` and the stream's write lag.
"""
import queue
import threading
import time
import types

import numpy as np
import pytest

from generativeaiexamples_tpu.engine import dispatch_timeline as dtl
from generativeaiexamples_tpu.engine import llm_engine
from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

PARTS = ("gap_decode_s", "gap_extend_s", "gap_other_s", "gap_starved_s", "gap_host_s")


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return self.t


class _Handle:
    """A launch's output, ready at a stated time on the fake clock."""

    def __init__(self, clock, ready_at):
        self.clock, self.ready_at = clock, ready_at

    def block_until_ready(self):
        self.clock.t = max(self.clock.t, self.ready_at)


@pytest.fixture
def clock(monkeypatch):
    """A fresh timeline without a watcher (``stamp_pending`` stamps), and
    the engine module's wall clock replaced by the fake one."""
    dtl.stop_watcher()
    dtl.reset()
    dtl.configure(enable=True)
    fake = _Clock()
    monkeypatch.setattr(llm_engine, "time", types.SimpleNamespace(
        time=fake, perf_counter=time.perf_counter, monotonic=time.monotonic, sleep=time.sleep))
    yield fake
    dtl.stop_watcher()
    dtl.reset()


def _launch(clock, kind, t_enq, ready_at, **fields):
    """A launch enqueued at ``t_enq`` whose output is ready at
    ``ready_at``; a decode block's span gets the gap keys at dispatch."""
    counters = dict(fields)
    if kind in ("decode", "spec", "spec_block"):
        counters.update(dict.fromkeys(dtl.GAP_FIELDS, 0))
    return dtl.record_span(
        kind, t_wall=t_enq - 0.001, lock_wait_s=0.0, run_s=0.001, rows=1,
        counters=counters, handle=_Handle(clock, ready_at),
    )


def _stub(timeline=dtl):
    stub = types.SimpleNamespace(
        _stop_ids=set(), max_seq_len=4096, _release_q=queue.Queue(),
        _lock=threading.Condition(), _dtl=timeline,
    )
    stub._emit = lambda *args: LLMEngine._emit(stub, *args)
    return stub


def _request(rid=1, **kw):
    return llm_engine._Request(
        rid=rid, prompt_ids=[1], params=SamplingParams(max_tokens=10_000), **kw)


def _hand(stub, clock, at, span, reqs, tokens=(7, 8), advance=True):
    """The reader reads ``span``'s launch back at ``at``."""
    clock.t = at
    LLMEngine._hand_off(stub, [(r, np.array(tokens)) for r in reqs], span, advance)


def _gap_count():
    return dtl._M_HANDOFF_GAP.count


def _part_sums():
    return {p: dtl._M_HANDOFF_GAP_PART.labels(part=p).value for p in dtl._GAP_PARTS}


def test_a_gap_holds_the_rows_own_block_and_every_launch_since_its_previous_one(clock):
    """decode, extend, extend, decode: the second block's gap holds three
    launches, its parts are their ``device_s``, the host's part is the
    rest, and a prefill's first token only STARTS the first gap."""
    stub, req = _stub(), _request()
    observed, parts0 = _gap_count(), _part_sums()
    chunk = _launch(clock, "prefill_chunk", 1000.0, 1000.1, width=16)
    dtl.stamp_pending(clock)
    _hand(stub, clock, 1000.1, chunk, [req], tokens=(5,), advance=False)
    assert _gap_count() == observed and req.gap_clock == dtl.device_clock()
    assert req.gap_clock.extend_s == pytest.approx(0.1) and req.gap_clock.launches == 1
    assert "gap_s" not in chunk.view()  # a chunk's span carries no gap

    d1 = _launch(clock, "decode", 1000.1, 1000.15)
    dtl.stamp_pending(clock)
    _hand(stub, clock, 1000.15, d1, [req])
    v1 = d1.view()
    assert v1["handoff_rows"] == 1 and v1["gap_launches"] == 1
    assert v1["gap_s"] == pytest.approx(0.05) and v1["gap_decode_s"] == pytest.approx(0.05)

    e1 = _launch(clock, "prefill_chunk", 1000.15, 1000.25, width=16)
    e2 = _launch(clock, "prefill_chunk", 1000.25, 1000.45, width=16)
    d2 = _launch(clock, "decode", 1000.45, 1000.5)
    dtl.stamp_pending(clock)
    _hand(stub, clock, 1000.52, d2, [req])  # the reader came 20 ms after the output
    v2 = d2.view()
    assert v2["gap_launches"] == 3 and v2["handoff_rows"] == 1
    assert v2["gap_s"] == pytest.approx(0.37)
    assert v2["gap_decode_s"] == pytest.approx(d2.device_s) == pytest.approx(0.05)
    assert v2["gap_extend_s"] == pytest.approx(e1.device_s + e2.device_s) == pytest.approx(0.3)
    assert v2["gap_other_s"] == 0 and v2["gap_starved_s"] == 0
    assert v2["gap_host_s"] == pytest.approx(0.02)
    # conservation, on the spans and on /metrics: every part sums to every gap
    for view in (v1, v2):
        assert sum(view[p] for p in PARTS) == pytest.approx(view["gap_s"])
    grown = {p: v - parts0[p] for p, v in _part_sums().items()}
    assert _gap_count() == observed + 2
    assert sum(grown.values()) == pytest.approx(0.05 + 0.37)
    assert grown["extend"] == pytest.approx(0.3) and grown["decode"] == pytest.approx(0.1)


def test_the_sums_are_conserved_over_a_long_sequence(clock):
    """Blocks, chunks, a copy and a starved stretch in a seeded order:
    the sum of every part of every gap equals the sum of every gap, and
    each launch lands in exactly one gap."""
    rng = np.random.default_rng(54)
    stub, req = _stub(), _request()
    req.gap_clock, req.t_last_token = dtl.device_clock(), 1000.0
    t, gaps, parts, launches = 1000.0, 0.0, 0.0, 0
    for _ in range(40):
        for kind in rng.choice(["prefill_chunk", "prefix_state_save", "none"], size=2):
            if kind != "none":
                t_enq = t + float(rng.choice([0.0, 0.004]))  # sometimes the device starves
                t = t_enq + float(rng.uniform(0.01, 0.05))
                _launch(clock, str(kind), t_enq, t)
        block = _launch(clock, "decode", t, t + 0.02)
        t += 0.02
        dtl.stamp_pending(clock)
        _hand(stub, clock, t + float(rng.uniform(0.0, 0.003)) + 0.003, block, [req])
        view = block.view()
        gaps += view["gap_s"]
        parts += sum(view[p] for p in PARTS)
        launches += view["gap_launches"]
    # the reader's lateness varies by 3 ms: where a gap's rest would be negative it is
    # floored and owed by the next gaps, so the sums differ by what is owed at the end
    assert parts + req.gap_owed == pytest.approx(gaps, abs=1e-4) and -0.003 <= req.gap_owed <= 0
    assert launches == dtl.device_clock().launches
    clock_now = dtl.device_clock()
    cum = dtl.counters_snapshot()
    assert clock_now.decode_s == pytest.approx(cum["timeline_decode_device_seconds"])
    assert clock_now.extend_s == pytest.approx(cum["timeline_prefill_device_seconds"])
    assert clock_now.other_s == pytest.approx(
        cum["timeline_other_device_seconds"] + cum["timeline_spec_device_seconds"])
    assert clock_now.starved_s == pytest.approx(cum["timeline_gap_seconds"]) and clock_now.starved_s > 0


def test_two_rows_of_one_block_with_different_previous_handoffs(clock):
    """Row A was handed tokens by the first block; row B got its first
    token after a chunk that ran since. The second block splits each
    against its own previous clock, once a DISTINCT clock, and its span
    carries the longer gap."""
    stub, a, b = _stub(), _request(1), _request(2)
    c = _request(3)
    d1 = _launch(clock, "decode", 1000.0, 1000.05)
    dtl.stamp_pending(clock)
    a.gap_clock = c.gap_clock = dtl.DeviceClock()
    a.t_last_token = c.t_last_token = 999.95
    _hand(stub, clock, 1000.05, d1, [a, c])
    assert a.gap_clock is c.gap_clock  # one object for the rows of one launch
    chunk = _launch(clock, "prefill_chunk", 1000.05, 1000.25, width=16)
    dtl.stamp_pending(clock)
    _hand(stub, clock, 1000.25, chunk, [b], tokens=(5,), advance=False)
    d2 = _launch(clock, "decode", 1000.25, 1000.3)
    dtl.stamp_pending(clock)
    observed, parts0 = _gap_count(), _part_sums()
    clock.t = 1000.3
    block = dtl.HandoffBlock(d2)
    for req in (a, b, c):
        block.handoff(req, clock.t)
    assert len(block._parts) == 2  # three rows, two distinct previous clocks
    block.close()
    view = d2.view()
    assert view["handoff_rows"] == 3 and view["gap_launches"] == 2
    assert view["gap_s"] == pytest.approx(0.25) and view["gap_extend_s"] == pytest.approx(0.2)
    grown = {p: v - parts0[p] for p, v in _part_sums().items()}
    assert _gap_count() == observed + 3
    # a and c waited behind the chunk and the block, b behind the block alone
    assert grown["extend"] == pytest.approx(0.4) and grown["decode"] == pytest.approx(0.15)
    assert grown["host"] == pytest.approx(0.0, abs=1e-9)


def test_a_reader_that_arrives_before_its_own_launchs_stamp_waits_for_it(monkeypatch):
    """The watcher and the reader wake on the same output. A reader that
    comes first waits for the stamp, so the launch counts in THIS gap."""
    dtl.stop_watcher()
    dtl.reset()
    dtl.configure(enable=True)
    monkeypatch.setattr(dtl, "_STAMP_WAIT_S", 30.0)
    ready = threading.Event()
    try:
        dtl.start_watcher()
        handle = types.SimpleNamespace(block_until_ready=lambda: ready.wait(30))
        span = dtl.record_span(
            "decode", t_wall=time.time(), lock_wait_s=0.0, run_s=0.0, rows=1,
            counters=dict.fromkeys(dtl.GAP_FIELDS, 0), handle=handle)
        stub, req = _stub(), _request()
        req.gap_clock, req.t_last_token = dtl.device_clock(), time.time()
        reader = threading.Thread(
            target=LLMEngine._hand_off, args=(stub, [(req, np.array([7, 8]))], span))
        reader.start()
        reader.join(0.2)
        assert reader.is_alive() and span.t_done is None  # it waits; nothing stamped yet
        ready.set()
        reader.join(30)
        assert not reader.is_alive()
        view = span.view()
        assert view["gap_launches"] == 1 and view["device_s"] > 0.15
        assert view["gap_decode_s"] == pytest.approx(view["device_s"], abs=1e-5)
        assert req.gap_clock.launches == 1  # and not in the next gap
    finally:
        ready.set()
        dtl.stop_watcher()
        dtl.reset()


@pytest.mark.parametrize("gap_s, usual_s, recorded", [
    (1.5, 0.1, True),    # over a second and over 8x the running median
    (0.9, 0.1, False),   # 9x the median, under a second
    (1.5, 0.5, False),   # over a second, 3x the median
])
def test_a_long_gap_leaves_one_stream_gap_record(clock, gap_s, usual_s, recorded):
    stub, req = _stub(), _request(41)
    req.gap_clock, req.t_last_token = dtl.device_clock(), 1000.0
    t = 1000.0
    for _ in range(4):  # the usual cadence: a block a gap
        block = _launch(clock, "decode", t, t + usual_s)
        t += usual_s
        dtl.stamp_pending(clock)
        _hand(stub, clock, t, block, [req])
    chunk = _launch(clock, "prefill_chunk", t, t + gap_s - 0.05, rows_dispatched=4, width=512)
    block = _launch(clock, "decode", t + gap_s - 0.05, t + gap_s)
    dtl.stamp_pending(clock)
    _hand(stub, clock, t + gap_s, block, [req, _request(42)])
    records = [v for v in dtl.spans_since(0)[0] if v["kind"] == "stream_gap"]
    assert len(records) == (1 if recorded else 0)
    if recorded:
        rec = records[0]
        assert rec["category"] == "stall" and rec["duration_s"] == pytest.approx(gap_s)
        assert rec["gap_extend_s"] == pytest.approx(gap_s - 0.05) and rec["gap_launches"] == 2
        assert rec["rid"] == 41 and [l["kind"] for l in rec["launches"]] == ["prefill_chunk", "decode"]
        assert rec["launches"][0]["width"] == 512 and rec["launches"][0]["seq"] == chunk.seq
        # the block's own span keeps its eight fields, not the record's list
        assert "launches" not in block.view() and block.view()["gap_s"] == pytest.approx(gap_s)
        assert chunk.view()["device_s"] == pytest.approx(gap_s - 0.05)


def test_emit_reads_the_wall_clock_once_a_call(clock):
    stub, req = _stub(), _request(t_submit=999.0)
    block = dtl.HandoffBlock()
    before = llm_engine._M_TOKEN_LATENCY.count
    for n, tokens in enumerate(([5], [1, 2, 3, 4, 5, 6, 7, 8], [9, 10]), start=1):
        clock.t += 0.1
        LLMEngine._emit(stub, req, np.array(tokens), True, block)
        assert clock.reads == n
    assert req.t_last_token == clock.t and req.generated == 11
    # the per-token histogram keeps its observations: one a token after the first
    assert llm_engine._M_TOKEN_LATENCY.count - before == 10
    assert req.out_queue.take_all(0) == [5, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
    assert req.out_queue.t_taken == pytest.approx(1000.1)  # the OLDEST un-taken put's time


def test_with_the_timeline_off_nothing_is_split(clock):
    """What the engine resolves at init with GENAI_DISPATCH_TIMELINE=off:
    no block, no observation, no time on the queue."""
    stub, req = _stub(timeline=None), _request()
    observed, parts0 = _gap_count(), _part_sums()
    for at in (1000.1, 1000.2, 1000.3):
        _hand(stub, clock, at, None, [req])
    assert _gap_count() == observed and _part_sums() == parts0 and req.gap_clock is None
    req.out_queue.take_all(0)
    assert req.out_queue.t_taken == 0.0 and req.generated == 6


def test_a_spanned_launch_is_named_on_the_profilers_host_track(clock, monkeypatch):
    names = []

    class Annotation:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(dtl, "_TRACE_ANNOTATION", Annotation)
    first = _launch(clock, "prefill_chunk", 1000.0, 1000.1)
    second = _launch(clock, "decode", 1000.1, 1000.2)
    assert names == [f"prefill_chunk#{first.seq}", f"decode#{second.seq}"]


# --------------------------------------------------------------------------- #
# served requests: the fields on the spans, the series on /metrics


_TINY = dict(
    max_batch_size=3, max_seq_len=128, prefill_chunk=16, decode_block=2, decode_runahead=1,
    dtype="float32", tensor_parallelism=1, page_size=8, watchdog_stall_s=0.0,
)


@pytest.fixture(scope="module", params=["debug", "phi4flash-debug"])
def engine(request):
    from generativeaiexamples_tpu.config import EngineConfig

    dtl.configure(enable=True)
    # an engine that is not warmed up compiles while it serves, and a compile can keep
    # the watcher off the interpreter for longer than a reader waits for a stamp
    waits, dtl._STAMP_WAIT_S = dtl._STAMP_WAIT_S, 30.0
    request.addfinalizer(lambda: setattr(dtl, "_STAMP_WAIT_S", waits))
    extra = {} if request.param == "debug" else {"prefix_cache_enable": "off"}
    eng = LLMEngine(EngineConfig(model_config_name=request.param, **_TINY, **extra))
    yield eng
    eng.shutdown()


def test_decode_spans_carry_the_eight_fields_after_served_requests(engine):
    """The dense family and a fixed-state family: two streams, the second
    admitted while the first decodes, so a gap holds a neighbour's chunk."""
    since = dtl.cursor()
    observed, parts0, gap_sum0 = _gap_count(), _part_sums(), dtl._M_HANDOFF_GAP.sum
    params = SamplingParams(temperature=0.0, max_tokens=24)
    a = engine.submit([3, 5, 8, 13, 4], params)
    assert a.out_queue.get(timeout=300) is not None
    b = engine.submit([(i * 7) % 250 + 1 for i in range(41)], params)
    for req in (b, a):
        while req.out_queue.get(timeout=300) is not None:
            pass
    time.sleep(0.3)  # the reader writes a block's fields after its last put
    spans = [s for s in dtl.spans_since(since, limit=10_000)[0] if s["kind"] == "decode"]
    assert spans and all(set(dtl.GAP_FIELDS) <= set(s) for s in spans)
    read = [s for s in spans if s["handoff_rows"]]
    assert read and all(s["gap_s"] > 0 and s["gap_launches"] >= 1 for s in read)
    assert any(s["gap_extend_s"] > 0 for s in read)  # a neighbour's chunk in a gap
    assert max(s["handoff_rows"] for s in read) == 2
    # /metrics: one observation a hand-off after a request's first; the parts sum to
    # the gaps plus what the streams still owed when they ended
    handoffs = sum(s["handoff_rows"] for s in read)
    assert _gap_count() - observed == handoffs
    grown = sum(_part_sums().values()) - sum(parts0.values())
    assert grown >= dtl._M_HANDOFF_GAP.sum - gap_sum0 - 1e-6 > 0


def test_metrics_show_the_new_families_and_a_read_stream_grows_the_write_lag(engine):
    from generativeaiexamples_tpu.utils import metrics as metrics_mod

    lag = llm_engine._M_WRITE_LAG
    before = lag.count
    blocks = 0
    for item in engine.stream_text([3, 5, 8, 13, 4], SamplingParams(temperature=0.0, max_tokens=12)):
        item.written()  # what server/api.py calls once the frames are on the wire
        blocks += 1
    assert blocks >= 1 and lag.count - before == blocks and lag.sum > 0
    text = metrics_mod.get_registry().render()
    for series in (
        "genai_stream_handoff_gap_seconds_bucket",
        'genai_stream_handoff_gap_part_seconds_total{part="extend"}',
        'genai_stream_handoff_gap_part_seconds_total{part="host"}',
        "genai_stream_write_lag_seconds_count",
        "genai_engine_token_latency_seconds_count",
    ):
        assert series in text
