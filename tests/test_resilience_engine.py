"""Engine-level resilience: queue cap, abort/slot release on consumer
disconnect, the dispatch-loop watchdog, and shutdown join detection.

Uses the tiny debug model on CPU (same budget class as the tier-1
warmup test in test_server_api.py); one shared engine plus one
watchdog-configured engine.
"""
import asyncio
import threading
import time

import pytest

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine import llm_engine
from generativeaiexamples_tpu.engine.llm_engine import (
    _M_ABORTS,
    _M_SLOTS_IN_USE,
    ENGINE_WEDGED,
    LLMEngine,
    SamplingParams,
)
from generativeaiexamples_tpu.utils import faults

TINY = dict(
    model_config_name="debug",
    max_batch_size=2,
    max_seq_len=64,
    prefill_chunk=16,
    decode_block=4,
    dtype="float32",
    tensor_parallelism=1,
    page_size=16,
    watchdog_stall_s=0.0,  # the shared engine keeps the watchdog off
)

PROMPT = [5 + i for i in range(8)]


def _wait(cond, timeout=60.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


def _drain(req):
    while req.out_queue.get(timeout=60) is not None:
        pass


@pytest.fixture(scope="module")
def eng():
    engine = LLMEngine(EngineConfig(max_queued_requests=2, **TINY))
    yield engine
    engine.shutdown()
    ENGINE_WEDGED.clear()


def test_submit_queue_cap_raises_typed_overload(eng):
    from generativeaiexamples_tpu.utils.resilience import EngineOverloaded

    params = SamplingParams(temperature=0.0, max_tokens=2)
    with eng.hold_admissions():
        r1 = eng.submit(PROMPT, params)
        r2 = eng.submit(PROMPT, params)
        assert eng.queue_depth() == 2
        with pytest.raises(EngineOverloaded):
            eng.submit(PROMPT, params)
    _drain(r1)
    _drain(r2)
    _wait(lambda: not eng.is_decoding(), msg="decode drain")
    assert eng.queue_depth() == 0


def test_stream_close_aborts_and_frees_slot(eng):
    """Closing the text stream mid-generation (the disconnect path)
    aborts the engine request: the slot frees well before max_tokens."""
    aborts_before = _M_ABORTS.value
    gen = eng.stream_text(
        PROMPT, SamplingParams(temperature=0.0, max_tokens=48)
    )
    first = next(gen)
    assert isinstance(first, str)
    gen.close()  # consumer disconnect -> finally -> engine.abort
    assert _M_ABORTS.value == aborts_before + 1
    _wait(
        lambda: not eng.is_decoding() and _M_SLOTS_IN_USE.value == 0,
        msg="slot release after abort",
    )
    assert len(eng._free_slots) == eng.num_slots


def test_unstarted_stream_generator_still_aborts_on_gc(eng):
    """stream_text submits eagerly; if the caller never starts the
    generator (e.g. resp.prepare() failed on a gone client), close()
    skips the finally — the weakref finalizer must abort instead, so
    the request never burns its slot to max_tokens."""
    import gc

    aborts_before = _M_ABORTS.value
    gen = eng.stream_text(
        PROMPT, SamplingParams(temperature=0.0, max_tokens=48)
    )
    del gen
    gc.collect()
    _wait(lambda: _M_ABORTS.value == aborts_before + 1, timeout=10,
          msg="finalizer abort of unstarted stream")
    _wait(
        lambda: not eng.is_decoding() and _M_SLOTS_IN_USE.value == 0,
        msg="slot release after finalizer abort",
    )


def test_abort_pending_request_unblocks_consumer(eng):
    params = SamplingParams(temperature=0.0, max_tokens=4)
    with eng.hold_admissions():
        req = eng.submit(PROMPT, params)
        assert eng.abort(req.rid)
        assert req.out_queue.get(timeout=5) is None  # end sentinel
        assert req.finished and eng.queue_depth() == 0
    assert not eng.abort(req.rid)  # already finished -> False


def test_ingest_window_coordinates_with_dispatch_loop(eng):
    """The retrieval micro-batcher's ingest gate, on the scheduler-
    policy seam (docs/retrieval_batching.md, docs/scheduler.md): under
    the default unified policy ``scheduler.ingest_window`` blocks while
    a request occupies a decode slot, times out honestly, and wakes
    when the dispatch loop frees the last slot — the behavior the old
    engine-global ``wait_decode_idle`` condition hook provided, now
    owned by the policy (identical under ``unified``)."""
    _wait(lambda: not eng.is_decoding(), msg="engine to drain prior tests")
    assert eng.scheduler.ingest_window(0.0)  # idle engine: immediate
    params = SamplingParams(temperature=0.0, max_tokens=40)
    reqs = [eng.submit(PROMPT, params) for _ in range(2)]  # queue cap is 2
    deadline = time.time() + 60
    while not eng.is_decoding() and time.time() < deadline:
        pass  # tight poll: the busy window can be tens of ms when warm
    # A bounded wait while busy must not report an open window (True is
    # only correct when decode genuinely drained in the window).
    idle = eng.scheduler.ingest_window(0.001)
    assert (not idle) or (not eng.is_decoding())
    done = threading.Event()

    def waiter():
        if eng.scheduler.ingest_window(60.0):
            done.set()

    t = threading.Thread(target=waiter)
    t.start()
    for req in reqs:
        _drain(req)
    t.join(timeout=60)
    assert done.is_set()  # slot release notified the waiter
    assert not eng.is_decoding()
    # The engine-global hook is gone — the policy seam is the only
    # coordination point (the disagg policy redefines the window as
    # prefill-tier-idle without touching the batcher).
    assert not hasattr(eng, "wait_decode_idle")


def test_aiter_threaded_disconnect_aborts_engine_request(eng):
    """The satellite contract for server/api.py _aiter_threaded: when
    the SSE consumer goes away, the producer unblocks, the generator
    chain closes, the engine request is aborted, and no slot leaks
    (slot-occupancy gauge returns to zero)."""
    from generativeaiexamples_tpu.server.api import _aiter_threaded

    aborts_before = _M_ABORTS.value

    async def drive():
        gen = eng.stream_text(
            PROMPT, SamplingParams(temperature=0.0, max_tokens=48)
        )
        agen = _aiter_threaded(gen)
        got = []
        async for chunk in agen:
            got.append(chunk)
            break  # consumer disconnects after the first chunk
        await agen.aclose()
        return got

    got = asyncio.run(drive())
    assert got and isinstance(got[0], str)
    _wait(lambda: _M_ABORTS.value == aborts_before + 1, timeout=30,
          msg="abort on generator close")
    _wait(
        lambda: not eng.is_decoding() and _M_SLOTS_IN_USE.value == 0,
        msg="no leaked slots after disconnect",
    )
    # producer threads are daemons named sse-producer; none should stay
    _wait(
        lambda: not any(
            t.name == "sse-producer" and t.is_alive()
            for t in threading.enumerate()
        ),
        timeout=30,
        msg="producer thread exit",
    )


def test_engine_stream_hands_over_blocks_and_its_spans_carry_the_backlog(eng):
    """The real engine behind _aiter_threaded (docs/streaming.md): with
    decode_block=4 a stream's tokens arrive several a hand-off, every
    id is reported written once its handler comes back, and decode
    dispatch spans carry stream_backlog_tokens."""
    from generativeaiexamples_tpu.engine import dispatch_timeline as dtl
    from generativeaiexamples_tpu.engine.tokenizer import TokenBlock
    from generativeaiexamples_tpu.server.api import _aiter_threaded

    since = dtl.cursor()
    h0, t0 = llm_engine._M_HANDOFFS.value, llm_engine._M_HANDOFF_TOKENS.value
    seen = []

    async def drive():
        gen = eng.stream_text(PROMPT, SamplingParams(temperature=0.0, max_tokens=40))
        async for chunk in _aiter_threaded(gen):
            seen.extend(eng._streams.values())
            assert isinstance(chunk, TokenBlock)
            await asyncio.sleep(0.005)  # a slow socket: the reader gets ahead

    asyncio.run(drive())
    req = seen[0]
    assert all(r is req for r in seen)
    handoffs = llm_engine._M_HANDOFFS.value - h0
    tokens = llm_engine._M_HANDOFF_TOKENS.value - t0
    assert req.queued == tokens == req.written  # nothing left behind
    assert tokens >= 30 and tokens / handoffs >= 2  # blocks, not tokens
    assert not eng._streams
    spans, _ = dtl.spans_since(since)
    backlogs = [v["stream_backlog_tokens"] for v in spans if v["kind"] == "decode"]
    assert backlogs and all(0 <= b <= 40 for b in backlogs)


def test_stream_timeout_modes_stall_vs_absolute():
    """timeout=None applies stream_timeout_s as a STALL deadline per
    awaited token — a healthy stream longer than the knob completes —
    while an explicit timeout is an absolute whole-stream budget that
    terminates even a fast, never-stalling stream (per-request
    deadlines). Pure host: drives _stream_from with a scripted queue."""
    from types import SimpleNamespace

    stub = LLMEngine.__new__(LLMEngine)
    stub.engine_config = SimpleNamespace(stream_timeout_s=1.0)
    stub.tokenizer = SimpleNamespace(decode=lambda ids: "x" * len(ids))
    stub.abort = lambda req: None
    stub._streams = {}
    params = SamplingParams(temperature=0.0, max_tokens=8)

    def scripted_req(n_tokens, interval, end):
        req = SimpleNamespace(out_queue=llm_engine._TokenQueue(), error=None)

        def feed():
            for _ in range(n_tokens):
                time.sleep(interval)
                req.out_queue.put(7)
            if end:
                req.out_queue.put(llm_engine._END)

        threading.Thread(target=feed, daemon=True).start()
        return req

    # stall mode: 15 tokens over ~1.5 s total > the 1.0 s knob, but no
    # single inter-token gap (0.1 s, 10x margin against scheduler
    # hiccups) comes near it -> the stream completes
    req = scripted_req(15, 0.1, end=True)
    assert "".join(stub._stream_from(req, params, None)) == "x" * 15

    # stall mode: an actual stall (no next token inside the window)
    req = scripted_req(1, 0.0, end=False)
    with pytest.raises(TimeoutError):
        list(stub._stream_from(req, params, None))

    # absolute mode: tokens keep flowing faster than any get() floor,
    # yet the whole-stream budget still terminates the stream
    req = scripted_req(100, 0.01, end=False)
    with pytest.raises(TimeoutError):
        list(stub._stream_from(req, params, 0.15))


def test_new_engine_clears_stale_wedged_global():
    """A wedge marked by a prior engine instance (watchdog or failed
    shutdown join) must not pin readiness at 503 for a freshly built
    replacement engine."""
    ENGINE_WEDGED.set()
    engine = LLMEngine(EngineConfig(**TINY))
    try:
        assert not llm_engine.engine_wedged()
        # the new engine still serves
        req = engine.submit(PROMPT, SamplingParams(temperature=0.0, max_tokens=2))
        _drain(req)
    finally:
        engine.shutdown()
        ENGINE_WEDGED.clear()


def test_watchdog_flags_and_clears_wedged_state():
    """A hang injected into the dispatch loop with work outstanding
    flips the wedged gauge + readiness; when the loop resumes, the
    watchdog clears it."""
    faults.reset()
    ENGINE_WEDGED.clear()
    engine = LLMEngine(
        EngineConfig(**{**TINY, "watchdog_stall_s": 0.5})
    )
    try:
        assert not llm_engine.engine_wedged()
        faults.configure("engine.dispatch", "hang", at=1, count=1, value=3.0)
        req = engine.submit(PROMPT, SamplingParams(temperature=0.0, max_tokens=2))
        _wait(lambda: llm_engine.engine_wedged(), timeout=3.0,
              msg="watchdog wedge detection")
        assert engine._wedged
        # the hang ends; the request completes and the state self-clears
        _drain(req)
        _wait(lambda: not llm_engine.engine_wedged(), timeout=30,
              msg="wedged state clears after recovery")
    finally:
        faults.reset()
        engine.shutdown()
        ENGINE_WEDGED.clear()


def test_shutdown_detects_stuck_threads(caplog):
    """shutdown() must not silently return when join() leaves a live
    thread: it logs an error, flips the wedged state, and returns
    False (pure-host unit: no engine build)."""

    class _StuckThread:
        name = "llm-decode"

        def join(self, timeout=None):
            pass

        def is_alive(self):
            return True

    class _SchedulerStub:
        def stop(self):
            return True

    stub = LLMEngine.__new__(LLMEngine)
    stub._lock = threading.Condition()
    stub._running = True
    stub._wd_stop = threading.Event()
    stub._thread = _StuckThread()
    stub._reader = _StuckThread()
    stub._watchdog = None
    stub._wedged = False
    stub.scheduler = _SchedulerStub()
    try:
        import logging

        with caplog.at_level(logging.ERROR):
            assert stub.shutdown() is False
        assert stub._wedged
        assert llm_engine.engine_wedged()
        assert any("join timeout" in r.message for r in caplog.records)
    finally:
        ENGINE_WEDGED.clear()
