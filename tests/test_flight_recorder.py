"""Flight recorder: ring semantics, slow-request capture, and the
acceptance contract — a request delayed via deterministic fault
injection yields a slow-request capture whose timeline covers
submit → admission → prefill → decode → finish, retrievable from
GET /internal/requests/{id} and linked to its trace id.

The engine half uses the tiny debug model on CPU (same budget class as
tests/test_resilience_engine.py).
"""
import json
import time

import pytest

from generativeaiexamples_tpu.utils import faults
from generativeaiexamples_tpu.utils import flight_recorder as fr


@pytest.fixture(autouse=True)
def _clean_recorder():
    fr.reset()
    yield
    fr.reset()
    faults.reset()


# --------------------------------------------------------------------------- #
# Pure recorder mechanics (no engine)


def test_record_lifecycle_and_views():
    rec = fr.start(trace_id="ab" * 16)
    assert rec is not None
    fr.bind(rec)
    fr.event("http_request", path="/generate")
    assert fr.current() is rec
    fr.unbind()
    assert fr.current() is None
    rec.event("admitted")
    assert [s["request_id"] for s in fr.inflight()] == [rec.request_id]
    fr.finish(rec)
    assert fr.inflight() == []
    recents = fr.recent()
    assert len(recents) == 1 and recents[0]["done"]
    assert recents[0]["trace_id"] == "ab" * 16
    timeline = fr.get_timeline(rec.request_id)
    names = [e["event"] for e in timeline["timeline"]]
    assert names == ["http_request", "admitted", "finish"]


def test_disabled_recorder_is_noop():
    fr.configure(enable=False)
    assert fr.start() is None
    fr.event("anything")  # must not raise
    fr.event_rid(123, "anything")
    fr.finish_rid(123)
    assert fr.inflight() == [] and fr.recent() == []


def test_rid_mapping_and_engine_ownership():
    rec = fr.start(owner="engine")
    fr.map_rid(7, rec)
    fr.event_rid(7, "submit", engine_rid=7)
    fr.finish_rid(7, "finish")
    assert rec.done and rec.outcome == "finish"
    # rid resolves through the completed ring too
    assert fr.get_timeline("7")["request_id"] == rec.request_id


def test_server_owned_record_survives_engine_finish():
    """One server record may span several engine rids (query
    decomposition): engine completion unmaps the rid but must NOT
    retire the record."""
    rec = fr.start(owner="server")
    fr.map_rid(1, rec)
    fr.map_rid(2, rec)
    fr.finish_rid(1)
    assert not rec.done
    fr.finish_rid(2)
    assert not rec.done
    fr.finish(rec)
    assert rec.done
    names = [e["event"] for e in fr.get_timeline(rec.request_id)["timeline"]]
    assert names.count("engine_finish") == 2 and names[-1] == "finish"


def test_eviction_drops_whole_timelines():
    """Ring overflow must evict entire records — a summary that survives
    eviction always resolves to a complete submit→finish timeline."""
    fr.configure(capacity=4)
    for i in range(10):
        rec = fr.start(request_id=f"req-{i}", owner="engine")
        rec.event("submit", rid=i)
        fr.finish(rec)
    recents = fr.recent()
    assert len(recents) == 4  # oldest 6 fully evicted
    for summary in recents:
        timeline = fr.get_timeline(summary["request_id"])
        names = [e["event"] for e in timeline["timeline"]]
        assert names[0] == "submit" and names[-1] == "finish"
    # evicted ids are gone entirely, not partially
    assert fr.get_timeline("req-0") is None


def test_event_cap_counts_drops():
    rec = fr.start()
    for i in range(fr.EVENT_CAP + 10):
        rec.event("e", i=i)
    assert len(rec.events) == fr.EVENT_CAP
    assert rec.dropped == 10


def test_completion_cursor_monotonic_and_incremental():
    """?since cursor semantics: every finish bumps the process cursor,
    completed_since(c) returns FULL timelines for seq > c oldest-first,
    and an idle poll returns an unchanged cursor."""
    assert fr.cursor() == 0
    for i in range(3):
        rec = fr.start(request_id=f"req-{i}")
        rec.event("submit", rid=i)
        fr.finish(rec)
    assert fr.cursor() == 3
    timelines, cur = fr.completed_since(0)
    assert cur == 3
    assert [t["request_id"] for t in timelines] == ["req-0", "req-1", "req-2"]
    assert [t["seq"] for t in timelines] == [1, 2, 3]
    # full timelines, not summaries
    assert [e["event"] for e in timelines[0]["timeline"]] == ["submit", "finish"]
    # incremental: only records after the cursor
    timelines, cur = fr.completed_since(2)
    assert [t["request_id"] for t in timelines] == ["req-2"] and cur == 3
    # idle poll: nothing new, cursor unchanged
    timelines, cur = fr.completed_since(3)
    assert timelines == [] and cur == 3
    # in-flight records are invisible to the tail until they finish
    live = fr.start(request_id="live")
    assert fr.completed_since(0)[1] == 3
    fr.finish(live)
    timelines, cur = fr.completed_since(3)
    assert [t["request_id"] for t in timelines] == ["live"] and cur == 4


def test_completion_cursor_limit_pages_oldest_first():
    for i in range(5):
        rec = fr.start(request_id=f"req-{i}")
        fr.finish(rec)
    page, cur = fr.completed_since(0, limit=2)
    assert [t["request_id"] for t in page] == ["req-0", "req-1"]
    assert cur == 5  # cursor is the process head even on a capped page
    # resume from the newest seq actually received
    page2, _ = fr.completed_since(page[-1]["seq"], limit=2)
    assert [t["request_id"] for t in page2] == ["req-2", "req-3"]


def test_completion_cursor_survives_eviction_whole():
    """A record evicted between polls is simply gone — the tail never
    sees a partial timeline, and the cursor keeps advancing."""
    fr.configure(capacity=2)
    for i in range(6):
        rec = fr.start(request_id=f"req-{i}")
        rec.event("submit", rid=i)
        fr.finish(rec)
    timelines, cur = fr.completed_since(0)
    assert cur == 6
    assert [t["request_id"] for t in timelines] == ["req-4", "req-5"]
    for tl in timelines:
        assert [e["event"] for e in tl["timeline"]] == ["submit", "finish"]


def test_completion_cursor_slow_ring():
    fr.configure(slow_total_ms=1.0)
    slow_rec = fr.start(request_id="slow-1")
    time.sleep(0.005)
    fr.finish(slow_rec)
    fr.configure(slow_total_ms=60000.0)
    fast = fr.start(request_id="fast-1")
    fr.finish(fast)
    timelines, cur = fr.completed_since(0, slow=True)
    assert [t["request_id"] for t in timelines] == ["slow-1"]
    assert cur == 2  # cursor counts ALL completions, not just slow ones


def test_requests_endpoint_since_and_slow_filters():
    """GET /internal/requests?since=/?slow= — the loadgen tail contract:
    incremental pages of full timelines, cursor in every response,
    400 on a garbage cursor."""
    import asyncio

    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.server.observability import (
        add_observability_routes,
    )

    fr.configure(slow_total_ms=1.0)
    slow_rec = fr.start(request_id="slow-1")
    time.sleep(0.005)
    fr.finish(slow_rec)
    fr.configure(slow_total_ms=60000.0)
    for i in range(3):
        rec = fr.start(request_id=f"req-{i}")
        rec.event("submit", rid=i)
        fr.finish(rec)

    async def scenario():
        app = web.Application()
        add_observability_routes(app)
        async with TestClient(TestServer(app)) as client:
            # default view now carries the cursor
            full = await (await client.get("/internal/requests")).json()
            assert full["cursor"] == 4
            # incremental tail: full timelines after the cursor
            tail = await (
                await client.get("/internal/requests?since=1")
            ).json()
            assert [t["request_id"] for t in tail["timelines"]] == [
                "req-0", "req-1", "req-2",
            ]
            assert tail["cursor"] == 4
            assert all("timeline" in t for t in tail["timelines"])
            # limit pages the tail
            page = await (
                await client.get("/internal/requests?since=0&limit=2")
            ).json()
            assert len(page["timelines"]) == 2
            # slow=1 restricts both modes to the slow ring
            slow_tail = await (
                await client.get("/internal/requests?since=0&slow=1")
            ).json()
            assert [t["request_id"] for t in slow_tail["timelines"]] == ["slow-1"]
            slow_view = await (
                await client.get("/internal/requests?slow=1")
            ).json()
            assert "recent" not in slow_view and "in_flight" not in slow_view
            assert [s["request_id"] for s in slow_view["slow"]] == ["slow-1"]
            # garbage cursor is a 400, not a silent full fetch
            bad = await client.get("/internal/requests?since=banana")
            assert bad.status == 400

    asyncio.run(scenario())


def test_slow_capture_thresholds_and_jsonl(tmp_path):
    path = tmp_path / "slow.jsonl"
    fr.configure(slow_total_ms=1.0, capture_path=str(path))
    rec = fr.start(trace_id="cd" * 16)
    rec.event("submit")
    time.sleep(0.01)
    fr.finish(rec)
    assert rec.slow
    assert fr.slow_captures() and fr.slow_captures()[0]["slow"]
    line = json.loads(path.read_text().splitlines()[0])
    assert line["trace_id"] == "cd" * 16
    assert [e["event"] for e in line["timeline"]][-1] == "finish"
    # fast request below the threshold: no capture
    fr.configure(slow_total_ms=60000.0)
    rec2 = fr.start()
    fr.finish(rec2)
    assert not rec2.slow


# --------------------------------------------------------------------------- #
# Engine integration: deterministic fault injection must produce a slow
# capture with the complete submit→finish chain (acceptance criterion).

TINY = dict(
    model_config_name="debug",
    max_batch_size=2,
    max_seq_len=64,
    prefill_chunk=16,
    decode_block=4,
    dtype="float32",
    tensor_parallelism=1,
    page_size=16,
    watchdog_stall_s=0.0,
)


@pytest.fixture(scope="module")
def eng():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    engine = LLMEngine(EngineConfig(**TINY))
    yield engine
    engine.shutdown()


def test_delayed_request_yields_complete_slow_capture(eng, tmp_path):
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    fr.reset()
    path = tmp_path / "slow.jsonl"
    fr.configure(slow_ttft_ms=20.0, capture_path=str(path))
    # Delay every engine dispatch-loop pass a little: TTFT crosses the
    # threshold deterministically, decode still completes.
    faults.configure("engine.dispatch", "delay", at=1, count=0, value=0.03)
    try:
        req = eng.submit([5] * 8, SamplingParams(temperature=0.0, max_tokens=4))
        while req.out_queue.get(timeout=60) is not None:
            pass
    finally:
        faults.reset()
    # the reader thread finishes the record asynchronously
    deadline = time.time() + 30
    while time.time() < deadline:
        slow = fr.slow_captures()
        if slow:
            break
        time.sleep(0.02)
    assert slow, "no slow capture after the injected dispatch delay"
    timeline = fr.get_timeline(slow[0]["request_id"])
    names = [e["event"] for e in timeline["timeline"]]
    # the full lifecycle chain, in order
    for earlier, later in zip(
        ["submit", "admit", "decode_join", "first_token", "finish"][:-1],
        ["admit", "decode_join", "first_token", "finish"],
    ):
        assert names.index(earlier) < names.index(later), names
    assert "prefill_chunk" in names and "prefill_wave" not in names, names
    assert timeline["ttft_s"] >= 0.02
    # the JSONL export carries the same chain
    exported = json.loads(path.read_text().splitlines()[0])
    assert [e["event"] for e in exported["timeline"]] == names


def test_endpoint_serves_fault_delayed_timeline(eng, tmp_path):
    """GET /internal/requests/{id} returns the slow timeline, and the
    summary list links it."""
    import asyncio

    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams
    from generativeaiexamples_tpu.server.observability import (
        add_observability_routes,
    )

    fr.reset()
    fr.configure(slow_ttft_ms=15.0)
    faults.configure("engine.dispatch", "delay", at=1, count=0, value=0.03)
    try:
        req = eng.submit([7] * 8, SamplingParams(temperature=0.0, max_tokens=4))
        while req.out_queue.get(timeout=60) is not None:
            pass
    finally:
        faults.reset()
    deadline = time.time() + 30
    while time.time() < deadline and not fr.slow_captures():
        time.sleep(0.02)
    assert fr.slow_captures()

    async def scenario():
        app = web.Application()
        add_observability_routes(app)
        async with TestClient(TestServer(app)) as client:
            resp = await client.get("/internal/requests")
            body = await resp.json()
            assert resp.status == 200 and body["slow"]
            request_id = body["slow"][0]["request_id"]
            detail = await client.get(f"/internal/requests/{request_id}")
            assert detail.status == 200
            timeline = await detail.json()
            missing = await client.get("/internal/requests/nonexistent")
            assert missing.status == 404
            return timeline

    timeline = asyncio.run(scenario())
    names = [e["event"] for e in timeline["timeline"]]
    assert names[0] == "submit" and names[-1] == "finish"
    assert "first_token" in names


def test_engine_requests_never_leave_partial_timelines_in_view(eng):
    """Ring churn under live engine traffic: every summary the view
    returns resolves to a timeline that starts at submit and ends at
    finish — eviction can never expose a truncated one."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    fr.reset()
    fr.configure(capacity=3)
    reqs = [
        eng.submit([9 + i] * 6, SamplingParams(temperature=0.0, max_tokens=2))
        for i in range(8)
    ]
    for req in reqs:
        while req.out_queue.get(timeout=60) is not None:
            pass
    deadline = time.time() + 30
    while time.time() < deadline and len(fr.recent()) < 3:
        time.sleep(0.02)
    recents = fr.recent()
    assert len(recents) == 3
    for summary in recents:
        timeline = fr.get_timeline(summary["request_id"])
        assert timeline is not None
        names = [e["event"] for e in timeline["timeline"]]
        assert names[0] == "submit" and names[-1] == "finish", names
