"""Engine drain-with-checkpoint + snapshot restore (tier-1, tiny CPU
debug engines — the test_resilience_engine budget class).

Pins the ISSUE 19 drain contracts:

- a mid-decode drain checkpoints every slotted request into the spool
  and terminates its stream with the typed ``RequestPreempted``
  carrying the snapshot id;
- restoring that snapshot on a (resumed) engine continues the stream
  TOKEN-IDENTICALLY to an uninterrupted run (the cross-engine matrix
  lives in the slow tier: test_preempt_restore_matrix);
- restore refuses config-fingerprint and KV-geometry drift, and
  refuses outright while the engine drains;
- never-admitted (pending) requests preempt replay-only;
- a KVHandoff sitting in the disagg TransferQueue at drain time is
  checkpointed or completed, NEVER dropped — including the
  abort-during-drain case;
- the drain lifecycle endpoints on the model server wire the whole
  workflow (drain summary, spool inventory, snapshot fetch, restore
  stream with the X-GenAI-Restore ack header, 409 refusals).
"""
import asyncio
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine import llm_engine
from generativeaiexamples_tpu.engine import request_snapshot as snap_mod
from generativeaiexamples_tpu.engine.llm_engine import (
    LLMEngine,
    SamplingParams,
)
from generativeaiexamples_tpu.utils import faults
from generativeaiexamples_tpu.utils.resilience import (
    EngineOverloaded,
    RequestPreempted,
)

TINY_PAGED = dict(
    model_config_name="debug",
    max_batch_size=2,
    max_seq_len=128,
    prefill_chunk=16,
    decode_block=4,
    dtype="float32",
    tensor_parallelism=1,
    page_size=8,
    watchdog_stall_s=0.0,
    drain_timeout_s=30.0,
)

PROMPT = [7 + i for i in range(10)]


def _wait(cond, timeout=60.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _pull(req, n, timeout=60.0):
    """Pop exactly n token ids off a live request's stream."""
    out = []
    while len(out) < n:
        item = req.out_queue.get(timeout=timeout)
        assert item is not None, "stream ended early"
        out.append(item)
    return out


def _rest(req, timeout=60.0):
    """Pop the remainder of a request's stream (to the end sentinel)."""
    out = []
    while True:
        item = req.out_queue.get(timeout=timeout)
        if item is None:
            return out
        out.append(item)


@pytest.fixture(scope="module")
def peng(tmp_path_factory):
    spool = tmp_path_factory.mktemp("spool-paged")
    engine = LLMEngine(
        EngineConfig(snapshot_spool_dir=str(spool), **TINY_PAGED)
    )
    yield engine
    engine.resume_from_drain()
    engine.shutdown()


def test_drain_idle_engine_and_resume(peng):
    summary = peng.drain()
    assert summary["draining"] and summary["parked"]
    assert summary["preempted"] == 0 and summary["spooled"] == 0
    assert peng.is_draining()
    with pytest.raises(EngineOverloaded, match="drain"):
        peng.submit(PROMPT, SamplingParams(max_tokens=2))
    peng.resume_from_drain()
    assert not peng.is_draining()
    # admission reopened: a normal stream completes
    ids = list(peng.iter_ids(PROMPT, SamplingParams(temperature=0.0,
                                                    max_tokens=4),
                             timeout=120))
    assert len(ids) == 4


def test_mid_decode_drain_then_restore_token_identical(peng):
    params = SamplingParams(temperature=0.0, max_tokens=20, seed=3)
    baseline = list(peng.iter_ids(PROMPT, params, timeout=120))
    assert len(baseline) == 20

    spooled_before = snap_mod._M_PREEMPTED.labels(mode="snapshot").value
    # Throttled (delay fault per dispatch pass, as in the lifecycle test
    # below): unthrottled, a fast host dispatches all 20 tokens' blocks
    # and frees the slot before drain() is even called — the outcome
    # then depends on the machine, not on the code.
    faults.reset()
    faults.configure("engine.dispatch", "delay", at=1, count=0, value=0.05)
    try:
        req = peng.submit(PROMPT, params)
        got = _pull(req, 6)
        summary = peng.drain()
    finally:
        faults.reset()
    tail = _rest(req)  # terminates with the preemption sentinel
    assert isinstance(req.error, RequestPreempted)
    sid = req.error.snapshot_id
    assert sid, "mid-decode victim must spool a restorable snapshot"
    assert summary["spooled"] >= 1 and sid in summary["snapshots"]
    assert snap_mod._M_PREEMPTED.labels(mode="snapshot").value == (
        spooled_before + 1
    )
    emitted = got + tail
    assert emitted == baseline[: len(emitted)]

    snap = peng.snapshot_spool.load(sid)
    assert snap.restorable and snap.emitted == emitted
    assert snap.sampling_seed == req.sampling_seed

    # refusals: while draining, and on geometry/fingerprint drift
    with pytest.raises(EngineOverloaded):
        peng.restore_snapshot(snap)
    peng.resume_from_drain()
    bad_geo = peng.snapshot_spool.load(sid)
    bad_geo.geometry = dict(bad_geo.geometry, page_size=999)
    with pytest.raises(snap_mod.SnapshotMismatch, match="geometry"):
        peng.restore_snapshot(bad_geo)
    bad_fp = peng.snapshot_spool.load(sid)
    bad_fp.config_fingerprint = "not-this-engine"
    with pytest.raises(snap_mod.SnapshotMismatch, match="fingerprint"):
        peng.restore_snapshot(bad_fp)

    # the real restore: token-identical continuation
    restored_before = snap_mod._M_RESTORED.labels(mode="restore").value
    req2, params2, prior, mode = peng.restore_snapshot(snap)
    assert mode == "restore"
    assert prior == emitted
    continuation = _rest(req2)
    assert prior + continuation == baseline
    assert snap_mod._M_RESTORED.labels(mode="restore").value == (
        restored_before + 1
    )


def test_pending_request_preempts_replay_only(peng):
    params = SamplingParams(temperature=0.0, max_tokens=4)
    with peng.hold_admissions():
        req = peng.submit(PROMPT, params)
        summary = peng.drain()
    _rest(req)
    assert isinstance(req.error, RequestPreempted)
    assert req.error.snapshot_id is None
    assert summary["replay_only"] >= 1
    peng.resume_from_drain()


def test_abort_during_drain_completes_not_preempts(peng):
    """An abort landing while the drain walks victims: the stream
    terminates cleanly (no RequestPreempted, nothing spooled). The
    dispatch loop is held at the chaos kill site so the cancelled
    request is still slotted when the drain reaches it — otherwise the
    loop's next pass wins the race and the drain never sees it."""
    params = SamplingParams(temperature=0.0, max_tokens=60)
    req = peng.submit(PROMPT, params)
    _pull(req, 4)
    faults.reset()
    faults.configure("replica.kill", "hang", at=1, count=0, value=30.0)
    held = faults._M_INJECTED.labels(site="replica.kill", mode="hang")
    before = held.value
    try:
        _wait(lambda: held.value > before, timeout=30,
              msg="dispatch loop held at the kill site")
        peng.abort(req)
        summary = peng.drain(timeout=0.5)
    finally:
        faults.reset()
    _rest(req)
    assert req.error is None, "aborted request must not be preempted"
    assert summary["completed"] >= 1
    assert summary["spooled"] == 0 and summary["preempted"] == 0
    peng.resume_from_drain()


def test_faults_kill_mode_sigkills_the_process(peng, monkeypatch):
    """The chaos harness's in-process kill point: a 'kill' rule at
    replica.kill fires a real SIGKILL from the dispatch loop (tests
    monkeypatch os.kill — the documented contract)."""
    import signal

    kills = []
    monkeypatch.setattr(
        faults.os, "kill", lambda pid, sig: kills.append((pid, sig))
    )
    faults.reset()
    faults.configure("replica.kill", "kill", at=1, count=0)
    try:
        ids = list(peng.iter_ids(PROMPT, SamplingParams(temperature=0.0,
                                                        max_tokens=2),
                                 timeout=120))
        assert len(ids) == 2
        _wait(lambda: kills, timeout=10, msg="injected SIGKILL")
        pid, sig = kills[0]
        assert pid == faults.os.getpid() and sig == signal.SIGKILL
    finally:
        faults.reset()


# --------------------------------------------------------------------------- #
# drain racing the prefill→decode handoff seam (disagg, satellite)


TINY_DISAGG = dict(TINY_PAGED, max_batch_size=4, page_size=16,
                   scheduler_policy="disagg")


@pytest.fixture(scope="module")
def deng(tmp_path_factory):
    spool = tmp_path_factory.mktemp("spool-disagg")
    engine = LLMEngine(
        EngineConfig(snapshot_spool_dir=str(spool), **TINY_DISAGG)
    )
    yield engine
    engine.resume_from_drain()
    engine.shutdown()


def _stage_queued_handoff(deng, params):
    """Park the decode tier's import seam and land one completed
    prefill in the TransferQueue — the exact state a drain must never
    drop."""
    original_admit = deng.scheduler.admit
    deng.scheduler.admit = lambda: None
    req = deng.submit([3] * 40, params)
    try:
        _wait(lambda: len(deng.scheduler.transfer) > 0, timeout=60,
              msg="handoff queued in the TransferQueue")
    except BaseException:
        deng.scheduler.admit = original_admit
        raise
    return req, original_admit


def test_drain_checkpoints_queued_handoff_never_drops(deng):
    params = SamplingParams(temperature=0.0, max_tokens=24, seed=11)
    req, original_admit = _stage_queued_handoff(deng, params)
    try:
        summary = deng.drain()
    finally:
        deng.scheduler.admit = original_admit
    assert len(deng.scheduler.transfer) == 0
    tail = _rest(req)  # the stream TERMINATED — not wedged, not dropped
    assert isinstance(req.error, RequestPreempted)
    # checkpointed (snapshot or replay-only) — accounted either way
    assert summary["preempted"] >= 1
    if req.error.snapshot_id:
        assert req.error.snapshot_id in summary["snapshots"]
        snap = deng.snapshot_spool.load(req.error.snapshot_id)
        assert snap.prompt_ids == [3] * 40
    deng.resume_from_drain()
    # the engine serves normally after the drain+resume (PROMPT is
    # known not to greedy-decode straight into EOS on debug weights)
    ids = list(deng.iter_ids(PROMPT, SamplingParams(temperature=0.0,
                                                    max_tokens=4),
                             timeout=120))
    assert len(ids) == 4
    assert tail is not None


def test_abort_during_drain_with_queued_handoff(deng):
    params = SamplingParams(temperature=0.0, max_tokens=24, seed=12)
    req, original_admit = _stage_queued_handoff(deng, params)
    deng.abort(req)
    try:
        summary = deng.drain()
    finally:
        deng.scheduler.admit = original_admit
    _rest(req)  # the abort still terminates the stream under drain
    assert req.error is None
    assert summary["completed"] >= 1
    assert summary["spooled"] == 0, "aborted handoff must not be spooled"
    deng.resume_from_drain()


# --------------------------------------------------------------------------- #
# the drain lifecycle HTTP surface (both replica kinds serve it; the
# model server app is the cheap one to boot around a live engine)


def test_drain_lifecycle_endpoints(peng, monkeypatch):
    from generativeaiexamples_tpu.engine.server import ModelServer
    from generativeaiexamples_tpu.server.api import RESTORE_HEADER

    monkeypatch.setattr(llm_engine, "_ENGINE", peng)
    params = SamplingParams(temperature=0.0, max_tokens=48, seed=21)
    baseline = "".join(peng.stream_text(PROMPT, params, timeout=120))

    async def scenario():
        app = ModelServer(engine=peng).build_app()
        async with TestClient(TestServer(app)) as client:
            # a live in-flight request for the drain to checkpoint —
            # throttled (delay fault per dispatch pass) so it cannot
            # outrun the HTTP round-trip into the drain handler
            faults.reset()
            faults.configure("engine.dispatch", "delay", at=1, count=0,
                             value=0.05)
            req = peng.submit(PROMPT, params)
            _pull(req, 4)
            resp = await client.post("/internal/drain", json={})
            assert resp.status == 200
            summary = await resp.json()
            faults.reset()  # un-throttle before the restore stream
            assert summary["draining"] and summary["spooled"] >= 1
            _rest(req)
            sid = req.error.snapshot_id
            assert sid in summary["snapshots"]

            resp = await client.get("/internal/snapshots")
            inventory = (await resp.json())["snapshots"]
            assert any(s["snapshot_id"] == sid for s in inventory)

            resp = await client.get(f"/internal/snapshots/{sid}")
            assert resp.status == 200
            doc = await resp.json()
            assert doc["snapshot_id"] == sid
            resp = await client.get("/internal/snapshots/snap-missing")
            assert resp.status == 404

            # restore refused while draining (503), then resume
            resp = await client.post("/internal/restore", json=doc)
            assert resp.status == 503
            resp = await client.post("/internal/drain",
                                     json={"resume": True})
            assert (await resp.json()) == {"draining": False}

            # fingerprint drift → 409, malformed body → 422
            bad = dict(doc, config_fingerprint="other-build")
            resp = await client.post("/internal/restore", json=bad)
            assert resp.status == 409
            resp = await client.post("/internal/restore",
                                     json=["not", "a", "snapshot"])
            assert resp.status == 422

            # the real restore: SSE continuation re-delivers the FULL
            # transcript (the router trims), stamped with the ack header
            resp = await client.post("/internal/restore", json=doc)
            assert resp.status == 200
            assert resp.headers[RESTORE_HEADER] == f"{sid}; mode=restore"
            assert "text/event-stream" in resp.headers["Content-Type"]
            body = await resp.text()
            text = "".join(
                c["message"]["content"]
                for frame in body.split("\n\n") if frame.startswith("data: ")
                for c in __import__("json").loads(frame[6:]).get("choices", [])
                if c.get("message") and not c.get("finish_reason")
            )
            # the frame builder HTML-escapes content (the /generate
            # sanitizer); unescape before the token-identity check
            import html

            assert html.unescape(text) == baseline
            assert '"finish_reason":"[DONE]"' in body.replace(" ", "")

    try:
        asyncio.run(scenario())
    finally:
        faults.reset()
