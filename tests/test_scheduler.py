"""The pluggable scheduler subsystem (engine/scheduler/,
docs/scheduler.md): policy registry + knob validation, the
AcceptanceTracker arithmetic behind draft-aware scheduling, the
TransferQueue handoff protocol, tier submesh planning, and the disagg
policy serving a tiny CPU engine end to end — concurrent mixed-length
load, handoff accounting, zero recompute on handed-off pages, abort
paths, and clean shutdown.

Uses the tiny debug model on CPU (the tier-1 engine budget class, same
as test_resilience_engine).
"""
import threading
import time
import types

import pytest

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine import kv_pages
from generativeaiexamples_tpu.engine import scheduler as scheduler_mod
from generativeaiexamples_tpu.engine.scheduler import handoff as handoff_mod
from generativeaiexamples_tpu.engine.scheduler.base import (
    AcceptanceTracker,
    SchedulerPolicy,
)
from generativeaiexamples_tpu.engine.llm_engine import (
    LLMEngine,
    SamplingParams,
)

TINY_DISAGG = dict(
    model_config_name="debug",
    max_batch_size=4,
    max_seq_len=128,
    prefill_chunk=16,
    page_size=16,  # pages must tile the 16-token chunk
    decode_block=4,
    dtype="float32",
    tensor_parallelism=1,
    scheduler_policy="disagg",
    watchdog_stall_s=0.0,
)


def _drain(req):
    out = []
    while True:
        item = req.out_queue.get(timeout=120)
        if item is None:
            return out
        out.append(item)


# --------------------------------------------------------------------- #
# knob validation + registry


def test_validate_config_matrix():
    ok = EngineConfig(model_config_name="debug")
    scheduler_mod.validate_config(ok)
    for kwargs in (
        dict(scheduler_policy="bogus"),
        dict(handoff_queue_depth=-1),
        dict(spec_draft_min_acceptance=-0.1),
        dict(spec_draft_min_acceptance=1.0),
    ):
        cfg = EngineConfig(model_config_name="debug", **kwargs)
        with pytest.raises(ValueError):
            scheduler_mod.validate_config(cfg)


def test_disagg_refuses_geometry_that_cannot_page():
    # Default 128-token pages cannot tile a 16-token chunk: start-up
    # refuses, it does not serve a handoff protocol with no page unit.
    cfg = EngineConfig(
        model_config_name="debug",
        max_batch_size=2,
        max_seq_len=64,
        prefill_chunk=16,
        decode_block=4,
        tensor_parallelism=1,
        scheduler_policy="disagg",
    )
    with pytest.raises(ValueError, match="page_size"):
        LLMEngine(cfg)


# --------------------------------------------------------------------- #
# AcceptanceTracker (draft-aware scheduling, ROADMAP 4c)


def test_tracker_disabled_always_drafts():
    t = AcceptanceTracker(min_acceptance=0.0)
    for _ in range(10):
        t.record(8, 0)
    assert all(t.should_draft() for _ in range(20))


def test_tracker_needs_evidence_before_skipping():
    t = AcceptanceTracker(min_acceptance=0.5, min_rounds=4)
    assert t.ratio() is None
    t.record(8, 0)
    t.record(8, 0)
    t.record(8, 0)
    # 3 rounds < min_rounds: no evidence, keep drafting
    assert t.should_draft()
    t.record(8, 0)
    assert t.ratio() == 0.0
    assert not t.should_draft()


def test_tracker_zero_draft_rounds_carry_no_evidence():
    t = AcceptanceTracker(min_acceptance=0.5, min_rounds=2)
    for _ in range(10):
        t.record(0, 0)
    assert t.ratio() is None and t.should_draft()


def test_tracker_window_and_ratio_arithmetic():
    t = AcceptanceTracker(min_acceptance=0.5, window=4, min_rounds=2)
    for drafted, accepted in ((4, 0), (4, 0), (4, 4), (4, 4)):
        t.record(drafted, accepted)
    assert t.ratio() == pytest.approx(0.5)
    assert t.should_draft()  # at threshold counts as healthy
    t.record(4, 0)  # window slides: drops one of the good rounds? no —
    # deque(maxlen=4) drops the OLDEST (4,0): window now 0,4,4,0 = 0.5
    assert t.ratio() == pytest.approx(0.5)
    t.record(4, 0)  # window 4,4,0,0 -> 0.5; then 4,0,0 ...
    t.record(4, 0)
    assert t.ratio() == pytest.approx(0.25)
    assert not t.should_draft()


def test_tracker_probe_cadence_and_recovery():
    t = AcceptanceTracker(
        min_acceptance=0.5, window=4, probe_interval=3, min_rounds=2
    )
    for _ in range(4):
        t.record(8, 0)  # collapsed
    decisions = [t.should_draft() for _ in range(6)]
    # skip, skip, probe, skip, skip, probe
    assert decisions == [False, False, True, False, False, True]
    # probes re-measure: a recovered workload refills the window with
    # healthy rounds and drafting resumes unconditionally
    for _ in range(4):
        t.record(8, 8)
    assert t.ratio() == 1.0
    assert [t.should_draft() for _ in range(3)] == [True, True, True]


def test_policy_skip_counter_increments():
    eng = types.SimpleNamespace(
        engine_config=types.SimpleNamespace(spec_draft_min_acceptance=0.5)
    )
    pol = SchedulerPolicy(eng)
    for _ in range(4):
        pol.record_spec_round(8, 0)
    before = scheduler_mod.metrics_snapshot()["spec_draft_skips"]
    assert not pol.should_draft()
    after = scheduler_mod.metrics_snapshot()["spec_draft_skips"]
    assert after == before + 1


# --------------------------------------------------------------------- #
# TransferQueue protocol


def _rec(rid=1, slot=0, pages=(1, 2)):
    req = types.SimpleNamespace(rid=rid)
    return handoff_mod.KVHandoff(
        req=req, slot=slot, position=8, budget=4, pages=tuple(pages),
        nbytes=128,
    )


def test_transfer_queue_put_pop_find():
    cond = threading.Condition()
    q = handoff_mod.TransferQueue(2, cond)
    with cond:
        assert q.has_room() and len(q) == 0
        q.put(_rec(rid=7))
        q.put(_rec(rid=9))
        assert not q.has_room()
        assert q.find_rid(9) is not None and q.find_rid(5) is None
        recs = q.pop_all()
        assert [r.req.rid for r in recs] == [7, 9]
        assert len(q) == 0 and q.find_rid(7) is None


def test_transfer_queue_backpressure_wait_and_release():
    cond = threading.Condition()
    q = handoff_mod.TransferQueue(1, cond)
    with cond:
        q.put(_rec())
    stalled = {}

    def prefill_tier():
        with cond:
            stalled["s"] = q.wait_room(stop=lambda: False, slice_s=0.02)
            q.put(_rec(rid=2))

    t = threading.Thread(target=prefill_tier)
    t.start()
    time.sleep(0.15)
    assert t.is_alive()  # genuinely blocked on a full queue
    with cond:
        q.pop_all()  # decode-tier import frees room + notifies
    t.join(timeout=10)
    assert not t.is_alive()
    assert stalled["s"] > 0.05


def test_transfer_queue_stop_predicate_aborts_wait():
    cond = threading.Condition()
    q = handoff_mod.TransferQueue(1, cond)
    with cond:
        q.put(_rec())
        stall = q.wait_room(stop=lambda: True)
        assert stall < 1.0 and not q.has_room()


def test_transfer_queue_capacity_validation():
    with pytest.raises(ValueError):
        handoff_mod.TransferQueue(0, threading.Condition())


# --------------------------------------------------------------------- #
# page accounting + tier planning


def test_page_bytes_arithmetic():
    # bf16: 2 (k+v) * layers * page * Hkv * Dh * 2 bytes
    assert kv_pages.page_bytes(2, 16, 2, 8, quantized=False) == (
        2 * 2 * 16 * 2 * 8 * 2
    )
    # int8: 1-byte rows + float32 [page, Hkv] scales for k and v
    assert kv_pages.page_bytes(2, 16, 2, 8, quantized=True) == (
        2 * 2 * 16 * 2 * 8 * 1 + 2 * 2 * 16 * 2 * 4
    )


def test_allocator_all_live():
    alloc = kv_pages.PageAllocator(8, 16)
    pages = alloc.alloc(3)
    assert alloc.all_live(pages)
    alloc.release(pages[:1])
    assert not alloc.all_live(pages)
    assert alloc.all_live(pages[1:])


def test_tier_submeshes_single_and_split():
    from generativeaiexamples_tpu.parallel.mesh import (
        create_mesh,
        tier_submeshes,
    )

    single = create_mesh(tensor_parallelism=1)
    p, d = tier_submeshes(single)
    assert p is single and d is single  # shared device = shared pool
    multi = create_mesh(tensor_parallelism=-1)  # 8-device virtual mesh
    if multi.size >= 2:
        p, d = tier_submeshes(multi)
        assert p.size == d.size == multi.size // 2
        assert not set(p.devices.reshape(-1)) & set(d.devices.reshape(-1))


# --------------------------------------------------------------------- #
# disagg engine end to end (tiny CPU debug engine)


@pytest.fixture(scope="module")
def deng():
    engine = LLMEngine(EngineConfig(**TINY_DISAGG))
    yield engine
    engine.shutdown()


def test_default_policy_is_unified():
    cfg = EngineConfig(model_config_name="debug")
    assert cfg.scheduler_policy == "unified"


def test_disagg_describe_and_policy_kind(deng):
    assert deng.scheduler.kind == "disagg"
    d = deng.scheduler.describe()
    assert d["tiers"] == 2 and d["shared_pool"] is True
    assert d["transfer_queue_capacity"] == 2 * deng.num_slots


def test_disagg_serves_concurrent_mixed_load_with_handoffs(deng):
    m0 = deng.metrics
    outs = {}

    def run(i):
        # odd ids: long-RAG-shaped prompts (many chunks); even: short
        plen = 100 if i % 2 else 10
        params = SamplingParams(
            temperature=0.0 if i % 2 else 0.7, top_p=0.8, seed=i + 1,
            max_tokens=6,
        )
        outs[i] = list(
            deng.iter_ids([3 + i] * plen, params, timeout=180)
        )

    threads = [
        threading.Thread(target=run, args=(i,), name=f"load-{i}")
        for i in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not any(t.is_alive() for t in threads)
    m1 = deng.metrics
    assert m1["handoffs"] - m0["handoffs"] >= 6
    assert m1["handoff_pages"] > m0["handoff_pages"]
    assert m1["handoff_bytes"] > m0["handoff_bytes"]
    # ZERO prefill recompute on handed-off pages (the zero-copy
    # discipline holds across the tier boundary).
    assert m1["handoff_recompute"] == m0["handoff_recompute"] == 0.0


def test_disagg_streams_match_unified(deng):
    """Sequential greedy + seeded-sampled streams through the disagg
    tiers are token-identical to a unified engine with the same config
    (the scheduler seam must not change WHAT is computed, only which
    thread schedules it)."""
    prompts = ([5] * 40, [9] * 12)
    params = (
        SamplingParams(temperature=0.0, max_tokens=8),
        SamplingParams(temperature=0.7, top_p=0.8, seed=42, max_tokens=8),
    )
    disagg_streams = [
        list(deng.iter_ids(p, pr, timeout=180))
        for p in prompts for pr in params
    ]
    uni = LLMEngine(
        EngineConfig(**dict(TINY_DISAGG, scheduler_policy="unified"))
    )
    try:
        unified_streams = [
            list(uni.iter_ids(p, pr, timeout=180))
            for p in prompts for pr in params
        ]
    finally:
        uni.shutdown()
    assert disagg_streams == unified_streams


def test_disagg_abort_pending_and_queued(deng):
    with deng.hold_admissions():
        req = deng.submit([5] * 30, SamplingParams(max_tokens=4))
        assert deng.abort(req.rid)
        assert req.out_queue.get(timeout=10) is None
    assert not deng.abort(req.rid)


def test_disagg_ingest_window_opens_when_prefill_idle(deng):
    # Engine idle -> prefill tier idle -> window open, regardless of
    # the (empty) decode batch.
    deadline = time.time() + 60
    while time.time() < deadline and deng.is_decoding():
        time.sleep(0.05)
    assert deng.scheduler.ingest_window(10.0)


def test_disagg_handoff_events_in_flight_recorder(deng):
    from generativeaiexamples_tpu.utils import flight_recorder

    if not flight_recorder.enabled():
        pytest.skip("flight recorder disabled in this environment")
    rec = flight_recorder.start(owner="server")
    flight_recorder.bind(rec)
    try:
        _drain(deng.submit([11] * 40, SamplingParams(
            temperature=0.0, max_tokens=4
        )))
    finally:
        flight_recorder.unbind()
    kinds = [name for _, name, _ in rec.events]
    assert "tier_assign" in kinds
    assert "kv_handoff" in kinds
    assert "decode_join" in kinds
    tiers = [
        (attrs or {}).get("tier")
        for _, name, attrs in rec.events
        if name == "tier_assign"
    ]
    assert "prefill" in tiers and "decode" in tiers
