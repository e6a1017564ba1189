"""Prefill/decode disaggregation: two execution tiers, one KV pool.

The structural answer to long-prompt RAG prefills stealing decode
dispatch slots (ROADMAP item 2; Trinity and the serving survey's
P/D-disagg sections): a dedicated **prefill tier** worker thread forms
admission waves and runs their chunked prefill, while the engine's
dispatch thread becomes a pure **decode tier** — decode blocks keep
their cadence because admission work never runs between them. The
tiers meet at the :class:`~.handoff.TransferQueue`: a finished
prefill's KV pages (chunk-aligned, hence page-aligned — ``page_size``
divides ``prefill_chunk``) hand to the decode tier as a
:class:`~.handoff.KVHandoff` record. On the same-host path both tiers
share the device page pool, so the handoff moves page OWNERSHIP
(refcounts funded at admission travel with the record): no copy, no
recompute — ``genai_engine_handoff_recompute_total`` stays flat and
the bench/loadgen gates assert it.

Tier topology: ``parallel.mesh.tier_submeshes`` plans the device
split — on the CPU-testable single-device mesh both tiers share the
device (and on it, the pool); disjoint-device tiers reuse this exact
record/queue protocol but additionally need the cross-pool page
transport (ROADMAP item 3's KV fabric), which plugs in at the
``TransferQueue`` seam.

Concurrency contract: the two tiers dispatch compiled programs that
DONATE shared device buffers (the KV pool, the slot state arrays), so
every compiled call + rebind runs under the engine's dispatch lock
(``LLMEngine._dispatch_lock``) — held only across the async enqueue,
never across device execution, so prefill chunks and decode blocks
still interleave on the device stream. Host bookkeeping stays under
the engine condition lock exactly as in the unified policy; decode-
side registration (``_slot_req`` et al.) happens only at import, on
the dispatch thread, preserving the engine's single-writer rules.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict

from generativeaiexamples_tpu.engine import dispatch_timeline
from generativeaiexamples_tpu.engine.scheduler import handoff as handoff_mod
from generativeaiexamples_tpu.engine.scheduler.base import SchedulerPolicy
from generativeaiexamples_tpu.utils import flight_recorder
from generativeaiexamples_tpu.utils import get_logger

logger = get_logger(__name__)


class DisaggPolicy(SchedulerPolicy):
    kind = "disagg"

    def __init__(self, engine) -> None:
        super().__init__(engine)
        cfg = engine.engine_config
        depth = cfg.handoff_queue_depth or 2 * engine.num_slots
        # The engine condition IS the tier coordination fabric: the
        # transfer queue, the inflight counter, and every tier wait
        # ride it, so submit/release notifications wake the tiers too.
        self._cond = engine._lock
        self.transfer = handoff_mod.TransferQueue(depth, self._cond)
        self._prefill_inflight = 0  # guarded by self._cond
        # Per-page transfer accounting for the handoff records.
        from generativeaiexamples_tpu.engine import kv_pages as kv_pages_mod

        mc = engine.model_config
        self._page_nbytes = kv_pages_mod.page_bytes(
            mc.num_layers, cfg.page_size, mc.num_kv_heads, mc.head_dim,
            quantized=engine._kv_quant,
            kv_width=engine._kv_byte_width,
        )
        # Tier topology plan (parallel/mesh.py): single-device meshes
        # share the device AND the pool (the zero-copy path this policy
        # serves); a disjoint split is recorded for the item-3 fabric.
        from generativeaiexamples_tpu.parallel.mesh import tier_submeshes

        self._prefill_mesh, self._decode_mesh = tier_submeshes(engine._mesh)
        self._thread: threading.Thread = threading.Thread(
            target=self._prefill_loop, daemon=True, name="llm-prefill-tier"
        )
        logger.info(
            "disagg scheduler: prefill tier %s / decode tier %s, "
            "transfer queue depth %d, %d B/page",
            dict(self._prefill_mesh.shape), dict(self._decode_mesh.shape),
            depth, self._page_nbytes,
        )

    # -- lifecycle ----------------------------------------------------- #
    def start(self) -> None:
        self._thread.start()

    def stop(self) -> bool:
        """Join the prefill tier (the engine already flipped _running
        and notified). True on a clean join."""
        if not self._thread.is_alive():
            return True
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            logger.error(
                "disagg prefill tier did not join within 10 s — a wedged "
                "prefill dispatch holds it"
            )
            return False
        return True

    # -- dispatch-loop hooks ------------------------------------------- #
    def has_work(self) -> bool:
        """Decode loop wakes for queued handoffs; raw pending requests
        belong to the prefill tier (caller holds the engine lock)."""
        return len(self.transfer) > 0

    def admit(self) -> None:
        """Decode-tier admission = importing completed prefills: pop
        every queued handoff and register it into the decode batch."""
        eng = self.engine
        with self._cond:
            recs = self.transfer.pop_all()
        for rec in recs:
            handoff_mod.record_wait(max(0.0, time.time() - rec.t_enqueue))
            eng._import_handoff(rec)

    def tier_busy(self) -> bool:
        """Prefill wave mid-flight or un-imported handoffs — the
        warmup quiesce must wait for both before dispatching
        donated-buffer warm programs. Caller holds self._cond (the
        engine lock)."""
        return self._prefill_inflight > 0 or len(self.transfer) > 0

    def find_rid(self, rid: int):
        return self.transfer.find_rid(rid)

    # -- drain seam ---------------------------------------------------- #
    def wave_inflight(self) -> int:
        """Caller holds self._cond (the engine lock): the drain thread
        waits for the claimed-but-unqueued window to close before it
        captures — a wave in this window holds funded pages whose
        handoff record does not exist yet."""
        return self._prefill_inflight

    def drain_handoffs(self) -> list:
        """Hand the drain thread every record the decode tier never
        imported (caller holds self._cond). The pop empties the queue,
        so a later resume starts clean."""
        return self.transfer.pop_all()

    # -- co-scheduling seams ------------------------------------------- #
    def ingest_window(self, timeout: float) -> bool:
        """Yield bulk ingest work to the PREFILL tier: the window opens
        when no admissions are pending and no prefill wave is in
        flight. Decode occupancy is irrelevant here — that is the
        point of the split: ingest embedding contends with prefill
        compute, not with the decode tier's cadence."""
        eng = self.engine
        deadline = time.monotonic() + max(0.0, timeout)
        with self._cond:
            while eng._pending or self._prefill_inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
            return True

    def retrieval_window(self, timeout: float) -> bool:
        """Same predicate as the ingest window: retrieval-tier search
        waves ride the prefill tier's idle slices (the tier split means
        decode cadence is structurally insulated already — prefill
        compute is the only contended resource left)."""
        eng = self.engine
        deadline = time.monotonic() + max(0.0, timeout)
        with self._cond:
            while eng._pending or self._prefill_inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
            return True

    def describe(self) -> Dict[str, Any]:
        eng = self.engine
        with self._cond:
            queued = len(self.transfer)
            inflight = self._prefill_inflight
        return {
            "policy": self.kind,
            "tiers": 2,
            "transfer_queue_capacity": self.transfer.capacity,
            "transfer_queued": queued,
            "prefill_inflight": inflight,
            "prefill_tier_devices": self._prefill_mesh.size,
            "decode_tier_devices": self._decode_mesh.size,
            "shared_pool": self._prefill_mesh.devices.tolist()
            == self._decode_mesh.devices.tolist(),
        }

    # -- wave formation hooks ------------------------------------------ #
    def _on_claimed(self, admitted) -> None:
        """Stamp each claimed request's tier (engine lock held)."""
        for req in admitted:
            flight_recorder.event_rid(
                req.rid, "tier_assign", tier="prefill", slot=req.slot
            )

    # -- the prefill tier ---------------------------------------------- #
    def _prefill_loop(self) -> None:
        """Prefill-tier worker: claim a wave, prefill it, hand the KV
        pages to the decode tier. Backpressure-first: a full transfer
        queue stalls this loop BEFORE the next claim, so decode-tier
        consumption paces prefill."""
        eng = self.engine
        while True:
            stall = 0.0
            with self._cond:
                while eng._running and (not eng._pending or eng._paused):
                    self._cond.wait(timeout=1.0)
                if not eng._running:
                    return
                stall = self.transfer.wait_room(
                    stop=lambda: (
                        not eng._running or eng._paused or not eng._pending
                    )
                )
                if not eng._running:
                    return
                if (
                    eng._paused
                    or not eng._pending
                    or not self.transfer.has_room()
                ):
                    continue
                self._prefill_inflight += 1
            if stall > 1e-3:
                handoff_mod.record_stall(stall)
                # Named span on the prefill tier's timeline track: the
                # handoff queue was full, so this thread idled with work
                # queued — a host-gap bubble by definition.
                dispatch_timeline.record_stall("handoff_backpressure", stall)
                flight_recorder.event(
                    "handoff_backpressure",
                    stall_s=round(stall, 6),
                    capacity=self.transfer.capacity,
                )
            try:
                admitted = self.claim_wave()
                if admitted:
                    records = eng._prefill_wave(admitted, register=False)
                    with self._cond:
                        for rec in records:
                            rec.t_enqueue = time.time()
                            handoff_mod.record_handoff(
                                len(rec.pages), rec.nbytes
                            )
                            flight_recorder.event_rid(
                                rec.req.rid, "kv_handoff",
                                pages=len(rec.pages), bytes=rec.nbytes,
                                slot=rec.slot,
                            )
                            self.transfer.put(rec)
                        # Wave completion is tier progress the watchdog
                        # should credit (the decode loop's idle wait
                        # only counts while every tier is idle).
                        eng._last_progress = time.time()
            except Exception as exc:  # noqa: BLE001
                # _prefill_wave's unwind already failed the wave's
                # requests and returned their slots/pages; the tier
                # itself must survive (the unified loop's contract).
                logger.exception("prefill-tier error: %s", exc)
            finally:
                with self._cond:
                    self._prefill_inflight -= 1
                    self._cond.notify_all()
