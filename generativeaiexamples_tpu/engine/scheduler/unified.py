"""The default single-tier scheduler policy.

``unified`` reproduces the monolithic pre-scheduler dispatch loop
exactly: the engine's dispatch thread forms one admission wave per
loop pass (``claim_wave``), prefills it inline, and registers the
slots itself, so admission still alternates with decode blocks on one
thread in the same order as before the extraction. Greedy and
seeded-sampled streams are token-identical to the pre-scheduler engine
across every layout (pinned by the slow identity suites — the same
contract the paged and spec-decode migrations carried).

The ingest window is the decode-idle condition the PR 5 micro-batcher
used to reach through ``LLMEngine.wait_decode_idle``: bulk side-model
dispatches wait for the decode slots to drain, waking exactly when the
dispatch loop frees the last slot.
"""
from __future__ import annotations

import time
from typing import Any, Dict

from generativeaiexamples_tpu.engine.scheduler.base import SchedulerPolicy


class UnifiedPolicy(SchedulerPolicy):
    kind = "unified"

    def has_work(self) -> bool:
        """Pending admissions wake the dispatch loop (caller holds the
        engine lock); warmup's hold_admissions masks them."""
        eng = self.engine
        return bool(eng._pending) and not eng._paused

    def admit(self) -> None:
        """One wave per loop pass, claimed, prefilled, and registered
        on the dispatch thread — the pre-extraction order, but for one
        decision (PR 30): between two chunks of a wave the loop's own
        decode step runs, so a decoding stream waits behind ONE extend
        program, not the wave; each chunk costs the wave's rows one
        more block before their first token (docs/scheduler.md). A
        block writes only the scratch page for the wave's rows, which
        are not live yet, so their half-prefilled pages stay intact."""
        admitted = self.claim_wave()
        if admitted:
            eng = self.engine
            eng._prefill_wave(admitted, between_chunks=eng._decode_if_busy)

    def ingest_window(self, timeout: float) -> bool:
        """Block until no request occupies a decode slot, or ``timeout``
        elapses; True when idle. The dispatch loop notifies the engine
        condition when the last slot frees, so a waiter wakes exactly
        when decode drains."""
        eng = self.engine
        deadline = time.monotonic() + max(0.0, timeout)
        with eng._lock:
            while eng._slot_req:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                eng._lock.wait(remaining)
            return True

    def retrieval_window(self, timeout: float) -> bool:
        """Retrieval-tier waves yield to PENDING ADMISSIONS only: on the
        single-tier policy a pending backlog means the dispatch thread
        is about to run prefill (the expensive contended phase), while
        decode occupancy alone is the steady state a latency-critical
        search wave must co-run with — waiting for decode idleness here
        would starve retrieval on any busy engine."""
        eng = self.engine
        deadline = time.monotonic() + max(0.0, timeout)
        with eng._lock:
            while eng._pending:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                eng._lock.wait(remaining)
            return True

    def describe(self) -> Dict[str, Any]:
        return {"policy": self.kind, "tiers": 1}
