"""Pluggable scheduler policies for the LLM engine (docs/scheduler.md).

Admission, wave formation, and slot placement used to live inline in
``llm_engine._loop``; this package extracts them behind ONE seam — a
:class:`SchedulerPolicy` object the dispatch loop consults — so
structural scheduling changes (prefill/decode disaggregation here;
fleet KV fabric and SLO-tier autoscaling as ROADMAP items 3/5) plug
into the engine without touching its dispatch mechanics:

- ``unified`` (the default, :mod:`.unified`) reproduces the exact
  pre-extraction dispatch order — the dispatch thread claims a wave,
  prefills it, and registers the slots itself, token-identical to the
  monolithic loop (the slow identity suites pin it);
- ``disagg`` (:mod:`.disagg`) runs prefill and decode as separate
  tiers: a prefill worker thread claims waves and streams finished KV
  pages to the decode tier through the bounded
  :class:`~generativeaiexamples_tpu.engine.scheduler.handoff.TransferQueue`.

The policy also owns three cross-cutting scheduling decisions:

- the retrieval micro-batcher's **ingest window** (PR 5's
  ``wait_decode_idle`` migrated onto this seam): the ingest lane asks
  the policy when bulk side-model work may run, instead of waiting on
  an engine-global condition hook;
- the retrieval tier's **retrieval window**
  (:mod:`~generativeaiexamples_tpu.engine.retrieval_tier`): before a
  batched embed→search→rerank wave dispatches, the tier asks when the
  prefill side is idle — latency-critical query work co-runs with
  decode but yields (bounded) to prefill compute, the inverse of the
  ingest lane's bulk-work gate;
- **draft-aware speculation** (ROADMAP item 4c): an
  :class:`AcceptanceTracker` watches the rolling draft-acceptance
  ratio, and when it collapses below ``spec_draft_min_acceptance`` the
  policy tells the engine to skip the resident-draft dispatch for the
  wave (counted by ``genai_engine_spec_draft_skips_total``), probing
  periodically so a recovered workload resumes drafting.
"""
from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional

from generativeaiexamples_tpu.utils import flight_recorder
from generativeaiexamples_tpu.utils import get_logger
from generativeaiexamples_tpu.utils import metrics as metrics_mod

logger = get_logger(__name__)

_REG = metrics_mod.get_registry()
_M_SPEC_DRAFT_SKIPS = _REG.counter(
    "genai_engine_spec_draft_skips_total",
    "Spec rounds where the scheduler policy skipped the resident-draft "
    "dispatch because the rolling acceptance ratio fell below "
    "spec_draft_min_acceptance (the wave ran the synced block-decode "
    "fallback instead; draft-aware scheduling, docs/scheduler.md).",
)

POLICY_KINDS = ("unified", "disagg")


def validate_config(cfg) -> None:
    """Validate the scheduler knobs (pure host; engine build time and
    chain-server startup both call this)."""
    if cfg.scheduler_policy not in POLICY_KINDS:
        raise ValueError(
            f"engine.scheduler_policy must be one of {POLICY_KINDS}, "
            f"got {cfg.scheduler_policy!r}"
        )
    if cfg.handoff_queue_depth < 0:
        raise ValueError(
            f"engine.handoff_queue_depth must be >= 0 (0 auto-sizes to "
            f"2 x max_batch_size), got {cfg.handoff_queue_depth}"
        )
    if not 0.0 <= cfg.spec_draft_min_acceptance < 1.0:
        raise ValueError(
            f"engine.spec_draft_min_acceptance must be in [0, 1) "
            f"(0 disables draft-aware skipping), got "
            f"{cfg.spec_draft_min_acceptance}"
        )


def build_policy(cfg, engine) -> "SchedulerPolicy":
    """Construct the configured policy against a built engine (called
    from ``_init_scheduler_state`` — slot state exists, threads don't
    yet; the returned policy's ``start()`` runs after they do)."""
    validate_config(cfg)
    if cfg.scheduler_policy == "disagg":
        from generativeaiexamples_tpu.engine.scheduler.disagg import DisaggPolicy

        return DisaggPolicy(engine)
    from generativeaiexamples_tpu.engine.scheduler.unified import UnifiedPolicy

    return UnifiedPolicy(engine)


def metrics_snapshot() -> Dict[str, float]:
    """Legacy flat-dict keys for the engine's ``metrics`` property
    (handoff protocol counters + the draft-skip counter)."""
    from generativeaiexamples_tpu.engine.scheduler import handoff as handoff_mod

    out = handoff_mod.metrics_snapshot()
    out["spec_draft_skips"] = _M_SPEC_DRAFT_SKIPS.value
    return out


class AcceptanceTracker:
    """Rolling draft-acceptance window for draft-aware scheduling.

    Pure host arithmetic, single-writer (the engine dispatch thread
    records rounds and asks ``should_draft`` — no lock needed). A round
    contributes only when it actually drafted; when the ratio over the
    last ``window`` drafting rounds drops below ``min_acceptance``
    (with at least ``min_rounds`` rounds of evidence), drafting is
    skipped — except every ``probe_interval``-th skipped round, which
    drafts anyway so the window keeps seeing fresh acceptance and a
    recovered workload turns drafting back on. ``min_acceptance <= 0``
    disables the tracker entirely (``should_draft`` is always True).
    """

    def __init__(
        self,
        min_acceptance: float = 0.0,
        window: int = 32,
        probe_interval: int = 16,
        min_rounds: int = 4,
    ) -> None:
        self.min_acceptance = float(min_acceptance)
        self.probe_interval = max(1, int(probe_interval))
        self.min_rounds = max(1, int(min_rounds))
        self._rounds: "collections.deque" = collections.deque(maxlen=max(1, window))
        self._skips_since_probe = 0

    def record(self, drafted: int, accepted: int) -> None:
        """Record one verify round's (drafted, accepted) token counts.
        Zero-draft rounds carry no acceptance evidence and are ignored."""
        if drafted > 0:
            self._rounds.append((int(drafted), int(accepted)))

    def ratio(self) -> Optional[float]:
        """Rolling acceptance ratio, or None without enough evidence."""
        if len(self._rounds) < self.min_rounds:
            return None
        drafted = sum(d for d, _ in self._rounds)
        if drafted <= 0:
            return None
        return sum(a for _, a in self._rounds) / drafted

    def should_draft(self) -> bool:
        """Whether the next spec round should run the draft dispatch."""
        if self.min_acceptance <= 0.0:
            return True
        r = self.ratio()
        if r is None or r >= self.min_acceptance:
            self._skips_since_probe = 0
            return True
        self._skips_since_probe += 1
        if self._skips_since_probe >= self.probe_interval:
            # Probe round: draft once so the window re-measures — a
            # workload that left its low-acceptance phase recovers.
            self._skips_since_probe = 0
            return True
        return False


class SchedulerPolicy:
    """The scheduler seam: admission, wave formation, slot placement,
    ingest-window coordination, and draft-aware gating.

    Subclasses implement the tier topology; the shared
    :meth:`claim_wave` holds the wave-formation rule both policies use,
    so ``unified``
    and ``disagg`` cannot drift on HOW a wave forms — only on WHICH
    thread forms it and where registration happens.
    """

    kind = "base"

    def __init__(self, engine) -> None:
        self.engine = engine
        cfg = engine.engine_config
        self.tracker = AcceptanceTracker(
            getattr(cfg, "spec_draft_min_acceptance", 0.0)
        )

    # -- lifecycle ----------------------------------------------------- #
    def start(self) -> None:
        """Spawn tier workers (after the engine's own threads start)."""

    def stop(self) -> bool:
        """Join tier workers; True when everything exited cleanly."""
        return True

    # -- dispatch-loop hooks ------------------------------------------- #
    def has_work(self) -> bool:
        """Whether the decode loop has admission-side work (caller
        holds the engine lock; live slots/releases are checked by the
        loop itself)."""
        raise NotImplementedError

    def admit(self) -> None:
        """The decode loop's admission step for this policy."""
        raise NotImplementedError

    def tier_busy(self) -> bool:
        """Whether a non-decode tier holds in-flight work (prefill wave
        mid-dispatch, un-imported handoffs). The warmup quiesce and the
        watchdog consult this; caller holds the engine lock."""
        return False

    def find_rid(self, rid: int):
        """A request held between tiers (e.g. in the transfer queue)
        with this rid, or None — the abort path's lookup for requests
        no longer pending and not yet decode-registered. Caller holds
        the engine lock."""
        return None

    # -- drain seam (engine/request_snapshot.py) ----------------------- #
    def wave_inflight(self) -> int:
        """Prefill waves currently mid-dispatch on a tier thread. The
        drain workflow waits for zero (after pausing claims) before it
        reads live request state — a mid-wave request is neither
        pending nor importable yet. Caller holds the engine lock."""
        return 0

    def drain_handoffs(self) -> list:
        """Pop and return every queued tier-crossing handoff record at
        drain time — each MUST be checkpointed or completed by the
        caller, never dropped. Unified policy holds none (admission is
        inline). Caller holds the engine lock."""
        return []

    # -- co-scheduling seams ------------------------------------------- #
    def ingest_window(self, timeout: float) -> bool:
        """Block until the policy grants bulk side-model (ingest) work
        a window, or ``timeout`` elapses; True when granted. The
        retrieval micro-batcher's ingest lane calls this between bulk
        embed dispatches (docs/retrieval_batching.md)."""
        raise NotImplementedError

    def retrieval_window(self, timeout: float) -> bool:
        """Block until the policy grants a retrieval-tier search wave a
        window, or ``timeout`` elapses; True when granted. Unlike the
        ingest window (bulk, deferrable), retrieval waves are
        latency-critical: the tier treats this as a bounded YIELD — it
        dispatches after ``timeout`` regardless — so implementations
        pick the predicate that frees the most contended resource
        (prefill idleness; decode keeps its cadence either way).
        Called from the retrieval-tier worker thread
        (docs/retrieval_tier.md)."""
        raise NotImplementedError

    def should_draft(self) -> bool:
        """Draft-aware gating (dispatch thread): False skips the
        resident-draft dispatch for this spec round (the engine runs
        the synced block fallback and counts the skip)."""
        ok = self.tracker.should_draft()
        if not ok:
            _M_SPEC_DRAFT_SKIPS.inc()
        return ok

    def record_spec_round(self, drafted: int, accepted: int) -> None:
        """Feed one verify round's acceptance into the tracker
        (dispatch thread, after the verify readback)."""
        self.tracker.record(drafted, accepted)

    def describe(self) -> Dict[str, Any]:
        """Introspection block (tests, /internal views)."""
        return {"policy": self.kind}

    # -- shared wave formation ----------------------------------------- #
    def _on_claimed(self, admitted: List[Any]) -> None:
        """Hook: a wave was claimed (engine lock held). Disagg stamps
        tier_assign events here; unified is single-tier and stays
        silent (no new events on pre-existing timelines)."""

    def claim_wave(self) -> List[Any]:
        """Form ONE admission wave from the backlog, claiming slots.

        The wave is the oldest claimable requests up to the row cap
        (``engine.shapes.max_wave_rows()``), whatever their lengths:
        every row runs the same chunk dispatches with per-row valid
        counts, so a mixed-length backlog fills one wave. The rest goes
        back to the queue front. Slot placement is the free-list pop
        (LIFO — the warm-slot reuse order the executables were warmed
        under). Returns the claimed requests, each holding a slot; none
        when paused or nothing is claimable.
        """
        import time as _time

        from generativeaiexamples_tpu.engine import llm_engine as eng_mod

        eng = self.engine
        admitted: List[Any] = []
        with eng._lock:
            if eng._paused:
                return admitted
            claimable: List[Any] = []
            while eng._pending and len(claimable) < len(eng._free_slots):
                req = eng._pending.popleft()
                if req.cancelled:
                    req.finished = True
                    req.out_queue.put(eng_mod._END)
                    continue
                req.prompt_ids = req.prompt_ids or [eng.tokenizer.bos_id]
                claimable.append(req)
            if not claimable:
                return admitted
            cap = eng.shapes.max_wave_rows()
            for req in claimable[:cap]:
                req.slot = eng._free_slots.pop()
                # A page-backpressure requeue re-enters this claim
                # path; observe the queue wait and emit "admit" only
                # for the FIRST claim, or every retry would add a
                # cumulative overlapping sample to the histogram.
                first_claim = req.t_admit == 0.0
                req.t_admit = _time.time()
                if first_claim:
                    eng_mod._M_QUEUE_WAIT.observe(
                        req.t_admit - req.t_submit,
                        trace_id=req.trace_hex,
                    )
                    flight_recorder.event_rid(
                        req.rid, "admit", slot=req.slot,
                        queue_wait_s=round(
                            req.t_admit - req.t_submit, 6
                        ),
                    )
                admitted.append(req)
            eng._pending.extendleft(reversed(claimable[cap:]))
            eng_mod._M_QUEUE_DEPTH.set(len(eng._pending))
            self._on_claimed(admitted)
        return admitted
