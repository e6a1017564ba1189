"""Server-side telemetry collection for a loadgen run.

The client half of the observability stack PRs 1/6 built: while the
workload runs, a scraper thread tails completed flight-recorder
timelines incrementally via ``GET /internal/requests?since=<cursor>``
(never re-fetching the ring — the cursor satellite of this PR), and at
the run boundaries snapshots ``GET /internal/metrics`` (the JSON
registry view) and ``GET /internal/slo``. From the metric deltas it
derives the run's cache/spec/batcher hit rates; from the SLO endpoint
the attainment verdict (with per-objective sample counts) and the live
MFU/HBM utilization gauges.

Scrapes are best-effort: a failed poll is retried next interval, and a
run against a server without these endpoints (older deployment) simply
yields no server-side telemetry rather than failing the run.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

import requests

from generativeaiexamples_tpu.utils import trace_stitch

_SCRAPE_TIMEOUT_S = 10.0


def _get_json(url: str) -> Optional[Dict]:
    try:
        resp = requests.get(url, timeout=_SCRAPE_TIMEOUT_S)
        if resp.status_code != 200:
            return None
        return resp.json()
    except (requests.RequestException, ValueError):
        return None


def _engine_metric(snapshot: Optional[Dict], key: str) -> float:
    if not snapshot:
        return 0.0
    engine = snapshot.get("engine") or {}
    try:
        return float(engine.get(key, 0.0))
    except (TypeError, ValueError):
        return 0.0


def _family_total(snapshot: Optional[Dict], family: str) -> float:
    """Sum a counter family's series values from the /internal/metrics
    structured dump."""
    if not snapshot:
        return 0.0
    fam = (snapshot.get("metrics") or {}).get(family) or {}
    total = 0.0
    for series in fam.get("series", []):
        try:
            total += float(series.get("value", 0.0))
        except (TypeError, ValueError):
            continue
    return total


def _family_buckets(snapshot: Optional[Dict], family: str) -> Dict[str, float]:
    """Cumulative histogram bucket counts (by formatted upper bound),
    summed across a family's label series — differenced before/after,
    these give run-window bucket counts, which is how the bubble block
    derives a gap p95 purely from scraper deltas (summable across a
    fleet, like every other delta)."""
    if not snapshot:
        return {}
    fam = (snapshot.get("metrics") or {}).get(family) or {}
    out: Dict[str, float] = {}
    for series in fam.get("series", []):
        for upper, count in (series.get("buckets") or {}).items():
            try:
                out[upper] = out.get(upper, 0.0) + float(count)
            except (TypeError, ValueError):
                continue
    return out


class TelemetryScraper:
    """Background poller joining server truth onto a loadgen run."""

    def __init__(self, base_url: str, interval_s: float = 0.5):
        self.base_url = base_url.rstrip("/")
        self.interval_s = max(0.05, float(interval_s))
        self.timelines: Dict[str, Dict] = {}  # guarded by self._lock
        self._lock = threading.Lock()
        # None = anchor probe failed at start(); tailing stays disabled.
        self._cursor: Optional[int] = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._before: Optional[Dict] = None
        self._after: Optional[Dict] = None
        self._slo: Optional[Dict] = None

    # ------------------------------------------------------------------ #
    def start(self) -> None:
        # Anchor the cursor so only THIS run's completions are tailed
        # (a long-lived server carries older rings). An unanchored tail
        # must NOT fall back to cursor 0: trace ids are deterministic
        # per spec+seed, so a prior same-spec run's timelines would
        # join into this run's phase attribution as silently wrong
        # data — no telemetry beats contaminated telemetry.
        probe = None
        for _ in range(3):
            probe = _get_json(
                f"{self.base_url}/internal/requests?since=0&limit=0"
            )
            if probe is not None:
                break
        if probe is None:
            self._cursor = None  # tailing disabled for the whole run
        else:
            self._cursor = int(probe.get("cursor", 0))
        self._before = _get_json(f"{self.base_url}/internal/metrics")
        self._thread = threading.Thread(
            target=self._loop, name="loadgen-scrape", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
        # Final drain: completions that landed after the last poll.
        self._poll()
        self._after = _get_json(f"{self.base_url}/internal/metrics")
        self._slo = _get_json(f"{self.base_url}/internal/slo")

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self._poll()

    def _poll(self, page_limit: int = 200) -> None:
        if self._cursor is None:
            return
        while True:
            page = _get_json(
                f"{self.base_url}/internal/requests"
                f"?since={self._cursor}&limit={page_limit}"
            )
            if page is None:
                return
            timelines = page.get("timelines") or []
            with self._lock:
                for tl in timelines:
                    trace = tl.get("trace_id")
                    if trace:
                        self.timelines[trace] = tl
            if timelines:
                # Resume from the newest seq actually RECEIVED — the
                # response cursor is the process head, which would skip
                # the remainder of a capped page.
                self._cursor = max(
                    self._cursor,
                    max(int(tl.get("seq", 0)) for tl in timelines),
                )
            if len(timelines) < page_limit:
                if not timelines:
                    # Nothing retained past our cursor (idle, or the
                    # ring evicted ahead of us): fast-forward to head.
                    self._cursor = max(
                        self._cursor, int(page.get("cursor", self._cursor))
                    )
                return

    # ------------------------------------------------------------------ #
    def snapshot_timelines(self) -> Dict[str, Dict]:
        with self._lock:
            return dict(self.timelines)

    def metric_deltas(self) -> Dict[str, float]:
        """Raw run-window counter deltas (summable across a fleet's
        replicas — :class:`FleetScraper` aggregates these before
        computing ratios, so fleet hit rates weight replicas by their
        actual traffic, not one ratio per replica averaged blind)."""
        before, after = self._before, self._after

        def delta_engine(key: str) -> float:
            return _engine_metric(after, key) - _engine_metric(before, key)

        deltas = {
            "prefix_cache_hits": delta_engine("prefix_cache_hits"),
            "prefix_cache_misses": delta_engine("prefix_cache_misses"),
            "spec_drafted_tokens": delta_engine("spec_drafted_tokens"),
            "spec_accepted_tokens": delta_engine("spec_accepted_tokens"),
            "spec_draft_dispatches": delta_engine("spec_draft_dispatches"),
            "spec_pipeline_rollbacks": delta_engine("spec_pipeline_rollbacks"),
            "spec_pipeline_confirmed": delta_engine("spec_pipeline_confirmed"),
            "spec_adaptive_rounds": delta_engine("spec_adaptive_rounds"),
            "spec_adaptive_k_sum": delta_engine("spec_adaptive_k_sum"),
            "generated_tokens": delta_engine("generated_tokens"),
            "decode_dispatches": delta_engine("decode_dispatches"),
            "paged_attn_kernel_dispatches": delta_engine(
                "paged_attn_kernel_dispatches"
            ),
            "paged_attn_gather_dispatches": delta_engine(
                "paged_attn_gather_dispatches"
            ),
            # P/D disaggregation handoff protocol (engine/scheduler/):
            # present (nonzero) only under scheduler_policy='disagg'.
            "handoffs": delta_engine("handoffs"),
            "handoff_pages": delta_engine("handoff_pages"),
            "handoff_bytes": delta_engine("handoff_bytes"),
            "handoff_stall_seconds": delta_engine("handoff_stall_seconds"),
            "handoff_wait_seconds": delta_engine("handoff_wait_seconds"),
            "handoff_recompute": delta_engine("handoff_recompute"),
            "batcher_coalesced_dispatches": _family_total(
                after, "genai_batcher_coalesced_dispatches_total"
            ) - _family_total(before, "genai_batcher_coalesced_dispatches_total"),
            # Disaggregated retrieval tier (engine/retrieval_tier.py):
            # batched ANN search waves — present (nonzero) only under
            # retriever.backend='tier'.
            "retrieval_tier_dispatches": _family_total(
                after, "genai_retrieval_tier_dispatches_total"
            ) - _family_total(before, "genai_retrieval_tier_dispatches_total"),
            "retrieval_tier_queries": _family_total(
                after, "genai_retrieval_tier_queries_total"
            ) - _family_total(before, "genai_retrieval_tier_queries_total"),
            "retrieval_tier_backpressure_stall_seconds": _family_total(
                after, "genai_retrieval_tier_backpressure_stall_seconds_total"
            ) - _family_total(
                before, "genai_retrieval_tier_backpressure_stall_seconds_total"
            ),
            "retrieval_tier_window_wait_seconds": _family_total(
                after, "genai_retrieval_tier_window_wait_seconds_total"
            ) - _family_total(
                before, "genai_retrieval_tier_window_wait_seconds_total"
            ),
            # compile-path observability (engine/compile_watch.py): any
            # post-warmup compile inside the measured window is a
            # hot-path stall the executable-ladder discipline forbids.
            "hot_path_compiles": _family_total(
                after, "genai_engine_hot_path_compiles_total"
            ) - _family_total(before, "genai_engine_hot_path_compiles_total"),
            "compiled_executables": _family_total(
                after, "genai_engine_compiled_executables"
            ),
        }
        # Dispatch-timeline bubble components
        # (engine/dispatch_timeline.py): cumulative per-category seconds
        # the engine folds into its flat metrics dict; zero deltas when
        # the recorder is off, so the bubble block self-omits.
        for key in (
            "timeline_spans",
            "timeline_device_seconds",
            "timeline_lock_wait_seconds",
            "timeline_gap_seconds",
            "timeline_readback_stall_seconds",
        ):
            deltas[key] = delta_engine(key)
        gap_before = _family_buckets(
            before, "genai_engine_dispatch_gap_seconds"
        )
        gap_after = _family_buckets(after, "genai_engine_dispatch_gap_seconds")
        for upper, count in gap_after.items():
            deltas[f"timeline_gap_le_{upper}"] = count - gap_before.get(
                upper, 0.0
            )
        return deltas

    def slo_snapshot(self) -> Optional[Dict]:
        return self._slo

    def summary(self) -> Dict:
        """Hit rates from metric deltas + the SLO/utilization verdicts."""
        deltas = self.metric_deltas()
        hit_rates = hit_rates_from_deltas(deltas)
        slo_block = None
        utilization = None
        if self._slo:
            utilization = self._slo.get("utilization")
            slo_block = _slo_block(self._slo)
        return {
            "hit_rates": hit_rates,
            "utilization": utilization,
            "slo": slo_block,
            "paged_attn": paged_attn_from_deltas(deltas),
            "spec": spec_from_deltas(deltas),
            "disagg": disagg_from_deltas(deltas),
            "retrieval_tier": retrieval_tier_from_deltas(deltas),
            "bubble": bubble_from_deltas(deltas),
            "compiles": compiles_from_deltas(
                deltas, scraped=self._after is not None
            ),
        }


def hit_rates_from_deltas(deltas: Dict[str, float]) -> Dict[str, float]:
    """The summary hit-rate block from raw counter deltas (single
    server or fleet-summed)."""
    hit_rates: Dict[str, float] = {}
    prefix_hits = deltas.get("prefix_cache_hits", 0.0)
    prefix_misses = deltas.get("prefix_cache_misses", 0.0)
    if prefix_hits or prefix_misses:
        hit_rates["prefix_cache"] = round(
            prefix_hits / (prefix_hits + prefix_misses), 4
        )
    drafted = deltas.get("spec_drafted_tokens", 0.0)
    if drafted:
        hit_rates["spec_acceptance"] = round(
            deltas.get("spec_accepted_tokens", 0.0) / drafted, 4
        )
    coalesced = deltas.get("batcher_coalesced_dispatches", 0.0)
    if coalesced:
        hit_rates["batcher_coalesced_dispatches"] = coalesced
    return hit_rates


def spec_from_deltas(deltas: Dict[str, float]) -> Optional[Dict]:
    """Speculative-decoding block over the run window (spec-on engines
    only — a spec-off server drafts nothing and the block is omitted,
    so the gate flags spec silently turning off as schema drift on the
    baseline side rather than trusting zeros).

    ``tokens_per_dispatch`` is emitted tokens per TARGET compiled
    launch (decode blocks + spec verifies — the ``decode_dispatches``
    counter); resident-draft launches ride their own counter and are
    reported as ``draft_dispatch_share`` so the small model's cost is
    visible next to the headline ratio, never hidden inside it."""
    drafted = deltas.get("spec_drafted_tokens", 0.0)
    draft_disp = deltas.get("spec_draft_dispatches", 0.0)
    if not drafted and not draft_disp:
        return None
    dispatches = deltas.get("decode_dispatches", 0.0)
    out = {
        "tokens_per_dispatch": round(
            deltas.get("generated_tokens", 0.0) / max(1.0, dispatches), 4
        ),
        "acceptance_ratio": round(
            deltas.get("spec_accepted_tokens", 0.0) / max(1.0, drafted), 4
        ),
        "draft_dispatch_share": round(
            draft_disp / max(1.0, draft_disp + dispatches), 4
        ),
        "drafted_tokens": drafted,
        "draft_dispatches": draft_disp,
    }
    # Pipelined-dispatch reconcile outcomes (spec_pipeline_enable,
    # docs/spec_decode.md): rollback_rate = re-proposed rows over all
    # reconciled rows — the pipeline's health signal. Keys appear only
    # when the pipeline actually reconciled something, so a baseline
    # WITH them flags the pipeline silently turning off as drift.
    rolled = deltas.get("spec_pipeline_rollbacks", 0.0)
    confirmed = deltas.get("spec_pipeline_confirmed", 0.0)
    if rolled or confirmed:
        out["pipeline_rollbacks"] = rolled
        out["pipeline_confirmed"] = confirmed
        out["pipeline_rollback_rate"] = round(
            rolled / (rolled + confirmed), 4
        )
    # Acceptance-adaptive draft width (spec_adaptive_k=on,
    # docs/spec_decode.md): mean verify width K over the run's adaptive
    # rounds. Gated — present only when the engine actually ran
    # adaptive rounds, so a baseline WITH the key flags adaptive K
    # silently turning off as schema drift.
    adaptive_rounds = deltas.get("spec_adaptive_rounds", 0.0)
    if adaptive_rounds:
        out["effective_k_mean"] = round(
            deltas.get("spec_adaptive_k_sum", 0.0) / adaptive_rounds, 4
        )
        out["adaptive_rounds"] = adaptive_rounds
    return out


def paged_attn_from_deltas(deltas: Dict[str, float]) -> Optional[Dict]:
    """Kernel-vs-gather dispatch split over the run window (a server
    that dispatched neither kind omits the block). ``kernel_share`` is the
    gate-facing ratio: a paged-kernel deployment silently regressing to
    the XLA gather (geometry drift, env force-off) drops it to 0."""
    kernel = deltas.get("paged_attn_kernel_dispatches", 0.0)
    gather = deltas.get("paged_attn_gather_dispatches", 0.0)
    total = kernel + gather
    if not total:
        return None
    return {
        "kernel_dispatches": kernel,
        "gather_dispatches": gather,
        "kernel_share": round(kernel / total, 4),
    }


def disagg_from_deltas(deltas: Dict[str, float]) -> Optional[Dict]:
    """P/D-disaggregation block over the run window (disagg-policy
    engines only — a unified server hands nothing off and the block is
    omitted, so a baseline WITH the block flags disagg silently
    reverting as schema drift). ``decode_stall_s`` is enqueue→import
    wait (prefill outran decode consumption); ``backpressure_stall_s``
    is prefill-tier time stalled on a full transfer queue;
    ``recompute`` must stay flat — a handoff whose pages died forced a
    re-prefill, which the same-host shared-pool protocol structurally
    never does (the gate judges it equal against a zero baseline)."""
    handoffs = deltas.get("handoffs", 0.0)
    if not handoffs:
        return None
    return {
        "handoffs": handoffs,
        "pages_transferred": deltas.get("handoff_pages", 0.0),
        "bytes_transferred": deltas.get("handoff_bytes", 0.0),
        "decode_stall_s": round(deltas.get("handoff_wait_seconds", 0.0), 4),
        "backpressure_stall_s": round(
            deltas.get("handoff_stall_seconds", 0.0), 4
        ),
        "recompute": deltas.get("handoff_recompute", 0.0),
    }


def retrieval_tier_from_deltas(deltas: Dict[str, float]) -> Optional[Dict]:
    """Retrieval-tier block over the run window (tier-backend servers
    only — with ``retriever.backend=off`` nothing dispatches and the
    block is omitted, so a baseline WITH it flags the tier silently
    reverting to synchronous per-request search as schema drift).
    ``queries_per_dispatch`` is the batching win the tier exists for —
    queries coalesced per compiled ANN launch; ``backpressure_stall_s``
    is submitter time stalled on a full transfer queue;
    ``window_wait_s`` is time the tier yielded to the scheduler's
    prefill-idle window before dispatching."""
    queries = deltas.get("retrieval_tier_queries", 0.0)
    dispatches = deltas.get("retrieval_tier_dispatches", 0.0)
    if not queries and not dispatches:
        return None
    return {
        "queries": queries,
        "dispatches": dispatches,
        "queries_per_dispatch": round(queries / max(1.0, dispatches), 4),
        "backpressure_stall_s": round(
            deltas.get("retrieval_tier_backpressure_stall_seconds", 0.0), 4
        ),
        "window_wait_s": round(
            deltas.get("retrieval_tier_window_wait_seconds", 0.0), 4
        ),
    }


def bubble_from_deltas(deltas: Dict[str, float]) -> Optional[Dict]:
    """Dispatch-bubble block over the run window (timeline-on engines
    only — with ``GENAI_DISPATCH_TIMELINE=off`` no spans record and the
    block is omitted, so a baseline WITH the block flags the recorder
    silently turning off as schema drift). The shares decompose the
    run's engine-ACTIVE wall (device + lock + gap + readback component
    seconds — engine/dispatch_timeline.py: ``device`` is the sum of the
    launches' ``device_s`` from the completion stamp, ``gap`` the sum of
    their ``starved_s``) and sum to 1.0;
    ``bubble_ratio`` is everything that is not device time, the gated
    headline next to ``lock_wait_share`` (cross-tier dispatch-lock
    contention), ``host_gap_share`` / ``readback_share`` (the two
    components the pipelined spec dispatch attacks — both gated with a
    ``lower`` direction), and ``gap_p95_s`` (the worst stretches the
    device had nothing of the engine's to run before a launch, from
    run-window histogram bucket deltas)."""
    spans = deltas.get("timeline_spans", 0.0)
    device = deltas.get("timeline_device_seconds", 0.0)
    lock = deltas.get("timeline_lock_wait_seconds", 0.0)
    gap = deltas.get("timeline_gap_seconds", 0.0)
    readback = deltas.get("timeline_readback_stall_seconds", 0.0)
    active = device + lock + gap + readback
    if spans <= 0 or active <= 0:
        return None
    out = {
        "bubble_ratio": round((active - device) / active, 4),
        "device_share": round(device / active, 4),
        "lock_wait_share": round(lock / active, 4),
        "host_gap_share": round(gap / active, 4),
        "readback_share": round(readback / active, 4),
        "active_wall_s": round(active, 4),
        "spans": spans,
    }
    gap_p95 = _gap_p95_from_deltas(deltas)
    if gap_p95 is not None:
        out["gap_p95_s"] = gap_p95
    return out


def _gap_p95_from_deltas(deltas: Dict[str, float]) -> Optional[float]:
    """Nearest-upper-bound p95 of the dispatch-gap distribution over
    the run window, from the ``timeline_gap_le_*`` cumulative-bucket
    deltas (+Inf resolves to the largest finite bound — a conservative
    floor rather than an unusable infinity)."""
    buckets = []
    for key, count in deltas.items():
        if not key.startswith("timeline_gap_le_"):
            continue
        raw = key[len("timeline_gap_le_"):]
        try:
            upper = float("inf") if raw == "+Inf" else float(raw)
        except ValueError:
            continue
        buckets.append((upper, count))
    if not buckets:
        return None
    buckets.sort()
    total = buckets[-1][1]  # +Inf cumulative = all observations
    if total <= 0:
        return None
    target = 0.95 * total
    finite = [u for u, _ in buckets if u != float("inf")]
    for upper, cumulative in buckets:
        if cumulative >= target:
            if upper == float("inf"):
                upper = finite[-1] if finite else 0.0
            return round(upper, 6)
    return None


def compiles_from_deltas(
    deltas: Dict[str, float], scraped: bool
) -> Optional[Dict]:
    """Compile-path block over the run window. ``hot_path_total`` is
    the gated headline — the executable-ladder discipline (PRs
    2/5/7/11) promises ZERO XLA compiles after warmup, so any nonzero
    value is a regression the perf gate refuses. Omitted entirely when
    the metrics scrape failed: a zero measured from no data would be
    the worst kind of green (the gate then flags the metric as
    disappeared against a baseline that carries it)."""
    if not scraped:
        return None
    return {
        "hot_path_total": deltas.get("hot_path_compiles", 0.0),
        "executables": deltas.get("compiled_executables", 0.0),
    }


def _slo_block(slo: Dict) -> Dict:
    return {
        "all_met": slo.get("all_met"),
        "objectives": {
            name: {
                k: v
                for k, v in obj.items()
                if k in ("met", "attainment", "p95_ms", "rate", "samples")
            }
            for name, obj in (slo.get("objectives") or {}).items()
        },
    }


class FleetScraper:
    """Telemetry over a ROUTED run: one :class:`TelemetryScraper` per
    replica (each replica's flight-recorder cursor tails
    independently), timelines merged by trace id at read time.

    Merge rule (``utils/trace_stitch.pick_richest`` — the shared
    stitching module): a request is served by exactly one replica, so
    trace collisions only arise from failover/shed remnants — the
    timeline with more events (the one that actually reached the
    engine) wins. Hit rates are computed from the SUMMED metric
    deltas, so the fleet ratio weights replicas by their real traffic.
    The per-replica SLO verdicts are router-side concerns (the router
    process evaluates its own objectives); a fleet summary reports
    ``slo: None`` rather than picking one replica's window as "the"
    verdict.
    """

    def __init__(self, replica_urls, interval_s: float = 0.5):
        if not replica_urls:
            raise ValueError("FleetScraper needs at least one replica URL")
        self.scrapers = [
            TelemetryScraper(url, interval_s=interval_s) for url in replica_urls
        ]

    def start(self) -> None:
        for scraper in self.scrapers:
            scraper.start()

    def stop(self) -> None:
        for scraper in self.scrapers:
            scraper.stop()

    def snapshot_timelines(self) -> Dict[str, Dict]:
        merged: Dict[str, Dict] = {}
        for scraper in self.scrapers:
            for trace, tl in scraper.snapshot_timelines().items():
                held = merged.get(trace)
                merged[trace] = (
                    tl if held is None
                    else trace_stitch.pick_richest((held, tl))
                )
        return merged

    def metric_deltas(self) -> Dict[str, float]:
        totals: Dict[str, float] = {}
        for scraper in self.scrapers:
            for key, value in scraper.metric_deltas().items():
                totals[key] = totals.get(key, 0.0) + value
        return totals

    def summary(self) -> Dict:
        deltas = self.metric_deltas()
        return {
            "hit_rates": hit_rates_from_deltas(deltas),
            "utilization": None,
            "slo": None,
            "paged_attn": paged_attn_from_deltas(deltas),
            "spec": spec_from_deltas(deltas),
            "retrieval_tier": retrieval_tier_from_deltas(deltas),
            "bubble": bubble_from_deltas(deltas),
            # ALL replicas must have scraped: a failed replica would
            # contribute a silent zero to the gated hot_path_total —
            # the "zero measured from no data" the block exists to
            # refuse.
            "compiles": compiles_from_deltas(
                deltas,
                scraped=all(s._after is not None for s in self.scrapers),
            ),
        }
