"""Run summarization: percentile math + the one-JSON-line record.

One loadgen run emits ONE JSON line holding
everything a trajectory comparison needs: the workload identity
(spec hash, seed, profile), run provenance (git SHA/dirty, config
fingerprint, weights regime — utils/provenance.py), client-observed
latency percentiles per scenario and overall, outcome rates, the
server-side hit rates and utilization gauges scraped over the run, the
SLO verdict with sample counts, and the phase-level latency
attribution joined from flight-recorder timelines.

``tools/check_perf_regression.py`` gates exactly this shape — the
gated-metric schema lives in ``tools/loadgen/schema.py`` and
``tests/test_loadgen.py`` pins that every summary field the schema
requires is actually emitted, so the two cannot drift silently.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from tools.loadgen import phases as phases_mod
from tools.loadgen.client import RequestOutcome
from tools.loadgen.schema import SCHEMA_VERSION
from tools.loadgen.workload import WorkloadSpec, schedule_stats, spec_hash


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank-with-rounding percentile (the SLO tracker's rule,
    utils/slo.py) so client-side and server-side p95s are computed the
    same way."""
    if not values:
        return None
    ordered = sorted(values)
    idx = min(len(ordered) - 1, max(0, int(round(p * (len(ordered) - 1)))))
    return ordered[idx]


def pct_block(values: Sequence[float]) -> Dict[str, Optional[float]]:
    return {
        "p50": _r(percentile(values, 0.50)),
        "p95": _r(percentile(values, 0.95)),
        "p99": _r(percentile(values, 0.99)),
    }


def _r(v: Optional[float]) -> Optional[float]:
    return round(v, 6) if v is not None else None


def build_summary(
    spec: WorkloadSpec,
    schedule,
    outcomes: List[RequestOutcome],
    wall_s: float,
    provenance: Dict,
    profile: str = "",
    timelines: Optional[Dict[str, Dict]] = None,
    telemetry: Optional[Dict] = None,
) -> Dict:
    """Assemble the run's JSON line. ``timelines`` maps trace id →
    flight-recorder timeline (the scraper's join set); ``telemetry``
    carries the scraper's hit-rate/utilization/SLO summaries."""
    counts = {s: 0 for s in ("ok", "degraded", "aborted", "shed", "deadline", "error")}
    for o in outcomes:
        counts[o.status] = counts.get(o.status, 0) + 1
    total = len(outcomes)
    ok = counts["ok"] + counts["degraded"]  # answered, possibly degraded
    ttfts = [o.ttft_s for o in outcomes if o.ttft_s is not None]
    lats = [o.latency_s for o in outcomes if o.status in ("ok", "degraded")]
    gaps: List[float] = []
    for o in outcomes:
        gaps.extend(o.gaps_s)

    per_scenario: Dict[str, Dict] = {}
    for o in outcomes:
        per_scenario.setdefault(o.scenario, []).append(o)
    scenario_block = {}
    for name, outs in sorted(per_scenario.items()):
        s_ok = [o for o in outs if o.status in ("ok", "degraded")]
        s_ttfts = [o.ttft_s for o in outs if o.ttft_s is not None]
        scenario_block[name] = {
            "requests": len(outs),
            "ok": len(s_ok),
            "qps": round(len(s_ok) / max(wall_s, 1e-9), 4),
            "ttft_p50_s": _r(percentile(s_ttfts, 0.50)),
            "ttft_p95_s": _r(percentile(s_ttfts, 0.95)),
            "latency_p95_s": _r(
                percentile([o.latency_s for o in s_ok], 0.95)
            ),
        }

    # Placement skew, straight from the X-GenAI-Replica response header
    # (router target mode only — bare servers stamp nothing): request
    # counts per serving replica, so a lopsided affinity ring shows up
    # in the bench line itself instead of needing a router-log join.
    replica_counts: Dict[str, int] = {}
    for o in outcomes:
        if getattr(o, "replica", ""):
            replica_counts[o.replica] = replica_counts.get(o.replica, 0) + 1

    # Phase attribution: join client outcomes with server timelines by
    # trace id, attribute each, cohort by latency percentile.
    timelines = timelines or {}
    attributed = []
    for o in outcomes:
        tl = timelines.get(o.trace_id)
        if tl is None:
            continue
        ph = phases_mod.attribute(tl)
        if ph is not None:
            attributed.append((o.latency_s, ph))
    phase_block = {
        "requests_joined": len(attributed),
        "buckets": phases_mod.bucketize(attributed),
    }

    out = {
        "kind": "loadgen",
        "schema_version": SCHEMA_VERSION,
        "profile": profile,
        "workload": spec.name,
        "seed": spec.seed,
        "spec_hash": spec_hash(spec),
        "provenance": provenance,
        "schedule": schedule_stats(schedule),
        "wall_s": round(wall_s, 3),
        "qps": round(ok / max(wall_s, 1e-9), 4),
        "requests": {"total": total, **counts},
        "rates": {
            "shed": round(counts["shed"] / max(total, 1), 4),
            "degraded": round(counts["degraded"] / max(total, 1), 4),
            "error": round(counts["error"] / max(total, 1), 4),
            "abort": round(counts["aborted"] / max(total, 1), 4),
            "deadline": round(counts["deadline"] / max(total, 1), 4),
        },
        "ttft_s": pct_block(ttfts),
        "latency_s": pct_block(lats),
        "inter_token_s": pct_block(gaps),
        "per_scenario": scenario_block,
        "phases": phase_block,
    }
    if replica_counts:
        out["per_replica"] = {"requests": dict(sorted(replica_counts.items()))}
    telemetry = telemetry or {}
    out["hit_rates"] = telemetry.get("hit_rates") or {}
    out["utilization"] = telemetry.get("utilization")
    out["slo"] = telemetry.get("slo")
    # kernel-vs-gather dispatch split (omitted when the server
    # dispatched neither, or no scrape)
    if telemetry.get("paged_attn"):
        out["paged_attn"] = telemetry["paged_attn"]
    # speculative-decoding block (spec-on engines; omitted when nothing
    # drafted over the run, so a baseline WITH the block flags spec
    # silently turning off as drift instead of gating zeros)
    if telemetry.get("spec"):
        out["spec"] = telemetry["spec"]
    # P/D-disaggregation block (omitted on unified-policy servers, so
    # a baseline WITH it flags disagg silently reverting).
    if telemetry.get("disagg"):
        out["disagg"] = telemetry["disagg"]
    # Retrieval-tier block (engine/retrieval_tier.py): omitted on
    # backend=off servers, so a baseline WITH it flags the tier
    # silently reverting to synchronous per-request search.
    if telemetry.get("retrieval_tier"):
        out["retrieval_tier"] = telemetry["retrieval_tier"]
    # dispatch-bubble block (engine/dispatch_timeline.py): omitted when
    # the timeline recorder is off or no spans landed in the window, so
    # a baseline WITH it flags the recorder silently turning off.
    if telemetry.get("bubble"):
        out["bubble"] = telemetry["bubble"]
    # compile-path block (engine/compile_watch.py): present whenever
    # the metrics scrape succeeded, so the gate's zero band on
    # compiles.hot_path_total refuses a PR that reintroduces
    # steady-state recompiles.
    if telemetry.get("compiles") is not None:
        out["compiles"] = telemetry["compiles"]
    return out
