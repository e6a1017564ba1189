"""The gated-metric schema shared by loadgen summaries, bench JSON
lines, and tools/check_perf_regression.py.

Every numeric leaf a loadgen summary emits must be claimed by exactly
one pattern here — the gate exits 2 (schema drift) when a run carries
a metric the schema has never heard of, the same contract
check_metric_docs enforces for the metric catalog: you cannot add a
measurement without deciding how it is judged. Patterns are dotted
paths with ``*`` wildcards per component (``per_scenario.*.qps``).

Each spec:

- ``direction`` — ``higher`` (throughput-like: regression when the
  value drops below the band), ``lower`` (latency/rate-like),
  ``equal`` (schedule-determined counts), or ``info`` (recorded,
  recognized, never gated);
- ``rel_tol`` / ``abs_tol`` — the tolerance band around the baseline;
  both default 0 and combine additively (band = base*rel + abs).

Defaults here are sized for the deterministic CPU smoke profile (wide
latency bands — CI machines jitter; zero-width bands on the
schedule-determined counts). A committed baseline file may override
any band via its ``tolerance_overrides`` map for hardware profiles.
"""
from __future__ import annotations

import fnmatch
from typing import Dict, Optional

SCHEMA_VERSION = 1

# An SLO "met" verdict backed by fewer window samples than this is not
# evidence — the gate refuses to treat it as pass/fail either way.
MIN_SLO_SAMPLES = 20

GATE_METRICS: Dict[str, Dict] = {
    # throughput
    "qps": {"direction": "higher", "rel_tol": 0.35},
    "per_scenario.*.qps": {"direction": "higher", "rel_tol": 0.40},
    # client latency (wide default bands with an absolute floor: CPU CI
    # jitters by hundreds of ms on sub-second baselines; tighten via
    # baseline tolerance_overrides on hardware)
    "ttft_s.*": {"direction": "lower", "rel_tol": 0.60, "abs_tol": 0.5},
    "latency_s.*": {"direction": "lower", "rel_tol": 0.60, "abs_tol": 0.5},
    "inter_token_s.*": {"direction": "lower", "rel_tol": 0.80, "abs_tol": 0.25},
    "per_scenario.*.requests": {"direction": "equal"},
    "per_scenario.*.ok": {"direction": "higher"},
    "per_scenario.*.ttft_p50_s": {"direction": "lower", "rel_tol": 0.60, "abs_tol": 0.5},
    "per_scenario.*.ttft_p95_s": {"direction": "lower", "rel_tol": 0.60, "abs_tol": 0.5},
    "per_scenario.*.latency_p95_s": {"direction": "lower", "rel_tol": 0.60, "abs_tol": 0.5},
    # outcome counts/rates: the deterministic profile admits no slack
    "requests.total": {"direction": "equal"},
    "requests.ok": {"direction": "higher"},
    "requests.degraded": {"direction": "lower"},
    "requests.shed": {"direction": "lower"},
    "requests.deadline": {"direction": "lower"},
    "requests.error": {"direction": "lower"},
    "requests.aborted": {"direction": "equal"},
    "rates.*": {"direction": "lower", "abs_tol": 0.01},
    # phase attribution: a regression names its phase; bands are wider
    # than the headline latency bands (cohorts are small)
    "phases.requests_joined": {"direction": "higher", "rel_tol": 0.25},
    "phases.buckets.*.queue_wait": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 0.5},
    "phases.buckets.*.prefill": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 0.5},
    "phases.buckets.*.decode": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 0.5},
    "phases.buckets.*.retrieval": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 0.5},
    "phases.buckets.*.batcher": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 0.5},
    "phases.buckets.*.other": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 0.5},
    "phases.buckets.*.latency_s": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 0.5},
    "phases.buckets.*.requests": {"direction": "info"},
    # server-side rates scraped over the run
    # Hit-rate bands are wide: a few dozen requests make coarse ratios
    # (the cpu_smoke profile sees ±0.12 run-to-run); tighten via
    # baseline tolerance_overrides on long hardware runs.
    "hit_rates.prefix_cache": {"direction": "higher", "abs_tol": 0.25},
    "hit_rates.spec_acceptance": {"direction": "higher", "abs_tol": 0.25},
    "hit_rates.batcher_coalesced_dispatches": {"direction": "info"},
    "utilization.*": {"direction": "info"},
    # paged attention serving-path split (scraped counter deltas): the
    # share is the gated headline — a paged-kernel deployment silently
    # regressing to the XLA gather (geometry drift, env force-off)
    # collapses it toward 0; raw dispatch counts are schedule-shaped
    # and recorded for attribution only.
    "paged_attn.kernel_dispatches": {"direction": "info"},
    "paged_attn.gather_dispatches": {"direction": "info"},
    "paged_attn.kernel_share": {"direction": "higher", "abs_tol": 0.10},
    # speculative decoding (engine/spec_decode.py + spec_draft.py):
    # tokens per target dispatch is the headline — spec silently
    # degrading (draft model gone, eligibility regression) collapses it
    # toward 1; acceptance guards draft quality. The draft-dispatch
    # share and raw counts attribute where launches went (the draft's
    # own cost is schedule-shaped — recorded, not gated).
    "spec.tokens_per_dispatch": {"direction": "higher", "rel_tol": 0.25},
    "spec.acceptance_ratio": {"direction": "higher", "abs_tol": 0.25},
    "spec.draft_dispatch_share": {"direction": "info"},
    "spec.drafted_tokens": {"direction": "info"},
    "spec.draft_dispatches": {"direction": "info"},
    # Pipelined spec dispatch (spec_pipeline_enable,
    # docs/spec_decode.md): the rollback rate is the pipeline's health
    # signal — optimistic runahead drafts that the verify refuted, each
    # costing a re-proposal stall. Gated lower with a wide band
    # (workload-shaped: copy-heavy prompts confirm far more often than
    # adversarial ones); the raw counts are attribution context.
    "spec.pipeline_rollback_rate": {"direction": "lower", "abs_tol": 0.25},
    "spec.pipeline_rollbacks": {"direction": "info"},
    "spec.pipeline_confirmed": {"direction": "info"},
    # Acceptance-adaptive draft width (spec_adaptive_k,
    # docs/spec_decode.md): the mean verify width over the run's
    # adaptive rounds. Gated higher with a wide band — a healthy
    # (accepting) workload holds K near the configured max, so adaptive
    # K silently collapsing to the floor (tracker starved, threshold
    # drift) fails against a full-width baseline; round counts are
    # schedule-shaped attribution.
    "spec.effective_k_mean": {"direction": "higher", "rel_tol": 0.5},
    "spec.adaptive_rounds": {"direction": "info"},
    # P/D disaggregation (engine/scheduler/, docs/scheduler.md):
    # recompute is the headline invariant — a handoff whose pages died
    # forced a re-prefill, which the same-host shared-pool protocol
    # structurally never does; it is judged `equal` against a zero
    # baseline with no band, the prefix-copy-dispatch discipline
    # applied to handoffs. Stall times gate with generous absolute
    # bands (CPU CI jitter); counts are schedule-shaped attribution.
    "disagg.handoffs": {"direction": "info"},
    "disagg.pages_transferred": {"direction": "info"},
    "disagg.bytes_transferred": {"direction": "info"},
    "disagg.decode_stall_s": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 2.0},
    "disagg.backpressure_stall_s": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 2.0},
    "disagg.recompute": {"direction": "equal"},
    # Disaggregated retrieval tier (engine/retrieval_tier.py,
    # docs/retrieval_tier.md): queries_per_dispatch is the batching
    # headline — queries coalesced per compiled ANN launch; it gates
    # higher with a wide band (wave shapes are arrival-timing shaped
    # on CPU CI). Stall/wait times take the disagg stall bands; raw
    # counts are schedule-shaped attribution.
    "retrieval_tier.queries": {"direction": "info"},
    "retrieval_tier.dispatches": {"direction": "info"},
    "retrieval_tier.queries_per_dispatch": {
        "direction": "higher", "rel_tol": 1.0,
    },
    "retrieval_tier.backpressure_stall_s": {
        "direction": "lower", "rel_tol": 1.0, "abs_tol": 2.0,
    },
    "retrieval_tier.window_wait_s": {
        "direction": "lower", "rel_tol": 1.0, "abs_tol": 2.0,
    },
    # Dispatch-bubble attribution (engine/dispatch_timeline.py): the
    # shares decompose the run's engine-active wall (device + lock +
    # gap + readback, summing to 1.0; device and gap come from the
    # launches' completion stamps: device_s and starved_s). bubble_ratio (everything that is
    # not device time) and the lock-wait share gate with wide absolute
    # bands — host-scheduling jitter on CPU CI moves them by tens of
    # points — so only a gross attribution regression (a new serial
    # section, a lock added to the hot path) fails; gap_p95_s gets the
    # stall-style band. host_gap_share and readback_share are the two
    # components the pipelined spec dispatch (spec_pipeline_enable)
    # exists to shrink — both gate lower with the same wide CPU-jitter
    # band, so the pipeline silently reverting to per-round syncs
    # (which re-inflates them) fails against a pipelined baseline.
    "bubble.bubble_ratio": {"direction": "lower", "abs_tol": 0.20},
    "bubble.lock_wait_share": {"direction": "lower", "abs_tol": 0.15},
    "bubble.gap_p95_s": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 1.0},
    "bubble.device_share": {"direction": "info"},
    "bubble.host_gap_share": {"direction": "lower", "abs_tol": 0.15},
    "bubble.readback_share": {"direction": "lower", "abs_tol": 0.15},
    "bubble.active_wall_s": {"direction": "info"},
    "bubble.spans": {"direction": "info"},
    # compile-path observability (engine/compile_watch.py): the
    # executable-ladder discipline (PRs 2/5/7/11) promises ZERO XLA
    # compiles after warmup — hot_path_total is judged `equal` against
    # a zero baseline with no band, so ONE post-warmup compile in the
    # measured window fails the gate. The executable count is
    # config-shaped context, recorded for attribution only.
    "compiles.hot_path_total": {"direction": "equal"},
    "compiles.executables": {"direction": "info"},
    # fleet A/B block (tools/loadgen/fleet.py, docs/router.md): the
    # acceptance ratios are the headline — affinity must keep >= its
    # baseline share of the single-replica hit rate, and its margin
    # over round-robin must not collapse. Per-policy hit rates inherit
    # the wide smoke-run band; failovers regress when they grow.
    "fleet.replicas": {"direction": "equal"},
    "fleet.policies.*.qps": {"direction": "higher", "rel_tol": 0.40},
    "fleet.policies.*.ok": {"direction": "higher"},
    "fleet.policies.*.prefix_cache_hit_rate": {
        "direction": "higher", "abs_tol": 0.25,
    },
    "fleet.policies.*.failovers": {"direction": "lower", "abs_tol": 2.0},
    "fleet.policies.*.sheds": {"direction": "info"},
    "fleet.policies.*.spills": {"direction": "info"},
    "fleet.hit_rate_preservation": {"direction": "higher", "abs_tol": 0.15},
    "fleet.hit_rate_delta_vs_round_robin": {
        "direction": "higher", "abs_tol": 0.20,
    },
    # Kill-replica chaos block (tools/loadgen/chaos.py,
    # docs/resilience.md): requests_lost is the headline invariant —
    # every client request answered despite the injected drain and the
    # SIGKILL; it is judged `equal` against a zero baseline with no
    # band (the disagg.recompute discipline applied to preemption).
    # The event counts are schedule-determined; restores must not
    # silently collapse to zero (a chaos pass where every preemption
    # degraded to prompt replay means snapshot relay is broken);
    # replay_fraction and the restore latency gate with wide CPU-CI
    # bands; raw counters are attribution context.
    "chaos.replicas": {"direction": "equal"},
    "chaos.kills": {"direction": "equal"},
    "chaos.drains": {"direction": "equal"},
    "chaos.restarts": {"direction": "equal"},
    "chaos.requests_lost": {"direction": "equal"},
    "chaos.preempted": {"direction": "info"},
    "chaos.spooled": {"direction": "info"},
    "chaos.restores": {"direction": "higher"},
    "chaos.replays": {"direction": "info"},
    "chaos.replay_fraction": {"direction": "lower", "abs_tol": 0.5},
    "chaos.restore_mean_s": {"direction": "lower", "rel_tol": 1.0, "abs_tol": 2.0},
    "chaos.failovers": {"direction": "info"},
    "chaos.retry_budget_exhausted": {"direction": "equal"},
    "chaos.snapshot_bytes": {"direction": "info"},
    # run shape
    "wall_s": {"direction": "info"},
    "schedule.*": {"direction": "equal"},
}

# Metrics a gateable loadgen line must carry — their absence is schema
# drift (exit 2), because a "pass" that silently measured nothing is
# the worst kind of green.
REQUIRED_METRICS = (
    "qps",
    "ttft_s.p50",
    "latency_s.p50",
    "rates.shed",
    "rates.error",
    "requests.total",
    "phases.requests_joined",
)

# Subtrees the flattener skips: identity/provenance (compared
# structurally, not numerically) and the SLO block (judged by the
# dedicated sample-aware check, not per-leaf bands).
SKIP_SUBTREES = ("provenance", "slo")
SKIP_LEAVES = ("seed", "schema_version", "spec_hash", "profile", "kind", "workload")


def path_matches(pattern: str, path: str) -> bool:
    """Dotted-path wildcard match: each ``.``-separated component of
    ``pattern`` may be a glob (``per_scenario.*.qps``); component
    counts must agree. One matcher for schema claims AND baseline
    ``tolerance_overrides`` so the two can never diverge."""
    parts = path.split(".")
    pat_parts = pattern.split(".")
    if len(pat_parts) != len(parts):
        return False
    return all(
        fnmatch.fnmatchcase(part, pat)
        for part, pat in zip(parts, pat_parts)
    )


def spec_for(path: str) -> Optional[Dict]:
    """The gate spec claiming a flattened metric path, or None when the
    schema has never heard of it (= drift)."""
    for pattern, spec in GATE_METRICS.items():
        if path_matches(pattern, path):
            return spec
    return None
