"""Loadgen harness math + the perf-regression gate (tier-1, no engine).

Covers the ISSUE-9 satellite surface: percentile estimation,
Poisson/think-time schedule determinism under a fixed seed, the
phase-attribution join (flight-recorder timeline → phase buckets),
regression-gate tolerance-band edges, schema-drift exit semantics, and
provenance refusal.
"""
import copy
import dataclasses
import json

import pytest

from generativeaiexamples_tpu.utils import provenance as provenance_mod
from tools import check_perf_regression as gate_mod
from tools.loadgen import phases as phases_mod
from tools.loadgen import schema as schema_mod
from tools.loadgen import summary as summary_mod
from tools.loadgen.client import RequestOutcome
from tools.loadgen.workload import (
    ScenarioSpec,
    WorkloadSpec,
    build_schedule,
    make_documents,
    schedule_stats,
    spec_hash,
)

# --------------------------------------------------------------------------- #
# Workload schedule determinism


def _mix(seed: int = 7) -> WorkloadSpec:
    return WorkloadSpec(
        name="mix",
        seed=seed,
        scenarios=(
            ScenarioSpec(name="chat", kind="sessions", sessions=3, turns=2,
                         think_time_s=0.5, max_tokens=16),
            ScenarioSpec(name="rag", kind="poisson", rate_qps=5.0,
                         duration_s=4.0, ramp_s=2.0, abort_fraction=0.3,
                         abort_after_frames=2),
            ScenarioSpec(name="ingest", kind="ingest", docs=2, doc_kb=1),
        ),
    )


def test_schedule_is_deterministic_under_seed():
    a, b = build_schedule(_mix()), build_schedule(_mix())
    assert a == b  # frozen dataclasses: full structural identity
    # a different seed produces a different schedule
    c = build_schedule(_mix(seed=8))
    assert a != c
    # ... and a different spec hash
    assert spec_hash(_mix()) == spec_hash(_mix())
    assert spec_hash(_mix()) != spec_hash(_mix(seed=8))


def test_adding_a_scenario_never_perturbs_the_others():
    base = _mix()
    grown = WorkloadSpec(
        name=base.name, seed=base.seed,
        scenarios=base.scenarios + (
            ScenarioSpec(name="extra", kind="poisson", rate_qps=1.0,
                         duration_s=1.0),
        ),
    )
    base_sched = [r for r in build_schedule(base)]
    grown_sched = [r for r in build_schedule(grown) if r.scenario != "extra"]
    assert base_sched == grown_sched


def test_poisson_arrivals_inside_horizon_and_ramp_thins():
    spec = WorkloadSpec(
        name="p", seed=3,
        scenarios=(
            ScenarioSpec(name="load", kind="poisson", rate_qps=50.0,
                         duration_s=4.0, ramp_s=4.0, start_s=1.0),
        ),
    )
    sched = build_schedule(spec)
    assert sched
    offsets = [r.at_s for r in sched]
    assert min(offsets) >= 1.0 and max(offsets) < 1.0 + 8.0
    # the linear ramp thins early arrivals: the first half of the ramp
    # window must hold fewer arrivals than the last (steady) window
    ramp_early = sum(1 for t in offsets if t < 3.0)
    steady = sum(1 for t in offsets if 5.0 <= t < 7.0)
    assert ramp_early < steady


def test_think_times_and_aborts_deterministic():
    sched = build_schedule(_mix())
    chat = [r for r in sched if r.scenario == "chat"]
    # first turn never thinks; later turns carry exponential draws
    for r in chat:
        assert (r.think_s == 0.0) == (r.turn == 0)
    aborts = {r.key for r in sched if r.abort_after_frames > 0}
    assert aborts == {r.key for r in build_schedule(_mix())
                      if r.abort_after_frames > 0}
    rag = [r for r in sched if r.scenario == "rag"]
    frac = len([r for r in rag if r.abort_after_frames > 0]) / len(rag)
    assert 0.05 < frac < 0.6  # around the configured 0.3


def test_trace_ids_unique_and_wellformed():
    sched = build_schedule(_mix())
    ids = [r.trace_id for r in sched]
    assert len(set(ids)) == len(ids)
    for t in ids:
        assert len(t) == 32 and int(t, 16) != 0


def test_make_documents_deterministic_and_sized():
    spec = _mix()
    sc = spec.scenarios[2]
    docs_a, docs_b = make_documents(spec, sc), make_documents(spec, sc)
    assert docs_a == docs_b and len(docs_a) == 2
    for _name, text in docs_a:
        assert len(text) >= sc.doc_kb * 1024


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="kind"):
        ScenarioSpec(name="x", kind="nope").validate()
    with pytest.raises(ValueError, match="rate_qps"):
        ScenarioSpec(name="x", kind="poisson").validate()
    with pytest.raises(ValueError, match="duplicate"):
        WorkloadSpec(
            name="d", seed=1,
            scenarios=(
                ScenarioSpec(name="a", kind="ingest", docs=1),
                ScenarioSpec(name="a", kind="ingest", docs=1),
            ),
        ).validate()
    round_trip = WorkloadSpec.from_dict(_mix().to_dict())
    assert round_trip == _mix()


# --------------------------------------------------------------------------- #
# Percentile math


def test_percentile_matches_slo_tracker_rule():
    from generativeaiexamples_tpu.utils.slo import SLOTracker

    values = [float(v) for v in (5, 1, 9, 3, 7, 2, 8, 4, 6, 10)]
    tracker_rule = SLOTracker._percentile(sorted(values), 0.95)
    assert summary_mod.percentile(values, 0.95) == tracker_rule
    assert summary_mod.percentile([], 0.5) is None
    assert summary_mod.percentile([4.0], 0.99) == 4.0
    assert summary_mod.percentile(values, 0.0) == 1.0
    assert summary_mod.percentile(values, 1.0) == 10.0
    assert summary_mod.percentile(values, 0.50) == 5.0  # round-half-even rank


# --------------------------------------------------------------------------- #
# Phase attribution


def _timeline(trace: str, events, total_s=1.0):
    return {
        "trace_id": trace,
        "total_s": total_s,
        "timeline": [{"t_s": t, "event": name, **attrs}
                     for t, name, attrs in events],
    }


def test_attribute_decomposes_phases():
    tl = _timeline("t1", [
        (0.00, "http_request", {}),
        (0.02, "retrieve", {"duration_s": 0.015}),
        (0.05, "submit", {"rid": 1}),
        (0.25, "admit", {"slot": 0, "queue_wait_s": 0.2}),
        (0.45, "first_token", {"ttft_s": 0.4}),
        (0.90, "decode_leave", {"slot": 0}),
        (0.95, "finish", {}),
    ], total_s=1.0)
    ph = phases_mod.attribute(tl)
    assert ph["queue_wait"] == pytest.approx(0.2)
    assert ph["prefill"] == pytest.approx(0.20)
    assert ph["decode"] == pytest.approx(0.45)
    assert ph["retrieval"] == pytest.approx(0.015)
    assert ph["other"] == pytest.approx(1.0 - (0.2 + 0.2 + 0.45 + 0.015))


def test_attribute_multi_rid_sums_queue_wait_and_batcher():
    tl = _timeline("t2", [
        (0.0, "submit", {"rid": 1}),
        (0.1, "admit", {"queue_wait_s": 0.1}),
        (0.2, "batcher_coalesced", {"wait_ms": 30.0}),
        (0.3, "submit", {"rid": 2}),
        (0.5, "admit", {"queue_wait_s": 0.2}),
        (0.6, "first_token", {}),
        (0.9, "decode_leave", {}),
    ])
    ph = phases_mod.attribute(tl)
    assert ph["queue_wait"] == pytest.approx(0.3)
    assert ph["batcher"] == pytest.approx(0.03)


def test_attribute_requires_engine_chain():
    # shed before submit: nothing to attribute
    assert phases_mod.attribute(
        _timeline("t3", [(0.0, "http_request", {}), (0.01, "shed", {})])
    ) is None


def test_bucketize_single_request_lands_in_one_cohort():
    one = [(1.0, {p: 0.1 for p in phases_mod.PHASES})]
    buckets = phases_mod.bucketize(one)
    assert sum(b["requests"] for b in buckets.values()) == 1
    assert list(buckets) == ["p50"]


def test_scraper_anchor_failure_disables_tail():
    """An unanchored tail must stay OFF: deterministic trace ids mean a
    cursor-0 fallback would join a PRIOR same-spec run's timelines into
    this run's phase attribution as silently wrong data."""
    from tools.loadgen.telemetry import TelemetryScraper

    scraper = TelemetryScraper("http://127.0.0.1:9")  # nothing listens
    scraper.start()
    try:
        assert scraper._cursor is None
        scraper._poll()  # must be a no-op, not a since=0 fetch
        assert scraper.snapshot_timelines() == {}
    finally:
        scraper.stop()
    summary = scraper.summary()
    assert summary["hit_rates"] == {} and summary["slo"] is None


def test_bucketize_cohorts_by_latency():
    attributed = [
        (float(i), {"queue_wait": float(i), "prefill": 0.0, "decode": 0.0,
                    "retrieval": 0.0, "batcher": 0.0, "other": 0.0})
        for i in range(1, 101)
    ]
    buckets = phases_mod.bucketize(attributed)
    assert set(buckets) == {"p50", "p50_p95", "p95_p99", "p99_up"}
    assert buckets["p50"]["requests"] == 50
    assert buckets["p95_p99"]["requests"] == 4
    assert buckets["p99_up"]["requests"] == 1
    assert buckets["p99_up"]["queue_wait"] == 100.0
    assert buckets["p50"]["latency_s"] < buckets["p50_p95"]["latency_s"]
    assert phases_mod.bucketize([]) == {}


# --------------------------------------------------------------------------- #
# Summary + schema coverage


def _outcomes():
    outs = []
    for i in range(20):
        outs.append(RequestOutcome(
            scenario="rag", key=f"rag/{i}", trace_id=f"{i:032x}",
            scheduled_s=0.1 * i, status="ok", http_status=200,
            ttft_s=0.1 + 0.01 * i, latency_s=0.5 + 0.02 * i, tokens=8,
            gaps_s=[0.01, 0.02],
        ))
    outs.append(RequestOutcome(
        scenario="rag", key="rag/20", trace_id=f"{20:032x}",
        scheduled_s=2.0, status="shed", http_status=429,
    ))
    outs.append(RequestOutcome(
        scenario="chat", key="chat/s0/t0", trace_id=f"{21:032x}",
        scheduled_s=0.0, status="degraded", http_status=200,
        ttft_s=0.2, latency_s=0.9, tokens=4,
    ))
    return outs


def _summary(with_slo=True):
    spec = _mix()
    sched = build_schedule(spec)
    outs = _outcomes()
    timelines = {}
    for i, o in enumerate(outs):
        if o.status == "shed":
            continue
        timelines[o.trace_id] = _timeline(o.trace_id, [
            (0.00, "submit", {"rid": i}),
            (0.05, "admit", {"queue_wait_s": 0.05}),
            (0.15, "first_token", {}),
            (0.40, "decode_leave", {}),
        ], total_s=o.latency_s)
    telemetry = {
        "hit_rates": {"prefix_cache": 0.8},
        "utilization": {"mfu_ratio": 0.31, "hbm_bw_ratio": 0.62},
        # paged-attention serving-path split (kernel-vs-gather): emitted
        # by paged engines; the coverage test pins its schema claims
        "paged_attn": {
            "kernel_dispatches": 40.0,
            "gather_dispatches": 2.0,
            "kernel_share": 0.9524,
        },
        # speculative-decoding block (spec-on engines): the coverage
        # test pins its schema claims
        "spec": {
            "tokens_per_dispatch": 3.2, "acceptance_ratio": 0.74,
            "draft_dispatch_share": 0.5, "drafted_tokens": 120.0,
            "draft_dispatches": 30.0,
            "pipeline_rollbacks": 3.0, "pipeline_confirmed": 27.0,
            "pipeline_rollback_rate": 0.1,
        },
        # dispatch-bubble block (engine/dispatch_timeline.py): the
        # coverage test pins its claims, including the lower-gated
        # host_gap_share / readback_share the spec pipeline attacks
        "bubble": {
            "bubble_ratio": 0.4, "device_share": 0.6,
            "lock_wait_share": 0.05, "host_gap_share": 0.25,
            "readback_share": 0.1, "active_wall_s": 8.0,
            "spans": 120.0, "gap_p95_s": 0.2,
        },
        # compile-path block (engine/compile_watch.py): the coverage
        # test pins its schema claims; hot_path_total is the
        # equal-direction zero band the gate enforces
        "compiles": {"hot_path_total": 0.0, "executables": 24.0},
        "slo": {
            "all_met": True,
            "objectives": {
                "ttft_p95": {"met": True, "attainment": 1.0,
                             "p95_ms": 150.0, "samples": 100},
                "shed_rate": {"met": True, "rate": 0.01, "samples": 100},
            },
        } if with_slo else None,
    }
    return summary_mod.build_summary(
        spec=spec, schedule=sched, outcomes=outs, wall_s=10.0,
        provenance=provenance_mod.provenance(
            config={"profile": "test"}, weights_random_init=True,
        ),
        profile="cpu_smoke", timelines=timelines, telemetry=telemetry,
    )


def test_summary_counts_rates_and_join():
    s = _summary()
    assert s["requests"]["total"] == 22
    assert s["requests"]["ok"] == 20 and s["requests"]["shed"] == 1
    assert s["rates"]["shed"] == round(1 / 22, 4)
    assert s["qps"] == round(21 / 10.0, 4)
    assert s["phases"]["requests_joined"] == 21
    assert "p50" in s["phases"]["buckets"]
    assert s["phases"]["buckets"]["p50"]["queue_wait"] > 0
    assert s["per_scenario"]["rag"]["requests"] == 21
    assert s["ttft_s"]["p95"] is not None
    assert json.loads(json.dumps(s)) == s  # one JSON line, serializable


def test_summary_schema_coverage_is_total():
    """Every numeric leaf the summary emits is claimed by the gate
    schema, and every REQUIRED metric is present — the summary and the
    gate cannot drift apart silently."""
    flat = gate_mod.flatten(_summary())
    unclaimed = [p for p in flat if schema_mod.spec_for(p) is None]
    assert unclaimed == []
    missing = [r for r in schema_mod.REQUIRED_METRICS if r not in flat]
    assert missing == []


# --------------------------------------------------------------------------- #
# Regression gate


def _baseline(record):
    return {
        "schema_version": schema_mod.SCHEMA_VERSION,
        "tolerance_overrides": {},
        "record": record,
    }


def test_gate_passes_against_identical_run():
    run = _summary()
    code, report = gate_mod.gate(copy.deepcopy(run), _baseline(run))
    assert code == 0, report
    assert report["regressions"] == [] and report["drift"] == []


def test_gate_tolerance_band_edges():
    base = _summary()
    # qps: higher-is-better, rel_tol 0.35 → exactly-at-band passes,
    # beyond-band fails
    band = base["qps"] * 0.35
    run_edge = copy.deepcopy(base)
    run_edge["qps"] = round(base["qps"] - band * 0.99, 6)
    code, report = gate_mod.gate(run_edge, _baseline(base))
    assert code == 0, report["regressions"]
    run_bad = copy.deepcopy(base)
    run_bad["qps"] = round(base["qps"] - band - 0.1, 4)
    code, report = gate_mod.gate(run_bad, _baseline(base))
    assert code == 1
    assert any("qps" in r for r in report["regressions"])


def test_gate_lower_direction_and_equal():
    base = _summary()
    run = copy.deepcopy(base)
    # ttft p95 lower-is-better: past the rel band + the CPU abs floor
    run["ttft_s"]["p95"] = base["ttft_s"]["p95"] * 2.0 + 1.0
    code, report = gate_mod.gate(run, _baseline(base))
    assert code == 1 and any("ttft_s.p95" in r for r in report["regressions"])
    # schedule-determined count drifting = the workload itself changed
    run2 = copy.deepcopy(base)
    run2["requests"]["total"] = base["requests"]["total"] + 1
    code, report = gate_mod.gate(run2, _baseline(base))
    assert code == 1
    assert any("requests.total" in r for r in report["regressions"])


def test_gate_refuses_hot_path_compiles():
    """compiles.hot_path_total is judged `equal` against the zero
    baseline with NO band: one post-warmup XLA compile in the measured
    window fails the gate (exit 1) — the executable-ladder regression
    guard."""
    base = _summary()
    assert base["compiles"]["hot_path_total"] == 0.0
    run = copy.deepcopy(base)
    run["compiles"]["hot_path_total"] = 1.0
    code, report = gate_mod.gate(run, _baseline(base))
    assert code == 1
    assert any("compiles.hot_path_total" in r for r in report["regressions"])
    # the executable count is config-shaped context, never gated
    run2 = copy.deepcopy(base)
    run2["compiles"]["executables"] = base["compiles"]["executables"] + 8
    code, report = gate_mod.gate(run2, _baseline(base))
    assert code == 0, report["regressions"]


def test_compiles_block_omitted_when_scrape_failed():
    """A zero measured from no data is the worst kind of green: the
    block is omitted entirely when the metrics scrape failed, and the
    gate then flags the metric as disappeared against a baseline that
    carries it."""
    from tools.loadgen.telemetry import compiles_from_deltas

    assert compiles_from_deltas({}, scraped=False) is None
    block = compiles_from_deltas(
        {"hot_path_compiles": 0.0, "compiled_executables": 12.0},
        scraped=True,
    )
    assert block == {"hot_path_total": 0.0, "executables": 12.0}
    base = _summary()
    run = copy.deepcopy(base)
    del run["compiles"]
    code, report = gate_mod.gate(run, _baseline(base))
    assert code == 1
    assert any(
        "compiles.hot_path_total" in r and "disappeared" in r
        for r in report["regressions"]
    )


def test_gate_tolerance_overrides_apply():
    base = _summary()
    run = copy.deepcopy(base)
    run["qps"] = base["qps"] * 0.2  # way past the default band
    baseline = _baseline(base)
    baseline["tolerance_overrides"] = {"qps": {"rel_tol": 5.0}}
    code, report = gate_mod.gate(run, baseline)
    assert code == 0, report["regressions"]


def test_gate_schema_drift_exits_2():
    base = _summary()
    # unknown metric in the run: exit 2 before any comparison
    run = copy.deepcopy(base)
    run["brand_new_number"] = 42.0
    code, report = gate_mod.gate(run, _baseline(base))
    assert code == 2
    assert any("brand_new_number" in d for d in report["drift"])
    # required metric missing: also drift
    run2 = copy.deepcopy(base)
    del run2["qps"]
    code, report = gate_mod.gate(run2, _baseline(base))
    assert code == 2
    assert any("required" in d for d in report["drift"])
    # metric present in baseline but vanished from the run: regression
    run3 = copy.deepcopy(base)
    del run3["hit_rates"]["prefix_cache"]
    code, report = gate_mod.gate(run3, _baseline(base))
    assert code == 1
    assert any("disappeared" in r for r in report["regressions"])


def test_gate_refuses_cross_provenance():
    base = _summary()
    run = copy.deepcopy(base)
    run["provenance"]["config_fingerprint"] = "deadbeef0000"
    code, report = gate_mod.gate(run, _baseline(base))
    assert code == 2
    assert any("provenance" in d for d in report["drift"])
    # weights regime mismatch refuses too
    run2 = copy.deepcopy(base)
    run2["provenance"]["weights_random_init"] = False
    code, _ = gate_mod.gate(run2, _baseline(base))
    assert code == 2
    # differing git SHAs alone are FINE — tracking change across
    # commits is the point
    run3 = copy.deepcopy(base)
    run3["provenance"]["git_sha"] = "f" * 40
    code, report = gate_mod.gate(run3, _baseline(base))
    assert code == 0, report


def test_gate_spec_hash_mismatch_is_not_a_comparison():
    base = _summary()
    run = copy.deepcopy(base)
    run["spec_hash"] = "000000000000"
    code, report = gate_mod.gate(run, _baseline(base))
    assert code == 1
    assert any("spec_hash" in r for r in report["regressions"])


def test_gate_slo_sample_awareness():
    base = _summary()
    # unmet with plenty of samples where baseline met: regression
    run = copy.deepcopy(base)
    run["slo"]["objectives"]["ttft_p95"]["met"] = False
    code, report = gate_mod.gate(run, _baseline(base))
    assert code == 1 and any("slo.ttft_p95" in r for r in report["regressions"])
    # same verdict but undersampled window: refused as evidence, no fail
    run2 = copy.deepcopy(base)
    run2["slo"]["objectives"]["ttft_p95"]["met"] = False
    run2["slo"]["objectives"]["ttft_p95"]["samples"] = (
        schema_mod.MIN_SLO_SAMPLES - 1
    )
    code, report = gate_mod.gate(run2, _baseline(base))
    assert code == 0
    assert any("ttft_p95" in u for u in report["undersampled"])
    # baseline verdict itself undersampled: not evidence either
    base3 = copy.deepcopy(base)
    base3["slo"]["objectives"]["ttft_p95"]["samples"] = 3
    run3 = copy.deepcopy(base)
    run3["slo"]["objectives"]["ttft_p95"]["met"] = False
    code, _ = gate_mod.gate(run3, _baseline(base3))
    assert code == 0


def test_gate_cli_contract(tmp_path):
    """File-level CLI: --record writes the baseline, a clean re-run
    passes (exit 0), a perturbed run fails (exit 1), drift exits 2."""
    run = _summary()
    run_path = tmp_path / "run.jsonl"
    run_path.write_text("# narrative\n" + json.dumps(run) + "\n")
    baseline_path = tmp_path / "LOADGEN_BASELINE.json"
    assert gate_mod.main(
        [str(run_path), "--baseline", str(baseline_path), "--record"]
    ) == 0
    assert baseline_path.exists()
    assert gate_mod.main(
        [str(run_path), "--baseline", str(baseline_path)]
    ) == 0
    bad = copy.deepcopy(run)
    bad["qps"] = run["qps"] * 0.1
    bad_path = tmp_path / "bad.jsonl"
    bad_path.write_text(json.dumps(bad) + "\n")
    assert gate_mod.main(
        [str(bad_path), "--baseline", str(baseline_path)]
    ) == 1
    drift = copy.deepcopy(run)
    drift["mystery"] = 1.0
    drift_path = tmp_path / "drift.jsonl"
    drift_path.write_text(json.dumps(drift) + "\n")
    assert gate_mod.main(
        [str(drift_path), "--baseline", str(baseline_path)]
    ) == 2
    # missing baseline without --record is a usage error
    assert gate_mod.main(
        [str(run_path), "--baseline", str(tmp_path / "absent.json")]
    ) == 2


# --------------------------------------------------------------------------- #
# Provenance module


def test_provenance_fingerprint_stability():
    fp = provenance_mod.config_fingerprint
    assert fp({"b": 2, "a": 1}) == fp({"a": 1, "b": 2})
    assert fp({"a": 1}) != fp({"a": 2})
    assert fp(None) is None

    @dataclasses.dataclass
    class Cfg:
        x: int = 1
        y: str = "z"

    assert fp(Cfg()) == fp(Cfg())
    assert fp(Cfg(x=2)) != fp(Cfg())


def test_provenance_env_overrides(monkeypatch):
    monkeypatch.setenv("GENAI_GIT_SHA", "cafe" * 10)
    monkeypatch.setenv("GENAI_GIT_DIRTY", "0")
    block = provenance_mod.provenance(config={"k": 1},
                                      weights_random_init=True)
    assert block["git_sha"] == "cafe" * 10
    assert block["git_dirty"] is False
    assert block["weights_random_init"] is True
    assert len(block["config_fingerprint"]) == 12


def test_provenance_comparable_reasons():
    a = {"config_fingerprint": "aaa", "weights_random_init": True,
         "git_sha": "1"}
    b = {"config_fingerprint": "bbb", "weights_random_init": False,
         "git_sha": "2"}
    reasons = provenance_mod.comparable(a, b)
    assert len(reasons) == 2
    assert provenance_mod.comparable(a, dict(a, git_sha="other")) == []
    # unknown (None) fields never block a comparison
    assert provenance_mod.comparable(
        a, {"config_fingerprint": None, "weights_random_init": None}
    ) == []


# --------------------------------------------------------------------------- #
# Fleet record (tools/loadgen/fleet.py)


def _fleet_summaries():
    base = _summary()
    affinity = copy.deepcopy(base)
    affinity["hit_rates"]["prefix_cache"] = 0.58
    affinity["router_counters"] = {"failovers": 0.0, "sheds": 1.0,
                                   "spills": 2.0}
    blind = copy.deepcopy(base)
    blind["qps"] = base["qps"] * 0.9
    blind["hit_rates"]["prefix_cache"] = 0.31
    blind["router_counters"] = {"failovers": 0.0, "sheds": 0.0,
                                "spills": 0.0}
    single = copy.deepcopy(base)
    single["hit_rates"]["prefix_cache"] = 0.60
    return {"affinity": affinity, "round_robin": blind, "single": single}


def test_fleet_record_comparison_block():
    from tools.loadgen import fleet as fleet_mod

    record = fleet_mod.build_fleet_record(_fleet_summaries(), n_replicas=2)
    fleet = record["fleet"]
    assert fleet["replicas"] == 2
    assert set(fleet["policies"]) == {"affinity", "round_robin", "single"}
    assert fleet["policies"]["affinity"]["prefix_cache_hit_rate"] == 0.58
    # preservation = affinity / single-replica reference
    assert fleet["hit_rate_preservation"] == round(0.58 / 0.60, 4)
    assert fleet["hit_rate_delta_vs_round_robin"] == round(0.58 - 0.31, 4)
    # the single pass never ran a router: counters default to 0
    assert fleet["policies"]["single"]["failovers"] == 0.0
    # the record body is the affinity pass's summary, counters stripped
    assert record["qps"] == _fleet_summaries()["affinity"]["qps"]
    assert "router_counters" not in record
    assert json.loads(json.dumps(record)) == record


def test_fleet_record_schema_coverage_is_total():
    """Every numeric leaf of a fleet-augmented record is claimed by the
    gate schema — the fleet block cannot drift out of the gate."""
    from tools.loadgen import fleet as fleet_mod

    record = fleet_mod.build_fleet_record(_fleet_summaries(), n_replicas=2)
    flat = gate_mod.flatten(record)
    unclaimed = [p for p in flat if schema_mod.spec_for(p) is None]
    assert unclaimed == []
    assert "fleet.hit_rate_preservation" in flat
    assert "fleet.policies.round_robin.qps" in flat


def test_fleet_record_gate_round_trip():
    """The fleet record passes the gate against itself and regresses
    when the preservation ratio collapses below its band."""
    from tools.loadgen import fleet as fleet_mod

    record = fleet_mod.build_fleet_record(_fleet_summaries(), n_replicas=2)
    base = _baseline(record)
    code, report = gate_mod.gate(record, base)
    assert code == 0, report
    bad = copy.deepcopy(record)
    bad["fleet"]["hit_rate_preservation"] = 0.4  # 0.9667 - 0.15 band > 0.4
    code, report = gate_mod.gate(bad, base)
    assert code == 1
    assert any("hit_rate_preservation" in r for r in report["regressions"])


def test_fleet_cli_rejects_unknown_policy():
    from tools.loadgen import fleet as fleet_mod

    with pytest.raises(SystemExit):
        fleet_mod.main(["--policies", "affinity,bogus"])
    with pytest.raises(SystemExit):
        fleet_mod.main(["--policies", ""])
    with pytest.raises(SystemExit):
        fleet_mod.main(["--replicas", "0"])


# --------------------------------------------------------------------------- #
# kill-replica chaos harness (tools/loadgen/chaos.py)


def test_chaos_smoke_profile_registered():
    from tools.loadgen.profiles import PROFILES

    profile = PROFILES["chaos_smoke"]
    assert profile.name == "chaos_smoke"
    assert profile.spec.seed == 31337  # the kill schedule derives from it
    kinds = {s.kind for s in profile.spec.scenarios}
    # open-loop arrivals AND closed-loop sessions must ride the chaos
    assert {"poisson", "sessions"} <= kinds
    # no abort traffic: client disconnects would alias with the
    # requests_lost invariant the gate pins to zero
    assert all(
        getattr(s, "abort_fraction", 0.0) in (0.0, None)
        for s in profile.spec.scenarios
    )


def test_kill_schedule_is_seed_deterministic():
    from tools.loadgen.chaos import build_kill_schedule

    a = build_kill_schedule(seed=1234)
    b = build_kill_schedule(seed=1234)
    assert a == b, "same seed must give the same schedule"
    assert a != build_kill_schedule(seed=1235)
    # the drain (graceful window) always lands before the hard kill
    assert 0 < a["drain_at_s"] < a["kill_at_s"]
    scaled = build_kill_schedule(seed=1234, time_scale=3.0)
    assert scaled["drain_at_s"] == pytest.approx(a["drain_at_s"] * 3.0)
    assert scaled["kill_at_s"] == pytest.approx(a["kill_at_s"] * 3.0)


def test_chaos_summary_block_fully_claimed_by_gate_schema():
    """Every key the chaos pass writes into summary["chaos"] is claimed
    by the gate schema, and the headline invariants carry the strict
    directions the CI gate depends on."""
    emitted = [
        "replicas", "kills", "drains", "restarts", "requests_lost",
        "preempted", "spooled", "restores", "replays", "replay_fraction",
        "restore_mean_s", "failovers", "retry_budget_exhausted",
        "snapshot_bytes",
    ]
    for key in emitted:
        spec = schema_mod.spec_for(f"chaos.{key}")
        assert spec is not None, f"chaos.{key} unclaimed by the schema"
    # zero-tolerance invariants: lost requests and schedule drift
    assert schema_mod.spec_for("chaos.requests_lost")["direction"] == "equal"
    assert schema_mod.spec_for("chaos.kills")["direction"] == "equal"
    # restore collapse (everything degrading to replay) must regress
    assert schema_mod.spec_for("chaos.restores")["direction"] == "higher"
