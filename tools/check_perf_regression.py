#!/usr/bin/env python3
"""Hard perf-regression gate over loadgen JSON lines.

Compares one measurement record (the last parseable JSON line of the
given run file — the loadgen stdout contract) against a committed
baseline file, using the tolerance bands declared in
``tools/loadgen/schema.py``. A record is a loadgen summary
(``{"kind": "loadgen", ...}``): every numeric leaf is flattened to a
dotted path and must be claimed by exactly one schema pattern;
unclaimed paths are SCHEMA DRIFT (exit 2, the check_metric_docs
contract: you cannot add a measurement without deciding how it is
judged). Claimed paths are gated by direction (``higher`` / ``lower`` /
``equal`` / ``info``) inside their band (``base*rel_tol + abs_tol``).

Provenance (utils/provenance.py) is enforced before any number is
compared: records measured under a different config fingerprint or
weights regime REFUSE to compare (exit 2) instead of charting noise.
SLO verdicts are judged sample-aware — an objective whose window held
fewer than ``MIN_SLO_SAMPLES`` samples is reported ``undersampled`` and
never counts as pass OR fail.

Usage:

    python tools/check_perf_regression.py RUN.json \
        [--baseline LOADGEN_BASELINE.json] [--record] [--json]

``--record`` validates the run against the schema and writes it as the
new baseline (with an empty ``tolerance_overrides`` map you may edit to
tighten/widen bands per deployment). Exit codes: 0 pass, 1 regression,
2 schema drift / provenance refusal / usage error.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Any, Dict, List, Optional, Tuple

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO_ROOT))

from generativeaiexamples_tpu.utils import provenance as provenance_mod  # noqa: E402
from tools.loadgen import schema as schema_mod  # noqa: E402

DEFAULT_BASELINE = "LOADGEN_BASELINE.json"


# --------------------------------------------------------------------------- #
# Record loading / flattening


def load_record(path: str) -> Dict[str, Any]:
    """The last parseable JSON object line of ``path`` (stdout captures
    interleave ``# comment`` lines with the one contract line)."""
    record: Optional[Dict[str, Any]] = None
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict):
                record = obj
    if record is None:
        raise ValueError(f"{path}: no JSON object line found")
    return record


def flatten(record: Dict[str, Any]) -> Dict[str, float]:
    """Numeric leaves as dotted paths, skipping the identity/provenance
    subtrees the schema declares non-numeric."""
    out: Dict[str, float] = {}

    def walk(node: Any, prefix: str) -> None:
        if isinstance(node, dict):
            for key, val in node.items():
                path = f"{prefix}.{key}" if prefix else str(key)
                if not prefix and key in schema_mod.SKIP_LEAVES:
                    continue
                if path.split(".")[0] in schema_mod.SKIP_SUBTREES:
                    continue
                walk(val, path)
        elif isinstance(node, bool):
            return  # booleans (slo met flags) are judged structurally
        elif isinstance(node, (int, float)):
            out[prefix] = float(node)

    walk(record, "")
    return out


# --------------------------------------------------------------------------- #
# Checks


def schema_check(record: Dict[str, Any]) -> List[str]:
    """Drift findings: unclaimed metric paths + missing required ones."""
    problems: List[str] = []
    flat = flatten(record)
    for path in sorted(flat):
        if schema_mod.spec_for(path) is None:
            problems.append(
                f"schema drift: metric {path!r} is not claimed by any "
                f"pattern in tools/loadgen/schema.py — add a gate spec for it"
            )
    for required in schema_mod.REQUIRED_METRICS:
        if required not in flat:
            problems.append(
                f"schema drift: required metric {required!r} is absent "
                f"from the run (a pass that measured nothing is not a pass)"
            )
    return problems


def _band(spec: Dict[str, Any], base: float,
          overrides: Optional[Dict[str, Any]]) -> float:
    rel = float(spec.get("rel_tol", 0.0))
    abs_ = float(spec.get("abs_tol", 0.0))
    if overrides:
        rel = float(overrides.get("rel_tol", rel))
        abs_ = float(overrides.get("abs_tol", abs_))
    return abs(base) * rel + abs_


def _override_for(path: str, overrides: Dict[str, Dict]) -> Optional[Dict]:
    for pattern, spec in overrides.items():
        if schema_mod.path_matches(pattern, path):
            return spec
    return None


def compare_loadgen(
    run: Dict[str, Any],
    base: Dict[str, Any],
    overrides: Dict[str, Dict],
) -> Tuple[List[str], List[str]]:
    """(regressions, notes) for a loadgen-shaped record pair."""
    regressions: List[str] = []
    notes: List[str] = []
    run_flat, base_flat = flatten(run), flatten(base)

    if run.get("spec_hash") != base.get("spec_hash"):
        regressions.append(
            f"workload mismatch: run spec_hash={run.get('spec_hash')!r} vs "
            f"baseline {base.get('spec_hash')!r} — different traffic is not "
            f"a comparison (re-record the baseline)"
        )
        return regressions, notes

    for path, base_val in sorted(base_flat.items()):
        spec = schema_mod.spec_for(path)
        if spec is None or spec["direction"] == "info":
            continue
        if path not in run_flat:
            regressions.append(
                f"{path}: present in baseline, absent from run "
                f"(metric disappeared)"
            )
            continue
        run_val = run_flat[path]
        band = _band(spec, base_val, _override_for(path, overrides))
        direction = spec["direction"]
        if direction == "higher" and run_val < base_val - band:
            regressions.append(
                f"{path}: {run_val:g} < baseline {base_val:g} - band {band:g} "
                f"(higher-is-better)"
            )
        elif direction == "lower" and run_val > base_val + band:
            regressions.append(
                f"{path}: {run_val:g} > baseline {base_val:g} + band {band:g} "
                f"(lower-is-better)"
            )
        elif direction == "equal" and abs(run_val - base_val) > band:
            regressions.append(
                f"{path}: {run_val:g} != baseline {base_val:g} "
                f"(schedule-determined; the workload itself changed?)"
            )
    for path in sorted(set(run_flat) - set(base_flat)):
        spec = schema_mod.spec_for(path)
        if spec is not None and spec["direction"] != "info":
            notes.append(
                f"{path}: new metric (no baseline value yet) — "
                f"re-record to start gating it"
            )

    regressions.extend(_slo_check(run, base))
    return regressions, notes


def _slo_check(run: Dict[str, Any], base: Dict[str, Any]) -> List[str]:
    """Sample-aware SLO verdict: an unmet objective regresses only when
    the baseline met it AND the run's window held enough samples to
    mean anything."""
    out: List[str] = []
    run_obj = ((run.get("slo") or {}).get("objectives")) or {}
    base_obj = ((base.get("slo") or {}).get("objectives")) or {}
    for name, obj in sorted(run_obj.items()):
        samples = int(obj.get("samples") or 0)
        met = obj.get("met")
        if samples < schema_mod.MIN_SLO_SAMPLES:
            continue  # undersampled: no verdict either way
        if met is False and (base_obj.get(name) or {}).get("met") is True:
            base_samples = int((base_obj.get(name) or {}).get("samples") or 0)
            if base_samples < schema_mod.MIN_SLO_SAMPLES:
                continue  # baseline verdict itself was not evidence
            out.append(
                f"slo.{name}: run unmet ({samples} samples) where baseline "
                f"was met ({base_samples} samples)"
            )
    return out


def slo_undersampled(run: Dict[str, Any]) -> List[str]:
    out = []
    for name, obj in sorted(
        (((run.get("slo") or {}).get("objectives")) or {}).items()
    ):
        samples = int(obj.get("samples") or 0)
        if samples < schema_mod.MIN_SLO_SAMPLES:
            out.append(
                f"slo.{name}: only {samples} window samples "
                f"(< {schema_mod.MIN_SLO_SAMPLES}) — verdict not gated"
            )
    return out


# --------------------------------------------------------------------------- #
# Gate entry (importable: tests drive gate() directly)


def gate(
    run: Dict[str, Any],
    baseline: Optional[Dict[str, Any]],
    record: bool = False,
) -> Tuple[int, Dict[str, Any]]:
    """Pure gate evaluation. Returns (exit_code, report). ``baseline``
    is the parsed baseline FILE ({"record": ..., "tolerance_overrides":
    ...}); None with record=False is a usage error handled by main."""
    report: Dict[str, Any] = {
        "drift": [], "regressions": [], "notes": [], "undersampled": [],
    }
    report["drift"] = schema_check(run)
    if report["drift"]:
        return 2, report
    if record:
        return 0, report

    assert baseline is not None
    base_rec = baseline.get("record") or {}
    overrides = baseline.get("tolerance_overrides") or {}

    reasons = provenance_mod.comparable(
        base_rec.get("provenance") or {}, run.get("provenance") or {}
    )
    if reasons:
        report["drift"] = [f"provenance refusal: {r}" for r in reasons]
        return 2, report
    if (run.get("provenance") or {}).get("git_dirty"):
        report["notes"].append(
            "run measured on a DIRTY tree — numbers are not attributable "
            "to a commit"
        )

    if base_rec.get("schema_version") != run.get("schema_version"):
        report["drift"] = [
            f"schema_version mismatch: baseline "
            f"{base_rec.get('schema_version')!r} vs run "
            f"{run.get('schema_version')!r} — re-record the baseline"
        ]
        return 2, report
    regressions, notes = compare_loadgen(run, base_rec, overrides)
    report["undersampled"] = slo_undersampled(run)
    report["regressions"] = regressions
    report["notes"].extend(notes)
    return (1 if regressions else 0), report


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("run", help="run JSON(L) file (last JSON line is the record)")
    parser.add_argument(
        "--baseline", default=DEFAULT_BASELINE,
        help=f"baseline file (default {DEFAULT_BASELINE})",
    )
    parser.add_argument(
        "--record", action="store_true",
        help="validate the run against the schema and write it as the baseline",
    )
    parser.add_argument("--json", action="store_true", help="machine-readable report")
    args = parser.parse_args(argv)

    try:
        run = load_record(args.run)
    except (OSError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2

    baseline: Optional[Dict[str, Any]] = None
    if not args.record:
        try:
            with open(args.baseline, encoding="utf-8") as fh:
                baseline = json.load(fh)
        except OSError as exc:
            print(
                f"error: baseline {args.baseline!r} unreadable ({exc}); "
                f"record one with --record",
                file=sys.stderr,
            )
            return 2
        except ValueError as exc:
            print(f"error: baseline {args.baseline!r} is not JSON: {exc}",
                  file=sys.stderr)
            return 2

    code, report = gate(run, baseline, record=args.record)

    if args.record and code == 0:
        payload = {
            "schema_version": schema_mod.SCHEMA_VERSION,
            "tolerance_overrides": {},
            "record": run,
        }
        with open(args.baseline, "w", encoding="utf-8") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"recorded baseline -> {args.baseline}")

    if args.json:
        print(json.dumps({"exit": code, **report}, indent=1, sort_keys=True))
    else:
        for kind, prefix in (
            ("drift", "DRIFT"), ("regressions", "REGRESSION"),
            ("undersampled", "undersampled"), ("notes", "note"),
        ):
            for line in report[kind]:
                print(f"{prefix}: {line}")
        if code == 0 and not args.record:
            print("perf gate: PASS")
        elif code == 1:
            print("perf gate: FAIL (regression)")
        elif code == 2:
            print("perf gate: FAIL (schema drift / provenance refusal)")
    return code


if __name__ == "__main__":
    sys.exit(main())
