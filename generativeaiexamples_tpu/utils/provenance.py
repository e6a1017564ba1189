"""Run provenance for measurement artifacts (bench + loadgen JSON lines).

Every performance record carries WHERE it came from: the git SHA (and
whether the tree was dirty), a fingerprint of the configuration that
produced it, and whether the model served random-init weights — so the
trajectory tooling (tools/check_perf_regression.py, bench-record
comparisons) can refuse to compare numbers measured under different
conditions instead of silently charting noise. bench has always run
random-init weights silently (ROADMAP item 5); the flag makes that
explicit in every line.

Pure host, no jax. Git queries shell out once and degrade to None on
non-git checkouts (exported tarballs); GENAI_GIT_SHA / GENAI_GIT_DIRTY
override both for environments where .git is absent but the build
system knows the answer.
"""
from __future__ import annotations

import hashlib
import json
import os
import pathlib
import subprocess
from typing import Any, Dict, Optional

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent.parent


def _git(*args: str) -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", *args],
            cwd=str(_REPO_ROOT),
            capture_output=True,
            text=True,
            timeout=10,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    if out.returncode != 0:
        return None
    return out.stdout.strip()


def git_sha() -> Optional[str]:
    """HEAD commit SHA, or None outside a git checkout."""
    env = os.environ.get("GENAI_GIT_SHA")
    if env:
        return env
    return _git("rev-parse", "HEAD") or None


def git_dirty() -> Optional[bool]:
    """True when the working tree differs from HEAD (uncommitted edits
    poison cross-run comparisons), None when git is unavailable."""
    env = os.environ.get("GENAI_GIT_DIRTY")
    if env is not None:
        return env.lower() not in ("0", "false", "no", "")
    status = _git("status", "--porcelain")
    if status is None:
        return None
    return bool(status)


def config_fingerprint(config: Any) -> Optional[str]:
    """Stable 12-hex digest of a configuration object: dataclasses,
    dicts, and anything JSON-serializable hash canonically (sorted
    keys); unknown leaves hash by repr. None stays None."""
    if config is None:
        return None

    def norm(obj: Any) -> Any:
        if hasattr(obj, "__dataclass_fields__"):
            return {
                name: norm(getattr(obj, name))
                for name in sorted(obj.__dataclass_fields__)
            }
        if isinstance(obj, dict):
            return {str(k): norm(v) for k, v in sorted(obj.items(), key=lambda kv: str(kv[0]))}
        if isinstance(obj, (list, tuple)):
            return [norm(v) for v in obj]
        if isinstance(obj, (str, int, float, bool)) or obj is None:
            return obj
        return repr(obj)

    blob = json.dumps(norm(config), sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:12]


def provenance(
    config: Any = None,
    weights_random_init: Optional[bool] = None,
    **extra: Any,
) -> Dict[str, Any]:
    """The provenance block measurement JSON lines embed. ``extra``
    keys (e.g. ``kv_cache_dtype``, ``paged_kernel_path``) are stamped
    verbatim — named serving-regime facts the fingerprint already
    covers opaquely, surfaced so a comparability refusal can SAY which
    regime knob differed."""
    out = {
        "git_sha": git_sha(),
        "git_dirty": git_dirty(),
        "config_fingerprint": config_fingerprint(config),
        "weights_random_init": weights_random_init,
    }
    out.update(extra)
    return out


def comparable(a: Dict[str, Any], b: Dict[str, Any]) -> list:
    """Reasons two provenance blocks must NOT be compared (empty list
    = comparable). Git SHAs are allowed to differ — tracking change
    across commits is the point — but the configuration and the
    weights regime must match. ``kv_cache_dtype`` is checked by name
    on top of the fingerprint: a bf16-vs-int8-vs-int4 compare is the
    classic cross-regime mistake (half the KV bytes, different
    numerics), and the refusal should name it rather than point at an
    opaque hash. Absent on one side (older baselines) skips the check
    — the fingerprint still guards those."""
    reasons = []
    for key in ("config_fingerprint", "weights_random_init",
                "kv_cache_dtype"):
        va, vb = a.get(key), b.get(key)
        if va is not None and vb is not None and va != vb:
            reasons.append(f"{key} differs: {va!r} vs {vb!r}")
    return reasons
