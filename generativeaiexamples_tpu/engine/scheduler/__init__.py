"""Pluggable engine scheduler subsystem (docs/scheduler.md).

``base`` defines the :class:`SchedulerPolicy` seam (admission, wave
formation, slot placement, ingest windows, draft-aware gating);
``unified`` is the default single-tier policy reproducing the
pre-scheduler dispatch order exactly; ``disagg`` runs prefill and
decode as separate tiers with the paged-KV handoff protocol in
``handoff``.
"""
from generativeaiexamples_tpu.engine.scheduler.base import (  # noqa: F401
    POLICY_KINDS,
    AcceptanceTracker,
    SchedulerPolicy,
    build_policy,
    metrics_snapshot,
    validate_config,
)
from generativeaiexamples_tpu.engine.scheduler import handoff  # noqa: F401
