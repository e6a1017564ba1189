"""metric-names: registered metric families follow Prometheus naming.

Migrated from the standalone ``tools/check_metric_names.py`` (which
remains as a thin CLI shim re-exporting this module). Imports every
module that registers metric families onto the process registry
(utils/metrics.py) and checks each family:

- names and label names are ``snake_case`` (``[a-z][a-z0-9_]*``);
- counters end in ``_total``;
- histograms end in a unit suffix (``_seconds``, ``_bytes``,
  ``_tokens``...) — distributions without a unit are unreadable in
  PromQL;
- no name ends in a reserved exposition suffix (``_sum``/``_count``/
  ``_bucket``) or, for gauges, in ``_total`` (which would make them
  read as counters);
- everything carries the ``genai_`` namespace prefix so dashboards can
  select this stack's metrics with one matcher;
- the RENDERED OpenMetrics exposition declares counter families without
  the ``_total`` sample suffix (strict parsers reject
  ``# TYPE foo_total counter``).
"""
from __future__ import annotations

import re
from typing import List

from tools.genai_lint.core import Finding, RepoRule

SNAKE_RE = re.compile(r"[a-z][a-z0-9_]*\Z")
# _rows and _ms cover the micro-batcher distributions
# (genai_batcher_batch_rows / genai_batcher_queue_wait_ms): batch
# geometry is a row count, and sub-millisecond queue waits are
# unreadable in a _seconds histogram's bucket labels. _pages covers the
# paged-KV allocator's per-request page-count distribution
# (genai_engine_kv_request_pages) — page counts, like rows, are a unit
# of their own.
HISTOGRAM_UNITS = (
    "_seconds", "_bytes", "_tokens", "_ratio", "_rows", "_ms", "_pages"
)
RESERVED_SUFFIXES = ("_sum", "_count", "_bucket")
NAMESPACE = "genai_"

# Modules that register families at import. Engine/server modules are
# import-light (jax is deferred), so linting never builds an engine.
REGISTRY_MODULES = (
    "generativeaiexamples_tpu.utils.metrics",
    "generativeaiexamples_tpu.utils.resilience",
    "generativeaiexamples_tpu.utils.faults",
    "generativeaiexamples_tpu.utils.flight_recorder",
    "generativeaiexamples_tpu.utils.slo",
    "generativeaiexamples_tpu.utils.blackbox",
    "generativeaiexamples_tpu.engine.llm_engine",
    "generativeaiexamples_tpu.engine.compile_watch",
    "generativeaiexamples_tpu.engine.dispatch_timeline",
    "generativeaiexamples_tpu.engine.kv_pages",
    "generativeaiexamples_tpu.engine.scheduler.base",
    "generativeaiexamples_tpu.engine.scheduler.handoff",
    "generativeaiexamples_tpu.engine.prefix_cache",
    "generativeaiexamples_tpu.engine.spec_decode",
    "generativeaiexamples_tpu.engine.batcher",
    "generativeaiexamples_tpu.engine.embedder",
    "generativeaiexamples_tpu.engine.reranker",
    "generativeaiexamples_tpu.retrieval.store",
    "generativeaiexamples_tpu.retrieval.bm25",
    "generativeaiexamples_tpu.chains.runtime",
    "generativeaiexamples_tpu.server.observability",
    "generativeaiexamples_tpu.router.metrics",
    "generativeaiexamples_tpu.engine.retrieval_tier",
)


def check_families() -> List[str]:
    """Import the registry modules and return a list of violations."""
    import importlib

    for module in REGISTRY_MODULES:
        importlib.import_module(module)

    from generativeaiexamples_tpu.utils.metrics import (
        Counter,
        Gauge,
        Histogram,
        get_registry,
    )

    problems: List[str] = []
    families = get_registry().families()
    if not families:
        problems.append("registry is empty — did the instrumented modules import?")
    for family in families:
        name = family.name
        if not SNAKE_RE.fullmatch(name):
            problems.append(f"{name}: not snake_case")
        if not name.startswith(NAMESPACE):
            problems.append(f"{name}: missing the {NAMESPACE!r} namespace prefix")
        if name.endswith(RESERVED_SUFFIXES):
            problems.append(f"{name}: ends in a reserved exposition suffix")
        if isinstance(family, Counter) and not name.endswith("_total"):
            problems.append(f"{name}: counter must end in _total")
        if isinstance(family, Histogram) and not name.endswith(HISTOGRAM_UNITS):
            problems.append(
                f"{name}: histogram must end in a unit suffix "
                f"{'/'.join(HISTOGRAM_UNITS)}"
            )
        if isinstance(family, Gauge) and name.endswith("_total"):
            problems.append(f"{name}: gauge must not end in _total")
        if not family.documentation.strip():
            problems.append(f"{name}: missing HELP text")
        for label in family.labelnames:
            if not SNAKE_RE.fullmatch(label):
                problems.append(f"{name}: label {label!r} not snake_case")
    problems.extend(check_openmetrics_families())
    return problems


def check_openmetrics_families() -> List[str]:
    """Lint the RENDERED OpenMetrics exposition: family declarations
    (HELP/TYPE lines) must not carry a reserved sample suffix —
    OpenMetrics counters declare the bare family name and only samples
    append ``_total`` (strict parsers like promtool reject
    ``# TYPE foo_total counter``). Guards render(), not just the
    registered names, so a rendering regression fails the linter."""
    from generativeaiexamples_tpu.utils.metrics import get_registry

    problems: List[str] = []
    for line in get_registry().render(openmetrics=True).splitlines():
        if not line.startswith(("# HELP ", "# TYPE ")):
            continue
        name = line.split(" ", 3)[2]
        if name.endswith("_total"):
            problems.append(
                f"OpenMetrics family declaration {name!r} keeps the "
                f"_total sample suffix: {line!r}"
            )
        if name.endswith(RESERVED_SUFFIXES):
            problems.append(
                f"OpenMetrics family declaration {name!r} ends in a "
                f"reserved exposition suffix"
            )
    return problems


class MetricNamesRule(RepoRule):
    name = "metric-names"
    description = (
        "registered genai_ metric families follow Prometheus naming "
        "(snake_case, _total counters, unit-suffixed histograms)"
    )

    def check_repo(self, root) -> List[Finding]:
        return [
            Finding(self.name, "<metrics registry>", 0, problem)
            for problem in check_families()
        ]
