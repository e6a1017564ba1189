"""Compile-path observability: make every XLA compile visible, and make
a post-warmup compile LOUD.

XLA compiles are the single biggest latency cliff on the serving path —
a cold executable stalls the dispatch loop for seconds to minutes while
every in-flight request waits. The whole scheduler is architected so
the compiled-program set is *bounded and warmable* (chunk ladders, wave
rungs, window buckets — PRs 2/5/7/11), yet nothing measured whether
that discipline actually holds: warmup coverage was asserted in
comments, and a reintroduced steady-state recompile would surface only
as mysterious p99 spikes.

:class:`CompileWatch` closes that gap. The engine wraps every compiled
callable at build time (``wrap(program, fn)``); the wrapper derives the
jit cache key's observable half — traced leaves by ``(shape, dtype)``,
static/python leaves by value, exactly the distinctions that decide
whether XLA compiles — and times the FIRST dispatch of each distinct
signature. A jitted call's synchronous cost is trace + compile
(execution is dispatched async), so the first-dispatch wall time is the
compile-path cost, charged to ``genai_engine_compile_seconds{program}``
and counted in the ``genai_engine_compiled_executables`` gauge.

Phases: compiles before :meth:`finish_warmup` (or inside a
:meth:`warmup_scope`, which the engine's warmup entry points hold) are
expected warmup work. Any first-seen signature AFTER warmup completion
is a **compile-on-hot-path**: it increments
``genai_engine_hot_path_compiles_total{program}``, logs an error, and
stamps a ``hot_path_compile`` flight event on every in-flight timeline
— the requests it actually stalled. :meth:`snapshot` reports warmup
coverage (rungs compiled during warmup vs rungs actually hit by
serving traffic) and rides the engine's utilization snapshot, so
``GET /internal/slo``, bench lines, and the loadgen ``compiles`` gate
block all read one source of truth.

Per-dispatch cost: one signature derivation (a tuple build over the
call's arg tree) plus a set lookup — host-side, dispatch-rate (not
token-rate), on par with the UtilizationEstimator record the same
thread already pays.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, Optional, Set, Tuple

from generativeaiexamples_tpu.engine import dispatch_timeline
from generativeaiexamples_tpu.utils import flight_recorder
from generativeaiexamples_tpu.utils import metrics as metrics_mod
from generativeaiexamples_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_REG = metrics_mod.get_registry()
_M_COMPILE_SECONDS = _REG.histogram(
    "genai_engine_compile_seconds",
    "Wall time of the first dispatch of each distinct compiled-program "
    "signature (trace + XLA compile; execution is async), by program "
    "family (prefill, decode, extend, finish, spec_verify, "
    "update_slots, page_tables).",
    ("program",),
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
             60.0, 120.0, 300.0, float("inf")),
)
_M_EXECUTABLES = _REG.gauge(
    "genai_engine_compiled_executables",
    "Distinct compiled-program signatures built this process (the live "
    "executable-ladder size; cumulative across engine rebuilds).",
)
_M_HOT = _REG.counter(
    "genai_engine_hot_path_compiles_total",
    "Compiled-program builds that landed AFTER warmup completion — "
    "every one stalled the dispatch loop mid-serving and violates the "
    "bounded-executable-set discipline, by program family.",
    ("program",),
)
_M_COVERAGE = _REG.gauge(
    "genai_engine_warmup_coverage_ratio",
    "Of the program signatures serving traffic has dispatched since "
    "warmup completed, the fraction warmup had already compiled "
    "(1.0 = steady state never compiles).",
)


def _signature(value: Any) -> Any:
    """The observable half of jit's cache key for one argument tree:
    array-likes by (shape, dtype) — value changes never recompile —
    and python scalars/strings by value (static args select
    executables by value). Containers recurse."""
    shape = getattr(value, "shape", None)
    if shape is not None:
        return ("a", tuple(shape), str(getattr(value, "dtype", "")))
    if isinstance(value, (list, tuple)):
        return tuple(_signature(v) for v in value)
    if isinstance(value, dict):
        return tuple(
            (k, _signature(v)) for k, v in sorted(value.items())
        )
    if isinstance(value, (bool, int, float, str, bytes, type(None))):
        # type name included: True == 1 == 1.0 under python equality,
        # but they are distinct static-arg values to jit
        return ("v", type(value).__name__, value)
    return ("t", type(value).__name__)


class CompileWatch:
    """Per-engine compile tracker; one instance per LLMEngine, created
    before the compiled steps are built."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # (program, signature) ever dispatched -> compile seconds
        self._seen: Dict[Tuple[str, Any], float] = {}  # guarded by self._lock
        # signatures known at warmup completion (pre-warmed set)
        self._warm: Set[Tuple[str, Any]] = set()  # guarded by self._lock
        # distinct signatures dispatched after warmup completion
        self._served: Set[Tuple[str, Any]] = set()  # guarded by self._lock
        self._warmup_done = False
        self._warmup_depth = 0  # guarded by self._lock
        self._hot_total = 0  # guarded by self._lock
        self._compile_s_total = 0.0  # guarded by self._lock

    # ------------------------------------------------------------------ #
    def wrap(self, program: str, fn: Callable) -> Callable:
        """Instrument one compiled callable. Call sites are unchanged —
        the wrapper is transparent for positional/keyword dispatch."""

        def dispatched(*args: Any, **kwargs: Any) -> Any:
            key = (
                program,
                (_signature(args), _signature(kwargs) if kwargs else None),
            )
            with self._lock:
                known = key in self._seen
                post_warmup = self._warmup_done and self._warmup_depth == 0
                if post_warmup:
                    self._served.add(key)
            if known:
                return fn(*args, **kwargs)
            t0 = time.monotonic()
            out = fn(*args, **kwargs)
            dt = time.monotonic() - t0
            self._record_compile(key, program, dt, post_warmup)
            return out

        # the jitted callable itself: its ``_cache_size()`` counts the
        # executables jit really holds, which key on more than shapes
        # (an operand's sharding, whether it is committed)
        dispatched.__wrapped__ = fn
        return dispatched

    def _record_compile(
        self, key: Tuple[str, Any], program: str, seconds: float,
        post_warmup: bool,
    ) -> None:
        with self._lock:
            if key in self._seen:  # racing first dispatches: charge once
                return
            self._seen[key] = seconds
            self._compile_s_total += seconds
            if post_warmup:
                self._hot_total += 1
            coverage = self._coverage_locked()
        _M_COMPILE_SECONDS.labels(program=program).observe(
            seconds, trace_id=None
        )
        _M_EXECUTABLES.inc()
        _M_COVERAGE.set(coverage)
        # Overlay span for the dispatch timeline: compile walls explain
        # the giant first-dispatch spans in a Perfetto dump (the time is
        # already inside the dispatch's run_s, so bubble accounting
        # excludes the "compile" category — this is annotation, not
        # double-charged wall).
        dispatch_timeline.record_compile(program, seconds, hot=post_warmup)
        if post_warmup:
            _M_HOT.labels(program=program).inc()
            stamped = flight_recorder.annotate_inflight(
                "hot_path_compile", program=program,
                seconds=round(seconds, 3),
            )
            logger.error(
                "COMPILE ON HOT PATH: program %r compiled %.3fs AFTER "
                "warmup completion (%d in-flight requests stalled) — a "
                "serving shape escaped the warmup ladder",
                program, seconds, stamped,
            )

    # ------------------------------------------------------------------ #
    # warmup phase accounting

    @contextlib.contextmanager
    def warmup_scope(self):
        """Context manager: compiles inside it count as warmup work even
        after finish_warmup (bench A/B re-warms, runtime spec toggles)."""
        with self._lock:
            self._warmup_depth += 1
        try:
            yield self
        finally:
            with self._lock:
                self._warmup_depth -= 1
                if self._warmup_done:
                    # late warm rungs join the pre-warmed set
                    self._warm.update(self._seen)

    def finish_warmup(self) -> None:
        """Warmup is complete: everything compiled so far is the
        pre-warmed rung set; from now on a first-seen signature is a
        hot-path compile. Idempotent."""
        with self._lock:
            self._warm.update(self._seen)
            self._warmup_done = True
            warmed = len(self._warm)
        _M_COVERAGE.set(1.0)
        logger.info(
            "compile watch: warmup complete with %d executables "
            "(hot-path compile detection armed)", warmed,
        )

    # ------------------------------------------------------------------ #
    def _coverage_locked(self) -> float:
        """Caller holds self._lock."""
        if not self._served:
            return 1.0
        return len(self._served & self._warm) / len(self._served)

    def snapshot(self) -> Dict[str, float]:
        """Flat compile stats, merged into the engine's utilization
        snapshot (prefixed keys so the loadgen schema's utilization.*
        claim covers them)."""
        with self._lock:
            per_program: Dict[str, int] = {}
            for prog, _ in self._seen:
                per_program[prog] = per_program.get(prog, 0) + 1
            out: Dict[str, float] = {
                "compile_executables": float(len(self._seen)),
                "compile_seconds_total": round(self._compile_s_total, 4),
                "compile_hot_path_total": float(self._hot_total),
                "compile_warmup_done": float(self._warmup_done),
                "compile_warmup_coverage": round(self._coverage_locked(), 4),
                "compile_rungs_hit": float(len(self._served)),
            }
            for prog, n in sorted(per_program.items()):
                out[f"compile_executables_{prog}"] = float(n)
        return out
