"""Compile-path observability: jit says what jit did, and a post-warmup
trace, compile or executable load inside an engine program is LOUD.

XLA compiles are the single biggest latency cliff on the serving path —
a cold executable stalls the dispatch loop for seconds to minutes while
every in-flight request waits. The whole scheduler is architected so
the compiled-program set is *bounded and warmable* (chunk ladders, wave
rungs, window buckets), and this module measures whether that holds.

It does not guess at jit's cache key (shapes and dtypes are only part of
it: an operand's sharding, whether it is committed, the mesh in its type
select executables too). It listens to JAX's own monitoring events:
``/jax/core/compile/jaxpr_trace_duration``,
``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``
(time spans) and ``/jax/compilation_cache/cache_hits`` (the backend
compile that follows was a load from the persistent cache). The engine
wraps every compiled callable at build time (``wrap(program, fn)``);
the wrapper is a thread-local "this thread is inside program P". An
event raised inside a wrapped call belongs to P, whatever keyed it; an
event outside any wrapped call (another thread compiling beside the
engine, eager operations) belongs to no engine program.

Phases: jit work before :meth:`finish_warmup` (or inside a
:meth:`warmup_scope`, which the engine's warmup entry points hold) is
expected warmup work. Any AFTER warmup completion is a
**compile-on-hot-path**: it increments
``genai_engine_hot_path_compiles_total{program,what}`` (``what`` =
``compile`` | ``cache_load`` | ``trace``), logs an error, and stamps a
``hot_path_compile`` flight event on every in-flight timeline — the
requests it actually stalled. Every wrapped call with jit work is
stamped on the dispatch span it happened in (``jit_s``, ``jit_what``)
and as a ``compile:`` overlay span. :meth:`snapshot` rides the engine's
utilization snapshot, so ``GET /internal/slo``, bench lines and the
loadgen ``compiles`` gate block all read one source of truth.

Per-dispatch cost: two thread-local writes.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from generativeaiexamples_tpu.engine import dispatch_timeline
from generativeaiexamples_tpu.utils import flight_recorder
from generativeaiexamples_tpu.utils import metrics as metrics_mod
from generativeaiexamples_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_REG = metrics_mod.get_registry()
_M_COMPILE_SECONDS = _REG.histogram(
    "genai_engine_compile_seconds",
    "Wall time jit spent tracing, lowering, compiling or loading inside "
    "one call of an engine program (from jit's own events; a call that "
    "found its executable records nothing), by program family (prefill, "
    "decode, extend, finish, spec_verify, update_slots, page_tables).",
    ("program",),
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
             60.0, 120.0, 300.0, float("inf")),
)
_M_EXECUTABLES = _REG.gauge(
    "genai_engine_compiled_executables",
    "Executables jit compiled or loaded for engine programs this "
    "process (the live executable-ladder size; cumulative across engine "
    "rebuilds).",
)
_M_HOT = _REG.counter(
    "genai_engine_hot_path_compiles_total",
    "Calls of an engine program in which jit traced, compiled or loaded "
    "an executable AFTER warmup completion — every one stalled the "
    "dispatch loop mid-serving and violates the bounded-executable-set "
    "discipline, by program family and by what jit did (compile, "
    "cache_load, trace).",
    ("program", "what"),
)
_M_COVERAGE = _REG.gauge(
    "genai_engine_warmup_coverage_ratio",
    "Of the calls of engine programs since warmup completed, the "
    "fraction that found everything warm (1.0 = steady state never "
    "compiles).",
)

_SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# .frame: the innermost wrapped call this thread is inside, as
# [events, cache_hit]; events are (what, start, end)
_TLS = threading.local()
_LISTENING = False
_LISTEN_LOCK = threading.Lock()


def _on_time_span(event: str, start: float, end: float, **_: Any) -> None:
    frame = getattr(_TLS, "frame", None)
    what = _SPAN_EVENTS.get(event)
    if frame is None or what is None:
        return
    if what == "compile" and frame[1]:
        what, frame[1] = "cache_load", False
    frame[0].append((what, start, end))


def _on_event(event: str, **_: Any) -> None:
    frame = getattr(_TLS, "frame", None)
    if frame is not None and event == _CACHE_HIT_EVENT:
        frame[1] = True


def _listen() -> None:
    """Register the listeners once a process (jax keeps them for good)."""
    global _LISTENING
    with _LISTEN_LOCK:
        if _LISTENING:
            return
        from jax import monitoring

        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_event_listener(_on_event)
        _LISTENING = True


class CompileWatch:
    """Per-engine compile tracker; one instance per LLMEngine, created
    before the compiled steps are built."""

    def __init__(self) -> None:
        _listen()
        self._lock = threading.Lock()
        self._executables: Dict[str, int] = {}  # guarded by self._lock
        self._warmup_done = False
        self._warmup_depth = 0  # guarded by self._lock
        self._hot_total = 0  # guarded by self._lock
        self._served = 0  # guarded by self._lock: calls since warmup
        self._compile_s_total = 0.0  # guarded by self._lock

    # ------------------------------------------------------------------ #
    def wrap(self, program: str, fn: Callable) -> Callable:
        """Mark one compiled callable as the engine program ``program``.
        Call sites are unchanged — the wrapper is transparent for
        positional/keyword dispatch."""

        def dispatched(*args: Any, **kwargs: Any) -> Any:
            outer = getattr(_TLS, "frame", None)
            frame = _TLS.frame = [[], False]
            try:
                return fn(*args, **kwargs)
            finally:
                _TLS.frame = outer
                self._after_call(program, frame[0])

        # the jitted callable itself (``_cache_size()`` for the tests)
        dispatched.__wrapped__ = fn
        return dispatched

    def _after_call(
        self, program: str, events: List[Tuple[str, float, float]]
    ) -> None:
        with self._lock:
            post_warmup = self._warmup_done and self._warmup_depth == 0
            self._served += post_warmup
            if not events:
                return
            kinds = {e[0] for e in events}
            what = next(w for w in ("compile", "cache_load", "trace") if w in kinds)
            # nested traces lie inside the outer one: the call's jit time
            # is the stretch its events cover
            seconds = max(e[2] for e in events) - min(e[1] for e in events)
            built = sum(e[0] != "trace" for e in events)
            self._executables[program] = self._executables.get(program, 0) + built
            self._compile_s_total += seconds
            self._hot_total += post_warmup
            coverage = self._coverage_locked()
        _M_COMPILE_SECONDS.labels(program=program).observe(seconds, trace_id=None)
        _M_EXECUTABLES.inc(built)
        _M_COVERAGE.set(coverage)
        # On the span it happened in, and as an overlay span: compile
        # walls explain the giant first-dispatch spans in a Perfetto
        # dump (the time is already inside the dispatch's enqueue_s).
        dispatch_timeline.note_jit(program, what, seconds)
        dispatch_timeline.record_compile(program, seconds, hot=post_warmup)
        if post_warmup:
            _M_HOT.labels(program=program, what=what).inc()
            stamped = flight_recorder.annotate_inflight(
                "hot_path_compile", program=program, what=what,
                seconds=round(seconds, 3),
            )
            logger.error(
                "COMPILE ON HOT PATH: program %r: jit %s took %.3fs AFTER "
                "warmup completion (%d in-flight requests stalled) — a "
                "serving operand escaped the warmup ladder",
                program, what, seconds, stamped,
            )

    # ------------------------------------------------------------------ #
    # warmup phase accounting

    @contextlib.contextmanager
    def warmup_scope(self):
        """Context manager: jit work inside it counts as warmup work even
        after finish_warmup (bench A/B re-warms, runtime spec toggles)."""
        with self._lock:
            self._warmup_depth += 1
        try:
            yield self
        finally:
            with self._lock:
                self._warmup_depth -= 1

    def finish_warmup(self) -> None:
        """Warmup is complete: from now on any jit work inside an engine
        program is a hot-path compile. Idempotent."""
        with self._lock:
            self._warmup_done = True
            warmed = sum(self._executables.values())
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
        return 1.0 - self._hot_total / self._served

    def snapshot(self) -> Dict[str, float]:
        """Flat compile stats, merged into the engine's utilization
        snapshot (prefixed keys so the loadgen schema's utilization.*
        claim covers them)."""
        with self._lock:
            out: Dict[str, float] = {
                "compile_executables": float(sum(self._executables.values())),
                "compile_seconds_total": round(self._compile_s_total, 4),
                "compile_hot_path_total": float(self._hot_total),
                "compile_warmup_done": float(self._warmup_done),
                "compile_warmup_coverage": round(self._coverage_locked(), 4),
                "compile_rungs_hit": float(self._served),
            }
            for prog, n in sorted(self._executables.items()):
                out[f"compile_executables_{prog}"] = float(n)
        return out
