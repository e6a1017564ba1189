"""On-demand JAX profiler capture + dispatch trace annotations.

The reference tunes its GPU inference plane with Nsight attached to the
Triton containers; the TPU analog is ``jax.profiler`` writing a
TensorBoard/XProf trace. This module makes capture an *operational*
action instead of a code change: the servers expose
``POST /internal/profile/start`` / ``/stop`` (handlers in
``server/observability.py``) which call :func:`start_profile` /
:func:`stop_profile` here, so an operator can bracket a live traffic
window and pull the trace from ``PROFILE_LOG_DIR`` — no restart, no
benchmark harness. The capture is of the benchmark's kind
(``perfbench/launcher.py``): the Python tracer is OFF and the handlers
run the start and the stop off the server's event loop. With the tracer
on and the call inside the loop, a capture on the chip stalled every
stream for its whole length.

Everything is gated on ``ENABLE_PROFILING`` (same pattern as
``ENABLE_TRACING``) and degrades gracefully: when the profiler is
unavailable (no jax, or a backend without profiling support) the
endpoints answer with a JSON error instead of crashing serving.

:func:`annotation_scope` wraps ``jax.profiler.TraceAnnotation`` so the
engine can label its prefill-chunk and decode-block dispatches in the
captured trace; when profiling is disabled the factory returns a no-op
context manager resolved once at engine init (zero per-dispatch cost).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Callable, ContextManager, Dict, Optional, Tuple

from generativeaiexamples_tpu.utils.logging import get_logger

logger = get_logger(__name__)

DEFAULT_PROFILE_DIR = "/tmp/genai_tpu_profiles"


def profiling_enabled() -> bool:
    return os.environ.get("ENABLE_PROFILING", "").lower() in ("true", "1", "yes")


def default_log_dir() -> str:
    return os.environ.get("PROFILE_LOG_DIR", DEFAULT_PROFILE_DIR)


def _profiler():
    """The jax.profiler module, or None when unavailable."""
    try:
        import jax

        profiler = jax.profiler
        # both entry points must exist for capture to work
        profiler.start_trace, profiler.stop_trace  # noqa: B018
        return profiler
    except Exception:  # noqa: BLE001 - any import/attr failure means no profiler
        return None


def _capture_options(profiler) -> Dict[str, Any]:
    """``start_trace`` options of an operational capture: device and
    host tracks, no Python tracer (a jax without ``ProfileOptions``
    takes its defaults)."""
    make = getattr(profiler, "ProfileOptions", None)
    if make is None:
        return {}
    opts = make()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return {"profiler_options": opts}


# --------------------------------------------------------------------------- #
# Capture session (process-wide: jax.profiler allows one active trace)

_LOCK = threading.Lock()
_ACTIVE_DIR: Optional[str] = None
_STARTED_AT: Optional[float] = None


def start_profile(log_dir: Optional[str] = None) -> Tuple[int, Dict[str, Any]]:
    """Begin a profiler capture. Returns (http_status, json_body)."""
    global _ACTIVE_DIR, _STARTED_AT
    if not profiling_enabled():
        return 403, {
            "error": "profiling disabled; set ENABLE_PROFILING=true to enable"
        }
    profiler = _profiler()
    if profiler is None:
        return 501, {"error": "jax profiler unavailable in this environment"}
    log_dir = log_dir or default_log_dir()
    with _LOCK:
        if _ACTIVE_DIR is not None:
            return 409, {
                "error": "profile capture already running",
                "log_dir": _ACTIVE_DIR,
            }
        try:
            os.makedirs(log_dir, exist_ok=True)
            profiler.start_trace(log_dir, **_capture_options(profiler))
        except Exception as exc:  # noqa: BLE001 - capture must not kill serving
            logger.warning("profiler start failed: %s", exc)
            return 500, {"error": f"profiler start failed: {exc}"}
        _ACTIVE_DIR = log_dir
        _STARTED_AT = time.time()
    logger.info("JAX profiler capture started → %s", log_dir)
    return 200, {"ok": True, "log_dir": log_dir}


def stop_profile() -> Tuple[int, Dict[str, Any]]:
    """End the active profiler capture. Returns (http_status, json_body)."""
    global _ACTIVE_DIR, _STARTED_AT
    if not profiling_enabled():
        return 403, {
            "error": "profiling disabled; set ENABLE_PROFILING=true to enable"
        }
    profiler = _profiler()
    if profiler is None:
        return 501, {"error": "jax profiler unavailable in this environment"}
    with _LOCK:
        if _ACTIVE_DIR is None:
            return 409, {"error": "no profile capture running"}
        log_dir, started = _ACTIVE_DIR, _STARTED_AT
        try:
            profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001
            # Keep the session marked active: jax's profiler may still be
            # running (e.g. the trace write failed), and clearing here
            # would wedge it — start would 500 ("already started") while
            # stop 409s without ever calling stop_trace. Leaving the
            # state lets the operator retry stop.
            logger.warning("profiler stop failed: %s", exc)
            return 500, {"error": f"profiler stop failed: {exc}", "log_dir": log_dir}
        _ACTIVE_DIR = _STARTED_AT = None
    duration = round(time.time() - started, 3) if started else None
    logger.info("JAX profiler capture stopped (%.3fs) → %s", duration or 0, log_dir)
    return 200, {"ok": True, "log_dir": log_dir, "duration_s": duration}


def capture_active() -> bool:
    with _LOCK:
        return _ACTIVE_DIR is not None


# --------------------------------------------------------------------------- #
# Dispatch annotations


def annotation_scope() -> Callable[[str], ContextManager]:
    """Factory for dispatch-labelling scopes, resolved ONCE (engine init).

    Returns ``jax.profiler.TraceAnnotation`` when ENABLE_PROFILING is set
    and the profiler exists, else a nullcontext factory — the hot decode
    loop pays nothing when profiling is off.
    """
    if profiling_enabled():
        profiler = _profiler()
        if profiler is not None and hasattr(profiler, "TraceAnnotation"):
            return profiler.TraceAnnotation
        logger.warning(
            "ENABLE_PROFILING set but jax.profiler.TraceAnnotation is "
            "unavailable; dispatch annotations disabled"
        )
    return lambda name: contextlib.nullcontext()
