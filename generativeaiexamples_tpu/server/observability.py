"""HTTP observability shared by the chain-server and the engine server.

- ``metrics_middleware`` — per-route request count / in-flight gauge /
  latency histogram (labels ``route``+``method``+``status``), the server
  layer of the registry in ``utils/metrics.py``;
- ``metrics_handler`` — ``GET /metrics`` in Prometheus text exposition
  format 0.0.4, upgrading to OpenMetrics (with trace exemplars) when the
  scraper's Accept header asks for ``application/openmetrics-text``;
- ``internal_metrics_handler`` — the backward-compatible
  ``/internal/metrics`` JSON view over the same registry;
- profiler capture endpoints wrapping ``utils/profiling.py``.

The scrape path NEVER builds an engine: it reads the process registry
and peeks at ``llm_engine._ENGINE`` only through the module attribute
(`None` stays `None`), preserving the guarantee the old
``/internal/metrics`` handler documented — a metrics scrape must not
trigger a multi-minute engine boot.
"""
from __future__ import annotations

import asyncio
import functools
import json
import time
from typing import Callable

from aiohttp import web

from generativeaiexamples_tpu.engine import dispatch_timeline
from generativeaiexamples_tpu.utils import blackbox
from generativeaiexamples_tpu.utils import flight_recorder
from generativeaiexamples_tpu.utils import metrics as metrics_mod
from generativeaiexamples_tpu.utils import profiling
from generativeaiexamples_tpu.utils import slo as slo_mod
from generativeaiexamples_tpu.utils import trace_stitch

_REG = metrics_mod.get_registry()

HTTP_REQUESTS = _REG.counter(
    "genai_http_requests_total",
    "HTTP requests served, by route pattern, method and status code.",
    ("route", "method", "status"),
)
HTTP_IN_FLIGHT = _REG.gauge(
    "genai_http_requests_in_flight",
    "HTTP requests currently being handled.",
)
HTTP_LATENCY = _REG.histogram(
    "genai_http_request_duration_seconds",
    "Wall time per HTTP request, by route pattern.",
    ("route",),
)
REQUESTS_SHED = _REG.counter(
    "genai_server_requests_shed_total",
    "/generate requests shed with 429 + Retry-After by admission "
    "control, by reason (active_streams, engine_queue, "
    "engine_overloaded, fault_injected).",
    ("reason",),
)
ACTIVE_STREAMS = _REG.gauge(
    "genai_server_active_streams",
    "SSE generation streams currently in flight on the chain-server.",
)
DEADLINE_EXCEEDED = _REG.counter(
    "genai_server_deadline_exceeded_total",
    "Requests whose deadline budget ran out, by stage (admission, "
    "stream).",
    ("stage",),
)


def _route_label(request: web.Request) -> str:
    """The matched route PATTERN (bounded label cardinality), falling
    back to a catch-all for unmatched paths."""
    try:
        resource = request.match_info.route.resource
        if resource is not None:
            return resource.canonical
    except Exception:  # noqa: BLE001 - label derivation must never fail a request
        pass
    return "unmatched"


@web.middleware
async def metrics_middleware(request: web.Request, handler: Callable) -> web.StreamResponse:
    route = _route_label(request)
    HTTP_IN_FLIGHT.inc()
    start = time.time()
    status = 500
    try:
        resp = await handler(request)
        status = resp.status
        return resp
    except web.HTTPException as exc:
        status = exc.status
        raise
    finally:
        HTTP_IN_FLIGHT.dec()
        HTTP_REQUESTS.labels(route=route, method=request.method, status=str(status)).inc()
        # The request span lives on the request (async handlers use
        # explicitly-managed spans, not the thread-local stack), so the
        # exemplar trace id is passed explicitly.
        span = request.get("trace_span")
        ctx = getattr(span, "context", None) if span is not None else None
        HTTP_LATENCY.labels(route=route).observe(
            time.time() - start,
            trace_id=f"{ctx.trace_id:032x}" if ctx is not None else None,
        )


# --------------------------------------------------------------------------- #
# Handlers


async def metrics_handler(request: web.Request) -> web.Response:
    """GET /metrics — Prometheus/OpenMetrics exposition of the registry."""
    registry = metrics_mod.get_registry()
    accept = request.headers.get("Accept", "")
    if "application/openmetrics-text" in accept:
        return web.Response(
            body=registry.render(openmetrics=True).encode("utf-8"),
            headers={"Content-Type": metrics_mod.CONTENT_TYPE_OPENMETRICS},
        )
    return web.Response(
        body=registry.render().encode("utf-8"),
        headers={"Content-Type": metrics_mod.CONTENT_TYPE_LATEST},
    )


async def internal_metrics_handler(request: web.Request) -> web.Response:
    """GET /internal/metrics — backward-compatible JSON view over the
    registry. Reads the live engine singleton without ever BUILDING one."""
    from generativeaiexamples_tpu.engine import llm_engine

    eng = llm_engine._ENGINE
    out: dict = {"engine": None}
    if eng is not None:
        m = dict(eng.metrics)
        out["engine"] = m
        if m.get("ttft_n"):
            out["ttft_avg_s"] = m["ttft_sum"] / m["ttft_n"]
            out["prefill_wait_avg_s"] = m.get("prefill_wait_sum", 0.0) / m["ttft_n"]
        if m.get("queue_wait_n"):
            out["queue_wait_avg_s"] = m["queue_wait_sum"] / m["queue_wait_n"]
    out["metrics"] = metrics_mod.get_registry().collect()
    return web.json_response(out)


async def internal_requests_handler(request: web.Request) -> web.Response:
    """GET /internal/requests — flight-recorder view: in-flight request
    timelines plus the newest completed and slow-captured summaries.

    Query params (docs/observability.md):

    - ``?limit=N`` bounds each list (default 50);
    - ``?slow=1`` restricts the view to the slow-capture ring;
    - ``?trace=<32 hex>`` switches to trace-filter mode: FULL timelines
      for every record carrying that W3C trace id (live + completed +
      slow), oldest first — the per-process half of fleet trace
      stitching (the router's ``/internal/trace/{id}`` fans this out
      to its replicas and merges). 400 on a malformed id;
    - ``?since=<cursor>`` switches to incremental-tail mode: FULL
      timelines for records that finished after the cursor (oldest
      first, ``limit``-capped — re-poll from the returned ``cursor``),
      so a poller (the loadgen telemetry scraper) never re-fetches the
      whole ring. Cursor 0 starts from the oldest retained record;
      every response carries the process cursor either way.
    """
    try:
        limit = int(request.query.get("limit", "50"))
    except ValueError:
        limit = 50
    slow_only = request.query.get("slow", "") in ("1", "true", "yes")
    trace_raw = request.query.get("trace")
    if trace_raw is not None:
        trace_id = trace_stitch.normalize_trace_id(trace_raw)
        if trace_id is None:
            return web.json_response(
                {"detail": f"?trace must be a 32-hex W3C trace id, got "
                           f"{trace_raw!r}"},
                status=400,
            )
        return web.json_response(
            {
                "enabled": flight_recorder.enabled(),
                "trace_id": trace_id,
                "timelines": flight_recorder.timelines_for_trace(trace_id),
            }
        )
    since_raw = request.query.get("since")
    if since_raw is not None:
        try:
            since = int(since_raw)
        except ValueError:
            return web.json_response(
                {"detail": f"?since must be an integer cursor, got {since_raw!r}"},
                status=400,
            )
        timelines, cur = flight_recorder.completed_since(
            since, slow=slow_only, limit=limit
        )
        return web.json_response(
            {
                "enabled": flight_recorder.enabled(),
                "cursor": cur,
                "timelines": timelines,
            }
        )
    out = {
        "enabled": flight_recorder.enabled(),
        "cursor": flight_recorder.cursor(),
        "slow": flight_recorder.slow_captures(limit),
    }
    if not slow_only:
        out["in_flight"] = flight_recorder.inflight()
        out["recent"] = flight_recorder.recent(limit)
    return web.json_response(out)


async def internal_timeline_handler(request: web.Request) -> web.Response:
    """GET /internal/timeline — the engine dispatch-timeline ring
    (engine/dispatch_timeline.py): per-launch spans with lock-wait /
    device-estimate / host-gap attribution, plus the rolling bubble
    decomposition.

    Query params (docs/observability.md):

    - ``?since=<cursor>`` — incremental tail, the same contract as
      ``/internal/requests``: spans recorded after the cursor (oldest
      first, ``limit``-capped — re-poll from the returned ``cursor``),
      400 on a non-integer cursor, and every response carries the
      process cursor. Cursor 0 starts from the oldest retained span;
    - ``?limit=N`` bounds the span list (default 500);
    - ``?format=perfetto`` — Chrome-trace JSON instead (load in
      ui.perfetto.dev): one track per tier thread plus a device track,
      flight-recorder request lifecycles overlaid as instants carrying
      their trace ids (the join key to stitched router traces);
    - ``?xplane=<logdir>`` (with perfetto) — replace the host-return
      device-estimate track with measured jit_* executable spans parsed
      from a ``jax.profiler`` capture under ``logdir``
      (utils/xplane.py); ignored when no trace file exists there.
    """
    from generativeaiexamples_tpu.utils import xplane

    try:
        limit = int(request.query.get("limit", "500"))
    except ValueError:
        limit = 500
    since_raw = request.query.get("since")
    since = 0
    if since_raw is not None:
        try:
            since = int(since_raw)
        except ValueError:
            return web.json_response(
                {"detail": f"?since must be an integer cursor, got {since_raw!r}"},
                status=400,
            )
    spans, cur = dispatch_timeline.spans_since(since, limit=limit)
    if request.query.get("format") == "perfetto":
        device_events: list = []
        xplane_dir = request.query.get("xplane")
        if xplane_dir:
            try:
                device_events = xplane.device_track_events(xplane_dir)
            except FileNotFoundError:
                device_events = []  # no capture yet: estimate track serves
        trace = dispatch_timeline.perfetto_trace(
            spans,
            flight=flight_recorder.recent_timelines(limit=32),
            device_events=device_events,
        )
        trace["cursor"] = cur
        trace["enabled"] = dispatch_timeline.enabled()
        return web.json_response(trace)
    out = {
        "enabled": dispatch_timeline.enabled(),
        "cursor": cur,
        "spans": spans,
        "bubble": dispatch_timeline.bubble_snapshot(),
    }
    return web.json_response(out)


async def internal_request_detail_handler(request: web.Request) -> web.Response:
    """GET /internal/requests/{id} — one request's full timeline, by
    flight-recorder request id or engine rid."""
    key = request.match_info.get("id", "")
    timeline = flight_recorder.get_timeline(key)
    if timeline is None:
        return web.json_response(
            {"detail": f"no timeline for request {key!r}"}, status=404
        )
    return web.json_response(timeline)


async def internal_slo_handler(request: web.Request) -> web.Response:
    """GET /internal/slo — sliding-window SLO evaluation plus the live
    engine-utilization snapshot (never builds an engine)."""
    from generativeaiexamples_tpu.engine import llm_engine

    out = slo_mod.summary()
    eng = llm_engine._ENGINE  # peek only — a scrape must stay cheap
    out["utilization"] = (
        eng.utilization_snapshot() if eng is not None else None
    )
    return web.json_response(out)


async def profile_start_handler(request: web.Request) -> web.Response:
    """POST /internal/profile/start — begin a jax.profiler capture.
    Optional JSON body: {"log_dir": "..."} overrides PROFILE_LOG_DIR."""
    log_dir = None
    if request.can_read_body:
        try:
            body = await request.json()
            log_dir = body.get("log_dir") or None
        except Exception:  # noqa: BLE001 - empty/invalid body means defaults
            pass
    # off the event loop: starting a capture takes the profiler's time,
    # and the streams are served by this loop
    status, payload = await asyncio.to_thread(profiling.start_profile, log_dir)
    return web.json_response(payload, status=status)


async def profile_stop_handler(request: web.Request) -> web.Response:
    """POST /internal/profile/stop — end the active capture."""
    # writing the capture out takes seconds: off the event loop
    status, payload = await asyncio.to_thread(profiling.stop_profile)
    return web.json_response(payload, status=status)


async def debug_bundles_handler(request: web.Request) -> web.Response:
    """GET /internal/debug/bundles — anomaly black-box capture index
    (newest first; fetch content by id below)."""
    return web.json_response(
        {"enabled": blackbox.enabled(), "bundles": blackbox.list_bundles()}
    )


async def debug_bundle_detail_handler(request: web.Request) -> web.Response:
    """GET /internal/debug/bundles/{id} — one bundle's full content."""
    bundle_id = request.match_info.get("id", "")
    bundle = blackbox.get_bundle(bundle_id)
    if bundle is None:
        return web.json_response(
            {"detail": f"no black-box bundle {bundle_id!r}"}, status=404
        )
    return web.json_response(
        bundle, dumps=functools.partial(json.dumps, default=str)
    )


def add_observability_routes(app: web.Application) -> None:
    """Wire /metrics + profiler + introspection endpoints onto an
    aiohttp application (shared by the chain-server, the engine server,
    and the router)."""
    app.router.add_get("/metrics", metrics_handler)
    app.router.add_post("/internal/profile/start", profile_start_handler)
    app.router.add_post("/internal/profile/stop", profile_stop_handler)
    app.router.add_get("/internal/requests", internal_requests_handler)
    app.router.add_get("/internal/requests/{id}", internal_request_detail_handler)
    app.router.add_get("/internal/timeline", internal_timeline_handler)
    app.router.add_get("/internal/slo", internal_slo_handler)
    app.router.add_get("/internal/debug/bundles", debug_bundles_handler)
    app.router.add_get(
        "/internal/debug/bundles/{id}", debug_bundle_detail_handler
    )
