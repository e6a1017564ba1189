"""The chain-server HTTP API.

Re-implements the reference FastAPI app (reference:
RetrievalAugmentedGeneration/common/server.py:44-427) on aiohttp/asyncio
with the identical observable contract:

- ``GET /health`` → ``{"message": "Service is up."}``
- ``POST /generate`` → ``text/event-stream`` of ``data: {ChainResponse}\\n\\n``
  frames, terminated by a frame with ``finish_reason="[DONE]"``; degraded
  single-frame 500 streams on errors (server.py:314-342);
- ``POST /documents`` multipart upload → save + ``ingest_docs``;
- ``POST /search``, ``GET /documents``, ``DELETE /documents?filename=``;
- 422 ``{"detail": [...]}`` on request-validation errors;
- permissive CORS (server.py:47-56).

Chains expose synchronous generators (parity with the reference chain
contract), so chain calls and chunk iteration run on a worker thread and
feed the asyncio response through a queue — the TPU decode loop lives in
its own thread inside the engine and is never blocked by slow SSE consumers.
"""
from __future__ import annotations

import asyncio
import html
import json
import os
import re
import threading
from pathlib import Path
from typing import Any, AsyncIterator, Callable, Generator, Optional, Type
from uuid import uuid4

from aiohttp import web
from pydantic import ValidationError

from generativeaiexamples_tpu.chains.base import BaseExample
from generativeaiexamples_tpu.chains.registry import resolve_example
from generativeaiexamples_tpu.chains.runtime import DegradedWarning
from generativeaiexamples_tpu.retrieval.errors import VectorStoreError
from generativeaiexamples_tpu.server.schemas import (
    ChainResponse,
    ChainResponseChoices,
    DocumentChunk,
    DocumentSearch,
    DocumentSearchResponse,
    DocumentsResponse,
    HealthResponse,
    MAX_CONTENT_LEN,
    Message,
    Prompt,
)
from generativeaiexamples_tpu.server.observability import (
    ACTIVE_STREAMS,
    DEADLINE_EXCEEDED,
    REQUESTS_SHED,
    add_observability_routes,
    internal_metrics_handler,
    metrics_middleware,
)
from generativeaiexamples_tpu.engine import dispatch_timeline
from generativeaiexamples_tpu.engine.tokenizer import pieces_of
from generativeaiexamples_tpu.utils import blackbox
from generativeaiexamples_tpu.utils import faults as faults_mod
from generativeaiexamples_tpu.utils import flight_recorder
from generativeaiexamples_tpu.utils import get_logger
from generativeaiexamples_tpu.utils import resilience
from generativeaiexamples_tpu.utils import slo as slo_mod
from generativeaiexamples_tpu.utils.resilience import (
    Deadline,
    DeadlineExceeded,
    EngineOverloaded,
    RequestPreempted,
)
from generativeaiexamples_tpu.utils.tracing import get_tracer

logger = get_logger(__name__)

UPLOAD_FOLDER = os.environ.get("DOC_UPLOAD_DIR", "/tmp-data/uploaded_files")

VECTOR_STORE_ERROR_MSG = (
    "Error from milvus server. Please ensure you have ingested some documents. "
    "Please check chain-server logs for more details."
)
GENERIC_ERROR_MSG = (
    "Error from chain server. Please check chain-server logs for more details."
)

# Response header on /internal/restore: the snapshot id this stream
# continues plus the mode the engine chose (restore | replay) — the
# router's handover path logs it and tests assert on it.
RESTORE_HEADER = "X-GenAI-Restore"

_SENTINEL = object()


def _sse_frame(resp: ChainResponse) -> str:
    # exclude_none keeps reference wire parity: the additive `warnings`
    # field appears only on frames that actually carry warnings.
    return "data: " + resp.model_dump_json(exclude_none=True) + "\n\n"


# What the Message schema's sanitizer (bleach) rewrites: markup and
# html5lib's control characters. Text without them is its own clean form.
_SANITIZER_REWRITES = re.compile("[\x00-\x08\x0b-\x1f&<>\x7f-\x9f\ud800-\udfff]")


def _wire_text(piece: str) -> str:
    """An answer piece as the Message schema holds it, at the schema's
    cost only where it would differ. One wire change (docs/streaming.md):
    a piece the sanitizer erases WHOLE (a token that reads as one tag,
    "<unk>") goes out escaped, as it does when it comes as three tokens."""
    if len(piece) <= MAX_CONTENT_LEN and not _SANITIZER_REWRITES.search(piece):
        return piece
    content = Message(role="assistant", content=piece).content
    return content or Message(role="assistant", content=html.escape(piece)).content


def _chunk_frames(resp_id: str) -> Callable[[Any], bytes]:
    """A response's frame builder: a chunk in, the bytes of its frames
    out, one per token (a ``TokenBlock``'s pieces; a plain ``str`` is
    one). The id is fixed, so a frame is the head and tail of the
    schema's own JSON around the text's."""
    head, tail = _sse_frame(ChainResponse(id=resp_id, choices=[ChainResponseChoices(
        index=0, message=Message(role="assistant", content="@"), finish_reason="",
    )])).rsplit('"@"', 1)

    def frames(chunk: Any) -> bytes:
        return "".join(
            head + json.dumps(_wire_text(piece), ensure_ascii=False) + tail
            for piece in pieces_of(chunk)
        ).encode()

    return frames


def _warning_frame(resp_id: str, warning: str) -> str:
    """A warnings-only SSE frame (no answer text, stream continues)."""
    return _sse_frame(ChainResponse(id=resp_id, choices=[], warnings=[warning]))


def _preempt_frame(resp_id: str, exc: RequestPreempted) -> str:
    """The drain terminator frame: ``finish_reason="PREEMPTED"`` plus a
    warning carrying the snapshot id the router's handover path needs
    for the sibling restore (an empty id means replay from the original
    prompt — nothing was spoolable)."""
    sid = getattr(exc, "snapshot_id", None) or ""
    return _sse_frame(
        ChainResponse(
            id=resp_id,
            choices=[ChainResponseChoices(index=0, finish_reason="PREEMPTED")],
            warnings=[f"preempted snapshot_id={sid}"],
        )
    )


# --------------------------------------------------------------------------- #
# preemption / drain lifecycle (docs/resilience.md) — module-level handlers
# shared by BOTH replica kinds: the chain-server registers them below, the
# engine OpenAI facade (engine/server.py) registers the same objects, so the
# router's handover path works against either half of a mixed fleet.

def _live_engine():
    from generativeaiexamples_tpu.engine import llm_engine

    return llm_engine._ENGINE  # peek only — never BUILD an engine here

async def engine_drain_handler(request: web.Request) -> web.Response:
    """POST /internal/drain — quiesce admission and checkpoint every
    in-flight request into the snapshot spool; returns the drain
    summary the router's handover consumes. ``{"resume": true}``
    lifts a previous drain instead. The blocking drain runs on an
    executor thread so the event loop keeps serving
    /internal/snapshots to the router meanwhile."""
    eng = _live_engine()
    if eng is None:
        return web.json_response(
            {"detail": "no live engine in this process"}, status=503
        )
    try:
        body = await request.json()
    except Exception:  # noqa: BLE001 — an empty body is the common case
        body = None
    loop = asyncio.get_running_loop()
    if isinstance(body, dict) and body.get("resume"):
        await loop.run_in_executor(None, eng.resume_from_drain)
        return web.json_response({"draining": False})
    summary = await loop.run_in_executor(None, eng.drain)
    return web.json_response(summary)

async def list_snapshots_handler(request: web.Request) -> web.Response:
    """GET /internal/snapshots — the spool inventory (how the router
    discovers a dead or draining replica's checkpoints)."""
    eng = _live_engine()
    if eng is None:
        return web.json_response(
            {"detail": "no live engine in this process"}, status=503
        )
    return web.json_response({"snapshots": eng.snapshot_spool.list()})

async def get_snapshot_handler(request: web.Request) -> web.Response:
    """GET /internal/snapshots/{snapshot_id} — the raw spool
    document, relayed verbatim by the router into a sibling's
    /internal/restore."""
    eng = _live_engine()
    if eng is None:
        return web.json_response(
            {"detail": "no live engine in this process"}, status=503
        )
    from generativeaiexamples_tpu.engine import request_snapshot as snap_mod

    sid = request.match_info.get("snapshot_id", "")
    try:
        doc = await asyncio.get_running_loop().run_in_executor(
            None, eng.snapshot_spool.load_doc, sid
        )
    except snap_mod.SnapshotError as exc:
        return web.json_response({"detail": str(exc)}, status=404)
    return web.json_response(doc)

async def restore_snapshot_handler(request: web.Request) -> web.StreamResponse:
    """POST /internal/restore — re-admit a snapshot document on this
    replica and stream the continuation as /generate-shaped SSE
    frames. The stream re-delivers the spooled transcript from the
    start; the router trims the re-delivered prefix by character
    offset before bridging into the original client stream. 409 on
    config-fingerprint or KV-geometry mismatch (refuse loudly, never
    resume garbage)."""
    eng = _live_engine()
    if eng is None:
        return web.json_response(
            {"detail": "no live engine in this process"}, status=503
        )
    from generativeaiexamples_tpu.engine import request_snapshot as snap_mod

    try:
        doc = await request.json()
        snap = snap_mod.RequestSnapshot.from_doc(doc)
    except snap_mod.SnapshotMismatch as exc:
        return web.json_response({"detail": str(exc)}, status=409)
    except Exception:  # noqa: BLE001 — malformed body
        return web.json_response(
            {"detail": "body must be a snapshot document"}, status=422
        )
    span = request.get("trace_span")
    trace_ctx = getattr(span, "context", None) if span is not None else None
    rec = flight_recorder.start(
        trace_id=f"{trace_ctx.trace_id:032x}" if trace_ctx is not None else None,
    )
    if rec is not None:
        rec.event("http_request", path=request.path)
    loop = asyncio.get_running_loop()
    try:
        req, params, prior_ids, mode = await loop.run_in_executor(
            None,
            _traced_call(
                trace_ctx,
                lambda: eng.restore_snapshot(snap),
                flight_rec=rec,
            ),
        )
    except snap_mod.SnapshotMismatch as exc:
        flight_recorder.finish(rec, "mismatch")
        return web.json_response({"detail": str(exc)}, status=409)
    except EngineOverloaded as exc:
        flight_recorder.finish(rec, "overload")
        return web.json_response({"detail": str(exc)}, status=503)
    except (snap_mod.SnapshotError, TimeoutError) as exc:
        logger.error("Restore of %s failed: %s", snap.snapshot_id, exc)
        flight_recorder.finish(rec, "error")
        return web.json_response({"detail": str(exc)}, status=500)
    resp = web.StreamResponse(
        status=200,
        headers={
            "Content-Type": "text/event-stream",
            RESTORE_HEADER: f"{snap.snapshot_id}; mode={mode}",
            "Access-Control-Allow-Origin": "*",
        },
    )
    await resp.prepare(request)
    resp_id = str(uuid4())
    try:
        gen = eng.stream_restored(req, params, prior_ids)
        frames = _chunk_frames(resp_id)
        async for chunk in _aiter_threaded(gen, trace_ctx, flight_rec=rec):
            await resp.write(frames(chunk))
        await resp.write(
            _sse_frame(
                ChainResponse(
                    id=resp_id,
                    choices=[ChainResponseChoices(finish_reason="[DONE]")],
                )
            ).encode()
        )
    except (ConnectionResetError, asyncio.CancelledError):
        logger.info("Client disconnected mid-restore-stream.")
        flight_recorder.finish(rec, "aborted")
        raise
    except RequestPreempted as exc:
        # Drained again mid-restore: hand the (new) snapshot id back
        # to the router so it can chain the handover once more.
        await resp.write(_preempt_frame(resp_id, exc).encode())
    except Exception as exc:  # noqa: BLE001
        logger.error("Error mid-stream in /internal/restore: %s", exc)
        await resp.write(_error_stream_body(GENERIC_ERROR_MSG).encode())
    finally:
        flight_recorder.finish(rec)
    await resp.write_eof()
    return resp



def _request_deadline(rcfg, request: web.Request, prompt: Prompt) -> Optional[Deadline]:
    """Resolve the request's deadline budget: the X-Request-Deadline-Ms
    header wins over the body's deadline_ms field, which wins over the
    resilience.request_deadline_ms config default. A value of 0 at any
    level explicitly disables the deadline (matching the config knob's
    '0 disables' contract)."""
    ms: Optional[int] = None
    header = request.headers.get("X-Request-Deadline-Ms")
    if header:
        try:
            ms = int(header)
        except ValueError:
            logger.warning("Ignoring malformed X-Request-Deadline-Ms: %r", header)
        else:
            if ms <= 0:
                return None  # explicit per-request opt-out
    if ms is None and prompt.deadline_ms is not None:
        if prompt.deadline_ms <= 0:
            return None  # explicit per-request opt-out via the body
        ms = prompt.deadline_ms
    if ms is None:
        ms = rcfg.request_deadline_ms
    return Deadline.after(ms / 1000.0) if ms and ms > 0 else None


def _engine_queue_depth() -> Optional[int]:
    """The live engine's admission-queue depth, or None when no engine
    exists in this process (remote-LLM deployments). Never builds one."""
    from generativeaiexamples_tpu.engine.llm_engine import live_queue_depth

    return live_queue_depth()


def _error_stream_body(msg: str) -> str:
    resp = ChainResponse(
        choices=[
            ChainResponseChoices(
                index=0,
                message=Message(role="assistant", content=msg),
                finish_reason="[DONE]",
            )
        ]
    )
    return _sse_frame(resp)


def _traced_call(trace_ctx, fn: Callable, deadline: Optional[Deadline] = None,
                 flight_rec=None) -> Callable:
    """Run ``fn`` on a worker thread with the request's span as the
    thread-local remote parent, so chain-internal spans nest correctly
    (reference: the instrumentation decorators at common/tracing.py:62-88
    thread trace context into the chain call). The request deadline and
    flight-recorder record are bound to the same thread (and always
    cleared — executor threads are pooled and reused)."""

    def run():
        tracer = get_tracer()
        tracer.attach_context(trace_ctx)
        resilience.set_current_deadline(deadline)
        flight_recorder.bind(flight_rec)
        try:
            return fn()
        finally:
            tracer.attach_context(None)
            resilience.set_current_deadline(None)
            flight_recorder.unbind()

    return run


async def _aiter_threaded(
    gen: Generator[Any, None, None], trace_ctx=None,
    deadline: Optional[Deadline] = None, flight_rec=None,
) -> AsyncIterator[Any]:
    """Drive a synchronous generator on a worker thread, yielding via asyncio.

    Items reach the event loop by ``call_soon_threadsafe``: no executor
    worker waits per stream (docs/streaming.md). A bounded count of
    items in flight applies backpressure to the producer when the SSE
    consumer is slow, without ever blocking the event loop. If the
    consumer goes away mid-stream (client disconnect, a cancelled
    handler), the stop flag unblocks the producer and the generator is
    closed so chain/engine resources are released rather than leaking a
    parked thread per disconnect.
    """
    loop = asyncio.get_running_loop()
    q: asyncio.Queue = asyncio.Queue()
    room = threading.Semaphore(64)  # items handed over and not yet taken
    stop = threading.Event()

    def _put(item: Any) -> bool:
        room.acquire()
        if stop.is_set():
            room.release()  # pass the wake-up on: no later put may wait
            return False
        try:
            loop.call_soon_threadsafe(q.put_nowait, item)
        except RuntimeError:  # the loop closed under a stream (shutdown)
            return False
        return True

    def _produce() -> None:
        get_tracer().attach_context(trace_ctx)
        # Generator bodies (multi_turn's rag_chain, the engine's token
        # stream) execute HERE, not on the chain-call thread — bind the
        # request deadline and flight-recorder record to this thread too.
        resilience.set_current_deadline(deadline)
        flight_recorder.bind(flight_rec)
        try:
            try:
                for item in gen:
                    if not _put(item):
                        return
                _put(_SENTINEL)
            except BaseException as exc:  # noqa: BLE001 - forwarded to consumer
                _put(exc)
        finally:
            # close() runs the generator chain's finally blocks — the
            # engine backend aborts its in-flight request there, freeing
            # the decode slot and prefix pins on consumer disconnect.
            # (Chains may also return plain iterators, which have no
            # close(): the canned-message fallbacks hold no resources.)
            close = getattr(gen, "close", None)
            if close is not None:
                close()
            resilience.set_current_deadline(None)
            flight_recorder.unbind()
            get_tracer().attach_context(None)

    thread = threading.Thread(target=_produce, daemon=True, name="sse-producer")
    thread.start()
    try:
        while True:
            item = await q.get()
            room.release()
            if item is _SENTINEL:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
            # the handler has written it: a TokenBlock tells the engine
            written = getattr(item, "written", None)
            if written is not None:
                written()
    finally:
        stop.set()
        room.release()  # wake a producer that waits for room


@web.middleware
async def tracing_middleware(request: web.Request, handler: Callable) -> web.StreamResponse:
    """Request span with W3C traceparent extraction (reference:
    common/tracing.py:62-73) and system metrics at span end."""
    tracer = get_tracer()
    span = tracer.start_span(
        f"{request.method} {request.path}",
        remote_ctx=tracer.extract(request.headers),
        attributes={"http.method": request.method, "http.target": request.path},
    )
    request["trace_span"] = span
    try:
        resp = await handler(request)
        span.set_attribute("http.status_code", resp.status)
        if resp.status >= 500:
            # Server errors returned as responses (e.g. the degraded SSE
            # 500 stream) must mark the span ERROR just like raised
            # exceptions do — otherwise error traces look healthy.
            span.status = "ERROR"
        return resp
    except BaseException as exc:
        span.record_exception(exc)
        raise
    finally:
        tracer.finish_span(span, system_metrics=True)


@web.middleware
async def cors_middleware(request: web.Request, handler: Callable) -> web.StreamResponse:
    if request.method == "OPTIONS":
        resp: web.StreamResponse = web.Response(status=204)
    else:
        resp = await handler(request)
    resp.headers["Access-Control-Allow-Origin"] = "*"
    resp.headers["Access-Control-Allow-Methods"] = "*"
    resp.headers["Access-Control-Allow-Headers"] = "*"
    return resp


def _validation_error_response(exc: ValidationError) -> web.Response:
    # Mirror FastAPI's 422 shape (reference: server.py:175-181).
    detail = [
        {k: v for k, v in err.items() if k != "input"} for err in exc.errors()
    ]
    for err in detail:
        if "ctx" in err:
            err["ctx"] = {k: str(v) for k, v in err["ctx"].items()}
        if "loc" in err:
            err["loc"] = ["body"] + list(err["loc"])
        err.pop("url", None)
    return web.json_response({"detail": detail}, status=422)


class ChainServer:
    """Owns the example-chain class and builds the aiohttp application."""

    def __init__(self, example_cls: Optional[Type[BaseExample]] = None):
        self._example_cls = example_cls
        # In-flight SSE stream count (event-loop-confined; no lock) for
        # admission control.
        self._active_streams = 0

    @property
    def example_cls(self) -> Type[BaseExample]:
        if self._example_cls is None:
            self._example_cls = resolve_example()
        return self._example_cls

    def build_app(self) -> web.Application:
        app = web.Application(
            middlewares=[tracing_middleware, metrics_middleware, cors_middleware],
            client_max_size=512 * 1024 * 1024,
        )
        app.router.add_get("/health", self.health_check)
        # Additive (non-reference) readiness probe: /health keeps the
        # reference's exact wire format, while this reports whether the
        # background engine warmup is still compiling serving shapes —
        # benchmarks/orchestrators wait on it so multi-minute XLA
        # compiles never land inside a measured window (ADVICE r2).
        app.router.add_get("/internal/ready", self.readiness_check)
        app.router.add_get("/internal/metrics", self.metrics_view)
        # Preemption / drain lifecycle (docs/resilience.md): the router's
        # handover path drives these on replica shutdown and restore.
        app.router.add_post("/internal/drain", engine_drain_handler)
        app.router.add_get("/internal/snapshots", list_snapshots_handler)
        app.router.add_get(
            "/internal/snapshots/{snapshot_id}", get_snapshot_handler
        )
        app.router.add_post("/internal/restore", restore_snapshot_handler)
        add_observability_routes(app)  # /metrics + profiler capture
        app.router.add_post("/generate", self.generate_answer)
        app.router.add_post("/search", self.document_search)
        app.router.add_post("/documents", self.upload_document)
        app.router.add_get("/documents", self.get_documents)
        app.router.add_delete("/documents", self.delete_document)
        app["chain_server"] = self
        return app

    # ------------------------------------------------------------------ //
    async def health_check(self, request: web.Request) -> web.Response:
        return web.json_response(HealthResponse(message="Service is up.").model_dump())

    async def readiness_check(self, request: web.Request) -> web.Response:
        from generativeaiexamples_tpu.engine.embedder import (
            retrieval_warmup_complete,
        )
        from generativeaiexamples_tpu.engine.llm_engine import (
            engine_wedged,
            warmup_complete,
        )

        wedged = engine_wedged()
        ready = warmup_complete() and retrieval_warmup_complete() and not wedged
        return web.json_response(
            {"ready": ready, "wedged": wedged}, status=200 if ready else 503
        )

    async def metrics_view(self, request: web.Request) -> web.Response:
        """Backward-compatible JSON view over the metrics registry
        (exposition format lives at /metrics). Reads the live engine
        singleton without ever BUILDING one (a metrics scrape must not
        trigger a multi-minute engine boot)."""
        return await internal_metrics_handler(request)

    # ------------------------------------------------------------------ //
    # admission control / deadlines (docs/resilience.md)

    def _admission_denied(self, rcfg) -> Optional[str]:
        """Load-shedding decision for a new /generate request; returns
        the shed reason or None to admit. Consulted only when the
        resilience layer is on. The server.admission fault point runs
        off-loop in generate_answer, not here — this method executes on
        the event loop, where a delay/hang-mode fault would freeze
        /health and every in-flight SSE stream, not just admission."""
        cap = rcfg.max_active_streams
        if cap > 0 and self._active_streams >= cap:
            return "active_streams"
        qcap = rcfg.engine_queue_cap
        if qcap > 0:
            from generativeaiexamples_tpu.engine import llm_engine

            eng = llm_engine._ENGINE  # never BUILD an engine here
            if eng is not None and eng.queue_depth() >= qcap:
                return "engine_queue"
        return None

    def _shed_response(self, rcfg, reason: str, span, detail: str = "",
                       flight_rec=None) -> web.Response:
        REQUESTS_SHED.labels(reason=reason).inc()
        slo_mod.observe_event("shed")
        blackbox.notify_shed(reason)
        if flight_rec is not None:
            flight_rec.event("shed", reason=reason)
            flight_recorder.finish(flight_rec, "shed")
        if span is not None:
            span.set_attribute("genai.request_shed", reason)
        retry_after = max(1, int(rcfg.shed_retry_after_s))
        logger.warning("Shedding /generate (%s): %s", reason, detail or "at capacity")
        headers = {"Retry-After": str(retry_after)}
        # Queue-depth context for the routing tier's bounded-load spill
        # (docs/router.md): how deep the engine's admission queue was at
        # shed time, from the same live value genai_engine_queue_depth
        # exports. Peek only — a shed must never BUILD an engine.
        depth = _engine_queue_depth()
        if depth is not None:
            headers["X-GenAI-Queue-Depth"] = str(depth)
        return web.json_response(
            {"detail": detail or f"server overloaded ({reason}); retry later"},
            status=429,
            headers=headers,
        )

    async def generate_answer(self, request: web.Request) -> web.StreamResponse:
        try:
            prompt = Prompt.model_validate(await request.json())
        except ValidationError as exc:
            return _validation_error_response(exc)
        except Exception:
            return web.json_response({"detail": "Invalid JSON body"}, status=422)

        from generativeaiexamples_tpu.config import get_config

        config = get_config()
        rcfg = config.resilience
        resilient_on = resilience.resilience_enabled(config)
        span = request.get("trace_span")
        trace_ctx0 = getattr(span, "context", None) if span is not None else None
        rec = flight_recorder.start(
            trace_id=f"{trace_ctx0.trace_id:032x}" if trace_ctx0 is not None else None,
        )
        if rec is not None:
            rec.event("http_request", path=request.path)
        deadline: Optional[Deadline] = None
        if resilient_on:
            if faults_mod.active():  # zero-cost when no rules are armed
                try:
                    # Off-loop: a delay/hang-mode fault configured at this
                    # site must park an executor thread, not the event loop.
                    await asyncio.get_running_loop().run_in_executor(
                        None, faults_mod.fault_point, "server.admission"
                    )
                except faults_mod.FaultInjected:
                    # An injected error at this site simulates saturation.
                    return self._shed_response(
                        rcfg, "fault_injected", span, flight_rec=rec
                    )
            shed_reason = self._admission_denied(rcfg)
            if shed_reason is not None:
                return self._shed_response(
                    rcfg, shed_reason, span, flight_rec=rec
                )
            deadline = _request_deadline(rcfg, request, prompt)
            if deadline is not None and deadline.expired:
                DEADLINE_EXCEEDED.labels(stage="admission").inc()
                if span is not None:
                    span.set_attribute("genai.deadline_exceeded", "admission")
                if rec is not None:
                    rec.event("deadline_exceeded", stage="admission")
                    flight_recorder.finish(rec, "deadline")
                return web.json_response(
                    {"detail": "request deadline exhausted before admission"},
                    status=504,
                )

        # Count the request against the admission cap from the moment it
        # is admitted — NOT only once the SSE stream is prepared. The
        # retrieval/submit phase can take seconds (longer under retry
        # backoff); leaving it invisible to _admission_denied would let a
        # burst overshoot max_active_streams arbitrarily, which is
        # exactly the load spike the cap exists for.
        self._active_streams += 1
        ACTIVE_STREAMS.set(self._active_streams)
        slo_mod.observe_event("admitted")
        if rec is not None:
            rec.event("admitted", active_streams=self._active_streams)
        try:
            return await self._generate_admitted(
                request, prompt, rcfg, span, deadline, rec
            )
        finally:
            self._active_streams -= 1
            ACTIVE_STREAMS.set(self._active_streams)
            # Retire the server-owned record (idempotent — shed paths
            # finished it already) and mirror slow timelines onto the
            # request span so the Jaeger trace carries the same
            # submit→finish chain as the JSONL capture.
            flight_recorder.finish(rec)
            flight_recorder.attach_span_events(rec, span)

    async def _generate_admitted(
        self,
        request: web.Request,
        prompt: Prompt,
        rcfg,
        span,
        deadline: Optional[Deadline],
        rec=None,
    ) -> web.StreamResponse:
        """The post-admission part of /generate: chain dispatch plus SSE
        streaming. The caller holds this request's _active_streams slot
        for the whole call."""
        chat_history = list(prompt.messages)
        # The last user message is the query for the chain (server.py:259-267).
        last_user_message = next(
            (m.content for m in reversed(chat_history) if m.role == "user"), None
        )
        for i in reversed(range(len(chat_history))):
            if chat_history[i].role == "user":
                del chat_history[i]
                break

        llm_settings = {
            key: value
            for key, value in dict(prompt).items()
            if key not in ("messages", "use_knowledge_base", "deadline_ms")
        }

        loop = asyncio.get_running_loop()
        trace_ctx = getattr(span, "context", None) if span is not None else None
        try:
            example = self.example_cls()
            if prompt.use_knowledge_base:
                logger.info("Knowledge base is enabled. Using rag chain for response generation.")
                chain_fn = example.rag_chain
            else:
                chain_fn = example.llm_chain
            generator = await loop.run_in_executor(
                None,
                _traced_call(
                    trace_ctx,
                    lambda: chain_fn(
                        query=last_user_message, chat_history=chat_history, **llm_settings
                    ),
                    deadline=deadline,
                    flight_rec=rec,
                ),
            )
        except EngineOverloaded as exc:
            # The engine's admission-queue cap (max_queued_requests)
            # raises at submit time — before any SSE bytes went out, so
            # the shed can still be a clean 429.
            return self._shed_response(
                rcfg, "engine_overloaded", span, str(exc), flight_rec=rec
            )
        except DeadlineExceeded as exc:
            DEADLINE_EXCEEDED.labels(stage="admission").inc()
            if span is not None:
                span.set_attribute("genai.deadline_exceeded", "admission")
            if rec is not None:
                rec.event("deadline_exceeded", stage="admission")
            return web.json_response({"detail": str(exc)}, status=504)
        except VectorStoreError as exc:
            logger.error("Vector store error in /generate: %s", exc)
            return self._degraded_stream(VECTOR_STORE_ERROR_MSG)
        except Exception as exc:  # noqa: BLE001
            logger.error("Error from /generate endpoint. Error details: %s", exc)
            return self._degraded_stream(GENERIC_ERROR_MSG)

        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                # The CORS middleware mutates headers after the handler
                # returns — too late for an already-prepared stream, so the
                # SSE response carries them itself.
                "Access-Control-Allow-Origin": "*",
                "Access-Control-Allow-Methods": "*",
                "Access-Control-Allow-Headers": "*",
            },
        )
        await resp.prepare(request)
        resp_id = str(uuid4())
        frames = _chunk_frames(resp_id)
        degraded_seen = False
        try:
            if generator:
                async for chunk in _aiter_threaded(
                    generator, trace_ctx, deadline, flight_rec=rec
                ):
                    if isinstance(chunk, DegradedWarning):
                        degraded_seen = True
                        # Structured degradation marker from a chain
                        # (retrieval down -> LLM-only answer): forwarded
                        # as a warnings-only frame, not answer text.
                        if span is not None:
                            span.set_attribute("genai.degraded", chunk.reason)
                        await resp.write(
                            _warning_frame(resp_id, str(chunk)).encode()
                        )
                        continue
                    if span is not None:
                        # one event a hand-off with its token count (one a
                        # token in the reference: opentelemetry_callback.py:248)
                        span.add_event("llm.new_token", {
                            "length": len(chunk), "count": len(pieces_of(chunk)),
                        })
                    await resp.write(frames(chunk))
                await resp.write(
                    _sse_frame(
                        ChainResponse(
                            id=resp_id,
                            choices=[ChainResponseChoices(finish_reason="[DONE]")],
                        )
                    ).encode()
                )
                if not degraded_seen:
                    # Degraded streams were counted by the chain; only
                    # clean completions feed the degraded-rate base.
                    slo_mod.observe_event("answered")
            else:
                await resp.write(_sse_frame(ChainResponse()).encode())
        except (ConnectionResetError, asyncio.CancelledError):
            logger.info("Client disconnected mid-stream.")
            raise
        except (DeadlineExceeded, TimeoutError) as exc:
            # Mid-stream deadline/stall: close the stream cleanly with a
            # structured warning instead of a generic 500-style frame.
            DEADLINE_EXCEEDED.labels(stage="stream").inc()
            if span is not None:
                span.set_attribute("genai.deadline_exceeded", "stream")
            if rec is not None:
                rec.event("deadline_exceeded", stage="stream")
            logger.warning("Deadline exceeded mid-stream in /generate: %s", exc)
            await resp.write(
                _sse_frame(
                    ChainResponse(
                        id=resp_id,
                        choices=[ChainResponseChoices(finish_reason="[DONE]")],
                        warnings=[f"deadline_exceeded: {exc}"],
                    )
                ).encode()
            )
        except RequestPreempted as exc:
            # Engine drain checkpointed this request mid-stream: close
            # with the typed terminator the router's handover path
            # intercepts (snapshot id → sibling restore; no id → replay
            # from the original prompt). Must precede the generic
            # handler or a 500-style frame would eat the signal.
            if span is not None:
                span.set_attribute(
                    "genai.preempted", exc.snapshot_id or "replay"
                )
            logger.warning(
                "Request preempted mid-stream (snapshot=%s)",
                exc.snapshot_id or "replay",
            )
            await resp.write(_preempt_frame(resp_id, exc).encode())
        except VectorStoreError as exc:
            logger.error("Vector store error mid-stream: %s", exc)
            await resp.write(_error_stream_body(VECTOR_STORE_ERROR_MSG).encode())
        except Exception as exc:  # noqa: BLE001
            logger.error("Error mid-stream in /generate. Error details: %s", exc)
            await resp.write(_error_stream_body(GENERIC_ERROR_MSG).encode())
        await resp.write_eof()
        return resp

    def _degraded_stream(self, msg: str) -> web.Response:
        # Single-frame 500 event-stream (reference: server.py:314-342).
        return web.Response(
            status=500, content_type="text/event-stream", text=_error_stream_body(msg)
        )

    async def upload_document(self, request: web.Request) -> web.Response:
        try:
            post = await request.post()
            file_field = post.get("file")
            if file_field is None or not getattr(file_field, "filename", ""):
                return web.json_response({"message": "No files provided"}, status=200)

            upload_file = os.path.basename(file_field.filename)
            if not upload_file:
                raise RuntimeError("Error parsing uploaded filename.")
            uploads_dir = Path(UPLOAD_FOLDER)
            uploads_dir.mkdir(parents=True, exist_ok=True)
            file_path = str(uploads_dir / upload_file)
            with open(file_path, "wb") as fh:
                fh.write(file_field.file.read())

            loop = asyncio.get_running_loop()
            example = self.example_cls()
            span = request.get("trace_span")
            await loop.run_in_executor(
                None,
                _traced_call(
                    getattr(span, "context", None),
                    lambda: example.ingest_docs(file_path, upload_file),
                ),
            )
            return web.json_response({"message": "File uploaded successfully"}, status=200)
        except Exception as exc:  # noqa: BLE001
            logger.error("Error from POST /documents endpoint: %s", exc)
            return web.json_response({"message": str(exc)}, status=500)

    async def document_search(self, request: web.Request) -> web.Response:
        try:
            data = DocumentSearch.model_validate(await request.json())
        except ValidationError as exc:
            return _validation_error_response(exc)
        except Exception:
            return web.json_response({"detail": "Invalid JSON body"}, status=422)
        try:
            example = self.example_cls()
            if hasattr(example, "document_search") and callable(example.document_search):
                loop = asyncio.get_running_loop()
                span = request.get("trace_span")
                search_result = await loop.run_in_executor(
                    None,
                    _traced_call(
                        getattr(span, "context", None),
                        lambda: example.document_search(data.query, data.top_k),
                    ),
                )
                chunks = [
                    DocumentChunk(
                        content=entry.get("content", ""),
                        filename=entry.get("source", ""),
                        score=entry.get("score", 0.0),
                    )
                    for entry in search_result
                ]
                return web.json_response(
                    DocumentSearchResponse(chunks=chunks).model_dump()
                )
            raise NotImplementedError(
                "Example class has not implemented the document_search method."
            )
        except Exception as exc:  # noqa: BLE001
            logger.error("Error from POST /search endpoint. Error details: %s", exc)
            return web.json_response(
                {"message": "Error occurred while searching documents."}, status=500
            )

    async def get_documents(self, request: web.Request) -> web.Response:
        try:
            example = self.example_cls()
            if hasattr(example, "get_documents") and callable(example.get_documents):
                loop = asyncio.get_running_loop()
                documents = await loop.run_in_executor(None, example.get_documents)
                return web.json_response(
                    DocumentsResponse(documents=documents).model_dump()
                )
            raise NotImplementedError(
                "Example class has not implemented the get_documents method."
            )
        except Exception as exc:  # noqa: BLE001
            logger.error("Error from GET /documents endpoint. Error details: %s", exc)
            return web.json_response(
                {"message": "Error occurred while fetching documents."}, status=500
            )

    async def delete_document(self, request: web.Request) -> web.Response:
        filename = request.query.get("filename", "")
        try:
            example = self.example_cls()
            if hasattr(example, "delete_documents") and callable(example.delete_documents):
                loop = asyncio.get_running_loop()
                status = await loop.run_in_executor(
                    None, lambda: example.delete_documents([filename])
                )
                if not status:
                    raise RuntimeError(f"Error in deleting document {filename}")
                return web.json_response(
                    {"message": f"Document {filename} deleted successfully"}, status=200
                )
            raise NotImplementedError(
                "Example class has not implemented the delete_document method."
            )
        except Exception as exc:  # noqa: BLE001
            logger.error("Error from DELETE /documents endpoint. Error details: %s", exc)
            return web.json_response(
                {"message": f"Error deleting document {filename}"}, status=500
            )


def start_engine_warmup():
    """Background-warm the in-process engine's serving shapes. Delegates
    to engine.llm_engine.start_background_warmup (shared with the /v1
    facade); gated here on the chain actually using the local TPU engine.
    Returns the warmup thread or None."""
    from generativeaiexamples_tpu.config import get_config

    config = get_config()
    if config.llm.model_engine != "tpu" or config.llm.server_url:
        return None
    from generativeaiexamples_tpu.engine.llm_engine import start_background_warmup

    return start_background_warmup(config.engine)


def create_app(example_cls: Optional[Type[BaseExample]] = None) -> web.Application:
    """Build the chain-server aiohttp application."""
    from generativeaiexamples_tpu.config import get_config

    config = get_config()
    # Knob validation fails startup loudly instead of shedding/retrying
    # with nonsense values at request time.
    from generativeaiexamples_tpu.config import validate as config_validate

    config_validate.validate_config(config)
    resilience.validate_config(config)
    from generativeaiexamples_tpu.engine import batcher as batcher_mod

    batcher_mod.validate_config(config)
    flight_recorder.validate_config(config)
    slo_mod.validate_config(config)
    blackbox.validate_config(config)
    dispatch_timeline.validate_config(config)
    flight_recorder.configure_from_config(config)
    slo_mod.configure_from_config(config)
    blackbox.configure_from_config(config)
    dispatch_timeline.configure_from_config(config)
    if config.resilience.faults:
        try:
            n = faults_mod.install(config.resilience.faults)
            logger.warning("Installed %d fault-injection rule(s) from config", n)
        except ValueError as exc:
            raise ValueError(f"invalid resilience.faults spec: {exc}") from exc
    app = ChainServer(example_cls).build_app()

    async def _warmup(app: web.Application) -> None:
        from generativeaiexamples_tpu.engine.embedder import (
            start_retrieval_warmup,
        )

        start_engine_warmup()  # spawns a daemon thread; returns immediately
        start_retrieval_warmup()  # embedder/reranker shape-ladder warmup

    app.on_startup.append(_warmup)
    return app
