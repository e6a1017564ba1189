"""OpenAI-compatible model server: the drop-in for the NIM containers.

Serves ``/v1/chat/completions`` (SSE streaming and non-streaming),
``/v1/completions``, ``/v1/embeddings``, ``/v1/models`` and
``/v1/health/ready`` — the API surface the reference consumes from its
NIM LLM and NeMo-Retriever embedding microservices (reference:
deploy/compose/docker-compose-nim-ms.yaml:2-56, healthcheck
``/v1/health/ready`` at :45-50; ChatNVIDIA base_url semantics at
common/utils.py:276). A chain-server configured with
``APP_LLM_SERVERURL``/``APP_EMBEDDINGS_SERVERURL`` pointing here works
unchanged — but colocated deployments skip HTTP entirely via the
in-process backends.

Run: ``python -m generativeaiexamples_tpu.engine.server --port 8000``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import time
import uuid
from typing import Any, Dict, Optional

from aiohttp import web

from generativeaiexamples_tpu.utils import get_logger
from generativeaiexamples_tpu.utils.resilience import EngineOverloaded

logger = get_logger(__name__)


def _now() -> int:
    return int(time.time())


def _overloaded_response(exc: EngineOverloaded) -> web.Response:
    """429 + Retry-After for an admission-queue rejection (OpenAI wire
    error shape). Carries the same X-GenAI-Queue-Depth context as the
    chain-server's sheds for the routing tier's bounded-load spill."""
    headers = {"Retry-After": str(max(1, int(exc.retry_after)))}
    from generativeaiexamples_tpu.engine.llm_engine import live_queue_depth

    depth = live_queue_depth()
    if depth is not None:
        headers["X-GenAI-Queue-Depth"] = str(depth)
    return web.json_response(
        {"error": {"message": str(exc), "type": "overloaded_error"}},
        status=429,
        headers=headers,
    )


class ModelServer:
    def __init__(self, engine=None, embedder=None, model_name: str = "", embed_model_name: str = ""):
        self._engine = engine
        self._embedder = embedder
        self._model_name = model_name or "tpu-llama"
        self._embed_model_name = embed_model_name or "tpu-arctic-embed"

    # lazily constructed so /v1/models and health work before weights load
    @property
    def engine(self):
        if self._engine is None:
            from generativeaiexamples_tpu.engine.llm_engine import get_engine

            self._engine = get_engine()
        return self._engine

    @property
    def embedder(self):
        if self._embedder is None:
            from generativeaiexamples_tpu.engine.embedder import create_embedder

            self._embedder = create_embedder()
        return self._embedder

    def build_app(self) -> web.Application:
        from generativeaiexamples_tpu.server.observability import (
            add_observability_routes,
            internal_metrics_handler,
            metrics_middleware,
        )

        app = web.Application(
            middlewares=[metrics_middleware], client_max_size=64 * 1024 * 1024
        )
        app.router.add_get("/v1/health/ready", self.health_ready)
        app.router.add_get("/v1/models", self.list_models)
        app.router.add_post("/v1/chat/completions", self.chat_completions)
        app.router.add_post("/v1/completions", self.completions)
        app.router.add_post("/v1/embeddings", self.embeddings)
        # Observability (same registry as the chain-server): /metrics
        # exposition + JSON view + on-demand profiler capture. None of
        # these build the engine — scrapes stay cheap before first load.
        add_observability_routes(app)
        app.router.add_get("/internal/metrics", internal_metrics_handler)
        # Replica-kind parity with the chain-server (genai_lint
        # http-contract): the router's health poller probes
        # /internal/ready on every replica it fronts — without this
        # route each poll of an engine replica paid a 404 plus the
        # /v1/health/ready fallback round-trip, and lost the
        # warmup-readiness half of the probe.
        app.router.add_get("/internal/ready", self.readiness_check)
        # Preemption / drain lifecycle, same handler objects as the
        # chain-server (server/api.py; docs/resilience.md): the
        # router's handover path drains, lists, fetches, and restores
        # live-request snapshots against whichever replica kind it
        # fronts. Imported here (not at module top) so the facade's
        # import cost stays light until an app is actually built.
        from generativeaiexamples_tpu.server.api import (
            engine_drain_handler,
            get_snapshot_handler,
            list_snapshots_handler,
            restore_snapshot_handler,
        )

        app.router.add_post("/internal/drain", engine_drain_handler)
        app.router.add_get("/internal/snapshots", list_snapshots_handler)
        app.router.add_get(
            "/internal/snapshots/{snapshot_id}", get_snapshot_handler
        )
        app.router.add_post("/internal/restore", restore_snapshot_handler)
        return app

    async def readiness_check(self, request: web.Request) -> web.Response:
        """Same wire shape as the chain-server's /internal/ready:
        ready covers warmup completion, wedged rides alongside. Reads
        module state only — a probe must never BUILD the engine."""
        from generativeaiexamples_tpu.engine.llm_engine import (
            engine_wedged,
            warmup_complete,
        )

        wedged = engine_wedged()
        ready = warmup_complete() and not wedged
        return web.json_response(
            {"ready": ready, "wedged": wedged}, status=200 if ready else 503
        )

    async def health_ready(self, request: web.Request) -> web.Response:
        from generativeaiexamples_tpu.engine.llm_engine import engine_wedged

        if engine_wedged():
            return web.json_response(
                {"object": "health", "message": "Engine wedged."}, status=503
            )
        return web.json_response({"object": "health", "message": "Service is ready."})

    async def list_models(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {"id": self._model_name, "object": "model", "created": _now(), "owned_by": "tpu"},
                    {"id": self._embed_model_name, "object": "model", "created": _now(), "owned_by": "tpu"},
                ],
            }
        )

    # ------------------------------------------------------------------ //
    def _sampling(self, body: Dict[str, Any]):
        from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

        stop = body.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        # spec_decode: non-standard per-request override for prompt-
        # lookup speculative decoding (docs/spec_decode.md); absent
        # means "follow the engine config", False opts the request out.
        # Strings parse by value ("false" must opt OUT — bool("false")
        # would silently invert clients that serialize booleans as
        # strings).
        spec = body.get("spec_decode")
        if isinstance(spec, str):
            spec = spec.strip().lower() in ("1", "true", "on", "yes")
        elif spec is not None:
            spec = bool(spec)
        return SamplingParams(
            temperature=float(body.get("temperature", 0.2)),
            top_p=float(body.get("top_p", 0.7)),
            max_tokens=int(body.get("max_tokens", 1024)),
            stop=tuple(stop),
            seed=int(body.get("seed", 0) or 0),
            spec_decode=spec,
            ignore_eos=body.get("ignore_eos") is True,
        )

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
            messages = [(m["role"], m["content"]) for m in body["messages"]]
        except Exception:
            return web.json_response({"error": "invalid request body"}, status=400)
        params = self._sampling(body)
        stream = bool(body.get("stream", False))
        rid = f"chatcmpl-{uuid.uuid4().hex[:24]}"

        loop = asyncio.get_running_loop()
        try:
            # submit happens eagerly inside chat/stream_text: the
            # admission-queue cap raises here, while 429 is still possible
            gen = await loop.run_in_executor(
                None, lambda: self.engine.chat(messages, params)
            )
        except EngineOverloaded as exc:
            return _overloaded_response(exc)

        if not stream:
            text = await loop.run_in_executor(None, lambda: "".join(gen))
            return web.json_response(self._chat_body(rid, text, "stop"))

        resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        from generativeaiexamples_tpu.engine.tokenizer import pieces_of
        from generativeaiexamples_tpu.server.api import _aiter_threaded

        first = True
        async for chunk in _aiter_threaded(gen):
            # one frame a token, a hand-off's frames in one write
            # (docs/streaming.md)
            frames = []
            for piece in pieces_of(chunk):
                delta: Dict[str, Any] = {"content": piece}
                if first:
                    delta["role"] = "assistant"
                    first = False
                frame = {
                    "id": rid,
                    "object": "chat.completion.chunk",
                    "created": _now(),
                    "model": self._model_name,
                    "choices": [{"index": 0, "delta": delta, "finish_reason": None}],
                }
                frames.append(f"data: {json.dumps(frame)}\n\n")
            await resp.write("".join(frames).encode())
        final = {
            "id": rid,
            "object": "chat.completion.chunk",
            "created": _now(),
            "model": self._model_name,
            "choices": [{"index": 0, "delta": {}, "finish_reason": "stop"}],
        }
        await resp.write(f"data: {json.dumps(final)}\n\n".encode())
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    def _chat_body(self, rid: str, text: str, finish: str) -> Dict[str, Any]:
        return {
            "id": rid,
            "object": "chat.completion",
            "created": _now(),
            "model": self._model_name,
            "choices": [
                {
                    "index": 0,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": finish,
                }
            ],
            "usage": {},
        }

    async def completions(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
            prompt = body["prompt"]
            if isinstance(prompt, list):
                prompt = prompt[0]
        except Exception:
            return web.json_response({"error": "invalid request body"}, status=400)
        params = self._sampling(body)
        loop = asyncio.get_running_loop()

        def run():
            ids = self.engine.tokenizer.encode(prompt, add_bos=True)
            return "".join(self.engine.stream_text(ids, params))

        try:
            text = await loop.run_in_executor(None, run)
        except EngineOverloaded as exc:
            return _overloaded_response(exc)
        return web.json_response(
            {
                "id": f"cmpl-{uuid.uuid4().hex[:24]}",
                "object": "text_completion",
                "created": _now(),
                "model": self._model_name,
                "choices": [{"index": 0, "text": text, "finish_reason": "stop"}],
            }
        )

    async def embeddings(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
            inputs = body["input"]
            if isinstance(inputs, str):
                inputs = [inputs]
        except Exception:
            return web.json_response({"error": "invalid request body"}, status=400)
        loop = asyncio.get_running_loop()
        vectors = await loop.run_in_executor(None, lambda: self.embedder.embed_documents(inputs))
        return web.json_response(
            {
                "object": "list",
                "model": body.get("model", self._embed_model_name),
                "data": [
                    {"object": "embedding", "index": i, "embedding": vec.tolist()}
                    for i, vec in enumerate(vectors)
                ],
                "usage": {},
            }
        )


def create_model_server_app(engine=None, embedder=None) -> web.Application:
    from generativeaiexamples_tpu.config import get_config
    from generativeaiexamples_tpu.engine import dispatch_timeline
    from generativeaiexamples_tpu.utils import blackbox
    from generativeaiexamples_tpu.utils import flight_recorder
    from generativeaiexamples_tpu.utils import slo as slo_mod

    config = get_config()
    flight_recorder.validate_config(config)
    slo_mod.validate_config(config)
    blackbox.validate_config(config)
    dispatch_timeline.validate_config(config)
    flight_recorder.configure_from_config(config)
    slo_mod.configure_from_config(config)
    blackbox.configure_from_config(config)
    dispatch_timeline.configure_from_config(config)
    app = ModelServer(engine, embedder).build_app()
    if engine is None:  # serving the singleton: warm its configured buckets

        async def _warmup(app: web.Application) -> None:
            from generativeaiexamples_tpu.engine.embedder import (
                start_retrieval_warmup,
            )
            from generativeaiexamples_tpu.engine.llm_engine import (
                start_background_warmup,
            )

            start_background_warmup()
            start_retrieval_warmup()  # embedder/reranker shape ladders

        app.on_startup.append(_warmup)
    return app


def main() -> None:
    parser = argparse.ArgumentParser(description="TPU OpenAI-compatible model server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    args = parser.parse_args()
    from generativeaiexamples_tpu.utils import jax_env

    jax_env.bootstrap()
    web.run_app(create_model_server_app(), host=args.host, port=args.port)


if __name__ == "__main__":
    main()
