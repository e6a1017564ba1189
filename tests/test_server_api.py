"""Golden tests for the chain-server wire protocol.

Checks the exact SSE framing and JSON shapes of the reference server
(reference: common/server.py:285-342) against our aiohttp implementation.
"""
import asyncio
import json
from typing import Any, Generator, List

from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.chains.base import BaseExample
from generativeaiexamples_tpu.chains.echo import EchoChain
from generativeaiexamples_tpu.retrieval.errors import VectorStoreError
from generativeaiexamples_tpu.server.api import create_app


def run_with_client(example_cls, scenario):
    async def _run():
        app = create_app(example_cls)
        async with TestClient(TestServer(app)) as client:
            return await scenario(client)

    return asyncio.run(_run())


def parse_sse(body: str) -> List[dict]:
    frames = []
    for block in body.split("\n\n"):
        block = block.strip()
        if not block:
            continue
        assert block.startswith("data: "), block
        frames.append(json.loads(block[len("data: "):]))
    return frames


def test_health():
    async def scenario(client):
        resp = await client.get("/health")
        assert resp.status == 200
        return await resp.json()

    body = run_with_client(EchoChain, scenario)
    assert body == {"message": "Service is up."}


def test_engine_server_internal_ready_parity():
    """The engine server answers /internal/ready with the chain-server's
    wire shape (router health pollers probe both replica kinds — genai
    lint's http-contract parity check pins the route, this pins the
    behavior). No engine is ever built by the probe."""
    from generativeaiexamples_tpu.engine.server import create_model_server_app

    async def _run():
        app = create_model_server_app()
        async with TestClient(TestServer(app)) as client:
            resp = await client.get("/internal/ready")
            assert resp.status == 200
            assert await resp.json() == {"ready": True, "wedged": False}

    asyncio.run(_run())


def test_generate_stream_golden():
    async def scenario(client):
        resp = await client.post(
            "/generate",
            json={
                "messages": [{"role": "user", "content": "hello tpu world"}],
                "use_knowledge_base": False,
            },
        )
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        return (await resp.read()).decode()

    body = run_with_client(EchoChain, scenario)
    frames = parse_sse(body)
    # word-by-word chunks then a [DONE] frame
    contents = [f["choices"][0]["message"]["content"] for f in frames[:-1]]
    assert contents == ["hello ", "tpu ", "world "]
    for f in frames[:-1]:
        choice = f["choices"][0]
        assert choice["index"] == 0
        assert choice["message"]["role"] == "assistant"
        assert choice["finish_reason"] == ""
        assert f["id"] == frames[0]["id"]
    assert frames[-1]["choices"][0]["finish_reason"] == "[DONE]"


def test_generate_validation_error():
    async def scenario(client):
        resp = await client.post(
            "/generate",
            json={"messages": [{"role": "wizard", "content": "x"}], "use_knowledge_base": False},
        )
        assert resp.status == 422
        return await resp.json()

    body = run_with_client(EchoChain, scenario)
    assert "detail" in body
    assert body["detail"][0]["loc"][0] == "body"


def test_generate_chain_error_degraded_stream():
    class BoomChain(EchoChain):
        def llm_chain(self, query, chat_history, **kwargs):
            raise RuntimeError("boom")

    async def scenario(client):
        resp = await client.post(
            "/generate",
            json={"messages": [{"role": "user", "content": "x"}], "use_knowledge_base": False},
        )
        assert resp.status == 500
        return (await resp.read()).decode()

    body = run_with_client(BoomChain, scenario)
    frames = parse_sse(body)
    assert len(frames) == 1
    choice = frames[0]["choices"][0]
    assert choice["finish_reason"] == "[DONE]"
    assert "chain server" in choice["message"]["content"]


def test_generate_vector_store_error_message():
    class DownChain(EchoChain):
        def rag_chain(self, query, chat_history, **kwargs):
            raise VectorStoreError("vector db down")

    async def scenario(client):
        resp = await client.post(
            "/generate",
            json={"messages": [{"role": "user", "content": "x"}], "use_knowledge_base": True},
        )
        assert resp.status == 500
        return (await resp.read()).decode()

    body = run_with_client(DownChain, scenario)
    frames = parse_sse(body)
    assert "milvus" in frames[0]["choices"][0]["message"]["content"]


def test_documents_roundtrip(tmp_path):
    class FreshEcho(EchoChain):
        documents = {}

    async def scenario(client):
        import aiohttp

        form = aiohttp.FormData()
        form.add_field("file", b"alpha beta gamma", filename="doc1.txt")
        resp = await client.post("/documents", data=form)
        assert resp.status == 200
        assert (await resp.json())["message"] == "File uploaded successfully"

        resp = await client.get("/documents")
        docs = (await resp.json())["documents"]
        assert docs == ["doc1.txt"]

        resp = await client.post("/search", json={"query": "alpha", "top_k": 4})
        chunks = (await resp.json())["chunks"]
        assert chunks and chunks[0]["filename"] == "doc1.txt"
        assert chunks[0]["score"] == 1.0

        resp = await client.delete("/documents", params={"filename": "doc1.txt"})
        assert resp.status == 200
        resp = await client.get("/documents")
        assert (await resp.json())["documents"] == []
        return True

    assert run_with_client(FreshEcho, scenario)


def test_generate_rag_uses_ingested_context():
    class FreshEcho(EchoChain):
        documents = {"d": "0123456789"}

    async def scenario(client):
        resp = await client.post(
            "/generate",
            json={"messages": [{"role": "user", "content": "q"}], "use_knowledge_base": True},
        )
        return (await resp.read()).decode()

    frames = parse_sse(run_with_client(FreshEcho, scenario))
    assert frames[0]["choices"][0]["message"]["content"] == "context:10 "


def test_engine_warmup_disabled_without_config(clean_app_env):
    """No warmup lengths configured (or non-TPU LLM) -> no warmup thread."""
    from generativeaiexamples_tpu.chains import runtime
    from generativeaiexamples_tpu.server.api import start_engine_warmup

    clean_app_env.setenv("APP_LLM_MODELENGINE", "echo")
    runtime.reset_runtime()
    try:
        assert start_engine_warmup() is None
        clean_app_env.setenv("APP_LLM_MODELENGINE", "tpu")
        clean_app_env.setenv("APP_ENGINE_WARMUPPROMPTLENGTHS", "")
        runtime.reset_runtime()
        assert start_engine_warmup() is None
    finally:
        runtime.reset_runtime()


def test_engine_warmup_precompiles_every_shape(clean_app_env):
    """Configured warmup builds the engine singleton and compiles its
    whole executable set, whatever lengths the switch names (the
    mid-serving cold-compile stall this feature removes, BASELINE.md
    round 2): no admission wave is driven for it."""
    from generativeaiexamples_tpu.chains import runtime
    from generativeaiexamples_tpu.engine import llm_engine
    from generativeaiexamples_tpu.server.api import start_engine_warmup

    clean_app_env.setenv("APP_LLM_MODELENGINE", "tpu")
    clean_app_env.setenv("APP_ENGINE_MODELCONFIGNAME", "debug")
    clean_app_env.setenv("APP_ENGINE_MAXBATCHSIZE", "2")
    clean_app_env.setenv("APP_ENGINE_MAXSEQLEN", "64")
    clean_app_env.setenv("APP_ENGINE_PREFILLCHUNK", "16")
    clean_app_env.setenv("APP_ENGINE_PAGESIZE", "16")
    clean_app_env.setenv("APP_ENGINE_TENSORPARALLELISM", "1")
    clean_app_env.setenv("APP_ENGINE_WARMUPPROMPTLENGTHS", "16,32")
    runtime.reset_runtime()
    saved = llm_engine._ENGINE
    llm_engine._ENGINE = None
    waves_before = llm_engine._M_WAVES.value
    try:
        thread = start_engine_warmup()
        assert thread is not None
        thread.join(timeout=300)
        assert not thread.is_alive()
        eng = llm_engine._ENGINE
        assert eng is not None
        snap = eng._compile_watch.snapshot()
        assert snap["compile_warmup_done"] == 1
        assert snap["compile_executables_extend"] == len(eng.shapes.extend_signatures())
        assert llm_engine._M_WAVES.value == waves_before  # (a counter of the process, not of the engine)
    finally:
        if llm_engine._ENGINE is not None:
            llm_engine._ENGINE.shutdown()
        llm_engine._ENGINE = saved
        runtime.reset_runtime()


def test_warmup_tolerates_malformed_config(clean_app_env):
    """A typo'd APP_ENGINE_WARMUPPROMPTLENGTHS must not prevent startup."""
    from generativeaiexamples_tpu.chains import runtime
    from generativeaiexamples_tpu.engine.llm_engine import start_background_warmup

    clean_app_env.setenv("APP_ENGINE_WARMUPPROMPTLENGTHS", "2048,abc")
    runtime.reset_runtime()
    try:
        assert start_background_warmup() is None
        # semicolons are tolerated as separators
        clean_app_env.setenv("APP_ENGINE_WARMUPPROMPTLENGTHS", " , ")
        runtime.reset_runtime()
        assert start_background_warmup() is None
    finally:
        runtime.reset_runtime()


def test_a_cancelled_stream_leaves_no_worker_and_no_producer_behind():
    """A handler cancelled before its stream's first item (server
    shutdown) holds nothing: no default-executor worker ever waited for
    the stream (with one worker, the next executor job runs at once),
    and the producer, once its generator moves, finds the stop flag
    instead of a queue to wait on, closes the generator and ends."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from generativeaiexamples_tpu.server.api import _aiter_threaded

    never = threading.Event()
    closed = threading.Event()

    def silent():
        try:
            never.wait(30)
            yield "late"
            yield "later"
        finally:
            closed.set()

    async def _run():
        loop.set_default_executor(ThreadPoolExecutor(max_workers=1))

        async def consume():
            async for _ in _aiter_threaded(silent(), None, None):
                pass

        task = asyncio.ensure_future(consume())
        await asyncio.sleep(0.2)  # the handler now waits for its first item
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        free = await asyncio.wait_for(loop.run_in_executor(None, lambda: "free"), timeout=5)
        never.set()
        return free, await loop.run_in_executor(None, closed.wait, 5)

    loop = asyncio.new_event_loop()  # (asyncio.run would join the executor, and hang where this fails)
    try:
        assert loop.run_until_complete(_run()) == ("free", True)
    finally:
        never.set()
        loop.close()
