"""TP e2e smoke (VERDICT r2 next #8): the chain-server with
tensor_parallelism=8 on the virtual CPU mesh — proof that
server → chain → retrieval → TP engine decode → SSE composes end to end,
not for numbers. The reference's analogue is the NIM container at
INFERENCE_GPU_COUNT=8 behind the same chain-server API
(deploy/compose/docker-compose-nim-ms.yaml:20).
"""
import asyncio
import json

import pytest

from aiohttp.test_utils import TestClient, TestServer


@pytest.fixture()
def tp_server_env(clean_app_env, tmp_path):
    clean_app_env.setenv("APP_LLM_MODELENGINE", "tpu")
    clean_app_env.setenv("APP_EMBEDDINGS_MODELENGINE", "hash")
    clean_app_env.setenv("APP_VECTORSTORE_NAME", "tpu")
    clean_app_env.setenv("APP_VECTORSTORE_PERSISTDIR", str(tmp_path / "vs"))
    clean_app_env.setenv("APP_RETRIEVER_SCORETHRESHOLD", "0")
    clean_app_env.setenv("APP_ENGINE_MODELCONFIGNAME", "debug-8dev")
    clean_app_env.setenv("APP_ENGINE_MAXBATCHSIZE", "2")
    clean_app_env.setenv("APP_ENGINE_MAXSEQLEN", "96")
    clean_app_env.setenv("APP_ENGINE_PREFILLCHUNK", "16")
    clean_app_env.setenv("APP_ENGINE_PAGESIZE", "16")
    clean_app_env.setenv("APP_ENGINE_DECODEBLOCK", "4")
    clean_app_env.setenv("APP_ENGINE_TENSORPARALLELISM", "8")
    clean_app_env.setenv("APP_ENGINE_WARMUPPROMPTLENGTHS", "")
    from generativeaiexamples_tpu.chains import runtime
    from generativeaiexamples_tpu.engine import llm_engine

    runtime.reset_runtime()
    saved = llm_engine._ENGINE
    llm_engine._ENGINE = None
    yield clean_app_env
    if llm_engine._ENGINE is not None:
        llm_engine._ENGINE.shutdown()
    llm_engine._ENGINE = saved
    runtime.reset_runtime()


def test_chain_server_tp8_end_to_end(tp_server_env, tmp_path):
    from generativeaiexamples_tpu.chains.developer_rag import QAChatbot
    from generativeaiexamples_tpu.engine import llm_engine
    from generativeaiexamples_tpu.server.api import create_app

    doc = tmp_path / "notes.txt"
    doc.write_text(
        "The scheduler admits prefill waves in buckets. "
        "Decode slots release eagerly when budgets exhaust."
    )

    async def scenario():
        app = create_app(QAChatbot)
        async with TestClient(TestServer(app)) as client:
            import aiohttp

            form = aiohttp.FormData()
            form.add_field(
                "file", doc.read_bytes(), filename="notes.txt",
                content_type="text/plain",
            )
            resp = await client.post("/documents", data=form)
            assert resp.status == 200

            resp = await client.post(
                "/generate",
                json={
                    "messages": [
                        {"role": "user", "content": "What does the scheduler admit?"}
                    ],
                    "use_knowledge_base": True,
                    "max_tokens": 8,
                    "temperature": 0.1,  # schema lower bound (server.py:83)
                },
            )
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/event-stream")
            return (await resp.read()).decode()

    body = asyncio.run(scenario())
    # SSE frames parse and terminate with the [DONE] finish reason
    frames = [
        json.loads(b.strip()[len("data: "):])
        for b in body.split("\n\n")
        if b.strip()
    ]
    assert frames, "no SSE frames"
    assert frames[-1]["choices"][0]["finish_reason"] == "[DONE]"
    # the engine behind the stream really ran 8-way tensor parallel
    eng = llm_engine._ENGINE
    assert eng is not None
    assert dict(eng._mesh.shape)["model"] == 8
    assert eng.metrics["generated_tokens"] >= 1
