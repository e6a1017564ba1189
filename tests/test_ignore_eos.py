"""``ignore_eos``: a request field that makes a stop id an ORDINARY token,
so that an answer ends at ``max_tokens`` (or at its slot's capacity).
Needed wherever the vocabulary is a few hundred ids (a byte-level model:
two stop ids are 1 in 160 of what a seeded model samples). From the
schema through the chain server's settings and the backends to
``SamplingParams`` and the reader's stop test; the default path is held
by the tests that were there (tests/test_stream_handoff.py
``test_emit_counts_per_token_and_ends_inside_a_block``)."""
import asyncio
import json

import numpy as np
import pydantic
import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.chains import runtime
from generativeaiexamples_tpu.chains.base import BaseExample
from generativeaiexamples_tpu.engine import llm_engine
from generativeaiexamples_tpu.engine.llm_backend import TPULLMBackend
from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams
from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer, IncrementalDecoder, load_tokenizer
from generativeaiexamples_tpu.server import api
from generativeaiexamples_tpu.server.schemas import Prompt
from tests.test_stream_handoff import _CountingQueue, _reader_stub


def _request(max_tokens, ignore_eos, **kw):
    return llm_engine._Request(rid=123457, prompt_ids=[1], out_queue=_CountingQueue(),
                               params=SamplingParams(max_tokens=max_tokens, ignore_eos=ignore_eos), **kw)


@pytest.mark.parametrize("case", ["max_tokens", "capacity", "stop_id_last"])
def test_with_ignore_eos_a_stop_id_is_a_token_and_the_request_ends_at_its_budget(case, monkeypatch):
    finished = []
    monkeypatch.setattr(llm_engine.flight_recorder, "finish_rid",
                        lambda rid, outcome="finish", **attrs: finished.append(attrs))
    stub = _reader_stub(stop_ids=(99,), max_seq_len=16 if case == "capacity" else 4096)
    req = _request(5 if case != "capacity" else 64, True, position=10)
    tokens = np.array([1, 2, 99, 4, 99, 6, 7, 8] if case == "stop_id_last" else [1, 99, 3, 4, 5, 6, 7, 8])
    before = llm_engine._M_TOKENS.value
    LLMEngine._emit(stub, req, tokens)
    want = tokens[:5].tolist()
    # the stop id is QUEUED like any token; the block and its end in one put
    assert 99 in want and req.out_queue.puts == [want + [llm_engine._END]]
    assert req.finished and req.generated == 5 and req.queued == 5 and req.position == 15
    assert llm_engine._M_TOKENS.value - before == 5
    # what a client calls ``length``: the request ran to its budget (or its slot's end), and every id was delivered
    assert finished == [{"generated": 5, "stop": "capacity" if case == "capacity" else "max_tokens"}]


def test_without_the_field_the_same_tokens_end_at_the_stop_id(monkeypatch):
    finished = []
    monkeypatch.setattr(llm_engine.flight_recorder, "finish_rid",
                        lambda rid, outcome="finish", **attrs: finished.append(attrs))
    req = _request(5, False, position=10)
    LLMEngine._emit(_reader_stub(stop_ids=(99,)), req, np.array([1, 99, 3, 4, 5, 6, 7, 8]))
    assert req.out_queue.puts == [[1, llm_engine._END]] and finished == [{"generated": 1, "stop": "eos"}]
    assert SamplingParams().ignore_eos is False and not llm_engine._NO_STOP_IDS


@pytest.mark.parametrize("value", ["true", "yes", 1, 0, None, [True]], ids=repr)
def test_the_schema_refuses_what_is_not_a_boolean(value):
    body = {"messages": [{"role": "user", "content": "q"}], "use_knowledge_base": False}
    assert Prompt(**body).ignore_eos is False and Prompt(**body, ignore_eos=True).ignore_eos is True
    with pytest.raises(pydantic.ValidationError):
        Prompt(**body, ignore_eos=value)


class _Capture(BaseExample):
    seen = []

    def llm_chain(self, query, chat_history, **kwargs):
        type(self).seen.append((kwargs, runtime.llm_settings(kwargs)))
        return iter(["ok"])

    def rag_chain(self, query, chat_history, **kwargs):
        return self.llm_chain(query, chat_history, **kwargs)

    def ingest_docs(self, data_dir, filename):
        pass


@pytest.mark.parametrize("sent", [None, True, False, "true"])
def test_the_chain_server_hands_the_field_to_the_chain_and_only_a_set_one_to_the_backend(sent):
    body = {"messages": [{"role": "user", "content": "q"}], "use_knowledge_base": False}
    if sent is not None:
        body["ignore_eos"] = sent

    async def _run():
        async with TestClient(TestServer(api.create_app(_Capture))) as client:
            resp = await client.post("/generate", json=body)
            return resp.status, (await resp.read()).decode()

    _Capture.seen.clear()
    status, text = asyncio.run(_run())
    if sent == "true":
        assert status == 422 and "ignore_eos" in text and not _Capture.seen
        return
    assert status == 200
    (kwargs, settings), = _Capture.seen
    assert kwargs["ignore_eos"] is bool(sent)
    # a backend that never heard of the field is called as before unless a request SET it
    assert settings.get("ignore_eos", "absent") == (True if sent else "absent")
    assert json.loads(text.split("\n\n")[0][len("data: "):])["choices"][0]["message"]["content"] == "ok"


def test_the_backends_build_sampling_params_with_it():
    class Engine:
        tokenizer = ByteTokenizer()

        def stream_text(self, ids, params, timeout=None):
            self.params = params
            return iter(())

    eng = Engine()
    TPULLMBackend(engine=eng).stream_chat([("user", "q")], max_tokens=7, ignore_eos=True)
    assert eng.params.ignore_eos is True and eng.params.max_tokens == 7
    TPULLMBackend(engine=eng).stream_chat([("user", "q")], **runtime.llm_settings({"max_tokens": 7, "ignore_eos": False}))
    assert eng.params.ignore_eos is False
    # the OpenAI-compatible facade takes the boolean and nothing that looks like one
    from generativeaiexamples_tpu.engine.server import ModelServer

    sampling = lambda body: ModelServer._sampling(None, body)  # noqa: E731
    assert sampling({"ignore_eos": True}).ignore_eos is True
    assert sampling({}).ignore_eos is False and sampling({"ignore_eos": "true"}).ignore_eos is False


def test_a_stop_id_of_the_harness_vocabulary_is_a_visible_frame(tmp_path):
    """With the field set a stop id reaches the stream: in the benchmark's
    320-id character vocabulary it decodes to text a client counts."""
    from perfbench.tokenizer_file import write_tokenizer

    path = tmp_path / "tokenizer.json"
    write_tokenizer(str(path), 320)
    tok = load_tokenizer(str(path))
    frames = api._chunk_frames("r")
    dec = IncrementalDecoder(tok)
    for stop in tok.stop_ids():
        assert 0 <= stop < 320
        frame = json.loads(frames(dec.push(stop))[len(b"data: "):])
        assert frame["choices"][0]["message"]["content"], (stop, tok.decode([stop]))
