"""The token's way from the engine's reader to the SSE socket
(docs/streaming.md): block hand-off, constant-cost detokenize, one
frame per token built cheaply, no executor worker per stream."""
import asyncio
import json
import queue
import random
import threading
import time
import types

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.chains.base import BaseExample
from generativeaiexamples_tpu.chains.runtime import DegradedWarning
from generativeaiexamples_tpu.engine import llm_engine
from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams
from generativeaiexamples_tpu.engine.tokenizer import (
    ByteTokenizer,
    HFTokenizer,
    IncrementalDecoder,
    TokenBlock,
)
from generativeaiexamples_tpu.server import api
from generativeaiexamples_tpu.server.api import _aiter_threaded, _chunk_frames

TEXT = "naïve café — ✓ 漢字 😀 don't stop. it's <b>fine</b> & done, isn't it?\n"


# --------------------------------------------------------------------------- #
# tokenizers the repo ships


def _byte_tokenizer(tmp_path):
    return ByteTokenizer()


def _bpe_tokenizer(tmp_path):
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers

    alphabet = sorted(pre_tokenizers.ByteLevel.alphabet())
    vocab = {ch: i for i, ch in enumerate(alphabet)}
    merges = []
    for a, b in (("Ġ", "d"), ("o", "n"), ("Ġd", "on"), ("i", "t"), ("Ã", "©")):
        merges.append((a, b))
        vocab[a + b] = len(vocab)
    t = Tokenizer(models.BPE(vocab=vocab, merges=merges))
    t.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    t.decoder = decoders.ByteLevel()
    t.add_special_tokens(["<|begin_of_text|>", "<|eot_id|>"])
    path = tmp_path / "bpe.json"
    t.save(str(path))
    return HFTokenizer(str(path))


def _wordpiece_tokenizer(tmp_path):
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers

    words = ["[UNK]", "[CLS]", "[SEP]", "do", "n", "'", "t", "stop", ".", ",", "it", "s", "is",
             "fine", "##ing", "##s", "##n", "?", "!", "don", "and", "done", "a", "##'", "##t"]
    t = Tokenizer(models.WordPiece(vocab={w: i for i, w in enumerate(words)}, unk_token="[UNK]"))
    t.pre_tokenizer = pre_tokenizers.BertPreTokenizer()
    t.decoder = decoders.WordPiece(prefix="##", cleanup=True)
    t.add_special_tokens(["[UNK]", "[CLS]", "[SEP]"])
    path = tmp_path / "wordpiece.json"
    t.save(str(path))
    return HFTokenizer(str(path))


def _harness_tokenizer(tmp_path):
    from perfbench.tokenizer_file import write_tokenizer

    path = tmp_path / "chars.json"
    write_tokenizer(str(path), 2048)
    return HFTokenizer(str(path))


TOKENIZERS = {
    "byte": _byte_tokenizer, "hf_bpe": _bpe_tokenizer,
    "hf_wordpiece": _wordpiece_tokenizer, "harness_chars": _harness_tokenizer,
}


def whole_answer_deltas(tokenizer, ids, prior=()):
    """The stream's text as the engine made it before: decode the WHOLE
    answer for every token, deliver what is new. The oracle."""
    cur, emitted, out = list(prior), "", []
    for tok in ids:
        cur.append(tok)
        text = tokenizer.decode(cur)
        delta = "" if text.endswith("�") else text[len(emitted):]
        emitted += delta
        out.append(delta)
    return out, tokenizer.decode(cur)[len(emitted):]


@pytest.mark.parametrize("kind", sorted(TOKENIZERS))
def test_incremental_detokenize_equals_whole_answer_decode(kind, tmp_path):
    tok = TOKENIZERS[kind](tmp_path)
    rng = random.Random(30)
    streams = [tok.encode(TEXT * 2)]  # multi-byte characters split over tokens
    for n in (1, 7, 64, 300):
        streams.append([rng.randrange(tok.vocab_size) for _ in range(n)])
    streams.append(tok.encode("é✓😀")[:-1])  # ends inside a character: the flush
    for ids in streams:
        for prior in ((), tuple(tok.encode("before ✓ "))):
            want, want_tail = whole_answer_deltas(tok, ids, prior)
            dec = IncrementalDecoder(tok, prior)
            got = [dec.push(t) for t in ids]
            assert got == want, (kind, ids[:16], prior)
            assert dec.flush() == want_tail
            assert "".join(got) + want_tail == tok.decode(list(prior) + list(ids))


def test_incremental_detokenize_decodes_a_window_not_the_answer():
    """Constant cost: the 2000th token decodes a few ids, not 2000."""
    seen = []

    class Counting(ByteTokenizer):
        def decode(self, ids):
            seen.append(len(ids))
            return super().decode(ids)

    dec = IncrementalDecoder(Counting())
    for t in (TEXT * 40).encode()[:2000]:
        dec.push(t)
    assert max(seen) <= 8 and len(seen) == 4000


# --------------------------------------------------------------------------- #
# _stream_from: blocks, stop strings, prior_ids


def _stub_engine(tokenizer):
    stub = LLMEngine.__new__(LLMEngine)
    stub.engine_config = types.SimpleNamespace(stream_timeout_s=5.0)
    stub.tokenizer = tokenizer
    stub.abort = lambda req: None
    stub._streams = {}
    return stub


def _scripted(blocks, error=None):
    req = types.SimpleNamespace(
        out_queue=llm_engine._TokenQueue(), error=error, queued=0, written=None)
    for block in blocks:
        req.out_queue.put_many(block)
    return req


def test_stream_from_yields_one_block_per_wakeup_with_per_token_pieces():
    tok = ByteTokenizer()
    ids = list("héllo wörld".encode())
    stub = _stub_engine(tok)
    req = _scripted([ids[:5]])
    gen = stub._stream_from(req, SamplingParams(), None)
    first = next(gen)
    assert isinstance(first, TokenBlock) and isinstance(first, str)
    assert first == "hél" + "l" and first.pieces == ["h", "é", "l", "l"]
    assert first.n_tokens == 5  # the two bytes of é are two ids, one piece
    req.out_queue.put_many(ids[5:] + [llm_engine._END])
    rest = list(gen)
    assert len(rest) == 1 and rest[0].pieces == list("o wörld")
    assert "".join([first] + rest) == "héllo wörld"
    first.written()
    rest[0].written()
    assert req.written == len(ids)
    assert not stub._streams  # the stream left the backlog's registry


def whole_answer_stream(tokenizer, ids, stops):
    """The deltas the stream yielded before (one token a wake-up, the
    stop strings searched in the whole answer). The oracle."""
    cur, emitted, out = [], "", []
    for tok in ids:
        cur.append(tok)
        text = tokenizer.decode(cur)
        if text.endswith("�") or len(text) <= len(emitted):
            continue
        candidate = emitted + text[len(emitted):]
        found = [i for i in (candidate.find(s) for s in stops) if i != -1]
        if found:
            if min(found) > len(emitted):
                out.append(candidate[len(emitted):min(found)])
            return out
        out.append(candidate[len(emitted):])
        emitted = candidate
    return out


@pytest.mark.parametrize("split", range(1, 14))
def test_stop_string_that_straddles_a_handoff(split):
    """Whatever hand-off boundary the stop string falls across, the
    stream's deltas are those of one token a wake-up: a stop string's
    first characters are delivered until it is whole, as before."""
    tok = ByteTokenizer()
    ids = list("one two STOP three".encode())
    stops = ("zzzzzzzz", "STOP", "o S")
    for stop in (stops[:2], stops):
        want = whole_answer_stream(tok, ids, stop)
        assert "".join(want) == ("one two STO" if len(stop) == 2 else "one two ")
        req = _scripted([ids[:split], ids[split:] + [llm_engine._END]])
        blocks = list(_stub_engine(tok)._stream_from(req, SamplingParams(stop=stop), None))
        assert [p for b in blocks for p in b.pieces] == want
        assert "".join(blocks) == "".join(want)


def test_stop_search_looks_at_a_tail_not_the_answer():
    tok = ByteTokenizer()
    stub = _stub_engine(tok)
    text = "ab" * 500 + "aXYb" + "c" * 10
    req = _scripted([[b] for b in text.encode()] + [[llm_engine._END]])
    out = "".join(stub._stream_from(req, SamplingParams(stop=("XY",)), None))
    assert out == "ab" * 500 + "aX"  # the X went out before the Y made the stop whole


def test_restored_stream_first_delta_carries_the_spooled_prefix():
    tok = ByteTokenizer()
    prior = list("spooled ✓ pre".encode())
    ids = list("fix and more".encode())
    stub = _stub_engine(tok)
    req = _scripted([ids[:4], ids[4:] + [llm_engine._END]])
    blocks = list(stub._stream_from(req, SamplingParams(), None, prior_ids=prior))
    assert blocks[0].pieces[0] == "spooled ✓ pref"
    assert "".join(blocks) == "spooled ✓ prefix and more"
    assert sum(b.n_tokens for b in blocks) == len(ids)


def test_stream_from_delivers_the_block_before_it_raises_the_engines_error():
    tok = ByteTokenizer()
    stub = _stub_engine(tok)
    req = _scripted([list(b"ab") + [llm_engine._END]], error=ValueError("boom"))
    gen = stub._stream_from(req, SamplingParams(), None)
    assert next(gen) == "ab"
    with pytest.raises(RuntimeError):
        next(gen)


def test_a_stream_that_ends_inside_a_character_still_delivers_it():
    tok = ByteTokenizer()
    stub = _stub_engine(tok)
    req = _scripted([list("ok ✓".encode())[:-1] + [llm_engine._END]])
    assert "".join(stub._stream_from(req, SamplingParams(), None)) == "ok �"


def test_handoff_counters_count_items_and_their_tokens():
    tok = ByteTokenizer()
    stub = _stub_engine(tok)
    h0, t0 = llm_engine._M_HANDOFFS.value, llm_engine._M_HANDOFF_TOKENS.value
    req = _scripted([list(b"12345678"), list(b"abcdefgh") + [llm_engine._END]])
    gen = stub._stream_from(req, SamplingParams(), None)
    # both blocks were queued before the stream woke: ONE hand-off of 16
    assert [b.n_tokens for b in gen] == [16]
    assert llm_engine._M_HANDOFFS.value - h0 == 1
    assert llm_engine._M_HANDOFF_TOKENS.value - t0 == 16


# --------------------------------------------------------------------------- #
# the reader's side: one put per request per slab


def _reader_stub(stop_ids=(), max_seq_len=4096):
    return types.SimpleNamespace(
        _stop_ids=set(stop_ids), max_seq_len=max_seq_len,
        _release_q=queue.Queue(), _lock=threading.Condition(), _dtl=None,
    )


def _request(**kw):
    return llm_engine._Request(
        rid=123456, prompt_ids=[1], params=SamplingParams(max_tokens=kw.pop("max_tokens", 64)), **kw)


class _CountingQueue(llm_engine._TokenQueue):
    def __init__(self):
        super().__init__()
        self.puts = []

    def put_many(self, items, t_put=0.0):
        self.puts.append(list(items))
        super().put_many(items, t_put)


def test_emit_slab_hands_each_request_its_tokens_in_one_put():
    stub = _reader_stub()
    stub._emit = lambda *args: LLMEngine._emit(stub, *args)
    stub._hand_off = lambda *args: LLMEngine._hand_off(stub, *args)
    reqs = [_request(out_queue=_CountingQueue(), position=10) for _ in range(3)]
    reqs[1].finished = True  # overran past its stop in an earlier slab
    slab = np.arange(8 * 4).reshape(8, 4)  # [block, batch]
    LLMEngine._emit_slab(stub, slab, [(0, reqs[0]), (2, reqs[1]), (3, reqs[2])])
    assert reqs[0].out_queue.puts == [[0, 4, 8, 12, 16, 20, 24, 28]]
    assert reqs[1].out_queue.puts == []
    assert reqs[2].out_queue.puts == [[3, 7, 11, 15, 19, 23, 27, 31]]
    assert reqs[0].position == 18 and reqs[0].generated == 8 and reqs[0].queued == 8
    assert reqs[0].emitted == [0, 4, 8, 12, 16, 20, 24, 28]
    assert reqs[0].out_queue.take_all(0) == reqs[0].out_queue.puts[0]


@pytest.mark.parametrize("case", ["stop_id", "max_tokens", "capacity", "cancelled"])
def test_emit_counts_per_token_and_ends_inside_a_block(case):
    stub = _reader_stub(stop_ids=(99,), max_seq_len=20 if case == "capacity" else 4096)
    req = _request(out_queue=_CountingQueue(), position=12 if case == "capacity" else 10,
                   max_tokens=5 if case == "max_tokens" else 64)
    req.cancelled = case == "cancelled"
    tokens = np.array([1, 2, 3, 99, 4, 5, 6, 7] if case == "stop_id" else [1, 2, 3, 4, 5, 6, 7, 8])
    before = llm_engine._M_TOKENS.value
    LLMEngine._emit(stub, req, tokens)
    want = {"stop_id": [1, 2, 3], "max_tokens": [1, 2, 3, 4, 5],
            "capacity": list(range(1, 8)), "cancelled": [1]}[case]
    counted = len(want) + (case == "stop_id")
    # the block and its end in ONE put; the stop id is counted, never queued
    assert req.out_queue.puts == [want + [llm_engine._END]]
    assert req.finished and req.generated == counted
    assert req.position == (12 if case == "capacity" else 10) + counted
    assert req.emitted == tokens[:counted].tolist()
    assert req.queued == len(want)
    assert llm_engine._M_TOKENS.value - before == counted


def test_token_queue_get_hands_out_one_item_and_times_out_like_a_queue():
    q = llm_engine._TokenQueue()
    q.put_many([1, 2])
    q.put(None)
    assert q.get(timeout=1) == 1
    assert q.take_all(timeout=1) == [2, None]
    with pytest.raises(queue.Empty):
        q.get(timeout=0.01)
    with pytest.raises(queue.Empty):
        q.take_all(timeout=0.01)


def test_iter_ids_reads_the_same_blocks_token_by_token():
    stub = LLMEngine.__new__(LLMEngine)
    stub.engine_config = types.SimpleNamespace(stream_timeout_s=5.0)
    stub.abort = lambda req: None
    req = _scripted([[5, 6, 7], [8, llm_engine._END]])
    stub.submit = lambda ids, params: req
    assert list(stub.iter_ids([1])) == [5, 6, 7, 8]


# --------------------------------------------------------------------------- #
# server: frames


AWKWARD = [
    "", " ", "x", "plain text", "a\nb\tc", "quote \" and \\ backslash", "é✓漢😀", "  ",
    "<b>bold</b>", "a & b", "1 < 2 > 0", "&amp;", "<script>alert(1)</script>", "\r\n", "\x00", "\x07bell",
    "\x0c", "\x1f", "\x7f", "\x85", "�", "￾", "'single'", "/slash", "w00042", " w00042",
    "".join(chr(c) for c in range(0x20, 0x3000)),
]


def _schema_frame(rid, text):
    """The frame as the schema itself writes it (the parent's ``_chunk_frame``)."""
    from generativeaiexamples_tpu.server.schemas import (
        ChainResponse, ChainResponseChoices, Message)

    return ("data: " + ChainResponse(id=rid, choices=[
        ChainResponseChoices(index=0, message=Message(role="assistant", content=text), finish_reason=""),
    ]).model_dump_json(exclude_none=True) + "\n\n").encode()


@pytest.mark.parametrize("i", range(len(AWKWARD)))
def test_preformatted_frame_is_byte_identical_to_chunk_frame(i):
    rid = "0b7e1f0a-1111-4222-8333-444455556666"
    text = AWKWARD[i]
    if text not in ("\x00",):  # erased whole and still nothing once escaped: an empty frame, as the schema's
        assert _chunk_frames(rid)(text) == _schema_frame(rid, text)
    # and in the middle of other text, and as a block's pieces
    block = TokenBlock(["a" + text, text + "b", "@"], 3)
    assert _chunk_frames(rid)(block) == b"".join(_schema_frame(rid, p) for p in block.pieces)


def test_a_token_with_text_is_never_a_frame_without():
    """The schema's sanitizer erases a piece that reads as one tag (the
    harness's visible token "<unk>"): such a frame would carry no
    content, and a client that counts content frames would count fewer
    tokens than the engine generated. It goes out escaped; every other
    piece is what ``model_dump_json`` makes of it."""
    from generativeaiexamples_tpu.server.schemas import Message

    frames = _chunk_frames("r")

    def content(text):
        return json.loads(frames(text)[len(b"data: "):])["choices"][0]["message"]["content"]

    assert content("<unk>") == "&lt;unk&gt;" and content("</s>") == "&lt;/s&gt;"
    # what the same text reads as when it arrives as three tokens
    assert "".join(map(content, "<unk>")) == "&lt;unk&gt;"
    assert content("<i>") == "<i></i>" and content("a<unk>") == "a"  # not erased: as the schema has it
    assert content("\x00") == ""  # nothing left to escape
    for text in AWKWARD:
        if Message(role="assistant", content=text).content or not text:
            assert frames(text) == _schema_frame("r", text)


def test_every_id_of_the_harness_vocabulary_is_a_frame_a_client_counts(tmp_path):
    tok = _harness_tokenizer(tmp_path)
    frames = _chunk_frames("r")
    stops = set(tok.stop_ids())
    dec = IncrementalDecoder(tok)
    for i in range(tok.vocab_size):
        if i in stops:
            continue
        frame = json.loads(frames(dec.push(i))[len(b"data: "):])
        assert frame["choices"][0]["message"]["content"], (i, tok.decode([i]))


def test_preformatted_frame_every_plain_code_point():
    """Every code point the sanitizer is spared (all but the few it
    rewrites) reads the same through the schema, 64 a frame; and every
    one it is not spared reads as the schema's own."""
    rid = "r"
    frames = _chunk_frames(rid)
    cps = [c for c in range(0x110000) if not 0xD800 <= c <= 0xDFFF and not api._SANITIZER_REWRITES.match(chr(c))]
    rng = random.Random(7)
    sample = cps[:0x3000] + rng.sample(cps[0x3000:], 20000)
    for k in range(0, len(sample), 64):
        text = "".join(map(chr, sample[k:k + 64]))
        assert frames(text) == _schema_frame(rid, text), hex(sample[k])
    for c in range(0xA0):
        if api._SANITIZER_REWRITES.match(chr(c)):
            assert frames("a" + chr(c) + "b") == _schema_frame(rid, "a" + chr(c) + "b"), hex(c)


class _BlockChain(BaseExample):
    """A chain that hands the server what the engine's stream does."""

    items = []

    def llm_chain(self, query, chat_history, **kwargs):
        return iter(type(self).items)

    def rag_chain(self, query, chat_history, **kwargs):
        return self.llm_chain(query, chat_history, **kwargs)

    def ingest_docs(self, data_dir, filename):
        pass


def _generate(chain_cls):
    async def _run():
        async with TestClient(TestServer(api.create_app(chain_cls))) as client:
            resp = await client.post("/generate", json={
                "messages": [{"role": "user", "content": "q"}], "use_knowledge_base": False})
            assert resp.status == 200
            return (await resp.read()).decode()

    body = asyncio.run(_run())
    return [json.loads(b[len("data: "):]) for b in body.split("\n\n") if b.strip()]


def test_a_handoff_of_n_tokens_writes_n_frames_in_order():
    acked = []
    _BlockChain.items = [
        TokenBlock(list("abcdefgh"), 8, acked.append),
        DegradedWarning("retrieval_degraded", "store down"),
        "plain chunk",
        TokenBlock(["<i>", "é", " w00001"], 5, acked.append),
    ]
    frames = _generate(_BlockChain)
    texts = [f["choices"][0]["message"]["content"] for f in frames if f["choices"]]
    # (the schema's sanitizer closes the tag, as it did a frame at a time)
    assert texts == list("abcdefgh") + ["plain chunk", "<i></i>", "é", " w00001", ""]
    assert frames[-1]["choices"][0]["finish_reason"] == "[DONE]"
    assert [f.get("warnings") for f in frames].count(None) == len(frames) - 1
    assert frames[8]["warnings"] and not frames[8]["choices"]  # in its place, in order
    assert len({f["id"] for f in frames}) == 1
    assert acked == [8, 5]  # each block reported written, once


def test_a_blocks_frames_go_out_in_one_write(monkeypatch):
    from aiohttp import web

    writes = []
    real = web.StreamResponse.write

    async def write(self, data):
        writes.append(bytes(data))
        return await real(self, data)

    monkeypatch.setattr(web.StreamResponse, "write", write)
    _BlockChain.items = [TokenBlock(list("abcdefgh"), 8), TokenBlock(list("ij"), 2)]
    _generate(_BlockChain)
    assert [w.count(b"data: ") for w in writes] == [8, 2, 1]


# --------------------------------------------------------------------------- #
# server: _aiter_threaded


def _collect(agen_factory):
    async def _run():
        return [item async for item in agen_factory()]

    return asyncio.run(_run())


def test_aiter_threaded_forwards_items_then_the_generators_exception():
    def gen():
        yield "a"
        yield TokenBlock(["b", "c"], 2)
        raise KeyError("from the chain")

    got = []

    async def _run():
        async for item in _aiter_threaded(gen()):
            got.append(item)

    with pytest.raises(KeyError):
        asyncio.run(_run())
    assert got == ["a", "bc"] and got[1].pieces == ["b", "c"]


def test_aiter_threaded_keeps_backpressure_on_the_producer():
    """A consumer that stops reading holds the producer at a bounded
    count of items in flight; it is woken without polling."""
    made = []

    def gen():
        for i in range(10_000):
            made.append(i)
            yield i

    async def _run():
        agen = _aiter_threaded(gen())
        assert await agen.__anext__() == 0
        await asyncio.sleep(0.5)  # the consumer stalls; the producer fills its room
        stalled_at = len(made)
        await asyncio.sleep(0.2)
        assert len(made) == stalled_at  # and waits
        assert await agen.__anext__() == 1
        await agen.aclose()
        return stalled_at

    stalled_at = asyncio.run(_run())
    assert 64 <= stalled_at <= 66


def test_aiter_threaded_closes_the_generator_when_the_consumer_leaves():
    closed = threading.Event()

    def gen():
        try:
            while True:
                yield "x"
                time.sleep(0.001)
        finally:
            closed.set()

    async def _run():
        agen = _aiter_threaded(gen())
        async for _ in agen:
            break
        await agen.aclose()

    asyncio.run(_run())
    assert closed.wait(5)
    deadline = time.time() + 5
    while time.time() < deadline and any(
            t.name == "sse-producer" and t.is_alive() for t in threading.enumerate()):
        time.sleep(0.01)
    assert not any(t.name == "sse-producer" and t.is_alive() for t in threading.enumerate())


def test_64_streams_keep_their_order_and_use_no_executor_worker(monkeypatch):
    """64 concurrent streams, every item in order, and not one job in
    the loop's default executor while they stream."""
    def gen(k):
        for i in range(200):
            yield TokenBlock([f"{k}:{i}"], 1)

    async def _run():
        loop = asyncio.get_running_loop()

        def refuse(*a, **kw):
            raise AssertionError("a stream used the default executor")

        monkeypatch.setattr(loop, "run_in_executor", refuse)

        async def consume(k):
            return [item async for item in _aiter_threaded(gen(k))]

        got = await asyncio.gather(*(consume(k) for k in range(64)))
        assert getattr(loop, "_default_executor", None) is None  # never even created
        return got

    got = asyncio.run(_run())
    for k, items in enumerate(got):
        assert items == [f"{k}:{i}" for i in range(200)]


def test_aiter_threaded_reports_a_block_written_after_the_consumer_took_the_next_step():
    order = []

    def gen():
        yield TokenBlock(["a"], 1, lambda n: order.append(("written", n)))
        yield TokenBlock(["b"], 1, lambda n: order.append(("written", n)))

    async def _run():
        async for item in _aiter_threaded(gen()):
            order.append(("handler wrote", str(item)))

    asyncio.run(_run())
    assert order == [("handler wrote", "a"), ("written", 1), ("handler wrote", "b"), ("written", 1)]


# --------------------------------------------------------------------------- #
# the backlog the dispatch spans carry


def test_stream_backlog_is_two_integers_a_stream():
    reqs = {i: _request() for i in range(3)}
    reqs[0].queued, reqs[0].written = 40, 24      # 16 behind
    reqs[1].queued, reqs[1].written = 8, None      # no handler reports: not counted
    reqs[2].queued, reqs[2].written = 100, 100     # drained
    stub = types.SimpleNamespace(_streams=reqs)
    assert LLMEngine._stream_backlog_tokens(stub) == 16


# --------------------------------------------------------------------------- #
# the device's side of the gap between two hand-offs


@pytest.fixture(scope="module")
def paged_engine():
    from generativeaiexamples_tpu.config import EngineConfig

    eng = LLMEngine(EngineConfig(
        model_config_name="debug", max_batch_size=4, max_seq_len=128, prefill_chunk=16,
        decode_block=2, dtype="float32", tensor_parallelism=1,
        page_size=8, decode_runahead=1, watchdog_stall_s=0.0,
        prefix_cache_slots=4,
    ))
    assert eng._prefix is not None
    yield eng
    eng.shutdown()


_SHARED = [(i * 11) % 240 + 3 for i in range(32)]  # two chunks, cached by a first request

# path -> (the long prompt, speculation on, chunk dispatches its wave still runs)
_WAVES = {
    "greedy": ([(i * 7) % 250 + 1 for i in range(41)], False, 3),
    "speculative": ([(i * 5) % 250 + 2 for i in range(41)], True, 3),
    # 72 tokens are five chunks; the first two are a prefix hit, mapped and skipped
    "prefix_warm": (_SHARED + [(i * 13) % 250 + 4 for i in range(40)], False, 3),
}


@pytest.mark.parametrize("path", sorted(_WAVES))
def test_a_decode_step_runs_between_the_chunks_of_a_wave_while_rows_decode(paged_engine, path):
    """Paged layout, unified policy: while a row decodes, an admission
    wave of several chunks does not hold the device for all of them back
    to back; the loop's decode step (a block, or a speculative round)
    runs between two chunk dispatches, so a decoding stream waits behind
    ONE extend program, and every stream still reads its own greedy
    tokens: with speculation on (a round lands, drafts and releases
    inside the wave) and with a prefix hit (mapped pages under the
    chunks that still run)."""
    from generativeaiexamples_tpu.engine import dispatch_timeline as dtl

    eng = paged_engine
    long_prompt, speculate, chunks = _WAVES[path]
    first = [3, 5, 8, 13] + [len(path)]

    def greedy(prompt, n):
        return list(eng.iter_ids(prompt, SamplingParams(temperature=0.0, max_tokens=n), timeout=300))

    alone_first = greedy(first, 110)
    hits = eng.metrics["prefix_cache_hits"]
    if path == "prefix_warm":
        greedy(_SHARED + [9], 2)
    assert eng.set_spec_decode(speculate) == speculate
    dtl.configure(enable=True)
    try:
        since = dtl.cursor()
        a = eng.submit(first, SamplingParams(temperature=0.0, max_tokens=110))
        assert a.out_queue.get(timeout=300) == alone_first[0]  # A decodes
        b = eng.submit(long_prompt, SamplingParams(temperature=0.0, max_tokens=6))
        got_b = []
        while (item := b.out_queue.get(timeout=300)) is not None:
            got_b.append(item)
        got_a = [alone_first[0]]
        while (item := a.out_queue.get(timeout=300)) is not None:
            got_a.append(item)
        spans, _ = dtl.spans_since(since)
    finally:
        eng.set_spec_decode(False)
    # the long prompt alone, AFTER (its prefix is cached now: one chunk; cold
    # against warm identity is tests/test_paged_kv.py's)
    assert got_a == alone_first and got_b == greedy(long_prompt, 6)
    assert eng.metrics["prefix_cache_hits"] - hits >= (path == "prefix_warm")
    order = ["chunk" if s["kind"] == "prefill_chunk" else "step"
             # by enqueue time: a pipelined verify's span is written a round late
             for s in sorted(spans, key=lambda s: s["t_wall"])
             # (the long prompt's chunks: the first request's own prefill is a
             # one-chunk dispatch too, as every prompt's)
             if (s["kind"] == "prefill_chunk" and b.rid in s["rids"])
             or s["kind"].startswith(("decode", "spec"))]
    k = order.index("chunk")
    wave = order[k:len(order) - order[::-1].index("chunk")]
    assert wave.count("chunk") == chunks, wave
    assert all(x != y for x, y in zip(wave, wave[1:])), wave  # never two chunks back to back
