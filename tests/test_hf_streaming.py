"""Streaming sharded checkpoint load (VERDICT r2 missing #3).

The stacked loader (hf_loader.load_params) stages the full checkpoint as
host numpy plus an np.stack copy — ~2x checkpoint size in host RAM,
structurally unable to load a 70B (~140 GB) checkpoint. The streaming
loader (load_params_layered_streaming) must place each layer on device
as its tensors complete, with bounded host memory, with optional
int8 quantize-on-load, matching the stacked loader's numerics.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.hf_loader import (
    config_from_hf,
    iter_param_groups,
    load_params,
    load_params_layered_streaming,
    write_hf_checkpoint,
)
from generativeaiexamples_tpu.ops import quant

CFG = llama.LlamaConfig(
    vocab_size=512,
    hidden_size=64,
    intermediate_size=128,
    num_layers=6,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    max_seq_len=128,
)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("stream_ckpt"))
    write_hf_checkpoint(CFG, path, seed=7, n_shards=3)
    return path


def test_writer_roundtrips_config(ckpt):
    cfg = config_from_hf(ckpt)
    assert cfg.num_layers == CFG.num_layers
    assert cfg.num_kv_heads == CFG.num_kv_heads
    assert cfg.head_dim == CFG.head_dim


def test_streaming_matches_stacked_loader(ckpt):
    stacked = load_params(ckpt, CFG, dtype=jnp.float32)
    streamed = load_params_layered_streaming(ckpt, CFG, dtype=jnp.float32)
    assert len(streamed["layers"]) == CFG.num_layers
    np.testing.assert_array_equal(
        np.asarray(streamed["embed"]), np.asarray(stacked["embed"])
    )
    np.testing.assert_array_equal(
        np.asarray(streamed["lm_head"]), np.asarray(stacked["lm_head"])
    )
    for i in range(CFG.num_layers):
        for key in ("attn_norm", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
            np.testing.assert_array_equal(
                np.asarray(streamed["layers"][i][key]),
                np.asarray(stacked["layers"][key][i]),
                err_msg=f"layer {i} {key}",
            )


def test_peak_host_memory_bounded(ckpt):
    """The point of streaming: the high-water mark of buffered host
    tensors stays well under the checkpoint size (~one layer + the
    in-flight tensor, not the full tree plus a stacked copy)."""
    stats: dict = {}
    groups = list(iter_param_groups(ckpt, CFG, stats=stats))
    total = sum(
        t.nbytes
        for k, g in groups
        for t in (g.values() if isinstance(g, dict) else [g])
    )
    assert stats["peak_host_bytes"] > 0
    assert stats["peak_host_bytes"] < total * 0.5, (
        f"peak {stats['peak_host_bytes']} vs total {total}: streaming is "
        "buffering most of the checkpoint"
    )


def test_streaming_incomplete_checkpoint_raises(tmp_path):
    from safetensors.numpy import save_file

    # one full layer, one partial
    path = tmp_path / "bad_ckpt"
    path.mkdir()
    cfg2 = llama.LlamaConfig(
        vocab_size=64, hidden_size=16, intermediate_size=32, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=8, max_seq_len=32,
    )
    tensors = {
        "model.embed_tokens.weight": np.zeros((64, 16), np.float32),
        "model.norm.weight": np.ones((16,), np.float32),
        "model.layers.0.input_layernorm.weight": np.ones((16,), np.float32),
    }
    save_file(tensors, str(path / "model.safetensors"))
    with pytest.raises(ValueError, match="incomplete"):
        list(iter_param_groups(str(path), cfg2))


def test_streaming_int8_quantize_on_load_matches_stacked_packs(ckpt):
    """Quantize-on-load produces bit-identical int8 packs to the stacked
    load->quantize pipeline (fused wqkv/w_gateup at tp_shards=1)."""
    streamed = load_params_layered_streaming(
        ckpt, CFG, dtype=jnp.bfloat16, quantization="int8"
    )
    stacked = quant.quantize_params_int8(load_params(ckpt, CFG, dtype=jnp.float32))
    for i in (0, CFG.num_layers - 1):
        for key in ("wqkv", "w_gateup", "wo", "w_down"):
            np.testing.assert_array_equal(
                np.asarray(streamed["layers"][i][key]["q"]),
                np.asarray(stacked["layers"][key]["q"][i]),
                err_msg=f"layer {i} {key} int8 values",
            )
            np.testing.assert_allclose(
                np.asarray(streamed["layers"][i][key]["scale"]),
                np.asarray(stacked["layers"][key]["scale"][i]),
                rtol=1e-6,
                err_msg=f"layer {i} {key} scales",
            )
    np.testing.assert_array_equal(
        np.asarray(streamed["lm_head"]["q"]), np.asarray(stacked["lm_head"]["q"])
    )


def test_engine_streams_layered_checkpoint(ckpt):
    """EngineConfig.checkpoint_path on the layered path goes through the
    streaming loader and serves real tokens."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

    eng = LLMEngine(
        EngineConfig(
            checkpoint_path=ckpt,
            tensor_parallelism=1,
            max_batch_size=2,
            max_seq_len=64,
            prefill_chunk=16,
            page_size=16,
            decode_block=2,
            quantization="int8",
        )
    )
    try:
        assert eng._streamed_load
        assert "wqkv" in eng.params["layers"][0]  # fused int8 pack
        out = list(
            eng.iter_ids(
                [1, 5, 9], SamplingParams(temperature=0.0, max_tokens=4), timeout=300
            )
        )
        assert len(out) >= 1
    finally:
        eng.shutdown()


def test_engine_streams_w8a8_checkpoint_produces_packed_leaves(ckpt):
    """quantization='w8a8' + checkpoint on the streaming path must
    quantize-on-load exactly like 'int8' (ADVICE r3 high: it previously
    loaded dense bf16 with no packs, so the memory-budget check counted
    1 byte/param while 2 were resident, and no w8a8 kernel ever ran)."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

    eng = LLMEngine(
        EngineConfig(
            checkpoint_path=ckpt,
            tensor_parallelism=1,
            max_batch_size=2,
            max_seq_len=64,
            prefill_chunk=16,
            page_size=16,
            decode_block=2,
            quantization="w8a8",
        )
    )
    try:
        assert eng._streamed_load
        layer0 = eng.params["layers"][0]
        assert isinstance(layer0["wqkv"], dict) and "q" in layer0["wqkv"], (
            "w8a8 streaming load must produce int8 packs, not dense bf16"
        )
        assert layer0["wqkv"]["q"].dtype == jnp.int8
        assert isinstance(eng.params["lm_head"], dict)
        out = list(
            eng.iter_ids(
                [1, 5, 9], SamplingParams(temperature=0.0, max_tokens=4), timeout=300
            )
        )
        assert len(out) >= 1
    finally:
        eng.shutdown()


def test_engine_streams_checkpoint_under_tp_kernels(tmp_path, monkeypatch):
    """Streaming load on a TP mesh: per-shard Megatron tiles placed with
    NamedSharding, served through the shard_map kernel path."""
    monkeypatch.setenv("GENAI_TPU_TP_KERNELS", "interpret")
    cfg8 = llama.PRESETS["debug-8dev"]
    path = str(tmp_path / "tp_ckpt")
    write_hf_checkpoint(cfg8, path, seed=3, n_shards=2)

    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

    eng = LLMEngine(
        EngineConfig(
            checkpoint_path=path,
            tensor_parallelism=8,
            max_batch_size=2,
            max_seq_len=64,
            prefill_chunk=16,
            page_size=16,
            decode_block=2,
            quantization="int8",
        )
    )
    try:
        assert eng._streamed_load
        assert eng._tp is not None
        layer0 = eng.params["layers"][0]
        assert "wq" in layer0 and "wqkv" not in layer0  # unfused TP tiles
        out = list(
            eng.iter_ids(
                [1, 5, 9], SamplingParams(temperature=0.0, max_tokens=4), timeout=600
            )
        )
        assert len(out) >= 1
    finally:
        eng.shutdown()
