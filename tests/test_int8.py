"""int8 weight-only quantization: packing, kernel numerics, engine path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.ops import int8_matmul
from generativeaiexamples_tpu.ops.quant import (
    dequantize_int8,
    quantize_int8,
    quantize_params_int8,
)


def test_quantize_roundtrip_error_small():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 96), jnp.float32) * 0.02
    packed = quantize_int8(w)
    assert packed["q"].dtype == jnp.int8
    from generativeaiexamples_tpu.ops.int8_matmul import F_BLK, K_ALIGN
    assert packed["q"].shape == (K_ALIGN, F_BLK)  # K padded to K_ALIGN, F to F_BLK
    assert packed["scale"].shape == (1, 96)
    back = dequantize_int8(packed, jnp.float32, k_features=64)
    assert back.shape == w.shape
    # per-channel int8: relative error well under 1%
    err = jnp.abs(back - w).max() / jnp.abs(w).max()
    assert float(err) < 0.01


def test_pallas_kernel_matches_xla_fallback():
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (5, 64), jnp.bfloat16)
    w = jax.random.normal(key, (64, 96), jnp.float32) * 0.1
    packed = quantize_int8(w)
    ref = int8_matmul.int8_matmul_xla(x, packed["q"], packed["scale"])
    out = int8_matmul.int8_matmul(x, packed["q"], packed["scale"], interpret=True)
    assert out.shape == ref.shape == (5, 96)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2, rtol=2e-2
    )


def test_quantized_engine_decodes():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

    cfg = EngineConfig(
        model_config_name="debug",
        max_batch_size=2,
        max_seq_len=64,
        prefill_chunk=16,
        page_size=16,
        tensor_parallelism=1,
        quantization="int8",
    )
    eng = LLMEngine(cfg)
    try:
        ids = eng.tokenizer.encode("quantized", add_bos=True)
        out = list(eng.stream_text(ids, SamplingParams(temperature=0.0, max_tokens=6), timeout=120))
        assert out
    finally:
        eng.shutdown()


def test_quantized_params_shard_on_mesh():
    """Packed pytrees flow through the TP sharding rules."""
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.parallel.mesh import create_mesh
    from generativeaiexamples_tpu.parallel.sharding import shard_params

    cfg = llama.PRESETS["debug-8dev"]
    params = quantize_params_int8(llama.init_params(cfg, jax.random.PRNGKey(0)))
    mesh = create_mesh(tensor_parallelism=1)
    sharded = shard_params(params, mesh)
    assert sharded["layers"]["wqkv"]["q"].dtype == jnp.int8
    assert sharded["layers"]["w_gateup"]["q"].dtype == jnp.int8


def test_w8a8_matmul_matches_dequant_reference():
    """int8-MXU W8A8 kernel (per-token activation quant) tracks the
    dequantized reference within activation-quantization error."""
    import numpy as np

    from generativeaiexamples_tpu.ops import quant
    from generativeaiexamples_tpu.ops.int8_matmul import int8_w8a8_matmul

    rng = np.random.default_rng(11)
    K, F, M = 256, 1024, 16
    w = jnp.asarray(rng.standard_normal((K, F)).astype(np.float32) * 0.05)
    x = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32), jnp.bfloat16)
    pack = quant.quantize_int8(w)
    got = np.asarray(
        int8_w8a8_matmul(x, pack["q"], pack["scale"], interpret=True), np.float32
    )
    want = np.asarray(x, np.float32) @ np.asarray(
        quant.dequantize_int8(pack, jnp.float32, k_features=K)
    )
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.02, rel


def test_w8a8_rejects_prefill_shapes():
    import numpy as np

    from generativeaiexamples_tpu.ops import quant
    from generativeaiexamples_tpu.ops.int8_matmul import M_MAX, int8_w8a8_matmul

    w = jnp.zeros((128, 512), jnp.float32)
    pack = quant.quantize_int8(w)
    x = jnp.zeros((M_MAX + 1, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="decode-shaped"):
        int8_w8a8_matmul(x, pack["q"], pack["scale"], interpret=True)


def test_w8a8_xla_prefill_path_matches_reference():
    """Dequant-free int8-dot XLA path (prefill-shaped w8a8 calls)."""
    import numpy as np

    from generativeaiexamples_tpu.ops import quant
    from generativeaiexamples_tpu.ops.int8_matmul import int8_matmul_xla_w8a8

    rng = np.random.default_rng(12)
    K, F = 256, 512
    w = jnp.asarray(rng.standard_normal((K, F)).astype(np.float32) * 0.05)
    x = jnp.asarray(rng.standard_normal((2, 160, K)).astype(np.float32), jnp.bfloat16)
    pack = quant.quantize_int8(w)
    got = np.asarray(int8_matmul_xla_w8a8(x, pack["q"], pack["scale"]), np.float32)
    want = np.asarray(x, np.float32) @ np.asarray(
        quant.dequantize_int8(pack, jnp.float32, k_features=K)
    )
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.02, rel
