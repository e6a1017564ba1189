"""Pallas flash-attention kernel vs. the einsum reference path.

Runs in interpret mode on the CPU test mesh (conftest pins JAX_PLATFORMS=cpu);
the same kernel compiles for real on TPU where models/llama.py:prefill
selects it automatically.
"""
import math

import jax
import jax.numpy as jnp
import pytest

from generativeaiexamples_tpu.ops.flash_attention import (
    flash_attention_causal,
    supported,
)


def _reference(q, k, v):
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    q4 = q.reshape(B, T, Hkv, g, D)
    s = jnp.einsum(
        "btkgd,bskd->bkgts", q4.astype(jnp.float32), k.astype(jnp.float32)
    ) / math.sqrt(D)
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return o.reshape(B, T, Hq, D)


@pytest.mark.parametrize(
    "B,T,Hq,Hkv,D",
    [
        (2, 128, 4, 2, 128),  # GQA group=2, exact blocks
        (1, 200, 8, 8, 128),  # MHA, ragged T (padding path)
        (2, 37, 4, 1, 128),  # MQA, T smaller than one block
    ],
)
def test_matches_reference(B, T, Hq, Hkv, D):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, T, Hq, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, Hkv, D), jnp.bfloat16)
    out = flash_attention_causal(q, k, v, interpret=True)
    ref = _reference(q, k, v)
    assert out.shape == (B, T, Hq, D)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    assert err < 0.05, err


def test_causality():
    """Token t's output must not change when tokens after t change."""
    B, T, H, D = 1, 64, 2, 128
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    out1 = flash_attention_causal(q, k, v, interpret=True)
    k2 = k.at[:, 40:].set(9.0)
    v2 = v.at[:, 40:].set(-9.0)
    out2 = flash_attention_causal(q, k2, v2, interpret=True)
    assert jnp.allclose(out1[:, :40], out2[:, :40], atol=1e-2)
    assert not jnp.allclose(out1[:, 41:], out2[:, 41:], atol=1e-2)


def test_supported_gate():
    assert supported(128, 128)
    assert not supported(128, 64)  # head_dim below one lane tile


def test_prefill_flash_glue_matches_einsum():
    """prefill_layers(use_flash=True) through the kernel == einsum path (GQA glue)."""
    from generativeaiexamples_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=256,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=2,
        num_kv_heads=1,
        head_dim=128,
        max_seq_len=64,
    )
    params = llama.consume_split_params_layers(
        llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 20), 0, 256)
    lengths = jnp.array([20], jnp.int32)
    last_ein, kv_ein = llama.prefill_layers(params, cfg, tokens, lengths, use_flash=False)
    last_fl, kv_fl = llama.prefill_layers(
        params, cfg, tokens, lengths, use_flash=True, interpret=True
    )
    assert jnp.allclose(last_ein, last_fl, atol=1e-3), float(
        jnp.max(jnp.abs(last_ein - last_fl))
    )
    for (k_e, _), (k_f, _) in zip(kv_ein, kv_fl):
        assert jnp.allclose(k_e[:, :20], k_f[:, :20], atol=1e-3)
