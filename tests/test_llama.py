"""Model correctness tests on the virtual CPU platform (tiny configs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from greedy_reference import served_walk_logits

from generativeaiexamples_tpu.models import (
    PRESETS,
    forward,
    init_params,
    sample_tokens,
)

CFG = PRESETS["debug"]


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def test_forward_shapes(params):
    tokens = jnp.array([[1, 2, 3, 4]], dtype=jnp.int32)
    positions = jnp.arange(4, dtype=jnp.int32)[None, :]
    logits, _ = forward(params, CFG, tokens, positions)
    assert logits.shape == (1, 4, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality(params):
    """Changing a future token must not change past logits."""
    key = jax.random.PRNGKey(1)
    tokens_a = jax.random.randint(key, (1, 8), 0, CFG.vocab_size, jnp.int32)
    tokens_b = tokens_a.at[0, 6].set((tokens_a[0, 6] + 1) % CFG.vocab_size)
    positions = jnp.arange(8, dtype=jnp.int32)[None, :]
    la, _ = forward(params, CFG, tokens_a, positions)
    lb, _ = forward(params, CFG, tokens_b, positions)
    np.testing.assert_allclose(la[0, :6], lb[0, :6], rtol=2e-4, atol=2e-4)
    assert not np.allclose(la[0, 6], lb[0, 6])


def test_prefill_decode_matches_full_forward(params):
    """The served walks (paged prefill, then one paged decode step a
    token) == one-shot causal forward."""
    key = jax.random.PRNGKey(2)
    T = 10
    tokens = jax.random.randint(key, (2, T), 0, CFG.vocab_size, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (2, T))
    full_logits, _ = forward(params, CFG, tokens, positions)

    # prefill the first 6 tokens, then decode 4 more one at a time
    P = 6
    last, steps = served_walk_logits(params, CFG, tokens, P)
    np.testing.assert_allclose(last, full_logits[:, P - 1], rtol=3e-2, atol=3e-2)
    for t, step_logits in zip(range(P, T), steps):
        np.testing.assert_allclose(step_logits, full_logits[:, t], rtol=3e-2, atol=3e-2)


def test_prefill_with_padding(params):
    """Right-padded prompts of different lengths prefill like unpadded ones."""
    from generativeaiexamples_tpu.models import llama

    key = jax.random.PRNGKey(3)
    toks = jax.random.randint(key, (1, 5), 0, CFG.vocab_size, jnp.int32)
    layered = llama.consume_split_params_layers(dict(params, layers=dict(params["layers"])))
    last1, _ = llama.prefill_layers(layered, CFG, toks, jnp.array([5], jnp.int32), use_flash=False)
    padded = jnp.pad(toks, ((0, 0), (0, 3)))  # pad to length 8
    last2, _ = llama.prefill_layers(layered, CFG, padded, jnp.array([5], jnp.int32), use_flash=False)
    np.testing.assert_allclose(last1, last2, rtol=2e-4, atol=2e-4)


def test_sampling_greedy_and_topp():
    logits = jnp.log(jnp.array([[0.05, 0.6, 0.3, 0.05]], jnp.float32))
    key = jax.random.PRNGKey(0)
    greedy = sample_tokens(logits, key, temperature=0.0, top_p=1.0)
    assert int(greedy[0]) == 1
    # top_p=0.5 keeps only token 1 (mass_before=0 < 0.5; next has 0.6 >= 0.5)
    for seed in range(5):
        t = sample_tokens(logits, jax.random.PRNGKey(seed), temperature=1.0, top_p=0.5)
        assert int(t[0]) == 1
    # top_p=1.0 eventually samples something other than argmax
    seen = {
        int(sample_tokens(logits, jax.random.PRNGKey(s), temperature=1.0, top_p=1.0)[0])
        for s in range(64)
    }
    assert len(seen) > 1


def test_byte_tokenizer_roundtrip():
    from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    ids = tok.encode("hello world", add_bos=True)
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "hello world"
    chat = tok.render_chat([("system", "be nice"), ("user", "hi")])
    assert chat[0] == tok.bos_id
    assert tok.vocab_size == 512


def test_decode_window_is_exact():
    """A window >= position+1 must not change decode logits vs the full
    window (the gather reads a power-of-two bucket of pages)."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama

    cfg = llama.PRESETS["debug"]
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    layered = llama.consume_split_params_layers(dict(params, layers=dict(params["layers"])))
    page = 8
    tables = 1 + jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    prompt = jnp.array([[3, 4, 5, 6], [7, 8, 9, 10]], jnp.int32)
    _, kvs = llama.prefill_layers(layered, cfg, prompt, jnp.array([4, 4], jnp.int32), use_flash=False)
    caches = llama.write_prefill_pages(llama.init_kv_pool(cfg, 17, page, jnp.float32), kvs, tables, page)
    tokens = jnp.array([11, 12], jnp.int32)
    positions = jnp.array([4, 4], jnp.int32)
    live = jnp.ones((2,), bool)
    full, _ = llama.decode_layers_paged(layered, cfg, tokens, positions, live, tables, caches, 64, page)
    windowed, _ = llama.decode_layers_paged(layered, cfg, tokens, positions, live, tables, caches, 16, page)
    assert jnp.allclose(full, windowed, atol=1e-5)


def test_serving_memory_budget_70b():
    """Fit-plan arithmetic for the flagship topologies (BASELINE.md;
    reference GPU requirements: 30 GB for 8B, 320 GB for 70B,
    docs/support-matrix.md:35-46)."""
    from generativeaiexamples_tpu.models import llama

    cfg70 = llama.PRESETS["llama3-70b"]
    est = llama.serving_memory_bytes(cfg70, batch=32, max_seq_len=8192,
                                     weight_bytes=1, kv_bytes=1)
    # int8 70B weights ~69-71 GB: more than 4 v5e chips, within 8.
    assert 65e9 < est["weights"] < 75e9
    assert est["weights"] > 4 * 16e9 * 0.92
    assert est["total"] < 8 * 16e9 * 0.92  # fits v5e-8 with int8 KV
    # bf16 cache at the same geometry would NOT fit alongside weights
    bf16 = llama.serving_memory_bytes(cfg70, batch=32, max_seq_len=8192,
                                      weight_bytes=1, kv_bytes=2)
    assert bf16["total"] > est["total"]

    cfg8 = llama.PRESETS["llama3-8b"]
    est8 = llama.serving_memory_bytes(cfg8, batch=64, max_seq_len=512,
                                      weight_bytes=1, kv_bytes=1)
    # int8 8B fits ONE 16 GB chip (the round-1 measured configuration)
    assert est8["total"] < 16e9 * 0.92
