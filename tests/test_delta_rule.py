"""ops/delta_rule.py (interpret mode, CPU) against the two jnp steps it
replaces at decode: ``kda_step`` (per-channel decay, as many key heads
as value heads) and ``gdn_step`` (one decay a head, each key head feeding
two value heads). Only the summation order over ``Dk`` may differ, so the
tolerance is float32 rounding. The families' own files hold the walks
and the engine with the path ON; the engine with it OFF is here, once
for both."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models.gigachat35 import gdn_step
from generativeaiexamples_tpu.models.glm5next import kda_step
from generativeaiexamples_tpu.ops import delta_rule

TOL = 1e-5
# (per-channel decay, key heads, value heads): KDA's shape and Gated DeltaNet's
FAMILIES = {"kda": (True, 4, 4), "gdn": (False, 2, 4)}
DK, DV = 16, 32


def draw(family, rows, seed=0):
    per_channel, Hk, Hv = FAMILIES[family]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    S = jax.random.normal(ks[0], (rows, Hv, DK, DV), jnp.float32)  # a non-zero state
    q = jax.random.normal(ks[1], (rows, Hk, DK)) * DK ** -0.5
    k = jax.random.normal(ks[2], (rows, Hk, DK))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[3], (rows, Hv, DV))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, Hv)))
    g = -jnp.exp(jax.random.normal(ks[5], (rows, Hv, DK) if per_channel else (rows, Hv)))
    return S, q, k, v, beta, g


def oracle(family, S, q, k, v, beta, g):
    """The model's own jnp step, the key heads repeated as its walk does."""
    ratio = S.shape[1] // q.shape[1]
    q, k = (jnp.repeat(t, ratio, axis=1) for t in (q, k))
    return (kda_step if FAMILIES[family][0] else gdn_step)(S, q, k, v, beta, g)


def kernel(S, q, k, v, beta, g, live):
    if g.ndim == 2:  # one decay a head: the kernel's is per channel
        g = jnp.broadcast_to(g[..., None], g.shape + (S.shape[2],))
    return delta_rule.delta_rule_step(S, q, k, v, beta, g, jnp.asarray(live), interpret=True)


def rel(a, b):
    b = np.asarray(b)
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("rows,dead", [(3, (1,)), (1, ()), (4, (0, 3))])
def test_several_steps_from_a_non_zero_state_equal_the_models_step(family, rows, dead):
    S0, *_ = draw(family, rows)
    live = np.array([r not in dead for r in range(rows)])
    S_ref, S_ker = S0, S0
    for step in range(4):
        _, q, k, v, beta, g = draw(family, rows, seed=1 + step)
        o_ref, S_new = oracle(family, S_ref, q, k, v, beta, g)
        S_ref = jnp.where(live[:, None, None, None], S_new, S_ref)
        o_ker, S_ker = kernel(S_ker, q, k, v, beta, g, live)
        assert S_ker.dtype == jnp.float32 and o_ker.dtype == jnp.float32
        assert rel(o_ker[live], o_ref[live]) < TOL and rel(S_ker[live], S_ref[live]) < TOL, step
        assert np.all(np.isfinite(np.asarray(o_ker)))  # a dead row's output too
    # a dead row's state: bit-identical, after every step
    assert np.array_equal(np.asarray(S_ker)[~live], np.asarray(S0)[~live])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_beta_zero_and_no_decay_leave_a_live_rows_state_as_it_is(family):
    S, q, k, v, beta, g = draw(family, 2)
    o, S1 = kernel(S, q, k, v, jnp.zeros_like(beta), jnp.zeros_like(g), [True, True])
    assert np.array_equal(np.asarray(S1), np.asarray(S))
    q_heads = jnp.repeat(q, S.shape[1] // q.shape[1], axis=1)
    assert rel(o, jnp.einsum("nhkv,nhk->nhv", S, q_heads, precision="highest")) < TOL  # S^T q of the state untouched


@pytest.mark.parametrize("heads,key_heads,want", [
    (64, 64, 16),  # GLM-5.3-Flash: 16 states of 64 KB are the 1 MB block
    (64, 32, 16),  # GigaChat3.5: 8 key heads a block, one sublane tile
    (4, 2, 4), (4, 4, 4),  # the debug presets: every head in one block
])
def test_head_block_divides_the_heads_and_keeps_key_heads_in_whole_tiles(heads, key_heads, want):
    hb = delta_rule.head_block(heads, key_heads, 128 * 128 * 4)
    ratio = heads // key_heads
    assert hb == want and heads % hb == 0 and hb % ratio == 0
    assert (hb // ratio) % 8 == 0 or hb == heads


@pytest.mark.parametrize("model", ["glm5next-debug", "gigachat35-debug"])
def test_engine_with_the_kernel_paths_off_counts_no_row_the_step_kernel_advanced(model):
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine import dispatch_timeline
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams
    from generativeaiexamples_tpu.utils import metrics as metrics_mod

    def counter():
        lines = [l for l in metrics_mod.get_registry().render().splitlines()
                 if l.startswith("genai_engine_state_kernel_rows_total ")]
        return float(lines[0].rsplit(" ", 1)[1]) if lines else 0.0

    eng = LLMEngine(EngineConfig(
        model_config_name=model, max_batch_size=3, max_seq_len=256, prefill_chunk=64, tensor_parallelism=1,
        decode_block=4, decode_runahead=1, page_size=16, prefix_cache_enable="off", dtype="float32",
        paged_kernel="off"))
    try:
        chunk = {"latent_chunk": None} if model == "gigachat35-debug" else {}
        assert eng._family_kernels == dict(grouped_matmul=None, delta_step=None, **chunk)
        before, t0 = counter(), time.time()
        out = list(eng.iter_ids(list(range(3, 40)), SamplingParams(temperature=0.0, max_tokens=5), timeout=600))
        assert len(out) == 5 and counter() == before
        # (the ring is the process's: the spans since t0 are this engine's)
        steps = [s for s in dispatch_timeline.recent_spans(64) if s["kind"] == "decode" and s["t_wall"] >= t0]
        assert steps and all(s["state_kernel_rows"] == 0 and s["state_rows"] == 1 for s in steps)
    finally:
        eng.shutdown()
