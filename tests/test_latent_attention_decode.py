"""``ops/latent_attention.py`` ``dense_latent_attention`` and
``latent_attention`` (the decode-side latent reads: N consecutive pages
of a row a grid step, one softmax update a step) in interpret mode
against the plain float32 gather of ``models/gigachat35.py``
``_attend_absorbed``, the rule of shapes that picks N, and the count the
engine's decode spans carry of the walk.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.ops import latent_attention as la
from generativeaiexamples_tpu.ops import page_attention as pa

H, W, R, PAGE, PMAX = 4, 128, 32, 8, 16
# a dead row, a one-page row, a context ending on a page's last token, one ending on a page's first
# token, live pages that are no multiple of any N (7), and a row at Pmax
POSITIONS = [0, 5, 3 * PAGE - 1, 5 * PAGE, 7 * PAGE - 3, PMAX * PAGE - 1]
B = len(POSITIONS)


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def operands(seed=0):
    k0 = jax.random.key(seed)
    P = 1 + B * PMAX
    pool = jax.random.normal(k0, (P, PAGE, W))
    # out of order, and the same page under several places and rows (shared prefix pages): pages drawn
    # WITH replacement
    tables = jnp.asarray(1 + np.random.default_rng(seed).integers(0, P - 1, size=(B, PMAX)), jnp.int32)
    q = jax.random.normal(jax.random.fold_in(k0, 9), (B, H, W))
    return q, pool, tables, jnp.asarray(POSITIONS, jnp.int32)


def gather(q, pool, tables, pos, value_dim, mask=None):
    """The gather path of ``_attend_absorbed``: each row's whole table, float32."""
    rows = pool[tables].reshape(B, PMAX * PAGE, -1)
    ok = jnp.arange(PMAX * PAGE)[None, :] <= pos[:, None]
    if mask is not None:
        ok = ok & mask
    sc = jnp.where(ok[:, None], jnp.einsum("bhw,bsw->bhs", q, rows) * 0.25, -1e30)
    return jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(sc, -1), rows[..., :value_dim])


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_the_dense_read_at_n_pages_a_step_equals_the_gather(n):
    q, pool, tables, pos = operands()
    work = pa.page_work_list(tables, pos, 1, PAGE, n)
    assert int(work.n_work[0]) == sum(-(-(p // PAGE + 1) // n) for p in POSITIONS)
    out = la.dense_latent_attention(q, pool, tables, pos, value_dim=R, scale=0.25, interpret=True, work=work)
    assert out.shape == (B, H, R) and rel(out, gather(q, pool, tables, pos, R)) < 1e-5


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_the_biased_read_at_n_pages_a_step_equals_the_gather_over_the_selected_rows(n):
    """What a query may read arrives as a bias; whole pages, and the
    whole of a step's pages, hold nothing selected (row 5's first
    eleven pages: its running max stays at -1e30 through them)."""
    q, pool, tables, pos = operands(1)
    tok = jnp.arange(PMAX * PAGE)[None, :]
    chosen = jax.random.uniform(jax.random.key(5), (B, PMAX * PAGE)) > 0.4
    chosen = chosen.at[5, :11 * PAGE].set(False).at[4, PAGE:3 * PAGE].set(False) | (tok == pos[:, None])
    bias = jnp.where(chosen & (tok <= pos[:, None]), 0.0, -1e30)
    work = pa.page_work_list(tables, pos, 1, PAGE, n)
    out = la.latent_attention(q, pool, bias, tables, pos, scale=0.25, interpret=True, work=work)
    assert out.shape == (B, H, W) and rel(out, gather(q, pool, tables, pos, W, chosen)) < 1e-5


@pytest.mark.parametrize("entry", ["dense", "biased"])
def test_a_read_given_no_list_builds_the_one_the_rule_names(entry):
    q, pool, tables, pos = operands(2)
    n = la.latent_pages_per_step(PAGE, W, pool.dtype, PMAX)
    assert n == 8
    work = pa.page_work_list(tables, pos, 1, PAGE, n)
    if entry == "dense":
        a = la.dense_latent_attention(q, pool, tables, pos, value_dim=R, scale=0.25, interpret=True)
        b = la.dense_latent_attention(q, pool, tables, pos, value_dim=R, scale=0.25, interpret=True, work=work)
    else:
        bias = jnp.where(jnp.arange(PMAX * PAGE)[None, :] <= pos[:, None], 0.0, -1e30)
        a = la.latent_attention(q, pool, bias, tables, pos, scale=0.25, interpret=True)
        b = la.latent_attention(q, pool, bias, tables, pos, scale=0.25, interpret=True, work=work)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bfloat16_pools_round_the_probabilities_once_as_one_page_a_step_did():
    """Same mathematics at every N: against the N = 1 walk the reads of a
    bfloat16 pool differ by float32 summation order alone."""
    q, pool, tables, pos = operands(3)
    q, pool = q.astype(jnp.bfloat16), pool.astype(jnp.bfloat16)
    outs = [la.dense_latent_attention(q, pool, tables, pos, value_dim=R, scale=0.25, interpret=True,
                                      work=pa.page_work_list(tables, pos, 1, PAGE, n)) for n in (1, 8)]
    assert rel(outs[1], outs[0]) < 4e-3
    assert rel(outs[1], gather(q.astype(jnp.float32), pool.astype(jnp.float32), tables, pos, R)) < 1e-2


@pytest.mark.parametrize("shape, n", [
    ((128, 640, "bfloat16", 192), 8),  # Kimi-K2.5's cell: 163,840 B a page, a table of 192
    ((128, 640, "bfloat16", 64), 8),  # GigaChat3.5's
    ((128, 512, "bfloat16", 64), 8),  # GLM-5.3-Flash's
    ((16, 128, "float32", 16), 8),  # the debug presets the tests serve
    ((128, 640, "bfloat16", 12), 4),  # N divides the table (a step's bias is one block)
    ((128, 640, "bfloat16", 6), 2),
    ((128, 640, "bfloat16", 7), 1),
    ((1024, 640, "bfloat16", 64), 1),  # a page over a megabyte walks alone: two pass the step's bytes
    ((512, 512, "bfloat16", 64), 4),
])
def test_pages_a_step_come_from_the_shapes(shape, n):
    assert la.latent_pages_per_step(*shape) == n


# --------------------------------------------------------------------------- #
# the count a decode span carries of the walk


@pytest.mark.parametrize("name", ["kimik2-debug", "gigachat35-debug", "glm5next-debug"])
def test_decode_spans_of_a_latent_pool_count_the_steps_of_n_pages(name):
    """``kv_pages_walked`` stays the LIVE pages (the roofline readers
    multiply it by a page's bytes) and ``kv_page_steps`` is ``sum(ceil(live
    / N))`` at the N the kernels' own rule names, the list the model
    files build."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine import dispatch_timeline as dtl
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams

    page, seq, n_prompt = 16, 256, 150
    dtl.reset()
    dtl.configure(enable=True)
    eng = LLMEngine(EngineConfig(
        model_config_name=name, max_batch_size=3, max_seq_len=seq, prefill_chunk=64, decode_block=4,
        decode_runahead=1, tensor_parallelism=1, page_size=page, prefix_cache_enable="off", dtype="float32",
        paged_kernel="interpret", watchdog_stall_s=0.0,
    ))
    try:
        assert eng._paged_kernel == "interpret"
        n = eng._kv_pages_a_step
        assert n == la.latent_pages_per_step(page, eng._kv_shape.head_dim, "float32", seq // page) == 8
        prompt = [5 + i % 200 for i in range(n_prompt)]  # first decode query at n_prompt: 10 live pages
        assert len(list(eng.iter_ids(prompt, SamplingParams(temperature=0.0, max_tokens=13, seed=1), timeout=600))) == 13
        decode = [v for v in dtl.spans_since(0)[0] if v["kind"] == "decode"]
        assert len(decode) >= 3 and all(v["path"] == "kernel" and "kv_score_rows" not in v for v in decode)
        for i, v in enumerate(decode[:3]):
            live = (n_prompt + 4 * i) // page + 1  # 10 pages
            assert v["kv_pages_walked"] == live + 2 == 12  # + one scratch page an empty slot
            # what page_work_list(tables, positions, 1, page, n) counts (the test of the dense read above)
            assert v["kv_page_steps"] == -(-live // n) + 2 == 4
    finally:
        eng.shutdown()
        dtl.reset()
