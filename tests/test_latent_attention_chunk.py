"""``ops/latent_attention.py`` ``latent_chunk_read`` (the chunk walk's
latent read: keys and values expanded, scored and summed inside the
kernel) in interpret mode against the XLA loop it replaces
(``models/gigachat35.py`` ``_attend_expanded``), the block work list it
walks, and the rule of shapes that decides which of the two a family
serves with.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import gigachat35, kimik2, registry
from generativeaiexamples_tpu.ops import latent_attention as la

# widths that tile the chip, at a head count and a latent a CPU walks in a moment
H, DN, DR, DV, R, PAGE, PMAX = 2, 128, 64, 128, 128, 128, 24
CFG = dataclasses.replace(
    kimik2.PRESETS["kimik2-debug"], num_heads=H, kv_lora_rank=R, qk_nope_head_dim=DN, qk_rope_head_dim=DR,
    v_head_dim=DV, max_seq_len=PAGE * PMAX)
ROW = CFG.latent_row
BLOCK = la.chunk_block_pages(PAGE, PMAX) * PAGE

# rows of different depth: a causal block only, an offset that is no multiple of the block,
# one deep enough for three blocks, one dead row
OFFSETS = {1: [2 * BLOCK + 52], 4: [0, BLOCK + 37, 2 * BLOCK + 52, 300]}
VALID = {1: [0.75], 4: [1.0, 0.5, 0.75, 0.0]}  # of the chunk width


def operands(dtype, T, N, seed=0, poison=None):
    ks = jax.random.split(jax.random.key(seed), 5)
    P = 1 + N * PMAX
    pool = (jax.random.normal(ks[0], (P, PAGE, ROW), jnp.float32) * 0.5).at[:, :, R + DR:].set(0.0)
    lp = {"wuk": (jax.random.normal(ks[1], (H, DN, R)) / np.sqrt(R)).astype(dtype),
          "wuv": (jax.random.normal(ks[2], (H, R, DV)) / np.sqrt(R)).astype(dtype)}
    q_nope, q_rope = jax.random.normal(ks[3], (N, T, H, DN)), jax.random.normal(ks[4], (N, T, H, DR))
    tables = jnp.asarray(1 + np.random.default_rng(seed).permutation(N * PMAX).reshape(N, PMAX), jnp.int32)
    offsets = jnp.asarray(OFFSETS[N], jnp.int32)
    valid = jnp.asarray([int(v * T) for v in VALID[N]], jnp.int32)
    positions = jnp.minimum(offsets[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :], PMAX * PAGE - 1)
    n_tokens = jnp.where(valid > 0, offsets + valid, 0)
    if poison is not None:
        # every cached row at or past a row's n_tokens, and the scratch page: another tenant's values
        s = jnp.arange(PMAX * PAGE, dtype=jnp.int32).reshape(PMAX, PAGE)
        for n in range(N):
            dead = (s >= n_tokens[n])[:, :, None]
            pool = pool.at[tables[n]].set(jnp.where(dead, poison, pool[tables[n]]))
        pool = pool.at[0].set(poison)
    return q_nope, q_rope, pool.astype(dtype), tables, positions, n_tokens, lp, valid


def both(args, **kw):
    q_nope, q_rope, pool, tables, positions, n_tokens, lp, _ = args
    xla = gigachat35._attend_expanded(q_nope, q_rope, pool, tables, positions, n_tokens, lp, CFG)
    kernel = gigachat35._attend_expanded(q_nope, q_rope, pool, tables, positions, n_tokens, lp, CFG,
                                         latent_chunk="interpret", **kw)
    return np.asarray(xla), np.asarray(kernel)


def live_queries(args):
    """[N, T] bool: the queries whose output a walk keeps."""
    valid = np.asarray(args[-1])
    return np.arange(args[0].shape[1])[None, :] < valid[:, None]


@pytest.mark.parametrize("T", [128, 512])
@pytest.mark.parametrize("N", [1, 4])
def test_float32_operands_agree_with_the_xla_loop(T, N):
    """Offsets of 0 (the causal block only), one that is no multiple of
    the block, one three blocks deep, a dead row; the kernel's blocks are
    not the loop's, so the running softmax rounds otherwise: 1e-5."""
    with jax.default_matmul_precision("highest"):
        args = operands(jnp.float32, T, N)
        xla, kernel = both(args)
    assert kernel.shape == xla.shape == (N, T, H, DV) and kernel.dtype == np.float32
    assert np.isfinite(kernel).all()  # a dead row and a padded query too
    keep = live_queries(args)
    assert keep.sum() == sum(int(v * T) for v in VALID[N])
    np.testing.assert_allclose(kernel[keep], xla[keep], rtol=0, atol=1e-5)


@pytest.mark.parametrize("T", [128, 512])
@pytest.mark.parametrize("N", [1, 4])
def test_bfloat16_operands_agree_to_the_xla_loops_own_rounding(T, N):
    """Against the same arithmetic on the same bfloat16 operands with
    nothing rounded on the way (float32 keys, values and probabilities):
    the kernel is as far from it as the XLA loop is."""
    args = operands(jnp.bfloat16, T, N)
    xla, kernel = both(args)
    q_nope, q_rope, pool, tables, positions, n_tokens, lp, _ = args
    wide = lambda x: x.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(gigachat35._attend_expanded(
            wide(q_nope.astype(jnp.bfloat16)), wide(q_rope.astype(jnp.bfloat16)), wide(pool), tables, positions,
            n_tokens, jax.tree.map(wide, lp), CFG))
    keep = live_queries(args)
    err_xla, err_kernel = np.abs(xla - exact)[keep].max(), np.abs(kernel - exact)[keep].max()
    assert 0 < err_kernel < 1.5 * err_xla and err_xla < 0.02 * np.abs(exact[keep]).max(), (err_kernel, err_xla)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_position_past_n_tokens_contributes_nothing(dtype):
    """The pool's dead pages, the rows past ``n_tokens`` of a live page
    and the scratch page hold large values; no live query's output moves
    by a bit."""
    dt = jnp.dtype(dtype)
    with jax.default_matmul_precision("highest"):
        clean = both(operands(dt, 128, 4))[1]
        args = operands(dt, 128, 4, poison=3.0e4)
        poisoned = both(args)[1]
    keep = live_queries(args)
    assert np.isfinite(poisoned[keep]).all() and np.array_equal(poisoned[keep], clean[keep])


@pytest.mark.parametrize("heads", [1, 2])
def test_heads_a_step_do_not_change_a_bit(heads):
    """A group of heads shares a fetched block; each keeps its own state."""
    args = operands(jnp.float32, 128, 4)
    q_nope, q_rope, pool, tables, positions, n_tokens, lp, _ = args
    read = lambda g: np.asarray(la.latent_chunk_read(  # noqa: E731
        jnp.moveaxis(q_nope, 2, 1), jnp.moveaxis(q_rope, 2, 1), pool, tables, positions, n_tokens, lp["wuk"], lp["wuv"],
        scale=CFG.softmax_scale, interpret=True, heads_per_step=g))
    assert la.chunk_heads_per_step(H) == 2 and la.chunk_heads_per_step(64) == 4
    assert np.array_equal(read(heads), read(None))


def test_the_work_list_walks_live_blocks_only():
    """Rows of 0 (dead), 1, BLOCK, BLOCK + 1 and every token the table
    maps: 1 + 1 + 1 + 2 + all blocks, rows ascending, blocks ascending
    inside a row, a place past the table's end on its last page, every
    page inside the pool."""
    n_tokens = jnp.asarray([0, 1, BLOCK, BLOCK + 1, PMAX * PAGE], jnp.int32)
    tables = jnp.asarray(np.arange(5 * PMAX).reshape(5, PMAX) + 7, jnp.int32)
    bp = la.chunk_block_pages(PAGE, PMAX)
    work = la.chunk_work_list(tables, n_tokens, PAGE, pool_pages=5 * PMAX)  # the last rows' pages lie past the pool
    n = int(work.n_work[0])
    assert bp == 8 and np.asarray(work.n_blocks).tolist() == [1, 1, 1, 2, 3] and n == 8
    assert np.asarray(work.row)[:n].tolist() == [0, 1, 2, 3, 3, 4, 4, 4]
    assert np.asarray(work.block)[:n].tolist() == [0, 0, 0, 0, 1, 0, 1, 2]
    phys = np.asarray(work.phys).reshape(-1, bp)
    assert phys[3].tolist() == list(range(7 + 3 * PMAX, 7 + 3 * PMAX + bp)) and phys.max() == 5 * PMAX - 1 and phys.min() >= 0
    assert len(work.row) == 5 * 3  # what a static grid would walk
    # a table that is no multiple of the block: the last block's places past its end repeat the last page
    short = la.chunk_work_list(tables[:1, :10], jnp.asarray([10 * PAGE], jnp.int32), PAGE, pool_pages=1000)
    assert np.asarray(short.phys).reshape(-1, bp)[1].tolist() == [15, 16] + [16] * 6


DEBUG_FAMILIES = ("kimik2-debug", "gigachat35-debug")


@pytest.mark.parametrize("name", DEBUG_FAMILIES + ("kimi-k2.5-ep32", "gigachat3.5-432b-a28b-ep16"))
def test_the_shapes_decide_which_read_a_family_names(name):
    """The published widths tile the chip and name the path; the debug
    presets' do not (heads of 16, a latent of 32) and name None: their
    walks then serve the XLA loop whatever the engine resolved."""
    fam, cfg = registry.resolve(name)
    resolved = fam.resolve_kernels(cfg, "compiled")
    assert fam.resolve_kernels(cfg, None)["latent_chunk"] is None
    if name in DEBUG_FAMILIES:
        assert resolved["latent_chunk"] is None
        assert not la.chunk_read_supported(cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                                           cfg.kv_lora_rank, cfg.latent_row)
    else:
        assert resolved["latent_chunk"] == "compiled"
        assert (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
                cfg.latent_row) == (64, 128, 64, 128, 512, 640)


@pytest.mark.parametrize("what, shapes", [
    ("the published widths", dict()),
    ("a head of 64", dict(dn=64)), ("a value of 192", dict(Dv=192)), ("a latent of 96", dict(R=96)),
    ("a row with no tail", dict(row=512)), ("a RoPE key wider than the tail", dict(dr=192)),
    ("a chunk of 8", dict(T=8)), ("a page of 8", dict(page_size=8)),
])
def test_chunk_read_supported_follows_the_shapes(what, shapes):
    base = dict(dn=128, dr=64, Dv=128, R=512, row=640, T=512, page_size=128)
    assert la.chunk_read_supported(**dict(base, **shapes)) == (not shapes), what


def test_a_walk_handed_the_path_at_widths_that_do_not_tile_serves_the_xla_loop():
    """``kimik2-debug`` given ``latent_chunk='interpret'``: the same bits
    as without, and the counts say which read served."""
    cfg = kimik2.PRESETS["kimik2-debug"]
    params = kimik2.init_params_fast(cfg, 0, jnp.float32)
    caches = kimik2.init_paged_cache(cfg, 9, 16, 1, jnp.float32)
    tables = jnp.asarray(1 + np.arange(8)[None, :], jnp.int32)
    row = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, size=(1, 32)), jnp.int32)
    one = lambda n: jnp.asarray([n], jnp.int32)  # noqa: E731
    walk = lambda kind: kimik2.extend_paged(  # noqa: E731
        params, cfg, caches, row, one(0), one(32), one(0), tables, 128, 16, latent_chunk=kind)
    (h0, c0), (h1, c1) = walk(None), walk("interpret")
    assert np.array_equal(np.asarray(h0), np.asarray(h1))
    stats = dict(zip(kimik2.STAT_NAMES, np.asarray(c1["stats"]).tolist()))
    assert stats["latent_chunk_kernel_layers"] == 0 and stats["latent_chunk_xla_layers"] == cfg.num_layers
