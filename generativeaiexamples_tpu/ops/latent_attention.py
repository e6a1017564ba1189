"""Pallas TPU kernel: decode attention over a head-less latent page pool.

A latent-attention layer served absorbed caches ONE row a token, the
compressed latent ``c`` (``[P, page, R]``: no head axis), and every
query head reads that same row twice: as its key (``qlat_h . c_s``) and
as its value (``sum_s p_s c_s``). So a page is fetched ONCE a step and
feeds both products for all heads: multi-query attention whose K and V
are the same bytes.

Which cached tokens a query may read is decided outside (a learned
indexer's top-k groups plus the open tail, models/glm5next.py) and
arrives as an additive float32 ``bias`` ``[B, Pmax * page]`` (0 or
-1e30; causality is folded in). The walk is ``ops/page_attention.py``'s
flat work list of each row's live pages (``page_work_list``), N
CONSECUTIVE pages of a row a grid step (``latent_pages_per_step``, from
the shapes; ``decode_work_list`` builds the list a step's layers share):
each place of a step is its own block operand of the pool, a dead place
(past the row's last live page) repeats the page its place last held so
that nothing is fetched for it, and the step's bias is one ``[1, 1, N *
page]`` block. The N pages are scored into ONE ``[H, N * page]`` float32
tile: one running max, one exponential pass, one row sum and one rescale
of the running ``[H, value]`` sum a STEP, N value products; the running
softmax state lives in VMEM scratch, reset at a row's first step and
normalised into the row's output at its last. One page a step was one
dependent chain with nothing beside it to fill the issue slots, and paid
a step's fixed cost (~0.4 us before its bytes arrive: one step's fetches
are in flight at a time) for 0.2 us of bytes: 0.64 us a page at one page
a step, 0.29 at eight (PERF.md section 6, PR 51). At the contexts one
chip serves (<= 8k) reading every live page and masking costs less than
a gather of two thousand 4 KB slabs a row: the selection saves score
columns here, not page fetches (PERF.md).

``dense_latent_attention`` is the second entry point over the same walk
and the same kernel body, for a latent layer that carries a decoupled
RoPE key and selects nothing (models/gigachat35.py, models/kimik2.py):
the cached row is ``[c | k_rope | padding]``, WIDER than the value,
which is its first ``value_dim`` columns; every cached token up to the
query's own position is read, so causality comes from the positions the
kernel already prefetches and no bias is built.

``latent_chunk_read`` is the third, for the CHUNK walk of the same
layers (models/gigachat35.py ``_attend_expanded``, which
models/kimik2.py calls in every layer): hundreds of queries a row read
the row's context EXPANDED, per-head keys ``c W_uk[h]^T`` and values ``c
W_uv[h]`` rebuilt from a block of cached rows, because the expansion is
shared by the chunk's queries and an absorbed query is four times as
wide (at 512 queries expanded is half the operations of absorbed; under
~170 absorbed wins, PERF.md section 7). The walk is the same idea one
size up: a flat list of the live BLOCKS of eight pages
(``chunk_work_list``), the running softmax in VMEM scratch across a
row's blocks, a group of heads a step sharing the fetched block. The
expanded keys and values, the float32 scores and the probabilities never
leave VMEM: on XLA they were 300-470 MB of HBM traffic for every 512
cached tokens, which bound the loop at a quarter of the MXU's peak.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.ops.page_attention import PageWork, page_work_list

_LANE = 128
_NEG_INF = -1e30


# what the pages of one decode step may hold in VMEM, one buffer of the pipeline's two
_DECODE_STEP_BYTES = 2 << 20
# pages a decode step walks where the bytes and the table allow. Measured on the chip, kernel alone, rows of
# 512-640 bfloat16 columns (PERF.md section 6, PR 51): a step costs ~0.42 us before its bytes arrive and 0.2 us a
# page, so 0.64 / 0.43 / 0.32 / 0.29 / 0.29 us a live page at 1 / 2 / 4 / 8 / 16 over contexts of 1-20 k, and
# 1.50 / 1.39 / 1.31 / 1.34 / 1.77 over contexts of 1-8 pages, where a step's dead places still cost their arithmetic
_DECODE_STEP_PAGES = 8


def latent_pages_per_step(page_size: int, row_width: int, dtype, max_pages: int) -> int:
    """Pages of one row a grid step of ``latent_attention`` /
    ``dense_latent_attention`` walks (``N``), from what is static: the
    page's bytes (a page of 128 rows of 640 bfloat16 columns is 163,840
    B; eight of them twice over, the pipeline's two buffers, are 2.6 MB
    of the scoped VMEM's 16) and the table's width, which N divides so
    that a step's bias is one block (``latent_attention``). The model
    files build their work list with it (``decode_work_list``) and the
    engine counts its decode spans' ``kv_page_steps`` with it."""
    page_bytes = page_size * row_width * jnp.dtype(dtype).itemsize
    n = _DECODE_STEP_PAGES
    while n > 1 and (max_pages % n or n * page_bytes > _DECODE_STEP_BYTES):
        n //= 2
    return n


def decode_work_list(pool, tables, positions) -> PageWork:
    """The list both decode reads walk over ``pool`` [P, page, W]: each
    row's live pages up to its query's position, ``latent_pages_per_step``
    consecutive pages an item (``ops/page_attention.py``
    ``page_work_list``: a dead row keeps one step, a dead place repeats
    the page its place last held). A step builds it once and every
    layer's read shares it."""
    _, page, W = pool.shape
    return page_work_list(tables, positions.astype(jnp.int32), 1, page,
                          latent_pages_per_step(page, W, pool.dtype, tables.shape[1]))


def _decode_kernel(row_ref, first_ref, phys_ref, pos_ref, q_ref, *rest, scale: float, page: int, n: int,
                   value_dim: int, biased: bool):
    """One step of the decode walk: the ``n`` consecutive pages
    ``first_ref[i] ..`` of row ``row_ref[i]``, scored into ONE ``[H, n *
    page]`` tile: one running max, one exponential pass, one row sum and
    one rescale of the running sum a step, ``n`` value products.
    ``biased``: what a query may read arrives as an additive bias block
    (a page may hold nothing selected); else every position up to the
    query's own is read, and a step always holds one (its first page is
    live, a dead row's holds position 0)."""
    del phys_ref
    pages, b_ref, (o_ref, m_ref, l_ref, acc_ref) = rest[:n], rest[n] if biased else None, rest[-4:]
    i = pl.program_id(0)
    first = first_ref[i]
    last_tok = pos_ref[row_ref[i]]

    @pl.when(first == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]
    # a place's page [page, W] is the key; its first value_dim columns are the value
    sc = jnp.concatenate([lax.dot_general(q, p[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                          for p in pages], axis=1) * scale  # [H, n * page]
    if biased:
        sc = sc + b_ref[0]  # [1, n * page]: 0 or -1e30, causality and the dead places folded in
    else:
        sc = jnp.where(first * page + lax.broadcasted_iota(jnp.int32, sc.shape, 1) <= last_tok, sc, _NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    prob = jnp.exp(sc - m_new)
    if biased:
        # a step with nothing selected leaves m at -1e30: exp(sc - m) would be 1
        prob = jnp.where(sc > 0.5 * _NEG_INF, prob, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(alpha * l_ref[:, :1] + jnp.sum(prob, axis=1, keepdims=True), l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    prob = prob.astype(pages[0].dtype)  # rounded to the pool's dtype before the value product
    acc_ref[...] = acc_ref[...] * alpha + sum(
        jnp.dot(prob[:, k * page:(k + 1) * page], p[0][:, :value_dim], preferred_element_type=jnp.float32)
        for k, p in enumerate(pages))

    @pl.when((first + n) * page > last_tok)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _decode_walk(q, pool, bias, tables, positions, work: Optional[PageWork], *, value_dim: int, scale: float,
                 interpret: bool, name: str):
    """Both decode entry points: ``q`` [B, H, W] against ``pool`` [P, page,
    W] over ``work`` (``decode_work_list`` where not given; its own
    shapes say how many pages a step it carries)."""
    B, H, W = q.shape
    P, page, _ = pool.shape
    Pmax = tables.shape[1]
    pos = positions.astype(jnp.int32)
    if work is None:
        work = decode_work_list(pool, tables, pos)
    n = work.phys.shape[0] // work.row.shape[0]
    in_specs = [pl.BlockSpec((1, H, W), lambda i, row, first, phys, pos: (row[i], 0, 0))]
    operands = [q]
    # the pool once a place of the step: Pallas's own pipeline double-buffers the n pages, and a dead place
    # names the page its place last held, so nothing is fetched for it
    in_specs += [pl.BlockSpec((1, page, W), lambda i, row, first, phys, pos, k=k: (phys[i * n + k], 0, 0))
                 for k in range(n)]
    operands += [pool] * n
    if bias is not None:
        assert Pmax % n == 0, (Pmax, n)  # a step's bias is one block
        in_specs.append(pl.BlockSpec(
            (1, 1, n * page), lambda i, row, first, phys, pos: (row[i] * (Pmax // n) + first[i] // n, 0, 0)))
        operands.append(bias.reshape(B * Pmax // n, 1, n * page))
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page=page, n=n, value_dim=value_dim, biased=bias is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(work.n_work[0],),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, value_dim), lambda i, row, first, phys, pos: (row[i], 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, _LANE), jnp.float32),
                pltpu.VMEM((H, _LANE), jnp.float32),
                pltpu.VMEM((H, value_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the benchmark's readers find both kernels by "latent_attention" in the operation's name
        name=name,
    )(work.row, work.page, work.phys, pos, *operands)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_attention(q, pool, bias, tables, positions, *, scale: float, interpret: bool = False,
                     work: Optional[PageWork] = None):
    """q [B, H, R] (absorbed queries, the pool's dtype), pool [P, page, R],
    bias [B, Pmax * page] float32, tables [B, Pmax], positions [B]
    (the query's own position; its row is already in the pool).
    Returns sum_s softmax_s(q . c_s * scale + bias_s) c_s: [B, H, R] float32."""
    return _decode_walk(q, pool, bias, tables, positions, work, value_dim=q.shape[2], scale=scale,
                        interpret=interpret, name="latent_attention")


@functools.partial(jax.jit, static_argnames=("value_dim", "scale", "interpret"))
def dense_latent_attention(q, pool, tables, positions, *, value_dim: int, scale: float,
                           interpret: bool = False, work: Optional[PageWork] = None):
    """q [B, H, W] (absorbed queries ``[W_uk^T q_nope | q_rope | 0]``, the
    pool's dtype), pool [P, page, W] (rows ``[c | k_rope | 0]``), tables
    [B, Pmax], positions [B] (the query's own position; its row is already
    in the pool). Returns ``sum_s softmax_s(q . row_s * scale) c_s`` over
    every s <= position, c_s the row's first ``value_dim`` columns:
    [B, H, value_dim] float32."""
    return _decode_walk(q, pool, None, tables, positions, work, value_dim=value_dim, scale=scale,
                        interpret=interpret, name="latent_attention_dense")


# --------------------------------------------------------------------- //
# The chunk walk's read: keys and values EXPANDED from the latent, in VMEM


class ChunkWork(NamedTuple):
    """The work list of one chunk read: step ``i < n_work[0]`` carries
    block ``block[i]`` (``block_pages`` consecutive logical pages) of row
    ``row[i]``; place ``k`` of it is pool page ``phys[i * block_pages +
    k]``. Rows ascend, blocks ascend inside a row, every row has at least
    one step (a dead row's writes its output block). A place past the
    table's end repeats the table's last page: its positions lie past
    every query's. Entries past ``n_work`` are in-bounds padding."""

    n_work: jax.Array  # [1] int32: steps
    row: jax.Array  # [N * ceil(Pmax / block_pages)] int32
    block: jax.Array  # the same length
    phys: jax.Array  # that length x block_pages
    n_blocks: jax.Array  # [N] int32: blocks of each row


# cached tokens a grid step expands and scores: eight 128-token pages. At 512 a (head, block) costs
# 2.4 us and a step 1.1 us of its own, at 1,024 2.15 us a 512 tokens and 0.3 us (PERF.md section 6, PR 50)
_CHUNK_BLOCK_TOKENS = 1024
# what a step may hold in VMEM; the default scoped limit (16 MB) is under a wide step's need
_CHUNK_VMEM_LIMIT = 64 * 1024 * 1024


def chunk_block_pages(page_size: int, max_pages: int) -> int:
    """Pages a grid step of ``latent_chunk_read`` covers."""
    return max(1, min(_CHUNK_BLOCK_TOKENS // page_size, max_pages))


def chunk_work_list(tables, n_tokens, page_size: int, pool_pages: int) -> ChunkWork:
    """Flatten each row's LIVE blocks, as far as ``n_tokens`` [N] reach,
    into the list ``latent_chunk_read`` walks: ``sum_n max(1, ceil(n_tokens
    / block))`` steps, not ``N * Pmax / block_pages``. Pure ``jnp``: a
    chunk walk computes it once and every layer's read shares it."""
    N, Pmax = tables.shape
    bp = chunk_block_pages(page_size, Pmax)
    W = bp * page_size
    per_row = -(-Pmax // bp)
    nb = jnp.clip((n_tokens.astype(jnp.int32) + W - 1) // W, 1, per_row)
    ends = jnp.cumsum(nb)
    item = jnp.arange(N * per_row, dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), N - 1)
    block = jnp.minimum(item - (ends - nb)[row], nb[row] - 1)
    place = jnp.minimum(block[:, None] * bp + jnp.arange(bp, dtype=jnp.int32)[None, :], Pmax - 1)
    phys = jnp.clip(tables.astype(jnp.int32)[row[:, None], place], 0, pool_pages - 1)
    return ChunkWork(ends[-1:], row, block, phys.reshape(-1), nb)


def chunk_heads_per_step(H: int) -> int:
    """Heads that share a fetched block: the block's DMA and the step's own
    overhead are paid once for them, against a running state of ``[T, Dv]``
    float32 (and the same again for the output block) a head in VMEM."""
    for g in (4, 2):
        if H % g == 0:
            return g
    return 1


def chunk_read_supported(dn: int, dr: int, Dv: int, R: int, row: int, T: Optional[int] = None,
                         page_size: Optional[int] = None) -> bool:
    """True where ``latent_chunk_read``'s tiling applies, from the shapes
    alone: head sizes and the latent whole lane tiles, the cached row's
    tail ``[k_rope | 0]`` whole lane tiles that hold the RoPE key; a chunk
    width ``T`` and a page, where they are known, whole sublane tiles of a
    bfloat16 operand."""
    widths = (dn % _LANE == 0 and Dv % _LANE == 0 and R % _LANE == 0 and row > R and (row - R) % _LANE == 0
              and dr <= row - R)
    return widths and all(n is None or n % 16 == 0 for n in (T, page_size))


def _chunk_kernel(row_ref, blk_ref, phys_ref, nb_ref, first_ref, q_ref, pos_ref, wuk_ref, wuv_ref, *rest,
                  scale: float, bp: int, page: int, heads: int, R: int, Dv: int):
    del phys_ref
    pages, o_ref, (m_ref, l_ref, acc_ref) = rest[:bp], rest[bp], rest[bp + 1:]
    i = pl.program_id(1)
    r, j = row_ref[i], blk_ref[i]
    W = bp * page
    T = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def read(masked: bool):
        c = jnp.concatenate([p[0] for p in pages], axis=0)  # [W, row]
        latent, tail = c[:, :R], c[:, R:]  # the tail: [k_rope | 0]
        if masked:
            ok = j * W + lax.broadcasted_iota(jnp.int32, (T, W), 1) <= pos_ref[0]
        for h in range(heads):
            # the expansion, rounded to the pool's dtype as the XLA walk rounds it
            kn = lax.dot_general(latent, wuk_ref[h], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32).astype(c.dtype)  # [W, dn]
            vh = jnp.dot(latent, wuv_ref[h], preferred_element_type=jnp.float32).astype(c.dtype)  # [W, Dv]
            # ONE product over [q_nope | q_rope | 0] . [kn | k_rope | 0]: no second score tensor to add
            sc = lax.dot_general(q_ref[0, h], jnp.concatenate([kn, tail], axis=1), (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale  # [T, W]
            if masked:
                sc = jnp.where(ok, sc, _NEG_INF)
            m_prev = m_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            # block 0 holds position 0, which every query may read: m is finite wherever a score is masked
            prob = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = jnp.broadcast_to(alpha * l_ref[h, :, :1] + jnp.sum(prob, axis=1, keepdims=True), l_ref.shape[1:])
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(prob.astype(c.dtype), vh, preferred_element_type=jnp.float32)

    # only a block that reaches past the chunk's first position can hold a key some query may not read
    reaches = (j + 1) * W - 1 > first_ref[r]
    pl.when(reaches)(functools.partial(read, True))
    pl.when(jnp.logical_not(reaches))(functools.partial(read, False))

    @pl.when(j == nb_ref[r] - 1)
    def _finish():
        for h in range(heads):
            l = l_ref[h, :, :1]
            o_ref[0, :, h * Dv:(h + 1) * Dv] = (acc_ref[h] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "heads_per_step"))
def latent_chunk_read(q_nope, q_rope, pool, tables, positions, n_tokens, wuk, wuv, *, scale: float,
                      interpret: bool = False, work: Optional[ChunkWork] = None,
                      heads_per_step: Optional[int] = None):
    """A chunk's queries against each row's cached rows up to every
    query's own position, the per-head keys and values EXPANDED from the
    latent inside the kernel: for a block of cached tokens, ``kn = c
    W_uk[h]^T`` and ``vh = c W_uv[h]`` (rounded to the pool's dtype), the
    scores ``q_nope . kn + q_rope . k_rope`` in float32, a running softmax
    across the row's blocks in VMEM scratch, the probabilities rounded
    once before the value product. Neither the expanded keys and values
    nor a score leaves the chip.

    q_nope [N, H, T, dn], q_rope [N, H, T, dr] (the pool's dtype); pool
    [P, page, row] (rows ``[c | k_rope | 0]``, ``c`` the first R = W_uk's
    last axis); tables [N, Pmax]; positions [N, T] (each query's own, never
    falling along a row); n_tokens [N] (how far a row's context reaches: 0
    walks one block); wuk [H, dn, R]; wuv [H, R, Dv]. Returns [N, T, H,
    Dv] float32.

    Grid ``(H / g, steps)``: a step is one block of ``chunk_block_pages``
    pages of one row, for ``g`` heads that share its DMA
    (``chunk_heads_per_step``); the steps are ``chunk_work_list``'s, the
    live blocks only, a run-time count. A block wholly under the chunk's
    first position is read unmasked."""
    N, H, T, dn = q_nope.shape
    P, page, row = pool.shape
    R, Dv = wuk.shape[2], wuv.shape[2]
    tail = row - R
    Pmax = tables.shape[1]
    bp = chunk_block_pages(page, Pmax)
    g = heads_per_step or chunk_heads_per_step(H)
    assert H % g == 0, (H, g)
    if work is None:
        work = chunk_work_list(tables, n_tokens, page, P)
    dt = pool.dtype
    pad = jnp.zeros(q_rope.shape[:-1] + (tail - q_rope.shape[-1],), dt)
    q = jnp.concatenate([q_nope.astype(dt), q_rope.astype(dt), pad], axis=-1)  # [N, H, T, dn + tail]
    pos = positions.astype(jnp.int32)
    # (an index map sees the head group, the step, then the prefetched lists: row, block, phys, n_blocks, first)
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, bp=bp, page=page, heads=g, R=R, Dv=Dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(H // g, work.n_work[0]),
            in_specs=[
                pl.BlockSpec((1, g, T, dn + tail), lambda hg, i, rw, *_: (rw[i], hg, 0, 0)),
                pl.BlockSpec((1, T, 1), lambda hg, i, rw, *_: (rw[i], 0, 0)),
                pl.BlockSpec((g, dn, R), lambda hg, *_: (hg, 0, 0)),
                pl.BlockSpec((g, R, Dv), lambda hg, *_: (hg, 0, 0)),
                # the pool once a place of the block: Pallas's own pipeline double-buffers the eight pages
                *[pl.BlockSpec((1, page, row), lambda hg, i, rw, blk, phys, *_, k=k: (phys[i * bp + k], 0, 0))
                  for k in range(bp)],
            ],
            out_specs=pl.BlockSpec((1, T, g * Dv), lambda hg, i, rw, *_: (rw[i], 0, hg)),
            scratch_shapes=[
                pltpu.VMEM((g, T, _LANE), jnp.float32),
                pltpu.VMEM((g, T, _LANE), jnp.float32),
                pltpu.VMEM((g, T, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, T, H * Dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=interpret,
        # NOT a name the benchmark's "latent_attention" match finds: those metrics read the decode kernel alone
        name="latent_chunk_read",
    )(work.row, work.block, work.phys, work.n_blocks, jnp.min(pos, axis=1),
      q, pos[..., None], wuk.astype(dt), wuv.astype(dt), *([pool] * bp))
    return out.reshape(N, T, H, Dv)
