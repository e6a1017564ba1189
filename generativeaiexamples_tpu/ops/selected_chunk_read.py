"""Pallas TPU kernel: a CHUNK's block-sparse GQA read over head-major pages.

The chunk walk of a block-sparse family (models/minimaxm3.py) gives every
query its own selection of pages, ``K`` page numbers a (query, KV head),
and its causal clamp. Hundreds of queries between them select nearly
every page of a row, so the read is a walk of the row's LIVE pages under
a mask, not a gather of each query's own (PERF.md section 7: the gather's
bytes and its products of one group's rows beat the masked walk only
past ~25 k of context). What the XLA loop it replaces
(``_attend_selected_blocks``) paid for was the float32 score tensor of a
block, ``[N, Hk, G, T, W]``, crossing HBM for the mask, the maximum, the
exponential, the sum and the rounded copy. Here a grid step is one
(row, query tile, block of pages) of one KV head: the head's ``[page,
Dh]`` key and value strips of the block's pages come from the pools
``[P, Hk, page, Dh]``, the group's ``G`` query heads share them, and the
scores, the mask, the probabilities and the running maximum, sum and
accumulator stay in VMEM across a tile's blocks. Only ``q``, the strips
and the output cross HBM.

The scores are held TRANSPOSED, ``[keys, queries]``: a query is a lane,
so a head's running maximum and sum are one ``[1, Tq]`` row (not a
column padded to 128 lanes), the reductions over keys are element-wise
across registers and never across lanes, and the mask, rebuilt in the
kernel from the tile's page numbers, is one ROW a page ("did query t
select page p") broadcast along the page's keys. A head's softmax is
updated ONCE a block of four pages, as the loop it replaces updates its
own, so the probabilities are rounded against the same running maximum:
the scores pass through VMEM a page at a time (a ``[page, Tq]`` tile
stays in the vector registers from the product to the store, and again
from the load to the exponential), first every head's scores and
maxima, then every head's exponentials and value products, so that
neighbouring heads' work overlaps (PERF.md section 6, PR 53: the first
form, one ``[G * Tq, W]`` product a step with queries on sublanes, spent
its time spilling).

The steps are a run-time list of live (row, tile, block) items
(``chunk_work_list``), as far as ``n_tokens`` and the tile's last
position reach, built once a chunk walk and shared by the layers. What
depends on a LAYER's selection rides beside it (``chunk_live_steps``):
for each (KV head, item) the last item at or before it whose block some
query of the tile selected. An item that is not its own is skipped: no
products, and its strips' block indices repeat the last live item's, so
nothing is fetched for it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
_NEG_INF = -1e30

# queries a tile holds where the chunk is whole tiles of that many, else one lane tile; cached tokens a grid step
# fetches: four 128-token pages (PERF.md section 6, PR 53 has the sweeps)
QUERY_TILE = 256
_BLOCK_TOKENS = 512
# what a step may hold in VMEM; the default scoped limit (16 MB) is under the published group's need (~17 MB)
_VMEM_LIMIT = 64 * 1024 * 1024


class ChunkWork(NamedTuple):
    """The work list of one chunk read: item ``i < n_work[0]`` is block
    ``block[i]`` (``block_pages`` consecutive logical pages) under query
    tile ``tile[i]`` of row ``row[i]``; place ``k`` of it is pool page
    ``phys[i * block_pages + k]``. Rows ascend, tiles ascend inside a
    row, blocks ascend inside a tile from 0; every (row, tile) has at
    least one item (it writes the tile's output block). ``flags[i]`` has
    bit 0 set where the block reaches past the tile's first position (a
    causal compare is needed) and bit 1 on a tile's last item. A place
    past the table's end repeats the table's last page: no query selects
    its logical page. Entries past ``n_work`` are in-bounds padding."""

    n_work: jax.Array  # [1] int32: items
    row: jax.Array  # [M] int32, M = N * tiles * ceil(Pmax / block_pages)
    tile: jax.Array  # [M]
    block: jax.Array  # [M]
    flags: jax.Array  # [M]
    phys: jax.Array  # [M * block_pages]


def query_tile(T: int) -> Optional[int]:
    """Queries a tile of a chunk of ``T`` holds (whole lane tiles: a
    query is a lane of the scores); None where the chunk does not cut
    into them."""
    return QUERY_TILE if T % QUERY_TILE == 0 else _LANE if T % _LANE == 0 else None


def block_pages(page_size: int, max_pages: int) -> int:
    """Pages a grid step covers."""
    return max(1, min(_BLOCK_TOKENS // page_size, max_pages))


def supported(page_size: int, head_dim: int, num_heads: int, num_kv_heads: int, T: Optional[int] = None) -> bool:
    """True where ``selected_chunk_read``'s tiling applies, from the
    shapes alone: the head size and the page whole lane tiles (a page's
    keys are whole tiles of the transposed scores and of the products),
    whole groups a KV head; a chunk width ``T``, where it is known, of
    whole query tiles; a group whose queries, accumulators, output block
    and one block of scores VMEM holds."""
    if num_kv_heads < 1 or num_heads % num_kv_heads or head_dim % _LANE or page_size % _LANE:
        return False
    if T is not None and query_tile(T) is None:
        return False
    # of one (KV head, tile): q and the output block (two buffers each), the accumulators, a block's float32 scores
    return num_heads // num_kv_heads * QUERY_TILE * (head_dim * 16 + max(_BLOCK_TOKENS, page_size) * 4) <= _VMEM_LIMIT // 2


def chunk_work_list(tables, positions, n_tokens, page_size: int, pool_pages: int) -> ChunkWork:
    """Flatten the LIVE blocks of every (row, query tile) into the list
    ``selected_chunk_read`` walks: a tile walks as far as its last
    position and the row's ``n_tokens`` [N] reach (a tile none of whose
    queries is valid, and a row with ``n_tokens == 0``, walk one block).
    ``positions`` [N, T] never fall along a row. Pure ``jnp``: a chunk
    walk computes it once and every layer's read shares it."""
    N, Pmax = tables.shape
    T = positions.shape[1]
    tq = query_tile(T)
    nt = T // tq
    bp = block_pages(page_size, Pmax)
    W = bp * page_size
    per_tile = -(-Pmax // bp)
    pos = positions.astype(jnp.int32).reshape(N, nt, tq)
    first, last = pos[:, :, 0], pos[:, :, -1]
    n_tok = n_tokens.astype(jnp.int32)[:, None]
    reach = jnp.where(first < n_tok, jnp.minimum(last + 1, n_tok), 0)
    nb = jnp.clip((reach + W - 1) // W, 1, per_tile).reshape(-1)  # [N * nt]
    ends = jnp.cumsum(nb)
    item = jnp.arange(N * nt * per_tile, dtype=jnp.int32)
    pair = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), N * nt - 1)
    block = jnp.minimum(item - (ends - nb)[pair], nb[pair] - 1)
    row, tile = pair // nt, pair % nt
    place = jnp.minimum(block[:, None] * bp + jnp.arange(bp, dtype=jnp.int32)[None, :], Pmax - 1)
    phys = jnp.clip(tables.astype(jnp.int32)[row[:, None], place], 0, pool_pages - 1)
    causal = (block + 1) * W - 1 > first.reshape(-1)[pair]
    flags = causal.astype(jnp.int32) + 2 * (block == nb[pair] - 1).astype(jnp.int32)
    return ChunkWork(ends[-1:], row, tile, block, flags, phys.reshape(-1))


def chunk_live_steps(work: ChunkWork, sel_pages, sel_valid) -> Tuple[jax.Array, jax.Array]:
    """What one LAYER's selection adds to the list: ``src`` [Hk * M]
    int32, for (KV head, item) the last item at or before it whose block
    holds a page some query of its tile selected (``sel_pages`` /
    ``sel_valid`` [N, T, Hk, K]); an item is LIVE where that is itself.
    Block 0 of a tile is always live (every query reads page 0, and the
    running softmax starts there). Also the count of live (head, item)
    pairs among the list's ``n_work``."""
    N, T, Hk, K = sel_pages.shape
    M = work.row.shape[0]
    bp = work.phys.shape[0] // M
    nt = T // query_tile(T)
    per_tile = M // (N * nt)
    blk = jnp.where(sel_valid, sel_pages // bp, -1).reshape(N, nt, T // nt, Hk, K)
    hit = jnp.any(blk[..., None] == jnp.arange(per_tile, dtype=jnp.int32), axis=(2, 4))  # [N, nt, Hk, per_tile]
    live = hit[work.row, work.tile, :, work.block].T | (work.block == 0)[None, :]  # [Hk, M]
    item = jnp.arange(M, dtype=jnp.int32)
    src = lax.cummax(jnp.where(live, item[None, :], 0), axis=1)
    n_live = jnp.sum(live & (item < work.n_work[0])[None, :], dtype=jnp.int32)
    return src.reshape(-1), n_live


def _kernel(row_ref, tile_ref, blk_ref, flags_ref, phys_ref, src_ref, q_ref, pos_ref, sel_ref, *rest,
            scale: float, bp: int, page: int, group: int, items: int):
    del row_ref, tile_ref, phys_ref  # consumed by the index maps only
    kv, o_ref, (m_ref, l_ref, acc_ref, sc_ref) = rest[:2 * bp], rest[2 * bp], rest[2 * bp + 1:]
    h, i = pl.program_id(0), pl.program_id(1)
    j, flags = blk_ref[i], flags_ref[i]
    tq = m_ref.shape[1]
    Dh = acc_ref.shape[0] // group

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def read(causal: bool):
        rel = sel_ref[0, 0] - j * bp  # [Kp, tq]: the place's page inside this block, where it is one

        def mask(p):  # what is added to page p's scores: 0 or -1e30, which leaves exactly -1e30 as a select would
            ok = jnp.max(jnp.where(rel == p, 1, 0), axis=0, keepdims=True) > 0  # [1, tq]: each query's OWN selection
            if causal:
                ok = ok & ((j * bp + p) * page + lax.broadcasted_iota(jnp.int32, (page, tq), 0) <= pos_ref[0])
            return jnp.where(ok, 0.0, _NEG_INF)  # one row a page, or [page, tq] under the causal clamp

        bias = [mask(p) for p in range(bp)]
        vT = [kv[2 * p + 1][0, 0].T for p in range(bp)]  # [Dh, page]
        m_new = []
        for g in range(group):  # the group's heads share the strips; consecutive heads do not depend on each other
            q = q_ref[0, 0, 0, g * tq:(g + 1) * tq, :]
            m = m_ref[g:g + 1, :]
            for p in range(bp):  # a page of scores at a time: [page, tq] stays in registers on its way to VMEM
                sc = lax.dot_general(kv[2 * p][0, 0], q, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32) * scale + bias[p]
                sc_ref[g, p * page:(p + 1) * page, :] = sc
                m = jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
            m_new.append(m)
        # ONE update a block, where the loop this replaces has its own: the probabilities are rounded against the
        # same running maximum. Block 0 holds position 0 of page 0, which every query selects: m is finite wherever
        # a score is masked
        for g in range(group):
            total = jnp.zeros((1, tq), jnp.float32)
            out = jnp.zeros((Dh, tq), jnp.float32)
            for p in range(bp):
                prob = jnp.exp(sc_ref[g, p * page:(p + 1) * page, :] - m_new[g])
                total = total + jnp.sum(prob, axis=0, keepdims=True)
                # rounded to the pool's dtype once, before the value product
                out = out + jnp.dot(vT[p], prob.astype(vT[p].dtype), preferred_element_type=jnp.float32)
            alpha = jnp.exp(m_ref[g:g + 1, :] - m_new[g])
            l_ref[g:g + 1, :] = alpha * l_ref[g:g + 1, :] + total
            m_ref[g:g + 1, :] = m_new[g]
            acc_ref[g * Dh:(g + 1) * Dh, :] = acc_ref[g * Dh:(g + 1) * Dh, :] * alpha + out

    live = src_ref[h * items + i] == i
    pl.when(live & (flags % 2 == 1))(functools.partial(read, True))
    pl.when(live & (flags % 2 == 0))(functools.partial(read, False))

    @pl.when(flags // 2 == 1)
    def _finish():
        for g in range(group):
            l = l_ref[g:g + 1, :]
            o = acc_ref[g * Dh:(g + 1) * Dh, :] / jnp.where(l == 0.0, 1.0, l)  # [Dh, tq]
            o_ref[0, :, g * Dh:(g + 1) * Dh] = o.T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selected_chunk_read(q, k, v, positions, sel_pages, sel_valid, work: ChunkWork, src, *, interpret: bool = False):
    """A chunk's queries against the keys each selected, up to its own
    position: ``softmax_s(q . k_s * Dh ** -0.5) v_s`` over the tokens
    ``s <= positions[n, t]`` of the pages ``sel_pages[n, t, h]`` names
    where ``sel_valid`` (place 0 is page 0, always valid), and over
    nothing else. bfloat16 products with float32 accumulation, float32
    scores, the probabilities rounded to the pool's dtype once before
    the value product; a query with nothing to read returns 0.

    q [N, T, Hq, Dh] (the pool's dtype); k, v [P, Hk, page, Dh]
    head-major pools; positions [N, T]; sel_pages / sel_valid [N, T, Hk,
    K]; ``work`` from ``chunk_work_list`` and ``src`` from
    ``chunk_live_steps`` for THIS selection. Returns [N, T, Hq, Dh]
    float32.

    Grid ``(Hk, items)``: a step is one block of ``block_pages`` pages
    under one query tile of one row, for one KV head; the items are a
    run-time count. T is whole query tiles (``query_tile``)."""
    N, T, Hq, Dh = q.shape
    P, Hk, page, _ = k.shape
    G = Hq // Hk
    K = sel_pages.shape[-1]
    M = work.row.shape[0]
    bp = work.phys.shape[0] // M
    tq = query_tile(T)
    nt = T // tq
    dt = k.dtype
    # a tile's rows: head-major inside the group, so the output's [tq, Dh] slabs are whole
    qt = jnp.transpose(q.astype(dt).reshape(N, nt, tq, Hk, G, Dh), (0, 3, 1, 4, 2, 5)).reshape(N, Hk, nt, G * tq, Dh)
    Kp = -(-K // 8) * 8
    sel = jnp.where(sel_valid, sel_pages.astype(jnp.int32), -1)  # a place that holds no page matches none
    sel = jnp.pad(jnp.transpose(sel, (0, 2, 3, 1)), ((0, 0), (0, 0), (0, Kp - K), (0, 0)), constant_values=-1)  # [N, Hk, Kp, T]
    pos = positions.astype(jnp.int32)[:, None, :]

    def strip(p):  # place p of the item's block, or of the last live item's: then nothing is fetched
        return pl.BlockSpec((1, 1, page, Dh),
                            lambda h, i, rw, tl, blk, fl, phys, src: (phys[src[h * M + i] * bp + p], h, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=Dh ** -0.5, bp=bp, page=page, group=G, items=M),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(Hk, work.n_work[0]),
            in_specs=[
                pl.BlockSpec((1, 1, 1, G * tq, Dh), lambda h, i, rw, tl, *_: (rw[i], h, tl[i], 0, 0)),
                pl.BlockSpec((1, 1, tq), lambda h, i, rw, tl, *_: (rw[i], 0, tl[i])),
                pl.BlockSpec((1, 1, Kp, tq), lambda h, i, rw, tl, *_: (rw[i], h, 0, tl[i])),
                *[strip(p) for p in range(bp) for _ in range(2)],
            ],
            out_specs=pl.BlockSpec((1, tq, G * Dh), lambda h, i, rw, tl, *_: (rw[i], tl[i], h)),
            scratch_shapes=[
                pltpu.VMEM((G, tq), jnp.float32),  # a head's running maximum: a row, a query a lane
                pltpu.VMEM((G, tq), jnp.float32),  # and sum
                pltpu.VMEM((G * Dh, tq), jnp.float32),  # the accumulators, transposed like the scores
                pltpu.VMEM((G, bp * page, tq), jnp.float32),  # a block's scores, between their maximum and their exponential
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, T, Hq * Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # NOT a name the benchmark's matches find ("selected_page_attention", "paged_attention", ...): those
        # metrics read the decode kernels alone
        name="selected_chunk_read",
    )(work.row, work.tile, work.block, work.flags, work.phys, src,
      qt, pos, sel, *[buf for _ in range(bp) for buf in (k, v)])
    return out.reshape(N, T, Hq, Dh)
