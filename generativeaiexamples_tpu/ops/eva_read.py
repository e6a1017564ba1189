"""Pallas TPU kernels: a decode row's read, and a chunk's
(``eva_chunk_read``, at the end of the file), of the open WINDOW and the
paged chunk SUMMARIES under one softmax (models/evabyte.py).

A row at position ``t`` reads two sources with different homes: the
exact rotated keys and values of its open window, rows ``0 .. t % W`` of
a dense per-slot buffer ``[slots, W, H * Dh]``, and one summary row of K
and of V for every chunk of every CLOSED window, ``(W / page_size) (t //
W)`` pages of its page table out of a pool ``[P, rows, H * Dh / 2]``
uint32. Both enter ONE running softmax (float32 maximum, sum and
accumulator in VMEM); only ``q``, the rows read and the output cross HBM.

**Every head is its own KV head** (32 of 32), so a head's query is one
row: the products run for all heads at once against the flat ``[keys, H
* Dh]`` tile with the query laid BLOCK-DIAGONALLY, ``qbd[h, h * Dh + d]
= q[h, d]`` and zero elsewhere. ``qbd [H, H * Dh] x tile^T`` is every
head's scores ``[H, keys]``; ``p [H, keys] x tile [keys, H * Dh]`` holds
head ``h``'s output in columns ``h * Dh ..`` of row ``h``, taken out once
a row at its last step. The MXU pays ``H`` times the products a head
needs and is idle otherwise: the read is bandwidth.

**A summary page is whole 32-bit tiles.** A page of ``page_size``
tokens is ``page_size / C`` summary rows (8 at the published sizes):
half a bfloat16 tile. The pool therefore stores a row as ``H * Dh / 2``
uint32 words, head ``h`` in the low half beside head ``h + H / 2`` in
the high half (``pack_rows``), so a page is one ``[8, 2048]`` 32-bit
tile, the pages of a window are laid under each other in VMEM with no
relayout, and two shifts widen them to the two halves of the heads.

**The steps** are a run-time list (``work_list``, once a decode step,
shared by the layers): a row's window tiles of ``tile`` rows as far as
its valid length reaches, then one step a closed window (its ``W /
page_size`` pages, each an operand of its own whose block index the list
names). An operand a step does not read keeps the block index of the
step before, so nothing is fetched for it. Every row has at least one
step (a dead row, whose position the engine zeroed, reads one key).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
_NEG_INF = -1e30
# buffer rows a window step fetches (2 MB of K and of V at the published widths)
WINDOW_TILE = 256


def pack_rows(x):
    """[.., H, Dh] -> uint32 [.., H * Dh / 2]: head ``h`` in the low half
    of a word, head ``h + H / 2`` in the high half, each rounded to
    bfloat16."""
    flat = x.astype(jnp.bfloat16).reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))
    bits = lax.bitcast_convert_type(flat, jnp.uint16).astype(jnp.uint32)
    half = bits.shape[-1] // 2
    return bits[..., :half] | (bits[..., half:] << 16)


def _halves(words):
    """uint32 [.., n] -> the float32 values of the low and the high halves (bfloat16 widened: exact)."""
    low = lax.bitcast_convert_type(words << 16, jnp.float32)
    high = lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000), jnp.float32)
    return low, high


def unpack_rows(words, num_heads: int):
    """uint32 [.., H * Dh / 2] -> float32 [.., H, Dh] (``pack_rows`` undone)."""
    flat = jnp.concatenate(_halves(words), axis=-1)
    return flat.reshape(flat.shape[:-1] + (num_heads, flat.shape[-1] // num_heads))


def supports(num_heads: int, head_dim: int, window: int) -> bool:
    """Whether the compiled kernel serves these widths: heads of whole
    lane tiles, whole bfloat16 sublane tiles of heads, a window of whole
    tiles. (A page must also hold 8 summary rows or a multiple: the walk
    knows ``page_size`` and says so there.)"""
    return head_dim % _LANE == 0 and num_heads % 16 == 0 and window % WINDOW_TILE == 0


class ReadWork(NamedTuple):
    """Step ``i < n_work[0]`` belongs to row ``row[i]``. ``flags[i]``:
    bit 0 its row's first step, bit 1 its last, bit 2 a SUMMARY step (a
    closed window's pages ``phys[i * ppw .. ]``), else a window step over
    buffer tile ``tile[i]``. ``valid[b]`` is the buffer rows row ``b``
    sees. Entries past ``n_work`` are in-bounds padding."""

    n_work: jax.Array  # [1] int32
    row: jax.Array  # [S] int32
    flags: jax.Array  # [S] int32
    tile: jax.Array  # [S] int32
    phys: jax.Array  # [S * ppw] int32
    valid: jax.Array  # [B] int32


def _flatten(total: jax.Array, S: int):
    """Owners with ``total[p] >= 1`` steps each, laid one after another on
    a list of ``S`` entries: (the steps in all [1], each entry's owner, its
    step inside the owner); entries past the end repeat the last owner's
    last step."""
    ends = jnp.cumsum(total)
    item = jnp.arange(S, dtype=jnp.int32)
    owner = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), total.shape[0] - 1)
    return ends[-1:].astype(jnp.int32), owner, jnp.minimum(item - (ends - total)[owner], total[owner] - 1)


def _held_pages(phys: jax.Array, is_sum: jax.Array):
    """``phys`` [S, ppw] with every window step's pages replaced by those
    of the summary step before it (an unchanged block index: no DMA);
    before any summary step: the scratch page."""
    held = lax.cummax(jnp.where(is_sum, jnp.arange(is_sum.shape[0], dtype=jnp.int32), -1), axis=0)
    return jnp.where((held >= 0)[:, None], phys[jnp.maximum(held, 0)], 0)


def work_list(tables: jax.Array, positions: jax.Array, window: int, page_size: int) -> ReadWork:
    """``tables`` [B, Pmax] int32, ``positions`` [B] int32 (the query's
    position; row ``b`` is slot ``b``). Pure ``jnp``."""
    B, pmax = tables.shape
    ppw = window // page_size  # pages a window
    tw = min(WINDOW_TILE, window)
    valid = positions % window + 1
    n_win = (valid + tw - 1) // tw
    n_sum = jnp.minimum(positions // window, pmax // ppw)
    total = n_win + n_sum
    n_work, row, j = _flatten(total, B * (window // tw + pmax // ppw))
    is_sum = j >= n_win[row]
    tile = jnp.minimum(j, n_win[row] - 1)
    closed = jnp.clip(j - n_win[row], 0, jnp.maximum(n_sum[row] - 1, 0))
    page = closed[:, None] * ppw + jnp.arange(ppw, dtype=jnp.int32)[None, :]  # [S, ppw] logical pages
    phys = _held_pages(tables.astype(jnp.int32)[row[:, None], jnp.minimum(page, pmax - 1)], is_sum)
    flags = (j == 0).astype(jnp.int32) + 2 * (j == total[row] - 1).astype(jnp.int32) + 4 * is_sum.astype(jnp.int32)
    return ReadWork(n_work, row, flags, tile.astype(jnp.int32), phys.reshape(-1), valid.astype(jnp.int32))


def _kernel(row_ref, flags_ref, tile_ref, phys_ref, valid_ref, q_ref, wk_ref, wv_ref, *refs,
            scale: float, tw: int, ppw: int, head_dim: int):
    sk, sv = refs[:ppw], refs[ppw:2 * ppw]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * ppw:]
    del phys_ref  # consumed by the index maps only
    i = pl.program_id(0)
    flags = flags_ref[i]
    H, HD = acc_ref.shape
    half = HD // 2

    @pl.when(flags % 2 == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(sc, values):
        """One softmax update over scores ``sc`` [H, n] float32 (masked
        columns at -inf) and ``values``: (column offset, [n, cols]) parts
        of the value tile."""
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        prob = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(alpha * l_ref[:, :1] + jnp.sum(prob, axis=1, keepdims=True), l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        for at, v in values:
            pv = lax.dot_general(prob.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            acc_ref[:, at:at + v.shape[1]] = acc_ref[:, at:at + v.shape[1]] * alpha + pv

    def scores(q, k):
        return lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale

    @pl.when(flags // 4 == 0)
    def _window():
        sc = scores(q_ref[0], wk_ref[0])  # [H, tw]
        at = tile_ref[i] * tw + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        update(jnp.where(at < valid_ref[row_ref[i]], sc, _NEG_INF), [(0, wv_ref[0])])

    @pl.when(flags // 4 == 1)
    def _summaries():
        q = q_ref[0]
        k_lo, k_hi = _halves(jnp.concatenate([r[0] for r in sk], axis=0) if ppw > 1 else sk[0][0])
        v_lo, v_hi = _halves(jnp.concatenate([r[0] for r in sv], axis=0) if ppw > 1 else sv[0][0])
        sc = scores(q[:, :half], k_lo.astype(q.dtype)) + scores(q[:, half:], k_hi.astype(q.dtype))
        update(sc, [(0, v_lo.astype(q.dtype)), (half, v_hi.astype(q.dtype))])

    @pl.when((flags // 2) % 2 == 1)
    def _finish():
        l = l_ref[:, :1]
        head = lax.broadcasted_iota(jnp.int32, (H, head_dim), 0)
        out = jnp.zeros((H, head_dim), jnp.float32)
        for h in range(H):  # row h of the accumulator holds head h in columns h * Dh ..
            out = out + jnp.where(head == h, acc_ref[:, h * head_dim:(h + 1) * head_dim], 0.0)
        o_ref[0] = (out / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def block_diagonal(q):
    """q [B, H, Dh] -> [B, H, H * Dh]: row ``h`` holds ``q[h]`` in columns ``h * Dh ..`` and zeros elsewhere."""
    B, H, Dh = q.shape
    eye = jnp.eye(H, dtype=q.dtype)
    return (eye[None, :, :, None] * q[:, :, None, :]).reshape(B, H, H * Dh)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def eva_decode_read(
    q: jax.Array,  # [B, H, Dh] - one query token a row, rotated
    win_k: jax.Array,  # [slots, W, H * Dh] the open windows' keys (row b is slot b)
    win_v: jax.Array,  # [slots, W, H * Dh]
    sum_k: jax.Array,  # [P, rows, H * Dh / 2] uint32 summary pages
    sum_v: jax.Array,  # [P, rows, H * Dh / 2] uint32
    work: ReadWork,  # of the rows' positions and page tables
    *,
    window: int,
    interpret: bool = False,
) -> jax.Array:
    """Attention output ``[B, H, Dh]`` float32 of each row's query, at
    the position ``p`` the work list was built from, over its buffer rows
    ``0 .. p % W`` and every summary row of the first ``p // W`` windows
    of its page table, under one softmax."""
    B, H, Dh = q.shape
    HD = H * Dh
    rows = sum_k.shape[1]
    ppw = work.phys.shape[0] // work.row.shape[0]
    tw = min(WINDOW_TILE, window)
    if not interpret and rows % 8:
        raise ValueError(f"the compiled read wants whole 32-bit tiles a summary page: 8 rows or a multiple, got {rows}")

    def window_spec():
        return pl.BlockSpec((1, tw, HD), lambda i, row, flags, tile, phys, valid: (row[i], tile[i], 0))

    def page_spec(n):
        return pl.BlockSpec((1, rows, HD // 2), lambda i, row, flags, tile, phys, valid: (phys[i * ppw + n], 0, 0))

    def row_spec(width):
        return pl.BlockSpec((1, H, width), lambda i, row, flags, tile, phys, valid: (row[i], 0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, scale=Dh ** -0.5, tw=tw, ppw=ppw, head_dim=Dh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(work.n_work[0],),
            in_specs=[row_spec(HD), window_spec(), window_spec()] + [page_spec(n) for _ in range(2) for n in range(ppw)],
            out_specs=row_spec(Dh),
            scratch_shapes=[
                pltpu.VMEM((H, _LANE), jnp.float32),
                pltpu.VMEM((H, _LANE), jnp.float32),
                pltpu.VMEM((H, HD), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="eva_decode_read",
    )(work.row, work.flags, work.tile, work.phys, work.valid, block_diagonal(q), win_k, win_v,
      *[sum_k] * ppw, *[sum_v] * ppw)


def eva_decode_read_xla(q, win_k, win_v, sum_k, sum_v, tables, positions, *, window: int, chunks_a_window: int):
    """The same read through XLA: the buffer whole under its valid
    length, the row's pages gathered under the count of its closed
    windows, one softmax over both. NOT a second serving path beside the
    kernel: what the tests hold the kernel to, and what ``decode_paged``
    falls back to where no kernel resolved (the CPU without
    ``interpret``, widths ``supports`` refuses). float32 out."""
    B, H, Dh = q.shape
    W = window
    k = jnp.concatenate([win_k[:B].reshape(B, W, H, Dh).astype(jnp.float32),
                         unpack_rows(sum_k[tables], H).reshape(B, -1, H, Dh)], axis=1)
    v = jnp.concatenate([win_v[:B].reshape(B, W, H, Dh).astype(jnp.float32),
                         unpack_rows(sum_v[tables], H).reshape(B, -1, H, Dh)], axis=1)
    n_sum = k.shape[1] - W
    seen = jnp.concatenate([
        jnp.arange(W, dtype=jnp.int32)[None, :] <= (positions % W)[:, None],
        jnp.arange(n_sum, dtype=jnp.int32)[None, :] < (positions // W * chunks_a_window)[:, None],
    ], axis=1)  # [B, W + n_sum]
    sc = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k) * (Dh ** -0.5)
    p = jax.nn.softmax(jnp.where(seen[:, None, :], sc, _NEG_INF), axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p.astype(q.dtype).astype(jnp.float32), v)


# --------------------------------------------------------------------- //
# The chunk walk's read: hundreds of queries a head
#
# The same two sources under the same one softmax, read in place through the same kind of run-time work list, but
# a head has a tile of queries, so the MXU works as in a prefill: per head ``k_tile [keys, Dh] x q^T`` and ``v_tile^T
# x p``, the heads walked inside a grid step over lane-aligned column slices of the whole ``[keys, H * Dh]`` tiles
# (the decode read's DMA pattern). The chunk's own keys are buffer rows: ``_chunk_walk`` writes them first.


# queries a tile of the chunk read holds where the chunk is whole tiles of that many, else one lane tile
QUERY_TILE = 256
# what a step may hold in VMEM: a query tile, its output block and accumulators beside the K and V tiles (~28 MB)
_CHUNK_VMEM_LIMIT = 64 * 1024 * 1024
# heads a trip of the kernel's loop over the heads holds
_HEADS_A_TRIP = 8


def chunk_query_tile(T: int) -> int:
    """Queries a tile of a chunk of ``T`` holds; a chunk that is no whole
    lane tiles is ONE tile (interpreted only: ``chunk_supports``)."""
    return QUERY_TILE if T % QUERY_TILE == 0 else _LANE if T % _LANE == 0 else T


def chunk_supports(T: int, summary_rows: int) -> bool:
    """Whether the compiled ``eva_chunk_read`` serves a chunk of ``T``
    queries over pages of ``summary_rows`` rows, beside what ``supports``
    asks of the widths: whole lane tiles of queries, whole 32-bit tiles a
    page."""
    return T % _LANE == 0 and summary_rows % 8 == 0


class ChunkReadWork(NamedTuple):
    """Step ``i < n_work[0]`` belongs to query tile ``qtile[i]`` of row
    ``row[i]``, whose buffer is slot ``slot[i]``. ``flags[i]``: bit 0 the
    tile's first step, bit 1 its last, bit 2 a SUMMARY step (a closed
    window's pages ``phys[i * ppw ..]``), else a window step over buffer
    tile ``tile[i]``; bit 3 a window step some query of the tile sees only
    part of: column ``c`` of the buffer tile is seen by query ``t`` of the
    query tile where ``c <= lim[i] + t``. Rows ascend, query tiles ascend
    inside a row, and every (row, query tile) has at least one step,
    buffer tile 0 (it writes the tile's output block). Entries past
    ``n_work`` are in-bounds padding."""

    n_work: jax.Array  # [1] int32
    row: jax.Array  # [S] int32
    slot: jax.Array  # [S]
    qtile: jax.Array  # [S]
    flags: jax.Array  # [S]
    tile: jax.Array  # [S]
    lim: jax.Array  # [S]
    phys: jax.Array  # [S * ppw]


def chunk_tiles(T: int, window: int, query_tile: Optional[int] = None, window_tile: Optional[int] = None):
    """(queries a query tile, buffer rows a window step) of a chunk of
    ``T`` over windows of ``window`` rows; a test may name smaller ones."""
    return query_tile or chunk_query_tile(T), window_tile or min(WINDOW_TILE, window)


def chunk_work_list(tables: jax.Array, slots: jax.Array, offsets: jax.Array, valid: jax.Array, T: int,
                    window: int, page_size: int, num_slots: int, pool_pages: int, *,
                    query_tile: Optional[int] = None, window_tile: Optional[int] = None) -> ChunkReadWork:
    """The steps of a chunk read AFTER the chunk's keys went to the
    buffer: ``tables`` [slots, Pmax], ``slots`` / ``offsets`` / ``valid``
    [N] (the chunk's slot, first position and valid tokens a row; the
    chunk lies inside one window). A query tile walks the buffer's tiles
    as far as its last VALID query's own row ``offsets % W + t`` reaches
    (a tile with no valid query walks tile 0: its output feeds nothing
    and stays finite), then one step a CLOSED window, ``offsets // W`` of
    them; the open window's pages are no step. Pure ``jnp``: once a chunk
    walk, shared by the layers."""
    N = slots.shape[0]
    pmax = tables.shape[1]
    ppw = window // page_size
    tq, tw = chunk_tiles(T, window, query_tile, window_tile)
    nq = T // tq
    slots = jnp.clip(slots.astype(jnp.int32), 0, num_slots - 1)
    base = (offsets % window).astype(jnp.int32)
    first = jnp.arange(nq, dtype=jnp.int32)[None, :] * tq  # [1, nq] a tile's first query
    n_valid = jnp.clip(valid.astype(jnp.int32)[:, None] - first, 0, tq)  # [N, nq] valid queries a tile
    reach = jnp.where(n_valid > 0, base[:, None] + first + n_valid, 1)  # buffer rows the tile's queries see
    n_win = ((reach + tw - 1) // tw).reshape(-1)  # [N * nq]
    n_sum = jnp.repeat(jnp.minimum(offsets.astype(jnp.int32) // window, pmax // ppw), nq)
    total = n_win + n_sum
    n_work, pair, j = _flatten(total, N * nq * (window // tw + pmax // ppw))
    row, qtile = pair // nq, pair % nq
    is_sum = j >= n_win[pair]
    tile = jnp.minimum(j, n_win[pair] - 1)
    lim = base[row] + qtile * tq - tile * tw
    partly = ~is_sum & (lim < tw - 1)  # the tile's last column lies past the first query's own row
    closed = jnp.clip(j - n_win[pair], 0, jnp.maximum(n_sum[pair] - 1, 0))
    page = closed[:, None] * ppw + jnp.arange(ppw, dtype=jnp.int32)[None, :]  # [S, ppw] logical pages
    slot = slots[row]
    phys = jnp.clip(tables.astype(jnp.int32)[slot[:, None], jnp.minimum(page, pmax - 1)], 0, pool_pages - 1)
    flags = ((j == 0).astype(jnp.int32) + 2 * (j == total[pair] - 1).astype(jnp.int32)
             + 4 * is_sum.astype(jnp.int32) + 8 * partly.astype(jnp.int32))
    return ChunkReadWork(n_work, row, slot, qtile, flags, tile.astype(jnp.int32), lim.astype(jnp.int32),
                         _held_pages(phys, is_sum).reshape(-1))


def _chunk_kernel(row_ref, slot_ref, qtile_ref, flags_ref, tile_ref, lim_ref, phys_ref, q_ref, wk_ref, wv_ref, *refs,
                  scale: float, ppw: int, heads: int):
    del row_ref, slot_ref, qtile_ref, tile_ref, phys_ref  # consumed by the index maps only
    sk, sv = refs[:ppw], refs[ppw:2 * ppw]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * ppw:]
    i = pl.program_id(0)
    flags = flags_ref[i]
    tq, HD = q_ref.shape[1:]
    Dh = HD // heads
    tw = wk_ref.shape[1]
    dt = q_ref.dtype
    cols = lambda h: pl.ds(pl.multiple_of(h * Dh, Dh), Dh)  # noqa: E731 - head h's columns: a whole lane tile or more

    def each(n, body, heads_a_call=1):
        """``body(h)`` for ``h < n``: a LOOP whose trip holds
        ``_HEADS_A_TRIP`` heads, so that one head's products overlap the
        next one's softmax, and not 32 copies of the body (PERF.md section
        6, PR 58: a trip costs ~220 bundles to fill, 32 copies ~2.3 s of
        every start to trace and lower a program)."""
        group = next(g for g in range(min(_HEADS_A_TRIP // heads_a_call, n), 0, -1) if n % g == 0)

        def trip(t, carry):
            for g in range(group):
                body(t * group + g)
            return carry

        lax.fori_loop(0, n // group, trip, 0)

    @pl.when(flags % 2 == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(h, k, v, bias=None):
        """Head ``h``'s softmax update over keys ``k`` [n, Dh] and values
        ``v`` [n, Dh]. The scores are held TRANSPOSED, [n, tq]: a query is
        a lane, so the head's running maximum and sum are one ``[1, tq]``
        row and the reductions over the keys run across registers, not
        across lanes; ``bias`` [n, tq] is 0 where a query sees a key and
        -1e30 where not (which leaves exactly -1e30, as a select would)."""
        sc = lax.dot_general(k, q_ref[0, :, cols(h)], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale  # [n, tq]
        if bias is not None:
            sc = sc + bias
        m_prev = m_ref[pl.ds(h, 1), :]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0, keepdims=True))
        # buffer tile 0 comes first and holds row 0, which every query sees: m is finite wherever a score is masked
        prob = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[pl.ds(h, 1), :] = alpha * l_ref[pl.ds(h, 1), :] + jnp.sum(prob, axis=0, keepdims=True)
        m_ref[pl.ds(h, 1), :] = m_new
        # the probabilities rounded to the cache's dtype once, before the value product
        acc_ref[cols(h), :] = acc_ref[cols(h), :] * alpha + jnp.dot(v.T, prob.astype(v.dtype),
                                                                    preferred_element_type=jnp.float32)

    def window(partly: bool):
        bias = None
        if partly:
            seen = (lax.broadcasted_iota(jnp.int32, (tw, tq), 0)
                    <= lim_ref[i] + lax.broadcasted_iota(jnp.int32, (tw, tq), 1))
            bias = jnp.where(seen, 0.0, _NEG_INF)
        each(heads, lambda h: update(h, wk_ref[0, :, cols(h)], wv_ref[0, :, cols(h)], bias))

    is_sum = (flags // 4) % 2 == 1
    pl.when(jnp.logical_not(is_sum) & (flags // 8 == 1))(functools.partial(window, True))
    pl.when(jnp.logical_not(is_sum) & (flags // 8 == 0))(functools.partial(window, False))

    @pl.when(is_sum)
    def _summaries():
        def pair(h):  # a word holds head h (low half) beside head h + H / 2 (high half)
            k_lo, k_hi = _halves(jnp.concatenate([r[0, :, cols(h)] for r in sk], axis=0))
            v_lo, v_hi = _halves(jnp.concatenate([r[0, :, cols(h)] for r in sv], axis=0))
            update(h, k_lo.astype(dt), v_lo.astype(dt))
            update(h + heads // 2, k_hi.astype(dt), v_hi.astype(dt))

        each(heads // 2, pair, heads_a_call=2)

    @pl.when((flags // 2) % 2 == 1)
    def _finish():
        def one(h):
            l = l_ref[pl.ds(h, 1), :]
            o_ref[0, :, cols(h)] = (acc_ref[cols(h), :] / jnp.where(l == 0.0, 1.0, l)).T.astype(o_ref.dtype)

        each(heads, one)


@functools.partial(jax.jit, static_argnames=("num_heads", "interpret", "query_tile", "window_tile"))
def eva_chunk_read(
    q: jax.Array,  # [N, T, H * Dh] - a chunk's rotated queries a row, in the buffers' dtype
    win_k: jax.Array,  # [slots, W, H * Dh] the open windows' keys, the chunk's own already written
    win_v: jax.Array,  # [slots, W, H * Dh]
    sum_k: jax.Array,  # [P, rows, H * Dh / 2] uint32 summary pages
    sum_v: jax.Array,  # [P, rows, H * Dh / 2] uint32
    work: ChunkReadWork,  # of the rows' slots, offsets, valid lengths and page tables
    *,
    num_heads: int,
    interpret: bool = False,
    query_tile: Optional[int] = None,  # the tiles the work list was built with
    window_tile: Optional[int] = None,
) -> jax.Array:
    """Attention output ``[N, T, H * Dh]`` float32 of a chunk's queries:
    query ``t`` of a row at ``offsets`` reads, under one softmax, rows ``0
    .. offsets % W + t`` of its slot's buffer (the chunk's own keys are
    buffer rows like any other) and every summary row of the first
    ``offsets // W`` windows of its page table. Grid: the work list's
    steps, a run-time count; a step is one buffer tile of ``WINDOW_TILE``
    rows, or one closed window's pages, under one query tile, the heads
    walked inside it over lane-aligned column slices. Scores, masks,
    probabilities and the running maximum and sum stay in VMEM; a
    PADDING query (``t >= valid``) may read stale rows: its output is
    finite and feeds nothing."""
    N, T, HD = q.shape
    W = win_k.shape[1]
    rows = sum_k.shape[1]
    S = work.row.shape[0]
    ppw = work.phys.shape[0] // S
    tq, tw = chunk_tiles(T, W, query_tile, window_tile)
    if not interpret and not chunk_supports(T, rows):
        raise ValueError(f"the compiled chunk read wants whole lane tiles of queries and whole 32-bit tiles a summary "
                         f"page (8 rows or a multiple), got {T} queries and {rows} rows")

    def tile_spec():  # (an index map sees the step, then the prefetched lists)
        return pl.BlockSpec((1, tq, HD), lambda i, row, slot, qtile, *_: (row[i], qtile[i], 0))

    def window_spec():
        return pl.BlockSpec((1, tw, HD), lambda i, row, slot, qtile, flags, tile, *_: (slot[i], tile[i], 0))

    def page_spec(n):
        return pl.BlockSpec((1, rows, HD // 2), lambda i, *lists: (lists[-1][i * ppw + n], 0, 0))

    return pl.pallas_call(
        functools.partial(_chunk_kernel, scale=(HD // num_heads) ** -0.5, ppw=ppw, heads=num_heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(work.n_work[0],),
            in_specs=[tile_spec(), window_spec(), window_spec()] + [page_spec(n) for _ in range(2) for n in range(ppw)],
            out_specs=tile_spec(),
            scratch_shapes=[
                pltpu.VMEM((num_heads, tq), jnp.float32),  # a head's running maximum: a row, a query a lane
                pltpu.VMEM((num_heads, tq), jnp.float32),  # and sum
                pltpu.VMEM((HD, tq), jnp.float32),  # the accumulators, transposed like the scores
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, T, HD), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=interpret,
        name="eva_chunk_read",
    )(work.row, work.slot, work.qtile, work.flags, work.tile, work.lim, work.phys, q, win_k, win_v,
      *[sum_k] * ppw, *[sum_v] * ppw)
