"""Pallas TPU kernel: ragged page-attention over the paged KV pool.

The engine's KV cache (docs/paged_kv.md) stores K/V in a shared page
pool ``[P, page, Hkv, Dh]`` with per-slot page tables; the XLA
dequant-gather that serves it everywhere reads a power-of-two window
``W`` of pages per row — the whole batch pays the longest live
sequence, exactly the padded-window traffic the paged design exists to
remove. This kernel is the ragged read (PAPERS.md: "Ragged Paged
Attention" is this kernel for TPU): it walks a flat WORK LIST
of the live (row, pages) groups only (``page_work_list``: each row's
pages up to its last query position, one or two a step, flattened in
row order), handed over by scalar prefetch, so both the cache traffic and the number of
grid steps track each sequence's true page-rounded length
(``utils/hardware.kv_read_bytes_ragged`` is this kernel's operand math).

Design:

- **token-major pages.** The pool keeps pages ``[page, Hkv, Dh]``
  token-major (one page is the write unit), not head-major strips, so
  the head-fused wide-dot trick runs over the MERGED ``[page*Hkv, Dh]``
  leading dims: ONE ``[rows, Dh] x [Dh, page*Hkv]`` MXU dot scores every
  (query row, token, kv head) triple — Hkv-fold redundant FLOPs on a
  ~99%-idle MXU. Of the ``Hkv`` rows ``h * G + g`` only one, the
  column's own head's, holds a score a query needs, so the rows are
  FOLDED onto each other before any exponential ("Masks" below) and the
  softmax runs over ``score_rows`` rows in the columns' own order ``t *
  Hkv + h``; the probabilities are unfolded, foreign heads' columns
  exact zeros, into the operand the one wide value dot takes.
- **page-granular scales.** The int8 variant's per-(token, head) scales
  live page-contiguous in two float32 planes a layer and fold into the
  score/prob matrices after the int8 dots, as the row ``[1, page *
  Hkv]`` in the scores' own column order ``t * Hkv + h``. The POOL
  decides how a page's scales are stored (models/llama.py
  ``kv_scale_plane_shape``) and the kernel reads that off the operand's
  static shape. LANE-DENSE ``[P, page * Hkv / 128, 128]`` (one device,
  a geometry that tiles the lanes): a page's block is one 4 KB tile
  whose sublanes, laid side by side, ARE the row. TOKEN-MAJOR ``[P,
  page, Hkv]`` (a head-sharded pool's local tile, any other geometry):
  token on sublanes and head on the first lanes of a padded tile, 64 KB
  moved for 4 KB, and ``_scale_row`` builds the row with strided lane
  rotates (the plain ``reshape`` relayout, twice a page, was 28 % of
  the kernel, PERF.md §6, PR 43). Same values, same products, same
  bits; lane-dense the kernel is 18 % shorter at one page a step and
  the padded copy of every plane around every dispatch is gone
  (PERF.md §6, PR 45).
- **bf16 AND int8.** The ragged walk is the win, not the dequant in
  VMEM alone, so every pool dtype gets the kernel.
- **multi-query rows.** ``q`` is ``[B, T, Hq, Dh]``: T=1 is block
  decode; small T (spec verify's K+1 chunk) runs the same kernel with a
  per-query-row causal clamp (query t of row b attends tokens
  ``<= positions[b] + t``). ``supports_geometry`` refuses a T past the
  VMEM row cap; a wider chunk reads as several sub-rows of
  ``query_fold`` queries each (the narrow rung of chunked prefill,
  models/llama.py), a full prefill chunk stays on the XLA gather.

Grid: one dimension of ``n_work = sum_b ceil(live_pages(b) / N)`` steps,
a DYNAMIC bound (``PrefetchScalarGridSpec`` takes a traced scalar; Mosaic
compiles the loop with a run-time trip count). Step ``i`` carries a GROUP
of up to ``N`` consecutive live pages of row ``row[i]``: every pool
operand is passed once a place of the group, with index map
``phys[i * N + n]``, so Pallas's own pipeline double-buffers ``N`` pages
(all KV heads) a step. The body walks the group's live pages in
ascending order through the one-page arithmetic (``page_step``), a dead
place of a row's last group under ``pl.when`` (it names the page its
place held a step earlier: no DMA, no arithmetic); the running softmax
max/sum/accumulator live in VMEM scratch, reset where a step is the
first group of its row and normalised into the row's output block where
it is the last. The output (and query) block index is the row, so a
block moves once per row. A row's pages stay in ascending order: the
accumulation order, and so every output bit, is that of one page a step
and of the ``(B, Pmax)`` grid before it. That grid took all ``B * Pmax``
steps whatever the rows held (an index-map clamp elided only a dead
page's DMA) and cost ~0.3 us a dead step: at 64 slots x 32 pages with
5-6 live pages a row, two thirds of the kernel's time (PERF.md, PR 27).
A dead row (position 0 pointing at the scratch page) keeps one step, so
its output block is still written: finite garbage that the engine
discards, identical to the fixed kernel's contract.

Why N (``pages_per_step``): a step costs ~0.4 us before its first byte
(0.42 us with an empty body and two 131 KB page DMAs, whose bytes are
0.32 us of HBM time) and ~0.1 us more for every operand it names, so a
page that is cheap to move and to compute shares its step: two
single-query pages a step wherever two page pairs fit ``_STEP_BYTES``,
bfloat16 or quantised with lane-dense scale planes (int8 at 32/8 heads:
0.256 -> 0.249 ms a layer, -3 %; 0.264 at four). Four a step measured
no better than two at any served geometry (what is left is per page: a
DMA's issue and wait, the int8 converts, the softmax); a
quantised page whose scale blocks are token-major (padded to 64 KB
each) and a multi-query page (spec verify, the folded extend read:
compute-heavy, +6-7 % paired over lane-dense planes too) measured slower
paired. So N is 1 or 2 and follows the static shapes alone: no setting.

Masks: the own-head mask ``head(column) == head(row)`` is a fold in and
an unfold out (``_fold``, ``_unfold``, ``softmax_folded``). In: the wide
scores are selected by it (foreign entries exact zeros) and a query's
``Hq`` rows are cut into slabs of ``max(G, 8)`` rows, whole vregs, added
elementwise: one value and zeros, exact. On Mistral's 32/8 heads that is
``[32, 1024] -> [8, 1024]``: sublane ``s`` holds group row ``s % 4`` of
the four KV heads congruent to ``s // 4`` modulo 2 (a parity mask rides
the token clamp); on 8 or 16 query heads a KV head a slab is one head's
group. Between the two dots everything runs on those rows: the K-scale
row, the token clamp, the running max, ``exp``, the running sum, the
V-scale row. A head's columns are the lanes congruent to it modulo
``Hkv`` (token-major) or its own lane tiles (head-major), so the max
folds the lane tiles elementwise and then meets across lanes
(``across_lanes``); the running sum stays a lane's own partial sum and
meets once a row, in ``_finish``. Out: the probabilities go back under
each of a query's slabs, selected by the same mask, cast, and ``alpha``
is picked out of the state by one lane reduce. What it bought, kernel
alone at Mistral's shapes (32 reads a jit, 355 live pages, int8 lane-
dense; PERF.md §6, PR 47): 0.834 -> 0.745 us a page at two pages a step
where the softmax knocked out altogether reads 0.650; the folded extend
read -21 %. What a page costs after it is mostly NOT the softmax: 648 of
its ~1,000 vector operations convert the int8 K and V to bfloat16 (a
page is ~380 instruction bundles of which the folded softmax is ~35).
Two things measured on the way: a sublane or lane ROTATE waits ~50
cycles for its result, so rotates that depend on each other are paid in
full (a four-step butterfly for the lanes' maximum read +24 % a page
where fifteen independent rotates of the same vreg read -3 %); and the
bundle count of the compiled body, which can be read WITHOUT the chip
(``--xla_jf_dump_to`` with ``--xla_jf_dump_llo_text`` on a compile for
a described device), predicts a page's time at two pages a step. The
masks are rebuilt on every page: together they are 1.4 % of it;
building the head mask once (a bias in PR 43, a 0 / 1 factor in VMEM
scratch in PR 47) and branching around the token mask on a row's inner
pages measured no faster.

The flat grid is ``arbitrary``: it gives up the ``parallel`` row
dimension of the old grid. On v5e (one TensorCore a chip) that costs
nothing; a two-core chip would want the list split in two halves of
about equal work, one per core.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
_NEG_INF = -1e30
# VMEM scratch holds the [T*Hq, Dh] float32 accumulator and the running
# max and sum ([score_rows, 128] folded, [T*Hq, 128] wide); 512 rows caps
# the trio near ~1 MB at Dh=128.
MAX_QUERY_ROWS = 512


def _unpack_nibbles(u):
    """[rows, dh//2] uint8 (two int4 per byte, split-halves codec from
    models/llama.quantize_kv_int4) -> [rows, dh] bf16 with exact integer
    values in [-8, 7]. Low nibble holds lanes [0, dh/2), high nibble
    [dh/2, dh) — a lane-axis concat, no interleave shuffle. Arithmetic
    widens to int32 first: Mosaic's sub-byte bitwise support varies
    across versions, int32 ops are universal and the unpack is
    bandwidth- not compute-bound anyway."""
    w = u.astype(jnp.int32)
    lo = w & 0xF
    hi = (w >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.bfloat16)


def _scale_row(s_ref, page: int, hkv: int):
    """A page's scales as the ``[1, page * Hkv]`` row the scores multiply
    by, column ``c = t * Hkv + h``. The block's own static shape says
    how the pool stores them (models/llama.py ``kv_scale_plane_shape``).

    LANE-DENSE ``[page * Hkv / 128, 128]``: the block IS the row, cut
    into sublanes (one 4 KB tile a page); laying the sublanes side by
    side is all there is to do.

    TOKEN-MAJOR ``[page, Hkv]`` (a head-sharded pool's local tile, a
    geometry that does not tile 128 lanes): token on sublanes, head on
    the first ``Hkv`` lanes of a padded tile. Eight tokens share a vreg;
    a lane tile of the row holds ``128 / Hkv`` tokens. One STRIDED lane
    rotate a vreg (sublane ``s`` moves by ``s * Hkv`` lanes more than
    its neighbour) puts every token's heads at their lanes, a select
    keeps them, and one sublane sum a lane tile (seven exact zeros and
    the value) collapses the eight tokens into the row: 16 rotates and
    8 sums for ``[128, 8]``, where the plain ``reshape(1, cols)``
    relayout was 0.17 us a block, twice a page (PERF.md §6, PR 43).
    Geometries the rotate does not tile (a head count that does not
    divide 128 into whole vregs) keep the reshape."""
    s = s_ref[0]
    cols = page * hkv
    if s.shape != (page, hkv):  # lane-dense
        return jnp.concatenate([s[r:r + 1] for r in range(s.shape[0])], axis=1)
    if _LANE % (8 * hkv) or cols % _LANE or page % 8:
        return s.reshape(1, cols)
    vregs = _LANE // (8 * hkv)  # 8-token vregs a lane tile of the row
    wide = jnp.pad(s, ((0, 0), (0, _LANE - hkv)))
    lane = lax.broadcasted_iota(jnp.int32, (8, _LANE), 1)
    sub = lax.broadcasted_iota(jnp.int32, (8, _LANE), 0)
    tiles = []
    for tile in range(cols // _LANE):
        acc = jnp.zeros((8, _LANE), jnp.float32)
        for m in range(vregs):
            at = 8 * (tile * vregs + m)
            moved, shift, left = wide[at:at + 8], 8 * hkv * m, hkv
            while left:  # the chip takes a rotate's stride modulo 8
                moved = pltpu.roll(moved, shift, 1, stride=min(left, 7), stride_axis=0)
                shift, left = 0, left - min(left, 7)
            acc = jnp.where((lane - 8 * hkv * m) // hkv == sub, moved, acc)
        tiles.append(jnp.sum(acc, axis=0, keepdims=True))
    return jnp.concatenate(tiles, axis=1)


_SUB = 8  # sublanes of a float32 vreg


def score_rows(num_heads: int, num_kv_heads: int, query_len: int = 1) -> int:
    """Rows of the matrix a page's softmax runs over. A query group
    shares one KV head, so of the wide score matrix's rows ``t * Hq + h
    * G + g`` only ONE KV head ``h`` holds a live value in any column.
    The kernel cuts a query's ``Hq`` rows into slabs of ``max(G, 8)``
    (whole vregs: a group of 8 or 16 query heads, or the ``8 / G``
    groups that share a vreg) and adds the slabs elementwise
    (``_fold``): ``T * max(G, 8)`` rows, every entry a score some query
    needs, with no relayout on the way in or out. A geometry whose
    slabs do not tile (``G`` neither divides 8 nor is a multiple of it,
    ``Hq`` not whole slabs), one KV head (nothing to fold) and one whose
    fold holds no fewer rows keep the wide body: ``T * Hq`` rows. The
    kernel sizes its softmax scratch by this and the engine's decode
    spans carry it (``kv_score_rows``)."""
    rows = query_len * num_heads
    g = num_heads // max(1, num_kv_heads)
    slab = max(g, _SUB)
    tiles = slab % g == 0 and slab % _SUB == 0 and num_heads % slab == 0
    return query_len * slab if num_kv_heads > 1 and tiles and slab < num_heads else rows


def _state_lanes(page: int, hkv: int, head_major: bool) -> int:
    """Lanes of the folded softmax's running max / sum, 0 where the
    columns of a page do not tile for it (the wide body serves those).
    TOKEN-MAJOR (column ``t * Hkv + h``): a head's columns are the lanes
    congruent to ``h`` modulo ``Hkv``; the state is one lane tile wide,
    the max CLASS-REPLICATED (every lane holds its own head's value).
    HEAD-MAJOR (column ``h * page + t``): a head's columns are its own
    lane tiles; the state holds one tile a head, the max lane-replicated.
    The running sum is in both a lane's own partial sum until a row's
    last page."""
    cols = page * hkv
    if head_major:
        w = min(page, _LANE)
        return hkv * w if page % w == 0 and hkv <= w else 0
    w = min(cols, _LANE)
    return w if cols % w == 0 and w % hkv == 0 else 0


def _tree(op, xs):
    """``op`` over the list in a balanced tree."""
    while len(xs) > 1:
        xs = [op(*xs[i:i + 2]) if i + 1 < len(xs) else xs[i] for i in range(0, len(xs), 2)]
    return xs[0]


def _fold(wide, t: int, hq: int, slab: int):
    """``[T * Hq, X] -> [T * slab, X]``: a query's ``Hq`` rows cut into
    slabs of ``slab`` rows and added elementwise. Row ``s`` of a query's
    slab takes wide rows ``s, s + slab, ..``: group row ``s % G`` of the
    KV heads congruent to ``s // G`` modulo ``slab / G``."""
    return jnp.concatenate([
        functools.reduce(jnp.add, [
            wide[q * hq + at:q * hq + at + slab] for at in range(0, hq, slab)
        ]) for q in range(t)
    ], axis=0)


def _unfold(narrow, t: int, hq: int, slab: int):
    """``[T * slab, X] -> [T * Hq, X]``: a query's slab under each of
    its ``Hq / slab`` slabs of wide rows (the caller's own-head select
    keeps one of them a column)."""
    return jnp.concatenate([
        narrow[q * slab:(q + 1) * slab] for q in range(t) for _ in range(hq // slab)
    ], axis=0)


def _kernel(
    row_ref, page_ref, phys_ref, pos_ref, q_ref, *refs,
    scale: float, page: int, hq: int, hkv: int, g: int,
    t: int, s_max: int, quantized: bool, packed: bool,
    head_major: bool = False, group: int = 1, lanes: int = 0,
):
    per_page = 4 if quantized else 2  # pool operands a page of the group
    o_ref, m_ref, l_ref, acc_ref = refs[group * per_page:]
    del phys_ref  # consumed by the pool index maps only
    i = pl.program_id(0)
    j0 = page_ref[i]  # the group's first logical page; ascending per row
    p_first = pos_ref[row_ref[i]]
    last_tok = jnp.minimum(p_first + t - 1, s_max - 1)
    rows = t * hq
    cols = page * hkv
    dh = q_ref.shape[-1]
    # lanes > 0: the softmax runs folded, on m_ref.shape[0] = score_rows
    # rows ("Masks" above); 0: the wide body, one row a query head
    tile = lanes // hkv if head_major else lanes  # lanes of one state tile
    slab = max(g, _SUB)  # rows of a query's folded scores (score_rows)

    @pl.when(j0 == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    col = lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    row_iota = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    row_head = (row_iota % hq) // g
    if head_major:  # a page is [Hkv, page, Dh]: column c = kv-head * page + token
        col_tok, col_head = col % page, col // page
    else:
        col_tok, col_head = col // hkv, col % hkv

    def fold_tiles(x, op):
        """``[score_rows, cols] -> [score_rows, lanes]``: the lane tiles
        of each head's columns under ``op`` (max or add), elementwise: a
        lane of the result holds ``op`` over its own lane of every tile
        (token-major: of the tokens that share its position in a tile)."""
        tiles = [x[:, at:at + tile] for at in range(0, cols, tile)]
        if head_major:
            n = len(tiles) // hkv
            return jnp.concatenate([
                functools.reduce(op, tiles[h * n:(h + 1) * n]) for h in range(hkv)
            ], axis=1)
        return functools.reduce(op, tiles)

    def across_lanes(r, op):
        """``[score_rows, lanes]``, folded tiles -> the state's form
        (``_state_lanes``), every lane ``op`` over ALL of its head's
        columns: one lane reduce a head (head-major), or lane rotates by
        every multiple of ``Hkv`` (token-major), all of them rotates of
        the folded tile itself so that none waits for another: a rotate
        costs little to issue and ~50 cycles to wait for, and a
        butterfly of four dependent ones read +24 % a page (PERF.md §6,
        PR 47)."""
        if head_major:
            reduce = jnp.max if op is jnp.maximum else jnp.sum
            return jnp.concatenate([
                jnp.broadcast_to(
                    reduce(r[:, h * tile:(h + 1) * tile], axis=1, keepdims=True), (r.shape[0], tile)
                ) for h in range(hkv)
            ], axis=1)
        return _tree(op, [r] + [pltpu.roll(r, k, 1) for k in range(hkv, tile, hkv)])

    def by_column(state):
        """``[score_rows, lanes] -> [score_rows, cols]``: every column
        its own head's value."""
        if head_major:
            n = page // tile
            return jnp.concatenate([
                state[:, h * tile:(h + 1) * tile] for h in range(hkv) for _ in range(n)
            ], axis=1)
        return jnp.concatenate([state] * (cols // tile), axis=1)

    def by_row(state):
        """``[score_rows, lanes] -> [T * Hq, 1]``: every wide row its own
        query head's value. Lane ``h`` of a tile holds head ``h``'s
        (token-major: as it stands); the unfold brings a group's row to
        its heads' rows and a lane select with one lane reduce keeps the
        head's own (the values are >= 0: alpha, l)."""
        if head_major:  # head h's tile onto lane h of one tile
            lane = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
            packed_state = state[:, :tile]
            for h in range(1, hkv):
                packed_state = jnp.where(lane == h, state[:, h * tile:(h + 1) * tile], packed_state)
            state = packed_state
        lane = lax.broadcasted_iota(jnp.int32, (rows, tile), 1)
        return jnp.max(
            jnp.where(lane == row_head, _unfold(state, t, hq, slab), 0.0),
            axis=1, keepdims=True,
        )

    def softmax_folded(j, sc, k_scale, v_scale):
        """The page's probabilities ``[T * Hq, cols]`` (foreign heads'
        columns exact zeros, V scales folded in) and ``alpha [T * Hq,
        1]``, with every pass between the fold and the unfold on
        ``score_rows`` rows: the scores a query needs and no others."""
        own = col_head == row_head
        s = _fold(jnp.where(own, sc, 0.0), t, hq, slab) * k_scale
        nrow = lax.broadcasted_iota(jnp.int32, (t * slab, 1), 0)
        # per-query causal clamp: query t attends <= positions + t
        live = j * page + col_tok <= jnp.minimum(p_first + nrow // slab, s_max - 1)
        if slab > g:  # a row holds the heads of its own class modulo slab / G
            live &= col_head % (slab // g) == nrow % slab // g
        s = jnp.where(live, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, across_lanes(fold_tiles(s, jnp.maximum), jnp.maximum))
        prob = jnp.exp(s - by_column(m_new))  # dead columns -> 0
        alpha = jnp.exp(m_prev - m_new)
        # the running sum stays a lane's own partial sum (alpha is the
        # same on every lane of a head): the lanes meet once, in _finish
        l_ref[...] = alpha * l_ref[...] + fold_tiles(prob, jnp.add)
        m_ref[...] = m_new
        if v_scale is not None:
            prob = prob * v_scale
        return jnp.where(own, _unfold(prob, t, hq, slab), 0.0), by_row(alpha)

    def softmax_wide(j, sc, k_scale, v_scale):
        """The same over ``[T * Hq, cols]``: the own-head lane mask
        folded into the token clamp (foreign columns sit at -inf and
        underflow to exact 0 probability)."""
        sc = sc * k_scale
        # per-query-row causal clamp: query t attends <= positions + t
        q_pos = jnp.minimum(p_first + row_iota // hq, s_max - 1)
        live = (j * page + col_tok <= q_pos) & (col_head == row_head)
        sc = jnp.where(live, sc, _NEG_INF)
        m_prev = m_ref[:, :1]  # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        prob = jnp.exp(sc - m_new)  # dead/foreign-head columns -> 0
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(prob, axis=1, keepdims=True),
            l_ref.shape,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        if v_scale is not None:
            prob = prob * v_scale
        return prob, alpha

    def page_step(j, *pool):
        """One live page into the running softmax: the arithmetic, and
        its order, of the one-page step this group replaced."""
        if quantized:
            k_ref, ks_ref, v_ref, vs_ref = pool
        else:
            k_ref, v_ref = pool
        q = q_ref[0].reshape(rows, dh)  # [T*Hq, Dh] (leading-dim merge)
        if packed:
            # int4 pool: nibble-unpack to exact bf16 integers in [-7, 7]
            # before the dot — the same exact-operand discipline as int8
            k_cat = _unpack_nibbles(k_ref[0].reshape(cols, dh // 2))
        else:
            k_cat = k_ref[0].reshape(cols, dh).astype(jnp.bfloat16)
        sc = lax.dot_general(
            q, k_cat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, page*Hkv]; column c = (token-in-page)*Hkv + kv-head
        # page-granular scales fold in AFTER the int8/int4 dots (small
        # integers convert to bf16 exactly, so the MXU saw exact operands)
        k_scale = _scale_row(ks_ref, page, hkv) * scale if quantized else scale
        v_scale = _scale_row(vs_ref, page, hkv) if quantized else None
        prob, alpha = (softmax_folded if lanes else softmax_wide)(j, sc, k_scale, v_scale)
        if packed:
            v_cat = _unpack_nibbles(v_ref[0].reshape(cols, dh // 2))
        else:
            v_cat = v_ref[0].reshape(cols, dh).astype(jnp.bfloat16)
        out = lax.dot_general(
            prob.astype(jnp.bfloat16), v_cat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, Dh]
        acc_ref[...] = acc_ref[...] * alpha + out

    for n in range(group):
        step = functools.partial(
            page_step, j0 + n, *refs[n * per_page:(n + 1) * per_page]
        )
        if n == 0:
            step()  # a group's first page is live: page_work_list emits no empty group
        else:
            # a dead place of the row's last group: its block holds a
            # page some earlier place named; none of it is computed
            pl.when((j0 + n) * page <= last_tok)(step)

    # the row's last group: its successor would start past last_tok
    @pl.when((j0 + group) * page > last_tok)
    def _finish():
        l = by_row(across_lanes(l_ref[...], jnp.add)) if lanes else l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # paranoia: never divide by 0
        o_ref[0] = (
            (acc_ref[...] / l).reshape(t, hq, dh).astype(o_ref.dtype)
        )


class PageWork(NamedTuple):
    """The ragged work list of one attention read: step ``i < n_work[0]``
    carries the ``N`` consecutive logical pages ``page[i] .. page[i] + N
    - 1`` of row ``row[i]``; place ``n`` of it is stored in pool page
    ``phys[i * N + n]``. ``N`` (pages a step) is the ratio of the two
    lengths; at ``N = 1`` an item is one page. Rows ascend, groups ascend
    inside a row, every row has at least one step. A DEAD place (past
    the row's last live page, in its last group only) names the pool
    page the same place held at the step before, so no DMA is issued for
    it. Entries past ``n_work`` are in-bounds padding."""

    n_work: jax.Array  # [1] int32 — steps
    row: jax.Array  # [B * ceil(Pmax / N)] int32
    page: jax.Array  # [B * ceil(Pmax / N)] int32 — first page of the group
    phys: jax.Array  # [B * ceil(Pmax / N) * N] int32


# What the pages of one grid step may move, K and V blocks together: two
# pages of a bfloat16 4-head pool (262 KB a pair) or of a ten-head pair
# layout (655 KB) fit; a page of a megabyte or more is bound by its
# bytes and walks alone.
_STEP_BYTES = 3 << 19  # 1.5 MiB


def pages_per_step(k, k_scale=None, query_len: int = 1) -> int:
    """Pages of one row a grid step carries (``N``, 1 or 2), from what
    is static: the pool's dtype, the bytes a page's block specs move
    against ``_STEP_BYTES``, the shape of its scale planes and the
    queries a row holds. A step costs ~0.4 us before its first byte,
    and ~0.1 us more for every operand it names; a single-query page
    whose bytes and arithmetic take about as long shares its step with
    its successor (bfloat16: -6 % and -12 % on the two head-major
    reads; quantised with lane-dense scale planes, 4 KB a block: -3 %;
    four pages a step measured no better than two anywhere: what is
    left is per page). Three kinds of page keep their own step because
    pairing them measured SLOWER: a quantised page whose scale planes
    are token-major (two of its four blocks padded to 64 KB, and the
    relayout of each: +14 % paired), a page of a multi-query row (spec
    verify, the folded extend read: compute-heavy, +3-8 %; +6-7 % over
    lane-dense planes), and a page of a megabyte or more (PERF.md §6,
    PR 43 and PR 45). ``k`` / ``k_scale`` are the pool arrays (or their
    shape structs)."""
    pair = 2 * math.prod(k.shape[1:]) * jnp.dtype(k.dtype).itemsize
    padded_scales = k_scale is not None and tuple(k_scale.shape[1:]) == tuple(k.shape[1:3])
    paired = not padded_scales and query_len == 1 and 2 * pair <= _STEP_BYTES
    return 2 if paired else 1


def pool_pages_per_step(page_size: int, num_kv_heads: int, head_dim: int,
                        dtype, scale_plane=None) -> int:
    """``pages_per_step`` of a pool known by its geometry alone (the
    engine's host-side step count holds no pool array); ``dtype`` is the
    pool's own and ``scale_plane`` a quantised pool's scale plane, one
    page of it, as the pool stores it (``ks.shape[1:]``; None: no
    scales). The packed int4 pool's rows hold ``head_dim // 2`` bytes."""
    packed = jnp.dtype(dtype) == jnp.uint8
    k = jax.ShapeDtypeStruct(
        (1, page_size, num_kv_heads, head_dim // 2 if packed else head_dim), dtype
    )
    scales = scale_plane and jax.ShapeDtypeStruct((1,) + tuple(scale_plane), jnp.float32)
    return pages_per_step(k, scales)


def page_work_list(
    tables: jax.Array,  # [B, Pmax] int32
    positions: jax.Array,  # [B] int32 — first query token's position
    query_len: int,
    page_size: int,
    group: int = 1,
) -> PageWork:
    """Flatten each row's LIVE pages into one list the kernel walks,
    ``group`` (N) consecutive pages a step.

    Row ``b`` holds ``n_b = min(pos_b + T - 1, S - 1) // page + 1`` live
    pages in ``ceil(n_b / N)`` steps, so the list has ``sum`` of those —
    not ``B * Pmax`` — and a dead row (position 0) keeps exactly one
    (its scratch page), which is what writes its output block. Pure
    ``jnp``: the paged model computes it once per step and every layer's
    read shares it, at the N that ``pages_per_step(pool)`` names.
    """
    B, Pmax = tables.shape
    N = group
    pos = positions.astype(jnp.int32)
    n = jnp.minimum(pos + query_len - 1, Pmax * page_size - 1) // page_size + 1
    groups = (n + N - 1) // N
    ends = jnp.cumsum(groups)
    item = jnp.arange(B * (-(-Pmax // N)), dtype=jnp.int32)
    row = jnp.minimum(
        jnp.sum(item[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), B - 1
    )
    first = N * jnp.minimum(item - (ends - groups)[row], groups[row] - 1)
    place = first[:, None] + jnp.arange(N, dtype=jnp.int32)[None, :]
    live = place < n[row][:, None]
    phys = tables.astype(jnp.int32)[row[:, None], jnp.minimum(place, Pmax - 1)]
    if N > 1:
        # a dead place repeats what its place held at the last step that
        # filled it (the block index does not change: no DMA); before any
        # did, the group's own first page
        filled = lax.cummax(jnp.where(live, item[:, None], -1), axis=0)
        held = jnp.take_along_axis(phys, jnp.maximum(filled, 0), axis=0)
        phys = jnp.where(filled >= 0, held, phys[:, :1])
    return PageWork(ends[-1:], row, first, phys.reshape(-1))


@functools.partial(
    jax.jit, static_argnames=("interpret", "head_major", "group")
)
def paged_attention(
    q: jax.Array,  # [B, T, Hq, Dh] bf16 — T query tokens per row
    k: jax.Array,  # [P, page, Hkv, Dh] int8 or bf16 page pool
    v: jax.Array,  # [P, page, Hkv, Dh]
    tables: jax.Array,  # [B, Pmax] int32 physical page ids per row
    positions: jax.Array,  # [B] int32 — FIRST query token's position
    k_scale: Optional[jax.Array] = None,  # f32 [P, page * Hkv / 128, 128]
    v_scale: Optional[jax.Array] = None,  # (lane-dense) or [P, page, Hkv]
    *,
    interpret: bool = False,
    work: Optional[PageWork] = None,
    head_major: bool = False,
    group: Optional[int] = None,
) -> jax.Array:
    """Attention output ``[B, T, Hq, Dh]`` over each row's live pages.

    ``head_major`` reads a pool laid out ``[P, Hkv, page, Dh]`` (bf16
    only). A token-major pool whose KV-head count is not a multiple of
    the sublane tile (16 for bf16) has no compact DEFAULT layout on the
    chip: XLA keeps it in a permuted one and copies the whole pool into
    the padded default around every kernel call. With the heads ahead
    of the tokens the two minor dims are ``(page, Dh)``, which tile
    exactly, and the same ``[Hkv * page, Dh]`` merge feeds the dots.

    Query token ``t`` of row ``b`` sits at absolute position
    ``positions[b] + t`` and attends cache rows at positions ``<= that``
    (the chunk's own rows must already be written to the pool — the
    paged model passes post-update pools, models/llama.py). Table
    entries past a row's live length (they point at the scratch page)
    are never read: the work list stops at ``positions[b] + T - 1``.

    ``work`` is ``page_work_list(tables, positions, T, page, N)``; a
    caller that reads many layers at the same positions passes it so the
    list is built once, and the kernel takes its pages a step from the
    list's own shapes. Otherwise the list is built here, ``group`` pages
    a step where given (the tests walk {1, 2, 4}) and
    ``pages_per_step(k, k_scale, T)`` where not.
    """
    B, T, Hq, Dh = q.shape
    if head_major:
        assert k_scale is None, "the head-major pool is bf16 only"
        P, Hkv, page, Dh_pool = k.shape
    else:
        P, page, Hkv, Dh_pool = k.shape
    Pmax = tables.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    quantized = k_scale is not None
    # int4 pool: two values per uint8 byte (models/llama.py split-halves
    # codec), so the pool's last dim is Dh//2. Static at trace time.
    packed = k.dtype == jnp.uint8
    if packed:
        assert quantized, "packed int4 pools always carry scales"
        assert Dh_pool * 2 == Dh, (Dh_pool, Dh)
    else:
        assert Dh_pool == Dh, (Dh_pool, Dh)
    if quantized:
        assert k_scale.shape == v_scale.shape and k_scale.shape[1:] in (
            (page, Hkv), (page * Hkv // _LANE, _LANE)
        ), (k_scale.shape, v_scale.shape, page, Hkv)
    S = Pmax * page
    scale = 1.0 / math.sqrt(Dh)
    pos = positions.astype(jnp.int32)
    if work is None:
        work = page_work_list(
            tables, pos, T, page, group or pages_per_step(k, k_scale, T)
        )
    N = work.phys.shape[0] // work.row.shape[0]
    # the softmax's rows and the lanes of its running max / sum: folded
    # onto a query group's own KV head where the rows and the columns
    # both tile for it, else one row a query head, lane-replicated
    n_rows = score_rows(Hq, Hkv, T)
    lanes = _state_lanes(page, Hkv, head_major) if n_rows < T * Hq else 0
    if not lanes:
        n_rows = T * Hq

    def pool_spec(n):
        return pl.BlockSpec(
            (1, Hkv, page, Dh_pool) if head_major else (1, page, Hkv, Dh_pool),
            lambda i, row, pg, phys, pos: (phys[i * N + n], 0, 0, 0),
        )

    def scale_spec(n):
        # one page of the plane as the pool stores it: [page, Hkv], or
        # lane-dense [page * Hkv / 128, 128] (a 4 KB block)
        return pl.BlockSpec(
            (1,) + k_scale.shape[1:],
            lambda i, row, pg, phys, pos: (phys[i * N + n], 0, 0),
        )

    def row_spec():
        # q and the output follow the step's row: fetched / written
        # back only where the row changes
        return pl.BlockSpec(
            (1, T, Hq, Dh), lambda i, row, pg, phys, pos: (row[i], 0, 0, 0)
        )

    # each pool operand is passed once a place of the group, so Pallas's
    # own pipeline double-buffers N pages a step
    in_specs, operands = [row_spec()], [q]
    for n in range(N):
        if quantized:
            in_specs += [pool_spec(n), scale_spec(n), pool_spec(n), scale_spec(n)]
            operands += [k, k_scale, v, v_scale]
        else:
            in_specs += [pool_spec(n), pool_spec(n)]
            operands += [k, v]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(work.n_work[0],),
        in_specs=in_specs,
        out_specs=row_spec(),
        scratch_shapes=[
            pltpu.VMEM((n_rows, lanes or _LANE), jnp.float32),
            pltpu.VMEM((n_rows, lanes or _LANE), jnp.float32),
            pltpu.VMEM((T * Hq, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, page=page, hq=Hq, hkv=Hkv, g=G, t=T,
            s_max=S, quantized=quantized, packed=packed,
            head_major=head_major, group=N, lanes=lanes,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, Hq, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(work.row, work.page, work.phys, pos, *operands)
    return out


def query_fold(query_len: int, num_heads: int) -> int:
    """Queries a kernel row of a ``query_len``-wide chunk holds: the
    largest divisor of ``query_len`` whose rows (queries x heads) fit
    ``MAX_QUERY_ROWS`` (0: the heads alone pass it). A chunk wider than
    that reads as ``query_len // fold`` sub-rows of one cache row, each
    at its own first position; the clamp is per query, so the split
    changes no query's key set."""
    t = min(query_len, MAX_QUERY_ROWS // max(1, num_heads))
    while t > 1 and query_len % t:
        t -= 1
    return t


def supports_geometry(
    page_size: int,
    head_dim: int,
    num_heads: int,
    num_kv_heads: int,
    query_len: int = 1,
    interpret: bool = False,
    kv_dtype: str = "bfloat16",
    shards: int = 1,
) -> bool:
    """Whether the ragged kernel serves this pool geometry.

    Compiled mode adds the Mosaic tiling constraints on top of the
    structural ones (GQA divisibility, the VMEM query-row cap that keeps
    prefill-length chunks on the XLA gather); ``interpret=True`` (CPU
    tests, tiny debug engines) needs only the structural half. Callers
    MUST fall back to the XLA gather — loudly — when this returns False.

    ``kv_dtype`` adds the int4 rules: the packed pool's last dim is
    ``head_dim // 2``, so head_dim must be even (structural) and the
    HALVED dim must still fill whole lanes in compiled mode. ``shards``
    is the mesh predicate for the TP shard_map variant
    (parallel/tp_kernels.paged_attention_tp): both head counts must
    divide evenly, and the LOCAL per-device geometry — heads divided by
    shards — must itself pass every check, since each device runs the
    ordinary single-device kernel on its tile.
    """
    if shards > 1:
        if num_heads % shards or num_kv_heads % shards:
            return False
        return supports_geometry(
            page_size, head_dim, num_heads // shards,
            num_kv_heads // shards, query_len=query_len,
            interpret=interpret, kv_dtype=kv_dtype,
        )
    packed = kv_dtype == "int4"
    structural = (
        query_len >= 1
        and num_kv_heads >= 1
        and num_heads % num_kv_heads == 0
        and query_len * num_heads <= MAX_QUERY_ROWS
        and page_size >= 1
        and (not packed or head_dim % 2 == 0)
    )
    if not structural:
        return False
    if interpret:
        return True
    # int4 pools store [.., Dh // 2] uint8 blocks — the LANE rule
    # applies to the stored (packed) dim, not the logical one.
    stored_dim = head_dim // 2 if packed else head_dim
    return (
        stored_dim % _LANE == 0
        # merged [page*Hkv, Dh] leading dims sit on the sublane axis:
        # int8/uint8 VMEM tiles are (32, 128) (bf16 (16, 128) — require
        # the stricter int8 grid uniformly so all pool dtypes share one
        # predicate)
        and (page_size * num_kv_heads) % 32 == 0
        # scratch/reshapes assume an 8-sublane [rows, 128] layout
        and num_heads % 8 == 0
    )
