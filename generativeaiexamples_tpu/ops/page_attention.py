"""Pallas TPU kernel: ragged page-attention over the paged KV pool.

The engine's KV cache (docs/paged_kv.md) stores K/V in a shared page
pool ``[P, page, Hkv, Dh]`` with per-slot page tables; the XLA
dequant-gather that serves it everywhere reads a power-of-two window
``W`` of pages per row — the whole batch pays the longest live
sequence, exactly the padded-window traffic the paged design exists to
remove. This kernel is the ragged read (PAPERS.md: "Ragged Paged
Attention" is this kernel for TPU): it walks a flat WORK LIST
of the live (row, page) pairs only (``page_work_list``: each row's
pages up to its last query position, flattened in row order), handed
over by scalar prefetch, so both the cache traffic and the number of
grid steps track each sequence's true page-rounded length
(``utils/hardware.kv_read_bytes_ragged`` is this kernel's operand math).

Design:

- **token-major pages.** The pool keeps pages ``[page, Hkv, Dh]``
  token-major (one page is the write unit), not head-major strips, so
  the head-fused wide-dot trick runs over the MERGED ``[page*Hkv, Dh]``
  leading dims: ONE ``[rows, Dh] x [Dh, page*Hkv]`` MXU dot scores every
  (query row, token, kv head) triple — Hkv-fold redundant FLOPs on a
  ~99%-idle MXU — and each query row's
  own-head columns are selected by a lane mask folded into the softmax
  masking (non-matching columns sit at -inf and underflow to exact 0
  probability), so no lane shuffle ever reorders the interleaved
  ``t*Hkv + h`` columns.
- **page-granular scales.** The int8 variant's per-(token, head) scales
  live page-contiguous (``[P, page, Hkv]``, engine/kv_pages.py /
  models/llama.py); they fold into the score/prob matrices after the
  int8 dots.
- **bf16 AND int8.** The ragged walk is the win, not the dequant in
  VMEM alone, so every pool dtype gets the kernel.
- **multi-query rows.** ``q`` is ``[B, T, Hq, Dh]``: T=1 is block
  decode; small T (spec verify's K+1 chunk) runs the same kernel with a
  per-query-row causal clamp (query t of row b attends tokens
  ``<= positions[b] + t``). ``supports_geometry`` refuses a T past the
  VMEM row cap; a wider chunk reads as several sub-rows of
  ``query_fold`` queries each (the narrow rung of chunked prefill,
  models/llama.py), a full prefill chunk stays on the XLA gather.

Grid: one dimension of ``n_work = sum_b live_pages(b)`` steps, a
DYNAMIC bound (``PrefetchScalarGridSpec`` takes a traced scalar; Mosaic
compiles the loop with a run-time trip count). Step ``i`` DMAs pool page
``phys[i]`` (all KV heads) for row ``row[i]``; the running softmax
max/sum/accumulator live in VMEM scratch, reset where an item is the
first page of its row and normalised into the row's output block where
it is the last. The output (and query) block index is the row, so a
block moves once per row. A row's pages stay in ascending order: the
accumulation order, and so every output bit, is that of the
``(B, Pmax)`` grid this walk replaced. That grid took all ``B * Pmax``
steps whatever the rows held (an index-map clamp elided only a dead
page's DMA) and cost ~0.3 us a dead step: at 64 slots x 32 pages with
5-6 live pages a row, two thirds of the kernel's time (PERF.md, PR 27).
A dead row (position 0 pointing at the scratch page) keeps one item, so
its output block is still written: finite garbage that the engine
discards, identical to the fixed kernel's contract.

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
# VMEM running-softmax scratch is [T*Hq, 128] f32 (m and l) plus the
# [T*Hq, Dh] accumulator; 512 rows caps the trio near ~1 MB at Dh=128.
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


def _kernel(
    row_ref, page_ref, phys_ref, pos_ref, q_ref, *refs,
    scale: float, page: int, hq: int, hkv: int, g: int,
    t: int, s_max: int, quantized: bool, packed: bool,
    head_major: bool = False,
):
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    del phys_ref  # consumed by the pool index maps only
    i = pl.program_id(0)
    j = page_ref[i]  # logical page of row row_ref[i]; ascending per row
    p_first = pos_ref[row_ref[i]]
    last_tok = jnp.minimum(p_first + t - 1, s_max - 1)
    rows = t * hq
    cols = page * hkv
    dh = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # every work item is a live page: page_work_list emits none past
    # the row's last live token
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
    if quantized:
        # page-granular K scales fold in AFTER the int8/int4 dot
        # (small integers convert to bf16 exactly, so the MXU saw
        # exact operands)
        sc = sc * (ks_ref[0].reshape(1, cols) * scale)
    else:
        sc = sc * scale
    col_iota = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    row_iota = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    if head_major:  # a page is [Hkv, page, Dh]: column c = kv-head * page + token
        tok = j * page + col_iota % page
        col_head = col_iota // page
    else:
        tok = j * page + col_iota // hkv
        col_head = col_iota % hkv
    row_head = (row_iota % hq) // g
    # per-query-row causal clamp: query t attends <= positions + t
    q_pos = jnp.minimum(p_first + row_iota // hq, s_max - 1)
    live = (tok <= q_pos) & (col_head == row_head)
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
    if quantized:
        prob = prob * vs_ref[0].reshape(1, cols)
    if packed:
        v_cat = _unpack_nibbles(v_ref[0].reshape(cols, dh // 2))
    else:
        v_cat = v_ref[0].reshape(cols, dh).astype(jnp.bfloat16)
    out = lax.dot_general(
        prob.astype(jnp.bfloat16), v_cat, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [rows, Dh]
    acc_ref[...] = acc_ref[...] * alpha + out

    # the row's last live page: its successor would start past last_tok
    @pl.when((j + 1) * page > last_tok)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # paranoia: never divide by 0
        o_ref[0] = (
            (acc_ref[...] / l).reshape(t, hq, dh).astype(o_ref.dtype)
        )


class PageWork(NamedTuple):
    """The ragged work list of one attention read: item ``i < n_work[0]``
    is logical page ``page[i]`` of row ``row[i]``, stored in pool page
    ``phys[i]``. Rows ascend, pages ascend inside a row, every row has
    at least one item. Entries past ``n_work`` are in-bounds padding."""

    n_work: jax.Array  # [1] int32
    row: jax.Array  # [B * Pmax] int32
    page: jax.Array  # [B * Pmax] int32
    phys: jax.Array  # [B * Pmax] int32


def page_work_list(
    tables: jax.Array,  # [B, Pmax] int32
    positions: jax.Array,  # [B] int32 — first query token's position
    query_len: int,
    page_size: int,
) -> PageWork:
    """Flatten each row's LIVE pages into one list the kernel walks.

    Row ``b`` holds ``n_b = min(pos_b + T - 1, S - 1) // page + 1``
    items, so the list has ``sum(n_b)`` of them — not ``B * Pmax`` —
    and a dead row (position 0) keeps exactly one (its scratch page),
    which is what writes its output block. Pure ``jnp``: the paged
    model computes it once per step and every layer's read shares it.
    """
    B, Pmax = tables.shape
    pos = positions.astype(jnp.int32)
    n = jnp.minimum(pos + query_len - 1, Pmax * page_size - 1) // page_size + 1
    ends = jnp.cumsum(n)
    item = jnp.arange(B * Pmax, dtype=jnp.int32)
    row = jnp.minimum(
        jnp.sum(item[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), B - 1
    )
    page = jnp.minimum(item - (ends - n)[row], n[row] - 1)
    return PageWork(
        ends[-1:], row, page, tables.astype(jnp.int32)[row, page]
    )


@functools.partial(jax.jit, static_argnames=("interpret", "head_major"))
def paged_attention(
    q: jax.Array,  # [B, T, Hq, Dh] bf16 — T query tokens per row
    k: jax.Array,  # [P, page, Hkv, Dh] int8 or bf16 page pool
    v: jax.Array,  # [P, page, Hkv, Dh]
    tables: jax.Array,  # [B, Pmax] int32 physical page ids per row
    positions: jax.Array,  # [B] int32 — FIRST query token's position
    k_scale: Optional[jax.Array] = None,  # [P, page, Hkv] f32 (int8)
    v_scale: Optional[jax.Array] = None,
    *,
    interpret: bool = False,
    work: Optional[PageWork] = None,
    head_major: bool = False,
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

    ``work`` is ``page_work_list(tables, positions, T, page)``; a caller
    that reads many layers at the same positions passes it so the list
    is built once, otherwise it is built here.
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
    S = Pmax * page
    scale = 1.0 / math.sqrt(Dh)
    pos = positions.astype(jnp.int32)
    if work is None:
        work = page_work_list(tables, pos, T, page)

    def pool_spec():
        return pl.BlockSpec(
            (1, Hkv, page, Dh_pool) if head_major else (1, page, Hkv, Dh_pool),
            lambda i, row, pg, phys, pos: (phys[i], 0, 0, 0),
        )

    def scale_spec():
        return pl.BlockSpec(
            (1, page, Hkv), lambda i, row, pg, phys, pos: (phys[i], 0, 0)
        )

    def row_spec():
        # q and the output follow the item's row: fetched / written
        # back only where the row changes
        return pl.BlockSpec(
            (1, T, Hq, Dh), lambda i, row, pg, phys, pos: (row[i], 0, 0, 0)
        )

    if quantized:
        in_specs = [row_spec(), pool_spec(), scale_spec(), pool_spec(), scale_spec()]
        operands = (q, k, k_scale, v, v_scale)
    else:
        in_specs = [row_spec(), pool_spec(), pool_spec()]
        operands = (q, k, v)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(work.n_work[0],),
        in_specs=in_specs,
        out_specs=row_spec(),
        scratch_shapes=[
            pltpu.VMEM((T * Hq, _LANE), jnp.float32),
            pltpu.VMEM((T * Hq, _LANE), jnp.float32),
            pltpu.VMEM((T * Hq, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, page=page, hq=Hq, hkv=Hkv, g=G, t=T,
            s_max=S, quantized=quantized, packed=packed,
            head_major=head_major,
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
