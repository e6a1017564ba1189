"""MiniMax-M3 (428B-A23B) for the serving engine, as the share ONE chip
holds of an expert-parallel deployment. The language model on text; the
multi-token-prediction modules and the vision tower are not served.

One residual stream, pre-norm, ``N(u) = u / sqrt(mean(u^2) + eps) * (1 +
w)`` in float32 (``use_gemma_norm``): ``h = x + Attn(N1(x))``, ``x' = h +
MLP(N2(h))``, a final ``N`` before the untied head.

- **Block-sparse GQA in EVERY layer**: 64 query / 4 KV heads of 128,
  each head normed (``N`` over its 128 dims, a weight a head) and its
  first 64 dims rotated (pairs ``(i, i + 32)``, theta 5e6); no output
  gate, no bias. A BLOCK is ``msa_block`` consecutive tokens and the
  engine's page IS a block (``init_paged_cache`` and the walks refuse
  any other page size). A complete block has a SUMMARY: the element-wise maximum
  of its cached keys, one ``[Hkv, Dh]`` row. The indexer projects each
  query token to ``msa_index_heads`` vectors of 128 a KV-head group;
  their (unweighted) sum scores a block by its dot with the block's
  summary, in float32. Query ``t`` (``j_t = t // block``) reads block
  0, block ``j_t - 1``, the open block ``j_t`` up to ``t``, and of the
  complete blocks ``1 .. j_t - 2`` the ``msa_topk`` with the highest
  score (all of them where there are no more). The 16 query heads of a
  group share the selection; two groups of one row choose apart.
- **MLP**: the leading dense layers a SwiGLU of 12288, every other
  layer ``models/glm5next.py``'s router and expert layer (sigmoid over
  all 128, top 4 of score + bias, the chosen scores normalised and
  scaled by 2; this chip HOLDS ``experts_held`` from ``experts_first``
  on, plus the shared expert; pairs routed to absent experts are left
  out), all with the activation ``swigluoai``: ``g' sigmoid(1.702 g')
  (u' + 1)``, ``g' = min(g, 7)``, ``u' = clip(u, -7, 7)``
  (``ops/grouped_matmul.py`` ``swiglu``).

**One kind of cache** (docs/model_registry.md): pages only. A layer's
pool is ``k`` / ``v`` ``[P, Hkv, page, Dh]`` (head-major: a step of the
selected read fetches one head's strip of a page) and ``kmax`` ``[P,
Hkv, Dh]``, the summaries, indexed by PHYSICAL page: whichever walk
writes a page's last token writes its summary, from the keys as they
are cached, and a page the prefix store shares brings its summary with
it (a shared page is always complete). No layer keeps state beside the
pages: the family registers ``fixed_state=False``.

**The reads.** A decode step scores a row's complete pages from the
summaries, turns the selection into a work list of (row, KV head, page)
and FETCHES ONLY THOSE (``ops/page_attention.py``
``selected_page_attention``; with no kernel resolved, an XLA gather of
the same strips). The chunk walk computes the same selection for each
of its queries and applies it as a MASK over a walk of the row's live
pages: every query's key set is the equations', but hundreds of queries
between them select nearly every page, so the bytes and the products
are a dense walk's (PERF.md section 7: a gather a query wins only past
~25 k of context). With ``selected_chunk`` resolved
(``selected_chunk_kind``: a head and a page of whole lane tiles, a chunk
that cuts into query tiles) the walk is ``ops/selected_chunk_read.py``:
one Pallas kernel over a run-time list of live (row, query tile, block
of four pages) items, the scores, the mask and the probabilities in
VMEM, an item no query of the tile selected skipped. Otherwise
``_attend_selected_blocks``, an XLA loop over blocks of four pages that
skips a block no query of the CHUNK selected (the CPU path, widths the
kernel declines, what the tests hold the kernel to).

``stats`` is a handful of int32 counts of the last walk.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from generativeaiexamples_tpu.models.afmoe import _draw, _gqa, _heads_first, rope
from generativeaiexamples_tpu.models.glm5next import MOE_STAT_NAMES, _mm, moe, rms_norm, swiglu_mlp
from generativeaiexamples_tpu.models.phi4flash import _write_rows
from generativeaiexamples_tpu.ops import page_attention, selected_chunk_read

Params = Dict[str, Any]
Caches = Dict[str, Any]
_HI = lax.Precision.HIGHEST
_NEG = -1e30

STAT_NAMES = MOE_STAT_NAMES + (
    "msa_pages_selected", "msa_pages_live", "msa_blocks_scored", "msa_pages_pooled",
    # the chunk walk's read: layers by path, and of the kernel's (KV head, tile, block) items those
    # whose block some query of the tile selected, summed over the layers (a decode step: zeros)
    "msa_chunk_kernel_layers", "msa_chunk_xla_layers", "msa_chunk_blocks_read", "msa_chunk_blocks_live")


@dataclasses.dataclass(frozen=True)
class MiniMaxM3Config:
    """Published widths; ``layers_served`` lists the published layers
    served (None: all); ``vocab_size``, ``experts_first`` and
    ``experts_held`` are this chip's share."""

    vocab_size: int = 200064
    hidden_size: int = 6144
    num_hidden_layers: int = 60
    layers_served: Optional[Tuple[int, ...]] = None
    num_dense_layers: int = 3  # moe_layer_freq: three leading zeros
    dense_intermediate_size: int = 12288
    intermediate_size: int = 3072  # one routed expert
    shared_intermediate_size: int = 3072
    num_local_experts: int = 128
    num_experts_per_tok: int = 4
    experts_first: int = 0
    experts_held: int = 128
    routed_scaling_factor: float = 2.0
    swiglu_limit: float = 7.0
    swiglu_alpha: float = 1.702
    num_heads: int = 64
    num_kv_heads: int = 4
    head_dim: int = 128
    rotary_dim: int = 64
    rope_theta: float = 5e6
    norm_eps: float = 1e-6
    msa_block: int = 128
    msa_topk: int = 16
    msa_index_heads: int = 4
    max_seq_len: int = 1048576

    @property
    def layers(self) -> Tuple[str, ...]:
        """'dense' | 'sparse': the MLP of each layer SERVED."""
        served = range(self.num_hidden_layers) if self.layers_served is None else self.layers_served
        return tuple("dense" if l < self.num_dense_layers else "sparse" for l in served)

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def pages_a_read(self) -> int:
        """The most pages one (row, KV head) reads: first, top-k, local, open."""
        return self.msa_topk + 3

    @property
    def splits(self) -> Tuple[int, int, int]:
        """Where the fused projection's columns end: q | k | v | indexer."""
        q = self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        return q, q + kv, q + 2 * kv


PRESETS: Dict[str, MiniMaxM3Config] = {
    # one chip's share of the 8-way expert-parallel deployment: published layer 0
    # for the leading dense layers and four expert layers, 16 of 128 experts, an
    # eighth of the vocabulary
    "minimax-m3-ep8": MiniMaxM3Config(
        vocab_size=25008, layers_served=(0, 3, 4, 5, 6), experts_held=16, max_seq_len=36864),
    # CPU tests: a block of 8 tokens and top 2, so that the selection bites at 64 tokens
    "minimaxm3-debug": MiniMaxM3Config(
        vocab_size=256, hidden_size=64, num_hidden_layers=6, layers_served=(0, 1, 2), num_dense_layers=1,
        dense_intermediate_size=96, intermediate_size=32, shared_intermediate_size=32, num_local_experts=16,
        num_experts_per_tok=4, experts_held=2, num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=8,
        msa_block=8, msa_topk=2, msa_index_heads=2, max_seq_len=1024,
    ),
}


def validate(cfg: MiniMaxM3Config) -> None:
    for l in cfg.layers_served or ():
        if not 0 <= l < cfg.num_hidden_layers:
            raise ValueError(f"layers_served names layer {l} of {cfg.num_hidden_layers}")
    if cfg.experts_first < 0 or cfg.experts_first + cfg.experts_held > cfg.num_local_experts:
        raise ValueError("the experts held must lie inside the routed experts")
    if cfg.num_heads % cfg.num_kv_heads:
        raise ValueError("query heads come in whole groups a KV head")
    if cfg.rotary_dim % 2 or cfg.rotary_dim > cfg.head_dim:
        raise ValueError("RoPE rotates pairs inside a head")


def _check_page(cfg: MiniMaxM3Config, page_size: int) -> None:
    if page_size != cfg.msa_block:
        raise ValueError(
            f"minimaxm3 selects BLOCKS of {cfg.msa_block} tokens and reads them as pages: "
            f"page_size must be {cfg.msa_block}, got {page_size}")


# --------------------------------------------------------------------- //
# Parameters


def _shapes(cfg: MiniMaxM3Config, mlp: str) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of one layer's leaves. kind: 'w' a bfloat16
    matrix (std 1/sqrt(fan_in)), 'o' one that writes the stream (scaled
    down by depth), or the name of a float32 leaf whose range
    ``init_params_fast`` gives."""
    D, Hq, Hk, Dh = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s: Dict[str, Tuple[Tuple[int, ...], str]] = {
        "ln_attn": ((D,), "near_zero"), "ln_mlp": ((D,), "near_zero"),
        # [q | k | v | indexer]: every split on a lane tile at the published widths
        "wx": ((D, (Hq + 2 * Hk + Hk * cfg.msa_index_heads) * Dh), "w"),
        "q_norm": ((Hq, Dh), "near_zero"), "k_norm": ((Hk, Dh), "near_zero"),
        "wo": ((Hq * Dh, D), "o"),
    }
    if mlp == "dense":
        F = cfg.dense_intermediate_size
        s.update({"w_gate_up": ((D, 2 * F), "w"), "w_down": ((F, D), "o")})
    else:
        F, Fs, E = cfg.intermediate_size, cfg.shared_intermediate_size, cfg.experts_held
        s.update({
            "router": ((D, cfg.num_local_experts), "router"), "e_bias": ((cfg.num_local_experts,), "e_bias"),
            "ws_gate_up": ((D, 2 * Fs), "w"), "ws_down": ((Fs, D), "o"),
            "we_gate_up": ((E, D, 2 * F), "w"), "we_down": ((E, F, D), "o"),
        })
    return s


def count_logical_params(cfg: MiniMaxM3Config) -> int:
    """Parameters this chip HOLDS (its layers, its experts, its vocabulary rows)."""
    n = sum(math.prod(shape) for mlp in cfg.layers for shape, _ in _shapes(cfg, mlp).values())
    return n + 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def init_params_fast(cfg: MiniMaxM3Config, seed: int = 0, dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Seeded random weights, drawn leaf by leaf ON the accelerator where
    there is one. Norm weights N(0, 0.1) (the norm multiplies by 1 + w: a
    dropped norm is not hidden), the selection bias ``e_bias`` N(0, 0.1),
    matrices that write the stream scaled down by depth."""
    validate(cfg)
    out_scale = 1.0 / math.sqrt(2 * cfg.num_layers)
    root = jax.random.key(seed, impl="rbg")  # the generator the chip has in hardware
    counter = [0]

    def normal(shape, std, dt=dtype):
        counter[0] += 1
        return _draw(jax.random.fold_in(root, counter[0]), tuple(shape), float(std), 0.0, jnp.dtype(dt).name)

    def leaf(shape, kind):
        if kind == "w":
            return normal(shape, 1 / math.sqrt(shape[-2]))
        if kind == "o":
            return normal(shape, out_scale / math.sqrt(shape[-2]))
        if kind == "router":
            return normal(shape, 1 / math.sqrt(shape[0]), jnp.float32)
        if kind in ("near_zero", "e_bias"):
            return normal(shape, 0.1, jnp.float32)
        raise ValueError(kind)

    with jax.default_device(jax.devices()[0]):  # the accelerator where there is one
        layers = [{name: leaf(shape, kind) for name, (shape, kind) in _shapes(cfg, mlp).items()}
                  for mlp in cfg.layers]
        D = cfg.hidden_size
        return {
            "embed": normal((cfg.vocab_size, D), 1 / math.sqrt(D)),
            "head": normal((D, cfg.vocab_size), 1 / math.sqrt(D)),
            "final_norm": leaf((D,), "near_zero"),
            "layers": layers,
        }


# --------------------------------------------------------------------- //
# Caches and the memory plan


def init_paged_cache(cfg: MiniMaxM3Config, pool_pages: int, page_size: int, num_slots: int,
                     dtype: jnp.dtype = jnp.bfloat16) -> Caches:
    del num_slots  # every layer's state is pages
    _check_page(cfg, page_size)
    Hk, Dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "kv": [{"k": jnp.zeros((pool_pages, Hk, page_size, Dh), dtype),
                "v": jnp.zeros((pool_pages, Hk, page_size, Dh), dtype),
                "kmax": jnp.zeros((pool_pages, Hk, Dh), dtype)} for _ in range(cfg.num_layers)],
        "stats": jnp.zeros((len(STAT_NAMES),), jnp.int32),
    }


def page_bytes(cfg: MiniMaxM3Config, kv_bytes: float = 2) -> int:
    """Bytes one pool page holds over every layer: K and V rows and the summary."""
    strip = cfg.num_kv_heads * cfg.head_dim
    return int(cfg.num_layers * (2 * cfg.msa_block + 1) * strip * kv_bytes)


def kv_bytes_per_token(cfg: MiniMaxM3Config, kv_bytes: float = 2) -> int:
    """Paged bytes a cached token costs, the summary's share rounded up."""
    return -(-page_bytes(cfg, kv_bytes) // cfg.msa_block)


def serving_memory_bytes(cfg: MiniMaxM3Config, batch: int, max_seq_len: int,
                         weight_bytes: int = 2, kv_bytes: float = 2) -> Dict[str, int]:
    weights = count_logical_params(cfg) * weight_bytes
    paged = batch * max_seq_len * kv_bytes_per_token(cfg, kv_bytes)
    return {"weights": weights, "kv_cache": paged, "fixed_state": 0, "total": weights + paged}


def read_stats(caches: Caches):
    return caches["stats"]


# --------------------------------------------------------------------- //
# Layer mathematics


def _norm(x, w, cfg: MiniMaxM3Config, out_dtype=jnp.float32):
    return rms_norm(x, 1.0 + w, cfg.norm_eps, out_dtype)


def _rope(x, positions, cfg: MiniMaxM3Config):
    """The first ``rotary_dim`` dims of x [.., T, H, Dh] rotated, pairs (i, i + rotary_dim / 2)."""
    r = cfg.rotary_dim
    return jnp.concatenate([rope(x[..., :r], positions, cfg.rope_theta), x[..., r:]], axis=-1)


def _project(u, positions, lp: Params, cfg: MiniMaxM3Config, dtype):
    """The normed input u [N, T, D] -> q [N, T, Hq, Dh], k, v [N, T, Hk,
    Dh] in ``dtype`` (what the pools hold and the score product
    multiplies) and the indexer's query [N, T, Hk, Dh] float32: the sum
    of a group's ``msa_index_heads`` heads (no norm, no rotation)."""
    Hq, Hk, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v, ix = jnp.split(_mm(u, lp["wx"]), cfg.splits, axis=-1)
    q = _rope(_norm(q.reshape(q.shape[:-1] + (Hq, Dh)), lp["q_norm"], cfg), positions, cfg)
    k = _rope(_norm(k.reshape(k.shape[:-1] + (Hk, Dh)), lp["k_norm"], cfg), positions, cfg)
    v = v.reshape(v.shape[:-1] + (Hk, Dh))
    ix = jnp.sum(ix.reshape(ix.shape[:-1] + (Hk, cfg.msa_index_heads, Dh)), axis=-2)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), ix


def _pool_summaries(pool: Params, phys, done):
    """Write the summary of the pages ``phys`` [..] whose last token this
    walk wrote (``done`` [..] bool): the element-wise maximum of the
    page's keys AS CACHED. ``pool["k"]`` already holds the walk's rows.
    Every index is in range: a place that completes no page rewrites the
    scratch page's summary with itself (a scatter that DROPS
    out-of-range rows misplaces live ones on this chip, PERF.md section
    7, Opened by PR 41)."""
    at = jnp.where(done, phys, 0)
    kmax = jnp.max(pool["k"][at], axis=-2)  # [.., Hk, Dh]
    return pool["kmax"].at[at].set(jnp.where(done[..., None, None], kmax, pool["kmax"][0]))


def block_scores(ix, kmax, pages):
    """ix [N, T, Hk, Dh] float32 x the summaries of each row's pages
    (``kmax`` [P, Hk, Dh], ``pages`` [N, Pmax]) -> [N, T, Hk, Pmax] float32."""
    km = kmax[pages].astype(jnp.float32)  # [N, Pmax, Hk, Dh]
    return jnp.einsum("nthd,nphd->nthp", ix, km, precision=_HI)


def select_pages(scores, positions, cfg: MiniMaxM3Config):
    """scores [N, T, Hk, Pmax], positions [N, T] -> (pages [N, T, Hk, K]
    int32, valid [N, T, Hk, K] bool), K = ``pages_a_read``: place 0 is
    block 0 (always valid: it holds a token the query sees), then the
    top-k of the complete blocks ``1 .. j_t - 2`` (valid where there is
    such a block), then ``j_t - 1`` (valid from block 1 on) and the open
    block ``j_t`` (valid from block 1 on)."""
    Pmax = scores.shape[-1]
    jt = (positions // cfg.msa_block)[..., None, None]  # [N, T, 1, 1]
    j = jnp.arange(Pmax, dtype=jnp.int32)
    cand = (j >= 1) & (j <= jt - 2)
    k = min(cfg.msa_topk, Pmax)
    vals, top = lax.top_k(jnp.where(cand, scores, _NEG), k)  # ties: the lower index first
    shape = scores.shape[:-1] + (1,)
    local, open_ = jnp.broadcast_to(jt - 1, shape), jnp.broadcast_to(jt, shape)
    pages = jnp.concatenate([jnp.zeros(shape, jnp.int32), top.astype(jnp.int32),
                             jnp.maximum(local, 0), open_], axis=-1)
    valid = jnp.concatenate([jnp.ones(shape, bool), vals > _NEG / 2, local >= 1, open_ >= 1], axis=-1)
    return pages, valid


def _selected_mask(sel_pages, sel_valid, n_pages: int):
    """The selection lists [N, T, Hk, K] as a mask over logical pages [N, Hk, T, n_pages]."""
    hit = (sel_pages[..., None] == jnp.arange(n_pages, dtype=jnp.int32)) & sel_valid[..., None]
    return jnp.moveaxis(jnp.any(hit, axis=-2), 2, 1)


def _attend_selected_blocks(q, pool, pages, positions, n_tokens, sel_pages, sel_valid, block_pages: int = 4):
    """Chunk attention over a row's pages with a running softmax, in
    blocks of ``block_pages`` pages, as far as ``n_tokens`` [N] reach,
    under each query's own selection (``sel_pages`` / ``sel_valid`` [N,
    T, Hk, K]) and its causal clamp; a block of pages NO query selected
    is skipped. q [N, T, Hq, Dh]; pool k / v [P, Hk, page, Dh]; pages
    [N, Pmax]; positions [N, T]. Returns [N, T, Hq, Dh] float32."""
    N, T, Hq, Dh = q.shape
    _, Hk, page, _ = pool["k"].shape
    G = Hq // Hk
    Pmax = pages.shape[1]
    bp = min(block_pages, Pmax)
    while Pmax % bp:
        bp -= 1
    W = bp * page
    n_blocks = jnp.max((n_tokens + W - 1) // W)
    q5 = q.reshape(N, T, Hk, G, Dh)
    sel = _selected_mask(sel_pages, sel_valid, Pmax)

    def rows(buf, pg):  # [P, Hk, page, Dh] x [N, bp] -> [N, Hk, bp * page, Dh]
        return jnp.moveaxis(buf[pg], 1, 2).reshape(N, Hk, W, Dh)

    def attend(i, carry, mine):
        m, l, acc = carry
        pg = lax.dynamic_slice_in_dim(pages, i * bp, bp, axis=1)
        kb, vb = rows(pool["k"], pg), rows(pool["v"], pg)
        sc = jnp.einsum("ntkgd,nksd->nkgts", q5, kb, preferred_element_type=jnp.float32) * (Dh ** -0.5)
        seen = (i * W + jnp.arange(W, dtype=jnp.int32))[None, None, :] <= positions[:, :, None]  # [N, T, W]
        ok = (jnp.repeat(mine, page, axis=-1) & seen[:, None])[:, :, None]  # [N, Hk, 1, T, W]
        sc = jnp.where(ok, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum("nkgts,nksd->nkgtd", p.astype(vb.dtype), vb,
                                       preferred_element_type=jnp.float32)
        return m_new, l, acc

    def body(i, carry):
        mine = lax.dynamic_slice_in_dim(sel, i * bp, bp, axis=3)  # [N, Hk, T, bp]
        return lax.cond(jnp.any(mine), lambda c: attend(i, c, mine), lambda c: c, carry)

    init = (jnp.full((N, Hk, G, T, 1), _NEG, jnp.float32), jnp.zeros((N, Hk, G, T, 1), jnp.float32),
            jnp.zeros((N, Hk, G, T, Dh), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_blocks, body, init)
    o = acc / jnp.where(l == 0.0, 1.0, l)
    return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(N, T, Hq, Dh)


def mlp_sublayer(x, lp: Params, mlp: str, cfg: MiniMaxM3Config, count, kernel: Optional[str]):
    """``x + MLP(N2(x))`` over x [.., D] float32; returns (x, moe stats or None)."""
    u = _norm(x, lp["ln_mlp"], cfg)
    if mlp == "dense":
        return x + swiglu_mlp(u, lp["w_gate_up"], lp["w_down"], cfg.swiglu_limit, cfg.swiglu_alpha), None
    with jax.named_scope("experts"):
        y, stats = moe(u.reshape(-1, u.shape[-1]), lp, cfg, count.reshape(-1), kernel, oai_alpha=cfg.swiglu_alpha)
    return x + y.reshape(x.shape), stats


def head(params: Params, cfg: MiniMaxM3Config, hidden):
    """hidden [N, D] -> float32 logits [N, V]."""
    return _mm(_norm(hidden, params["final_norm"], cfg), params["head"])


def _selection_stats(valid, positions, counted, done, cfg: MiniMaxM3Config):
    """[selected, live, scored, pooled] of ONE layer's read: the (page, KV
    head) strips the counted queries selected, the strips live under them
    (what a walk of every page would fetch), the candidate (block, KV
    head) scores, the pages whose summary was written."""
    jt = positions // cfg.msa_block
    per_head = jnp.where(counted, 1, 0) * cfg.num_kv_heads
    return jnp.stack([
        jnp.sum(jnp.where(counted[..., None, None], valid, False)),
        jnp.sum(per_head * (jt + 1)),
        jnp.sum(per_head * jnp.maximum(jt - 2, 0)),
        jnp.sum(done),
    ]).astype(jnp.int32)


# --------------------------------------------------------------------- //
# The chunk walk: prefill and chunked extend


def _chunk_walk(params: Params, cfg: MiniMaxM3Config, caches: Caches, tokens, offsets, valid, slots,
                tables, page_size: int, grouped_matmul: Optional[str] = None,
                capture: Optional[Dict[str, Any]] = None, selected_chunk: Optional[str] = None):
    """All layers over a chunk [N, C] per row; returns (the residual row
    of each row's last valid position [N, D], caches).

    A row's context is whatever its page table maps below ``offsets``:
    pages this row wrote, or pages a prefix entry shares with it, each
    complete one with its summary. A row with ``valid == 0`` changes
    nothing: its pool writes are dropped. A layer writes the chunk's
    keys and values, then the summary of every page the chunk completed
    (from the keys as cached), THEN scores its queries: a block the
    chunk completes is a candidate for the chunk's later queries.
    ``capture`` (a dict) receives every layer's selection, ``pages`` /
    ``valid`` [L, N, C, Hk, K]. ``selected_chunk`` ('compiled' /
    'interpret') reads through ``ops/selected_chunk_read.py`` where the
    shapes tile (``selected_chunk_kind``), else the XLA loop does."""
    _check_page(cfg, page_size)
    N, C = tokens.shape
    Pmax = tables.shape[1]
    S = Pmax * page_size
    idx = jnp.arange(C, dtype=jnp.int32)
    positions = jnp.minimum(offsets[:, None] + idx[None, :], S - 1)
    tok_valid = idx[None, :] < valid[:, None]
    row_live = valid > 0
    last = jnp.clip(valid, 1, C) - 1
    row_tables = tables[slots]
    P = caches["kv"][0]["k"].shape[0]
    phys = jnp.take_along_axis(row_tables, positions // page_size, axis=1)
    phys = jnp.where(tok_valid, phys, P)  # padding: dropped
    sip = positions % page_size
    n_tokens = jnp.where(row_live, offsets + valid, 0)
    # the pages this chunk completes: page j with its last token inside [offsets, offsets + valid)
    cand = offsets[:, None] // page_size + jnp.arange(C // page_size + 1, dtype=jnp.int32)[None, :]
    ends = (cand + 1) * page_size
    done = (ends > offsets[:, None]) & (ends <= n_tokens[:, None])
    done_phys = jnp.take_along_axis(row_tables, jnp.minimum(cand, Pmax - 1), axis=1)
    selected_chunk = selected_chunk_kind(cfg, selected_chunk, C)
    # the kernel's list of live (row, query tile, block) items: once a walk, every layer's read shares it
    work = selected_chunk_read.chunk_work_list(row_tables, positions, n_tokens, page_size, P) if selected_chunk else None

    x = params["embed"][tokens].astype(jnp.float32)  # [N, C, D]
    dtype = params["embed"].dtype
    new = dict(caches, kv=list(caches["kv"]))
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)
    msa_stats = jnp.zeros((4,), jnp.int32)
    blocks_read = jnp.zeros((), jnp.int32)
    kept = []
    for l, mlp in enumerate(cfg.layers):
        lp = params["layers"][l]
        with jax.named_scope("msa_chunk"):
            q, k, v, ix = _project(_norm(x, lp["ln_attn"], cfg), positions, lp, cfg, dtype)
            old = caches["kv"][l]
            pool = {"k": _write_rows(old["k"], phys, sip, k), "v": _write_rows(old["v"], phys, sip, v),
                    "kmax": old["kmax"]}
            pool["kmax"] = _pool_summaries(pool, done_phys, done)
            new["kv"][l] = pool
            with jax.named_scope("msa_index"):
                sel_pages, sel_valid = select_pages(block_scores(ix, pool["kmax"], row_tables), positions, cfg)
            kept.append((sel_pages, sel_valid))
            if selected_chunk:
                src, n_read = selected_chunk_read.chunk_live_steps(work, sel_pages, sel_valid)
                blocks_read = blocks_read + n_read
                o = selected_chunk_read.selected_chunk_read(
                    q, pool["k"], pool["v"], positions, sel_pages, sel_valid, work, src,
                    interpret=(selected_chunk == "interpret"))
            else:
                o = _attend_selected_blocks(q, pool, row_tables, positions, n_tokens, sel_pages, sel_valid)
            x = x + _mm(o.reshape(N, C, -1), lp["wo"])
            msa_stats = msa_stats + _selection_stats(sel_valid, positions, tok_valid, done, cfg)
        x, stats = mlp_sublayer(x, lp, mlp, cfg, tok_valid, grouped_matmul)
        if stats is not None:
            moe_stats = moe_stats + stats
    if capture is not None:
        capture.update(pages=jnp.stack([p for p, _ in kept]), valid=jnp.stack([v for _, v in kept]))
    L = cfg.num_layers
    if selected_chunk:
        chunk_stats = jnp.stack([L, 0, blocks_read, L * cfg.num_kv_heads * work.n_work[0]])
    else:
        chunk_stats = jnp.asarray([0, L, 0, 0])
    new["stats"] = jnp.concatenate([moe_stats, msa_stats, chunk_stats]).astype(jnp.int32)
    return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0], new


def prefill_paged(params: Params, cfg: MiniMaxM3Config, caches: Caches, tokens, lengths, slots, tables,
                  page_size: int, grouped_matmul: Optional[str] = None, selected_chunk: Optional[str] = None,
                  **_paths):
    """A whole prompt in one program, the REFERENCE walk: (last-position logits [N, V], caches)."""
    hidden, caches = _chunk_walk(params, cfg, caches, tokens, jnp.zeros_like(lengths), lengths, slots,
                                 tables, page_size, grouped_matmul, selected_chunk=selected_chunk)
    return head(params, cfg, hidden), caches


def extend_paged(params: Params, cfg: MiniMaxM3Config, caches: Caches, tokens, offsets, valid, slots,
                 tables, window: int, page_size: int, grouped_matmul: Optional[str] = None,
                 capture: Optional[Dict[str, Any]] = None, selected_chunk: Optional[str] = None, **_paths):
    """One chunk of a chunked prefill: (the residual row [N, D] of each
    row's last valid position, caches)."""
    del window  # the read follows each row's own context
    return _chunk_walk(params, cfg, caches, tokens, offsets, valid, slots, tables, page_size, grouped_matmul,
                       capture, selected_chunk)


# --------------------------------------------------------------------- //
# One decode step


def decode_paged(params: Params, cfg: MiniMaxM3Config, caches: Caches, tokens, positions, live, tables,
                 window: Optional[int], page_size: int, page_kernel: Optional[str] = None,
                 grouped_matmul: Optional[str] = None, selected_read: Optional[str] = None,
                 capture: Optional[Dict[str, Any]] = None, **_paths):
    """One token per slot: (logits [B, V], caches). A dead row writes
    nothing to the pools. ``selected_read`` ('compiled' / 'interpret')
    fetches the selected pages through ``ops/page_attention.py``
    ``selected_page_attention``; None gathers the same strips in XLA.
    Either way a step reads the SELECTED pages and no others."""
    del window, page_kernel  # the live-page walk is not this family's read
    _check_page(cfg, page_size)
    B = tokens.shape[0]
    P = caches["kv"][0]["k"].shape[0]
    pos2 = positions[:, None]
    sip = pos2 % page_size
    phys = jnp.where(live[:, None], jnp.take_along_axis(tables, pos2 // page_size, axis=1), P)
    done = live & (positions % page_size == page_size - 1)  # the step writes the page's last token

    x = params["embed"][tokens].astype(jnp.float32)[:, None]  # [B, 1, D]
    dtype = params["embed"].dtype
    new = dict(caches, kv=list(caches["kv"]))
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)
    msa_stats = jnp.zeros((4,), jnp.int32)
    kept = []
    for l, mlp in enumerate(cfg.layers):
        lp = params["layers"][l]
        q, k, v, ix = _project(_norm(x, lp["ln_attn"], cfg), pos2, lp, cfg, dtype)
        old = caches["kv"][l]
        pool = {"k": _write_rows(old["k"], phys, sip, k), "v": _write_rows(old["v"], phys, sip, v),
                "kmax": old["kmax"]}
        pool["kmax"] = _pool_summaries(pool, phys[:, 0], done)
        new["kv"][l] = pool
        with jax.named_scope("msa_index"):
            sel_pages, sel_valid = select_pages(block_scores(ix, pool["kmax"], tables), pos2, cfg)
            sel_pages, sel_valid = sel_pages[:, 0], sel_valid[:, 0]  # [B, Hk, K]
            kept.append((sel_pages, sel_valid))
            work = page_attention.selected_work_list(tables, sel_pages, sel_valid) if selected_read else None
        with jax.named_scope("msa_read"):
            if selected_read:
                o = page_attention.selected_page_attention(
                    q[:, 0], pool["k"], pool["v"], positions, work, interpret=(selected_read == "interpret"))
            else:
                o = page_attention.selected_page_gather(
                    q[:, 0], pool["k"], pool["v"], tables, positions, sel_pages, sel_valid)
        x = x + _mm(o.reshape(B, 1, -1), lp["wo"])
        msa_stats = msa_stats + _selection_stats(sel_valid[:, None], pos2, live[:, None], done, cfg)
        x, stats = mlp_sublayer(x, lp, mlp, cfg, live[:, None], grouped_matmul)
        if stats is not None:
            moe_stats = moe_stats + stats
    if capture is not None:  # every layer's selection [L, B, Hk, K]
        capture.update(pages=jnp.stack([p for p, _ in kept]), valid=jnp.stack([v for _, v in kept]))
    new["stats"] = jnp.concatenate([moe_stats, msa_stats, jnp.zeros((4,), jnp.int32)]).astype(jnp.int32)
    return head(params, cfg, x[:, 0]), new


def selected_read_kind(cfg: MiniMaxM3Config, kind: Optional[str]) -> Optional[str]:
    """``kind`` where ``ops/page_attention.py`` ``selected_page_attention``
    serves this geometry, else None (the XLA gather of the same strips)."""
    ok = kind and page_attention.supports_selected(
        cfg.msa_block, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads, interpret=(kind == "interpret"))
    return kind if ok else None


def selected_chunk_kind(cfg: MiniMaxM3Config, kind: Optional[str], chunk: Optional[int] = None) -> Optional[str]:
    """``kind`` where ``ops/selected_chunk_read.py`` tiles this
    configuration's widths (and a chunk width, where the caller knows
    it), else None (``_attend_selected_blocks``): decided from the shapes."""
    ok = kind and selected_chunk_read.supported(cfg.msa_block, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads, chunk)
    return kind if ok else None


# --------------------------------------------------------------------- //
# The whole sequence at once, no cache: what the tests hold the paged
# walks against (the plain reference of the benchmark is
# perfbench/arch/minimaxm3.py and imports nothing from here)


def selection_mask(ix, k, cfg: MiniMaxM3Config):
    """ix [N, T, Hk, Dh] float32, k [N, T, Hk, Dh] as cached -> the key
    set of every query [N, Hk, T, T] bool, from the equations: block
    summaries of complete blocks, top-k, first, local, open, causal."""
    N, T, Hk, Dh = k.shape
    B = cfg.msa_block
    nb = -(-T // B)
    kp = jnp.pad(k, ((0, 0), (0, nb * B - T), (0, 0), (0, 0)), constant_values=-jnp.inf)
    kbar = jnp.max(kp.reshape(N, nb, B, Hk, Dh), axis=2).astype(jnp.float32)  # [N, nb, Hk, Dh]
    kbar = jnp.where(jnp.isfinite(kbar), kbar, 0.0)  # an incomplete block is never a candidate
    scores = jnp.einsum("nthd,nphd->nthp", ix, kbar, precision=_HI)
    t = jnp.arange(T, dtype=jnp.int32)
    pages, valid = select_pages(scores, jnp.broadcast_to(t, (N, T)), cfg)
    blocks = _selected_mask(pages, valid, nb)  # [N, Hk, T, nb]
    return jnp.repeat(blocks, B, axis=-1)[..., :T] & (t[:, None] >= t[None, :])


def forward_full(params: Params, cfg: MiniMaxM3Config, tokens):
    """Logits [N, T, V] of tokens [N, T]: the selection as a mask over the
    whole causal score matrix, the experts densely over the held ones."""
    N, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (N, T))
    everyone = jnp.ones((N, T), bool)
    dtype = params["embed"].dtype
    G = cfg.num_heads // cfg.num_kv_heads
    x = params["embed"][tokens].astype(jnp.float32)
    for l, mlp in enumerate(cfg.layers):
        lp = params["layers"][l]
        q, k, v, ix = _project(_norm(x, lp["ln_attn"], cfg), positions, lp, cfg, dtype)
        mask = selection_mask(ix, k, cfg)  # [N, Hk, T, T]
        kh, vh = _heads_first(k), _heads_first(v)
        o = jnp.concatenate([
            _gqa(q[:, :, h * G:(h + 1) * G], kh[:, h:h + 1], vh[:, h:h + 1], mask[:, h])
            for h in range(cfg.num_kv_heads)], axis=2)
        x = x + _mm(o.reshape(N, T, -1), lp["wo"])
        x, _ = mlp_sublayer(x, lp, mlp, cfg, everyone, None)
    return head(params, cfg, x.reshape(N * T, -1)).reshape(N, T, -1)
