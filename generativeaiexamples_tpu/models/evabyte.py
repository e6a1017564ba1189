"""EvaByte (``evabyte``, 6.5B, byte-level) for the serving engine.

A dense pre-norm decoder over a 320-id byte vocabulary whose attention
keeps the PRESENT exact and the PAST compressed (EVA, Zheng et al., ICLR
2023, arXiv:2302.04542, in the chunked form the published model runs).
Residual stream in float32; ``N(u) = u / sqrt(mean(u^2) + 1e-5) * (1 +
g)`` (``norm_add_unit_offset``). With ``a = N_1(x)``: ``q, k, v = W_q a,
W_k a, W_v a`` (one matrix ``wqkv``), ``H`` heads of ``Dh``, no bias; q
and k rotated over the whole head in pairs ``(i, i + Dh/2)``
(``models/afmoe.py`` ``rope``), theta 1e5. ``s = Dh^-0.5``, window ``W``
= 2048, chunk ``C`` = 16. Per head with learned ``mu, phi in R^Dh``:

- chunk ``c`` = tokens ``[C c, C c + C)``: ``kbar_c = sum_m softmax_m(mu
  . k_m) k_m`` and ``vbar_c = sum_m softmax_m(s (phi . k_m - |k_m|^2 /
  2)) v_m``, both softmaxes over the chunk's tokens, in float32;
- query ``t`` in window ``w = t // W``: ONE softmax over the exact keys
  ``w W .. t`` and the summaries of every chunk of every CLOSED window
  (``c < (W / C) w``), scores ``s q . k`` and ``s q . kbar``;
- ``x += W_o o``; ``x += W_down(silu(W_gate b) * W_up b)``, ``b =
  N_2(x)``; ``logits = W_head N_f(x)`` in float32, ``W_head [D, 8 x
  320]``: rows ``[320 j, 320 j + 320)`` predict byte ``t + 1 + j``. The
  engine serves the next byte (``head``: the first 320 columns).

**Two kinds of cache with different lifetimes** (docs/model_registry.md).
Fixed per slot: the rotated K and V of the OPEN window, ``win [slots, W,
H * Dh]`` a layer, position ``t`` at row ``t % W`` with valid length ``t
% W + 1``: the buffer RESTARTS at a window boundary by its valid length
alone (nothing is cleared, a stale row is never read). Paged: one summary
row of K and one of V a chunk of every window, ``sum [P, page_size / C,
H * Dh / 2]`` uint32 a layer: a row packs head ``h``'s bfloat16 (low
half) beside head ``h + H / 2``'s (high half), so that a page of fewer
than sixteen rows is whole 32-bit tiles for the read kernel's DMA
(``ops/eva_read.py`` ``pack_rows`` / ``unpack_rows``). A chunk's summary is written by the
walk that writes the chunk's last token (the chunk walk from its own
keys, a decode step from the buffer's last ``C`` rows); a row reads the
first ``(W / page_size) (t // W)`` pages of its table, so the pages of
the open window are written as its chunks complete and never read before
it closes. ``stats`` holds ``STAT_NAMES``' int32 counts of the last walk.

**One resolved kind, two reads** (``eva_read``: ``compiled`` /
``interpret`` / None). A decode step reads through
``ops/eva_read.py`` ``eva_decode_read``, a chunk walk through
``eva_chunk_read`` where its shapes tile too (``chunk_read_in_kernel``):
the chunk's keys go to the buffer first and are then buffer rows under a
causal bound, so both reads have two sources, read in place. Where no
kernel serves, ``eva_decode_read_xla`` and ``_attend`` read the same keys
through XLA: what the tests hold the kernels to, not a second serving
path. ``eva_chunk_kernel_layers`` / ``eva_chunk_xla_layers`` in the stats
say which body read a chunk.

**What the walks ask of the engine**: ``page_size`` divides ``W`` and is
a multiple of ``C``; a chunk of an extend lies inside ONE window (the
engine sends chunks at multiples of ``prefill_chunk`` no wider than it,
and ``prefill_chunk`` divides ``W``: the walk refuses a width that does
not divide ``W``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models.afmoe import rope
from generativeaiexamples_tpu.models.glm5next import _mm, rms_norm, swiglu_mlp
from generativeaiexamples_tpu.ops import eva_read as eva_ops

Params = Dict[str, Any]
Caches = Dict[str, Any]
_NEG = -1e30

STAT_NAMES = ("eva_window_tokens_read", "eva_summaries_read", "eva_summaries_written", "eva_windows_closed",
              "eva_chunk_kernel_layers", "eva_chunk_xla_layers")


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """Published sizes (config.json); ``layers_served`` lists the
    published layers this chip serves (None: all)."""

    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    layers_served: Optional[Tuple[int, ...]] = None
    num_heads: int = 32
    head_dim: int = 128
    chunk_size: int = 16
    window_size: int = 2048
    num_pred_heads: int = 8
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 32768

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers if self.layers_served is None else len(self.layers_served)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def chunks_a_window(self) -> int:
        return self.window_size // self.chunk_size


PRESETS: Dict[str, EvaByteConfig] = {
    # one stage of a four-stage pipeline: the first eight layers, the embedding and all eight output heads
    "evabyte-6.5b-pp4": EvaByteConfig(layers_served=tuple(range(8)), max_seq_len=20480),
    # CPU tests: two layers at a size a test counts by hand; window 32, chunk 4, two output heads
    "evabyte-debug": EvaByteConfig(
        vocab_size=40, hidden_size=64, intermediate_size=96, num_hidden_layers=2, num_heads=4, head_dim=16,
        chunk_size=4, window_size=32, num_pred_heads=2, max_seq_len=1024,
    ),
}


def validate(cfg: EvaByteConfig) -> None:
    for l in cfg.layers_served or ():
        if not 0 <= l < cfg.num_hidden_layers:
            raise ValueError(f"layers_served names layer {l} of {cfg.num_hidden_layers}")
    if cfg.head_dim % 2 or cfg.num_heads % 2:
        raise ValueError("RoPE rotates halves of a head, and a summary row packs two halves of the heads")
    if cfg.window_size % cfg.chunk_size:
        raise ValueError("a window is whole chunks")


def _check_pages(cfg: EvaByteConfig, page_size: int) -> None:
    if page_size % cfg.chunk_size or cfg.window_size % page_size:
        raise ValueError(
            f"evabyte pages hold whole chunk summaries and a window is whole pages: page_size ({page_size}) must "
            f"be a multiple of chunk_size ({cfg.chunk_size}) and divide window_size ({cfg.window_size})")


# --------------------------------------------------------------------- //
# Parameters


def _shapes(cfg: EvaByteConfig) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of one layer's leaves. kind: 'w' a bfloat16
    matrix (std 1/sqrt(fan_in)); 'offset' a norm's float32 ``g`` and
    'pool' a head's float32 ``mu`` / ``phi``, both N(0, 0.1)."""
    D, F, H, Dh = cfg.hidden_size, cfg.intermediate_size, cfg.num_heads, cfg.head_dim
    return {
        "n1": ((D,), "offset"), "n2": ((D,), "offset"),
        "wqkv": ((D, 3 * cfg.q_dim), "w"), "wo": ((cfg.q_dim, D), "w"),
        "mu": ((H, Dh), "pool"), "phi": ((H, Dh), "pool"),
        "w_gate_up": ((D, 2 * F), "w"), "w_down": ((F, D), "w"),
    }


def count_logical_params(cfg: EvaByteConfig) -> int:
    """Parameters this chip HOLDS: its layers, the embedding, every output head, the final norm."""
    n = cfg.num_layers * sum(math.prod(shape) for shape, _ in _shapes(cfg).values())
    return n + (1 + cfg.num_pred_heads) * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def init_params_fast(cfg: EvaByteConfig, seed: int = 0, dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Seeded random weights, drawn leaf by leaf ON the accelerator where
    there is one. Every term of the equations is drawn away from the
    value that would hide it: the norms' offsets ``g`` and the heads'
    ``mu`` and ``phi`` N(0, 0.1) (a dropped offset, a pooling that
    ignores its vector, each show); the embedding N(0, 1), a unit row."""
    validate(cfg)
    root = jax.random.key(seed, impl="rbg")  # the generator the chip has in hardware
    counter = [0]

    def normal(shape, std, dt=dtype):
        counter[0] += 1
        return _draw(jax.random.fold_in(root, counter[0]), tuple(shape), float(std), jnp.dtype(dt).name)

    def leaf(shape, kind):
        return normal(shape, 1 / math.sqrt(shape[-2])) if kind == "w" else normal(shape, 0.1, jnp.float32)

    with jax.default_device(jax.devices()[0]):  # the accelerator where there is one
        D = cfg.hidden_size
        return {
            "embed": normal((cfg.vocab_size, D), 1.0),
            "head": normal((D, cfg.num_pred_heads * cfg.vocab_size), 1 / math.sqrt(D)),
            "final_norm": leaf((D,), "offset"),
            "layers": [{name: leaf(shape, kind) for name, (shape, kind) in _shapes(cfg).items()}
                       for _ in range(cfg.num_layers)],
        }


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype_name):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.dtype(dtype_name))


# --------------------------------------------------------------------- //
# Caches and the memory plan


def init_paged_cache(cfg: EvaByteConfig, pool_pages: int, page_size: int, num_slots: int,
                     dtype: jnp.dtype = jnp.bfloat16) -> Caches:
    _check_pages(cfg, page_size)
    win = (num_slots, cfg.window_size, cfg.q_dim)
    pages = (pool_pages, page_size // cfg.chunk_size, cfg.q_dim // 2)
    return {
        "win": [{"k": jnp.zeros(win, dtype), "v": jnp.zeros(win, dtype)} for _ in range(cfg.num_layers)],
        "sum": [{"k": jnp.zeros(pages, jnp.uint32), "v": jnp.zeros(pages, jnp.uint32)}
                for _ in range(cfg.num_layers)],
        "stats": jnp.zeros((len(STAT_NAMES),), jnp.int32),
    }


def kv_bytes_per_token(cfg: EvaByteConfig, kv_bytes: float = 2) -> int:
    """Paged bytes a cached token costs: a K and a V summary row a chunk of ``chunk_size`` tokens, every layer."""
    return int(cfg.num_layers * 2 * cfg.q_dim * kv_bytes) // cfg.chunk_size


def fixed_state_bytes_per_slot(cfg: EvaByteConfig, kv_bytes: float = 2) -> int:
    """Bytes a slot holds whatever its sequence length: the open window's K and V, every layer."""
    return int(cfg.num_layers * cfg.window_size * 2 * cfg.q_dim * kv_bytes)


def serving_memory_bytes(cfg: EvaByteConfig, batch: int, max_seq_len: int,
                         weight_bytes: int = 2, kv_bytes: float = 2) -> Dict[str, int]:
    weights = count_logical_params(cfg) * weight_bytes
    paged = batch * max_seq_len * kv_bytes_per_token(cfg, kv_bytes)
    fixed = batch * fixed_state_bytes_per_slot(cfg, kv_bytes)
    return {"weights": weights, "kv_cache": paged + fixed, "fixed_state": fixed, "total": weights + paged + fixed}


def read_stats(caches: Caches):
    return caches["stats"]


def eva_read_kind(cfg: EvaByteConfig, kind: Optional[str]) -> Optional[str]:
    """The decode read's path: ``ops/eva_read.py`` compiled where the
    published widths tile the chip, interpreted on request, else None
    (the XLA read of the same keys)."""
    if kind == "compiled" and not eva_ops.supports(cfg.num_heads, cfg.head_dim, cfg.window_size):
        return None
    return kind


# --------------------------------------------------------------------- //
# Layer mathematics


def norm(x, g, cfg: EvaByteConfig):
    return rms_norm(x, 1.0 + g, cfg.norm_eps, jnp.float32)


def _project(a, positions, lp: Params, cfg: EvaByteConfig, dtype):
    """The normed input a [N, T, D] -> q, k, v [N, T, H, Dh] in ``dtype`` (what the caches hold and the score
    product multiplies); q and k rotated at ``positions`` [N, T]."""
    shape = a.shape[:-1] + (cfg.num_heads, cfg.head_dim)
    q, k, v = (y.reshape(shape) for y in jnp.split(_mm(a, lp["wqkv"]), 3, axis=-1))
    q, k = rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def summarise(k, v, lp: Params, cfg: EvaByteConfig):
    """k, v [.., C, H, Dh] as cached -> (kbar, vbar) [.., H, Dh] float32:
    the chunk's learned poolings, both softmaxes over the chunk's ``C``
    tokens in float32."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    alpha = jax.nn.softmax(jnp.sum(k32 * lp["mu"], axis=-1), axis=-2)  # [.., C, H]
    feat = cfg.head_dim ** -0.5 * (jnp.sum(k32 * lp["phi"], axis=-1) - 0.5 * jnp.sum(k32 * k32, axis=-1))
    beta = jax.nn.softmax(feat, axis=-2)
    return jnp.sum(alpha[..., None] * k32, axis=-3), jnp.sum(beta[..., None] * v32, axis=-3)


def _write_summaries(pool, k, v, done, page, row, lp: Params, cfg: EvaByteConfig):
    """The summaries of chunks k, v [.., C, H, Dh] into ``pool`` at
    (``page``, ``row``) [..] where ``done`` [..] says the chunk is
    complete; an open chunk's write is dropped."""
    kbar, vbar = summarise(k, v, lp, cfg)
    at_page = jnp.where(done, page, pool["k"].shape[0])
    return {"k": pool["k"].at[at_page, row].set(eva_ops.pack_rows(kbar), mode="drop"),
            "v": pool["v"].at[at_page, row].set(eva_ops.pack_rows(vbar), mode="drop")}


def _write_rows(buf, rows, slots, base, keep):
    """A chunk's ``rows`` [N, T, H * Dh] into the buffers ``buf`` [slots,
    W, H * Dh]: row ``n``'s into slot ``slots[n]`` from buffer row
    ``base[n]`` on (the chunk lies inside one window: ``base + T <= W``),
    ONE slab a row; where ``keep`` [N, T] is False (a padding token) the
    buffer keeps what it held. A scatter of the rows one by one under
    ``mode="drop"`` cost 157 us a buffer on the chip, 2.5 ms a chunk of
    eight layers, for 4 MB written (PERF.md section 6, PR 58)."""
    N, T, HD = rows.shape
    for n in range(N):
        start = (slots[n], base[n], jnp.int32(0))
        held = jax.lax.dynamic_slice(buf, start, (1, T, HD))
        buf = jax.lax.dynamic_update_slice(buf, jnp.where(keep[n][None, :, None], rows[n][None], held), start)
    return buf


def _attend(q, keys, vals, seen, head_groups: int = 4):
    """q [N, T, H, Dh] against keys / vals [N, S, H, Dh] under the mask
    ``seen`` [N, T, S], one softmax a query and head: [N, T, H * Dh]
    float32. The heads go through a group at a time (``lax.map``): a
    chunk of 512 queries against ~3,800 keys is 252 MB of float32 scores
    over 32 heads, and XLA keeps three such arrays alive (0.80 GB of
    temporaries in the rehearsal compile, beside a cache that fills the
    chip); a quarter of the heads at a time keeps under half of it."""
    N, T, H, Dh = q.shape
    groups = head_groups if H % head_groups == 0 else 1
    split = lambda x: jnp.moveaxis(x.reshape(x.shape[:2] + (groups, H // groups, Dh)), 2, 0)  # noqa: E731

    def one(group):
        qg, kg, vg = group
        sc = jnp.einsum("nthd,nshd->nhts", qg, kg, preferred_element_type=jnp.float32) * (Dh ** -0.5)
        p = jax.nn.softmax(jnp.where(seen[:, None], sc, _NEG), axis=-1)
        return jnp.einsum("nhts,nshd->nthd", p.astype(vg.dtype), vg, preferred_element_type=jnp.float32)

    out = jax.lax.map(one, (split(q), split(keys), split(vals)))  # [groups, N, T, H / groups, Dh]
    return jnp.moveaxis(out, 0, 2).reshape(N, T, H * Dh)


def _mlp(x, lp: Params, cfg: EvaByteConfig):
    with jax.named_scope("dense_mlp"):
        return x + swiglu_mlp(norm(x, lp["n2"], cfg), lp["w_gate_up"], lp["w_down"], math.inf)


def head(params: Params, cfg: EvaByteConfig, hidden, all_heads: bool = False):
    """hidden [N, D] -> float32 logits: the next byte's [N, V] (what the
    engine samples), or with ``all_heads`` every output head's [N, 8 V]."""
    w = params["head"] if all_heads else params["head"][:, : cfg.vocab_size]
    return _mm(norm(hidden, params["final_norm"], cfg), w)


def _stats(cfg: EvaByteConfig, window_read, summaries_read, written, closed, chunk_kernel: Optional[bool] = None):
    """``STAT_NAMES`` of one walk, every count summed over the layers;
    ``chunk_kernel`` says which body read a CHUNK walk's attention (None:
    a decode step, neither)."""
    chunk = [jnp.int32(chunk_kernel is True), jnp.int32(chunk_kernel is False)]
    return (jnp.stack([window_read, summaries_read, written, closed] + chunk) * cfg.num_layers).astype(jnp.int32)


# --------------------------------------------------------------------- //
# The chunk walk: prefill and chunked extend


def chunk_read_in_kernel(cfg: EvaByteConfig, eva_read: Optional[str], T: int, page_size: int) -> bool:
    """Whether a chunk walk of ``T`` tokens reads through
    ``ops/eva_read.py`` ``eva_chunk_read``: the family's resolved
    ``eva_read`` kind governs both reads, and the chunk's shapes must tile
    too (interpreted: any size)."""
    return eva_read == "interpret" or (
        eva_read == "compiled" and eva_ops.chunk_supports(T, page_size // cfg.chunk_size))


def _chunk_walk(params: Params, cfg: EvaByteConfig, caches: Caches, tokens, offsets, valid, slots, tables,
                page_size: int, eva_read: Optional[str] = None):
    """All layers over a chunk [N, T] a row that lies inside ONE window;
    returns (the residual row of each row's last valid position [N, D],
    caches). A query sees, under one softmax, the buffer's rows below the
    chunk (``offsets % W`` of them), the chunk's own keys up to itself and
    the summaries in the row's pages of every closed window. The chunk's
    keys and values go to the buffer, and its completed chunks' summaries
    to the window's pages. Where ``eva_read`` resolved and the shapes tile
    (``chunk_read_in_kernel``) the buffer is written FIRST and the read is
    one Pallas kernel over two sources, the chunk's own keys ordinary
    buffer rows under a causal bound (``eva_chunk_read``); otherwise
    ``_attend`` reads the three sources through XLA. A row with ``valid ==
    0`` changes nothing: its writes are dropped."""
    N, T = tokens.shape
    W, C, H, Dh = cfg.window_size, cfg.chunk_size, cfg.num_heads, cfg.head_dim
    _check_pages(cfg, page_size)
    if W % T:
        raise ValueError(f"an extend chunk of {T} tokens can straddle a window of {W}")
    if T % C:
        raise ValueError(f"an extend chunk is whole chunks of {C} tokens, got {T}")
    rpp = page_size // C  # summary rows a page
    idx = jnp.arange(T, dtype=jnp.int32)
    positions = offsets[:, None] + idx[None, :]  # [N, T]
    tok_valid = idx[None, :] < valid[:, None]
    last = jnp.clip(valid, 1, T) - 1
    base = offsets % W  # the buffer's rows this window already holds
    closed = (offsets // W) * cfg.chunks_a_window  # summaries a query of this chunk sees
    row_tables = tables[slots]  # [N, Pmax]
    n_sum = row_tables.shape[1] * rpp
    in_kernel = chunk_read_in_kernel(cfg, eva_read, T, page_size)
    if in_kernel:
        work = eva_ops.chunk_work_list(tables, slots, offsets, valid, T, W, page_size,
                                       caches["win"][0]["k"].shape[0], caches["sum"][0]["k"].shape[0])
    else:
        seen = jnp.concatenate([
            jnp.broadcast_to((jnp.arange(W, dtype=jnp.int32)[None, :] < base[:, None])[:, None, :], (N, T, W)),
            jnp.broadcast_to((idx[:, None] >= idx[None, :])[None], (N, T, T)),
            jnp.broadcast_to((jnp.arange(n_sum, dtype=jnp.int32)[None, :] < closed[:, None])[:, None, :],
                             (N, T, n_sum)),
        ], axis=2)  # [N, T, W + T + n_sum]
    # the buffer takes the chunk's valid tokens; a padding token is dropped
    to_buffer = functools.partial(_write_rows, slots=slots, base=base, keep=tok_valid)
    # chunk j of the extend chunk is complete where its last token is valid
    cj = jnp.arange(T // C, dtype=jnp.int32)
    chunk_pos = offsets[:, None] + cj[None, :] * C  # [N, T / C] first position of each chunk
    chunk_done = (cj[None, :] + 1) * C <= valid[:, None]
    sum_page = jnp.take_along_axis(row_tables, jnp.minimum(chunk_pos // page_size, row_tables.shape[1] - 1), axis=1)
    sum_row = (chunk_pos % page_size) // C
    n_tok = jnp.sum(tok_valid.astype(jnp.int32), axis=1)
    stats = _stats(
        cfg,
        jnp.sum(jnp.where(tok_valid, base[:, None] + idx[None, :] + 1, 0)),
        jnp.sum(n_tok * closed),
        jnp.sum(chunk_done),
        jnp.sum((valid > 0) & (base + valid == W)),
        chunk_kernel=in_kernel,
    )

    x = params["embed"][tokens].astype(jnp.float32)  # [N, T, D]
    new = {"win": list(caches["win"]), "sum": list(caches["sum"])}
    dtype = caches["win"][0]["k"].dtype
    for l, lp in enumerate(params["layers"]):
        with jax.named_scope("eva_chunk_attn"):
            q, k, v = _project(norm(x, lp["n1"], cfg), positions, lp, cfg, dtype)
            win, pool = caches["win"][l], caches["sum"][l]
            wk, wv = to_buffer(win["k"], k.reshape(N, T, H * Dh)), to_buffer(win["v"], v.reshape(N, T, H * Dh))
            new["win"][l] = {"k": wk, "v": wv}
            if in_kernel:  # over the buffer as WRITTEN: the chunk's own keys are rows under the read's causal bound
                o = eva_ops.eva_chunk_read(q.reshape(N, T, H * Dh), wk, wv, pool["k"], pool["v"], work, num_heads=H,
                                           interpret=(eva_read == "interpret"))
            else:  # over the buffer as it WAS, the chunk's keys beside it
                sk, sv = (eva_ops.unpack_rows(pool[n][row_tables], H).reshape(N, n_sum, H, Dh) for n in ("k", "v"))
                keys = jnp.concatenate([win["k"][slots].reshape(N, W, H, Dh), k, sk.astype(dtype)], axis=1)
                vals = jnp.concatenate([win["v"][slots].reshape(N, W, H, Dh), v, sv.astype(dtype)], axis=1)
                o = _attend(q, keys, vals, seen)
            x = x + _mm(o, lp["wo"])
            new["sum"][l] = _write_summaries(pool, k.reshape(N, T // C, C, H, Dh), v.reshape(N, T // C, C, H, Dh),
                                             chunk_done, sum_page, sum_row, lp, cfg)
        x = _mlp(x, lp, cfg)
    new["stats"] = stats
    return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0], new


def prefill_paged(params: Params, cfg: EvaByteConfig, caches: Caches, tokens, lengths, slots, tables,
                  page_size: int, eva_read: Optional[str] = None, **_paths):
    """A REFERENCE walk, a whole prompt in one program: (last-position
    logits [N, V], caches). The prompt is walked a window at a time (a
    chunk walk lies inside one window); the logits are those of each
    row's last valid position."""
    N, T = tokens.shape
    W = cfg.window_size
    hidden = jnp.zeros((N, cfg.hidden_size), jnp.float32)
    for start in range(0, T, W):
        piece = tokens[:, start:start + W]
        width = piece.shape[1]
        pad = -width % cfg.chunk_size
        if width < W and W % (width + pad):
            pad = W - width
        piece = jnp.pad(piece, ((0, 0), (0, pad)))
        n = jnp.clip(lengths - start, 0, width)
        h, caches = _chunk_walk(params, cfg, caches, piece, jnp.full_like(lengths, start), n, slots, tables,
                                page_size, eva_read)
        hidden = jnp.where((n > 0)[:, None], h, hidden)
    return head(params, cfg, hidden), caches


def extend_paged(params: Params, cfg: EvaByteConfig, caches: Caches, tokens, offsets, valid, slots, tables,
                 window: int, page_size: int, eva_read: Optional[str] = None, **_paths):
    """One chunk of a chunked prefill: (the residual row [N, D] of each row's last valid position, caches)."""
    del window  # the read follows each row's own window and pages
    return _chunk_walk(params, cfg, caches, tokens, offsets, valid, slots, tables, page_size, eva_read)


# --------------------------------------------------------------------- //
# One decode step


def decode_paged(params: Params, cfg: EvaByteConfig, caches: Caches, tokens, positions, live, tables,
                 window: Optional[int], page_size: int, eva_read: Optional[str] = None,
                 all_heads: bool = False, **_paths):
    """One token per slot: (logits [B, V], caches). Row ``b`` is slot
    ``b``. The token's K and V go to buffer row ``t % W``; the read takes
    ONE softmax over the buffer's first ``t % W + 1`` rows and the
    summaries of the row's closed windows (``ops/eva_read.py``: one
    Pallas kernel where ``eva_read`` resolved, the XLA read of the
    same keys otherwise); a token that completes a chunk (``t % C == C -
    1``) summarises the buffer's last ``C`` rows into the window's page.
    A dead row (``live`` False; the engine has zeroed its position)
    writes nothing to the buffer or the pages."""
    del window
    B = tokens.shape[0]
    W, C, H, Dh = cfg.window_size, cfg.chunk_size, cfg.num_heads, cfg.head_dim
    _check_pages(cfg, page_size)
    pos2 = positions[:, None]
    rows = jnp.arange(B, dtype=jnp.int32)
    at = positions % W
    win_at = jnp.where(live, at, W)  # dead rows: dropped
    closes_chunk = live & (positions % C == C - 1)
    chunk_rows = (at // C * C)[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # [B, C] the chunk's buffer rows
    chunk_first = positions // C * C
    sum_page = jnp.take_along_axis(tables, (chunk_first // page_size)[:, None], axis=1)[:, 0]
    sum_row = (chunk_first % page_size) // C
    n_closed = positions // W  # closed windows behind the row
    work = eva_ops.work_list(tables, positions, W, page_size) if eva_read else None
    stats = _stats(
        cfg,
        jnp.sum(jnp.where(live, at + 1, 0)),
        jnp.sum(jnp.where(live, n_closed * cfg.chunks_a_window, 0)),
        jnp.sum(closes_chunk),
        jnp.sum(live & (at == W - 1)),
    )

    x = params["embed"][tokens[:, None]].astype(jnp.float32)  # [B, 1, D]
    new = {"win": list(caches["win"]), "sum": list(caches["sum"])}
    dtype = caches["win"][0]["k"].dtype
    for l, lp in enumerate(params["layers"]):
        with jax.named_scope("eva_decode_attn"):
            q, k, v = _project(norm(x, lp["n1"], cfg), pos2, lp, cfg, dtype)
            win, pool = caches["win"][l], caches["sum"][l]
            wk = win["k"].at[rows, win_at].set(k.reshape(B, H * Dh), mode="drop")
            wv = win["v"].at[rows, win_at].set(v.reshape(B, H * Dh), mode="drop")
            new["win"][l] = {"k": wk, "v": wv}
            if eva_read:
                o = eva_ops.eva_decode_read(q[:, 0], wk, wv, pool["k"], pool["v"], work,
                                             window=W, interpret=(eva_read == "interpret"))
            else:
                o = eva_ops.eva_decode_read_xla(q[:, 0], wk, wv, pool["k"], pool["v"], tables, positions,
                                                 window=W, chunks_a_window=cfg.chunks_a_window)
            x = x + _mm(o.reshape(B, 1, H * Dh), lp["wo"])
            new["sum"][l] = _write_summaries(pool, wk[rows[:, None], chunk_rows].reshape(B, C, H, Dh),
                                             wv[rows[:, None], chunk_rows].reshape(B, C, H, Dh),
                                             closes_chunk, sum_page, sum_row, lp, cfg)
        x = _mlp(x, lp, cfg)
    new["stats"] = stats
    return head(params, cfg, x[:, 0], all_heads), new
