"""Trinity-Mini (``afmoe``, 26B-A3B) for the serving engine.

Every layer is plain GQA (32 query / 4 KV heads of 128) in one of two
kinds, then a dense or an expert MLP, each sublayer normed before AND
after (``N(u) = u / sqrt(mean(u^2) + eps) * w``, float32):

- ``h = x + N2(Attn(N1(x)))``, ``x' = h + N4(MLP(N3(h)))``; the first
  residual row is ``E[token] * sqrt(hidden)`` (``mup_enabled``); a final
  ``N`` before the untied head.
- **Attention**: ``q = N_q(W_q u)`` and ``k = N_k(W_k u)`` per head (a
  learned weight of 128 each), ``v = W_v u``, ``g = W_g u``; the four
  projections are ONE matrix ``wqkvg`` (every split on a lane tile at
  the published widths). A ``sliding`` layer rotates q and k (RoPE,
  rotate-half over the whole head, theta 10000) and a query at position
  t sees keys t-W+1..t (its own included); a ``full`` layer rotates
  NOTHING and sees every key 0..t. ``Attn = W_o [softmax(q k^T /
  sqrt(128)) v * sigmoid(g)]``, softmax in float32.
- **MLP**: the leading ``num_dense_layers`` layers a SwiGLU; every other
  layer ``models/glm5next.py``'s router and expert layer (sigmoid scores
  in float32, top k of score + bias, the chosen scores normalised and
  scaled by ``route_scale``; one shared expert; ``ops/grouped_matmul.py``
  over the experts this chip HOLDS, all of them in the served preset),
  shared code on purpose: a change there shows in three cells. No clamp
  (``swiglu_limit`` = inf).

**Two kinds of cache** (docs/model_registry.md). Paged: K and V of each
``full`` layer, head-major pages ``[P, Hkv, page, Dh]`` (four KV heads
are no multiple of the bfloat16 sublane tile: a token-major pool would
be copied around every read, ``ops/page_attention.py`` ``head_major``),
read at decode by the page kernel. Fixed per slot: a RING of
``sliding_window`` K/V rows a ``sliding`` layer ``[slots, Hkv, W, Dh]``:
position ``p`` lives at index ``p % W`` with its key stored ROTATED, so
the order of the ring does not matter to softmax, a wrapped row replaces
the one that left the window, a row below the window masks what it has
not written, and nothing is reset at admission. ``stats`` is a handful
of int32 counts of the last walk.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from generativeaiexamples_tpu.models.glm5next import MOE_STAT_NAMES, _mm, moe, rms_norm, swiglu_mlp
from generativeaiexamples_tpu.models.phi4flash import _write_rows
from generativeaiexamples_tpu.ops import page_attention

Params = Dict[str, Any]
Caches = Dict[str, Any]
_NEG = -1e30

STAT_NAMES = MOE_STAT_NAMES + ("window_tokens_read", "full_tokens_read")

_PUBLISHED_TYPES = ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention") * 8


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """Published sizes (config.json); ``layers_served`` lists the
    published layers this chip serves (None: all); ``experts_first`` and
    ``experts_held`` are the chip's share of each expert layer."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    layer_types: Tuple[str, ...] = _PUBLISHED_TYPES
    num_dense_layers: int = 2
    layers_served: Optional[Tuple[int, ...]] = None
    n_routed_experts: int = 128
    num_experts_per_tok: int = 8
    experts_first: int = 0
    experts_held: int = 128
    routed_scaling_factor: float = 2.826  # ``route_scale``, under the name models/glm5next.py ``route`` reads
    swiglu_limit: float = math.inf  # no clamp
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    mup_enabled: bool = True
    max_seq_len: int = 131072

    @property
    def layers(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, mlp) of each layer SERVED: mixer 'window' | 'full', mlp 'dense' | 'sparse'."""
        served = range(len(self.layer_types)) if self.layers_served is None else self.layers_served
        return tuple(("full" if self.layer_types[l] == "full_attention" else "window",
                      "dense" if l < self.num_dense_layers else "sparse") for l in served)

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def layers_of(self, mixer: str) -> List[int]:
        return [l for l, (m, _) in enumerate(self.layers) if m == mixer]


PRESETS: Dict[str, AfmoeConfig] = {
    # one pipeline stage's chip: published layer 0 (sliding + dense MLP) and the
    # whole period 4-7 (sliding x 3, full; all with experts), every expert held
    "trinity-mini": AfmoeConfig(layers_served=(0, 4, 5, 6, 7), max_seq_len=8192),
    # CPU tests: one sliding dense, one sliding expert and one full expert layer at
    # a size a test checks by hand; window 8, 8 experts top 2
    "afmoe-debug": AfmoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        layer_types=("sliding_attention", "sliding_attention", "full_attention"), num_dense_layers=1,
        n_routed_experts=8, num_experts_per_tok=2, experts_held=8, num_heads=8, num_kv_heads=2,
        head_dim=16, sliding_window=8, max_seq_len=1024,
    ),
}


def validate(cfg: AfmoeConfig) -> None:
    for t in cfg.layer_types:
        if t not in ("sliding_attention", "full_attention"):
            raise ValueError(f"unknown layer type {t!r}")
    for l in cfg.layers_served or ():
        if not 0 <= l < len(cfg.layer_types):
            raise ValueError(f"layers_served names layer {l} of {len(cfg.layer_types)}")
    if cfg.experts_first < 0 or cfg.experts_first + cfg.experts_held > cfg.n_routed_experts:
        raise ValueError("the experts held must lie inside the routed experts")
    if cfg.num_heads % cfg.num_kv_heads:
        raise ValueError("every KV head must serve the same number of query heads")
    if cfg.head_dim % 2:
        raise ValueError("RoPE rotates halves")


# --------------------------------------------------------------------- //
# Parameters

_NORMS = ("n_attn_in", "n_attn_out", "n_mlp_in", "n_mlp_out")


def _shapes(cfg: AfmoeConfig, mlp: str) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of one layer's leaves. kind: 'w' a bfloat16
    matrix (std 1/sqrt(fan_in)), or the name of a float32 leaf whose
    range ``init_params_fast`` gives. Both mixers have the same leaves."""
    D = cfg.hidden_size
    s: Dict[str, Tuple[Tuple[int, ...], str]] = {n: ((D,), "near_one") for n in _NORMS}
    s.update({
        # [q | k | v | output gate]
        "wqkvg": ((D, 2 * cfg.q_dim + 2 * cfg.kv_dim), "w"),
        "q_norm": ((cfg.head_dim,), "near_one"), "k_norm": ((cfg.head_dim,), "near_one"),
        "wo": ((cfg.q_dim, D), "w"),
    })
    if mlp == "dense":
        F = cfg.intermediate_size
        s.update({"w_gate_up": ((D, 2 * F), "w"), "w_down": ((F, D), "w")})
    else:
        F, E = cfg.moe_intermediate_size, cfg.experts_held
        s.update({
            "router": ((D, cfg.n_routed_experts), "router"), "e_bias": ((cfg.n_routed_experts,), "e_bias"),
            "ws_gate_up": ((D, 2 * F), "w"), "ws_down": ((F, D), "w"),
            "we_gate_up": ((E, D, 2 * F), "w"), "we_down": ((E, F, D), "w"),
        })
    return s


def count_logical_params(cfg: AfmoeConfig) -> int:
    """Parameters this chip HOLDS (its layers, its experts)."""
    n = sum(math.prod(shape) for _, mlp in cfg.layers for shape, _ in _shapes(cfg, mlp).values())
    return n + 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def init_params_fast(cfg: AfmoeConfig, seed: int = 0, dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Seeded random weights, drawn leaf by leaf ON the accelerator where
    there is one. Every term of the equations is drawn away from the
    value that would hide it: norm weights 1 + N(0, 0.1), the selection
    bias ``e_bias`` N(0, 0.01) (so the tie-break is exercised), the
    embedding N(0, 1/hidden) so that the muP multiplier brings the first
    residual row to unit size, the size every normed sublayer adds."""
    validate(cfg)
    root = jax.random.key(seed, impl="rbg")  # the generator the chip has in hardware
    counter = [0]

    def normal(shape, std, dt=dtype, mean=0.0):
        counter[0] += 1
        return _draw(jax.random.fold_in(root, counter[0]), tuple(shape), float(std), float(mean),
                     jnp.dtype(dt).name)

    def leaf(shape, kind):
        if kind == "w":
            return normal(shape, 1 / math.sqrt(shape[-2]))
        if kind == "router":
            return normal(shape, 1 / math.sqrt(shape[0]), jnp.float32)
        if kind == "near_one":
            return normal(shape, 0.1, jnp.float32, mean=1.0)
        if kind == "e_bias":
            return normal(shape, 0.01, jnp.float32)
        raise ValueError(kind)

    with jax.default_device(jax.devices()[0]):  # the accelerator where there is one
        layers = [{name: leaf(shape, kind) for name, (shape, kind) in _shapes(cfg, mlp).items()}
                  for _, mlp in cfg.layers]
        D = cfg.hidden_size
        return {
            "embed": normal((cfg.vocab_size, D), 1 / math.sqrt(D)),
            "head": normal((D, cfg.vocab_size), 1 / math.sqrt(D)),
            "final_norm": leaf((D,), "near_one"),
            "layers": layers,
        }


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, shape, std, mean, dtype_name):
    return (jax.random.normal(key, shape, jnp.float32) * std + mean).astype(jnp.dtype(dtype_name))


# --------------------------------------------------------------------- //
# Caches and the memory plan


def init_paged_cache(cfg: AfmoeConfig, pool_pages: int, page_size: int, num_slots: int,
                     dtype: jnp.dtype = jnp.bfloat16) -> Caches:
    Hk, Dh = cfg.num_kv_heads, cfg.head_dim
    page = (pool_pages, Hk, page_size, Dh)  # head-major pages
    ring = (num_slots, Hk, cfg.sliding_window, Dh)  # heads ahead of the tokens, as the pool
    return {
        "full": [{"k": jnp.zeros(page, dtype), "v": jnp.zeros(page, dtype)} for _ in cfg.layers_of("full")],
        "win": [{"k": jnp.zeros(ring, dtype), "v": jnp.zeros(ring, dtype)} for _ in cfg.layers_of("window")],
        "stats": jnp.zeros((len(STAT_NAMES),), jnp.int32),
    }


def kv_bytes_per_token(cfg: AfmoeConfig, kv_bytes: float = 2) -> int:
    """Paged bytes a cached token costs: K and V of each full layer."""
    return int(len(cfg.layers_of("full")) * 2 * cfg.kv_dim * kv_bytes)


def fixed_state_bytes_per_slot(cfg: AfmoeConfig, kv_bytes: float = 2) -> int:
    """Bytes a slot holds whatever its sequence length: the rings."""
    return int(len(cfg.layers_of("window")) * cfg.sliding_window * 2 * cfg.kv_dim * kv_bytes)


def serving_memory_bytes(cfg: AfmoeConfig, batch: int, max_seq_len: int,
                         weight_bytes: int = 2, kv_bytes: float = 2) -> Dict[str, int]:
    weights = count_logical_params(cfg) * weight_bytes
    paged = batch * max_seq_len * kv_bytes_per_token(cfg, kv_bytes)
    fixed = batch * fixed_state_bytes_per_slot(cfg, kv_bytes)
    return {"weights": weights, "kv_cache": paged + fixed, "fixed_state": fixed,
            "total": weights + paged + fixed}


def read_stats(caches: Caches):
    return caches["stats"]


# --------------------------------------------------------------------- //
# Layer mathematics


def embed(params: Params, cfg: AfmoeConfig, tokens):
    x = params["embed"][tokens].astype(jnp.float32)
    return x * math.sqrt(cfg.hidden_size) if cfg.mup_enabled else x


def rope(x, positions, theta: float):
    """Rotate-half RoPE over the whole last axis: x [.., T, H, Dh]
    float32, positions [.., T]."""
    half = x.shape[-1] // 2
    inv_freq = jnp.asarray([theta ** (-i / half) for i in range(half)], jnp.float32)
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _project(u, positions, lp: Params, cfg: AfmoeConfig, mixer: str, dtype):
    """The normed input u [N, T, D] -> q [N, T, Hq, Dh], k, v
    [N, T, Hkv, Dh] in ``dtype`` (what the caches hold and the score
    product multiplies) and the output gate [N, T, Hq * Dh] float32.
    q and k are normed per head, and rotated on a window layer."""
    Hq, Hk, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qkvg = _mm(u, lp["wqkvg"])
    q, k, v, g = jnp.split(qkvg, [cfg.q_dim, cfg.q_dim + cfg.kv_dim, cfg.q_dim + 2 * cfg.kv_dim], axis=-1)
    q = rms_norm(q.reshape(q.shape[:-1] + (Hq, Dh)), lp["q_norm"], cfg.norm_eps, jnp.float32)
    k = rms_norm(k.reshape(k.shape[:-1] + (Hk, Dh)), lp["k_norm"], cfg.norm_eps, jnp.float32)
    if mixer == "window":
        q, k = rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta)
    v = v.reshape(v.shape[:-1] + (Hk, Dh))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), jax.nn.sigmoid(g)


def _heads_first(x):
    """[N, T, Hk, Dh] (as projected) -> [N, Hk, T, Dh] (as cached and attended)."""
    return jnp.swapaxes(x, 1, 2)


def _gqa(q, k, v, mask):
    """q [N, T, Hq, Dh], k / v [N, Hk, S, Dh] (heads first, as the
    caches hold them), mask [N, T, S] bool -> [N, T, Hq, Dh] float32.
    KV head j serves query heads j * G .. j * G + G - 1."""
    N, T, Hq, Dh = q.shape
    Hk, S = k.shape[1], k.shape[2]
    G = Hq // Hk
    q5 = q.reshape(N, T, Hk, G, Dh)
    sc = jnp.einsum("ntkgd,nksd->nkgts", q5, k, preferred_element_type=jnp.float32) * (Dh ** -0.5)
    p = jax.nn.softmax(jnp.where(mask[:, None, None], sc, _NEG), axis=-1)
    # a batched matmul as written (an einsum here the CPU backend lowers, at one query, to a
    # transposed bfloat16 dot with a float32 result, which it cannot run)
    o = jnp.matmul(p.astype(v.dtype).reshape(N, Hk, G * T, S), v, preferred_element_type=jnp.float32)
    return jnp.transpose(o.reshape(N, Hk, G, T, Dh), (0, 3, 1, 2, 4)).reshape(N, T, Hq, Dh)


def _attend_pages(q, pool, pages, positions, n_tokens, block_pages: int = 4):
    """Chunk attention over a row's pages with a running softmax, in
    blocks of ``block_pages`` pages, as far as ``n_tokens`` [N] reach:
    ONE program whatever the context. q [N, T, Hq, Dh]; pool k / v
    [P, Hk, page, Dh]; pages [N, Pmax]; positions [N, T]. Returns
    [N, T, Hq, Dh] float32."""
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

    def rows(buf, pg):  # [P, Hk, page, Dh] x [N, bp] -> [N, Hk, bp * page, Dh]
        return jnp.moveaxis(buf[pg], 1, 2).reshape(N, Hk, W, Dh)

    def body(i, carry):
        m, l, acc = carry
        pg = lax.dynamic_slice_in_dim(pages, i * bp, bp, axis=1)
        kb, vb = rows(pool["k"], pg), rows(pool["v"], pg)
        sc = jnp.einsum("ntkgd,nksd->nkgts", q5, kb, preferred_element_type=jnp.float32) * (Dh ** -0.5)
        ok = (i * W + jnp.arange(W, dtype=jnp.int32))[None, None, :] <= positions[:, :, None]  # [N, T, W]
        ok = ok[:, None, None]
        sc = jnp.where(ok, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum("nkgts,nksd->nkgtd", p.astype(vb.dtype), vb,
                                       preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((N, Hk, G, T, 1), _NEG, jnp.float32), jnp.zeros((N, Hk, G, T, 1), jnp.float32),
            jnp.zeros((N, Hk, G, T, Dh), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_blocks, body, init)
    o = acc / jnp.where(l == 0.0, 1.0, l)
    return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(N, T, Hq, Dh)


def _attn_output(o, gate, lp: Params):
    """o [.., Hq, Dh] float32 -> the mixer's output [.., D]: gated per channel, then ``W_o``."""
    return _mm(o.reshape(gate.shape) * gate, lp["wo"])


def sublayer(x, lp: Params, sub: str, cfg: AfmoeConfig, fn):
    """``x + N_out(fn(N_in(x)))``; x float32 [.., D]."""
    u = rms_norm(x, lp[f"n_{sub}_in"], cfg.norm_eps, jnp.float32)
    return x + rms_norm(fn(u), lp[f"n_{sub}_out"], cfg.norm_eps, jnp.float32)


def mlp_sublayer(x, lp: Params, mlp: str, cfg: AfmoeConfig, count, kernel: Optional[str]):
    """The MLP sublayer over x [.., D]; returns (x, moe stats or None)."""
    box = []

    def fn(u):
        if mlp == "dense":
            with jax.named_scope("dense_mlp"):
                return swiglu_mlp(u, lp["w_gate_up"], lp["w_down"], cfg.swiglu_limit)
        # the router scores the float32 row; the experts multiply it in the weights' dtype
        y, stats = moe(u.reshape(-1, u.shape[-1]), lp, cfg, count.reshape(-1), kernel,
                       expert_dtype=lp["we_gate_up"].dtype)
        box.append(stats)
        return y.reshape(u.shape[:-1] + (y.shape[-1],))

    x = sublayer(x, lp, "mlp", cfg, fn)
    return x, (box[0] if box else None)


def head(params: Params, cfg: AfmoeConfig, hidden):
    """hidden [N, D] -> float32 logits [N, V]."""
    return _mm(rms_norm(hidden, params["final_norm"], cfg.norm_eps, jnp.float32), params["head"])


def _stats(moe_stats, window_read, full_read):
    return jnp.concatenate([moe_stats, jnp.stack([window_read, full_read])]).astype(jnp.int32)


# --------------------------------------------------------------------- //
# The chunk walk: prefill and chunked extend


def _chunk_walk(params: Params, cfg: AfmoeConfig, caches: Caches, tokens, offsets, valid, slots,
                tables, page_size: int, fresh: bool, grouped_matmul: Optional[str] = None):
    """All layers over a chunk [N, C] per row; returns (the residual row
    of each row's last valid position [N, D], caches).

    ``fresh`` (the prefill program): every row starts at position 0, so
    no ring is read. Otherwise a window layer reads its slot's ring AS IT
    STOOD (the window's positions before the chunk; a position this
    request never wrote is masked, so a row at ``offsets == 0`` needs no
    reset) beside the chunk's own keys, then writes the chunk's last
    ``window`` valid tokens. A row with ``valid == 0`` changes nothing:
    its pool and ring writes are dropped. The full layer writes its pages
    and walks each row's own pages as far as its context reaches
    whatever window the engine names: one program a chunk width."""
    N, C = tokens.shape
    Wn = cfg.sliding_window
    S = tables.shape[1] * page_size
    idx = jnp.arange(C, dtype=jnp.int32)
    positions = jnp.minimum(offsets[:, None] + idx[None, :], S - 1)  # [N, C]
    tok_valid = idx[None, :] < valid[:, None]
    row_live = valid > 0
    last = jnp.clip(valid, 1, C) - 1
    row_tables = tables[slots]
    causal = positions[:, :, None] >= positions[:, None, :]  # [N, C, C] chunk keys
    in_window = causal & (positions[:, None, :] > positions[:, :, None] - Wn)
    keys_seen = jnp.where(tok_valid, positions + 1, 0)
    if not fresh:
        # ring index r holds the newest position below the chunk that is
        # congruent to r (none: masked)
        r = jnp.arange(Wn, dtype=jnp.int32)[None, :]
        ring_pos = offsets[:, None] - 1 - jnp.mod(offsets[:, None] - 1 - r, Wn)  # [N, Wn]
        ring_ok = (ring_pos >= 0)[:, None, :] & (ring_pos[:, None, :] > positions[:, :, None] - Wn)
        seen = jnp.concatenate([ring_ok, in_window], axis=2)  # [N, C, Wn + C]
    # the ring keeps the chunk's last `window` valid tokens; the rest is dropped
    ring_at = jnp.where(tok_valid & (idx[None, :] >= valid[:, None] - Wn), positions % Wn, Wn)
    ring_lead = jnp.broadcast_to(slots[:, None], ring_at.shape)
    window_read = jnp.zeros((), jnp.int32)
    full_read = jnp.zeros((), jnp.int32)
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)

    x = embed(params, cfg, tokens)  # [N, C, D]
    new = {"full": list(caches["full"]), "win": list(caches["win"])}
    dtype = params["layers"][0]["wqkvg"].dtype
    i_win = i_full = 0
    for l, (mixer, mlp) in enumerate(cfg.layers):
        lp = params["layers"][l]
        if mixer == "window":
            i = i_win
            i_win += 1

            def mix(u, lp=lp, i=i):
                nonlocal window_read
                with jax.named_scope("window_attn"):
                    q, k, v, gate = _project(u, positions, lp, cfg, "window", dtype)
                    ring = caches["win"][i]
                    if fresh:
                        o = _gqa(q, _heads_first(k), _heads_first(v), in_window)
                    else:
                        o = _gqa(q,
                                 jnp.concatenate([ring["k"][slots], _heads_first(k)], axis=2),
                                 jnp.concatenate([ring["v"][slots], _heads_first(v)], axis=2), seen)
                    new["win"][i] = {"k": _write_rows(ring["k"], ring_lead, ring_at, k),
                                     "v": _write_rows(ring["v"], ring_lead, ring_at, v)}
                    window_read = window_read + jnp.sum(jnp.minimum(keys_seen, Wn))
                    return _attn_output(o, gate, lp)
        else:
            i = i_full
            i_full += 1

            def mix(u, lp=lp, i=i):
                nonlocal full_read
                with jax.named_scope("full_attn"):
                    q, k, v, gate = _project(u, positions, lp, cfg, "full", dtype)
                    old = caches["full"][i]
                    phys = jnp.take_along_axis(row_tables, positions // page_size, axis=1)
                    phys = jnp.where(tok_valid, phys, old["k"].shape[0])  # padding: dropped
                    sip = positions % page_size
                    pool = {"k": _write_rows(old["k"], phys, sip, k), "v": _write_rows(old["v"], phys, sip, v)}
                    new["full"][i] = pool
                    full_read = full_read + jnp.sum(keys_seen)
                    if fresh:
                        o = _gqa(q, _heads_first(k), _heads_first(v), causal)
                    else:
                        o = _attend_pages(q, pool, row_tables, positions, jnp.where(row_live, offsets + valid, 0))
                    return _attn_output(o, gate, lp)

        x = sublayer(x, lp, "attn", cfg, mix)
        x, stats = mlp_sublayer(x, lp, mlp, cfg, tok_valid, grouped_matmul)
        if stats is not None:
            moe_stats = moe_stats + stats
    new["stats"] = _stats(moe_stats, window_read, full_read)
    return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0], new


def prefill_paged(params: Params, cfg: AfmoeConfig, caches: Caches, tokens, lengths, slots, tables,
                  page_size: int, grouped_matmul: Optional[str] = None, **_paths):
    """A monolithic admission wave: (last-position logits [N, V], caches)."""
    hidden, caches = _chunk_walk(params, cfg, caches, tokens, jnp.zeros_like(lengths), lengths, slots,
                                 tables, page_size, True, grouped_matmul)
    return head(params, cfg, hidden), caches


def extend_paged(params: Params, cfg: AfmoeConfig, caches: Caches, tokens, offsets, valid, slots,
                 tables, window: int, page_size: int, grouped_matmul: Optional[str] = None, **_paths):
    """One chunk of a chunked prefill: (the residual row [N, D] of each
    row's last valid position, caches)."""
    del window  # the full layer's read follows each row's own context
    return _chunk_walk(params, cfg, caches, tokens, offsets, valid, slots, tables, page_size, False,
                       grouped_matmul)


# --------------------------------------------------------------------- //
# One decode step


def decode_paged(params: Params, cfg: AfmoeConfig, caches: Caches, tokens, positions, live, tables,
                 window: Optional[int], page_size: int, page_kernel: Optional[str] = None,
                 grouped_matmul: Optional[str] = None, **_paths):
    """One token per slot: (logits [B, V], caches). A dead row (``live``
    False; the engine has zeroed its position) writes nothing to the
    pools or the rings: its slot may be between two chunks of a prefill."""
    del window
    B = tokens.shape[0]
    Wn = cfg.sliding_window
    S = tables.shape[1] * page_size
    pos2 = positions[:, None]
    ring_at = jnp.where(live, positions % Wn, Wn)[:, None]  # dead rows: dropped
    ring_mask = ((jnp.arange(Wn, dtype=jnp.int32)[None, :] <= pos2) | (pos2 >= Wn))[:, None, :]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    sip = pos2 % page_size
    work = page_attention.page_work_list(
        tables, positions, 1, page_size, page_attention.pages_per_step(caches["full"][0]["k"])
    ) if page_kernel else None
    keys_seen = jnp.where(live, positions + 1, 0)
    window_read = jnp.zeros((), jnp.int32)
    full_read = jnp.zeros((), jnp.int32)
    moe_stats = jnp.zeros((len(MOE_STAT_NAMES),), jnp.int32)

    x = embed(params, cfg, tokens[:, None])  # [B, 1, D]
    new = {"full": list(caches["full"]), "win": list(caches["win"])}
    dtype = params["layers"][0]["wqkvg"].dtype
    i_win = i_full = 0
    for l, (mixer, mlp) in enumerate(cfg.layers):
        lp = params["layers"][l]
        if mixer == "window":
            i = i_win
            i_win += 1

            def mix(u, lp=lp, i=i):
                nonlocal window_read
                with jax.named_scope("window_attn"):
                    q, k, v, gate = _project(u, pos2, lp, cfg, "window", dtype)
                    ring = caches["win"][i]
                    rk, rv = _write_rows(ring["k"], rows, ring_at, k), _write_rows(ring["v"], rows, ring_at, v)
                    new["win"][i] = {"k": rk, "v": rv}
                    window_read = window_read + jnp.sum(jnp.minimum(keys_seen, Wn))
                    return _attn_output(_gqa(q, rk, rv, ring_mask), gate, lp)
        else:
            i = i_full
            i_full += 1

            def mix(u, lp=lp, i=i):
                nonlocal full_read
                with jax.named_scope("full_attn"):
                    q, k, v, gate = _project(u, pos2, lp, cfg, "full", dtype)
                    old = caches["full"][i]
                    phys = jnp.where(live[:, None], jnp.take_along_axis(tables, pos2 // page_size, axis=1),
                                     old["k"].shape[0])
                    pool = {"k": _write_rows(old["k"], phys, sip, k), "v": _write_rows(old["v"], phys, sip, v)}
                    new["full"][i] = pool
                    full_read = full_read + jnp.sum(keys_seen)
                    if page_kernel:
                        o = page_attention.paged_attention(
                            q, pool["k"], pool["v"], tables, positions,
                            interpret=(page_kernel == "interpret"), work=work, head_major=True)
                    else:
                        gk, gv = (jnp.moveaxis(buf[tables], 1, 2).reshape(B, cfg.num_kv_heads, S, cfg.head_dim)
                                  for buf in (pool["k"], pool["v"]))
                        o = _gqa(q, gk, gv, jnp.arange(S, dtype=jnp.int32)[None, None, :] <= pos2[:, :, None])
                    return _attn_output(o.astype(jnp.float32), gate, lp)

        x = sublayer(x, lp, "attn", cfg, mix)
        x, stats = mlp_sublayer(x, lp, mlp, cfg, live[:, None], grouped_matmul)
        if stats is not None:
            moe_stats = moe_stats + stats
    new["stats"] = _stats(moe_stats, window_read, full_read)
    return head(params, cfg, x[:, 0]), new


# --------------------------------------------------------------------- //
# The whole sequence at once, no cache: what the tests hold the paged walks against


def forward_full(params: Params, cfg: AfmoeConfig, tokens):
    """Logits [N, T, V] of tokens [N, T] with no cache, no kernel: every
    layer at every position, the window and the causal masks whole."""
    N, T = tokens.shape
    idx = jnp.arange(T, dtype=jnp.int32)
    positions = jnp.broadcast_to(idx[None, :], (N, T))
    causal = jnp.broadcast_to((idx[:, None] >= idx[None, :])[None], (N, T, T))
    in_window = causal & (idx[None, :] > idx[:, None] - cfg.sliding_window)[None]
    count = jnp.ones((N, T), bool)
    dtype = params["layers"][0]["wqkvg"].dtype
    x = embed(params, cfg, tokens)
    for l, (mixer, mlp) in enumerate(cfg.layers):
        lp = params["layers"][l]

        def mix(u, lp=lp, mixer=mixer):
            q, k, v, gate = _project(u, positions, lp, cfg, mixer, dtype)
            o = _gqa(q, _heads_first(k), _heads_first(v), in_window if mixer == "window" else causal)
            return _attn_output(o, gate, lp)

        x = sublayer(x, lp, "attn", cfg, mix)
        x, _ = mlp_sublayer(x, lp, mlp, cfg, count, None)
    return head(params, cfg, x.reshape(N * T, -1)).reshape(N, T, -1)
