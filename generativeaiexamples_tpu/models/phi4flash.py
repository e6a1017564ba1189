"""Phi-4-mini-flash-reasoning (SambaY: arXiv:2507.06607) for the serving engine.

A decoder-hybrid-decoder: the lower half of the stack (the "self
decoder") alternates Mamba-1 layers with sliding-window differential
attention and ends in ONE full-attention layer; the upper half (the
"cross decoder") alternates gated memory units, which reuse the last
Mamba layer's scan output, with cross attention onto that one full
layer's K/V. No positional encoding anywhere. Every layer is
``h = h + Mixer_l(LN(h)); h = h + MLP(LN(h))`` with a SwiGLU MLP, and
with ``n`` layers the mixer of layer ``l`` is

- ``l`` even, ``l <= n/2``: Mamba-1 (layer ``n/2`` also publishes its
  pre-gate scan output ``m_t`` as the memory);
- ``l`` odd, ``l < n/2``: differential attention over a window;
- ``l == n/2 + 1``: the same, full causal — the only layer whose K/V
  the upper half ever reads;
- ``l`` odd, ``l >= n/2 + 2``: differential cross attention (a query
  projection only) onto that layer's K/V;
- ``l`` even, ``l >= n/2 + 2``: gated memory unit,
  ``W_2 (m_t * silu(W_1 x_t))``.

**Two kinds of cache, one pytree** (docs/model_registry.md). Only the
full layer's K/V grows with the sequence, so only it is paged: ONE page
pool ``[P, Hkv/2, page, 2*Dh]`` (head-major pages). Everything else is a fixed per-slot
state: a ring of ``sliding_window`` K/V rows per window layer
``[slots, Hkv/2, window, 2*Dh]`` (position ``p`` lives at index
``p % window``; there is no positional encoding, so the order of the
ring does not matter to attention and a wrapped row simply replaces the
one that left the window), the SSM state ``[slots, d_state, d_inner]``
in float32 (``d_inner`` on the lane axis) and the last ``d_conv - 1``
convolution inputs per Mamba layer.

**Pair layout.** Differential attention pairs heads: diff-head ``i``
owns query heads ``(2i, 2i+1)``, diff-KV-head ``j = i // 2`` owns KV
heads ``(2j, 2j+1)``, and both softmaxes of a diff-head multiply the
concatenated value ``[v1|v2]``. Stored as ``Hkv/2`` heads of ``2*Dh``
— ``[k1|k2]`` and ``[v1|v2]``, a free reshape — with query head ``2i``
zero-padded to ``[q1|0]`` and ``2i+1`` to ``[0|q2]``, an ordinary GQA
attention with group 4 returns ``a1`` and ``a2`` as its query heads:
the same bytes, and a head size of 128 that ``ops/page_attention.py``
serves compiled. ``_pair_queries`` scales by ``sqrt(2)`` so that the
kernel's ``1/sqrt(2*Dh)`` is the model's ``1/sqrt(Dh)``.

**Prefill shortcut.** The upper half is computed for a chunk's last
valid position only (the design's linear prefill): the walk returns
that position's hidden state and counts the rest as skipped.

The three walks (``prefill_paged``, ``extend_paged``, ``decode_paged``)
share the engine's paged contracts (models/registry.py). Matrices are
bfloat16; ``A_log``, ``D``, the ``dt`` bias, the lambda vectors and the
SSM state are float32. The selective scan is a plain ``lax.scan`` over
time (no Pallas kernel in this PR; PERF.md has the extend program's
device time for the ``perf_opt`` PR that writes one).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from generativeaiexamples_tpu.ops import page_attention

Params = Dict[str, Any]
Caches = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """Published sizes (config.json) plus the HF class defaults the
    published file omits (the ``assumed`` list of the benchmark's
    configuration file names each)."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    sliding_window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    tie_embeddings: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden_size // 16)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    # the pair layout: what the pool, the rings and the page kernel see
    @property
    def pair_dim(self) -> int:
        return 2 * self.head_dim

    @property
    def pair_kv_heads(self) -> int:
        return self.num_kv_heads // 2

    @property
    def memory_layer(self) -> int:
        return self.num_layers // 2

    @property
    def full_layer(self) -> int:
        return self.num_layers // 2 + 1

    def layer_kind(self, l: int) -> str:
        half = self.num_layers // 2
        if l % 2 == 0:
            return "mamba" if l <= half else "gmu"
        if l < half:
            return "window"
        return "full" if l == half + 1 else "cross"

    def layers_of(self, kind: str) -> List[int]:
        return [l for l in range(self.num_layers) if self.layer_kind(l) == kind]

    def lambda_init(self, l: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * l)


PRESETS: Dict[str, Phi4FlashConfig] = {
    "phi-4-mini-flash-reasoning": Phi4FlashConfig(),
    # CPU tests: keeps the layer rule (Mamba 0,2,4; window 1,3; full 5;
    # GMU 6; cross 7) at a size a test can check by hand.
    "phi4flash-debug": Phi4FlashConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=8,
        num_heads=8, num_kv_heads=4, sliding_window=8, max_seq_len=1024,
    ),
}


def validate(cfg: Phi4FlashConfig) -> None:
    if cfg.num_layers % 4 or cfg.num_layers < 8:
        raise ValueError(f"num_layers must be a multiple of 4 and >= 8, got {cfg.num_layers}")
    if cfg.num_heads % 2 or cfg.num_kv_heads % 2 or (cfg.num_heads // 2) % (cfg.num_kv_heads // 2):
        raise ValueError(
            f"differential attention pairs heads: num_heads ({cfg.num_heads}) and "
            f"num_kv_heads ({cfg.num_kv_heads}) must be even and divide"
        )


# --------------------------------------------------------------------- //
# Parameters


def count_logical_params(cfg: Phi4FlashConfig) -> int:
    h, m, di, ds, r = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner, cfg.d_state, cfg.dt_rank
    q, kv = cfg.q_dim, cfg.kv_dim
    lam = 4 * cfg.head_dim + cfg.pair_dim
    per_kind = {
        "mamba": h * 2 * di + cfg.d_conv * di + di + di * (r + 2 * ds) + r * di + di + di * ds + di + di * h,
        "window": h * (q + 2 * kv) + (q + 2 * kv) + q * h + h + lam,
        "cross": h * q + q + q * h + h + lam,
        "gmu": h * di + di * h,
    }
    per_kind["full"] = per_kind["window"]
    shared = 4 * h + h * 2 * m + m * h  # two LayerNorms, the SwiGLU MLP
    n = sum(per_kind[cfg.layer_kind(l)] + shared for l in range(cfg.num_layers))
    return n + cfg.vocab_size * h + 2 * h


def init_params_fast(cfg: Phi4FlashConfig, seed: int = 0, dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Seeded random weights, drawn layer by layer on the host (numpy
    PCG64: jax's threefry on one CPU core needs minutes for 3.8 B).
    Returns the per-layer-list layout the walks read."""
    import numpy as np

    validate(cfg)
    rng = np.random.default_rng(seed)
    h, m, di, ds, r = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner, cfg.d_state, cfg.dt_rank
    q, kv, L = cfg.q_dim, cfg.kv_dim, cfg.num_layers
    out_scale = 1.0 / math.sqrt(2 * L)

    def normal(shape, std, dt=dtype):
        w = rng.standard_normal(size=shape, dtype=np.float32) * np.float32(std)
        return jnp.asarray(w.astype(jnp.dtype(dt)))

    def attention(with_kv: bool) -> Params:
        width = q + 2 * kv if with_kv else q
        lp = {
            ("wqkv" if with_kv else "wq"): normal((h, width), 1 / math.sqrt(h)),
            ("bqkv" if with_kv else "bq"): normal((width,), 0.02),
            "wo": normal((q, h), out_scale / math.sqrt(q)),
            "bo": normal((h,), 0.02),
            "subln": jnp.ones((cfg.pair_dim,), dtype),
        }
        for name in ("lq1", "lk1", "lq2", "lk2"):
            lp[name] = normal((cfg.head_dim,), 0.1, jnp.float32)
        return lp

    layers = []
    for l in range(L):
        kind = cfg.layer_kind(l)
        if kind == "mamba":
            dt0 = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), size=(di,))).astype(np.float32)
            lp = {
                "in_proj": normal((h, 2 * di), 1 / math.sqrt(h)),
                "conv_w": normal((cfg.d_conv, di), 1 / math.sqrt(cfg.d_conv)),
                "conv_b": normal((di,), 0.02),
                "x_proj": normal((di, r + 2 * ds), 1 / math.sqrt(di)),
                "dt_proj": normal((r, di), 1 / math.sqrt(r)),
                # softplus^-1(dt0): the step sizes start log-uniform in [1e-3, 1e-1]
                "dt_bias": jnp.asarray(dt0 + np.log(-np.expm1(-dt0))),
                "A_log": jnp.asarray(np.log(np.tile(np.arange(1, ds + 1, dtype=np.float32)[:, None], (1, di)))),
                "D": jnp.ones((di,), jnp.float32),
                "out_proj": normal((di, h), out_scale / math.sqrt(di)),
            }
        elif kind in ("window", "full"):
            lp = attention(with_kv=True)
        elif kind == "cross":
            lp = attention(with_kv=False)
        else:
            lp = {
                "w1": normal((h, di), 1 / math.sqrt(h)),
                "w2": normal((di, h), out_scale / math.sqrt(di)),
            }
        lp.update({
            "ln1_w": jnp.ones((h,), dtype), "ln1_b": normal((h,), 0.02),
            "ln2_w": jnp.ones((h,), dtype), "ln2_b": normal((h,), 0.02),
            "w_gate_up": normal((h, 2 * m), 1 / math.sqrt(h)),
            "w_down": normal((m, h), out_scale / math.sqrt(m)),
        })
        layers.append(lp)
    return {
        "embed": normal((cfg.vocab_size, h), 1 / math.sqrt(h)),
        "layers": layers,
        "final_norm_w": jnp.ones((h,), dtype),
        "final_norm_b": normal((h,), 0.02),
    }


# --------------------------------------------------------------------- //
# Caches and the memory plan


def init_paged_cache(cfg: Phi4FlashConfig, pool_pages: int, page_size: int, num_slots: int,
                     dtype: jnp.dtype = jnp.bfloat16) -> Caches:
    """The one cache pytree: the page pool of the full layer, and the
    per-slot fixed state of the window and Mamba layers."""
    kvh, pd, di = cfg.pair_kv_heads, cfg.pair_dim, cfg.d_inner
    ring = (num_slots, kvh, cfg.sliding_window, pd)  # heads ahead of the tokens, as the pool
    return {
        # head-major pages [Hkv/2, page, 2*Dh]: ten heads ahead of the tokens
        # (ops/page_attention.py ``head_major``: ten is no multiple of the
        # sublane tile, and a token-major pool would be copied around every read)
        "pool": {
            "k": jnp.zeros((pool_pages, kvh, page_size, pd), dtype),
            "v": jnp.zeros((pool_pages, kvh, page_size, pd), dtype),
        },
        "win": [{"k": jnp.zeros(ring, dtype), "v": jnp.zeros(ring, dtype)}
                for _ in cfg.layers_of("window")],
        "ssm": [jnp.zeros((num_slots, cfg.d_state, di), jnp.float32) for _ in cfg.layers_of("mamba")],
        "conv": [jnp.zeros((num_slots, cfg.d_conv - 1, di), dtype) for _ in cfg.layers_of("mamba")],
    }


def kv_bytes_per_token(cfg: Phi4FlashConfig, kv_bytes: float = 2) -> int:
    """Paged bytes one cached token costs: K and V of ONE layer."""
    return int(2 * cfg.kv_dim * kv_bytes)


def fixed_state_bytes_per_slot(cfg: Phi4FlashConfig, kv_bytes: float = 2) -> int:
    """Bytes a slot holds whatever its sequence length: the window
    rings, the float32 SSM states and the convolution tails."""
    ring = len(cfg.layers_of("window")) * cfg.sliding_window * 2 * cfg.kv_dim * kv_bytes
    mamba = len(cfg.layers_of("mamba")) * (cfg.d_state * cfg.d_inner * 4 + (cfg.d_conv - 1) * cfg.d_inner * kv_bytes)
    return int(ring + mamba)


def serving_memory_bytes(cfg: Phi4FlashConfig, batch: int, max_seq_len: int,
                         weight_bytes: int = 2, kv_bytes: float = 2) -> Dict[str, int]:
    weights = count_logical_params(cfg) * weight_bytes
    paged = batch * max_seq_len * kv_bytes_per_token(cfg, kv_bytes)
    fixed = batch * fixed_state_bytes_per_slot(cfg, kv_bytes)
    return {"weights": weights, "kv_cache": paged + fixed, "fixed_state": fixed,
            "total": weights + paged + fixed}


# --------------------------------------------------------------------- //
# Layer mathematics


def layer_norm(x, w, b, eps: float):
    """LayerNorm in float32 on the float32 residual stream; the result
    takes the weights' dtype (what the matrices multiply)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps) * w.astype(jnp.float32) + b.astype(jnp.float32)
    return y.astype(w.dtype)


def _mm(x, w):
    """A matrix product whose result stays float32. The matrices and
    what multiplies them are the weights' dtype (bfloat16 when served);
    everything BETWEEN two products — biases, activations, gates, the
    scan, the residual stream (as Mamba's own ``residual_in_fp32``) — is
    float32 and is rounded once, where it enters the next product."""
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=jnp.float32)



def _embed(params: Params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def _silu(x):
    return jax.nn.silu(x.astype(jnp.float32))


def _mlp(h, lp, cfg: Phi4FlashConfig):
    x = layer_norm(h, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps)
    gate, up = jnp.split(_mm(x, lp["w_gate_up"]), 2, axis=-1)
    return h + _mm(_silu(gate) * up, lp["w_down"])


def _pair_queries(q, cfg: Phi4FlashConfig, dtype):
    """[..., Hq * Dh] -> [..., Hq, 2 * Dh]: head 2i as [q1|0], head
    2i+1 as [0|q2], times sqrt(2) (see the module docstring)."""
    lead, Dh = q.shape[:-1], cfg.head_dim
    q = q.reshape(lead + (cfg.num_heads // 2, 2, Dh)) * math.sqrt(2.0)
    z = jnp.zeros_like(q[..., 0, :])
    first = jnp.concatenate([q[..., 0, :], z], axis=-1)
    second = jnp.concatenate([z, q[..., 1, :]], axis=-1)
    return jnp.stack([first, second], axis=-2).reshape(lead + (cfg.num_heads, 2 * Dh)).astype(dtype)


def _pair_kv(x, cfg: Phi4FlashConfig, dtype):
    """[..., Hkv * Dh] -> [..., Hkv/2, 2 * Dh]: a free reshape."""
    return x.reshape(x.shape[:-1] + (cfg.pair_kv_heads, cfg.pair_dim)).astype(dtype)


def _heads_first(x):
    """[N, T, Hk, Dp] (as projected) -> [N, Hk, T, Dp] (as cached and attended)."""
    return jnp.swapaxes(x, 1, 2)


def _pair_attention(qp, k, v, mask):
    """GQA attention in the pair layout. qp [N, T, Hq, Dp] (already
    scaled for 1/sqrt(Dp)), k/v [N, Hk, S, Dp] (heads first, as the
    caches hold them), mask [N, T, S] bool. The scores of every head
    exist at once ([N, Hq, T, S] float32: 0.38 GB for one row of 512
    queries over a 4096-token window); the engine sends a fixed-state
    family one row a wave (``ShapePlan.max_wave_rows``)."""
    N, T, Hq, Dp = qp.shape
    Hk = k.shape[1]
    q5 = qp.reshape(N, T, Hk, Hq // Hk, Dp)
    sc = jnp.einsum("ntkgd,nksd->nkgts", q5, k, preferred_element_type=jnp.float32) * (1.0 / math.sqrt(Dp))
    sc = jnp.where(mask[:, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("nkgts,nksd->ntkgd", p.astype(v.dtype), v).reshape(N, T, Hq, Dp)


def _diff_output(a, lp, cfg: Phi4FlashConfig, l: int):
    """a [..., Hq, Dp] (a1, a2 interleaved) -> the mixer's output:
    (1 - lam0) * RMSNorm(a1 - lam * a2) per diff-head, then W_o."""
    lam0 = cfg.lambda_init(l)
    lam = jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"])) - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])) + lam0
    a = a.astype(jnp.float32).reshape(a.shape[:-2] + (cfg.num_heads // 2, 2, cfg.pair_dim))
    d = a[..., 0, :] - lam * a[..., 1, :]
    d = d * lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + cfg.norm_eps)
    d = d * lp["subln"].astype(jnp.float32) * (1.0 - lam0)
    return _mm(d.reshape(d.shape[:-2] + (cfg.q_dim,)), lp["wo"]) + lp["bo"].astype(jnp.float32)


def _qkv(x, lp, cfg: Phi4FlashConfig):
    """The fused projection and its bias, then the pair layout: queries
    [..., Hq, 2*Dh], keys and values [..., Hkv/2, 2*Dh], in x's dtype."""
    qkv = _mm(x, lp["wqkv"]) + lp["bqkv"].astype(jnp.float32)
    q, k, v = jnp.split(qkv, [cfg.q_dim, cfg.q_dim + cfg.kv_dim], axis=-1)
    return _pair_queries(q, cfg, x.dtype), _pair_kv(k, cfg, x.dtype), _pair_kv(v, cfg, x.dtype)


def _conv(cat, lp, T: int):
    """Causal depthwise convolution: cat [N, T + d_conv - 1, di] float32
    (the tail, then the inputs) -> [N, T, di]."""
    w = lp["conv_w"].astype(jnp.float32)
    return sum(cat[:, k:k + T] * w[k] for k in range(w.shape[0])) + lp["conv_b"].astype(jnp.float32)


def _mamba_inputs(x, lp, cfg: Phi4FlashConfig):
    """The scan's operands from the convolved, activated input x
    [..., d_inner]: (dt, B, C) in float32."""
    d, Bm, Cm = jnp.split(_mm(x, lp["x_proj"]), [cfg.dt_rank, cfg.dt_rank + cfg.d_state], axis=-1)
    # (the step size enters an exponential: its small projection stays float32)
    dt = jax.nn.softplus(d @ lp["dt_proj"].astype(jnp.float32) + lp["dt_bias"])
    return dt, Bm, Cm


def selective_scan(x, dt, A, Bm, Cm, D, s0, unroll: int = 8):
    """s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t^T; y_t = s_t C_t + D x_t.
    x, dt [N, T, di]; Bm, Cm [N, T, ds]; A [ds, di]; s0 [N, ds, di];
    all float32. A token with dt = 0 leaves the state as it is."""
    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[:, None, :] * A) * s + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    xs = tuple(jnp.swapaxes(a, 0, 1) for a in (x, dt, Bm, Cm))
    s, ys = lax.scan(step, s0, xs, unroll=min(unroll, x.shape[1]))
    return jnp.swapaxes(ys, 0, 1) + D * x, s


def _write_rows(buf, lead, row, values):
    """Write token rows ``values`` [..., Hk, Dp] into a heads-first
    buffer [L, Hk, R, Dp] (the pool: L pages of R tokens; a ring: L
    slots of R window rows) at ``buf[lead, :, row]`` (``lead``, ``row``
    [...]); a ``row`` of R or more is dropped. The buffer is seen as
    [L * Hk, R, Dp] (a free reshape), so that the scatter's window is
    the minor dim alone: indexed as ``[lead, :, row]`` XLA gives the
    buffer a layout with the heads next to the lanes and copies it into
    the readers' layout around every read of every decode step (a
    quarter of the step, measured on the chip)."""
    L, Hk, R, Dp = buf.shape
    flat = buf.reshape(L * Hk, R, Dp)
    at = lead[..., None] * Hk + jnp.arange(Hk, dtype=lead.dtype)
    return flat.at[at, row[..., None]].set(values, mode="drop").reshape(buf.shape)


def _gather_window(pool, pages):
    """pool [P, Hk, page, Dp] x pages [N, Pw] -> [N, Hk, Pw * page, Dp]."""
    g = jnp.moveaxis(pool[pages], 1, 2)  # [N, Hk, Pw, page, Dp]
    return g.reshape(g.shape[:2] + (g.shape[2] * g.shape[3], g.shape[4]))


def head(params: Params, cfg: Phi4FlashConfig, hidden):
    """Final LayerNorm and the tied output head; float32 logits."""
    h = layer_norm(hidden, params["final_norm_w"], params["final_norm_b"], cfg.norm_eps)
    return jnp.einsum("...d,vd->...v", h, params["embed"], preferred_element_type=jnp.float32)


def _upper_layers(params, cfg: Phi4FlashConfig, h, memory, shared_read):
    """Layers past the full one on h [N, T, D] with the memory
    [N, T, d_inner] of the same tokens; ``shared_read(qp)`` attends the
    full layer's K/V."""
    for l in range(cfg.full_layer + 1, cfg.num_layers):
        lp = params["layers"][l]
        x = layer_norm(h, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        if cfg.layer_kind(l) == "gmu":
            with jax.named_scope("gmu"):
                h = h + _mm(memory * _silu(_mm(x, lp["w1"])), lp["w2"])
        else:
            with jax.named_scope("shared_kv_attn"):
                a = shared_read(_pair_queries(_mm(x, lp["wq"]) + lp["bq"].astype(jnp.float32), cfg, x.dtype))
                h = h + _diff_output(a, lp, cfg, l)
        h = _mlp(h, lp, cfg)
    return h


# --------------------------------------------------------------------- //
# The chunk walk: prefill (fresh) and chunked extend


def _chunk_walk(params: Params, cfg: Phi4FlashConfig, caches: Caches, tokens, offsets, valid, slots,
                tables, window: int, page_size: int, fresh: bool):
    """Layers 0..n/2+1 over a chunk [N, C] per row, the upper layers on
    each row's last valid position only. Returns (hidden [N, D] of that
    position, caches).

    ``fresh`` (the prefill program): every row starts at position 0, so
    nothing is read from the caches — a slot's fixed state is replaced
    whole, which is what resets it at admission. Otherwise (extend) a
    row at ``offsets == 0`` starts from a zero state the same way, and a
    row at ``offsets > 0`` carries its slot's state on. A row with
    ``valid == 0`` (finished, padding, warm-up) changes nothing: its pool
    writes go to the scratch page, its ring writes are dropped and its
    slot's state is written back as it was.
    """
    N, C = tokens.shape
    Wn, di = cfg.sliding_window, cfg.d_inner
    S = tables.shape[1] * page_size
    idx = jnp.arange(C, dtype=jnp.int32)
    positions = jnp.minimum(offsets[:, None] + idx[None, :], S - 1)  # [N, C]
    tok_valid = idx[None, :] < valid[:, None]
    row_live = valid > 0
    started = row_live & (offsets > 0) if not fresh else jnp.zeros_like(row_live)
    causal = positions[:, :, None] >= positions[:, None, :]  # [N, C, C] chunk keys
    in_window = causal & (positions[:, None, :] > positions[:, :, None] - Wn)
    last = jnp.clip(valid, 1, C) - 1

    h = _embed(params, tokens)
    new = {"pool": caches["pool"], "win": [], "ssm": [], "conv": []}
    memory = gathered = None
    for l in range(cfg.full_layer + 1):
        lp = params["layers"][l]
        kind = cfg.layer_kind(l)
        x = layer_norm(h, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        if kind == "mamba":
            with jax.named_scope("ssm_scan"):
                i = len(new["ssm"])
                old_s, old_tail = caches["ssm"][i][slots], caches["conv"][i][slots]
                xin, z = jnp.split(_mm(x, lp["in_proj"]), 2, axis=-1)
                tail = jnp.where(started[:, None, None], old_tail, jnp.zeros_like(old_tail))
                cat = jnp.concatenate([tail.astype(jnp.float32), xin], axis=1)  # [N, C + d_conv - 1, di]
                xc = _silu(_conv(cat, lp, C))
                dt, Bm, Cm = _mamba_inputs(xc, lp, cfg)
                dt = jnp.where(tok_valid[..., None], dt, 0.0)
                s0 = jnp.where(started[:, None, None], old_s, jnp.zeros_like(old_s))
                y, s = selective_scan(xc, dt, -jnp.exp(lp["A_log"]), Bm, Cm, lp["D"], s0)
                if l == cfg.memory_layer:
                    memory = y
                h = h + _mm(y * _silu(z), lp["out_proj"])
                # the last d_conv - 1 inputs up to the row's last valid token
                taps = valid[:, None] + jnp.arange(cfg.d_conv - 1, dtype=jnp.int32)[None, :]
                new_tail = jnp.take_along_axis(cat, taps[:, :, None], axis=1).astype(old_tail.dtype)
                keep = row_live[:, None, None]
                new["ssm"].append(caches["ssm"][i].at[slots].set(jnp.where(keep, s, old_s)))
                new["conv"].append(caches["conv"][i].at[slots].set(jnp.where(keep, new_tail, old_tail)))
        else:
            qp, kp, vp = _qkv(x, lp, cfg)
            if kind == "window":
                with jax.named_scope("window_attn"):
                    i = len(new["win"])
                    ring = caches["win"][i]
                    if fresh:
                        a = _pair_attention(qp, _heads_first(kp), _heads_first(vp), in_window)
                    else:
                        # ring index r holds the newest position below the
                        # chunk that is congruent to r (none: masked)
                        r = jnp.arange(Wn, dtype=jnp.int32)[None, :]
                        ring_pos = offsets[:, None] - 1 - jnp.mod(offsets[:, None] - 1 - r, Wn)  # [N, Wn]
                        ring_ok = (ring_pos >= 0)[:, None, :] & (ring_pos[:, None, :] > positions[:, :, None] - Wn)
                        a = _pair_attention(
                            qp,
                            jnp.concatenate([ring["k"][slots], _heads_first(kp)], axis=2),
                            jnp.concatenate([ring["v"][slots], _heads_first(vp)], axis=2),
                            jnp.concatenate([ring_ok, in_window], axis=2),
                        )
                    # keep the chunk's last `window` valid tokens; the rest is dropped
                    keep = tok_valid & (idx[None, :] >= valid[:, None] - Wn)
                    at = jnp.where(keep, positions % Wn, Wn)
                    lead = jnp.broadcast_to(slots[:, None], at.shape)
                    new["win"].append({"k": _write_rows(ring["k"], lead, at, kp),
                                       "v": _write_rows(ring["v"], lead, at, vp)})
            else:  # the full layer: the only paged K/V
                with jax.named_scope("shared_kv_attn"):
                    row_tables = tables[slots]
                    phys = jnp.take_along_axis(row_tables, positions // page_size, axis=1)
                    phys = jnp.where(tok_valid, phys, 0)  # padding -> scratch page
                    sip = positions % page_size
                    pool = {"k": _write_rows(caches["pool"]["k"], phys, sip, kp),
                            "v": _write_rows(caches["pool"]["v"], phys, sip, vp)}
                    new["pool"] = pool
                    if fresh:
                        gathered = (_heads_first(kp), _heads_first(vp), positions)
                    else:
                        W = min(window, S)
                        pages = row_tables[:, : W // page_size]
                        gk, gv = _gather_window(pool["k"], pages), _gather_window(pool["v"], pages)
                        gathered = (gk, gv, jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (N, W)))
                    mask = gathered[2][:, None, :] <= positions[:, :, None]
                    a = _pair_attention(qp, gathered[0], gathered[1], mask)
            h = h + _diff_output(a, lp, cfg, l)
        h = _mlp(h, lp, cfg)

    # the prefill shortcut: the upper half sees one position a row
    pick = last[:, None, None]
    h_last = jnp.take_along_axis(h, pick, axis=1)  # [N, 1, D]
    m_last = jnp.take_along_axis(memory, pick, axis=1)
    pos_last = jnp.take_along_axis(positions, last[:, None], axis=1)  # [N, 1]
    gk, gv, gpos = gathered
    last_mask = gpos[:, None, :] <= pos_last[:, :, None]
    h_last = _upper_layers(params, cfg, h_last, m_last,
                           lambda qp: _pair_attention(qp, gk, gv, last_mask))
    return h_last[:, 0], new


def prefill_paged(params: Params, cfg: Phi4FlashConfig, caches: Caches, tokens, lengths, slots,
                  tables, page_size: int, **_paths):
    """A monolithic admission wave: (last-position logits [N, V], caches)."""
    hidden, caches = _chunk_walk(
        params, cfg, caches, tokens, jnp.zeros_like(lengths), lengths, slots, tables,
        window=tokens.shape[1], page_size=page_size, fresh=True,
    )
    return head(params, cfg, hidden), caches


def extend_paged(params: Params, cfg: Phi4FlashConfig, caches: Caches, tokens, offsets, valid, slots,
                 tables, window: int, page_size: int, **_paths):
    """One chunk of a chunked prefill: (hidden [N, D] of each row's last
    valid position in the chunk, caches). The state goes from chunk to
    chunk in the slot's fixed state and the pool."""
    return _chunk_walk(params, cfg, caches, tokens, offsets, valid, slots, tables,
                       window=window, page_size=page_size, fresh=False)


# --------------------------------------------------------------------- //
# One decode step


def decode_paged(params: Params, cfg: Phi4FlashConfig, caches: Caches, tokens, positions, live, tables,
                 window: Optional[int], page_size: int, page_kernel: Optional[str] = None, **_paths):
    """One token per slot: (logits [B, V], caches). Dead rows (``live``
    False; the engine has zeroed their positions) leave every fixed
    state as it is — a slot may be between two chunks of its prefill —
    and write the pool's scratch page."""
    B = tokens.shape[0]
    Wn = cfg.sliding_window
    S = tables.shape[1] * page_size
    W = min(window or S, S)
    pos2 = positions[:, None]
    phys = jnp.where(live[:, None], jnp.take_along_axis(tables, pos2 // page_size, axis=1), 0)
    sip = pos2 % page_size
    ring_at = jnp.where(live, positions % Wn, Wn)[:, None]  # dead rows: dropped
    ring_mask = ((jnp.arange(Wn, dtype=jnp.int32)[None, :] <= pos2) | (pos2 >= Wn))[:, None, :]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    keep = live[:, None, None]
    # one ragged work list a step, shared by the full layer and every cross layer
    work = page_attention.page_work_list(
        tables, positions, 1, page_size, page_attention.pages_per_step(caches["pool"]["k"])
    ) if page_kernel else None

    h = _embed(params, tokens[:, None])  # [B, 1, D]
    new = {"pool": caches["pool"], "win": [], "ssm": [], "conv": []}
    memory = shared_read = None
    for l in range(cfg.full_layer + 1):
        lp = params["layers"][l]
        kind = cfg.layer_kind(l)
        x = layer_norm(h, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        if kind == "mamba":
            with jax.named_scope("ssm_step"):
                i = len(new["ssm"])
                old_s, old_tail = caches["ssm"][i], caches["conv"][i]
                xin, z = jnp.split(_mm(x, lp["in_proj"]), 2, axis=-1)  # [B, 1, di]
                cat = jnp.concatenate([old_tail.astype(jnp.float32), xin], axis=1)  # [B, d_conv, di]
                xc = _silu(_conv(cat, lp, 1))
                dt, Bm, Cm = _mamba_inputs(xc, lp, cfg)
                x32, dt, Bm, Cm = xc[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0]
                s = jnp.exp(dt[:, None, :] * -jnp.exp(lp["A_log"])) * old_s + (dt * x32)[:, None, :] * Bm[:, :, None]
                y = (jnp.sum(s * Cm[:, :, None], axis=1) + lp["D"] * x32)[:, None]
                if l == cfg.memory_layer:
                    memory = y
                h = h + _mm(y * _silu(z), lp["out_proj"])
                new["ssm"].append(jnp.where(keep, s, old_s))
                new["conv"].append(jnp.where(keep, cat[:, 1:].astype(old_tail.dtype), old_tail))
        else:
            qp, kp, vp = _qkv(x, lp, cfg)
            if kind == "window":
                with jax.named_scope("window_attn"):
                    ring = caches["win"][len(new["win"])]
                    rk = _write_rows(ring["k"], rows, ring_at, kp)
                    rv = _write_rows(ring["v"], rows, ring_at, vp)
                    new["win"].append({"k": rk, "v": rv})
                    a = _pair_attention(qp, rk, rv, ring_mask)
            else:
                pool = {"k": _write_rows(caches["pool"]["k"], phys, sip, kp),
                        "v": _write_rows(caches["pool"]["v"], phys, sip, vp)}
                new["pool"] = pool
                if page_kernel:
                    def shared_read(qp, pool=pool):
                        return page_attention.paged_attention(
                            qp, pool["k"], pool["v"], tables, positions,
                            interpret=(page_kernel == "interpret"), work=work,
                            head_major=True,
                        ).astype(qp.dtype)
                else:
                    pages = tables[:, : W // page_size]
                    gk, gv = _gather_window(pool["k"], pages), _gather_window(pool["v"], pages)
                    gmask = jnp.arange(W, dtype=jnp.int32)[None, None, :] <= pos2[:, :, None]

                    def shared_read(qp, gk=gk, gv=gv, gmask=gmask):
                        return _pair_attention(qp, gk, gv, gmask)
                with jax.named_scope("shared_kv_attn"):
                    a = shared_read(qp)
            h = h + _diff_output(a, lp, cfg, l)
        h = _mlp(h, lp, cfg)
    h = _upper_layers(params, cfg, h, memory, shared_read)
    return head(params, cfg, h[:, 0]), new


# --------------------------------------------------------------------- //
# The whole sequence at once, no cache: what the tests hold the paged walks against


def forward_full(params: Params, cfg: Phi4FlashConfig, tokens, lengths=None):
    """Logits of tokens [N, T] with no cache. Without ``lengths`` every
    layer runs at every position ([N, T, V]); with ``lengths`` [N] the
    upper half runs at each row's last position only ([N, V], the
    prefill shortcut) — the two agree there, which is the design's point
    and a test's subject."""
    N, T = tokens.shape
    Wn = cfg.sliding_window
    idx = jnp.arange(T, dtype=jnp.int32)
    causal = jnp.broadcast_to((idx[:, None] >= idx[None, :])[None], (N, T, T))
    in_window = causal & (idx[None, :] > idx[:, None] - Wn)[None]
    h = _embed(params, tokens)
    memory = shared = None
    for l in range(cfg.full_layer + 1):
        lp = params["layers"][l]
        x = layer_norm(h, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        if cfg.layer_kind(l) == "mamba":
            xin, z = jnp.split(_mm(x, lp["in_proj"]), 2, axis=-1)
            xc = _silu(_conv(jnp.pad(xin, ((0, 0), (cfg.d_conv - 1, 0), (0, 0))), lp, T))
            dt, Bm, Cm = _mamba_inputs(xc, lp, cfg)
            s0 = jnp.zeros((N, cfg.d_state, cfg.d_inner), jnp.float32)
            y, _ = selective_scan(xc, dt, -jnp.exp(lp["A_log"]), Bm, Cm, lp["D"], s0)
            if l == cfg.memory_layer:
                memory = y
            h = h + _mm(y * _silu(z), lp["out_proj"])
        else:
            qp, kp, vp = _qkv(x, lp, cfg)
            window = cfg.layer_kind(l) == "window"
            kp, vp = _heads_first(kp), _heads_first(vp)
            a = _pair_attention(qp, kp, vp, in_window if window else causal)
            if not window:
                shared = (kp, vp)
            h = h + _diff_output(a, lp, cfg, l)
        h = _mlp(h, lp, cfg)
    mask = causal
    if lengths is not None:
        pick = (lengths - 1)[:, None, None]
        h = jnp.take_along_axis(h, pick, axis=1)
        memory = jnp.take_along_axis(memory, pick, axis=1)
        mask = jnp.take_along_axis(causal, pick, axis=1)
    h = _upper_layers(params, cfg, h, memory, lambda qp: _pair_attention(qp, shared[0], shared[1], mask))
    return head(params, cfg, h if lengths is None else h[:, 0])
